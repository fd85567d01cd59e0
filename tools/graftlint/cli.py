"""graftlint CLI: the PR gate.

    python -m tools.graftlint                # scan the hot-path surface
    python -m tools.graftlint --self-check   # detectors vs seeded fixtures
    python -m tools.graftlint path/to.py     # scoped scan
    python -m tools.graftlint --write-baseline   # acknowledge current debt

Exit codes: 0 = clean, 1 = unsuppressed findings (or a failed self-check),
2 = usage/internal error. tools/lint.sh
runs ``--self-check`` then the full scan between the prometheus conformance
check and ruff, so a broken detector fails the gate as loudly as a broken
hot path.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from tools.graftlint.core import (
    Finding,
    ScanContext,
    SourceFile,
    apply_baseline,
    iter_python_files,
    load_baseline,
    write_baseline,
)
from tools.graftlint.detectors import ALL_DETECTORS

#: what the repo gate scans: the package plus the tooling the tier-1 suite
#: shells out to. Tests are deliberately out of scope — they block, sync and
#: fake metrics on purpose.
DEFAULT_SCAN_ROOTS = ("dynamo_tpu", "tools")

DEFAULT_BASELINE = "tools/graftlint/baseline.json"


def run_scan(
    paths: list[Path], root: Path, force_hot: bool = False
) -> tuple[list[Finding], list[str]]:
    """(findings, parse errors). Findings include suppressed/baselined ones;
    callers partition by status."""
    ctx = ScanContext(root=root, force_hot=force_hot)
    files: list[SourceFile] = []
    errors: list[str] = []
    for f in iter_python_files(paths, root):
        try:
            files.append(SourceFile.load(f, root))
        except (SyntaxError, UnicodeDecodeError) as e:
            errors.append(f"{f}: {e}")
    findings: list[Finding] = []
    detectors = [cls() for cls in ALL_DETECTORS]
    for sf in files:
        for det in detectors:
            findings.extend(det.scan(sf, ctx))
    for det in detectors:
        findings.extend(det.finalize(files, ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="graftlint",
        description="JAX/asyncio-aware static analysis gating the hot path; "
        "see ARCHITECTURE.md 'The lint gate' for the detector catalogue.",
    )
    p.add_argument(
        "paths",
        nargs="*",
        help=f"files/dirs to scan (default: {' '.join(DEFAULT_SCAN_ROOTS)})",
    )
    p.add_argument(
        "--root",
        default=".",
        help="repo root for relative paths and the metric declaration module",
    )
    p.add_argument(
        "--baseline",
        default=DEFAULT_BASELINE,
        help="acknowledged-debt baseline file (relative to --root)",
    )
    p.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore the baseline (report acknowledged debt as live findings)",
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current unsuppressed findings to the baseline and exit 0",
    )
    p.add_argument(
        "--force-hot",
        action="store_true",
        help="treat every scanned file as hot-path (fixture/debug use)",
    )
    p.add_argument(
        "--show-suppressed",
        action="store_true",
        help="also list suppressed/baselined findings",
    )
    p.add_argument("--quiet", action="store_true", help="summary line only")
    p.add_argument(
        "--self-check",
        action="store_true",
        help="verify every detector against its seeded positive/negative "
        "fixtures (the lint-gate wiring)",
    )
    args = p.parse_args(argv)

    if args.self_check:
        from tools.graftlint.selfcheck import self_check

        problems = self_check()
        for prob in problems:
            print(f"FAIL graftlint self-check: {prob}")
        if not problems:
            print("ok: graftlint self-check passed (6 detectors)")
        return 1 if problems else 0

    root = Path(args.root).resolve()
    if args.paths:
        paths = [Path(p) if Path(p).is_absolute() else root / p for p in args.paths]
    else:
        paths = [root / p for p in DEFAULT_SCAN_ROOTS]
    paths = [p for p in paths if p.exists()]
    if not paths:
        print("graftlint: nothing to scan", file=sys.stderr)
        return 2

    try:
        findings, errors = run_scan(paths, root, force_hot=args.force_hot)
    except Exception as e:  # a crashed detector must fail the gate loudly
        print(f"graftlint: internal error: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for err in errors:
        print(f"graftlint: parse error: {err}", file=sys.stderr)

    baseline_path = root / args.baseline
    if not args.no_baseline:
        apply_baseline(findings, load_baseline(baseline_path))

    active = [f for f in findings if not f.suppressed and not f.baselined]
    suppressed = [f for f in findings if f.suppressed]
    baselined = [f for f in findings if f.baselined]

    if args.write_baseline:
        write_baseline(baseline_path, active)
        print(f"graftlint: wrote {len(active)} finding(s) to {baseline_path}")
        return 0

    if not args.quiet:
        for f in active:
            print(f.render())
        if args.show_suppressed:
            for f in suppressed:
                print(f"{f.render()}  [suppressed: {f.suppress_reason}]")
            for f in baselined:
                print(f"{f.render()}  [baselined]")
    print(
        f"graftlint: {len(active)} finding(s), {len(suppressed)} suppressed, "
        f"{len(baselined)} baselined"
        + (f", {len(errors)} parse error(s)" if errors else "")
    )
    if errors:
        return 2
    return 1 if active else 0


if __name__ == "__main__":
    raise SystemExit(main())
