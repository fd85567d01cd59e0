"""tools/graftlint: the static-analysis half of the lint gate. Acceptance:
each of the six detectors catches its seeded positive fixture and stays
silent on its negative fixture (which includes reasoned suppressions, so the
allowlist machinery is exercised), the whole-repo scan comes back with zero
unsuppressed findings, the suppression/baseline plumbing behaves, exit codes
are 0 clean / 1 findings / 2 usage error, and the metric-conformance detector's
static view of DECLARED_METRIC_FAMILIES matches the runtime declaration the
prometheus --check gate validates against the rendered surfaces.

Tier-1, CPU, fast: everything here is stdlib AST work except the one
exposition cross-validation test that renders the sample surfaces.
"""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.graftlint.cli import DEFAULT_SCAN_ROOTS, main, run_scan  # noqa: E402
from tools.graftlint.core import load_baseline, write_baseline  # noqa: E402
from tools.graftlint.selfcheck import _HEADER_RE, FIXTURES_DIR, self_check  # noqa: E402

# ---------------- fixtures: one positive and one negative per detector ----


def _fixture_cases():
    cases = []
    for f in sorted(FIXTURES_DIR.glob("*.py")):
        m = _HEADER_RE.search(f.read_text().splitlines()[0])
        assert m, f"{f.name} missing its graftlint-fixture header"
        cases.append(pytest.param(f, m.group(1), int(m.group(2)), id=f.name))
    return cases


def test_fixture_inventory_covers_all_detectors():
    cases = [c.values for c in _fixture_cases()]
    rules = {rule for (_fixture, rule, _expect) in cases}
    assert rules == {
        "host-sync",
        "use-after-donation",
        "recompile-hazard",
        "async-blocking",
        "metric-conformance",
        "event-conformance",
    }
    # a positive AND a negative per rule
    by_rule = {}
    for _fixture, rule, expect in cases:
        by_rule.setdefault(rule, set()).add(expect > 0)
    assert all(v == {True, False} for v in by_rule.values()), by_rule


@pytest.mark.parametrize("fixture,rule,expect", _fixture_cases())
def test_detector_fixture(fixture, rule, expect):
    findings, errors = run_scan([fixture], root=FIXTURES_DIR, force_hot=True)
    assert not errors
    active = [f for f in findings if not f.suppressed]
    mine = [f for f in active if f.rule == rule]
    assert len(mine) == expect, [f.render() for f in active]
    # no detector bleeds findings into another detector's fixture
    assert [f for f in active if f.rule != rule] == []


def test_self_check_green():
    assert self_check() == []


# ---------------- whole-repo gate ----------------


def test_repo_scan_zero_unsuppressed_findings():
    """The acceptance criterion: the shipped tree is clean under all six
    detectors (modulo reasoned suppressions and the checked-in baseline)."""
    findings, errors = run_scan([ROOT / p for p in DEFAULT_SCAN_ROOTS], root=ROOT)
    assert not errors
    baseline = load_baseline(ROOT / "tools/graftlint/baseline.json")
    active = [
        f
        for f in findings
        if not f.suppressed and f.fingerprint not in baseline
    ]
    assert active == [], "\n" + "\n".join(f.render() for f in active)
    # every suppression in the tree carries a reason (reasonless ones are
    # converted into findings by make_finding, so active==[] implies this;
    # assert the stronger property directly for a readable failure)
    for f in findings:
        if f.suppressed:
            assert f.suppress_reason, f.render()


# ---------------- suppression + baseline machinery ----------------


def test_suppression_without_reason_is_a_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n\ndef f(x):\n"
        "    jax.block_until_ready(x)  # graftlint: sync-ok\n"
    )
    findings, _ = run_scan([bad], root=tmp_path, force_hot=True)
    active = [f for f in findings if not f.suppressed]
    assert len(active) == 1
    assert "suppression without a reason" in active[0].message


def test_reasoned_suppression_suppresses(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(
        "import jax\n\n\ndef f(x):\n"
        "    jax.block_until_ready(x)  # graftlint: sync-ok warmup only\n"
    )
    findings, _ = run_scan([ok], root=tmp_path, force_hot=True)
    assert [f for f in findings if not f.suppressed] == []
    assert [f.suppress_reason for f in findings if f.suppressed] == ["warmup only"]


def test_baseline_acknowledges_debt(tmp_path):
    src = tmp_path / "debt.py"
    src.write_text("import jax\n\n\ndef f(x):\n    jax.block_until_ready(x)\n")
    findings, _ = run_scan([src], root=tmp_path, force_hot=True)
    active = [f for f in findings if not f.suppressed]
    assert len(active) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(bl, active)
    assert load_baseline(bl) == {active[0].fingerprint}
    # fingerprints survive line drift: prepend a comment line and re-scan
    src.write_text("# a new comment\n" + src.read_text())
    findings2, _ = run_scan([src], root=tmp_path, force_hot=True)
    fps = load_baseline(bl)
    assert [f for f in findings2 if not f.suppressed and f.fingerprint not in fps] == []


# ---------------- CLI exit codes: 0 clean, 1 findings, 2 usage -----------


def test_cli_exit_codes(tmp_path, capsys):
    dirty = tmp_path / "dirty"
    dirty.mkdir()
    (dirty / "x.py").write_text(
        "import time\n\n\nasync def tick():\n    time.sleep(1)\n"
    )
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "y.py").write_text("import asyncio\n\n\nasync def tick():\n    await asyncio.sleep(1)\n")
    assert main([str(dirty), "--root", str(tmp_path), "--no-baseline"]) == 1
    assert main([str(clean), "--root", str(tmp_path), "--no-baseline"]) == 0
    capsys.readouterr()


def test_module_entrypoint_self_check():
    """lint.sh invokes `python -m tools.graftlint --self-check`; pin the -m
    wiring from a clean interpreter."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--self-check"],
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "self-check passed" in proc.stdout


# ---------------- metric-conformance cross-validation ----------------


def test_static_declaration_matches_runtime_tuple():
    """The detector's AST view of DECLARED_METRIC_FAMILIES must equal the
    tuple Python sees at import time (same file, two readers)."""
    import ast

    from dynamo_tpu.utils.prometheus import DECLARED_METRIC_FAMILIES
    from tools.graftlint.detectors.metrics_conformance import (
        DECLARING_MODULE,
        _find_declaration,
    )

    tree = ast.parse((ROOT / DECLARING_MODULE).read_text())
    declared, _ = _find_declaration(tree)
    assert {name for name, _ in declared} == set(DECLARED_METRIC_FAMILIES)
    assert len(DECLARED_METRIC_FAMILIES) == len(set(DECLARED_METRIC_FAMILIES))


def test_declared_families_match_rendered_surfaces():
    """The runtime half of the contract: every declared family is rendered
    by the cluster-free sample surfaces and vice versa (what
    `python -m dynamo_tpu.utils.prometheus --check` gates in lint.sh)."""
    from dynamo_tpu.utils.prometheus import _declaration_problems, _sample_surfaces

    assert _declaration_problems(_sample_surfaces()) == []


def test_metric_typo_is_caught(tmp_path):
    """End-to-end: a typo'd emitting literal fails the gate even though the
    declaration itself is well-formed."""
    mod = tmp_path / "emitter.py"
    mod.write_text(
        "DECLARED_METRIC_FAMILIES = (\n"
        '    "dynamo_demo_requests_total",\n'
        ")\n\n\n"
        "def render():\n"
        '    return "dynamo_demo_reqeusts_total"\n'  # transposed letters
    )
    findings, _ = run_scan([mod], root=tmp_path)
    msgs = [f.message for f in findings if not f.suppressed]
    assert any("dynamo_demo_reqeusts_total" in m for m in msgs), msgs
    assert any("never referenced" in m for m in msgs), msgs
