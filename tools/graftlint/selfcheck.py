"""graftlint --self-check: detectors vs their seeded fixtures.

A gate that checks itself first: before the repo scan runs, every detector
must (a) catch exactly the seeded violations in its POSITIVE
fixture, (b) stay silent on its NEGATIVE fixture — which includes annotated
violations, so the suppression machinery is exercised too — and (c) never
bleed findings into another detector's fixture. A detector that rots fails
the lint gate itself, not silently stops finding bugs.

Each fixture's first line declares its contract:

    # graftlint-fixture: <rule> expect=<N>

Fixtures are scanned standalone with ``force_hot`` (hot-path scoping is the
repo scan's business) and without the baseline.
"""

from __future__ import annotations

import re
from pathlib import Path

from tools.graftlint.cli import run_scan

FIXTURES_DIR = Path(__file__).parent / "fixtures"

_HEADER_RE = re.compile(r"#\s*graftlint-fixture:\s*(\S+)\s+expect=(\d+)")


def self_check() -> list[str]:
    """Problem list (empty = every detector healthy)."""
    problems: list[str] = []
    fixtures = sorted(FIXTURES_DIR.glob("*.py"))
    if len(fixtures) < 10:
        problems.append(
            f"expected >=10 fixtures (pos+neg per detector), found {len(fixtures)}"
        )
    seen_rules: set[str] = set()
    for fixture in fixtures:
        header = fixture.read_text().splitlines()[0]
        m = _HEADER_RE.search(header)
        if not m:
            problems.append(f"{fixture.name}: missing graftlint-fixture header")
            continue
        rule, expect = m.group(1), int(m.group(2))
        seen_rules.add(rule)
        findings, errors = run_scan([fixture], root=FIXTURES_DIR, force_hot=True)
        for err in errors:
            problems.append(f"{fixture.name}: parse error: {err}")
        active = [f for f in findings if not f.suppressed]
        mine = [f for f in active if f.rule == rule]
        others = [f for f in active if f.rule != rule]
        if len(mine) != expect:
            lines = ", ".join(str(f.line) for f in mine) or "none"
            problems.append(
                f"{fixture.name}: expected {expect} {rule} finding(s), got "
                f"{len(mine)} (lines: {lines})"
            )
        if others:
            problems.append(
                f"{fixture.name}: {len(others)} finding(s) bled in from other "
                f"detectors: {[f.rule for f in others]}"
            )
    missing = {
        "host-sync",
        "use-after-donation",
        "recompile-hazard",
        "async-blocking",
        "metric-conformance",
        "event-conformance",
    } - seen_rules
    if missing:
        problems.append(f"no fixtures cover rule(s): {sorted(missing)}")
    return problems
