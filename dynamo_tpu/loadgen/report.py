"""replaytop-style text report over replay results.

Pure renderer (testable without an engine): one row per scenario with the
goodput verdict, latency percentiles against their budgets, throughput, and
the replay harness's own health (schedule lag, errors).
"""

from __future__ import annotations


def _ms(v) -> str:
    return f"{v:.1f}" if isinstance(v, (int, float)) else "-"


def _pct(v) -> str:
    return f"{100.0 * v:.1f}%" if isinstance(v, (int, float)) else "-"


def render_report(reports: list, title: str = "replay") -> str:
    """reports: list of replay report dicts (loadgen/replay.py _report)."""
    header = (
        f"{'SCENARIO':<24} {'REQS':>5} {'ERR':>4} {'GOODPUT':>8} "
        f"{'TTFT p50/p99':>14} {'ITL p50/p99':>13} {'TOK/S':>8} "
        f"{'LAG':>7}  BUDGET(ttft/itl ms)"
    )
    lines = [f"{title} — {len(reports)} scenario(s)", "", header, "-" * len(header)]
    for r in reports:
        budget = (
            f"{_ms(r.get('ttft_budget_ms'))}/{_ms(r.get('itl_budget_ms'))}"
        )
        lines.append(
            f"{r.get('scenario', '?'):<24} {r.get('requests', 0):>5} "
            f"{r.get('errors', 0):>4} {_pct(r.get('goodput')):>8} "
            f"{_ms(r.get('ttft_p50_ms')):>6}/{_ms(r.get('ttft_p99_ms')):<7} "
            f"{_ms(r.get('itl_p50_ms')):>5}/{_ms(r.get('itl_p99_ms')):<7} "
            f"{r.get('tok_s') if r.get('tok_s') is not None else '-':>8} "
            f"{_ms(1e3 * r.get('schedule_lag_max_s', 0.0)):>7}  {budget}"
        )
        # per-tenant cost rollup rows (loadgen/replay.py _tenant_rollup):
        # shown when the run was multi-tenant or an engine meter priced it
        tenants = r.get("tenants") or {}
        metered = any("device_ms" in t for t in tenants.values())
        if len(tenants) > 1 or metered:
            for name, t in sorted(tenants.items()):
                toks = t.get("prompt_tokens", 0) + t.get("output_tokens", 0)
                lines.append(
                    f"  tenant {name or '-':<16} req={t.get('requests', 0):>4} "
                    f"tok={toks:>7} ({_pct(t.get('token_share'))}) "
                    f"dev_ms={t.get('device_ms', '-')} "
                    f"({_pct(t.get('device_share'))}) "
                    f"kv_Bs={t.get('kv_byte_s', '-')} "
                    f"({_pct(t.get('kv_share'))})"
                )
    if not reports:
        lines.append("(no scenarios replayed)")
    return "\n".join(lines)
