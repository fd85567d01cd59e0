"""The third reduction (device time by part of the model) held to a recorded
slice of a chip trace kept beside this file, and the nine readers over it on
a table small enough to check by hand. `tests/test_trace_parts.py` (tier-1)
holds the reduction's arithmetic, the wire reader and the program's scopes.

The slice (`trace_parts_slice.json`) was cut from the builder's traced run of
`qwen2.5-3b.chat-over` (PR 39, seed 3000000011, TPU v5e): the leaf-bearing
`XLA Ops` events of some whole module runs from the third run on, those runs
under their full names, the engine thread's spans around them, and the map's
entries for the instructions seen; `expect` is what `reduce` gave then."""

import json
from pathlib import Path

import pytest
import trace_parts
import trace_reduce
from layer_metrics import (_dense_cost, _parts, decode_dense_roofline, dense_share_of_busy,
                           glue_share_of_busy, head_share_of_busy, moe_dispatch_share_of_busy,
                           prefill_fill_share, prefill_mfu, ssm_prefill_share_of_busy,
                           unnamed_share_of_busy)

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent


def _events(doc):
    return {"modules": {p: [tuple(e) for e in v] for p, v in doc["modules"].items()},
            "ops": {p: [tuple(e) for e in v] for p, v in doc["ops"].items()},
            "host": [tuple(e) for e in doc["host"]],
            "map": {m: {i: tuple(meta) for i, meta in insts.items()} for m, insts in doc["map"].items()}}


def test_recorded_chip_slice():
    doc = json.loads((HERE / "trace_parts_slice.json").read_text())
    ev = _events(doc)
    r = trace_parts.reduce(ev)
    want = doc["expect"]
    assert set(r["by_step_part"]) == set(want["by_step_part"])
    for step, by_part in want["by_step_part"].items():
        assert set(r["by_step_part"][step]) == set(by_part), step
        for part, secs in by_part.items():
            assert r["by_step_part"][step][part] == pytest.approx(secs, rel=1e-9), (step, part)
    for key in ("leaf_s", "busy_s", "no_module_s", "body_named_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9, abs=1e-15), key
    assert r["decode"]["runs"] == want["decode"]["runs"] and r["decode"]["steps"] == want["decode"]["steps"]
    assert len(r["prefill"]["pairs"]) == want["prefill_pairs"] and r["fill"] == want["fill"]
    # the parts add up to the leaf seconds `trace_reduce` counts by name on the same events
    by_name = trace_reduce.reduce_events({p: [tuple(e) for e in v] for p, v in doc["ops"].items()})
    named = sum(s for by in r["by_step_part"].values() for s in by.values()) + r["no_module_s"]
    assert named == pytest.approx(sum(by_name["ops_by_name"].values()), rel=1e-9)
    assert r["busy_s"] == pytest.approx(by_name["busy_s"], rel=1e-9)
    # a chip's trace names nearly everything: what is left is the compiler's own
    unnamed = sum(by.get("unnamed", 0.0) for by in r["by_step_part"].values())
    assert unnamed + r["no_module_s"] < 0.05 * r["leaf_s"]
    # ... and the runner's two small programs between the steps (the next key)
    assert all(not u["op_name"] or "dynamo_" not in u["module"] for u in r["unnamed_top"])
    # the kernels keep their names beside their part
    assert any("paged_decode_attention" in name for name in r["ops_by_part"]["attn"])


# ---------------- the readers, on a table checked by hand ----------------

QWEN = json.loads((BENCH / "configs" / "qwen2.5-3b.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def _ctx(**over):
    table = {
        "by_step_part": {
            "decode_window": {"attn_proj": 0.30, "mlp": 1.00, "norm": 0.05, "lm_head": 0.15, "sample": 0.05,
                              "attn": 0.50, "attn_kv": 0.06, "embed": 0.01, "step": 0.03, "unnamed": 0.02,
                              "moe_router": 0.02, "moe_dispatch": 0.05, "moe_experts": 0.40,
                              "shared_experts": 0.10, "ssm_proj": 0.04, "ssm": 0.20},
            "prefill_packed": {"attn_proj": 0.10, "mlp": 0.40, "ssm": 0.07, "step": 0.01},
        },
        "ops_by_part": {"moe_experts": {"moe_grouped_matmul": 0.37, "maximum_multiply_fusion": 0.03}},
        "decode": {"runs": 50, "steps": 200, "seconds": 2.4,
                   "seconds_by_part": {"attn_proj": 0.30, "mlp": 1.00, "norm": 0.05, "lm_head": 0.15, "attn": 0.5}},
        "prefill": {"pairs": [{"seq": i, "step": "prefill_packed", "rows": 400, "lanes": 2, "padded": 512,
                               "ctx": 100, "device_s": 0.020} for i in range(6)]},
        "fill": {"rows": 3000, "padded": 4096, "spans": 8},
        "no_module_s": 0.01, "leaf_s": 3.57,
    }
    table.update(over)
    return {"trace_parts": table, "trace": {"busy_s": 3.57}, "peaks": PEAKS, "config": QWEN}


def test_shares_by_hand():
    ctx = _ctx()
    assert dense_share_of_busy.read(ctx) == pytest.approx(100 * (0.30 + 1.00 + 0.10 + 0.04 + 0.10 + 0.40) / 3.57)
    assert head_share_of_busy.read(ctx) == pytest.approx(100 * 0.20 / 3.57)
    assert glue_share_of_busy.read(ctx) == pytest.approx(100 * (0.01 + 0.05 + 0.06 + 0.03 + 0.01) / 3.57)
    # the router, the dispatch, and what runs under the experts' scope that is no product
    assert moe_dispatch_share_of_busy.read(ctx) == pytest.approx(100 * (0.02 + 0.05 + 0.03) / 3.57)
    assert ssm_prefill_share_of_busy.read(ctx) == pytest.approx(100 * 0.07 / 3.57)  # not the decode side's 0.20
    assert unnamed_share_of_busy.read(ctx) == pytest.approx(100 * (0.02 + 0.01) / 3.57)
    assert prefill_fill_share.read(ctx) == pytest.approx(100 * 3000 / 4096)
    # the parts of the table account for all of busy
    assert sum(s for by in ctx["trace_parts"]["by_step_part"].values() for s in by.values()) + 0.01 \
        == pytest.approx(3.57)


def test_roofline_and_mfu_by_hand():
    ctx = _ctx()
    # 6.17 GB at 819 GB/s = 7.54 ms a step; the four parts take 1.5 s over 200 steps = 7.5 ms
    import costs

    floor_s = costs.weight_bytes(QWEN) / 819e9
    assert floor_s == pytest.approx(7.54e-3, rel=5e-3)
    assert decode_dense_roofline.read(ctx) == pytest.approx(100 * floor_s / (1.5 / 200))
    # a pack of 400 real rows: 2 x 400 x 36 layers x 77.07 M parameters = 2.22 TFLOP of
    # matrices, and at least 100 + 400 x 201 / 2 = 40300 attended pairs x 8192 x 36
    assert _dense_cost.layer_matrix_params(QWEN) == 2048 * 128 * 20 + 2048 * 2048 + 3 * 2048 * 11008
    assert _dense_cost.attended_pairs_at_least(400, 100, 2) == 100 + 400 * 201 / 2
    flops = 2 * 400 * 36 * _dense_cost.layer_matrix_params(QWEN) + 4 * 16 * 128 * 40300 * 36
    assert _dense_cost.prefill_useful_flops(QWEN, 400, 100, 2) == pytest.approx(flops)
    assert prefill_mfu.read(ctx) == pytest.approx(100 * flops / (0.020 * 197e12))
    assert 0 < prefill_mfu.read(ctx) < 100 and 0 < decode_dense_roofline.read(ctx) < 105


def test_the_bound_on_attended_pairs_is_one():
    """Whatever the split of a pack's rows and context over its sequences, the
    pairs it attends over are at least what the sums allow."""
    import itertools

    for rows, ctxs in itertools.product([(1, 399), (200, 200), (399, 1), (7, 50, 300)],
                                        [(0, 100, 0), (100, 0, 0), (33, 33, 34)]):
        ctxs = ctxs[:len(rows)] + (0,) * (len(rows) - len(ctxs))
        true = sum(r * s + r * (r + 1) / 2 for r, s in zip(rows, ctxs))
        for lanes in (len(rows), 4):
            assert _dense_cost.attended_pairs_at_least(sum(rows), sum(ctxs), lanes) <= true


def test_readers_say_nothing_without_the_parts():
    """An untraced run, a program without the scopes (no `step` anywhere: the
    reduction is kept from `ctx`), spans without `padded`: nothing, no error."""
    readers = (dense_share_of_busy, head_share_of_busy, glue_share_of_busy, moe_dispatch_share_of_busy,
               ssm_prefill_share_of_busy, unnamed_share_of_busy, decode_dense_roofline, prefill_mfu,
               prefill_fill_share)
    for ctx in ({}, {"trace_report": None}, {"trace_parts": None, "trace": {"busy_s": 3.0}}):
        assert [r.read(dict(ctx)) for r in readers] == [None] * 9
    bare = _ctx(decode=None, prefill={"pairs": []}, fill={"rows": 0, "padded": 0, "spans": 0})
    assert decode_dense_roofline.read(bare) is None and prefill_mfu.read(bare) is None
    assert prefill_fill_share.read(bare) is None
    # the parent's scopes (`attn`, `mlp`, `lm_head`, `sample`, no `attn_proj`): no roofline from half the parts
    old = _ctx(decode={"runs": 5, "steps": 20, "seconds": 0.2, "seconds_by_part": {"mlp": 0.1, "lm_head": 0.01}})
    assert decode_dense_roofline.read(old) is None
    assert _parts.share(old, 0.0) is None and _parts.share({"trace": {"busy_s": 0.0}}, 1.0) is None
