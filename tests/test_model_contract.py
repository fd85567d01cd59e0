"""The seam between a model and the engine (ISSUE 48): `models/paged.py` says
what a model owes the engine (`PagedModel`) and owns the step's plumbing over
the paged pool (`DecodeStep`, `Pack`, `state_rows`, `route`, `ExpertCounts`).
Held here: every model class signs the contract, nobody outside `models/`
probes a model for a contract name, no model file but `paged.py` reaches the
attention dispatches, and the plumbing addresses the rows a hand-written
numpy version addresses."""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.paged import (
    DecodeStep,
    ExpertCounts,
    Pack,
    PagedModel,
    route,
    state_rows,
)
from dynamo_tpu.models.registry import ARCHITECTURES, _resolve, load_model

PACKAGE = Path(__file__).resolve().parents[1] / "dynamo_tpu"
#: every name the engine may read of a model: the base's public names
CONTRACT = {"config"} | {n for n in vars(PagedModel) if not n.startswith("_")}
TINY_FAMILIES = ["tiny", "tiny-moe", "tiny-mla", "tiny-vl", "tiny-hybrid", "tiny-window",
                 "tiny-conv", "tiny-parallel"]
#: the kernels' entry points and what a decode step makes once for them
PLUMBING = {"live_rows", "decode_tile_runs", "dispatch_paged_decode_attention",
            "dispatch_paged_prefill_attention"}


def test_the_contract_names_what_the_engine_reads():
    assert CONTRACT >= {
        "layer_groups", "kv_page_bytes", "wire_n_axis", "recurrent", "kv_tables", "expert_mesh",
        "attn_mesh", "init_state_cache", "state_cache_sharding", "window_counters",
        "SUPPORTS_LORA", "SUPPORTS_KV_INT8", "prefill_sp", "prefill_packed", "config",
        "init_params", "kv_cache_shape", "init_kv_cache", "kv_cache_sharding", "prefill",
        "decode", "state_bytes", "param_shardings"}


# ---------------- (a) every model class signs it ----------------


@pytest.mark.parametrize("architecture", sorted(ARCHITECTURES))
def test_architecture_subclasses_the_contract(architecture):
    _, model_cls, _ = _resolve(ARCHITECTURES[architecture])
    assert issubclass(model_cls, PagedModel)
    for name in CONTRACT - {"config"}:  # the config is an instance's
        getattr(model_cls, name)
    # what the engine cannot run without is the class's own, not the base's refusal
    for name in ("init_params", "kv_cache_shape", "prefill", "decode"):
        assert getattr(model_cls, name) is not getattr(PagedModel, name), name


@pytest.mark.parametrize("family", TINY_FAMILIES)
def test_tiny_family_resolves_every_contract_name(family):
    model, _ = load_model(family)
    assert isinstance(model, PagedModel)
    for name in CONTRACT:
        getattr(model, name)
    assert isinstance(model.recurrent, bool) and isinstance(model.kv_tables, int)
    assert model.wire_n_axis in (1, 2)
    assert model.prefill_packed is None or callable(model.prefill_packed)
    assert model.prefill_sp is None or callable(model.prefill_sp)
    # the state cache: its sharding names its leaves, the window's counters are
    # leaves of it, and `state_bytes` is the rest
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    state = jax.eval_shape(lambda: model.init_state_cache(3))
    assert set(model.state_cache_sharding(mesh)) == set(state)
    assert set(model.window_counters) <= set(state)
    rest = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for name, x in state.items() if name not in model.window_counters)
    assert model.state_bytes(3) == rest
    assert bool(rest) == model.recurrent
    # a page's price is what a page of the pools holds (deepseek's: never priced)
    pools = jax.eval_shape(lambda: model.init_kv_cache(5, 4))
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools)) // 5
    assert model.kv_page_bytes(4) == (0 if family == "tiny-mla" else held)
    assert set(model.kv_cache_sharding(mesh)) == set(pools)


# ---------------- (b) nobody probes a model for a contract name ----------------


def _ends_in_model(node) -> bool:
    if isinstance(node, ast.Name):
        return node.id.endswith("model")
    if isinstance(node, ast.Attribute):
        return node.attr.endswith("model")
    if isinstance(node, ast.Call) and getattr(node.func, "id", "") == "getattr" and len(node.args) > 1:
        return isinstance(node.args[1], ast.Constant) and node.args[1].value == "model"
    return False


def test_no_probe_of_a_model_for_a_contract_name_outside_models():
    probes = []
    for path in sorted(PACKAGE.rglob("*.py")):
        if path.parent == PACKAGE / "models":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and getattr(node.func, "id", "") in ("getattr", "hasattr")
                    and len(node.args) >= 2 and isinstance(node.args[1], ast.Constant)
                    and node.args[1].value in CONTRACT and _ends_in_model(node.args[0])):
                probes.append(f"{path.relative_to(PACKAGE.parent)}:{node.lineno} {node.args[1].value}")
    assert not probes, "read the name off the model (models/paged.py PagedModel): " + "; ".join(probes)


# ---------------- (c) one file reaches the attention dispatches ----------------


def test_only_paged_py_reaches_the_attention_dispatches():
    reach = {}
    for path in sorted((PACKAGE / "models").glob("*.py")):
        names = set()
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                names |= {a.name for a in node.names}
            elif isinstance(node, ast.Name):
                names.add(node.id)
            elif isinstance(node, ast.Attribute):
                names.add(node.attr)
        if names & PLUMBING:
            reach[path.name] = names & PLUMBING
    assert reach == {"paged.py": PLUMBING}


# ---------------- (d) the plumbing addresses the rows a plain version does ----------------

PS, PAGES, WIDTH = 4, 24, 6


def _geometry(family):
    model, _ = load_model(family)
    k_pool = jnp.zeros(model.kv_cache_shape(PAGES, PS), jnp.float32)
    return model, k_pool, model.kv_tables


def _tables(rng, rows, kv_tables):
    """[rows, kv_tables * WIDTH] as the runner carries them, and [kv_tables, rows, WIDTH]."""
    by_table = rng.integers(1, PAGES, (kv_tables, rows, WIDTH)).astype(np.int32)
    return np.concatenate(list(by_table), axis=1), by_table


@pytest.mark.parametrize("family", ["tiny-hybrid", "tiny-conv", "tiny-parallel", "tiny-window"])
def test_decode_step_addresses_the_rows_numpy_does(family):
    model, k_pool, kv_tables = _geometry(family)
    rng = np.random.default_rng(7)
    B = 5
    side_by_side, by_table = _tables(rng, B, kv_tables)
    positions = rng.integers(0, PS * WIDTH, B).astype(np.int32)
    active = np.array([True, False, True, True, False])
    step = DecodeStep(jnp.asarray(side_by_side), jnp.asarray(positions), jnp.asarray(active),
                      k_pool, model.config.head_dim, None, kv_tables=kv_tables)
    np.testing.assert_array_equal(step.offsets, np.where(active, positions % PS, 0))
    np.testing.assert_array_equal(step.live.mask, active)
    assert int(step.live.count[0]) == 3
    np.testing.assert_array_equal(np.sort(np.asarray(step.live.order)[:3]), [0, 2, 3])
    for l in range(kv_tables):
        at = step.layer(l) if kv_tables > 1 else step
        want = np.where(active, by_table[l][np.arange(B), positions // PS], 0)  # inactive: null page
        np.testing.assert_array_equal(at.phys, want)
        np.testing.assert_array_equal(at.tables, by_table[l])
        assert at.live is step.live and at.offsets is step.offsets
    assert step.runs is None  # no kernel on this backend reads them


@pytest.mark.parametrize("family", ["tiny-hybrid", "tiny-conv", "tiny-parallel", "tiny-window"])
def test_pack_addresses_the_rows_numpy_does(family):
    model, k_pool, kv_tables = _geometry(family)
    rng = np.random.default_rng(11)
    N, T = 3, 8
    side_by_side, by_table = _tables(rng, N, kv_tables)
    start = np.array([0, 5, 12], np.int32)  # lane 0 starts its sequence
    positions = start[:, None] + np.arange(T, dtype=np.int32)[None]
    valid = np.arange(T)[None] < np.array([8, 3, 0])[:, None]  # lane 2 is padding
    flat = {"tiny-parallel": ("phys", "offsets")}.get(family, ("offsets",))
    pack = Pack(jnp.asarray(side_by_side), jnp.asarray(positions), jnp.asarray(valid), PS, None,
                kv_tables=kv_tables, flat=flat)
    assert (pack.N, pack.T) == (N, T)
    np.testing.assert_array_equal(pack.offsets, np.where(valid, positions % PS, 0).reshape(N * T))
    np.testing.assert_array_equal(pack.flat_positions, positions.reshape(N * T))
    for l in range(kv_tables):
        at = pack.layer(l) if kv_tables > 1 else pack
        want = np.where(valid, by_table[l][np.arange(N)[:, None], positions // PS], 0)
        np.testing.assert_array_equal(at.phys, want.reshape(N * T) if "phys" in flat else want)
    if model.recurrent:
        slot_rows = 4 + 1  # max_seqs + the trash row
        named = np.array([2, -1, 4], np.int32)  # a slot, none, one past the last
        fresh, slots = state_rows(jnp.asarray(named), slot_rows, jnp.asarray(positions))
        np.testing.assert_array_equal(fresh, [True, False, False])
        np.testing.assert_array_equal(slots, [2, slot_rows - 1, slot_rows - 1])


def test_pack_by_lane_and_flat_hold_the_same_rows():
    rng = np.random.default_rng(3)
    tables, _ = _tables(rng, 2, 1)
    positions = jnp.asarray(np.arange(8, dtype=np.int32)[None] + np.array([[0], [4]], np.int32))
    valid = jnp.ones((2, 8), bool)
    by_lane = Pack(jnp.asarray(tables), positions, valid, PS, None, flat=())
    flat = Pack(jnp.asarray(tables), positions, valid, PS, None, flat=("phys", "offsets"))
    assert by_lane.phys.shape == by_lane.offsets.shape == (2, 8)
    np.testing.assert_array_equal(by_lane.phys.reshape(16), flat.phys)
    np.testing.assert_array_equal(by_lane.offsets.reshape(16), flat.offsets)


def test_route_masks_the_rows_that_are_not_counted():
    h = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.bfloat16)
    router = jnp.asarray(np.random.default_rng(1).normal(size=(8, 6)), jnp.float32)
    seen = {}

    def score(logits):
        seen["dtype"] = logits.dtype
        return jax.lax.top_k(logits, 2)

    weights, idx = route(h, router, score, count_rows=jnp.asarray([True, False, True, False]))
    assert seen["dtype"] == jnp.float32 and weights.shape == idx.shape == (4, 2)
    _, plain = route(h, router, score)
    np.testing.assert_array_equal(idx[0], plain[0])
    np.testing.assert_array_equal(idx[1], [-1, -1])  # held nowhere


def test_expert_counts_add_where_an_engine_keeps_them():
    routed = ExpertCounts({"k": 1})
    routed.add(jnp.asarray([1, 0, 2]))
    assert routed.into({"k": 1}) == {"k": 1}  # absent: nothing to hand back
    cache = {"moe_counts": jnp.zeros((3,), jnp.int32), "moe_touched": jnp.zeros((1,), jnp.int32)}
    routed = ExpertCounts(cache)
    routed.add(jnp.asarray([1, 0, 2]))
    routed.add(jnp.asarray([0, 0, 4]))
    out = routed.into(cache)
    np.testing.assert_array_equal(out["moe_counts"], [1, 0, 6])
    np.testing.assert_array_equal(out["moe_touched"], [3])
