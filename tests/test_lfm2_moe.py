"""LFM2-MoE (models/lfm2_moe.py): gated short convolutions with a two-row
window per decode slot beside a folded paged KV, a norm per head on q and k
before rope, dense FFNs first and then sigmoid-routed experts, all of them held.

Everything is compared with the plain reference the benchmark uses
(`benchmark/reference/lfm2_moe.py`: numpy float32, no cache), which reads the
same checkpoint files the program loads.
"""

import asyncio
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.models.lfm2_moe import ROUTING_EPS, Lfm2MoeConfig, Lfm2MoeModel
from dynamo_tpu.models.registry import load_model
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops.moe import sigmoid_topk_routing

from hybrid_helpers import (
    Driver as _Driver,
    bench_module as _bench_module,
    generate as _generate,
    tokens as _tokens,
    window_off_by_one as _window_off_by_one,
)

reference = _bench_module("reference", "lfm2_moe")
plan = _bench_module("checkpoints", "lfm2_moe")

#: config.json keys of a small LFM2-MoE: conv before and after attention, one
#: dense FFN and four expert layers, float32 so that the comparison's
#: tolerance is float32 rounding and nothing hides under it
HF_TINY = {
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "torch_dtype": "float32", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "full_attention"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "rope_theta": 1000000,
    "num_dense_layers": 1, "intermediate_size": 96,
    "num_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "use_expert_bias": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": True,
}


def write_checkpoint(out: Path, hf: dict, seed: int) -> Path:
    """The plan's tensors in float32 at a scale where every block matters
    (matrices at 1/sqrt(fan_in), taps at 0.5, norm weights near 1 so that a
    norm after rope is another model, the selection bias at 0.05): a
    checkpoint only these tests write."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, kind in plan.tensor_plan(hf):
        if name.endswith("conv.conv.weight"):
            t = rng.normal(0.0, 0.5, shape)
        elif name.endswith("layernorm.weight"):
            t = 1.0 + rng.normal(0.0, 0.3, shape)
        elif kind == "ones":
            t = 1.0 + rng.normal(0.0, 0.1, shape)
        elif name.endswith("expert_bias"):
            t = rng.normal(0.0, 0.05, shape)
        else:
            t = rng.normal(0.0, 1.0 / np.sqrt(shape[1]), shape)
        tensors[name] = t.astype(np.float32)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(hf))
    save_file(tensors, str(out / "model.safetensors"))
    return out


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return write_checkpoint(tmp_path_factory.mktemp("lfm2_moe") / "ckpt", HF_TINY, 42)


@pytest.fixture(scope="module")
def loaded(ckpt):
    return load_model(str(ckpt))


def _ref_logits(ckpt, tokens, options=None):
    return reference.forward_logits(ckpt, [tokens], [(0, len(tokens))], options)[0]


# ---------------------------------------------------------------- the model, on logits

#: float32 on both sides: the program's grouped products, paged attention and
#: carried window against the reference's dense products and one pass differ
#: by summation order only. Measured 5.4e-6 at most on logits of size ~3 (this
#: file, PR 42, CPU); 1e-4 leaves an order of magnitude, and a wrong window,
#: norm, expert weight or mask moves logits by 1e-2 to 1 (the controls below).
LOGIT_ATOL = 1e-4


def test_the_full_forward_matches_the_reference(ckpt, loaded):
    """(a) one pass over a whole sequence, every position's logits."""
    model, params = loaded
    a = _tokens(1, 40)
    d = _Driver(model, params)
    T = 48
    toks = np.zeros((1, T), np.int32)
    toks[0, :40] = a
    hidden, _ = jax.jit(model._packed_forward)(
        params, d.cache, jnp.asarray(toks), jnp.arange(T)[None], jnp.asarray(d.tables[:1]),
        jnp.asarray(np.arange(T) < 40)[None], jnp.zeros((1,), jnp.int32),
    )
    got = np.asarray(model._unembed(params, hidden))[:40]
    np.testing.assert_allclose(got, _ref_logits(ckpt, a), atol=LOGIT_ATOL)


def test_prefill_chunks_packs_and_decode_match_the_reference_logits(ckpt, loaded):
    """(a) prefill in two chunks, the second packed with another sequence and
    a padding lane, then 8 decode steps through the page pool and the state
    rows with a slot left inactive, against the reference's one pass."""
    model, params = loaded
    a, b = _tokens(1, 46), _tokens(2, 21)
    ref_a, ref_b = _ref_logits(ckpt, a), _ref_logits(ckpt, b)
    d = _Driver(model, params)
    got = d.prefill([(2, a[:16], 0)], 16)
    np.testing.assert_allclose(got[0], ref_a[15], atol=LOGIT_ATOL)
    got = d.prefill([(2, a[16:37], 16), (0, b[:18], 0), (-1, [], 0)], 32)
    np.testing.assert_allclose(got[0], ref_a[36], atol=LOGIT_ATOL)
    np.testing.assert_allclose(got[1], ref_b[17], atol=LOGIT_ATOL)
    trash_before = np.asarray(d.cache["conv"][d.max_seqs])
    for step in range(8):
        fed = {2: (a[37 + step], 37 + step)}
        if step < 3:
            fed[0] = (b[18 + step], 18 + step)
        got = d.decode(fed)
        np.testing.assert_allclose(got[2], ref_a[37 + step], atol=LOGIT_ATOL)
        if step < 3:
            np.testing.assert_allclose(got[0], ref_b[18 + step], atol=LOGIT_ATOL)
    assert not np.asarray(d.cache["conv"][1]).any(), "an inactive slot's window was touched"
    np.testing.assert_array_equal(np.asarray(d.cache["conv"][d.max_seqs]), trash_before)
    # every expert is held: each active row made K assignments a layer, and the
    # last step's count of (layer, expert) pairs touched lies between K and 4 K
    assert int(d.cache["moe_counts"].sum()) > 0
    assert 0 < int(d.cache["moe_touched"][0])


# ---------------------------------------------------------------- (b) the conv state

def test_a_slot_used_again_starts_from_zeros_and_padding_writes_the_trash_row(ckpt, loaded):
    model, params = loaded
    a, b = _tokens(3, 20), _tokens(4, 12)
    d = _Driver(model, params)
    d.prefill([(1, a, 0)], 32)
    assert np.asarray(d.cache["conv"][1]).any()
    slot_rows = d.max_seqs + 1
    trash = [m * slot_rows + d.max_seqs for m in range(3)]
    before = np.asarray(d.cache["conv"])
    # the same slot, a new sequence from position 0, beside a padding lane
    got = d.prefill([(1, b, 0), (-1, [], 0)], 16)
    np.testing.assert_allclose(got[0], _ref_logits(ckpt, b)[11], atol=LOGIT_ATOL)
    after = np.asarray(d.cache["conv"])
    changed = {int(r) for r in np.flatnonzero((before != after).any(axis=(1, 2)))}
    assert changed <= {m * slot_rows + 1 for m in range(3)} | set(trash)
    for r in set(range(after.shape[0])) - {m * slot_rows + 1 for m in range(3)}:
        if r not in trash:
            np.testing.assert_array_equal(after[r], before[r])


@pytest.mark.parametrize("fault", ["zeroed_window", "shifted_window"])
def test_a_broken_hand_off_fails_the_logit_tolerance(ckpt, loaded, fault):
    """The control of (a) and (b): a window not handed from prefill to decode,
    and a window off by one position, each move the first decoded logits far
    past LOGIT_ATOL."""
    model, params = loaded
    a = _tokens(3, 30)
    ref = _ref_logits(ckpt, a)
    d = _Driver(model, params)
    d.prefill([(0, a[:24], 0)], 32)
    if fault == "zeroed_window":
        d.cache["conv"] = jnp.zeros_like(d.cache["conv"])
    else:
        d.cache["conv"] = _window_off_by_one(d.cache["conv"])
    got = d.decode({0: (a[24], 24)})
    assert np.abs(got[0] - ref[24]).max() > 100 * LOGIT_ATOL


@pytest.mark.parametrize("control", [{"conv_window": "lost"}, {"conv_window": "late"},
                                     {"qk_norm": False}, {"bias_in_weights": True}],
                         ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_the_references_controls_move_the_decoded_rows(ckpt, control):
    """What `benchmark/tests/test_controls_lfm2_moe.py` asks of the reference
    at full width, at this size: each control moves the rows a served run
    decodes, and the conv faults leave the prompt's rows alone."""
    a = _tokens(6, 30)
    spans = [(23, 30)]
    healthy = reference.forward_logits(ckpt, [a], spans)[0]
    got = reference.forward_logits(ckpt, [a], spans, control)[0]
    if "conv_window" in control:
        np.testing.assert_allclose(got[0], healthy[0], atol=1e-6)  # the prompt's last row
    assert np.abs(got[1:] - healthy[1:]).max() > 20 * LOGIT_ATOL


# ---------------------------------------------------------------- (c) routing

def test_the_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(0, 1, (64, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (32,)), jnp.float32)
    w0, i0 = sigmoid_topk_routing(logits, jnp.zeros(32), 4, eps=ROUTING_EPS)
    w1, i1 = sigmoid_topk_routing(logits, bias, 4, eps=ROUTING_EPS)
    assert (np.sort(np.asarray(i0)) != np.sort(np.asarray(i1))).any(), "the bias chose nothing"
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(s, np.asarray(i1), axis=-1)
    np.testing.assert_allclose(np.asarray(w1), chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    total = np.asarray(w1).sum(-1)
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()  # 1 up to the 1e-6
    # the callers that were there keep 1e-20: their weights sum to 1 to rounding
    w2, _ = sigmoid_topk_routing(logits, bias, 4)
    np.testing.assert_allclose(np.asarray(w2).sum(-1), 1.0, rtol=3e-7)


def _expert_layer(cfg, seed=5):
    rng = np.random.default_rng(seed)
    D, Fm, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.moe_routed_over

    def mat(*shape):
        return rng.normal(0, 1 / np.sqrt(shape[-2]), shape).astype(np.float32)

    return {"router": mat(D, E), "router_bias": rng.normal(0, 0.05, (E,)).astype(np.float32),
            "w1": mat(E, D, Fm), "w3": mat(E, D, Fm), "w2": mat(E, Fm, D)}


def test_all_held_counts_every_assignment_and_the_shares_add_up():
    """All 32 held: `counts.sum() == T * 4`. And `model-configs` section 4 in
    its trivial form here: shares of 8 + 8 + 8 + 8 experts (offsets 0, 8, 16,
    24) add up to the all-held layer."""
    whole_cfg = Lfm2MoeConfig.tiny(num_experts=32, moe_routed_over=32, num_experts_per_tok=4)
    bp = _expert_layer(whole_cfg)
    T = 40
    x = jnp.asarray(np.random.default_rng(8).normal(0, 1, (T, whole_cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts = Lfm2MoeModel(whole_cfg)._experts(jax.tree.map(jnp.asarray, bp), x)
        assert int(counts.sum()) == T * 4
        total, got = np.zeros_like(np.asarray(whole)), 0
        for off in (0, 8, 16, 24):
            cfg = Lfm2MoeConfig.tiny(num_experts=8, moe_routed_over=32, moe_expert_offset=off,
                                     num_experts_per_tok=4)
            share = dict(bp, **{k: bp[k][off:off + 8] for k in ("w1", "w3", "w2")})
            out, n = Lfm2MoeModel(cfg)._experts(jax.tree.map(jnp.asarray, share), x)
            total += np.asarray(out)
            got += int(n.sum())
    assert got == T * 4, "an assignment was dropped or counted twice"
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5)


# ---------------------------------------------------------------- (d) QK-norm before rope

@pytest.mark.parametrize("fault", ["after_rope", "left_out"])
def test_the_norm_per_head_comes_before_rope(ckpt, loaded, monkeypatch, fault):
    """With norm weights that differ by lane, a norm applied after the rope
    (or not at all) is another model: the healthy path is held to the
    reference above, these two move the logits far past LOGIT_ATOL."""
    import dynamo_tpu.models.lfm2_moe as M

    model, params = loaded
    a = _tokens(9, 24)
    ref = _ref_logits(ckpt, a)
    if fault == "left_out":
        real = M.rms_norm
        monkeypatch.setattr(M, "rms_norm", lambda x, w, eps: x if x.ndim == 3 else real(x, w, eps))
    else:
        real_norm, real_rope = M.rms_norm, M.apply_rope
        weights = []

        def norm_later(x, w, eps):
            if x.ndim != 3:
                return real_norm(x, w, eps)
            weights.append(w)
            return x

        monkeypatch.setattr(M, "rms_norm", norm_later)
        monkeypatch.setattr(M, "apply_rope", lambda x, p, t: real_norm(
            real_rope(x, p, t), weights.pop(0), model.config.norm_eps))
    d = _Driver(model, params)
    got = d.prefill([(0, a, 0)], 32)
    assert np.abs(got[0] - ref[23]).max() > 20 * LOGIT_ATOL


# ---------------------------------------------------------------- (e) refusals by name

@pytest.mark.parametrize("key, value, why", [
    ("conv_bias", True, "conv_bias=True is not supported"),
    ("norm_topk_prob", False, "norm_topk_prob=False is not supported"),
    ("use_expert_bias", False, "use_expert_bias=False is not supported"),
    ("tie_word_embeddings", False, "tie_word_embeddings=False is not supported"),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv", "full_attention"],
     "each conv or full_attention"),
    ("num_hidden_layers", 4, "layer_types must name num_hidden_layers=4"),
])
def test_from_hf_config_refuses_by_name(key, value, why):
    with pytest.raises(ValueError, match=why):
        Lfm2MoeConfig.from_hf_config(dict(HF_TINY, **{key: value}))


def test_the_published_keys_are_read():
    c = Lfm2MoeConfig.from_hf_config(HF_TINY)
    assert (c.head_dim, c.conv_kernel, c.num_dense_layers, c.num_expert_layers) == (16, 3, 1, 4)
    assert c.routed_per_token == 12 and c.count("conv") == 3 and c.rope_theta == 1e6
    newer = {k: v for k, v in HF_TINY.items() if k != "rope_theta"}
    newer["rope_parameters"] = {"rope_theta": 5e5, "rope_type": "default"}
    assert Lfm2MoeConfig.from_hf_config(newer).rope_theta == 5e5
    model = Lfm2MoeModel(c)
    assert model.kv_cache_shape(10, 16) == (20, 16, 32)  # the attention layers only, folded
    assert model.state_bytes(7) == 3 * 8 * 2 * 64 * 4


# ---------------------------------------------------------------- (f) the folded dispatch

#: (name, Hq, Hkv, D, the folded prefill's block_q): the shapes that were there
#: keep their 64 rows; LFM2's 32 heads over 8 x 64 folded lanes get 32
FOLDED_SHAPES = [("tinyllama-1.1b", 32, 4, 64, 64), ("qwen2-0.5b", 14, 2, 64, 64),
                 ("lfm2-8b-a1b", 32, 8, 64, 32)]


@pytest.mark.parametrize("name, Hq, Hkv, D, block_q", FOLDED_SHAPES, ids=[s[0] for s in FOLDED_SHAPES])
def test_the_folded_dispatch_takes_a_kernel_and_agrees_with_the_gather(name, Hq, Hkv, D, block_q,
                                                                      monkeypatch):
    from dynamo_tpu.ops.pallas.prefill_attention import folded_prefill_block_q

    assert folded_prefill_block_q(Hq, Hkv * D) == block_q
    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    monkeypatch.setattr(attn_ops, "_logged_paths", set())
    seen = []
    monkeypatch.setattr(attn_ops, "_log_path", lambda op, path, why: seen.append((op, path)))
    rng = np.random.default_rng(11)
    ps, pages, T, B = 16, 12, 64, 2
    pool = lambda: jnp.asarray(rng.normal(0, 1, (pages, ps, Hkv * D)), jnp.float32)
    k_pool, v_pool = pool(), pool()
    table = jnp.asarray(1 + np.arange(8), jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (T, Hq, D)), jnp.float32)
    pos = jnp.arange(40, 40 + T, dtype=jnp.int32)
    got = attn_ops.dispatch_paged_prefill_attention(q, k_pool, v_pool, table, pos)
    want = attn_ops.paged_prefill_attention(q, k_pool, v_pool, table, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    qd = q[:B]
    tables = jnp.stack([table, table[::-1]])
    at = jnp.asarray([100, 57], jnp.int32)
    got = attn_ops.dispatch_paged_decode_attention(qd, k_pool, v_pool, tables, at)
    want = attn_ops.paged_decode_attention(qd, k_pool, v_pool, tables, at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert dict(seen)["prefill"].startswith("pallas:folded"), seen
    assert dict(seen)["decode"].startswith(
        "pallas:paged_decode_attention_pallas_folded tile=8x16 window=2"), seen
    assert ("block_q=32" in dict(seen)["prefill"]) == (block_q == 32)


# ---------------------------------------------------------------- through the engine

#: logprobs of the tokens the engine chose, float32 on both sides (see
#: LOGIT_ATOL: a logprob is a logit minus a log-sum-exp of logits)
LOGPROB_ATOL = 1e-4


ENGINE_CASES = {
    # a prompt of three chunks, two decode windows
    "chunked": dict(prompts=[_tokens(11, 75)], max_tokens=8),
    # four sequences through two slots: their chunks share packed calls, and
    # each slot's window is used again by a sequence that must not see it
    "slots_reused": dict(prompts=[_tokens(14 + i, 12 + 9 * i) for i in range(4)], max_tokens=7),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_engine_matches_the_reference(ckpt, case):
    """(a) through the scheduler, runner, page table, state slots and sampler;
    and the counters item 7 of ISSUE 42 asks for."""
    spec = ENGINE_CASES[case]

    async def body():
        eng = AsyncJaxEngine(EngineConfig(
            model_id=str(ckpt), num_pages=64, max_seqs=2, max_model_len=128,
            prefill_buckets=(16, 32), decode_steps=4))
        await eng.start()
        try:
            results = await asyncio.gather(*[
                _generate(eng, f"{case}-{i}", p, spec["max_tokens"])
                for i, p in enumerate(spec["prompts"])
            ])
            return (results, eng.resource_snapshot(), eng.render_stage_metrics(),
                    eng.debug_steps(limit=128), eng.config.page_size)
        finally:
            await eng.shutdown()

    results, snap, text, steps, page_size = asyncio.run(body())
    probes = [{"tokens": list(p) + toks, "prompt_len": len(p)}
              for p, (toks, _) in zip(spec["prompts"], results)]
    for (toks, lps), want in zip(results, reference.teacher_forced_logprobs(ckpt, probes)):
        assert len(toks) == len(want) == spec["max_tokens"]
        np.testing.assert_allclose(lps, want, atol=LOGPROB_ATOL)
    assert snap["state_slots_total"] == 2 and snap["state_slots_active"] == 0
    # 3 conv layers x (2 slots + a trash row) x 2 rows x 64 lanes of float32
    assert snap["hbm_state_bytes"] == 3 * 3 * 2 * 64 * 4
    # every expert is held: what was routed landed here, 3 a token in 4 layers
    assert snap["moe_routed"] == snap["moe_assignments"] > 0
    # a step touches at least 3 and at most min(8, 3 x rows) experts a layer
    steps_at_most = snap["moe_assignments"] // 12
    assert 12 <= snap["moe_experts_touched"] <= 4 * 8 * steps_at_most
    assert f"dynamo_engine_moe_experts_touched_total {snap['moe_experts_touched']}" in text
    assert snap["prefix_cache_hit_blocks"] == 0
    # what `benchmark/layer_metrics/attn_decode_folded_roofline.py` reads: a
    # decode window's record gives back the pages of the sequences that decode
    roof, windows = steps["summary"]["roofline"], [r for r in steps["records"] if r["kind"] == "decode_window"]
    assert windows and roof["page_size"] == page_size
    for r in windows:
        pages, rest = divmod(r["floor_bytes"] // r["steps"] - roof["param_bytes"], roof["page_bytes"])
        assert rest == 0 and r["participants"] <= pages <= r["participants"] * (128 // page_size)


def test_every_expert_model_declares_the_counters_its_decode_window_returns():
    """The engine zeroes and returns what a model names in `window_counters`
    and nothing it guesses from a key: the three expert models name the same
    two leaves, and each is a leaf of the model's own state cache."""
    from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeModel
    from dynamo_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    for model in (NemotronHModel(NemotronHConfig.tiny()), Cohere2MoeModel(Cohere2MoeConfig.tiny()),
                  Lfm2MoeModel(Lfm2MoeConfig.tiny())):
        assert model.window_counters == ("moe_counts", "moe_touched")
        assert set(model.window_counters) <= set(model.init_state_cache(2))
