"""Falcon-H1 (models/falcon_h1.py): a Mamba-2 mixer and an attention mixer side
by side in every block on the same normed input, a float32 state and a
convolution window per decode slot AND pages for the same layer, five query
heads a key head, fourteen forward multipliers under nine keys.

Everything is compared with the plain reference the benchmark uses
(`benchmark/reference/falcon_h1.py`: `jax.numpy` float32, sequential
recurrence, no cache), which reads the same checkpoint files the program loads.
"""

import asyncio
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.models.falcon_h1 import MULTIPLIER_KEYS, FalconH1Config, FalconH1Model
from dynamo_tpu.models.registry import load_model
from dynamo_tpu.ops import attention as attn_ops

from hybrid_helpers import (
    ROOT,
    Driver as _Driver,
    bench_module as _bench_module,
    generate as _generate,
    tokens as _tokens,
    window_off_by_one as _window_off_by_one,
)

reference = _bench_module("reference", "falcon_h1")
plan = _bench_module("checkpoints", "falcon_h1")

#: config.json keys of a small Falcon-H1: two groups of two Mamba heads, five
#: query heads a key head, no multiplier at one, float32 so that the
#: comparison's tolerance is float32 rounding and nothing hides under it
HF_TINY = {
    "architectures": ["FalconH1ForCausalLM"], "model_type": "falcon_h1",
    "torch_dtype": "float32", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 3, "intermediate_size": 96,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 16,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "tie_word_embeddings": False,
    "embedding_multiplier": 2.5, "lm_head_multiplier": 0.5, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.6, "key_multiplier": 0.7, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 0.75, "ssm_multipliers": [0.7, 0.8, 0.6, 0.9, 0.65],
    "mlp_multipliers": [0.85, 0.55],
}
LAYERS = HF_TINY["num_hidden_layers"]


def write_checkpoint(out: Path, hf: dict, seed: int) -> Path:
    """The plan's tensors in float32 at a scale where every part matters
    (matrices at 1/sqrt(fan_in), the per-head vectors and the convolution's
    bias at 0.5, D and the `ones` near 1): a checkpoint only these tests write."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, kind in plan.tensor_plan(hf):
        if kind == "ones":
            t = 1.0 + rng.normal(0.0, 0.1, shape)
        elif name.endswith(("dt_bias", "A_log", "conv1d.bias")):
            t = rng.normal(0.0, 0.5, shape)
        elif name.endswith("mamba.D"):
            t = 1.0 + rng.normal(0.0, 0.5, shape)
        else:
            t = rng.normal(0.0, 1.0 / np.sqrt(shape[1]), shape)
        tensors[name] = t.astype(np.float32)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(hf))
    save_file(tensors, str(out / "model.safetensors"))
    return out


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return write_checkpoint(tmp_path_factory.mktemp("falcon_h1") / "ckpt", HF_TINY, 42)


@pytest.fixture(scope="module")
def loaded(ckpt):
    return load_model(str(ckpt))


def _ref_logits(ckpt, tokens, options=None, first=0):
    return reference.forward_logits(ckpt, [tokens], [(first, len(tokens))], options)[0]


# ---------------------------------------------------------------- the model, on logits

#: float32 on both sides: the program's chunked scan, paged attention and
#: carried state against the reference's token-by-token recurrence and one
#: pass differ by summation order only. Measured 4.9e-6 at most on logits of
#: size ~1.8 (this file, PR 45, CPU); 1e-4 leaves an order of magnitude, and a
#: multiplier left out, a lost state or a late window moves logits by 1e-2 to 1
#: (the controls below).
LOGIT_ATOL = 1e-4


class _Driver(_Driver):
    def state_rows(self, slot):
        return [l * (self.max_seqs + 1) + slot for l in range(LAYERS)]


def _full_forward(model, params, tokens, T=48):
    """One pass over a whole sequence, every position's logits."""
    d = _Driver(model, params)
    n = len(tokens)
    toks = np.zeros((1, T), np.int32)
    toks[0, :n] = tokens
    hidden, _ = jax.jit(model._packed_forward)(
        params, d.cache, jnp.asarray(toks), jnp.arange(T)[None], jnp.asarray(d.tables[:1]),
        jnp.asarray(np.arange(T) < n)[None], jnp.zeros((1,), jnp.int32),
    )
    return np.asarray(model._unembed(params, hidden))[:n]


def test_the_full_forward_matches_the_reference(ckpt, loaded):
    model, params = loaded
    a = _tokens(1, 40)
    np.testing.assert_allclose(_full_forward(model, params, a), _ref_logits(ckpt, a), atol=LOGIT_ATOL)


def test_prefill_chunks_packs_and_decode_match_the_reference_logits(ckpt, loaded):
    """Prefill in two chunks, the second packed with another sequence and a
    padding lane, then 8 decode steps through the page pool AND the state rows
    of every layer with a slot left inactive, against the reference's one
    pass; and the same sequence in ONE chunk lands on the same logits."""
    model, params = loaded
    a, b = _tokens(1, 46), _tokens(2, 21)
    ref_a, ref_b = _ref_logits(ckpt, a), _ref_logits(ckpt, b)
    d = _Driver(model, params)
    got = d.prefill([(2, a[:16], 0)], 16)
    np.testing.assert_allclose(got[0], ref_a[15], atol=LOGIT_ATOL)
    got = d.prefill([(2, a[16:37], 16), (0, b[:18], 0), (-1, [], 0)], 32)
    np.testing.assert_allclose(got[0], ref_a[36], atol=LOGIT_ATOL)
    np.testing.assert_allclose(got[1], ref_b[17], atol=LOGIT_ATOL)
    one = _Driver(model, params)
    np.testing.assert_allclose(one.prefill([(1, a[:37], 0)], 48)[0], got[0], atol=LOGIT_ATOL)
    trash = d.state_rows(d.max_seqs)
    trash_before = np.asarray(d.cache["ssm"][jnp.asarray(trash)])
    for step in range(8):
        fed = {2: (a[37 + step], 37 + step)}
        if step < 3:
            fed[0] = (b[18 + step], 18 + step)
        got = d.decode(fed)
        np.testing.assert_allclose(got[2], ref_a[37 + step], atol=LOGIT_ATOL)
        if step < 3:
            np.testing.assert_allclose(got[0], ref_b[18 + step], atol=LOGIT_ATOL)
    for row in d.state_rows(1):
        assert not np.asarray(d.cache["ssm"][row]).any(), "an inactive slot's state was touched"
        assert not np.asarray(d.cache["conv"][row]).any(), "an inactive slot's window was touched"
    np.testing.assert_array_equal(np.asarray(d.cache["ssm"][jnp.asarray(trash)]), trash_before)


# ---------------------------------------------------------------- the state, beside the pages

def test_a_slot_used_again_starts_from_zeros_and_padding_writes_the_trash_row(ckpt, loaded):
    model, params = loaded
    a, b = _tokens(3, 20), _tokens(4, 12)
    d = _Driver(model, params)
    d.prefill([(1, a, 0)], 32)
    mine, trash = set(d.state_rows(1)), set(d.state_rows(d.max_seqs))
    assert all(np.asarray(d.cache["ssm"][r]).any() for r in mine)
    before = {k: np.asarray(d.cache[k]) for k in ("ssm", "conv")}
    # the same slot, a new sequence from position 0, beside a padding lane
    got = d.prefill([(1, b, 0), (-1, [], 0)], 16)
    np.testing.assert_allclose(got[0], _ref_logits(ckpt, b)[11], atol=LOGIT_ATOL)
    for k in ("ssm", "conv"):
        after = np.asarray(d.cache[k])
        changed = {int(r) for r in np.flatnonzero(
            (before[k] != after).reshape(after.shape[0], -1).any(axis=1))}
        assert mine <= changed <= mine | trash, (k, changed)


HAND_OFF_FAULTS = {
    "lost_state": lambda c: dict(c, ssm=jnp.zeros_like(c["ssm"])),
    "lost_window": lambda c: dict(c, conv=jnp.zeros_like(c["conv"])),
    "late_window": lambda c: dict(c, conv=_window_off_by_one(c["conv"])),
    "lost_pages": lambda c: dict(c, k=jnp.zeros_like(c["k"]), v=jnp.zeros_like(c["v"])),
}


@pytest.mark.parametrize("fault", sorted(HAND_OFF_FAULTS))
def test_a_broken_hand_off_fails_the_logit_tolerance(ckpt, loaded, fault):
    """Each of the three things a layer hands from prefill to decode (the
    state row, the window row, the pages), lost or late, moves the first
    decoded logits far past LOGIT_ATOL."""
    model, params = loaded
    a = _tokens(3, 30)
    ref = _ref_logits(ckpt, a)
    d = _Driver(model, params)
    d.prefill([(0, a[:24], 0)], 32)
    d.cache = HAND_OFF_FAULTS[fault](d.cache)
    got = d.decode({0: (a[24], 24)})
    assert np.abs(got[0] - ref[24]).max() > 100 * LOGIT_ATOL


CONTROLS = [{"rope": False}, {"decode_position_skew": 1}, {"without": ["key_multiplier"]},
            {"attention": False}, {"mamba": False}, {"state": "lost"}, {"conv_window": "late"},
            {"without": ["ssm_multipliers.3"]}, {"quant": "fp8"}, {"quant": "int8"}]


@pytest.mark.parametrize("control", CONTROLS, ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_the_references_controls_move_the_decoded_rows(ckpt, control):
    """What `benchmark/tests/test_controls_falcon_h1.py` asks of the reference
    at full width, at this size: each control moves the rows a served run
    would decode (from the hand-off on), and those that are faults of the
    hand-off leave the rows before it alone."""
    a = _tokens(5, 30)
    plain = _ref_logits(ckpt, a, first=0)
    got = _ref_logits(ckpt, a, control, first=0)
    assert np.abs(got[24:] - plain[24:]).max() > 100 * LOGIT_ATOL
    if set(control) & {"state", "conv_window", "decode_position_skew"}:
        # the hand-off is after position 0 (`rows` = (0, 30)): row 0 is before it
        np.testing.assert_allclose(got[0], plain[0], atol=1e-6)


def test_the_state_lost_at_the_hand_off_is_what_the_program_loses(ckpt, loaded):
    """The reference's control `state: lost` IS the program's fault: a decode
    that starts from a state row of zeros reads the same logits."""
    model, params = loaded
    a = _tokens(6, 30)
    want = reference.forward_logits(ckpt, [a], [(23, 30)], {"state": "lost"})[0]
    d = _Driver(model, params)
    d.prefill([(0, a[:24], 0)], 32)
    d.cache = HAND_OFF_FAULTS["lost_state"](d.cache)
    for step in range(3):
        got = d.decode({0: (a[24 + step], 24 + step)})
        np.testing.assert_allclose(got[0], want[1 + step], atol=LOGIT_ATOL)


# ---------------------------------------------------------------- the multipliers and the branches

MULTIPLIERS = [(key, i) for key in MULTIPLIER_KEYS
               for i in (range(len(HF_TINY[key])) if isinstance(HF_TINY[key], list) else [None])]


@pytest.mark.parametrize("key, index", MULTIPLIERS, ids=[f"{k}{'' if i is None else f'.{i}'}" for k, i in MULTIPLIERS])
def test_every_multiplier_is_applied(ckpt, loaded, key, index):
    """Fourteen numbers under nine keys (`ssm_multipliers` has one a segment
    of the Mamba projection, z, x, B, C, dt; `mlp_multipliers` one for the
    gate's product and one for the down product): the program with one of them
    left out (1) is another model than the reference with it, and the
    reference with the same one left out is that model."""
    model, params = loaded
    a = _tokens(7, 24)
    value = 1.0 if index is None else tuple(
        1.0 if j == index else v for j, v in enumerate(getattr(model.config, key)))
    without = FalconH1Model(replace(model.config, **{key: value}))
    got = _full_forward(without, params, a, T=32)
    assert np.abs(got - _ref_logits(ckpt, a)).max() > 100 * LOGIT_ATOL
    name = key if index is None else f"{key}.{index}"
    np.testing.assert_allclose(got, _ref_logits(ckpt, a, {"without": [name]}), atol=LOGIT_ATOL)


@pytest.mark.parametrize("branch", ["attention", "mamba"])
def test_both_mixers_reach_the_logits(ckpt, loaded, branch):
    """A block without its attention mixer, or without its Mamba mixer, is
    another model by far more than the tolerance."""
    model, params = loaded
    a = _tokens(8, 24)
    got = _full_forward(model, params, a, T=32)
    assert np.abs(got - _ref_logits(ckpt, a, {branch: False})).max() > 1000 * LOGIT_ATOL


# ---------------------------------------------------------------- the configuration

@pytest.mark.parametrize("key, value, why", [
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
    ("mamba_d_ssm", 48, "mamba_d_ssm"),
    ("ssm_multipliers", [1.0, 1.0], "five segments"),
])
def test_from_hf_config_refuses_by_name(key, value, why):
    with pytest.raises(ValueError, match=why):
        FalconH1Config.from_hf_config({**HF_TINY, key: value})


def test_the_published_keys_are_read():
    """The benchmark's configuration file is the catalog row's `config` under
    the same keys, cut in depth alone, and every width and multiplier of it
    reaches the model's config."""
    conf = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-d6.json").read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.open() if '"Falcon-H1-34B-Instruct"' in line)
        changed = {k for k, v in row["config"].items() if conf.get(k) != v}
        assert changed == {"num_hidden_layers"} == set(conf["reduced"])
        assert conf["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
        assert conf["source"] == row["source_url"]
    c = FalconH1Config.from_hf_config(conf)
    assert (c.hidden_size, c.intermediate_size, c.vocab_size, c.num_layers) == (5120, 21504, 261120, 6)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.rope_theta) == (20, 4, 128, 1e11)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_n_groups, c.mamba_d_conv) == (32, 128, 256, 2, 4)
    assert (c.mamba_inner, c.conv_dim, c.in_proj_width) == (4096, 5120, 9248)
    for key in MULTIPLIER_KEYS:
        want = conf[key]
        assert getattr(c, key) == (tuple(want) if isinstance(want, list) else want)
    model = FalconH1Model(c)
    # 4.19 MB of state and 31 kB of window a slot and layer; 12 KiB of KV a token
    assert model.state_bytes(96) == 6 * 97 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    assert model.kv_page_bytes(16) == 16 * 6 * 2 * 4 * 128 * 2 == 192 * 1024
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(model.init_params, jax.random.key(0))))
    assert round(n / 1e6, 1) == 5254.6


def test_tiny_parallel_is_a_tiny_family():
    model, params = load_model('tiny-parallel:{"num_layers": 2}')
    assert isinstance(model, FalconH1Model) and model.config.num_layers == 2
    assert model.config.num_heads // model.config.num_kv_heads == 5 and model.config.mamba_n_groups == 2
    widths = [params["layers"][k].shape for k in ("in_z", "in_xbc", "in_dt")]
    assert widths == [(2, 64, 32), (2, 64, 96), (2, 64, 4)] and model.config.in_proj_width == 132


# ---------------------------------------------------------------- the shared kernels at this geometry

def test_five_query_heads_a_kv_head_in_the_unfolded_kernels(monkeypatch):
    """20 query over 4 kv heads of 128 (the first group that is no power of
    two) through the dispatch, interpret mode: the prefill and decode kernels
    `qwen2.5-3b` takes, against the gather."""
    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    monkeypatch.setattr(attn_ops, "_logged_paths", set())
    seen = []
    monkeypatch.setattr(attn_ops, "_log_path", lambda op, path, why: seen.append((op, path)))
    rng = np.random.default_rng(11)
    Hq, Hkv, D, ps, pages, T, B = 20, 4, 128, 16, 24, 128, 2
    pool = lambda: jnp.asarray(rng.normal(0, 1, (pages, ps, Hkv, D)), jnp.float32)
    k_pool, v_pool = pool(), pool()
    table = jnp.asarray(1 + np.arange(16), jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (T, Hq, D)), jnp.float32)
    pos = jnp.arange(70, 70 + T, dtype=jnp.int32)
    got = attn_ops.dispatch_paged_prefill_attention(q, k_pool, v_pool, table, pos)
    want = attn_ops.paged_prefill_attention(q, k_pool, v_pool, table, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    qd = q[:B]
    tables = jnp.stack([table, table[::-1]])
    at = jnp.asarray([200, 57], jnp.int32)
    got = attn_ops.dispatch_paged_decode_attention(qd, k_pool, v_pool, tables, at)
    want = attn_ops.paged_decode_attention(qd, k_pool, v_pool, tables, at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert dict(seen)["prefill"].startswith("pallas:") and "block_q=128" in dict(seen)["prefill"], seen
    assert dict(seen)["decode"].startswith("pallas:paged_decode_attention_pallas_lookahead"), seen


# ---------------------------------------------------------------- through the engine

#: logprobs of the tokens the engine chose, float32 on both sides (see
#: LOGIT_ATOL: a logprob is a logit minus a log-sum-exp of logits)
LOGPROB_ATOL = 1e-4


ENGINE_CASES = {
    # a prompt of three chunks, two decode windows
    "chunked": dict(prompts=[_tokens(11, 75)], max_tokens=8, engine={}),
    # four sequences through two slots: their chunks share packed calls, and
    # each slot's state and window are used again by a sequence that must not
    # see them
    "slots_reused": dict(prompts=[_tokens(14 + i, 12 + 9 * i) for i in range(4)], max_tokens=7,
                         engine={}),
    # 7 usable pages for two sequences that need 4 each: the younger is
    # preempted, its state dropped, and it resumes by recomputing
    "preempted": dict(prompts=[_tokens(20, 30), _tokens(21, 30)], max_tokens=30,
                      engine=dict(num_pages=8, max_model_len=64, watermark=0.0)),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_engine_matches_the_reference(ckpt, case):
    """Through the scheduler, runner, page table, state slots and sampler."""
    spec = ENGINE_CASES[case]

    async def body():
        eng = AsyncJaxEngine(EngineConfig(**{**dict(
            model_id=str(ckpt), num_pages=64, max_seqs=2, max_model_len=128,
            prefill_buckets=(16, 32), decode_steps=4), **spec["engine"]}))
        await eng.start()
        try:
            results = await asyncio.gather(*[
                _generate(eng, f"{case}-{i}", p, spec["max_tokens"])
                for i, p in enumerate(spec["prompts"])
            ])
            return (results, eng.scheduler.preempt_count, eng.resource_snapshot(),
                    eng.render_stage_metrics())
        finally:
            await eng.shutdown()

    results, preempted, snap, text = asyncio.run(body())
    probes = [{"tokens": list(p) + toks, "prompt_len": len(p)}
              for p, (toks, _) in zip(spec["prompts"], results)]
    for (toks, lps), want in zip(results, reference.teacher_forced_logprobs(ckpt, probes)):
        assert len(toks) == len(want) == spec["max_tokens"]
        np.testing.assert_allclose(lps, want, atol=LOGPROB_ATOL)
    if case == "preempted":
        assert preempted >= 1
    assert snap["state_slots_total"] == 2 and snap["state_slots_active"] == 0
    # 3 layers x (2 slots + a trash row) x (4 x 8 x 16 of state + 3 x 96 of window) float32
    row = (4 * 8 * 16 + 3 * 96) * 4
    assert snap["hbm_state_bytes"] == 3 * 3 * row
    assert snap["prefix_cache_hit_blocks"] == 0
    for family in ('dynamo_engine_state_slots{state="total"} 2',
                   f'dynamo_engine_state_bytes{{cache="state"}} {3 * 3 * row}',
                   f'dynamo_engine_state_bytes{{cache="state_per_slot"}} {3 * row}',
                   f'dynamo_engine_state_bytes{{cache="pages"}} {snap["kv_pool_bytes_total"]}'):
        assert family in text, family


def test_a_model_with_no_recurrent_layers_reports_no_state_bytes():
    async def body():
        eng = AsyncJaxEngine(EngineConfig(model_id="tiny", num_pages=16, max_seqs=2, max_model_len=64))
        await eng.start()
        try:
            return eng.render_stage_metrics()
        finally:
            await eng.shutdown()

    text = asyncio.run(body())
    assert 'dynamo_engine_state_bytes{cache="state"} 0' in text
    assert 'dynamo_engine_state_bytes{cache="state_per_slot"} 0' in text


# ---------------------------------------------------------------- the benchmark's plan, at small width

#: the published multipliers (`benchmark/configs/falcon-h1-34b-d6.json`) over small widths
PUBLISHED = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-d6.json").read_text())
HF_PLAN = {
    **HF_TINY, **{k: PUBLISHED[k] for k in MULTIPLIER_KEYS}, "rope_theta": PUBLISHED["rope_theta"],
    "hidden_size": 256, "vocab_size": 512, "intermediate_size": 512,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 32,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_ssm": 128, "mamba_d_state": 32,
}


def _written_as_the_plan_says(out: Path, kinds: dict, seed: int = 7) -> Path:
    """What `benchmark/checkpoint.py` writes for this plan (normal 0.02 or
    ones), in float32, with `kinds` {name's end: kind} in place of the plan's."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, kind in plan.tensor_plan(HF_PLAN):
        kind = next((k for end, k in kinds.items() if name.endswith(end)), kind)
        draw = rng.normal(0.0, 0.02, shape)  # drawn either way: the other tensors stay the same
        tensors[name] = (np.ones(shape) if kind == "ones" else draw).astype(np.float32)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(HF_PLAN))
    save_file(tensors, str(out / "model.safetensors"))
    return out


def _reads(ckpt: Path, control: dict) -> float:
    """Worst |logprob - reference's own| over 3 probes x 8 tokens, as `run.py` reads it."""
    rng = np.random.default_rng(3)
    probes = [{"tokens": [int(t) for t in rng.integers(3, 512, n + 8)], "prompt_len": n}
              for n in (40, 90, 150)]
    want = reference.teacher_forced_logprobs(ckpt, probes)
    got = reference.teacher_forced_logprobs(ckpt, probes, control)
    return max(abs(a - b) for g, w in zip(got, want) for a, b in zip(g, w))


@pytest.fixture(scope="module")
def plan_ckpts(tmp_path_factory):
    root = tmp_path_factory.mktemp("falcon_h1_plan")
    variants = {"plan": {}, "k_normal": {"k_proj.weight": "normal"},
                "A_log_ones": {"mamba.A_log": "ones"}, "conv_normal": {"conv1d.weight": "normal"}}
    return {name: _written_as_the_plan_says(root / name, kinds) for name, kinds in variants.items()}


def test_the_plan_under_the_published_multipliers_leaves_logits_a_hundredth_wide(plan_ckpts):
    """`lm_head_multiplier` 2^-7 over a 0.02-normal head: logits 0.02 x
    sqrt(hidden) / 128 wide (0.0025 here, 0.011 at 5120), so a tolerance in the
    tenths would pass any program."""
    logits = _ref_logits(plan_ckpts["plan"], _tokens(9, 64))
    assert logits.std() == pytest.approx(0.02 * np.sqrt(256) / 128, rel=0.2)
    assert np.abs(reference.log_softmax(logits) + np.log(512)).max() < 0.02


#: (control, the writer's other kind for one tensor, how many times larger the
#: control has to read under the plan's choice); measured at this width (PR 45,
#: CPU): rope left out 1.65e-6 against 3.7e-8 with `k_proj` normal, positions
#: off by one 4.6e-7 against 1.7e-8, the lost state 1.48e-7 against 1.4e-8 with
#: `A_log` ones, the late window 1.47e-6 against 4.8e-8 with the taps normal.
#: `mamba.D` is not here: its share of y turns on the widths (at 256 the skip
#: term leads under either kind), and was measured at full width alone
#: (`benchmark/checkpoints/falcon_h1.py`: 0.0084 against 0.0013)
PLAN_CHOICES = [
    ({"rope": False}, "k_normal", 20), ({"decode_position_skew": 1}, "k_normal", 15),
    ({"state": "lost"}, "A_log_ones", 3), ({"conv_window": "late"}, "conv_normal", 10),
]


@pytest.mark.parametrize("control, other, times", PLAN_CHOICES,
                         ids=[f"{next(iter(c))}-against-{o}" for c, o, _ in PLAN_CHOICES])
def test_the_plan_under_the_published_multipliers_keeps_a_mechanism_visible(plan_ckpts, control, other, times):
    """Each kind the plan's docstring argues for, measured: the control it is
    meant to keep visible reads `times` larger under the plan's choice than
    under the writer's other kind."""
    ours, theirs = _reads(plan_ckpts["plan"], control), _reads(plan_ckpts[other], control)
    assert ours > times * theirs, (control, other, ours, theirs)
