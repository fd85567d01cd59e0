"""Cohere2-MoE (models/cohere2_moe.py): a parallel attention + expert block
under one LayerNorm, sliding-window layers with rope by interleaved pairs
beside full NoPE layers, a share of the routed experts held, shared experts
averaged. Model-level: hand-made page tables, one per attention layer.

Everything is compared with the plain reference the benchmark uses
(`benchmark/reference/cohere2_moe.py`: numpy float32, no cache), which reads
the same checkpoint files the program loads. The engine's side (layer groups
in the allocator and the scheduler) is `tests/test_layer_groups.py`.
"""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeModel
from dynamo_tpu.models.registry import load_model
from dynamo_tpu.ops.norms import layer_norm
from dynamo_tpu.ops.rotary import apply_rope, apply_rope_pairs

ROOT = Path(__file__).resolve().parents[1]


def bench_module(kind: str, name: str):
    path = ROOT / "benchmark" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = bench_module("reference", "cohere2_moe")
plan = bench_module("checkpoints", "cohere2_moe")

#: config.json keys of a small Cohere2-MoE: window layers before and after a
#: full one, a window of 32 that a context of 100 passes three times, half of
#: 8 experts held, float32 so that the tolerance is float32 rounding
HF_TINY = {
    "architectures": ["Cohere2MoeForCausalLM"], "model_type": "cohere2_moe",
    "torch_dtype": "float32", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "sliding_window": 32, "rope_theta": 50000, "position_embedding_type": "rope_gptj",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "moe_routed_over": 8, "moe_expert_offset": 0, "num_experts_per_tok": 3,
    "num_shared_experts": 2, "intermediate_size": 48, "layer_norm_eps": 1e-5,
    "rms_norm_eps": None, "logit_scale": 1, "tie_word_embeddings": True,
    "use_parallel_block": True, "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average", "first_k_dense_replace": 0,
}

#: float32 on both sides: summation order only (measured 2e-5 at most on logits
#: of size ~2); a wrong mask, rope, share or norm moves logits by 1e-2 to 1
LOGIT_ATOL = 1e-4


def write_checkpoint(out: Path, hf: dict, seed: int) -> Path:
    """The plan's tensors in float32 at a scale where every layer matters
    (matrices at 1/sqrt(fan_in), norms near 1): a checkpoint only tests write."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, kind in plan.tensor_plan(hf):
        if kind == "ones":
            t = 1.0 + rng.normal(0.0, 0.1, shape)
        else:
            t = rng.normal(0.0, 1.0 / np.sqrt(shape[-1]), shape)
        tensors[name] = t.astype(np.float32)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(hf))
    save_file(tensors, str(out / "model.safetensors"))
    return out


def tokens(seed: int, n: int) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(3, HF_TINY["vocab_size"], n)]


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return write_checkpoint(tmp_path_factory.mktemp("cohere2_moe") / "ckpt", HF_TINY, 37)


@pytest.fixture(scope="module")
def loaded(ckpt):
    return load_model(str(ckpt))


def ref_logits(ckpt, seq, options=None):
    return reference.forward_logits(ckpt, [seq], [(0, len(seq))], options)[0]


# ---------------------------------------------------------------- the ops


def test_rope_by_pairs_is_the_reference_and_not_rope_by_halves():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((9, 3, 16)).astype(np.float32)
    pos = np.arange(40, 49)
    pairs = np.asarray(apply_rope_pairs(jnp.asarray(x), jnp.asarray(pos), 50000.0))
    np.testing.assert_allclose(pairs, reference.rope(x, pos, 50000.0, "pairs"), atol=1e-5)
    halves = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), 50000.0))
    np.testing.assert_allclose(halves, reference.rope(x, pos, 50000.0, "halves"), atol=1e-5)
    assert np.abs(pairs - halves).max() > 0.5
    # a rotation: norms of each pair are kept, and position 0 is the identity
    np.testing.assert_allclose(
        np.asarray(apply_rope_pairs(jnp.asarray(x), jnp.zeros(9, jnp.int32), 50000.0)), x, atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(pairs, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_layer_norm_centres_and_has_no_bias():
    rng = np.random.default_rng(1)
    x = (3.0 + rng.standard_normal((5, 64))).astype(np.float32)
    w = (1.0 + 0.1 * rng.standard_normal(64)).astype(np.float32)
    got = np.asarray(layer_norm(jnp.asarray(x), jnp.asarray(w), 1e-5))
    np.testing.assert_allclose(got, reference.layer_norm(x, w, 1e-5), atol=1e-5)
    np.testing.assert_allclose((got / w).mean(axis=-1), 0.0, atol=1e-5)


# ---------------------------------------------------------------- the model, on logits


class Driver:
    """The model's own prefill and decode functions over a hand-made pool: a
    page table per attention layer, each layer's pages its own."""

    def __init__(self, model, params, max_seqs=2, width=8, page_size=16):
        self.model, self.params, self.ps = model, params, page_size
        self.T, self.width, self.max_seqs = model.kv_tables, width, max_seqs
        self.cache = {**model.init_kv_cache(1 + max_seqs * self.T * width, page_size),
                      **model.init_state_cache(max_seqs)}
        ids = 1 + np.arange(max_seqs * self.T * width, dtype=np.int32)
        self.tables = ids.reshape(max_seqs, self.T * width)  # table-major rows

    def prefill(self, lanes, T):
        N = len(lanes)
        toks, pos = np.zeros((N, T), np.int32), np.zeros((N, T), np.int32)
        valid, last = np.zeros((N, T), bool), np.zeros(N, np.int32)
        pts = np.zeros((N, self.T * self.width), np.int32)
        for j, (slot, seq, start) in enumerate(lanes):
            toks[j, :len(seq)], pos[j], valid[j, :len(seq)] = seq, start + np.arange(T), True
            last[j] = len(seq) - 1
            if slot >= 0:
                pts[j] = self.tables[slot]
        logits, self.cache = jax.jit(self.model.prefill_packed)(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(pts),
            jnp.asarray(valid), jnp.asarray(last))
        return np.asarray(logits)

    def decode(self, fed: dict):
        B = self.max_seqs
        toks, pos, act = np.zeros(B, np.int32), np.zeros(B, np.int32), np.zeros(B, bool)
        for slot, (t, p) in fed.items():
            toks[slot], pos[slot], act[slot] = t, p, True
        logits, self.cache = jax.jit(self.model.decode)(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(self.tables), jnp.asarray(act))
        return np.asarray(logits)


def test_chunked_packed_prefill_and_decode_match_the_reference_past_the_window(ckpt, loaded):
    """A context of 100 under a window of 32: chunks of 32 (each later chunk
    sees keys of the chunk before it), a packed call with another sequence and
    a padding lane, then decode steps; every logit against the reference's one
    forward pass."""
    model, params = loaded
    a, b = tokens(1, 110), tokens(2, 21)
    ra, rb = ref_logits(ckpt, a), ref_logits(ckpt, b)
    d = Driver(model, params)
    for start in (0, 32, 64):
        got = d.prefill([(1, a[start:start + 32], start)], 32)
        np.testing.assert_allclose(got[0], ra[start + 31], atol=LOGIT_ATOL)
    got = d.prefill([(1, a[96:100], 96), (0, b[:18], 0), (-1, [], 0)], 32)
    np.testing.assert_allclose(got[0], ra[99], atol=LOGIT_ATOL)
    np.testing.assert_allclose(got[1], rb[17], atol=LOGIT_ATOL)
    for step in range(10):
        fed = {1: (a[100 + step], 100 + step)}
        if step < 3:
            fed[0] = (b[18 + step], 18 + step)
        got = d.decode(fed)
        np.testing.assert_allclose(got[1], ra[100 + step], atol=LOGIT_ATOL)
        if step < 3:
            np.testing.assert_allclose(got[0], rb[18 + step], atol=LOGIT_ATOL)
    counts = np.asarray(d.cache["moe_counts"])
    assert 0 < counts.sum() < 13 * 3 * 4  # 13 rows x top-3 x 4 layers, half held
    assert 0 < int(d.cache["moe_touched"][0]) <= counts.sum()


def test_pages_behind_the_window_are_never_read(ckpt, loaded):
    """What the engine does to a window layer's table: entries wholly behind
    the window point at the null page, and the logits do not move; the same
    done to the FULL layer's table moves them."""
    model, params = loaded
    a = tokens(3, 104)
    ra = ref_logits(ckpt, a)
    d = Driver(model, params, width=8)
    for start in (0, 32, 64):
        d.prefill([(0, a[start:start + 32], start)], 32)
    kept = d.tables.copy()
    tables = d.tables.reshape(d.max_seqs, d.T, d.width)
    behind = (96 - 32 + 1) // 16  # pages every token of which is over W behind position 96
    window_layers = [i for i, k in enumerate(HF_TINY["layer_types"]) if k == "sliding_attention"]
    tables[0, window_layers, :behind] = 0
    got = d.prefill([(0, a[96:100], 96)], 32)
    np.testing.assert_allclose(got[0], ra[99], atol=LOGIT_ATOL)
    got = d.decode({0: (a[100], 100)})
    np.testing.assert_allclose(got[0], ra[100], atol=LOGIT_ATOL)
    tables[0, 2, :behind] = 0  # the full layer
    got = d.decode({0: (a[101], 101)})
    assert np.abs(got[0] - ra[101]).max() > 100 * LOGIT_ATOL
    d.tables[...] = kept


CONTROLS = {
    "rope_by_halves": {"rope": "halves"},
    "no_window_mask": {"window": False},
    "shared_experts_summed": {"shared": "sum"},
    "a_held_expert_zeroed": {"zero_expert": 1},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_wrong_model_fails_the_logit_tolerance(ckpt, loaded, control):
    """Each reading the reference could have got wrong moves the logits far
    past LOGIT_ATOL, so the agreement above is of THIS model."""
    a = tokens(4, 80)
    wrong = ref_logits(ckpt, a, CONTROLS[control])
    assert np.abs(wrong - ref_logits(ckpt, a)).max() > 100 * LOGIT_ATOL


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip's expert layer = its held experts' part of the routed sum plus
    every shared expert. Over the shares that cover all experts, the routed
    parts plus the shared experts counted once are the uncut layer."""
    cfg = Cohere2MoeConfig.tiny(num_experts=8, moe_routed_over=8)
    whole = Cohere2MoeModel(cfg)
    params = whole.init_params(jax.random.key(5))
    lp = params["layers"][0]
    n = jax.random.normal(jax.random.key(6), (11, cfg.hidden_size), jnp.float32)
    want, counts = whole._experts(lp, n)
    assert int(counts.sum()) == 11 * cfg.num_experts_per_tok
    zero_shared = dict(lp, shared_down=jnp.zeros_like(lp["shared_down"]))
    shared_only, _ = whole._experts(dict(lp, w_down=jnp.zeros_like(lp["w_down"])), n)
    total = shared_only
    for share in range(4):  # 4 shares of 2 experts
        held = slice(2 * share, 2 * share + 2)
        part = Cohere2MoeModel(Cohere2MoeConfig.tiny(
            num_experts=2, moe_routed_over=8, moe_expert_offset=2 * share))
        got, c = part._experts(
            dict(zero_shared, w_gate=lp["w_gate"][held], w_up=lp["w_up"][held],
                 w_down=lp["w_down"][held]), n)
        np.testing.assert_array_equal(c, counts[held])
        total = total + got
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_layer_groups_follow_layer_types(loaded):
    model, _ = loaded
    groups = model.layer_groups
    assert [(g.name, g.tables, g.window) for g in groups] == [
        ("window", (0, 1, 3), 32), ("full", (2,), 0)]
    assert model.kv_tables == 4
    assert model.kv_page_bytes(16) == 2 * 16 * 2 * 16 * 4  # K and V, one layer, float32


@pytest.mark.parametrize("key,value", [
    ("use_parallel_block", False), ("expert_selection_fn", "softmax"),
    ("position_embedding_type", "rope_neox"), ("first_k_dense_replace", 1),
    ("shared_expert_combination_strategy", "sum"),
])
def test_a_config_the_model_does_not_implement_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        Cohere2MoeConfig.from_hf_config({**HF_TINY, key: value})
