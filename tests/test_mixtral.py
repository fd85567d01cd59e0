"""Mixtral MoE: paged forward vs a naive dense-dispatch reference + ep sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.models.mixtral import MixtralConfig, MixtralModel
from dynamo_tpu.ops.moe import moe_block, topk_routing
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rotary import apply_rope


# compile-heavy JAX e2e: runs in the full matrix, not the <2-min default tier
pytestmark = pytest.mark.slow

PAGE_SIZE = 4
NUM_PAGES = 16
PROMPT = np.array([5, 9, 2, 77, 31, 8, 100], dtype=np.int32)
PAGE_TABLE = np.array([3, 5, 7, 0, 0, 0, 0, 0], dtype=np.int32)


def naive_moe(hidden, router_w, w_gate, w_up, w_down, k):
    """Per-token loop over selected experts — the semantic reference."""
    T = hidden.shape[0]
    logits = hidden.astype(jnp.float32) @ router_w.astype(jnp.float32)
    weights, idx = topk_routing(logits, k)
    out = jnp.zeros_like(hidden, dtype=jnp.float32)
    for t in range(T):
        acc = jnp.zeros(hidden.shape[1], jnp.float32)
        for j in range(k):
            e = int(idx[t, j])
            x = hidden[t].astype(w_gate.dtype)
            g = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
            acc += float(weights[t, j]) * (g @ w_down[e]).astype(jnp.float32)
        out = out.at[t].set(acc)
    return out.astype(hidden.dtype)


def test_moe_block_matches_naive():
    rng = np.random.default_rng(0)
    T, D, F, E, K = 10, 16, 32, 4, 2
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((D, E)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    expected = naive_moe(h, router, wg, wu, wd, K)
    got = moe_block(h, router, wg, wu, wd, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-4)


def test_moe_collisions_drop_nothing():
    """A uniform router sends every token to the same two experts: 64
    assignments on 2 of 4 experts, where the old capacity rule
    (ceil(T*K/E*2) = 32 an expert) kept half. The dispatch keeps them all."""
    rng = np.random.default_rng(1)
    T, D, F, E, K = 32, 16, 32, 4, 2
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    router = jnp.zeros((D, E), jnp.float32)  # uniform router -> heavy collisions
    wg = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    out = moe_block(h, router, wg, wu, wd, K)
    expected = naive_moe(h, router, wg, wu, wd, K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4)


@pytest.fixture(scope="module")
def setup():
    cfg = MixtralConfig.tiny_moe()
    model = MixtralModel(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def naive_forward_moe(cfg, params, tokens):
    T = len(tokens)
    pos = jnp.arange(T)
    h = params["embed"][jnp.array(tokens)].astype(cfg.dtype)
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda x: x[l], params["layers"])
        x = rms_norm(h, lp["input_norm"], cfg.rms_norm_eps)
        q = apply_rope((x @ lp["wq"]).reshape(T, cfg.num_heads, cfg.head_dim), pos, cfg.rope_theta)
        k = apply_rope((x @ lp["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim), pos, cfg.rope_theta)
        v = (x @ lp["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
        g = cfg.num_heads // cfg.num_kv_heads
        kr = jnp.repeat(k, g, axis=1)
        vr = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), kr.astype(jnp.float32))
        s = s / np.sqrt(cfg.head_dim)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), vr.astype(jnp.float32)).astype(cfg.dtype)
        h = h + a.reshape(T, -1) @ lp["wo"]
        x = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
        h = h + naive_moe(x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                          cfg.num_experts_per_tok)
    x = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"] if cfg.tie_word_embeddings else params["lm_head"]
    return jnp.einsum("td,vd->tv", x.astype(jnp.float32), head.astype(jnp.float32))


def test_mixtral_paged_prefill_matches_naive(setup):
    cfg, model, params = setup
    ref = naive_forward_moe(cfg, params, PROMPT)[-1]
    Tn, T_pad = len(PROMPT), 8
    tokens = np.zeros(T_pad, np.int32)
    tokens[:Tn] = PROMPT
    positions = np.arange(T_pad, dtype=np.int32)
    kv = model.init_kv_cache(NUM_PAGES, PAGE_SIZE)
    logits, _ = model.prefill(
        params, kv, jnp.array(tokens), jnp.array(positions),
        jnp.array(PAGE_TABLE), jnp.array(positions < Tn), jnp.array(Tn - 1),
    )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=2e-4)


def test_mixtral_ep_sharded_prefill(setup):
    """Experts sharded over ep=4 x tp=2 mesh produce identical logits."""
    cfg, model, params = setup
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("ep", "tp"))
    params_sh = jax.device_put(params, model.param_shardings(mesh))
    kv = jax.device_put(
        model.init_kv_cache(NUM_PAGES, PAGE_SIZE), model.kv_cache_sharding(mesh)
    )
    Tn, T_pad = len(PROMPT), 8
    tokens = np.zeros(T_pad, np.int32)
    tokens[:Tn] = PROMPT
    positions = np.arange(T_pad, dtype=np.int32)
    logits_sh, _ = jax.jit(model.prefill)(
        params_sh, kv, jnp.array(tokens), jnp.array(positions),
        jnp.array(PAGE_TABLE), jnp.array(positions < Tn), jnp.array(Tn - 1),
    )
    ref = naive_forward_moe(cfg, params, PROMPT)[-1]
    np.testing.assert_allclose(np.asarray(logits_sh), np.asarray(ref), atol=2e-4)


def test_mixtral_in_engine():
    """MixtralModel through the full async engine (registry dispatch)."""
    import asyncio

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    from tests.test_engine import tiny_engine_config

    cfg = tiny_engine_config(model_id="tiny-moe")
    eng = AsyncJaxEngine(cfg)

    async def body():
        await eng.start()
        req = EngineRequest(
            request_id="m1",
            token_ids=[5, 9, 2, 77],
            sampling=SamplingParams(temperature=0.0, max_tokens=4),
        )
        toks = []
        async for out in eng.generate(req):
            if out.token is not None:
                toks.append(out.token)
        await eng.shutdown()
        return toks

    toks = asyncio.run(body())
    assert len(toks) == 4
