"""Pallas paged decode attention (interpret mode on CPU) vs the pure-JAX
reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_decode_attention
from dynamo_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


# compile-heavy JAX e2e: runs in the full matrix, not the <2-min default tier
pytestmark = pytest.mark.slow


def make_case(B=3, Hq=4, Hkv=2, D=16, P=16, ps=4, max_pages=6, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    # distinct pages per sequence, lengths straddling page boundaries
    pt = np.zeros((B, max_pages), np.int32)
    for b in range(B):
        pt[b] = rng.choice(np.arange(1, P), size=max_pages, replace=False)
    positions = jnp.asarray([3, 9, 14], jnp.int32)[:B]  # lengths 4, 10, 15
    return q, k, v, jnp.asarray(pt), positions


def test_pallas_matches_reference():
    q, k, v, pt, pos = make_case()
    ref = paged_decode_attention(q, k, v, pt, pos)
    got = paged_decode_attention_pallas(q, k, v, pt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_pallas_single_token_context():
    q, k, v, pt, _ = make_case(B=1)
    pos = jnp.asarray([0], jnp.int32)
    ref = paged_decode_attention(q, k, v, pt, pos)
    got = paged_decode_attention_pallas(q, k, v, pt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_pallas_gqa_and_mha():
    for Hq, Hkv in [(8, 8), (8, 2), (4, 1)]:
        q, k, v, pt, pos = make_case(Hq=Hq, Hkv=Hkv, seed=Hq * 10 + Hkv)
        ref = paged_decode_attention(q, k, v, pt, pos)
        got = paged_decode_attention_pallas(q, k, v, pt, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, err_msg=f"Hq={Hq} Hkv={Hkv}"
        )


def test_pallas_tp_shard_map():
    """dispatch under a tp=2 mesh runs the kernel via shard_map (heads split
    across devices, no collectives) and matches the unsharded reference."""
    from jax.sharding import Mesh

    from dynamo_tpu.ops.attention import dispatch_paged_decode_attention

    q, k, v, pt, pos = make_case(Hq=8, Hkv=2)
    ref = paged_decode_attention(q, k, v, pt, pos)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    got = jax.jit(
        lambda *a: dispatch_paged_decode_attention(*a, mesh=mesh)
    )(q, k, v, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_engine_tp2_uses_pallas_under_shard_map(monkeypatch):
    """A tp=2 engine with the Pallas kernel forced on generates the same
    greedy tokens as tp=1 (kernel correctness through the whole stack)."""
    import asyncio

    from tests.test_engine import tiny_engine_config

    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    async def body():
        eng = AsyncJaxEngine(tiny_engine_config(tp=2))
        await eng.start()
        req = EngineRequest(
            request_id="tp2",
            token_ids=[5, 9, 2, 77, 31],
            sampling=SamplingParams(temperature=0.0, max_tokens=6),
        )
        toks = []
        async for out in eng.generate(req):
            if out.token is not None:
                toks.append(out.token)
        await eng.shutdown()
        return toks

    got = asyncio.run(body())

    monkeypatch.setenv("DYNTPU_PALLAS", "0")

    async def ref_body():
        eng = AsyncJaxEngine(tiny_engine_config(tp=1))
        await eng.start()
        req = EngineRequest(
            request_id="ref",
            token_ids=[5, 9, 2, 77, 31],
            sampling=SamplingParams(temperature=0.0, max_tokens=6),
        )
        toks = []
        async for out in eng.generate(req):
            if out.token is not None:
                toks.append(out.token)
        await eng.shutdown()
        return toks

    ref = asyncio.run(ref_body())
    assert got == ref, f"tp2 pallas {got} != tp1 reference {ref}"


# ---------------- chunked-prefill flash kernel ----------------

from dynamo_tpu.ops.attention import paged_prefill_attention
from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention_pallas


def make_prefill_case(T=128, Hq=4, Hkv=2, D=16, P=48, ps=4, max_pages=40, start=0, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    pt = jnp.asarray(rng.choice(np.arange(1, P), size=max_pages, replace=False), jnp.int32)
    positions = jnp.asarray(start + np.arange(T), jnp.int32)
    return q, k, v, pt, positions


def test_prefill_pallas_matches_reference():
    q, k, v, pt, pos = make_prefill_case()
    ref = paged_prefill_attention(q, k, v, pt, pos)
    got = paged_prefill_attention_pallas(q, k, v, pt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_prefill_pallas_cached_prefix_chunk():
    """Chunk starting mid-sequence (cached prefix skipped): attends over all
    earlier pages + its own rows."""
    q, k, v, pt, pos = make_prefill_case(T=128, start=57, seed=3)
    ref = paged_prefill_attention(q, k, v, pt, pos)
    got = paged_prefill_attention_pallas(q, k, v, pt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_prefill_pallas_multi_block_and_gqa():
    for T, Hq, Hkv in [(256, 8, 2), (128, 4, 4), (384, 8, 1)]:
        q, k, v, pt, pos = make_prefill_case(
            T=T, Hq=Hq, Hkv=Hkv, P=128, max_pages=100, seed=T + Hq
        )
        ref = paged_prefill_attention(q, k, v, pt, pos)
        got = paged_prefill_attention_pallas(q, k, v, pt, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_prefill_dispatch_gates_on_block_divisibility():
    from dynamo_tpu.ops.attention import use_pallas_prefill

    assert not use_pallas_prefill(128, 96)  # not block-divisible: XLA path


def test_prefill_dispatch_tp2_shard_map(monkeypatch):
    """dispatch_paged_prefill_attention under a tp=2 mesh (kernel forced on,
    interpret mode) matches the unsharded XLA reference."""
    from jax.sharding import Mesh

    from dynamo_tpu.ops.attention import dispatch_paged_prefill_attention

    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    q, k, v, pt, pos = make_prefill_case(T=128, Hq=8, Hkv=2, P=64, max_pages=40, seed=11)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    ref = paged_prefill_attention(q, k, v, pt, pos)
    got = jax.jit(
        lambda *a: dispatch_paged_prefill_attention(*a, mesh=mesh)
    )(q, k, v, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_pallas_folded_matches_reference():
    """head_dim < 128 variant: heads folded into lanes, zero-placed Q."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas_folded,
    )

    for B, Hq, Hkv, D, seed in [(3, 8, 2, 16, 0), (4, 32, 4, 16, 1), (2, 4, 4, 8, 2)]:
        q, k, v, pt, pos = make_case(B=B, Hq=Hq, Hkv=Hkv, D=D, seed=seed)
        pos = jnp.asarray(np.random.default_rng(seed).integers(0, 15, B), jnp.int32)
        ref = paged_decode_attention(q, k, v, pt, pos)
        got = paged_decode_attention_pallas_folded(q, k, v, pt, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, err_msg=f"B={B} Hq={Hq} D={D}"
        )


def test_prefill_pallas_folded_matches_reference():
    """Folded-lane flash prefill (head_dim < 128 layouts)."""
    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas_folded,
    )

    for T, Hq, Hkv, start, seed in [
        (128, 4, 2, 0, 0), (256, 8, 2, 0, 1), (128, 4, 4, 57, 3), (128, 8, 4, 9, 4),
    ]:
        q, k, v, pt, pos = make_prefill_case(
            T=T, Hq=Hq, Hkv=Hkv, P=128, max_pages=100, start=start, seed=seed
        )
        ref = paged_prefill_attention(q, k, v, pt, pos)
        got = paged_prefill_attention_pallas_folded(q, k, v, pt, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5,
            err_msg=f"T={T} Hq={Hq} Hkv={Hkv} start={start}",
        )


def test_pallas_lookahead_matches_reference():
    """Cross-program-prefetch kernel (r5 default): ragged lengths straddling
    the prefetch window W — some sequences fully inside it, some spilling
    into the tail double-buffer path — must match the XLA reference."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        lookahead_window,
        paged_decode_attention_pallas_lookahead,
    )

    q, k, v, pt, pos = make_case()
    assert lookahead_window(4, 2, 16, 4) >= 1
    got = paged_decode_attention_pallas_lookahead(q, k, v, pt, pos, interpret=True)
    want = paged_decode_attention(q, k, v, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pallas_lookahead_ragged_and_long_tails():
    """Lengths from 1 token to many pages past the prefetch window, odd B
    (parity alternation), duplicated shapes across calls."""
    from dynamo_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas_lookahead,
    )

    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, P, ps, max_pages = 5, 4, 2, 16, 64, 4, 12
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    pt = np.zeros((B, max_pages), np.int32)
    used = set([0])
    for b in range(B):
        for j in range(max_pages):
            p = int(rng.integers(1, P))
            while p in used:
                p = int(rng.integers(1, P))
            used.add(p)
            pt[b, j] = p
    # lengths: 1 token; exactly W pages; W pages + 1 token; deep tail; page-1
    positions = jnp.asarray([0, 2 * ps - 1, 2 * ps, 11 * ps - 1, ps - 1], jnp.int32)
    got = paged_decode_attention_pallas_lookahead(
        q, k, v, jnp.asarray(pt), positions, interpret=True
    )
    want = paged_decode_attention(q, k, v, jnp.asarray(pt), positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pallas_lookahead_vmem_fallback():
    """A geometry whose prefetch window would blow the VMEM budget must fall
    back to perseq (same contract) rather than compile an oversized scratch."""
    from dynamo_tpu.ops.pallas import paged_attention as pa

    assert pa.lookahead_window(512, 32, 128, 2) == 0
    # budget-fitting case picks at least 1, capped at 4
    assert 1 <= pa.lookahead_window(128, 8, 128, 2) <= 4
