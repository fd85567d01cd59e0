"""The sliding-window variants of the decode and prefill attention kernels
(interpret mode) against the gather reference: a query at position p sees the
keys in (p - W, p]; page-table entries behind the window may be the null page
(the engine gives those pages back)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_decode_attention, paged_prefill_attention
from dynamo_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas_lookahead
from dynamo_tpu.ops.pallas.prefill_attention import (
    paged_prefill_attention_pallas,
    prefill_block_q,
)

PS, HKV, D = 16, 2, 128


def _pool(rng, pages):
    k = jnp.asarray(rng.standard_normal((pages, PS, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((pages, PS, HKV, D)), jnp.float32)
    return k, v


def _released(table, position, window):
    """The table as the engine leaves it: pages wholly behind the window of a
    query at `position` point at the null page."""
    table = np.array(table)
    table[: max(0, position - window + 1) // PS] = 0
    return table


@pytest.mark.parametrize("window", [32, 128, 200])
def test_decode_window_kernel_matches_reference(window):
    rng = np.random.default_rng(0)
    B, Hq, width = 4, 4, 40
    k, v = _pool(rng, 1 + B * width)
    positions = np.array([5, window - 1, 3 * window + 7, 600], np.int32)
    tables = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    want = paged_decode_attention(q, k, v, jnp.asarray(tables), jnp.asarray(positions), window)
    assert not np.allclose(
        want, paged_decode_attention(q, k, v, jnp.asarray(tables), jnp.asarray(positions)), atol=1e-3
    )
    given_back = np.stack([_released(tables[b], positions[b], window) for b in range(B)])
    got = paged_decode_attention_pallas_lookahead(
        q, k, v, jnp.asarray(given_back), jnp.asarray(positions), interpret=True, window=window
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("alive", [
    [0, 1, 1, 1], [1, 1, 1, 0], [1, 0, 1, 0], [0, 1, 0, 1], [1, 1, 1, 1], [0, 0, 1, 0], [0, 0, 0, 0],
], ids=["first_dead", "last_dead", "alternating", "alternating_first_dead", "all_live",
        "one_live", "none_live"])
def test_decode_window_kernel_serves_the_live_rows_alone(alive):
    """The window kernel under a step's live rows: a live row reads what the
    all-live call gives it to the bit, a dead row zero."""
    from dynamo_tpu.ops.live_rows import every_row, live_rows

    window = 128
    rng = np.random.default_rng(2)
    B, Hq, width = 4, 4, 40
    k, v = _pool(rng, 1 + B * width)
    positions = np.array([5, window - 1, 3 * window + 7, 600], np.int32)
    tables = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    want = paged_decode_attention(q, k, v, jnp.asarray(tables), jnp.asarray(positions), window)
    given_back = np.stack([_released(tables[b], positions[b], window) for b in range(B)])
    whole = paged_decode_attention_pallas_lookahead(
        q, k, v, jnp.asarray(given_back), jnp.asarray(positions), every_row(B),
        interpret=True, window=window,
    )
    alive = np.asarray(alive, bool)
    got = paged_decode_attention_pallas_lookahead(
        q, k, v, jnp.asarray(given_back), jnp.asarray(positions), live_rows(jnp.asarray(alive)),
        interpret=True, window=window,
    )
    np.testing.assert_array_equal(np.asarray(got)[alive], np.asarray(whole)[alive])
    np.testing.assert_allclose(np.asarray(got)[alive], np.asarray(want)[alive], atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[~alive], 0.0)


@pytest.mark.parametrize("window,start", [(32, 0), (32, 256), (200, 384), (4096, 128)])
@pytest.mark.parametrize("lookahead", [True, False])
def test_prefill_window_kernel_matches_reference(window, start, lookahead):
    rng = np.random.default_rng(1)
    T, Hq, width = 128, 4, 40
    k, v = _pool(rng, 1 + width)
    table = 1 + np.arange(width, dtype=np.int32)
    positions = jnp.arange(start, start + T, dtype=jnp.int32)
    q = jnp.asarray(rng.standard_normal((T, Hq, D)), jnp.float32)
    want = paged_prefill_attention(q, k, v, jnp.asarray(table), positions, window)
    got = paged_prefill_attention_pallas(
        q, k, v, jnp.asarray(_released(table, start, window)), positions,
        block_q=32, interpret=True, lookahead=lookahead, window=window,
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_prefill_block_rows_follow_the_heads():
    assert [prefill_block_q(h) for h in (16, 32, 64, 128)] == [128, 128, 64, 32]
