"""ops/ssm.py: the chunked scan against the sequential one across chunk and
sequence boundaries, the carried convolution window, and the state-update
kernel (interpret mode) against `jax.numpy`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.live_rows import every_row, live_rows
from dynamo_tpu.ops.pallas.ssm_update import ssm_state_update_pallas
from dynamo_tpu.ops.ssm import (
    causal_conv,
    ssd_chunked,
    ssd_sequential,
    ssm_state_update_reference,
)

L, H, P, G, N = 3, 8, 16, 2, 32


def _inputs(seed, T):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, B, C = f(L, T, H, P), f(L, T, G, N), f(L, T, G, N)
    dt = jax.nn.softplus(f(L, T, H))
    A, D = -jnp.exp(0.3 * f(H)), f(H)
    return x, dt, A, B, C, D, f(L, H, P, N)


#: float32, summed in another order: measured 1e-4 at most on outputs of size
#: ~30 (this file, PR 29); a dropped carry or a wrong decay moves them by O(1)
ATOL = 2e-3


@pytest.mark.parametrize("T,chunk", [(64, 64), (64, 16), (48, 16), (16, 128)])
def test_chunked_scan_matches_the_sequential_one(T, chunk):
    x, dt, A, B, C, D, S0 = _inputs(T, T)
    with jax.default_matmul_precision("highest"):
        y0, s0 = ssd_sequential(x, dt, A, B, C, D, S0)
        y1, s1 = ssd_chunked(x, dt, A, B, C, D, S0, chunk_size=chunk)
    np.testing.assert_allclose(y1, y0, atol=ATOL)
    np.testing.assert_allclose(s1, s0, atol=ATOL)


def test_scan_continues_across_calls_and_restarts_per_lane():
    """Two calls of 32 tokens that hand the state on equal one call of 64;
    and each lane is its own sequence: lane 1's result does not change when
    lane 0's inputs do (a packed prefill's sequence boundary)."""
    x, dt, A, B, C, D, S0 = _inputs(7, 64)
    with jax.default_matmul_precision("highest"):
        y, s = ssd_chunked(x, dt, A, B, C, D, S0, chunk_size=16)
        ya, sa = ssd_chunked(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32], D, S0, 16)
        yb, sb = ssd_chunked(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:], D, sa, 16)
        y2, s2 = ssd_chunked(x.at[0].set(0.0), dt, A, B, C, D, S0, chunk_size=16)
    np.testing.assert_allclose(jnp.concatenate([ya, yb], axis=1), y, atol=ATOL)
    np.testing.assert_allclose(sb, s, atol=ATOL)
    np.testing.assert_array_equal(y2[1:], y[1:])
    np.testing.assert_array_equal(s2[1:], s[1:])


def test_padding_is_the_identity_on_the_state():
    """dt = 0 at a lane's tail: the state stays where the last real token
    left it, whatever the padding holds."""
    x, dt, A, B, C, D, S0 = _inputs(8, 32)
    dt = dt.at[:, 20:].set(0.0)
    with jax.default_matmul_precision("highest"):
        _, s_pad = ssd_chunked(x, dt, A, B, C, D, S0, chunk_size=16)
        _, s_cut = ssd_sequential(x[:, :20], dt[:, :20], A, B[:, :20], C[:, :20], D, S0)
    np.testing.assert_allclose(s_pad, s_cut, atol=ATOL)


@pytest.mark.parametrize("split", [1, 3, 10, 16])
def test_convolution_continues_from_its_window(split):
    """One pass over 16 inputs equals two passes that hand the window on, at
    any split, with padding after the real tokens of the first pass."""
    rng = np.random.default_rng(split)
    K, Cn, T = 4, 6, 16
    x = jnp.asarray(rng.standard_normal((2, T, Cn)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, Cn)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(Cn), jnp.float32)
    zero = jnp.zeros((2, K - 1, Cn), jnp.float32)
    whole, win = causal_conv(x, zero, w, b, jnp.array([T, T]))
    # the first pass is padded to T with junk after `split` real tokens
    junk = x.at[:, split:].set(99.0)
    first, mid = causal_conv(junk, zero, w, b, jnp.array([split, split]))
    rest, end = causal_conv(x[:, split:], mid, w, b, jnp.array([T - split, T - split]))
    np.testing.assert_allclose(first[:, :split], whole[:, :split], atol=1e-5)
    np.testing.assert_allclose(rest, whole[:, split:], atol=1e-5)
    np.testing.assert_allclose(end, win, atol=1e-6)
    np.testing.assert_allclose(win, x[:, T - K + 1:], atol=1e-6)


#: name -> which of the five batch rows hold a sequence
LIVE_MASKS = {
    "scattered": [1, 0, 1, 1, 0],
    "first_dead": [0, 1, 1, 1, 1],
    "last_dead": [1, 1, 1, 1, 0],
    "alternating": [0, 1, 0, 1, 0],
    "all_live": [1, 1, 1, 1, 1],
    "one_live": [0, 0, 0, 1, 0],
    "none_live": [0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("mask", LIVE_MASKS)
@pytest.mark.parametrize("head_block", [8, 4], ids=["nb1", "nb2"])
def test_state_update_kernel_matches_jax_numpy(head_block, mask):
    """Interpret mode, in place over the state, one block of heads a row and
    two: live rows updated (to the bit what a call with every row live gives
    them), rows that are not live and the trash row untouched whatever decay
    and dt x they carry, a dead row's y zero; with no live row the call
    returns and the state is what came in."""
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    nb = 5
    state = f(nb + 1, H, P, N)
    alive = np.asarray(LIVE_MASKS[mask], bool)
    live = live_rows(jnp.asarray(alive))
    rows = jnp.arange(nb, dtype=jnp.int32)
    decay, dtx = jnp.exp(-jnp.abs(f(nb, H))), f(nb, H, P)
    b, c = f(nb, G, N), f(nb, G, N)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssm_state_update_reference(state, decay, dtx, b, c, rows, live)
    got_y, got_s = ssm_state_update_pallas(
        state, decay, dtx, b, c, rows, live, head_block=head_block, interpret=True
    )
    whole_y, whole_s = ssm_state_update_pallas(
        state, decay, dtx, b, c, rows, every_row(nb), head_block=head_block, interpret=True
    )
    np.testing.assert_allclose(got_y, want_y, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_y)[alive], np.asarray(whole_y)[alive])
    np.testing.assert_array_equal(np.asarray(got_s)[:nb][alive], np.asarray(whole_s)[:nb][alive])
    np.testing.assert_array_equal(np.asarray(got_y)[~alive], 0.0)
    np.testing.assert_array_equal(np.asarray(got_s)[:nb][~alive], np.asarray(state)[:nb][~alive])
    np.testing.assert_array_equal(got_s[nb], state[nb])


def test_the_head_block_follows_from_the_blocks_bytes():
    """2 MiB of float32 state a block each way, in whole groups: NemotronH's
    published shape keeps the 64 heads (four groups) it has run with since
    PR 29, Falcon-H1's takes one group of 16."""
    from dynamo_tpu.ops.pallas.ssm_update import STATE_BLOCK_BYTES, head_block_for

    assert head_block_for(128, 64, 128, 8) == 64
    assert 64 * 64 * 128 * 4 == STATE_BLOCK_BYTES
    assert head_block_for(32, 128, 256, 2) == 16
    assert 16 * 128 * 256 * 4 == STATE_BLOCK_BYTES
    # a group larger than the budget is still one whole group; small shapes take all
    assert head_block_for(8, 256, 512, 2) == 4
    assert head_block_for(H, P, N, G) == H


@pytest.mark.parametrize("alive", [[True, False], [False, True]], ids=["first", "second"])
def test_state_update_kernel_at_falcon_h1s_published_shape(alive):
    """Interpret mode at H = 32, P = 128, N = 256, G = 2, the block chosen
    from its bytes (16 heads, two blocks a row): a live row, a row that is
    not live, the trash row."""
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    Hh, Ph, Nh, Gh, nb = 32, 128, 256, 2, 2
    state = f(nb + 1, Hh, Ph, Nh)
    live = live_rows(jnp.asarray(alive))
    rows = jnp.arange(nb, dtype=jnp.int32)
    decay, dtx = jnp.exp(-jnp.abs(f(nb, Hh))), f(nb, Hh, Ph)
    b, c = f(nb, Gh, Nh), f(nb, Gh, Nh)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssm_state_update_reference(state, decay, dtx, b, c, rows, live)
    got_y, got_s = ssm_state_update_pallas(state, decay, dtx, b, c, rows, live, interpret=True)
    mine, other = alive.index(True), alive.index(False)
    np.testing.assert_allclose(got_y[mine], want_y[mine], atol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    np.testing.assert_array_equal(got_y[other], 0.0)
    np.testing.assert_array_equal(got_s[other], state[other])
    np.testing.assert_array_equal(got_s[nb], state[nb])
