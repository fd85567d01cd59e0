"""Mamba-2 state-space mixing: the causal depthwise convolution with a carried
window, the chunked scan for prefill, and the one-token update for decode.

Per sequence and head h (group g = h // (H / G)), with S [P, N] float32:

    a_t = exp(dt_t * A_h)
    S_t = a_t * S_{t-1} + (dt_t * x_t) (x) B_t
    y_t = S_t C_t + D_h * x_t

`ssd_sequential` is that recurrence one token at a time (the tests' yardstick).
`ssd_chunked` computes the same thing a chunk of `chunk_size` tokens at a time:
inside a chunk the pairwise decays exp(sum dt*A over (s, t]) weight C_t . B_s
(matrix products), and between chunks the state carries. It continues from a
state handed in, and a token with dt = 0 is the identity on the state, which
is how padding at the tail of a lane leaves the state where the last real
token put it. Lanes are a batch axis: every lane is its own sequence, so the
scan restarts at every sequence boundary of a packed prefill by construction.

Everything here is float32: the decays multiply over hundreds of tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.live_rows import zero_dead_rows


@jax.named_scope("ssm")
def causal_conv(
    x: jnp.ndarray,  # [L, T, C] the lane's new inputs
    window: jnp.ndarray,  # [L, K-1, C] the K-1 inputs before them (zeros at a start)
    w: jnp.ndarray,  # [K, C] tap k multiplies the input K-1-k positions back
    bias: jnp.ndarray | None,  # [C], or None: the convolution has none
    n_valid: jnp.ndarray,  # [L] real tokens in each lane (the rest is padding)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution that continues from a carried window.
    Returns (y [L, T, C] float32, the window after each lane's last real
    token [L, K-1, C] in ``window``'s dtype)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.concatenate([window.astype(jnp.float32), x.astype(jnp.float32)], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(xp[:, k:k + T] * wf[k] for k in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    # inputs n-K+1 .. n-1 sit at xp[n : n+K-1]; n = 0 gives the old window back
    idx = n_valid[:, None] + jnp.arange(K - 1)[None, :]
    new_window = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return y, new_window.astype(window.dtype)


def ssd_sequential(x, dt, A, B, C, D, state):
    """The recurrence, token by token. x [L, T, H, P], dt [L, T, H] (0 at
    padding), A, D [H], B, C [L, T, G, N], state [L, H, P, N]; all float32.
    Returns (y [L, T, H, P], final state)."""
    G = B.shape[2]
    hpg = x.shape[2] // G

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp  # [L,H,P], [L,H], [L,G,N], [L,G,N]
        Bh = jnp.repeat(B_t, hpg, axis=1)
        Ch = jnp.repeat(C_t, hpg, axis=1)
        S = S * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * Bh[:, :, None, :]
        y = jnp.einsum("lhpn,lhn->lhp", S, Ch) + D[None, :, None] * x_t
        return S, y

    xs = (x.swapaxes(0, 1), dt.swapaxes(0, 1), B.swapaxes(0, 1), C.swapaxes(0, 1))
    state, y = jax.lax.scan(step, state, xs)
    return y.swapaxes(0, 1), state


@jax.named_scope("ssm")
def ssd_chunked(x, dt, A, B, C, D, state, chunk_size: int = 128):
    """`ssd_sequential`'s result, a chunk at a time (same arguments)."""
    L, T, H, P = x.shape
    G, N = B.shape[2:]
    Q = min(chunk_size, T)
    if T % Q:
        raise ValueError(f"{T} tokens do not divide into chunks of {Q}")
    nc = T // Q
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunks(t):  # [L, T, ...] -> [nc, L, Q, ...]
        return t.reshape(L, nc, Q, *t.shape[2:]).swapaxes(0, 1)

    J = H // G

    def body(S, inp):
        x_c, dt_c, B_c, C_c = inp  # [L,Q,H,P], [L,Q,H], [L,Q,G,N] x 2
        # heads lead, so that the [Q, Q] and [Q, P] matrices are the minor dims
        dth = dt_c.swapaxes(1, 2)  # [L,H,Q]
        la = jnp.cumsum(dth * A[:, None], axis=-1)  # log decay since the chunk's start
        xdt = (x_c.swapaxes(1, 2) * dth[..., None]).reshape(L, G, J, Q, P)
        Bg, Cg = B_c.swapaxes(1, 2), C_c.swapaxes(1, 2)  # [L,G,Q,N]
        # inside the chunk: decay(s -> t) * (C_t . B_s), s <= t
        cb = jnp.einsum("lgqn,lgsn->lgqs", Cg, Bg)
        diff = la[..., :, None] - la[..., None, :]  # [L,H,Q(t),Q(s)]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        w = cb[:, :, None] * decay.reshape(L, G, J, Q, Q)
        y = jnp.einsum("lgjqs,lgjsp->lgjqp", w, xdt)
        # what the state carried in adds to each token
        Sg = S.reshape(L, G, J, P, N)
        y = y + jnp.einsum("lgqn,lgjpn->lgjqp", Cg, Sg) \
            * jnp.exp(la).reshape(L, G, J, Q)[..., None]
        # the state after the chunk
        to_end = jnp.exp(la[..., -1:] - la).reshape(L, G, J, Q)
        Sg = Sg * jnp.exp(la[..., -1]).reshape(L, G, J)[..., None, None] \
            + jnp.einsum("lgjqp,lgqn->lgjpn", xdt * to_end[..., None], Bg)
        y = y.reshape(L, H, Q, P).swapaxes(1, 2) + D[None, None, :, None] * x_c
        return Sg.reshape(L, H, P, N), y

    state, y = jax.lax.scan(body, state, (chunks(x), chunks(dt), chunks(B), chunks(C)))
    return y.swapaxes(0, 1).reshape(L, T, H, P), state


def ssm_state_update_reference(state, decay, dtx, b_vec, c_vec, rows, live=None):
    """`ssm_state_update_pallas` in `jax.numpy` (same arguments and result):
    the path off the chip, and the kernel's yardstick."""
    hpg = dtx.shape[1] // b_vec.shape[1]
    old = state[rows]
    S = old * decay[..., None, None] \
        + dtx[..., None] * jnp.repeat(b_vec, hpg, axis=1)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", S, jnp.repeat(c_vec, hpg, axis=1))
    if live is not None:
        S = jnp.where(live.mask[:, None, None, None], S, old)
    return zero_dead_rows(y, live), state.at[rows].set(S)


@jax.named_scope("ssm")
def ssm_state_update(state, rows, x, dt, A, B, C, D, live):
    """One decode token for every live batch row b, whose state is row
    ``rows[b]`` of ``state`` [R, H, P, N]; ``live`` is the step's
    `ops.live_rows.LiveRows`, and a row that is not live keeps its state and
    reads y = D x. x [B, H, P], dt [B, H], B, C [B, G, N], float32. Returns
    (y [B, H, P], state updated in place where donated)."""
    from dynamo_tpu.ops.attention import _on_tpu, _pallas_enabled
    from dynamo_tpu.ops.pallas.ssm_update import ssm_state_update_pallas

    rows = rows.astype(jnp.int32)
    decay = jnp.exp(dt * A)
    dtx = dt[..., None] * x
    if _pallas_enabled(True):
        y, state = ssm_state_update_pallas(
            state, decay, dtx, B, C, rows, live, interpret=not _on_tpu()
        )
    else:
        y, state = ssm_state_update_reference(state, decay, dtx, B, C, rows, live)
    return y + D[None, :, None] * x, state
