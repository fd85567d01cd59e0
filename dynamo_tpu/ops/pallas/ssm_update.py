"""One-token state update of a Mamba-2 layer as a TPU kernel, in place over the
donated state.

A decode step's recurrence, per live sequence and head,

    S <- a * S + (dt * x) (x) B        S [P, N] float32
    y  = S C

reads and writes every live sequence's state once: 2 x 4.19 MB a sequence and
layer at either published shape (NemotronH: 128 heads x 64 x 128 float32;
Falcon-H1: 32 x 128 x 256), a third of a full-batch decode step's bytes, and
nothing else of size. As an XLA fusion it
has no name a trace reader can find; as a kernel it is `ssm_state_update` on
the device line, and the state array is aliased to the output so no copy of
it is ever made.

Layout. The grid is (batch row, block of heads); the heads a block follow
from the block's bytes (`head_block_for`). The state block is
[1, Hb, P, N] with N on the lanes. The per-row vectors come transposed, P on
the sublanes and the block's heads on the lanes ([B, H/Hb, P, Hb]), so that
head j's `dt * x` is a [P, 1] column that broadcasts along the lanes against
B's [1, N] row: the outer product needs no relayout. `y`'s column is the lane
reduction of `S * C` and is stored into the same transposed layout (at
Falcon-H1's 16 heads a block the vectors fill an eighth of a lane row; they
are a thousandth of the block's bytes). The decay `a` is a scalar per
(row, head), read from SMEM.

Rows that are not live map, through the scalar-prefetched `rows`, to a
trash row of the state: their blocks are the same block step after
step, so the pipeline neither re-fetches nor rewrites anything for them, and
the caller hands them `a = 1`, `dt * x = 0`, which leaves the trash row as it
was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of one state block, each way: input and output double-buffered are
#: four of them, half the 16 MiB of scoped VMEM a v5e kernel has by default.
#: NemotronH's 128 heads x 64 x 128 give 64 heads a block (four groups of 16):
#: on the v5e a call over 128 live slots (1.083 GB in and out) takes 1.77 ms
#: inside a decode step, 75% of the HBM roofline (PERF.md section 6, PR 29).
#: Falcon-H1's 32 heads x 128 x 256 give 16 (one group)
STATE_BLOCK_BYTES = 2 << 20


def head_block_for(H: int, P: int, N: int, G: int) -> int:
    """Heads per grid step: the most whole groups whose float32 state fills
    no more than `STATE_BLOCK_BYTES`, a divisor of the G groups, one at least."""
    hpg = H // G
    fit = max(1, STATE_BLOCK_BYTES // (hpg * P * N * 4))
    return hpg * max(g for g in range(1, G + 1) if G % g == 0 and g <= fit)



def _kernel(rows_ref, a_ref, s_ref, dtx_ref, b_ref, c_ref, y_ref, so_ref, *,
            head_block: int, heads_per_group: int):
    del rows_ref  # used by the index maps
    b = pl.program_id(0)
    hb = pl.program_id(1)
    for j in range(head_block):
        g = j // heads_per_group
        a = a_ref[b, hb * head_block + j]
        col = dtx_ref[0, 0, :, j:j + 1]  # [P, 1]
        s_new = s_ref[0, j] * a + col * b_ref[0, 0, g:g + 1, :]
        so_ref[0, j] = s_new
        y_ref[0, 0, :, j:j + 1] = jnp.sum(
            s_new * c_ref[0, 0, g:g + 1, :], axis=1, keepdims=True
        )


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def ssm_state_update_pallas(
    state: jnp.ndarray,  # [R, H, P, N] float32
    decay: jnp.ndarray,  # [B, H] float32: exp(dt * A); 1 for rows not live
    dtx: jnp.ndarray,  # [B, H, P] float32: dt * x; 0 for rows not live
    b_vec: jnp.ndarray,  # [B, G, N] float32
    c_vec: jnp.ndarray,  # [B, G, N] float32
    rows: jnp.ndarray,  # [B] int32: each batch row's state row (a trash row if not live)
    *,
    head_block: int | None = None,  # None: from the block's bytes
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B, H, P] float32 = S_new C, the state updated in place)."""
    B, H, P = dtx.shape
    G, N = b_vec.shape[1:]
    hpg = H // G
    Hb = head_block_for(H, P, N, G) if head_block is None else min(head_block, H)
    if H % Hb or Hb % hpg:
        raise ValueError(f"head block {Hb} must divide {H} heads in whole groups of {hpg}")
    nb = H // Hb
    dtx_t = dtx.reshape(B, nb, Hb, P).transpose(0, 1, 3, 2)  # [B, nb, P, Hb]
    b_blk = b_vec.reshape(B, nb, Hb // hpg, N)
    c_blk = c_vec.reshape(B, nb, Hb // hpg, N)

    vec_spec = pl.BlockSpec((1, 1, P, Hb), lambda b, hb, rows, a: (b, hb, 0, 0))
    grp_spec = pl.BlockSpec((1, 1, Hb // hpg, N), lambda b, hb, rows, a: (b, hb, 0, 0))
    state_spec = pl.BlockSpec((1, Hb, P, N), lambda b, hb, rows, a: (rows[b], hb, 0, 0))
    y_t, new_state = pl.pallas_call(
        functools.partial(_kernel, head_block=Hb, heads_per_group=hpg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nb),
            in_specs=[state_spec, vec_spec, grp_spec, grp_spec],
            out_specs=[vec_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, P, Hb), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # inputs count the two prefetched scalars: the state is input 2
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="ssm_state_update",
        interpret=interpret,
    )(rows.astype(jnp.int32), decay, state, dtx_t, b_blk, c_blk)
    return y_t.transpose(0, 1, 3, 2).reshape(B, H, P), new_state
