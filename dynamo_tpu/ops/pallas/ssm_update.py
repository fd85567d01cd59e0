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

Rows that are not live cost nothing (PR 46). The grid's first axis is over the
step's LIVE rows (`ops/live_rows.py`, made once a decode step on the device):
`order` rides as scalar prefetch beside `rows`, grid step (i, hb) serves batch
row `order[i]`, and the live count is the grid's bound, read on the device
(Pallas lowers a traced bound on this backend; in interpret mode it is a
`while_loop`). A dead row has no step: no block of its state, its vectors or
its y is fetched or written, its state row and the trash row stay as they
were whatever decay and dt x it carries, and the caller's mask reads its y as
zero. With no live row at all (a warm-up shape) the kernel runs no step.

Until PR 46 the grid walked the whole slot table and a dead row mapped to a
trash row, "the same block step after step, so the pipeline neither
re-fetches nor rewrites anything". With two blocks of heads a row (both
published shapes) that was false: consecutive dead rows alternate between the
trash row's two blocks, the block index changes at every step, and each dead
row streamed 2 x 2 MiB in and out like a live one. On the v5e, 36 chained
calls, best of 5 (`tools/profile_live_rows.py`, my chip run, PR 46):

  us per call (share of the HBM roofline)   until PR 46     the live rows only
  NemotronH, 82 of 128 rows live            1858 (45.2%)    1193 (70.4%)
  NemotronH, 128 of 128                     1829 (71.7%)    1828 (71.7%)
  Falcon-H1, 91 of 96                       1277 (73.0%)    1212 (76.9%)
  Falcon-H1, 96 of 96                       1281 (76.8%)    1281 (76.8%)

A grid clamped to the table, its dead steps repeating the last live step's
blocks so that nothing moves, timed 1223 at 82 of 128 in an earlier call: a
dead step that does nothing still costs 0.2-0.3 us of grid, and the bound by
count takes that too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.live_rows import LiveRows, every_row, zero_dead_rows

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



def _kernel(rows_ref, order_ref, a_ref, s_ref, dtx_ref, b_ref, c_ref, y_ref, so_ref, *,
            head_block: int, heads_per_group: int):
    del rows_ref  # used by the index maps
    b = order_ref[pl.program_id(0)]  # the grid is over the live rows
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
    decay: jnp.ndarray,  # [B, H] float32: exp(dt * A)
    dtx: jnp.ndarray,  # [B, H, P] float32: dt * x
    b_vec: jnp.ndarray,  # [B, G, N] float32
    c_vec: jnp.ndarray,  # [B, G, N] float32
    rows: jnp.ndarray,  # [B] int32: each batch row's state row
    live: LiveRows | None = None,  # the rows to update (None: every row)
    *,
    head_block: int | None = None,  # None: from the block's bytes
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B, H, P] float32 = S_new C, zero for a row that is not
    live; the state updated in place, a dead row's state row untouched)."""
    B, H, P = dtx.shape
    G, N = b_vec.shape[1:]
    if live is None:
        live = every_row(B)
    hpg = H // G
    Hb = head_block_for(H, P, N, G) if head_block is None else min(head_block, H)
    if H % Hb or Hb % hpg:
        raise ValueError(f"head block {Hb} must divide {H} heads in whole groups of {hpg}")
    nb = H // Hb
    dtx_t = dtx.reshape(B, nb, Hb, P).transpose(0, 1, 3, 2)  # [B, nb, P, Hb]
    b_blk = b_vec.reshape(B, nb, Hb // hpg, N)
    c_blk = c_vec.reshape(B, nb, Hb // hpg, N)

    def vec_map(i, hb, rows, order, a):
        return order[i], hb, 0, 0

    def state_map(i, hb, rows, order, a):
        return rows[order[i]], hb, 0, 0

    vec_spec = pl.BlockSpec((1, 1, P, Hb), vec_map)
    grp_spec = pl.BlockSpec((1, 1, Hb // hpg, N), vec_map)
    state_spec = pl.BlockSpec((1, Hb, P, N), state_map)
    y_t, new_state = pl.pallas_call(
        functools.partial(_kernel, head_block=Hb, heads_per_group=hpg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(live.count[0], nb),
            in_specs=[state_spec, vec_spec, grp_spec, grp_spec],
            out_specs=[vec_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, P, Hb), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # inputs count the three prefetched scalars: the state is input 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="ssm_state_update",
        interpret=interpret,
    )(rows.astype(jnp.int32), live.order, decay, state, dtx_t, b_blk, c_blk)
    # no step wrote a dead row's y block: it is memory as it was found
    return zero_dead_rows(y_t.transpose(0, 1, 3, 2).reshape(B, H, P), live), new_state
