"""The live rows of a decode step, for the kernels whose grid walks the batch.

A decode batch is the whole slot table, and most of it may hold nobody. The
decode attention kernel (`ops/pallas/paged_attention._kernel_lookahead`) and
the state update (`ops/pallas/ssm_update`) run one grid program a row; given
the live rows first and their count (the grid's bound) they serve those, and
a row that is not live costs nothing: no grid step, no DMA, no block fetched
or written, no arithmetic. Nothing writes such a row's output, so the
kernels' wrappers read it as zero (`zero_dead_rows`).

The pair is made ON THE DEVICE, once a decode step and outside the layer scan,
from the `active` mask the step already carries: a sequence can freeze at its
limit inside a window of steps, so the host cannot know it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class LiveRows(NamedTuple):
    order: jnp.ndarray  # [B] int32: the live rows, ascending, then the others
    count: jnp.ndarray  # [1] int32: how many are live
    mask: jnp.ndarray  # [B] bool: row b is live


@jax.named_scope("live_rows")
def live_rows(active: jnp.ndarray) -> LiveRows:
    """`active` [B] bool -> the pair the kernels prefetch as scalars, and the
    mask their callers zero a dead row's output by. No sort and no scatter:
    a row's place is a prefix count, and `order` is its inverse by a [B, B]
    comparison (a slot table holds a few hundred rows)."""
    B = active.shape[0]
    live = active.astype(jnp.int32)
    count = jnp.sum(live)
    # place of row b: among the live rows if live, behind all of them if not
    place = jnp.where(active, jnp.cumsum(live) - 1, count + jnp.cumsum(1 - live) - 1)
    rows = jnp.arange(B, dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[:, None] == rows[None, :], rows[:, None], 0), axis=0)
    return LiveRows(order.astype(jnp.int32), count.reshape(1).astype(jnp.int32), active)


def zero_dead_rows(x: jnp.ndarray, live: LiveRows | None) -> jnp.ndarray:
    """`x` [B, ...] with the rows that are not live zeroed (a select, so what a
    dead row held, NaN included, does not matter); `live` None: every row."""
    if live is None:
        return x
    mask = live.mask.reshape(-1, *[1] * (x.ndim - 1))
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


def every_row(B: int) -> LiveRows:
    """A batch with no dead row: what a caller that knows none hands over."""
    return LiveRows(jnp.arange(B, dtype=jnp.int32), jnp.full((1,), B, jnp.int32),
                    jnp.ones((B,), bool))
