"""Sparse Mixture-of-Experts: routing, and ONE dispatch that drops nothing.

The dispatch sorts the (token, choice) assignments by expert and runs the
expert FFN as a grouped matrix product over the experts HELD here: rows of one
group meet one expert's matrix. Shapes are static at ``T * K`` rows, the worst
case, so no assignment is ever dropped whatever the routing: there is no
capacity and no factor to tune.

The product (`grouped_matmul`) is the kernel `moe_grouped_matmul`
(`ops/pallas/grouped_matmul.py`): it streams the matrix of each expert that
received a row once, visits row tiles by the groups' real boundaries and stops
at the last real row, so the static worst case costs nothing where it is not
met. Rows past the last group are UNDEFINED in its result (stale memory); a
row-by-row function such as `relu2` may stand between two products, and
`moe_dispatch` selects those rows away. `jax.lax.ragged_dot` is the reference
and the path that is taken off the chip, for a weight-only int8 bank, for a
bank too wide for the kernel's blocks, and whenever the caller's model runs
under a mesh of several devices (the expert axis of the banks shards over a
mesh's "ep" axis under GSPMD, which gathers what the product needs and cannot
partition a kernel: Mixtral's test). The caller says so with ``mesh``: inside
a jit a bank's sharding is not there to ask. `tools/tpu_compile.py` asks the
chip's compiler for the kernel at the shapes served.

An expert layer may hold a SHARE of the experts it routes over (one chip of an
expert-parallel deployment): the router scores all of them, the weights are
normalised over everything a token chose, and this chip computes the part of
the sum that its own experts give. Assignments to experts held elsewhere sort
past the last group and contribute nothing here; nothing stands in for them.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from dynamo_tpu.quant import QuantizedLinear


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    """``relu(x) ** 2`` (``mlp_hidden_act: relu2``)."""
    r = jnp.maximum(x, 0)
    return r * r


@jax.named_scope("moe_router")
def topk_routing(
    router_logits: jnp.ndarray,  # [T, E] float32
    k: int,
    renormalize: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax routing. Returns (weights [T, K], indices [T, K]).

    renormalize=True (Mixtral, norm_topk_prob): softmax over the selected k.
    renormalize=False (DeepSeek default): softmax over ALL experts, top-k
    probabilities used as-is."""
    top_logits, top_idx = jax.lax.top_k(router_logits, k)
    if renormalize:
        weights = jax.nn.softmax(top_logits, axis=-1)
    else:
        probs = jax.nn.softmax(router_logits, axis=-1)
        weights = jnp.take_along_axis(probs, top_idx, axis=-1)
    return weights, top_idx


@jax.named_scope("moe_router")
def sigmoid_topk_routing(
    router_logits: jnp.ndarray,  # [T, E] float32, over ALL experts routed over
    bias: jnp.ndarray,  # [E] e_score_correction_bias: moves the choice only
    k: int,
    scale: float = 1.0,
    eps: float = 1e-20,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid routing with a selection bias (no group limit):
    ``s = sigmoid(logits)``; chosen = top-k of ``s + bias``;
    ``w = scale * s_chosen / (sum of s over the chosen + eps)``.
    ``eps`` is the published model's: 1e-20 for NemotronH and Cohere2-MoE
    (models/nemotron_h.py, models/cohere2_moe.py: the default, a guard against
    0/0 and nothing else), 1e-6 for LFM2-MoE (models/lfm2_moe.py), whose
    weights then sum to a little under ``scale``."""
    s = jax.nn.sigmoid(router_logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)
    return weights, idx


@jax.named_scope("moe_experts")
def grouped_matmul(rows: jnp.ndarray, bank, group_sizes: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """``rows [M, in]`` (sorted by group) times ``bank [G, in, out]``: row r
    meets the matrix of its group. Rows past the last group are UNDEFINED
    (whatever memory held; never read them but through a select).
    ``mesh``: the mesh of several devices the caller's model runs under, if
    any; the bank may be sharded over it, and the product is then left to XLA.
    A weight-only int8 bank dequantizes into the product, scaled per row by
    its group's output-channel scales."""
    if isinstance(bank, QuantizedLinear):
        y = jax.lax.ragged_dot(
            rows, bank.q.astype(rows.dtype), group_sizes,
            preferred_element_type=jnp.float32,
        )
        ends = jnp.cumsum(group_sizes)
        group_of_row = jnp.minimum(
            jnp.searchsorted(ends, jnp.arange(rows.shape[0]), side="right"),
            bank.s.shape[0] - 1,
        )
        return (y * bank.s[group_of_row]).astype(rows.dtype)
    from dynamo_tpu.ops.attention import _on_tpu, _pallas_enabled
    from dynamo_tpu.ops.pallas.grouped_matmul import column_block, grouped_matmul_pallas

    fits = column_block(*bank.shape[1:], bank.dtype.itemsize) is not None
    if mesh is None and fits and rows.dtype == bank.dtype and _pallas_enabled(True):
        return grouped_matmul_pallas(rows, bank, group_sizes, interpret=not _on_tpu())
    return jax.lax.ragged_dot(rows, bank, group_sizes)


@jax.named_scope("moe_dispatch")
def moe_dispatch(
    hidden: jnp.ndarray,  # [T, D]
    weights: jnp.ndarray,  # [T, K] float32, normalised over all K chosen
    idx: jnp.ndarray,  # [T, K] expert ids among the experts ROUTED over
    expert_ffn: Callable,  # (rows [T*K, D], group_sizes [held]) -> [T*K, Dout]
    num_held: int,
    offset: int = 0,  # first expert id held here
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each token's weighted sum over the chosen experts that are held here.
    Returns (out [T, Dout] float32, counts [num_held] int32: the assignments
    each held expert received)."""
    T, K = idx.shape
    local = idx.reshape(T * K).astype(jnp.int32) - offset
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held)  # absent experts sort last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros(num_held + 1, jnp.int32).at[key].add(1)[:num_held]
    rows = hidden[order // K]
    with jax.named_scope("moe_experts"):  # the products and what stands between them
        y = expert_ffn(rows, counts)  # [T*K, Dout], sorted order
    y = jnp.where(held[order][:, None], y.astype(jnp.float32), 0.0)
    # back to (token, choice) order: a gather, so the sum over a token's
    # choices has one order whatever the routing
    y = y[jnp.argsort(order)].reshape(T, K, -1)
    return jnp.einsum("tk,tkd->td", weights.astype(jnp.float32), y), counts


def moe_block(
    hidden: jnp.ndarray,  # [T, D]
    router_w: jnp.ndarray,  # [D, E]
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
    num_experts_per_tok: int,
    renormalize: bool = True,
    mesh=None,  # the caller's mesh of several devices, if any: `grouped_matmul`
) -> jnp.ndarray:
    """Softmax-routed SwiGLU experts, all held here (Mixtral, DeepSeek-V2)."""
    with jax.named_scope("moe_router"):
        logits = hidden.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
        weights, idx = topk_routing(logits, num_experts_per_tok, renormalize=renormalize)

    def ffn(rows, group_sizes):
        gated = jax.nn.silu(grouped_matmul(rows, w_gate, group_sizes, mesh))
        up = grouped_matmul(rows, w_up, group_sizes, mesh)
        return grouped_matmul(gated * up, w_down, group_sizes, mesh)

    out, _ = moe_dispatch(hidden, weights, idx, ffn, num_held=router_w.shape[1])
    return out.astype(hidden.dtype)
