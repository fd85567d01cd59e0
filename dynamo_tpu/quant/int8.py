"""Weight-only int8 quantization: symmetric per-output-channel scales.

Why weight-only, why int8: the decode window is weight-bound — the r5 roofline
decomposition has the bf16 weight stream as the dominant HBM term of every
decode step — so storing the big linear weights as int8 (+ one f32 scale per
output channel) halves the bytes each step reads. The matmul stays on the MXU
at the activation dtype: the int8 weight is converted on the fly inside the
fused dot (XLA folds the convert into the weight read on TPU — the HBM
traffic is the int8 bytes, not the upcast bf16 bytes), accumulated in f32 via
``preferred_element_type``, and the per-channel scale is applied to the f32
product. Per-OUTPUT-channel symmetric scales make that exact algebra:

    h @ dequant(q, s) == (h @ q) * s        (s broadcast over output channels)

so no zero points, no activation quantization, and the scale multiply commutes
with the tensor-parallel psum of row-parallel layers.

Layout contract (matches every weight this framework stores): weights are
``[..., in, out]`` — leading stack axes (layers ``L``, experts ``E``), then
the contracted (input) axis SECOND-TO-LAST, the output-channel axis LAST.
``QuantizedLinear.s`` therefore has the weight's shape with the ``in`` axis
removed, which keeps the container scan-sliceable (``lax.scan`` over the
layer stack slices ``q`` and ``s`` together) and makes the sharding rule
mechanical: ``q`` keeps the bf16 weight's sharding; ``s`` keeps the same spec
minus the contracted-axis entry (so scales follow their weight's
output-channel sharding and replicate everywhere else — in particular they
replicate across tp for row-parallel weights, and shard on the stage axis
under pp exactly like the weight's leading ``[L]`` dim).

What never quantizes: embeddings, the lm_head, norms, biases, MoE routers
(f32 by design), and the MLA k-up/v-up banks (3-D per-head einsum operands,
~1% of bytes). Models list their quantizable leaves in
``QUANT_WEIGHT_NAMES`` (models/llama.py etc.).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

#: modes EngineConfig.quantize / model configs accept (None = full precision)
QUANT_MODES = ("int8_wo",)

_INT8_MAX = 127.0


@jax.tree_util.register_pytree_node_class
class QuantizedLinear:
    """Param container for one weight-only-int8 linear weight.

    q: int8 ``[..., in, out]`` — same layout as the bf16 weight it replaces
    s: f32 ``[..., out]`` — per-output-channel scales (``in`` axis removed)

    Registered as a pytree node so the container rides everything the plain
    weight rode: ``lax.scan`` over layer stacks (both leaves slice on the
    leading axis), ``jax.device_put`` with a mirrored sharding tree
    (quantize_shardings_int8), jit/eval_shape, and the pipeline shard_map's
    in_specs trees.
    """

    __slots__ = ("q", "s")

    def __init__(self, q, s):
        self.q = q
        self.s = s

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    def __repr__(self):  # pragma: no cover - debugging aid
        qs = getattr(self.q, "shape", None)
        return f"QuantizedLinear(q={qs}, s={getattr(self.s, 'shape', None)})"


def quantize_int8(w) -> QuantizedLinear:
    """``[..., in, out]`` weight -> symmetric per-output-channel int8.

    scale[..., o] = max_i |w[..., i, o]| / 127 (floored so an all-zero channel
    divides cleanly); q = round(w / scale) clipped to [-127, 127].
    """
    w32 = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)  # [..., out]
    scale = jnp.maximum(absmax, 1e-12) / _INT8_MAX
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -_INT8_MAX, _INT8_MAX)
    return QuantizedLinear(q=q.astype(jnp.int8), s=scale)


def dequantize_int8(w: QuantizedLinear, dtype=jnp.float32) -> jnp.ndarray:
    """Materialize the full-precision weight (tests / offline tooling only —
    the hot path never materializes it; see qlinear)."""
    return (w.q.astype(jnp.float32) * w.s[..., None, :]).astype(dtype)


def qlinear(h, w):
    """``h @ w`` for a plain 2-D weight or a (scan-sliced, 2-D) QuantizedLinear.

    Quantized: one fused dot — int8 weight upcast on the fly to the
    activation dtype (HBM reads stay int8 on TPU), f32 accumulation, then the
    per-output-channel scale on the f32 product, cast back to h.dtype. The
    scale multiply is per OUTPUT channel, so under tensor parallelism it is
    correct both before a row-parallel psum (it distributes over the sum) and
    on column-parallel output shards (s shards with the same channels).
    """
    if not isinstance(w, QuantizedLinear):
        return h @ w
    y = jax.lax.dot_general(
        h,
        w.q.astype(h.dtype),
        (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (y * w.s).astype(h.dtype)


def quantize_tree_int8(group: dict, names) -> dict:
    """Replace the named leaves of one layer-group dict with QuantizedLinear
    containers (idempotent: already-quantized leaves and absent names skip)."""
    out = dict(group)
    for k, v in group.items():
        if k in names and not isinstance(v, QuantizedLinear):
            out[k] = quantize_int8(v)
    return out


def _scale_sharding(ws: NamedSharding) -> NamedSharding:
    """The scale sharding mirroring a weight's: same spec with the contracted
    (second-to-last) axis entry removed. Model shardings in this codebase are
    full-rank PartitionSpecs, so positional deletion is exact."""
    spec = list(ws.spec)
    del spec[-2]
    return NamedSharding(ws.mesh, P(*spec))


def quantize_shardings_int8(group: dict, names) -> dict:
    """Mirror quantize_tree_int8 onto a sharding tree: the named NamedSharding
    leaves become QuantizedLinear(q=<weight sharding>, s=<scale sharding>) so
    jax.device_put sees structurally matching param/sharding trees."""
    out = dict(group)
    for k, v in group.items():
        if k in names and not isinstance(v, QuantizedLinear):
            out[k] = QuantizedLinear(q=v, s=_scale_sharding(v))
    return out
