"""Normalization layers (computed in float32, cast back)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("norm")
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm over the last axis; accumulates in f32 like the TPU-friendly norm."""
    xf = x.astype(jnp.float32)
    variance = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jnp.reciprocal(jnp.sqrt(variance + eps))
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("norm")
def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Cohere's LayerNorm over the last axis: mean-centred, divided by
    ``sqrt(var + eps)``, times a weight, no bias; in f32, cast back."""
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    variance = jnp.mean(centred * centred, axis=-1, keepdims=True)
    normed = centred * jnp.reciprocal(jnp.sqrt(variance + eps))
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)
