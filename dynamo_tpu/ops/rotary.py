"""Rotary position embeddings (RoPE): by halves (NeoX/Llama, `apply_rope`) and
by interleaved pairs (GPT-J, `apply_rope_pairs`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies [head_dim // 2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


@jax.named_scope("attn_kv")
def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate q or k by position.

    x: [T, num_heads, head_dim]; positions: [T] int32. Returns same shape/dtype.
    Uses the split-halves (rotate_half) convention matching HF Llama.
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos = jnp.cos(angles)[:, None, :]  # [T, 1, hd/2]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


@jax.named_scope("attn_kv")
def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate q or k by position, by INTERLEAVED PAIRS (`rope_gptj`): lanes
    (2i, 2i + 1) are one pair turned by frequency i, where `apply_rope` pairs
    lane i with lane i + head_dim // 2. Same shapes as `apply_rope`."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos = jnp.cos(angles)[:, None, :]  # [T, 1, hd/2]
    sin = jnp.sin(angles)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], head_dim // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


@jax.named_scope("attn_kv")
def apply_mrope(
    x: jnp.ndarray,  # [T, num_heads, head_dim]
    positions3: jnp.ndarray,  # [T, 3] (temporal, row, col) position per token
    sections: tuple[int, int, int],  # frequency split, sums to head_dim // 2
    theta: float,
) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL): the inverse-frequency vector is split into
    (temporal, row, col) sections; frequency j takes its angle from the
    position component its section belongs to. Text tokens carry equal
    components, for which this reduces EXACTLY to apply_rope — so text-only
    prompts match the plain path bit-for-bit.

    x: [T, H, D]; positions3: [T, 3] int32. sections must sum to D // 2.
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)  # [D/2]
    # component selector per frequency: 0 (temporal) | 1 (row) | 2 (col)
    comp = jnp.repeat(
        jnp.arange(3, dtype=jnp.int32), jnp.asarray(sections, jnp.int32),
        total_repeat_length=head_dim // 2,
    )
    pos = positions3.astype(jnp.float32)[:, comp]  # [T, D/2]
    angles = pos * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)
