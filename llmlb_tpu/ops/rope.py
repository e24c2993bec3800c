"""Rotary position embeddings (interleaved-half convention, HF-compatible).

Supports plain RoPE (Llama-2/Qwen/Mistral) and Llama-3 frequency scaling.
Frequencies are computed from integer positions at trace time — no precomputed
table in HBM, XLA fuses the sin/cos into the surrounding elementwise graph.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama-3-style rope scaling (factor-based NTK with wavelength thresholds)."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192


def rope_frequencies(
    head_dim: int,
    theta: float = 10000.0,
    scaling: RopeScaling | None = None,
) -> jnp.ndarray:
    """Per-pair inverse frequencies, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**exponents)
    if scaling is not None:
        low_wl = scaling.original_max_position / scaling.low_freq_factor
        high_wl = scaling.original_max_position / scaling.high_freq_factor
        wavelen = 2.0 * math.pi / inv_freq
        smooth = (scaling.original_max_position / wavelen - scaling.low_freq_factor) / (
            scaling.high_freq_factor - scaling.low_freq_factor
        )
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / scaling.factor
        blended = (1.0 - smooth) * scaled + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > low_wl, scaled, jnp.where(wavelen < high_wl, inv_freq, blended)
        )
    return inv_freq


def apply_rope(
    x: jnp.ndarray,  # [B, T, H, D]
    positions: jnp.ndarray,  # [B, T] int32
    inv_freq: jnp.ndarray,  # [D // 2]
    interleaved: bool = False,
) -> jnp.ndarray:
    """Rotate q or k by position. Split-half (rotate_half) layout, as HF
    Llama: pair i is (x[i], x[i + D/2]). `interleaved` (DeepSeek-V3's
    `rope_interleave`): pair i is (x[2i], x[2i+1]), rotated in place."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, T, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        rotated = jnp.stack((x1 * cos - x2 * sin, x2 * cos + x1 * sin),
                            axis=-1).reshape(x.shape)
        return rotated.astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)
    return rotated.astype(x.dtype)


def rotary_dim(head_dim: int, partial_rotary_factor: float) -> int:
    """Numbers of a head that RoPE turns under `partial_rotary_factor`: the
    factor times the head, rounded down to an even count (0.334 x 192 =
    64.1 -> 64)."""
    return int(head_dim * partial_rotary_factor) // 2 * 2


def apply_partial_rope(
    x: jnp.ndarray,  # [B, T, H, D]
    positions: jnp.ndarray,  # [B, T] int32
    inv_freq: jnp.ndarray,  # [R // 2]: rope_frequencies(R, theta)
) -> jnp.ndarray:
    """RoPE on the FIRST R = 2 x len(inv_freq) numbers of each head, paired
    split-half within those R (pair i is (x[i], x[i + R/2])); the other
    D - R pass as they are (`partial_rotary_factor`)."""
    r = 2 * inv_freq.shape[0]
    if r == x.shape[-1]:
        return apply_rope(x, positions, inv_freq)
    return jnp.concatenate(
        (apply_rope(x[..., :r], positions, inv_freq), x[..., r:]), axis=-1)
