"""Rotary position embeddings.

Capability parity with the reference:
  - plain RoPE, theta=10k (LLaMA-2)      — Models/Llama/Llama2.py:34-55
  - RoPE with LLaMA-3.1 frequency
    smoothing (wavelength bands)         — Models/Llama/Llama3.py:74-104
  - rotate-half application on (b,h,t,d) — Models/Llama/common_components.py:6-35

Design difference from the reference: cos/sin tables are computed once per
model setup as fp32 host constants and closed over by the jitted step (the
reference caches them per-process in a ``SharedBuffers`` dict keyed by config,
Models/Llama/Llama3.py:55-70 — under jit, constant-folding makes that cache
unnecessary). No (ctx, ctx) mask buffer is ever built.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from building_llm_from_scratch_tpu.configs import RopeScaling


def precompute_rope_params(
    head_dim: int,
    theta_base: float = 10_000.0,
    context_length: int = 4096,
    rope_scaling: Optional[RopeScaling] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (cos, sin), each of shape (context_length, head_dim), fp32."""
    assert head_dim % 2 == 0, "head_dim must be even for RoPE"
    inv_freq = 1.0 / (
        theta_base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )

    if rope_scaling is not None:
        # LLaMA-3.1 frequency smoothing: keep high-frequency components,
        # downscale low-frequency ones, and blend linearly in between.
        orig_ctx = rope_scaling.original_context_length
        low_freq_wavelen = orig_ctx / rope_scaling.low_freq_factor
        high_freq_wavelen = orig_ctx / rope_scaling.high_freq_factor
        wavelen = 2.0 * jnp.pi / inv_freq

        scaled = inv_freq / rope_scaling.factor
        smooth = (orig_ctx / wavelen - rope_scaling.low_freq_factor) / (
            rope_scaling.high_freq_factor - rope_scaling.low_freq_factor
        )
        smoothed = (1.0 - smooth) * scaled + smooth * inv_freq

        inv_freq = jnp.where(wavelen > low_freq_wavelen, scaled, inv_freq)
        is_medium = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
        inv_freq = jnp.where(is_medium, smoothed, inv_freq)

    positions = jnp.arange(context_length, dtype=jnp.float32)
    angles = positions[:, None] * inv_freq[None, :]        # (T, head_dim/2)
    angles = jnp.concatenate([angles, angles], axis=-1)    # (T, head_dim)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate-half RoPE application (``interleaved``: rotate the pairs
    (2i, 2i+1) by the i-th frequency instead of (i, i + d/2): GPT-J's
    layout, the same tables).

    x: (batch, seq, n_heads, head_dim) — note head axis AFTER seq (our layout;
    the reference uses (b, h, t, d)).
    positions: optional (seq,) or (batch, seq) absolute positions for decode;
    defaults to arange(seq).
    """
    b, t, h, d = x.shape
    if positions is None:
        cos_t = cos[:t]                                    # (T, d)
        sin_t = sin[:t]
        cos_t = cos_t[None, :, None, :]                    # (1, T, 1, d)
        sin_t = sin_t[None, :, None, :]
    else:
        cos_t = jnp.take(cos, positions, axis=0)           # (..., d)
        sin_t = jnp.take(sin, positions, axis=0)
        if positions.ndim == 1:
            cos_t = cos_t[None, :, None, :]
            sin_t = sin_t[None, :, None, :]
        else:  # (batch, seq)
            cos_t = cos_t[:, :, None, :]
            sin_t = sin_t[:, :, None, :]

    if interleaved:
        pairs = x.astype(jnp.float32).reshape(b, t, h, d // 2, 2)
        even, odd = pairs[..., 0], pairs[..., 1]
        c, s = cos_t[..., : d // 2], sin_t[..., : d // 2]
        out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
        return out.reshape(b, t, h, d).astype(x.dtype)
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = x.astype(jnp.float32) * cos_t + rotated.astype(jnp.float32) * sin_t
    return out.astype(x.dtype)
