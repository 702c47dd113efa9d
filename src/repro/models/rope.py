"""Rotary position embeddings: standard, YaRN-scaled (DeepSeek-V2),
ChatGLM 2-D, and Qwen2-VL M-RoPE.

All functions operate on tensors shaped [..., seq, heads, head_dim] and take
explicit integer position ids so the same code serves prefill (positions
0..S-1) and speculative decode steps (positions L..L+K).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _rotate_half_pairs(x):
    """Rotate interleaved pairs (x0,x1) -> (-x1,x0) on the last dim."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def rope_angles(positions, dim: int, theta: float):
    """positions [...,S] -> angles [...,S,dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions[..., None].astype(jnp.float32) * inv_freq


def apply_rope(x, positions, theta: float = 10000.0, *, inv_freq=None,
               scale: float = 1.0):
    """Standard RoPE over the full head_dim. x: [B,S,H,D], positions: [B,S].
    `inv_freq` [D/2] replaces theta's frequencies (YaRN); `scale`
    multiplies cos and sin (YaRN's attention factor)."""
    d = x.shape[-1]
    if inv_freq is None:
        ang = rope_angles(positions, d, theta)      # [B,S,d/2]
    else:
        ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    out = x.astype(jnp.float32) * cos + _rotate_half_pairs(x.astype(jnp.float32)) * sin
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor 0.1 * mscale * ln(factor) + 1 (1 unscaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max_pos: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies [dim/2]: pair i keeps
    theta^(-2i/dim) below the correction dim of `beta_fast` rotations over
    `original_max_pos` positions, takes it divided by `factor` above the
    correction dim of `beta_slow`, and a linear ramp between the two."""
    def corr(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope_scaled(cfg, x, positions):
    """Standard RoPE at cfg.rope_theta, YaRN-scaled where cfg.yarn_factor
    is set: its frequencies, and cos/sin times mscale / mscale_all_dim."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    inv = yarn_inv_freq(x.shape[-1], cfg.rope_theta, cfg.yarn_factor,
                        cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return apply_rope(x, positions, inv_freq=jnp.asarray(inv), scale=scale)


def apply_rope_2d(x, positions, theta: float = 10000.0):
    """ChatGLM-style 2-D RoPE: rotate only the first half of head_dim,
    leave the second half untouched (the '2d' scheme of GLM)."""
    d = x.shape[-1]
    half = d // 2
    x_rot, x_pass = x[..., :half], x[..., half:]
    ang = rope_angles(positions, half, theta)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, :, None, :]
    rot = x_rot.astype(jnp.float32) * cos + _rotate_half_pairs(x_rot.astype(jnp.float32)) * sin
    return jnp.concatenate([rot.astype(x.dtype), x_pass], axis=-1)


def apply_mrope(x, positions_3d, sections, theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE. positions_3d: [3,B,S] (t,h,w ids);
    `sections` splits head_dim//2 frequency slots among (t,h,w)."""
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    # angles per modality: [3,B,S,d/2]
    ang = positions_3d[..., None].astype(jnp.float32) * inv_freq
    # select which modality drives each frequency slot
    sel = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)
    ])                                              # [d/2]
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang, 0, -1),                   # [B,S,d/2,3]
        sel[None, None, :, None], axis=-1)[..., 0]  # [B,S,d/2]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, :, None, :]
    out = x.astype(jnp.float32) * cos + _rotate_half_pairs(x.astype(jnp.float32)) * sin
    return out.astype(x.dtype)


def apply_positional(cfg, x, positions):
    """Dispatch on cfg.rope_variant. `positions` is [B,S] int32, or [3,B,S]
    for mrope."""
    if cfg.rope_variant == "none":
        return x
    if cfg.rope_variant == "2d":
        return apply_rope_2d(x, positions, cfg.rope_theta)
    if cfg.rope_variant == "mrope":
        if positions.ndim == 2:  # text-only fallback: same id on all three
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return apply_rope_scaled(cfg, x, positions)
