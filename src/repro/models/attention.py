"""Grouped-query attention (full / sliding-window / cross) used by every
attention-bearing family. This is the canonical jnp implementation the models
run on CPU and in the dry-run; `repro.kernels.flash_attention` and
`repro.kernels.decode_attention` provide the Pallas TPU versions validated
against the same math."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import rope as rope_mod
from .layers import _dense_init

NEG_INF = -1e30


def init_attention(cfg, key, dtype):
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h * hd), dtype),
        "wk": _dense_init(ks[1], (d, hk * hd), dtype),
        "wv": _dense_init(ks[2], (d, hk * hd), dtype),
        "wo": _dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_cross_attention(cfg, key, dtype):
    """Cross-attention (whisper decoder): keys/values from encoder states."""
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    de = cfg.encoder_d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, h * hd), dtype),
        "wk": _dense_init(ks[1], (de, h * hd), dtype),
        "wv": _dense_init(ks[2], (de, h * hd), dtype),
        "wo": _dense_init(ks[3], (h * hd, d), dtype),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def attend(q, k, v, q_pos, kv_pos, *, window: int = 0, causal: bool = True,
           scale=None):
    """Masked GQA attention core.

    q: [B,T,H,D]; k,v: [B,S,Hkv,D]
    q_pos: [B,T] absolute positions of queries
    kv_pos: [B,S] absolute positions of keys (-1 marks empty cache slots)
    window: if >0, keys older than q_pos - window are masked (sliding window)
    scale: the score scale, 1/sqrt(D) where None
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[3]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv

    if scale is None:
        qf = q.astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    else:
        qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # [B,T,S] scores per kv-group, queries grouped onto kv heads
    qg = qf.reshape(b, t, hkv, group, d)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, kf)   # [B,Hkv,G,T,S]

    valid = kv_pos[:, None, :] >= 0                    # [B,1,S]
    if causal:
        valid = valid & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window and window > 0:
        valid = valid & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
    mask = valid[:, None, None, :, :]                  # [B,1,1,T,S]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no valid key (shouldn't happen for causal self-attn) -> zeros
    probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, vf)
    return out.reshape(b, t, h, dv).astype(q.dtype)


def qkv(cfg, p, x, positions):
    """Project + rope. Returns q [B,T,H,D], k/v [B,T,Hkv,D]."""
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _split_heads(x @ p["wq"], h, hd)
    k = _split_heads(x @ p["wk"], hk, hd)
    v = _split_heads(x @ p["wv"], hk, hd)
    if "q_norm" in p:
        q = _rms(q) * p["q_norm"]
        k = _rms(k) * p["k_norm"]
    pos2d = positions if positions.ndim == 2 else positions[0]
    del pos2d
    q = rope_mod.apply_positional(cfg, q, positions)
    k = rope_mod.apply_positional(cfg, k, positions)
    return q, k, v


def _rms(x, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)).astype(x.dtype)


def self_attention(cfg, p, x, positions, *, window: int = 0):
    """Full-sequence self attention (train / prefill)."""
    q, k, v = qkv(cfg, p, x, positions)
    pos2d = positions if positions.ndim == 2 else positions[0]
    out = attend(q, k, v, pos2d, pos2d, window=window, causal=True)
    b, t = out.shape[:2]
    return out.reshape(b, t, -1) @ p["wo"], (k, v)


def cached_attention(cfg, p, x, positions, k_cache, v_cache, cache_pos,
                     *, window: int = 0):
    """Decode/verify step: new tokens x [B,T,:] attend over cache + selves.

    k_cache/v_cache: [B,S_max,Hkv,D] with new keys already written.
    cache_pos: [B,S_max] absolute position per slot, -1 where empty.
    """
    q, k_new, v_new = qkv(cfg, p, x, positions)
    del k_new, v_new  # caller already wrote them into the cache
    pos2d = positions if positions.ndim == 2 else positions[0]
    out = attend(q, k_cache, v_cache, pos2d, cache_pos, window=window, causal=True)
    b, t = out.shape[:2]
    return out.reshape(b, t, -1) @ p["wo"]


def cross_attention(cfg, p, x, enc_k, enc_v):
    """x: [B,T,d]; enc_k/enc_v: [B,S_enc,H,D] precomputed at prefill."""
    h, hd = cfg.num_heads, cfg.head_dim
    q = _split_heads(x @ p["wq"], h, hd)
    b, t = q.shape[:2]
    s_enc = enc_k.shape[1]
    q_pos = jnp.zeros((b, t), jnp.int32)
    kv_pos = jnp.zeros((b, s_enc), jnp.int32)
    out = attend(q, enc_k, enc_v, q_pos, kv_pos, window=0, causal=False)
    return out.reshape(b, t, -1) @ p["wo"]


def encode_cross_kv(cfg, p, enc_out):
    """Precompute K/V of the encoder output for one decoder layer."""
    h, hd = cfg.num_heads, cfg.head_dim
    k = _split_heads(enc_out @ p["wk"], h, hd)
    v = _split_heads(enc_out @ p["wv"], h, hd)
    return k, v
