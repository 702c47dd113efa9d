"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

The KV cache stores only the compressed latent c_kv (kv_lora_rank dims) plus
a single shared RoPE key (qk_rope_dim dims) per position. Prefill/train use
the naive expanded form; decode uses the *absorbed* form (W_UK folded into
the query, W_UV folded into the output) so per-step work reads only the
latent cache — the property that makes MLA decode cheap and that shifts the
MoE verification bottleneck squarely onto the experts (paper §2.4)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .attention import attend, NEG_INF
from .layers import _dense_init
from .rope import apply_rope_scaled, yarn_mscale


def init_mla(cfg, key, dtype):
    d, h = cfg.d_model, cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_kva": _dense_init(ks[0], (d, cfg.kv_lora_rank), dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "w_kr": _dense_init(ks[1], (d, rope), dtype),
        "w_uk": _dense_init(ks[2], (cfg.kv_lora_rank, h, nope), dtype),
        "w_uv": _dense_init(ks[3], (cfg.kv_lora_rank, h, vdim), dtype),
        "wo": _dense_init(ks[4], (h * vdim, d), dtype),
    }
    if cfg.q_lora_rank:
        p["w_qa"] = _dense_init(ks[5], (d, cfg.q_lora_rank), dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["w_qb"] = _dense_init(ks[6], (cfg.q_lora_rank, h, nope + rope), dtype)
    else:
        p["w_q"] = _dense_init(ks[7], (d, h, nope + rope), dtype)
    return p


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def softmax_scale(cfg) -> float:
    """1/sqrt(qk_nope + qk_rope), times mscale(factor, mscale_all_dim)^2
    under YaRN (DeepSeek-V2's attention temperature)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _queries(cfg, p, x, pos2d):
    """-> q_nope [B,T,H,nope], q_rope [B,T,H,rope] (roped)."""
    nope = cfg.qk_nope_dim
    if cfg.q_lora_rank:
        qa = _rms(x @ p["w_qa"], p["q_norm"])
        q = jnp.einsum("btl,lhd->bthd", qa, p["w_qb"])
    else:
        q = jnp.einsum("btd,dhe->bthe", x, p["w_q"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope_scaled(cfg, q_rope, pos2d)
    return q_nope, q_rope


def latent_kv(cfg, p, x, pos2d):
    """Compress x -> (c_kv [B,T,R], k_rope [B,T,rope]) — what gets cached."""
    c_kv = _rms(x @ p["w_kva"], p["kv_norm"])
    k_rope = apply_rope_scaled(cfg, (x @ p["w_kr"])[:, :, None, :], pos2d)[:, :, 0]
    return c_kv, k_rope


def mla_full(cfg, p, x, pos2d):
    """Train/prefill: expand the latent into per-head K/V and run standard
    MHA. Returns (out [B,T,d], (c_kv, k_rope)) for caching."""
    b, t, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _queries(cfg, p, x, pos2d)
    c_kv, k_rope = latent_kv(cfg, p, x, pos2d)
    k_nope = jnp.einsum("btl,lhd->bthd", c_kv, p["w_uk"])
    v = jnp.einsum("btl,lhd->bthd", c_kv, p["w_uv"])
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = attend(q, k, v, pos2d, pos2d, window=0, causal=True,
                 scale=softmax_scale(cfg))
    out = out.reshape(b, t, -1) @ p["wo"]
    return out, (c_kv, k_rope)


def mla_absorbed(cfg, p, x, pos2d, ckv_cache, krope_cache, cache_pos,
                 *, window: int = 0):
    """Decode/verify: attention in latent space over the compressed cache.

    ckv_cache: [B,R,kv_lora] (new entries already written)
    krope_cache: [B,R,rope]
    cache_pos: [B,R] absolute positions, -1 = empty.
    """
    b, t, _ = x.shape
    scale = softmax_scale(cfg)
    q_nope, q_rope = _queries(cfg, p, x, pos2d)
    # absorb W_UK into the query: q_lat [B,T,H,R]
    q_lat = jnp.einsum("bthd,lhd->bthl", q_nope.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    scores = (jnp.einsum("bthl,bsl->bhts", q_lat, ckv_cache.astype(jnp.float32))
              + jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                           krope_cache.astype(jnp.float32))) * scale
    valid = (cache_pos[:, None, :] >= 0) & (cache_pos[:, None, :] <= pos2d[:, :, None])
    if window:
        valid = valid & (cache_pos[:, None, :] > pos2d[:, :, None] - window)
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(valid[:, None, :, :].any(-1, keepdims=True), probs, 0.0)
    out_lat = jnp.einsum("bhts,bsl->bthl", probs, ckv_cache.astype(jnp.float32))
    out = jnp.einsum("bthl,lhd->bthd", out_lat, p["w_uv"].astype(jnp.float32))
    return (out.reshape(b, t, -1) @ p["wo"].astype(jnp.float32)).astype(x.dtype)
