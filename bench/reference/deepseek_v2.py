"""Plain float32 reference of DeepSeek-V2's decoder, as the configuration
file `deepseek-v2-lite.8l` states it (arXiv:2405.04434, and the model's
published `modeling_deepseek.py`):

    x = embedding[token]
    per layer:  h = rms(x);  x += mla(h) @ wo
                h = rms(x);  x += ffn(h)
    logits = rms(x) @ unembed

rms(v) = v / sqrt(mean(v^2) + eps), with unit scales (the weights carry
no others). The first `first_k_dense_replace` layers' ffn is one SwiGLU
of width `intermediate_size`; every later layer's is

    sum over the top-k experts e of  w_e * expert_e(h)  +  shared(h),

a SwiGLU(w) = (silu(h @ gate) * (h @ up)) @ down per expert, of width
`moe_intermediate_size`, and for the shared experts one SwiGLU of width
`n_shared_experts * moe_intermediate_size`. The router takes
softmax(h @ router) over all experts and keeps the greedy top k: divided
by their sum where `norm_topk_prob` holds, else times
`routed_scaling_factor`.

Multi-head latent attention, expanded: per position the latent
c = rms(h @ w_kva) (eps 1e-6, the published latent norm's) and one rope
key k_r = rope(h @ w_kr) shared by every head; per head the query
[q_nope, rope(q_r)] = h @ w_q, the key [c @ w_uk, k_r] and the value
c @ w_uv. Scores are causal, scaled by 1/sqrt(qk_nope + qk_rope) times
mscale(factor, mscale_all_dim)^2. Rotary positions are YaRN's: the pair
(2i, 2i+1) (the published code takes pairs so, before it rotates halves)
turned by pos * f_i, with f_i = theta^(-2i/d) below the correction dim of
`beta_fast` rotations over `original_max_position_embeddings`, f_i /
factor above that of `beta_slow`, a linear ramp between, and cos and sin
times mscale(factor, mscale) / mscale(factor, mscale_all_dim), where
mscale(s, m) = 0.1 m ln s + 1.

Every product runs in float32 at the highest matmul precision, over the
whole sequence at once, with no cache and no batching of requests into
spans. It imports nothing of the program: the weights are the
benchmark's own (`bench.archs.deepseek_v2`), regenerated one layer at a
time; the lowered products, the embedding and the head's gaps are
`bench.reference.moe_decoder`'s.

The correctness control is this reference computed in the precision
below the configuration's (`LOWER`): every product with a weight matrix
(attention projections, router, routed, shared and dense experts, LM
head) takes both operands in float8 (e4m3) under bfloat16, with one
absmax scale per row of activations and per output column of weights, or
in bfloat16 under float32, and accumulates in float32. At each served
position it reads the float32 reference's gap of the token that the
lowered reference puts first."""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..archs.deepseek_v2 import Arch, global_weights, layer_weights
from .moe_decoder import (F32, HI, LOWER, _embed, _head_gaps, _mm,
                          _per_request, _rms)

__all__ = ["LOWER", "served_gaps"]

#: epsilon of the latent's norm (`kv_a_layernorm`, the published default)
LATENT_EPS = 1e-6


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_freq(a: Arch) -> np.ndarray:
    """The rope key's frequencies f_i, i < qk_rope / 2."""
    d = a.qk_rope
    freq = a.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not a.yarn_factor:
        return freq

    def corr(rotations):
        return (d * math.log(a.yarn_original_max_pos
                             / (rotations * 2 * math.pi))
                / (2 * math.log(a.rope_theta)))
    low = max(math.floor(corr(a.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(a.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq / a.yarn_factor * ramp + freq * (1 - ramp)


def _rotary(a: Arch, x):
    """x [N, S, heads, qk_rope] at positions 0..S-1."""
    pos = jnp.arange(x.shape[1], dtype=F32)
    ang = pos[:, None] * jnp.asarray(_yarn_freq(a), F32)[None, :]
    m = 1.0
    if a.yarn_factor:
        m = (_mscale(a.yarn_factor, a.yarn_mscale)
             / _mscale(a.yarn_factor, a.yarn_mscale_all_dim))
    cos = m * jnp.cos(ang)[None, :, None]
    sin = m * jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(a: Arch) -> float:
    scale = 1.0 / math.sqrt(a.qk_nope + a.qk_rope)
    if a.yarn_factor and a.yarn_mscale_all_dim:
        scale *= _mscale(a.yarn_factor, a.yarn_mscale_all_dim) ** 2
    return scale


def _mla(a: Arch, w, h, lower):
    n, s, d = h.shape
    hh = a.heads
    q = _mm(h, w["w_q"].reshape(d, -1), lower).reshape(
        n, s, hh, a.qk_nope + a.qk_rope)
    q_nope, q_rope = q[..., :a.qk_nope], _rotary(a, q[..., a.qk_nope:])
    c = _rms(_mm(h, w["w_kva"], lower), LATENT_EPS)
    k_rope = _rotary(a, _mm(h, w["w_kr"], lower)[:, :, None, :])
    k_nope = _mm(c, w["w_uk"].reshape(a.kv_lora, -1), lower).reshape(
        n, s, hh, a.qk_nope)
    v = _mm(c, w["w_uv"].reshape(a.kv_lora, -1), lower).reshape(
        n, s, hh, a.v_dim)
    scores = (jnp.einsum("nqhd,nshd->nhqs", q_nope, k_nope, precision=HI)
              + jnp.einsum("nqhd,nsd->nhqs", q_rope, k_rope[:, :, 0],
                           precision=HI)) * softmax_scale(a)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("nhqs,nshd->nqhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HI).reshape(n, s, hh * a.v_dim)
    return _mm(att, w["wo"], lower)


def _swiglu(h, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(h, gate, lower)) * _mm(h, up, lower), down,
               lower)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _dense_layer(a: Arch, w, x, lower: Optional[str] = None):
    x = x + _mla(a, w, _rms(x, a.rms_eps), lower)
    h = _rms(x, a.rms_eps)
    return x + _swiglu(h, w["ffn_gate"], w["ffn_up"], w["ffn_down"], lower)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _moe_layer(a: Arch, w, x, lower: Optional[str] = None):
    x = x + _mla(a, w, _rms(x, a.rms_eps), lower)
    h = _rms(x, a.rms_eps)
    probs = jax.nn.softmax(_mm(h, w["router"], lower), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top = top * a.routed_scale
    gate = jnp.sum(jax.nn.one_hot(idx, a.experts, dtype=F32)
                   * top[..., None], axis=-2)           # [N, S, E]

    def expert(e, y):
        out = _swiglu(h, w["w_gate"][e], w["w_up"][e], w["w_down"][e],
                      lower)
        return y + gate[..., e, None] * out

    y = jax.lax.fori_loop(0, a.experts, expert, jnp.zeros_like(x))
    if a.shared:
        y = y + _swiglu(h, w["shared_gate"], w["shared_up"],
                        w["shared_down"], lower)
    return x + y


def served_gaps(a: Arch, seed: int, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]], pad_to: int,
                rows_to: int, batch_to: int, lower: Optional[str] = None):
    """Teacher-forced over each prompt with its served tokens: for every
    served token, the reference's best logit minus the logit of the served
    token at the position that produced it (0 where the served token is
    the reference's own greedy choice). Returns (gaps, reference's greedy
    tokens, control gaps), each a list per request; the control gaps,
    given `lower`, are the reference's gaps of the tokens that the
    reference with its products in `lower` puts first at the same
    positions, else None. Sequences are padded at the end to `pad_to`
    tokens, which causal attention never reads, the batch to `batch_to`
    sequences and the served positions to `rows_to`, so that every run
    compiles the same shapes."""
    toks = np.zeros((max(batch_to, len(prompts)), pad_to), np.int32)
    rows, cols, chosen = [], [], []
    for i, (p, out) in enumerate(zip(prompts, served)):
        seq = list(p) + list(out)
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} tokens > {pad_to}")
        toks[i, :len(seq)] = seq
        rows += [i] * len(out)
        cols += list(range(len(p) - 1, len(p) - 1 + len(out)))
        chosen += list(out)
    if len(rows) > rows_to:
        raise ValueError(f"{len(rows)} served tokens > {rows_to}")
    pad = rows_to - len(rows)
    rows, cols, chosen = rows + [0] * pad, cols + [0] * pad, chosen + [0] * pad
    g = global_weights(a, seed)
    x = _embed(a, g, jnp.asarray(toks))
    xl = x
    for layer in range(a.layers):
        w = layer_weights(a, seed, layer)
        step = _dense_layer if layer < a.dense_layers else _moe_layer
        x = step(a, w, x, None)
        if lower is not None:
            xl = step(a, w, xl, lower)
        del w
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    gaps, best = _head_gaps(a, g, x, rows, cols,
                            jnp.asarray(chosen, jnp.int32), None)
    control = None
    if lower is not None:
        _, lower_best = _head_gaps(a, g, xl, rows, cols, best, lower)
        control, _ = _head_gaps(a, g, x, rows, cols, lower_best, None)
        control = _per_request(np.asarray(control, np.float64), served)
    return (_per_request(np.asarray(gaps, np.float64), served),
            _per_request(np.asarray(best), served), control)
