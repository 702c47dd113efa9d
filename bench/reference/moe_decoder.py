"""Plain float32 reference of a decoder-only mixture of experts, as the
configuration files `olmoe-1b-7b.8l` and `mixtral-8x7b.4l` state it:

    x = embedding[token]
    per layer:  h = rms(x);  x += attention(h) @ wo
                h = rms(x);  x += sum over the top-k experts e of
                             p_e * (silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e]
    logits = rms(x) @ unembed

rms(v) = v / sqrt(mean(v^2) + eps), with unit scales (the weights carry
no others). Attention is causal grouped-query attention (query head h
reads key/value head h // (heads / kv_heads)) over rotary positions, the
pair (2i, 2i+1) of each head turned by pos * theta^(-2i / head_dim).
The router takes softmax(h @ router) over all experts, keeps the top k,
and divides them by their sum where `norm_topk_prob` holds.

Every product runs in float32 at the highest matmul precision, over the
whole sequence at once, with no cache and no batching of requests into
spans. It imports nothing of the program: the weights are the
benchmark's own (`bench.archs.moe_decoder`), regenerated one layer at a
time.

The correctness control is this reference computed in the precision
below the configuration's (`LOWER`): every product with a weight matrix
(attention projections, router, experts, LM head) takes both operands
in float8 (e4m3) under bfloat16, with one absmax scale per row of
activations and per output column of weights, or in bfloat16 under
float32, and accumulates in float32. At each served position it reads the
float32 reference's gap of the token that the lowered reference puts
first."""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..archs.moe_decoder import Arch, global_weights, layer_weights

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: the control's precision: the nearest below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
#: the largest finite float8_e4m3fn
FP8_MAX = 448.0


def _lowered(v, axis: int, lower: Optional[str]):
    """v as float32, first put in the precision `lower`: float8_e4m3fn
    after scaling by s, the largest |v| along `axis` over the largest
    finite float8 (one scale per row of activations, per output column of
    weights), then scaled back; bfloat16 is v rounded to it."""
    v = v.astype(F32)
    if lower == "float8_e4m3fn":
        s = jnp.max(jnp.abs(v), axis=axis, keepdims=True) / FP8_MAX
        s = jnp.where(s > 0, s, 1.0)
        return (v / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    if lower == "bfloat16":
        return v.astype(jnp.bfloat16).astype(F32)
    if lower is not None:
        raise ValueError(f"no control precision {lower!r}")
    return v


def _mm(x, w, lower: Optional[str] = None):
    """x @ w in float32 at the highest precision, with both operands first
    put in the precision `lower` where it is given."""
    return jnp.matmul(_lowered(x, -1, lower), _lowered(w, 0, lower),
                      precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotary(x, theta):
    """x [N, S, heads, hd] at positions 0..S-1."""
    hd = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=F32)
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None] * freq[None, :]                  # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(a: Arch, w, x, lower: Optional[str] = None):
    n, s, d = x.shape
    g = a.heads // a.kv_heads
    h = _rms(x, a.rms_eps)
    q = _mm(h, w["wq"], lower)
    k = _mm(h, w["wk"], lower)
    v = _mm(h, w["wv"], lower)
    q = _rotary(q.reshape(n, s, a.heads, a.head_dim), a.rope_theta)
    k = _rotary(k.reshape(n, s, a.kv_heads, a.head_dim), a.rope_theta)
    v = v.reshape(n, s, a.kv_heads, a.head_dim)
    q = q.reshape(n, s, a.kv_heads, g, a.head_dim)
    scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k,
                        precision=HI) / np.sqrt(a.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("nkgqs,nskd->nqkgd", jax.nn.softmax(scores, axis=-1),
                     v, precision=HI).reshape(n, s, a.heads * a.head_dim)
    x = x + _mm(att, w["wo"], lower)

    h = _rms(x, a.rms_eps)
    probs = jax.nn.softmax(_mm(h, w["router"], lower), axis=-1)
    top, idx = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, a.experts, dtype=F32)
                   * top[..., None], axis=-2)           # [N, S, E]

    def expert(e, y):
        act = (jax.nn.silu(_mm(h, w["w_gate"][e], lower))
               * _mm(h, w["w_up"][e], lower))
        return y + gate[..., e, None] * _mm(act, w["w_down"][e], lower)

    return x + jax.lax.fori_loop(0, a.experts, expert, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(a: Arch, g, tokens):
    return g["embedding"].astype(F32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 6))
def _head_gaps(a: Arch, g, x, rows, cols, chosen,
               lower: Optional[str] = None):
    """At positions (rows, cols) of x: the gap by which the logit of the
    `chosen` token lies below the best logit, and the best token."""
    head = (g["embedding"].T if a.tie_embeddings else g["unembed"])
    h = _rms(x[rows, cols], a.rms_eps)
    logits = _mm(h, head, lower)                             # [P, V]
    best = jnp.max(logits, axis=-1)
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return best - picked, jnp.argmax(logits, axis=-1)


def served_gaps(a: Arch, seed: int, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]], pad_to: int,
                rows_to: int, batch_to: int, lower: Optional[str] = None):
    """Teacher-forced over each prompt with its served tokens: for every
    served token, the reference's best logit minus the logit of the served
    token at the position that produced it (0 where the served token is
    the reference's own greedy choice). Returns (gaps, reference's greedy
    tokens, control gaps), each a list per request; the control gaps,
    given `lower`, are the reference's gaps of the tokens that the
    reference with experts in `lower` puts first at the same positions,
    else None. Sequences are padded at the end to
    `pad_to` tokens, which causal attention never reads, the batch to
    `batch_to` sequences and the served positions to `rows_to`, so that
    every run compiles the same shapes."""
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
        x = _layer(a, w, x, None)
        if lower is not None:
            xl = _layer(a, w, xl, lower)
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


def _per_request(flat: np.ndarray, served) -> List[np.ndarray]:
    out, at = [], 0
    for s in served:
        out.append(flat[at:at + len(s)])
        at += len(s)
    return out
