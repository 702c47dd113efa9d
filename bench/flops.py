"""Operations and bytes the served work needs, counted from a
configuration's sizes alone. Padded rows, padded span positions, rejected
draft tokens and experts no live token routed to are not needed work, so
these counts read the same whatever implements the pass."""

from __future__ import annotations

from typing import Sequence

from .model import Arch


def attn_params(a: Arch) -> int:
    """q, k, v and output projections of one layer."""
    return (2 * a.d_model * a.heads * a.head_dim
            + 2 * a.d_model * a.kv_heads * a.head_dim)


def expert_params(a: Arch) -> int:
    """gate, up and down matrices of one expert."""
    return 3 * a.d_model * a.expert_width


def dense_params(a: Arch) -> int:
    """Weights every pass reads whatever the routing: attention, router
    and norms of every layer, the final norm and the output head (the
    embedding is only indexed, see `pass_bytes`)."""
    per_layer = attn_params(a) + a.d_model * a.experts + 2 * a.d_model
    return a.layers * per_layer + a.d_model + a.d_model * a.vocab


def flops_per_token(a: Arch, context: float) -> float:
    """Forward operations of one token at `context` earlier positions:
    2 per multiply-add of every matrix the token meets (attention
    projections, router, its top-k experts, output head), and 4 * heads *
    head_dim per earlier position and layer for the scores and the
    weighted sum of values."""
    matmul = (a.layers * (attn_params(a) + a.d_model * a.experts
                          + a.top_k * expert_params(a))
              + a.d_model * a.vocab)
    attn = 4 * a.layers * a.heads * a.head_dim * (context + 1)
    return 2.0 * matmul + attn


def kv_bytes_per_position(a: Arch) -> int:
    """Key and value bytes one position holds over all layers."""
    return 2 * a.layers * a.kv_heads * a.head_dim * a.dtype_bytes


def pass_flops(a: Arch, tokens: int, contexts: Sequence[int]) -> float:
    """One pass: `tokens` live tokens at the rows' mean cache length."""
    ctx = sum(contexts) / len(contexts) if contexts else 0.0
    return tokens * flops_per_token(a, ctx)


def pass_bytes(a: Arch, tokens: int, contexts: Sequence[int],
               union_experts_mean: float) -> float:
    """Bytes one pass needs to move: the dense weights once; each layer's
    union of routed experts (`union_experts_mean` is the mean over layers)
    once; the keys and values of every live row's cached positions, read,
    and of the live tokens, written; an embedding row in and a row of
    logits (in the served type) out per live token."""
    b = a.dtype_bytes
    weights = (dense_params(a)
               + a.layers * union_experts_mean * expert_params(a)) * b
    kv = kv_bytes_per_position(a) * (sum(contexts) + tokens)
    rows = tokens * (a.d_model + a.vocab) * b
    return weights + kv + rows


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of operations over peak
    operations and bytes over peak bandwidth."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
