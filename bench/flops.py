"""Operations and bytes the served work needs, counted from a
configuration's sizes alone by the architecture module that defines its
`Arch` (`bench.archs`). Padded rows, padded span positions, rejected draft
tokens and experts no live token routed to are not needed work, so these
counts read the same whatever implements the pass."""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from . import archs


def attn_params(a) -> int:
    """Attention weights of one layer."""
    return archs.of(a).attn_params(a)


def expert_params(a) -> int:
    """Weights of one routed expert."""
    return archs.of(a).expert_params(a)


def dense_params(a) -> int:
    """Weights every pass reads whatever the routing."""
    return archs.of(a).dense_params(a)


def flops_per_token(a, context: float) -> float:
    """Forward operations of one token at `context` earlier positions."""
    return archs.of(a).flops_per_token(a, context)


def kv_bytes_per_position(a) -> int:
    """Cache bytes one position holds over all layers."""
    return archs.of(a).kv_bytes_per_position(a)


def pass_bytes(a, tokens: int, contexts: Sequence[int],
               union_experts_mean: float) -> float:
    """Bytes one pass of `tokens` live tokens needs to move, over rows of
    `contexts` cached positions, `union_experts_mean` experts a layer."""
    return archs.of(a).pass_bytes(a, tokens, contexts, union_experts_mean)


def scope_counts(a, tokens: int, contexts: Sequence[int],
                 union_experts_mean: float
                 ) -> Dict[str, Tuple[float, float]]:
    """(operations, bytes) one pass needs under each program scope the
    architecture counts (`attention`, `moe_ffn`)."""
    return archs.of(a).scope_counts(a, tokens, contexts, union_experts_mean)


def pass_flops(a, tokens: int, contexts: Sequence[int]) -> float:
    """One pass: `tokens` live tokens at the rows' mean cache length."""
    ctx = sum(contexts) / len(contexts) if contexts else 0.0
    return tokens * flops_per_token(a, ctx)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of operations over peak
    operations and bytes over peak bandwidth."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def scope_least_time(run, scope: str, n: int) -> float:
    """The sum, over the first `n` steps of `run`'s window, of the least
    time the chip needs for `scope`'s share of each step's pass."""
    return sum(
        least_time(*scope_counts(run.arch, step.tokens_in_flight, ctx,
                                 step.union_experts)[scope], run.peaks)
        for step, ctx in zip(run.steps[:n], run.step_ctx[:n]))
