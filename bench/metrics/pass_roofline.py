"""The served pass's share of its roofline, in %: the sum over the traced
passes of the least time the chip needs for the pass's needed work
(`bench.flops`: live tokens, the union of experts live tokens routed to,
live KV, dense weights, logits out), over the sum of the passes' device
time in the trace. Nothing is read where the trace's passes do not pair
one to one with the engine's steps in the traced window."""

from bench.flops import least_time, pass_bytes, pass_flops


def read(run):
    tr = run.trace
    n = 0 if tr is None else len(tr.pass_s)
    if not n or n != tr.steps_traced or n > len(run.steps):
        return None
    need = 0.0
    for step, ctx in zip(run.steps[:n], run.step_ctx[:n]):
        need += least_time(
            pass_flops(run.arch, step.tokens_in_flight, ctx),
            pass_bytes(run.arch, step.tokens_in_flight, ctx,
                       step.union_experts),
            run.peaks)
    return 100.0 * need / sum(tr.pass_s)
