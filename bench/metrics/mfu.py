"""Model FLOP/s utilization of the whole step, in %: (prompt tokens
prefilled + output tokens delivered) in the window, times the forward
operations one token needs at the window's mean cache length
(`bench.flops.flops_per_token`), over the window's seconds and the chip's
peak. Rejected draft tokens and padding do not count."""

from bench.flops import flops_per_token


def read(run):
    work = run.prefill_tokens + run.tokens
    if work == 0:
        return None
    flops = work * flops_per_token(run.arch, run.mean_context)
    return 100.0 * flops / (run.window_s * run.peaks["flops_per_s"])
