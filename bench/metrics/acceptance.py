"""Accepted draft tokens over drafted tokens, in %, over the decode
iterations of the window (`IterationTelemetry`: tokens_emitted - 1 of
k_drafted); 0 where the window's decode iterations drafted nothing, so
nothing was accepted. Nothing is read where the window decoded nothing."""


def read(run):
    if not run.iterations:
        return None
    drafted = sum(it.k_drafted for it in run.iterations)
    if drafted == 0:
        return 0.0
    accepted = sum(it.tokens_emitted - 1 for it in run.iterations)
    return 100.0 * accepted / drafted
