"""The `moe_ffn` scope's share of its roofline, in %: the sum over the
traced passes of the least time the chip needs for the scope's needed work
(`bench.flops.scope_counts`: the router and the union of experts the live
tokens routed to, in every layer), over the self time of the passes'
operations under the scope (`bench.stagereduce`, as `moe_ffn_ms` reads
it). Nothing is read where no operation carries the scope, or where the
trace's passes do not pair one to one with the engine's steps in the
traced window."""

from bench.flops import scope_least_time
from bench.stagereduce import for_run

SCOPE = "moe_ffn"


def read(run):
    st = for_run(run)
    if st is None or not st.scope_s.get(SCOPE):
        return None
    n = len(st.base.pass_s)
    if n != st.base.steps_traced or n > len(run.steps):
        return None
    return 100.0 * scope_least_time(run, SCOPE, n) / st.scope_s[SCOPE]
