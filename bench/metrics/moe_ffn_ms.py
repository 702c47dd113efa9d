"""Device time of the MoE FFN, in ms per traced pass: the self time of the
passes' operations under the program's `moe_ffn` scope
(`bench.stagereduce`), over the passes. Nothing is read where no
operation carries the scope."""

from bench.stagereduce import for_run


def read(run):
    st = for_run(run)
    if st is None or "moe_ffn" not in st.scope_s:
        return None
    return 1e3 * st.scope_s["moe_ffn"] / len(st.base.pass_s)
