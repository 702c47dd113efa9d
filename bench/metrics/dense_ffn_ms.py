"""Device time of the leading dense layers' FFN, in ms per traced pass:
the self time of the passes' operations with the program's `dense_ffn`
scope on their name stack, over the passes. The scope is not one of
`bench.stagereduce.SCOPES`, so `stagereduce` charges such an operation to
the stack between the jitted function and the operation
(`while/body/closed_call/dense_ffn`, or `.../dense_ffn/jit(silu)`), and
this reads every such stack. Nothing is read where no operation carries
the scope.

On the TPU the compiler copies the layer's weights into on-chip memory
by asynchronous copies scheduled ahead of it; those copies carry no
scope, so this holds the layer's matmuls and activation, not the
weights' HBM reads, and may read below their bytes over HBM bandwidth."""

from bench.stagereduce import for_run

SCOPE = "dense_ffn"


def read(run):
    st = for_run(run)
    if st is None:
        return None
    under = [s for scope, s in st.scope_s.items()
             if SCOPE in scope.split("/")]
    if not under:
        return None
    return 1e3 * sum(under) / len(st.base.pass_s)
