"""Device-idle time, in ms per traced engine step, while the host ran the
engine's stages after the pass (`engine.fetch_logits`, `engine.verify`,
`engine.rollback`, `engine.cost`, `engine.feedback`): the idle time
`bench.stagereduce` charges to those program spans, over the
`engine.step` spans begun in the traced window. Nothing is read where
the trace holds no program span."""

from bench.stagereduce import POST_PASS, for_run


def read(run):
    st = for_run(run)
    if st is None or not st.engine_steps:
        return None
    return 1e3 * st.idle_in(POST_PASS) / st.engine_steps
