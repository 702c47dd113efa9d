"""The stage reduction (bench/stagereduce.py) on a synthetic trace whose
program spans, scopes, passes and idle gaps are known by construction,
and on a few engine steps recorded on a TPU v5e; and the four per-layer
metrics that read it."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec as specs  # noqa: E402
from bench import stagereduce as sr  # noqa: E402
from bench import tracereduce as tr  # noqa: E402
from bench import xspace  # noqa: E402
from bench.record import RunRecord  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tpu_v5e_stages.xplane.pb"
OLD_RECORDED = Path(__file__).parent / "data" / "tpu_v5e_3steps.xplane.pb"
METRICS = ("pre_pass_host_ms", "post_pass_host_ms", "moe_ffn_ms",
           "attention_ms")

# ns; the geometry of test_bench_trace.py's synthetic trace, with the
# program's spans inside its two steps and a layer scope on each operation
WINDOW = (0, 100_000)
STEPS = [(10_000, 40_000), (50_000, 90_000)]
DELIVER = [(40_000, 50_000)]
PASSES = [(20_000, 35_000), (60_000, 80_000)]
PROGRAM = [  # (name, start, end, step argument)
    ("sched.step", 10_500, 39_500, None),
    ("sched.admit", 10_500, 11_500, None),
    ("engine.join", 10_600, 11_400, None),
    ("engine.step", 12_000, 39_000, 7),
    ("engine.plan", 12_000, 14_000, None),
    ("engine.draft", 14_000, 16_000, None),
    ("engine.pack", 16_000, 18_000, None),
    ("engine.dispatch", 18_000, 21_000, None),
    ("engine.fetch_logits", 21_000, 36_000, None),
    ("engine.verify", 36_000, 37_000, None),
    ("engine.rollback", 37_000, 37_500, None),
    ("engine.cost", 37_500, 38_500, None),
    ("engine.feedback", 38_500, 39_000, None),
    ("sched.retire", 39_100, 39_400, None),
    ("sched.step", 50_000, 90_000, None),
    ("engine.step", 52_000, 88_000, 8),
    ("engine.plan", 52_000, 55_000, None),
    ("engine.dispatch", 55_000, 61_000, None),
    ("engine.fetch_logits", 61_000, 83_000, None),
    ("engine.cost", 83_000, 88_000, None),
]
OPS = [  # (metadata id, start, end): tf_op and name below
    (3, 20_000, 24_000), (4, 24_000, 30_000), (5, 31_000, 35_000),
    (8, 59_000, 81_000), (9, 60_000, 80_000),
    (6, 36_000, 37_000), (7, 95_000, 120_000)]
OP_META = {
    3: ("fusion.1", "jit(step)/while/body/closed_call/attention/dot:"),
    4: ("fusion.2", "jit(step)/while/body/closed_call/moe_ffn/jit(f)/x:"),
    5: ("fusion.3", "jit(step)/lm_head/dot_general:"),
    6: ("where.4", "jit(small)/select_n:"),
    7: ("copy.5", ""),
    8: ("while.6", "jit(step)/while:"),
    9: ("dynamic-slice_fusion.7", "jit(step)/while/body/dynamic_slice:"),
}


def _events(meta_id, spans, stats=""):
    return "\n".join(
        f"events {{ metadata_id: {meta_id} offset_ps: {a * 1000} "
        f"duration_ps: {(b - a) * 1000} {stats} }}" for a, b in spans)


def synthetic_bytes() -> bytes:
    from google.protobuf import text_format
    names = sorted({n for n, *_ in PROGRAM})
    ids = {n: 10 + i for i, n in enumerate(names)}
    program = "\n".join(
        _events(ids[n], [(a, b)],
                "" if step is None else
                f"stats {{ metadata_id: 1 int64_value: {step} }}")
        for n, a, b, step in PROGRAM)
    host_meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in ids.items())
    op_meta = "\n".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" '
        + (f'stats {{ metadata_id: 1 str_value: "{op}" }}' if op else "")
        + " } }" for k, (n, op) in OP_META.items())
    txt = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_events(1, [WINDOW])} {_events(2, STEPS)} {_events(3, DELIVER)}
    {program} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.deliver" }} }}
  {host_meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "step" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events(1, PASSES[:1])} {_events(2, PASSES[1:])}
    {_events(11, [(36_000, 37_000)])} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {" ".join(_events(k, [(a, b)]) for k, a, b in OPS)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(1)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_step(3)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_small(2)" }} }}
  {op_meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
"""
    return text_format.Parse(txt, xspace.XSpace()).SerializeToString()


@pytest.fixture
def synthetic(tmp_path):
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(synthetic_bytes())
    return path


def test_scope_of_a_name_stack():
    assert sr.scope_of("jit(<lambda>)/while/body/closed_call/moe_ffn/"
                       "jit(_take)/gather:") == "moe_ffn"
    assert sr.scope_of("jit(<lambda>)/lm_head/dot_general:") == "lm_head"
    assert sr.scope_of("jit(<lambda>)/while/body/dynamic_slice:") == \
        "while/body"
    assert sr.scope_of("jit(<lambda>)/while:") == "top"
    assert sr.scope_of("") == "unnamed"
    # an operation named like a scope is no scope
    assert sr.scope_of("jit(f)/attention:") == "top"


def test_innermost_pieces_of_nested_spans():
    E = sr.Event
    spans = [E("outer", 0, 10), E("a", 1, 2), E("b", 4, 4), E("c", 5, 1),
             E("d", 12, 1)]
    assert sr.innermost(spans) == [
        ("outer", 0, 1), ("a", 1, 3), ("outer", 3, 4), ("b", 4, 5),
        ("c", 5, 6), ("b", 6, 8), ("outer", 8, 10), ("d", 12, 13)]


def test_loaded_trace_reduces_as_profile_data_does(synthetic):
    """`tracereduce` reads the stage reduction's own loading of a trace
    exactly as it reads JAX's `ProfileData` of it."""
    from jax.profiler import ProfileData
    old = tr.reduce(ProfileData.from_serialized_xspace(synthetic_bytes()))
    st = sr.reduce(sr.load(str(synthetic)))
    assert st.base == old
    assert sr.reduce(sr.load(str(OLD_RECORDED))).base == \
        tr.reduce(tr.load(str(OLD_RECORDED)))


def test_synthetic_idle_charged_to_program_spans(synthetic):
    st = sr.reduce(sr.load(str(synthetic)))
    idle = {k: v * 1e9 for k, v in st.idle_gaps}
    # see test_bench_trace.py for the gaps; here each is cut by the
    # innermost program span open in it
    assert idle == pytest.approx({
        "host.other": 15_000, "client.deliver": 10_000,
        "step.before_pass": 500,
        "step.before_pass/sched.admit": 200,
        "step.before_pass/engine.join": 800,
        "step.before_pass/sched.step": 2_500,
        "step.before_pass/engine.plan": 5_000,
        "step.before_pass/engine.draft": 2_000,
        "step.before_pass/engine.pack": 2_000,
        "step.before_pass/engine.dispatch": 6_000,
        "step.in_pass/engine.fetch_logits": 1_000,
        "step.after_pass/engine.fetch_logits": 3_000,
        "step.after_pass/engine.rollback": 500,
        "step.after_pass/engine.cost": 6_000,
        "step.after_pass/engine.feedback": 500,
        "step.after_pass/sched.step": 2_200,
        "step.after_pass/sched.retire": 300,
        "step.after_pass": 500})
    # the old labels are the sums by prefix
    old = dict((k, v) for k, v in st.base.idle_gaps)
    assert st.idle_by_prefix() == pytest.approx(old)
    assert st.engine_steps == 2 and st.program_spans == len(PROGRAM)
    assert st.idle_in(sr.PRE_PASS) * 1e9 == pytest.approx(15_000)
    assert st.idle_in(sr.POST_PASS) * 1e9 == pytest.approx(11_000)
    summary = sr.summary(st)
    assert summary["idle_labelled_share"] == pytest.approx(32 / 33)
    assert summary["pre_pass_host_ms"] == pytest.approx(7.5e-3)


def test_synthetic_device_time_charged_to_scopes(synthetic):
    st = sr.reduce(sr.load(str(synthetic)))
    # pass 1: [20,24] attention, [24,30] moe_ffn, [31,35] lm_head; pass 2:
    # the loop's own slice [60,80] (the loop itself began before the pass)
    assert {k: v * 1e9 for k, v in st.scope_s.items()} == pytest.approx(
        {"attention": 4_000, "moe_ffn": 6_000, "lm_head": 4_000,
         "while/body": 20_000})
    assert sum(st.scope_s.values()) == pytest.approx(st.pass_busy_s)
    assert st.pass_busy_s == pytest.approx(34_000e-9)
    ops = dict(st.device_ops)
    assert ops["while/body/dynamic-slice_fusion.7"] == pytest.approx(20e-6)
    assert ops["moe_ffn/fusion.2"] == pytest.approx(6e-6)
    assert ops["unnamed/copy.5"] == pytest.approx(5e-6)        # clipped
    assert ops["top/while.6"] == pytest.approx(2e-6)


def _run(trace):
    return RunRecord(arch=None, peaks={}, setup_s=1.0, window_s=1.0,
                     tokens=0, gaps=[], ttfts=[], steps=[], step_ctx=[],
                     iterations=[], compiles=0, trace=trace)


def _read(run):
    return specs.read_metrics([{"name": n, "unit": "ms"} for n in METRICS],
                              run)


def test_metrics_read_the_newest_trace(synthetic, monkeypatch):
    monkeypatch.setattr(sr, "TRACES", synthetic.parent)
    from jax.profiler import ProfileData
    run = _run(tr.reduce(ProfileData.from_serialized_xspace(
        synthetic_bytes())))
    got = {k: v["value"] for k, v in _read(run).items()}
    assert got == pytest.approx({
        "pre_pass_host_ms": 7.5e-3, "post_pass_host_ms": 5.5e-3,
        "moe_ffn_ms": 3e-3, "attention_ms": 2e-3})


def test_metrics_read_nothing_without_spans_or_scopes(synthetic,
                                                      monkeypatch):
    """A program without spans or scopes (the trace recorded before they
    existed), an untraced run, or a trace of another window: the four
    metrics are left out, and nothing raises."""
    monkeypatch.setattr(sr, "TRACES", OLD_RECORDED)
    assert _read(_run(tr.reduce(tr.load(str(OLD_RECORDED))))) == {}
    assert _read(_run(None)) == {}
    monkeypatch.setattr(sr, "TRACES", synthetic.parent)
    assert _read(_run(tr.reduce(tr.load(str(OLD_RECORDED))))) == {}
    monkeypatch.setattr(sr, "TRACES", synthetic.parent / "none")
    assert _read(_run(tr.reduce(tr.load(str(OLD_RECORDED))))) == {}


def test_recorded_tpu_stages():
    """A few engine steps of the tiny test cell, traced on a TPU v5e: the
    host spans and the device events share one clock, every step's stages
    are there, and the scopes name the passes' operations."""
    trace = sr.load(str(RECORDED))
    st = sr.reduce(trace)
    base = st.base
    assert base == tr.reduce(tr.load(str(RECORDED)))
    assert base.planes == ["/device:TPU:0"]
    assert len(base.pass_s) == base.steps_traced == st.engine_steps >= 4
    spans = [e for p in trace.planes for ln in p.lines for e in ln.events
             if e.name.startswith(sr.PROGRAM_SPANS)]
    passes = sorted((e.start_ns, e.start_ns + e.duration_ns)
                    for p in trace.planes if p.name == base.planes[0]
                    for ln in p.lines if ln.name == tr.MODULES_LINE
                    for e in ln.events
                    if tr.program_name(e.name) == base.pass_name)

    def starts(name):
        return sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in spans if e.name == name)

    dispatch, fetch = starts("engine.dispatch"), starts("engine.fetch_logits")
    assert len(dispatch) == len(fetch) == len(passes)
    for (d0, _), (p0, p1), (_, f1) in zip(dispatch, passes, fetch):
        assert d0 < p0            # the pass starts after it is dispatched
        assert p1 <= f1           # the logits are fetched after it ends
    # each step's stages, in order
    names = [e.name for e in sorted(spans, key=lambda e: e.start_ns)
             if e.name in sr.PRE_PASS + sr.POST_PASS]
    assert names == [n for n in sr.PRE_PASS + sr.POST_PASS
                     if n != "engine.prefetch"] * len(passes)
    # the scopes and the loop's remainder cover the passes' busy time
    assert {"attention", "moe_ffn", "lm_head"} <= set(st.scope_s)
    assert sum(st.scope_s.values()) == pytest.approx(st.pass_busy_s,
                                                     rel=1e-9)
    assert st.pass_busy_s == pytest.approx(sum(base.pass_s), rel=0.05)
    # idle time inside the steps is charged to program spans
    old = dict((k, v) for k, v in base.idle_gaps)
    assert st.idle_by_prefix() == pytest.approx(old, rel=1e-9)
    assert sr.summary(st)["idle_labelled_share"] > 0.9
    assert st.idle_in(sr.POST_PASS) > 0 and st.idle_in(sr.PRE_PASS) > 0
