"""The trace reduction (bench/tracereduce.py) on a synthetic trace whose
busy time, passes and idle gaps are known by construction, and on a small
trace recorded on a TPU v5e."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracereduce as tr  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tpu_v5e_3steps.xplane.pb"

# ns; host spans and device events share one clock, as in a real trace
WINDOW = (0, 100_000)
STEPS = [(10_000, 40_000), (50_000, 90_000)]
DELIVER = [(40_000, 50_000)]
PASSES = [(20_000, 35_000), (60_000, 80_000)]
OPS = [(20_000, 24_000), (24_000, 30_000), (31_000, 35_000),   # pass 1
       (60_000, 80_000),                                         # pass 2
       (95_000, 120_000)]                                        # clipped


def _events(meta_id, spans, base=0):
    return "\n".join(
        f"events {{ metadata_id: {meta_id} offset_ps: {(a - base) * 1000} "
        f"duration_ps: {(b - a) * 1000} }}" for a, b in spans)


def synthetic_trace():
    from jax.profiler import ProfileData
    txt = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_events(1, [WINDOW])} {_events(2, STEPS)} {_events(3, DELIVER)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.deliver" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events(1, PASSES[:1])} {_events(7, PASSES[1:])}
    {_events(2, [(36_000, 37_000)])} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events(3, OPS[:3])} {_events(4, OPS[3:4])} {_events(5, OPS[4:])}
    {_events(6, [(36_000, 37_000)])} {_events(8, [(59_000, 81_000)])} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(1)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_small(2)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "fusion.2" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "copy.3" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "where.4" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_step(3)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name:
    "%while.9 = (s32[], bf16[8,1,64]{{2,1,0}}) while(%t), body=%b" }} }}
}}
"""
    return ProfileData.from_text_proto(txt)


def test_names_and_self_times():
    assert tr.program_name("jit__lambda(1092226)") == "jit__lambda"
    assert tr.op_name("%fusion.3 = bf16[64,8,1024]{2,1,0:T(8,128)} "
                      "fusion(bf16[64] %x), kind=kLoop") == \
        "fusion.3 bf16[64,8,1024]"
    got = tr.self_times([("outer", 0, 10), ("a", 1, 3), ("b", 4, 8),
                         ("c", 5, 6), ("d", 12, 13)])
    assert dict(got) == {"outer": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_union_and_gaps_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(0, 3), (5, 8)], (0, 10)) == [(3, 5), (8, 10)]
    assert tr.gaps([], (2, 4)) == [(2, 4)]


def test_synthetic_trace_reduction():
    red = tr.reduce(synthetic_trace())
    # busy: [20,30] + [31,35] + [36,37] + [59,81] (the loop around pass
    # 2's fusion) + [95,100] (clipped)
    busy_ns = 10_000 + 4_000 + 1_000 + 22_000 + 5_000
    assert red.window_s == pytest.approx(100_000e-9)
    assert red.busy_s == pytest.approx(busy_ns * 1e-9)
    # the two variants of jit_step are one program
    assert red.pass_name == "jit_step"
    assert red.pass_s == pytest.approx([15_000e-9, 20_000e-9])
    ops = dict((n, s) for n, s in red.device_ops)
    assert ops["fusion.1"] == pytest.approx(14_000e-9)   # 4 + 6 + 4 us
    # fusion.2 runs inside the while loop, which keeps no self time
    assert ops["fusion.2"] == pytest.approx(20_000e-9)
    assert ops["while.9 (s32[],"] == pytest.approx(2_000e-9)
    assert ops["copy.3"] == pytest.approx(5_000e-9)       # clipped
    idle = dict((n, s) for n, s in red.idle_gaps)
    # gaps: [0,20]: [0,10] before any span, [10,20] in step 1 before its
    # pass; [30,31] in pass 1; [35,36] after it; [37,59]: [37,40] after
    # pass 1, [40,50] delivering, [50,59] step 2 before its pass;
    # [81,95]: [81,90] after pass 2, [90,95] after the last span
    assert idle["host.other"] == pytest.approx(15_000e-9)
    assert idle["step.before_pass"] == pytest.approx(19_000e-9)
    assert idle["step.in_pass"] == pytest.approx(1_000e-9)
    assert idle["step.after_pass"] == pytest.approx(13_000e-9)
    assert idle["client.deliver"] == pytest.approx(10_000e-9)
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_trace_that_stops_early_ends_the_traced_window():
    """The device trace records pass 1 whole, part of pass 2, and stops:
    step 3 begins and ends after its last operation, so the traced window
    ends with pass 1 and holds one step and one pass."""
    from jax.profiler import ProfileData
    txt = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_events(1, [WINDOW])} {_events(2, STEPS + [(92_000, 98_000)])} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.step" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events(1, PASSES[:1])} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events(3, OPS[:3] + [(60_000, 70_000)])} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(1)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
}}
"""
    red = tr.reduce(ProfileData.from_text_proto(txt))
    assert red.window_s == pytest.approx(35_000e-9)
    assert red.cut_s == pytest.approx(65_000e-9)
    assert red.busy_s == pytest.approx(14_000e-9)
    assert red.steps_traced == 1 and len(red.pass_s) == 1
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(
        red.window_s - red.busy_s)
    # the full synthetic trace runs to the window's end: nothing is cut
    full = tr.reduce(synthetic_trace())
    assert full.cut_s == 0 and full.steps_traced == 2


def test_trace_without_window_or_device_is_refused():
    from jax.profiler import ProfileData
    no_window = ProfileData.from_text_proto(
        'planes { id: 2 name: "/device:TPU:0" }')
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(no_window)
    no_device = ProfileData.from_text_proto("""
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }""")
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce(no_device)


def test_recorded_tpu_trace():
    """Three annotated steps of one small program, traced on a TPU v5e:
    three passes, busy time inside the window, idle time labelled."""
    red = tr.reduce(tr.load(str(RECORDED)))
    assert red.planes == ["/device:TPU:0"]
    assert len(red.pass_s) == 3
    assert 0 < red.busy_s < red.window_s
    # only the one program ran: its executions cover the busy time
    assert sum(red.pass_s) == pytest.approx(red.busy_s, rel=0.01)
    assert red.device_ops and all(s > 0 for _, s in red.device_ops)
    idle = dict((n, s) for n, s in red.idle_gaps)
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    # each step sleeps 3 ms before its program, each delivery 2 ms
    assert idle["step.before_pass"] > 3 * 3e-3 * 0.9
    assert idle["client.deliver"] > 2 * 2e-3 * 0.9
