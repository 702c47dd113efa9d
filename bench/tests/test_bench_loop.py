"""The client loop's end-to-end metrics (tok_s, itl_p95_ms, ttft_p50_ms)
on a synthetic timeline: a fake scheduler whose steps take set times on a
fake clock, with and without stalls. The expected values are worked out
from the fake's own record of when it handed out each token."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.loop import (ClosedLoop, percentile,  # noqa: E402
                        supported_percentile)
from bench.record import RunRecord  # noqa: E402
from bench.spec import read_metrics  # noqa: E402
from bench.traffic import ClosedLoopTraffic  # noqa: E402

MIX = {"name": "fixed", "loop": "closed", "clients": 2,
       "tasks": ["code", "math", "extract"],
       "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.0,
                      "lo": 4, "hi": 64},
       "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.0,
                      "lo": 2, "hi": 64},
       "length_seed": 0, "length_cycle": 8}
PREFILL_STEPS = 2


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeScheduler:
    """Admits every submitted request into a free row; a row prefills for
    PREFILL_STEPS steps, then takes one token per step. Step n lasts
    `duration(n)` seconds of the fake clock."""

    def __init__(self, clock, duration, rows=4):
        self.clock, self.duration, self.n = clock, duration, 0
        self.queue = []
        self.engine = SimpleNamespace(
            slots=[None] * rows, telemetry=SimpleNamespace(steps=[]))
        self.handed = {}          # request id -> [time of each token]

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        slots = self.engine.slots
        for i, s in enumerate(slots):
            if s is None and self.queue:
                r = self.queue.pop(0)
                slots[i] = SimpleNamespace(
                    request_id=r.request_id, max_new=r.max_new, out=[],
                    done=False, phase="prefill", prefill_pos=0,
                    history=list(r.prompt), left=PREFILL_STEPS,
                    tel=SimpleNamespace(iterations=[]))
        self.clock.t += self.duration(self.n)
        self.n += 1
        for i, s in enumerate(slots):
            if s is None:
                continue
            if s.phase == "prefill":
                s.left -= 1
                s.prefill_pos += 1
                if s.left:
                    continue
                s.phase = "decode"
            s.out.append(7)
            s.history.append(7)
            self.handed.setdefault(s.request_id, []).append(self.clock.t)
            if len(s.out) >= s.max_new:
                s.done = True
                slots[i] = None
        self.engine.telemetry.steps.append(
            SimpleNamespace(prefill_tokens=0, occupancy=1,
                            union_experts=0.0))


def Request(**kw):
    return SimpleNamespace(**kw)


def serve(duration, seconds=2.0):
    clock = Clock()
    sched = FakeScheduler(clock, duration)
    loop = ClosedLoop(sched, ClosedLoopTraffic(MIX, seed=1, vocab=100),
                      Request, clock=clock)
    loop.run_window(seconds)
    loop.finish_first_tokens(60.0)
    return loop, sched


def metrics(loop):
    run = RunRecord(arch=None, peaks={}, setup_s=1.0,
                    window_s=loop.window_s, tokens=loop.tokens_in_window(),
                    gaps=loop.gaps(), ttfts=loop.ttfts(), steps=[],
                    step_ctx=[], iterations=[], compiles=0)
    specs = [{"name": n, "unit": u} for n, u in
             (("tok_s", "tokens/s"), ("itl_p95_ms", "ms"),
              ("ttft_p50_ms", "ms"), ("setup_s", "s"))]
    return {k: v["value"] for k, v in read_metrics(specs, run).items()}


def expected(loop, sched):
    t0, t1 = loop.t0, loop.t_end
    tokens = sum(1 for ts in sched.handed.values() for t in ts if t <= t1)
    gaps = [b - a for ts in sched.handed.values()
            for a, b in zip(ts, ts[1:]) if b <= t1]
    ttft = [sched.handed[log.job.request_id][0] - log.t_submit
            for log in loop.window_logs() if log.t_submit < t1]
    return {"tok_s": tokens / (t1 - t0),
            "itl_p95_ms": 1e3 * percentile(gaps, 0.95),
            "ttft_p50_ms": 1e3 * percentile(ttft, 0.50), "setup_s": 1.0}


def steady(n):
    return 0.010


def stalled(n):
    return 0.100 if n % 3 == 1 else 0.010


@pytest.mark.parametrize("duration", [steady, stalled])
def test_metrics_match_the_timeline(duration):
    loop, sched = serve(duration)
    got, want = metrics(loop), expected(loop, sched)
    assert got == pytest.approx(want)
    assert loop.window_s >= 2.0
    # every client stays busy: one request each in flight at any time
    assert len(loop.live) == MIX["clients"]


def test_steady_timeline_by_hand():
    """10 ms steps, 2 prefill steps, 6 tokens: a request spans 7 steps
    and gives 6 tokens, with 10 ms between them and 20 ms to the first."""
    loop, _ = serve(steady)
    got = metrics(loop)
    assert got["itl_p95_ms"] == pytest.approx(10.0)
    assert got["ttft_p50_ms"] == pytest.approx(20.0)
    assert got["tok_s"] == pytest.approx(2 * 6 / 0.070, rel=0.02)


def test_stall_moves_every_metric():
    base, _ = serve(steady)
    stall, _ = serve(stalled)
    b, s = metrics(base), metrics(stall)
    assert s["tok_s"] < 0.8 * b["tok_s"]
    assert s["itl_p95_ms"] >= 5 * b["itl_p95_ms"]
    assert s["ttft_p50_ms"] > 2 * b["ttft_p50_ms"]


def test_supported_percentile():
    assert supported_percentile(10) is None
    assert supported_percentile(100) == pytest.approx(90.0)
    assert percentile([3, 1, 2], 0.5) == 2
    assert percentile(list(range(1, 101)), 0.95) == 95
    assert np.isclose(percentile([5.0], 0.95), 5.0)
