"""From a profiler trace (`.xplane.pb`) of one measured window to the
numbers the per-layer metrics read: device busy time, each pass's device
time, the device operations that took the most time, and the device's
idle gaps by what the host was doing.

The window is the host span `bench.window` the harness wraps around it.
Busy time is the union of the intervals of the operations (line
`XLA Ops`) on each TPU plane, clipped to the window, averaged over the
chips used. A pass is an execution (line `XLA Modules`) of the program
that takes the most device time in the window: the served step, whose
compiled variants (one per span length) share a name up to the
fingerprint in parentheses. Operations nest (a layer loop holds its
body's operations), so the top operations are ranked by self time. An idle
gap is split by the host spans open during it: inside a `bench.step`,
before, during or after that step's pass; else the client's own span.

The profiler keeps a bounded number of device events. Where it stops
recording before the window closes (whole `bench.step` spans begin and
end after the last device operation, yet every step runs a pass), the
traced window ends with the last pass recorded whole, and only the steps
that began before its end count (`steps_traced`)."""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.window", "bench.step", "bench.submit", "bench.deliver")

Interval = Tuple[float, float]


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    pass_name: str
    pass_s: List[float]                 # device seconds of each pass
    device_ops: List[list]              # [[name, seconds]] top 10
    idle_gaps: List[list]               # [[host activity, seconds]]
    planes: List[str] = field(default_factory=list)
    #: every program run in the window: {name: [runs, device seconds]}
    modules: Dict[str, list] = field(default_factory=dict)
    #: `bench.step` spans begun inside the traced window
    steps_traced: int = 0
    #: seconds of the window after the device trace stopped (0: none)
    cut_s: float = 0.0


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    a, b = max(iv[0], w[0]), min(iv[1], w[1])
    return (a, b) if b > a else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of `intervals`."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Interval], w: Interval) -> List[Interval]:
    """The parts of window w that `busy` (merged) does not cover."""
    out, at = [], w[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if w[1] > at:
        out.append((at, w[1]))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def program_name(module: str) -> str:
    """`jit_step(1234)` -> `jit_step`: the compiled variants of one jitted
    function differ only in the fingerprint."""
    return module.split("(", 1)[0]


def op_name(op: str) -> str:
    """A short name for an HLO operation event: its instruction name and
    result type (`%fusion.3 = bf16[64,8,1024]{...} fusion(...)` ->
    `fusion.3 bf16[64,8,1024]`)."""
    lhs, _, rhs = op.partition(" = ")
    kind = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return f"{lhs.lstrip('%')} {kind}".strip()


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Duration of each event less the events nested inside it, summed by
    name (events on one line nest or do not overlap)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []        # [name, end, time of children, start]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            n, end, child, start = stack.pop()
            out[n] += (end - start) - child
        if stack:
            stack[-1][2] += b - a
        stack.append([name, b, 0.0, a])
    while stack:
        n, end, child, start = stack.pop()
        out[n] += (end - start) - child
    return out


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(prof, chips: int = 1) -> Reduction:
    """Reduce a `ProfileData` of one window; raises ValueError where the
    trace holds no window span or no device plane."""
    host: Dict[str, List[Interval]] = defaultdict(list)
    devices = []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name in HOST_SPANS:
                    host[name].append((a, b))
    if not host["bench.window"]:
        raise ValueError("the trace holds no bench.window span")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    devices = sorted(devices, key=lambda p: p.name)[:chips]
    w = max(host["bench.window"], key=lambda iv: iv[1] - iv[0])
    cut_ns = w[1] - _traced_until(devices, host["bench.step"], w)
    w = (w[0], w[1] - cut_ns)
    window_ns = w[1] - w[0]

    busy_ns, per_plane_busy, first_ops = 0.0, [], []
    modules: Dict[str, List[Interval]] = defaultdict(list)
    for i, plane in enumerate(devices):
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for name, a, b in _events(line):
                    iv = _clip((a, b), w)
                    if iv:
                        ops.append(iv)
                        if i == 0:
                            first_ops.append((op_name(name),) + iv)
            elif line.name == MODULES_LINE and i == 0:
                for name, a, b in _events(line):
                    if w[0] <= a and b <= w[1]:
                        modules[program_name(name)].append((a, b))
        merged = union(ops)
        per_plane_busy.append(merged)
        busy_ns += sum(b - a for a, b in merged)
    busy_ns /= len(devices)

    pass_name = max(modules, key=lambda n: sum(b - a for a, b in modules[n]),
                    default="")
    passes = sorted(modules.get(pass_name, []))
    device_ops = sorted(self_times(first_ops).items(),
                        key=lambda kv: -kv[1])[:10]
    return Reduction(
        window_s=window_ns * 1e-9, busy_s=busy_ns * 1e-9,
        pass_name=pass_name, pass_s=[(b - a) * 1e-9 for a, b in passes],
        device_ops=[[n, s * 1e-9] for n, s in device_ops],
        idle_gaps=_idle_by_host(gaps(per_plane_busy[0], w), host, passes),
        planes=[p.name for p in devices],
        modules={n: [len(ivs), sum(b - a for a, b in ivs) * 1e-9]
                 for n, ivs in modules.items()},
        steps_traced=sum(w[0] <= a < w[1] for a, _ in host["bench.step"]),
        cut_s=cut_ns * 1e-9)


def _traced_until(devices, steps: List[Interval], w: Interval) -> float:
    """Where the device trace of window w ends: w's end, or, where whole
    steps follow the last device operation inside w, the end of the last
    pass (run of the program with the most device time) recorded whole
    before it."""
    last = w[0]
    runs: Dict[str, List[Interval]] = defaultdict(list)
    for plane in devices[:1]:
        for line in plane.lines:
            if line.name == OPS_LINE:
                for _, a, b in _events(line):
                    if a < w[1]:
                        last = max(last, min(b, w[1]))
            elif line.name == MODULES_LINE:
                for name, a, b in _events(line):
                    if w[0] <= a:
                        runs[program_name(name)].append((a, b))
    if not [s for s in steps if last < s[0] and s[1] <= w[1]]:
        return w[1]
    busiest = max(runs.values(), key=lambda ivs: sum(b - a for a, b in ivs),
                  default=[])
    return max([b for _, b in busiest if b <= last], default=w[0])


def host_timeline(host, passes: List[Interval]) -> List[tuple]:
    """Disjoint host intervals named for what the host was doing: each
    `bench.step` cut at its pass into `step.before_pass`, `step.in_pass`
    and `step.after_pass`; the client's `bench.deliver` and
    `bench.submit` spans."""
    out = []
    pass_starts = [a for a, _ in passes]
    for s0, s1 in host["bench.step"]:
        j = bisect.bisect_left(pass_starts, s0)
        if j < len(passes) and passes[j][0] <= s1:
            p0, p1 = passes[j]
            out += [("step.before_pass", s0, p0), ("step.in_pass", p0, p1),
                    ("step.after_pass", p1, s1)]
        else:
            out.append(("step.before_pass", s0, s1))
    for name in ("bench.deliver", "bench.submit"):
        out += [("client." + name.split(".")[1], a, b) for a, b in host[name]]
    return sorted(out, key=lambda e: e[1])


def _idle_by_host(idle: List[Interval], host, passes: List[Interval]):
    """Idle device seconds summed by what the host was doing at the time
    (`host_timeline`; `host.other` where none of its spans was open),
    longest first."""
    spans = host_timeline(host, passes)
    total: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(spans) and spans[j][2] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][1] < b:
            name, s0, s1 = spans[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                total[name] += ov * 1e-9
                covered += ov
            k += 1
        if b - a > covered:
            total["host.other"] += (b - a - covered) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]
