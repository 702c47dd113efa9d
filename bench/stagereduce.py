"""From a profiler trace of one measured window to the program's own
stages and layers: the device's idle time charged to the program's host
spans, and the passes' device time charged to the program's named
scopes.

The program opens host spans (`jax.profiler.TraceAnnotation`) named
`sched.*` around the scheduler's step and `engine.*` around each stage of
`BatchedEngine.step`, and names the pass's layers with `jax.named_scope`
(`attention`, `moe_ffn`, `lm_head`), which reach each device operation's
`tf_op` name stack in the trace's event metadata.

Built on `bench.tracereduce`, whose numbers it leaves as they are: each
idle piece that `tracereduce` charges to a part of a `bench.step`
(`step.before_pass`, `step.in_pass`, `step.after_pass`) is charged here
to `<that label>/<innermost program span open>`, for example
`step.after_pass/engine.cost`, and keeps its label where no program span
is open; so the old totals are the sums by prefix. A device operation
outside every scope is named by the tail of its name stack (`while/body`
for the layer loop's own operations).

    python3 -m bench.stagereduce experiments/bench_traces/<cell>

prints the reduction of the newest trace under a directory (or of one
`.xplane.pb` file) as JSON."""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import tracereduce as tr
from . import xspace

ROOT = Path(__file__).resolve().parents[1]
TRACES = ROOT / "experiments" / "bench_traces"
PROGRAM_SPANS = ("engine.", "sched.")
SCOPES = ("attention", "moe_ffn", "lm_head")
#: the engine's stages before the pass starts on the device, and after
PRE_PASS = ("engine.plan", "engine.draft", "engine.pack", "engine.prefetch",
            "engine.dispatch")
POST_PASS = ("engine.fetch_logits", "engine.verify", "engine.rollback",
             "engine.cost", "engine.feedback")
STEP_SPAN, STEP_ARG = "engine.step", "step"
TF_OP = "tf_op"


class Event(NamedTuple):
    """One trace event, timed as `jax.profiler.ProfileData` times it."""
    name: str
    start_ns: float
    duration_ns: float
    scope: str = ""                 # a device operation's layer
    step: Optional[int] = None      # an `engine.step` span's argument


@dataclass
class Line:
    name: str
    events: List[Event]


@dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclass
class Trace:
    """The planes, lines and events `tracereduce.reduce` reads, and the
    program's spans and scopes."""
    planes: List[Plane]


def scope_of(tf_op: str) -> str:
    """A device operation's layer from its `tf_op` name stack
    (`jit(f)/while/body/closed_call/moe_ffn/dot_general:` -> `moe_ffn`):
    the outermost of `SCOPES` on the stack; else the stack between the
    jitted function and the operation (`while/body`); `top` for an
    operation of the function's own body, `unnamed` with no stack."""
    if not tf_op:
        return "unnamed"
    parts = tf_op.rsplit(":", 1)[0].split("/")
    for p in parts[:-1]:
        if p in SCOPES:
            return p
    return "/".join(parts[1:-1]) or "top"


def _stat(stats, stat_names: Dict[int, str], name: str):
    for s in stats:
        if stat_names.get(s.metadata_id) == name:
            return xspace.stat_value(s, stat_names)
    return None


def load(path: str) -> Trace:
    """The events of the `.xplane.pb` at `path` that the reductions read:
    the host's `bench.*` and program spans, and the first lines of
    operations and of programs of each TPU plane."""
    with open(path, "rb") as f:
        space = xspace.parse(f.read())
    planes = []
    for p in space.planes:
        stat_names = {k: m.name for k, m in p.stat_metadata.items()}
        device = p.name.startswith(tr.DEVICE_PREFIX)
        meta: Dict[int, Tuple[str, str]] = {}
        for k, m in p.event_metadata.items():
            if device:
                meta[k] = (m.name,
                           scope_of(_stat(m.stats, stat_names, TF_OP) or ""))
            elif m.name in tr.HOST_SPANS or m.name.startswith(PROGRAM_SPANS):
                meta[k] = (m.name, "")
        lines = []
        for ln in p.lines:
            if device and ln.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            events = []
            for e in ln.events:
                m = meta.get(e.metadata_id)
                if m is None:
                    continue
                step = (_stat(e.stats, stat_names, STEP_ARG)
                        if m[0] == STEP_SPAN else None)
                events.append(Event(
                    m[0], float(ln.timestamp_ns + e.offset_ps // 1000),
                    float(e.duration_ps // 1000), m[1], step))
            if events:
                lines.append(Line(ln.name, events))
        planes.append(Plane(p.name, lines))
    return Trace(planes)


@dataclass
class Stages:
    base: tr.Reduction              # `tracereduce.reduce` of the trace
    #: [[host activity, idle device s]], longest first; the parts of a
    #: `bench.step` as `<label>/<innermost program span>`
    idle_gaps: List[list]
    engine_steps: int               # `engine.step`s begun in the window
    scope_s: Dict[str, float]       # passes' operations' self s by scope
    pass_busy_s: float              # union of the passes' operations
    device_ops: List[list]          # [[<scope>/<op>, self s]] top 10
    program_spans: int              # program spans in the window

    def idle_in(self, spans: Iterable[str]) -> float:
        """Idle device seconds inside a `bench.step` while the innermost
        open program span was one of `spans`."""
        want = set(spans)
        return sum(s for label, s in self.idle_gaps
                   if label.partition("/")[2] in want)

    def idle_by_prefix(self) -> Dict[str, float]:
        """The idle seconds by `tracereduce`'s own labels."""
        out: Dict[str, float] = defaultdict(float)
        for label, s in self.idle_gaps:
            out[label.partition("/")[0]] += s
        return dict(out)


def innermost(spans: List[Event]) -> List[Tuple[str, float, float]]:
    """Disjoint, time-ordered pieces of the time inside `spans` (spans of
    one thread, so they nest), each named for the innermost span open."""
    out, stack, at = [], [], 0.0
    for e in sorted(spans, key=lambda e: (e.start_ns, -e.duration_ns)):
        a = e.start_ns
        while stack and stack[-1][1] <= a:
            name, end = stack.pop()
            out.append((name, at, end))
            at = max(at, end)
        if stack:
            out.append((stack[-1][0], at, a))
        stack.append((e.name, a + e.duration_ns))
        at = a
    while stack:
        name, end = stack.pop()
        out.append((name, at, end))
        at = max(at, end)
    return [s for s in out if s[2] > s[1]]


def refine(timeline: List[tuple], pieces: List[tuple]) -> List[tuple]:
    """`tracereduce.host_timeline`'s spans with each part of a `bench.step`
    cut by the program's `innermost` pieces: `<label>/<span>` where one
    is open, `<label>` elsewhere."""
    starts = [s for _, s, _ in pieces]
    out = []
    for label, a, b in timeline:
        if not label.startswith("step."):
            out.append((label, a, b))
            continue
        at = a
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        while k < len(pieces) and pieces[k][1] < b:
            name, lo, hi = pieces[k]
            lo, hi = max(a, lo), min(b, hi)
            if hi > lo:
                if lo > at:
                    out.append((label, at, lo))
                out.append((f"{label}/{name}", lo, hi))
                at = hi
            k += 1
        if b > at:
            out.append((label, at, b))
    return out


def idle_by(idle: List[tr.Interval], spans: List[tuple]) -> List[list]:
    """Idle device seconds summed by the host span open at the time
    (`host.other` where none was), longest first: `tracereduce`'s rule,
    over the given disjoint, time-ordered spans."""
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


def reduce(trace: Trace, chips: int = 1) -> Stages:
    """Reduce a `Trace` of one window; raises ValueError where
    `tracereduce.reduce` does."""
    base = tr.reduce(trace, chips)
    host: Dict[str, List[tr.Interval]] = defaultdict(list)
    program: List[Event] = []
    devices = []
    for plane in trace.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            spans = [e for e in line.events
                     if e.name.startswith(PROGRAM_SPANS)]
            if len(spans) > len(program):
                program = spans         # the thread that ran the program
            for e in line.events:
                if e.name in tr.HOST_SPANS:
                    host[e.name].append((e.start_ns,
                                         e.start_ns + e.duration_ns))
    devices = sorted(devices, key=lambda p: p.name)[:chips]
    # the traced window, as `tracereduce.reduce` cuts it
    w = max(host["bench.window"], key=lambda iv: iv[1] - iv[0])
    cut_ns = w[1] - tr._traced_until(devices, host["bench.step"], w)
    w = (w[0], w[1] - cut_ns)

    ops, passes = [], []
    for line in devices[0].lines:
        for e in line.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if line.name == tr.OPS_LINE:
                iv = tr._clip((a, b), w)
                if iv:
                    ops.append(((e.scope, tr.op_name(e.name)),) + iv)
            elif (w[0] <= a and b <= w[1]
                  and tr.program_name(e.name) == base.pass_name):
                passes.append((a, b))
    passes.sort()

    idle = tr.gaps(tr.union([(a, b) for _, a, b in ops]), w)
    program = [e for e in program if w[0] <= e.start_ns < w[1]]
    timeline = refine(tr.host_timeline(host, passes), innermost(program))

    pass_starts = [a for a, _ in passes]

    def in_pass(t: float) -> bool:
        j = bisect.bisect_right(pass_starts, t) - 1
        return j >= 0 and t < passes[j][1]

    pass_ops = [op for op in ops if in_pass(op[1])]
    scope_s: Dict[str, float] = defaultdict(float)
    for (scope, _), s in tr.self_times(pass_ops).items():
        scope_s[scope] += s * 1e-9
    top = sorted(tr.self_times(ops).items(), key=lambda kv: -kv[1])[:10]
    return Stages(
        base=base, idle_gaps=idle_by(idle, timeline),
        engine_steps=len({e.step for e in program if e.name == STEP_SPAN}),
        scope_s=dict(scope_s),
        pass_busy_s=sum(b - a for a, b in tr.union(
            [(a, b) for _, a, b in pass_ops])) * 1e-9,
        device_ops=[[f"{scope}/{op}", s * 1e-9] for (scope, op), s in top],
        program_spans=len(program))


def newest_trace(where=None) -> Optional[str]:
    """The newest `.xplane.pb` under `where` (default `TRACES`), or
    `where` itself where it names one."""
    where = TRACES if where is None else where
    if str(where).endswith(".xplane.pb"):
        return str(where)
    paths = glob.glob(str(Path(where) / "**" / "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int, chips: int) -> Stages:
    """One trace's reduction, shared by the metrics that read it."""
    return reduce(load(path), chips=chips)


def for_run(run, where=None) -> Optional[Stages]:
    """The stages of the traced run `run`: the reduction of the newest
    trace the benchmark wrote, where it reduces to the window and passes
    of `run.trace`; else None."""
    if run.trace is None:
        return None
    path = newest_trace(where)
    if path is None:
        return None
    st = _reduced(path, os.stat(path).st_mtime_ns, len(run.trace.planes))
    same = (len(st.base.pass_s) == len(run.trace.pass_s)
            and st.base.steps_traced == run.trace.steps_traced
            and abs(st.base.window_s - run.trace.window_s)
            <= 1e-6 * run.trace.window_s)
    return st if same else None


def summary(st: Stages) -> dict:
    """What `main` prints: the attribution of one traced window."""
    base = st.base
    in_step = sum(s for label, s in st.idle_gaps
                  if label.startswith("step."))
    labelled = sum(s for label, s in st.idle_gaps
                   if label.startswith("step.") and "/" in label)
    n_pass = len(base.pass_s)
    per_step = 1e3 / st.engine_steps if st.engine_steps else 0.0
    return {
        "window_s": base.window_s, "busy_s": base.busy_s,
        "passes": n_pass, "steps_traced": base.steps_traced,
        "engine_steps": st.engine_steps, "program_spans": st.program_spans,
        "pre_pass_host_ms": st.idle_in(PRE_PASS) * per_step,
        "post_pass_host_ms": st.idle_in(POST_PASS) * per_step,
        "idle_in_steps_s": in_step,
        "idle_labelled_share": labelled / in_step if in_step else None,
        "idle_gaps": st.idle_gaps,
        "idle_by_old_label": st.idle_by_prefix(),
        "pass_busy_s": st.pass_busy_s,
        "scope_sum_s": sum(st.scope_s.values()),
        "scope_ms_per_pass": {
            k: 1e3 * v / n_pass if n_pass else None
            for k, v in sorted(st.scope_s.items(), key=lambda kv: -kv[1])},
        "device_ops": st.device_ops,
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    path = newest_trace(args[0] if args else None)
    if path is None:
        print("no .xplane.pb found", file=sys.stderr)
        return 1
    chips = int(args[1]) if len(args) > 1 else 1
    print(json.dumps({"trace": path,
                      **summary(reduce(load(path), chips=chips))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
