"""One run of one cell: set-up (weights, engine, warm-up), the measured
window, the trace reduction, the correctness check, and the result line.

`run_cell` takes no notice of which chip it is on; `bench/run.py` looks
for the chip first and refuses to start without one."""

from __future__ import annotations

import contextlib
import gc
import glob
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from . import archs
from . import spec as specs
from .check import check_served
from .loop import ClosedLoop, supported_percentile, warm_up
from .record import RunRecord
from .traffic import ClosedLoopTraffic, prompt_tokens
from .tracereduce import load as load_trace, reduce as reduce_trace

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: seconds after the window in which every request submitted in it must
#: have had its first token
FIRST_TOKEN_WAIT_S = 120.0


class CompileCounter:
    """Counts programs JAX lowers (one per compile, whether the compiled
    program then comes from the persistent cache or not)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == LOWERING_EVENT:
            self.n += 1


def _trace_dir(root: Path, workload: str) -> Path:
    """Where a traced run writes its profile: one fixed directory per
    cell under the ignored `experiments/`, emptied first."""
    d = root / "experiments" / "bench_traces" / workload
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    return d


def run_cell(cell: specs.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict, hw,
             root: Path = specs.ROOT, bench_dir: Path = specs.BENCH_DIR,
             control: bool = False, log=print) -> dict:
    """Run `cell` once; returns the result line's object. `hw` is the
    program's description of the chip, which its planner prices with.
    `control` checks the window's requests with the correctness control
    (`bench.check`) in place of the served tokens."""
    from repro.core import CascadeController
    from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                               NGramDrafter, Request)

    conf, traffic_spec = cell.config, cell.traffic
    serving = conf["serving"]
    model = archs.for_config(conf)
    arch = model.arch_of(conf)
    cfg = model.program_config(conf)
    counter = CompileCounter()
    dev = jax.devices()[0]

    params = model.program_params(arch, seed)
    engine = BatchedEngine(
        cfg, params, NGramDrafter, max_batch=int(serving["max_batch"]),
        controller_factory=CascadeController, clock="wall", hw=hw,
        max_len=int(serving["max_len"]), temperature=0.0, seed=seed,
        chunk=int(serving["chunk"]), packed=True)
    traffic = ClosedLoopTraffic(traffic_spec, seed, arch.vocab)
    warm_rng = np.random.default_rng([int(seed), 3])
    warm_steps = warm_up(engine, ContinuousBatchingScheduler, Request,
                         traffic.clients, int(serving["chunk"]),
                         lambda n: prompt_tokens("math", warm_rng,
                                                 arch.vocab, n))

    span = ((lambda name: jax.profiler.TraceAnnotation(name)) if trace
            else (lambda name: contextlib.nullcontext()))
    sched = ContinuousBatchingScheduler(engine)
    loop = ClosedLoop(sched, traffic, Request, span=span)
    trace_dir = None
    if trace:
        trace_dir = _trace_dir(root, cell.name)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans are ours alone
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    n_steps0 = len(engine.telemetry.steps)
    setup_s = time.perf_counter() - t_start
    compiles0 = counter.n
    with span("bench.window"):
        loop.run_window(seconds)
    compiles = counter.n - compiles0
    if trace:
        jax.profiler.stop_trace()
    loop.finish_first_tokens(FIRST_TOKEN_WAIT_S)

    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    steps = engine.telemetry.steps[n_steps0:n_steps0 + len(loop.step_ctx)]
    iterations = [it for log in loop.window_logs() if log.slot is not None
                  for it in log.slot.tel.iterations[:log.iters_at_close]]
    finished = loop.finished()
    window_logs = loop.window_logs()
    run = RunRecord(
        arch=arch, peaks=peaks, setup_s=setup_s, window_s=loop.window_s,
        tokens=loop.tokens_in_window(), gaps=loop.gaps(), ttfts=loop.ttfts(),
        steps=steps, step_ctx=loop.step_ctx, iterations=iterations,
        compiles=compiles)
    # the program's state goes before the reference runs on the chip
    del engine, sched, loop, params
    gc.collect()

    if trace:
        pb = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                              recursive=True))
        if not pb:
            raise FileNotFoundError(f"no trace written under {trace_dir}")
        run.trace = reduce_trace(load_trace(pb[-1]), chips=cell.chips)

    t_check = time.perf_counter()
    check = check_served(conf, arch, seed, finished, traffic_spec,
                         control=control)
    check_s = time.perf_counter() - t_check

    log(f"window: {run.window_s:.3f} s, {len(steps)} steps, "
        f"{warm_steps} warm-up steps, {compiles} programs lowered inside")
    log(f"samples: requests submitted {len(window_logs)}, finished "
        f"{len(finished)}, gaps {len(run.gaps)} (highest supported "
        f"percentile {supported_percentile(len(run.gaps))}), first tokens "
        f"{len(run.ttfts)} (highest supported percentile "
        f"{supported_percentile(len(run.ttfts))})")
    log(f"check: {check.info} in {check_s:.1f} s")
    metrics = cell.per_layer if trace else cell.end_to_end
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": check.correct, "attempted": len(window_logs),
           "failed": 0,
           "metrics": specs.read_metrics(metrics, run, bench_dir),
           "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps[:10]}
        log(f"trace: pass program {run.trace.pass_name!r}, "
            f"{len(run.trace.pass_s)} passes for {len(steps)} steps, "
            f"planes {run.trace.planes}, programs {run.trace.modules}, "
            f"{run.trace.steps_traced} steps traced, device trace cut "
            f"{run.trace.cut_s:.3f} s before the window's end")
    out["checks"] = {k: {"value": v, "limit": check.limits.get(k)}
                     for k, v in check.numbers.items()}
    out["_check_lines"] = check.lines()
    return out

