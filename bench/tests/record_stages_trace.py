"""Record the small TPU trace that `test_bench_stages.py` reads: a few
steps of the tiny CPU-test cell (`data/tiny-moe.json` served under
`data/tiny-mix.json`) through the program's scheduler, with the host spans
the harness opens (`bench.window`, `bench.step`, ...) and the program's
own spans and scopes. Needs a TPU:

    python3 bench/tests/record_stages_trace.py --steps 6 \\
        --out chiprun_out/tpu_v5e_stages.xplane.pb

The file keeps what the reductions read (`trim`): the host thread that
ran the steps, the TPU's lines of programs and of operations, each
operation's `tf_op` stat and its name up to its result type. The
compiled programs' HLO (plane `/host:metadata`) and the other planes,
lines and stats are dropped.
"""

import argparse
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

DATA = Path(__file__).parent / "data"
SEED = 2 ** 31 + 77


def _short(name: str) -> str:
    """An HLO operation's text up to its result type: what
    `tracereduce.op_name` reads of it."""
    lhs, eq, rhs = name.partition(" = ")
    return lhs + eq + rhs.split("{", 1)[0] if eq else name


def trim(data: bytes) -> bytes:
    """The serialised XSpace `data` with only what the reductions read."""
    from bench import stagereduce as sr, tracereduce as tr, xspace
    space = xspace.parse(data)

    def drop(items, unwanted):
        for i in reversed(range(len(items))):
            if unwanted(items[i]):
                del items[i]

    drop(space.planes, lambda p: p.name not in ("/host:CPU",
                                                tr.DEVICE_PREFIX + "0"))
    for p in space.planes:
        stat_names = {k: m.name for k, m in p.stat_metadata.items()}
        names = {k: m.name for k, m in p.event_metadata.items()}
        if p.name.startswith(tr.DEVICE_PREFIX):
            drop(p.lines, lambda ln: ln.name not in (tr.OPS_LINE,
                                                     tr.MODULES_LINE))
        else:
            drop(p.lines, lambda ln: not any(
                names[e.metadata_id].startswith(("bench.",)
                                                + sr.PROGRAM_SPANS)
                for e in ln.events))
        used = {e.metadata_id for ln in p.lines for e in ln.events}
        for k in set(p.event_metadata) - used:
            del p.event_metadata[k]
        for ln in p.lines:
            for e in ln.events:
                if names[e.metadata_id] != sr.STEP_SPAN:
                    del e.stats[:]
        for m in p.event_metadata.values():
            m.name = _short(m.name)
            drop(m.stats, lambda s: stat_names.get(s.metadata_id) != sr.TF_OP)
        refs = {s.metadata_id for m in p.event_metadata.values()
                for s in m.stats}
        refs |= {s.metadata_id for ln in p.lines for e in ln.events
                 for s in e.stats}
        for k in set(p.stat_metadata) - refs:
            del p.stat_metadata[k]
    space.DiscardUnknownFields()
    return space.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import archs, spec as specs
    from bench.loop import ClosedLoop, warm_up
    from bench.traffic import ClosedLoopTraffic, prompt_tokens
    from repro.core import CascadeController, hardware_for_device_kind
    from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                               NGramDrafter, Request)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 3
    conf = specs.load_json(DATA / "tiny-moe.json")
    mix = specs.load_json(DATA / "tiny-mix.json")
    serving = conf["serving"]
    model = archs.for_config(conf)
    arch = model.arch_of(conf)
    engine = BatchedEngine(
        model.program_config(conf), model.program_params(arch, SEED),
        NGramDrafter,
        max_batch=int(serving["max_batch"]),
        controller_factory=CascadeController, clock="wall",
        hw=hardware_for_device_kind(dev.device_kind),
        max_len=int(serving["max_len"]), temperature=0.0, seed=SEED,
        chunk=int(serving["chunk"]), packed=True)
    traffic = ClosedLoopTraffic(mix, SEED, arch.vocab)
    rng = np.random.default_rng([SEED, 3])
    warm_up(engine, ContinuousBatchingScheduler, Request, traffic.clients,
            int(serving["chunk"]),
            lambda n: prompt_tokens("math", rng, arch.vocab, n))

    loop = ClosedLoop(ContinuousBatchingScheduler(engine), traffic, Request,
                      span=TraceAnnotation)
    trace_dir = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for c in range(traffic.clients):
            loop.submit(c, loop.clock(), in_window=True)
        for _ in range(args.steps):
            loop.step(record_ctx=True)
    jax.profiler.stop_trace()
    pb = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_bytes(trim(Path(pb[-1]).read_bytes()))
    shutil.rmtree(trace_dir)
    print(f"{args.out}: {Path(args.out).stat().st_size} bytes, "
          f"{args.steps} steps on {dev.device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
