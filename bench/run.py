"""Run one cell of `BENCHMARK.json` on the chip this process finds, and
print its result as the last line of standard output:

    python3 bench/run.py --workload olmoe_8l.single --seed 7 \\
        --seconds 45 --trace 0

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a profiler trace of the window written under
`experiments/bench_traces/`. Both check what the window served against the
configuration's plain reference, and print each number compared beside
its limit, last on standard error and under `checks` in the result.

With no TPU, or fewer chips than the cell asks for, it exits 3 and prints
no result. JAX's compilation cache is `.jax_cache/` in the checkout."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
#: a fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"


def _paths() -> None:
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != BENCH_DIR]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="check with the correctness control, the "
                         "reference computed in the precision below the "
                         "configuration's, in place of the served tokens "
                         "(not a benchmark run)")
    return ap.parse_args(argv)


def die(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(3)


def find_chips(n: int, peaks: dict):
    """The first device, if JAX finds at least `n` TPU chips whose kind
    the peaks table lists; else exits 3."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        die(f"JAX found platform {dev.platform!r}, not a TPU")
    if len(devs) < n:
        die(f"the cell asks for {n} chips, JAX found {len(devs)}")
    if dev.device_kind not in peaks:
        die(f"no peaks for device kind {dev.device_kind!r} in "
            "bench/peaks.json")
    return dev


def main(argv=None) -> int:
    args = parse(argv)
    _paths()
    from bench import spec as specs
    cell = specs.load_cell(args.workload)
    peaks = specs.load_json(BENCH_DIR / "peaks.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        dev = find_chips(cell.chips, peaks)
    except RuntimeError as e:      # no backend could be initialised
        die(f"no accelerator: {e}")

    from bench.harness import run_cell
    from repro.core import hardware_for_device_kind
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=T_START,
                   peaks=peaks[dev.device_kind],
                   hw=hardware_for_device_kind(dev.device_kind),
                   control=args.control,
                   log=lambda s: print(s, flush=True))
    lines = out.pop("_check_lines")
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
