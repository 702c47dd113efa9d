"""Serving launcher: continuous batching (`BatchedEngine` +
`ContinuousBatchingScheduler`) with per-request Cascade control, on the
wall clock, over a synthetic mixed code/math/extract request stream.

    # on a TPU: OLMoE-1B-7B at published widths, depth cut to 8 layers
    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --layers 8
    # on the CPU: the reduced config (d_model <= 256, float32)
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced

`serve()` is the serving loop; `chip_smoke.py` drives the same function.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config
from repro.core import (CascadeController, Hardware, StaticKController,
                        TPU_V5E, hardware_for_device_kind)
from repro.data import make_sample
from repro.models import transformer as T
from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                           GenerationResult, NGramDrafter, Request)

#: the checkout this module runs from (src/repro/launch/serve.py)
REPO_ROOT = Path(__file__).resolve().parents[3]
#: the serving shape: rows in the batch, tokens each row's cache holds,
#: and prompt tokens a row feeds per pass under chunked prefill
MAX_BATCH, MAX_LEN, CHUNK = 8, 2048, 32


def use_compile_cache(root) -> str:
    """Keep compiled programs across runs. Where JAX_COMPILATION_CACHE_DIR
    is set, JAX reads it itself and nothing is set here; otherwise the
    cache is `<root>/.jax_cache`, a fixed path, so the next run from the
    same checkout finds it. Call from entry points only: importing a module
    must not change where the tests' compiles go."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def mixed_requests(cfg, n: int, *, seed: int,
                   max_new: int) -> List[Request]:
    """`n` requests cycling code/math/extract 64-token prompts (65 with
    BOS) from `data.make_sample`, drawn from one generator seeded with
    `seed`."""
    rng = np.random.default_rng(seed)
    tasks = ("code", "math", "extract")
    return [Request(request_id=f"r{i}", task=tasks[i % 3], max_new=max_new,
                    prompt=make_sample(tasks[i % 3], rng,
                                       vocab=cfg.vocab_size,
                                       prompt_len=64,
                                       cont_len=1).prompt)
            for i in range(n)]


@dataclasses.dataclass
class ServeReport:
    results: List[GenerationResult]   # in submission order
    scheduler: ContinuousBatchingScheduler
    first_token_s: float   # wall seconds from the call to the first token
    wall_s: float          # wall seconds of the whole call


def serve(cfg, params, requests, *, hw: Hardware,
          controller_factory: Callable = CascadeController,
          seed: int = 0) -> ServeReport:
    """Serve `requests` to completion on the main path: a wall-clock,
    greedy, union-packed `BatchedEngine` with chunked prefill (span
    lengths stay bucketed, so the decode step compiles once per bucket)
    behind a `ContinuousBatchingScheduler`. `hw` prices the planner's
    grants; pass the chip's own entry (`hardware_for_device_kind`)."""
    engine = BatchedEngine(cfg, params, NGramDrafter, max_batch=MAX_BATCH,
                           controller_factory=controller_factory,
                           clock="wall", hw=hw, max_len=MAX_LEN,
                           temperature=0.0, seed=seed, chunk=CHUNK,
                           packed=True)
    sched = ContinuousBatchingScheduler(engine)
    t0 = time.perf_counter()
    first: Optional[float] = None
    for req in requests:
        sched.submit(req)
    while sched.step():
        if first is None and any(s is not None and s.out
                                 for s in engine.slots):
            first = time.perf_counter() - t0
    results = sched.run([])   # drained: collects results in submission order
    wall = time.perf_counter() - t0
    return ServeReport(results, sched, wall if first is None else first,
                       wall)


def acceptance(results) -> float:
    """Accepted drafts over drafted tokens, across every decode iteration
    (0 when nothing was drafted)."""
    its = [it for r in results for it in r.telemetry.iterations]
    drafted = sum(it.k_drafted for it in its)
    accepted = sum(it.tokens_emitted - 1 for it in its)
    return accepted / drafted if drafted else 0.0


def mean_granted_k(results) -> float:
    its = [it for r in results for it in r.telemetry.iterations]
    return sum(it.k_granted for it in its) / len(its) if its else 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b", choices=ALL_ARCHS)
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: serve the first N layers (0 = all)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU widths (ModelConfig.reduced(), float32)")
    ap.add_argument("--policy", default="cascade",
                    choices=["cascade", "k0", "k1", "k2", "k3"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache(REPO_ROOT)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if dev.platform == "cpu":
        if not args.reduced:
            raise SystemExit("published widths need an accelerator; pass "
                             "--reduced to serve the reduced config on CPU")
        # a CPU run rehearses control flow; its planner prices the chip
        # this repository targets
        hw = TPU_V5E
    else:
        hw = hardware_for_device_kind(dev.device_kind)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    params = jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    factory = (CascadeController if args.policy == "cascade"
               else lambda: StaticKController(int(args.policy[1:])))
    reqs = mixed_requests(cfg, args.requests, seed=args.seed,
                          max_new=args.max_new)
    rep = serve(cfg, params, reqs, hw=hw, controller_factory=factory,
                seed=args.seed)
    toks = sum(len(r.tokens) for r in rep.results)
    print(f"{cfg.name} x{cfg.num_layers} layers {cfg.dtype} "
          f"policy={args.policy} on {dev.device_kind}: {toks} tokens in "
          f"{rep.wall_s:.2f} s wall (first token at {rep.first_token_s:.2f}"
          f" s), engine-clock decode {rep.scheduler.tokens_per_second():.1f}"
          f" tok/s, acceptance {acceptance(rep.results):.3f}, mean granted "
          f"K {mean_granted_k(rep.results):.2f}")
    for r in rep.results:
        t = r.telemetry
        print(f"  {t.request_id} [{t.task:8s}] out={t.output_tokens} "
              f"iters={len(t.iterations)} etr={t.etr:.2f}")


if __name__ == "__main__":
    main()
