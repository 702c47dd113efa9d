"""`moe_in_place_share` on a whole run of the tiny cell on the CPU: the
cell's 4 rows route 8 or more choices over 4 experts, so every pass of
its window reads the stacked experts in place; a program whose telemetry
lacks the field reads nothing."""

import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec as specs  # noqa: E402
from bench.harness import run_cell  # noqa: E402

DATA = Path(__file__).parent / "data"
BENCH = specs.load_json(ROOT / "BENCHMARK.json")
SEED = 2 ** 31 + 77


def test_synthetic_run_reads_experts_in_place(monkeypatch):
    from repro.core.cost_model import TPU_V5E
    runs = []
    real = specs.read_metrics

    def keep(metrics, run, *a, **kw):
        runs.append(run)
        return real(metrics, run, *a, **kw)

    monkeypatch.setattr(specs, "read_metrics", keep)
    cell = specs.Cell("tiny.cell", 1, specs.load_json(DATA / "tiny-moe.json"),
                      specs.load_json(DATA / "tiny-mix.json"),
                      BENCH["end_to_end"], BENCH["per_layer"])
    out = run_cell(cell, seed=SEED, seconds=3.0, trace=False,
                   t_start=time.perf_counter(),
                   peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                   hw=TPU_V5E, log=lambda s: None)
    assert out["correct"] is True
    reader = specs.load_reader("moe_in_place_share")
    assert runs[0].steps
    assert reader(runs[0]) == 100.0
    old = SimpleNamespace(steps=[SimpleNamespace(packed_experts=4)])
    assert reader(old) is None
