"""`d2h_kb_per_step`: the mean of the engine's per-pass device-to-host
bytes, in KB; nothing where the telemetry lacks the field."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec as specs  # noqa: E402


def steps(**fields):
    return [SimpleNamespace(**{k: v[i] for k, v in fields.items()})
            for i in range(len(next(iter(fields.values()))))]


def test_reads_mean_kb_per_step():
    read = specs.load_reader("d2h_kb_per_step")
    run = SimpleNamespace(steps=steps(d2h_bytes=[1024, 2000, 0, 976]))
    assert read(run) == pytest.approx(1.0)


@pytest.mark.parametrize("run", [
    SimpleNamespace(steps=steps(union_experts=[3.0, 4.0])),
    SimpleNamespace(steps=[]),
], ids=["field-missing", "no-steps"])
def test_reads_nothing_without_the_field(run):
    assert specs.load_reader("d2h_kb_per_step")(run) is None


def test_benchmark_lists_the_metric():
    bench = specs.load_json(ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "d2h_kb_per_step")
    assert entry == {"name": "d2h_kb_per_step", "unit": "KB",
                     "better": "lower", "source": "program_counter",
                     "layer": "engine host after the pass",
                     "moves": "ttft_p50_ms"}
