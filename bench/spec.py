"""Find a cell's parts by name: its workload entry and end-to-end and
per-layer metrics in `BENCHMARK.json`, its configuration file, its traffic
file and its metric readers. Nothing here names a cell: a cell, a
configuration, a traffic mix or a metric is added as files and entries."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[dict]


def _reported_in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def load_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named `workload` in `<root>/BENCHMARK.json`; raises
    KeyError for a name the file does not list."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(traffic_path(w["traffic"], bench_dir))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"]
                    if _reported_in(m, workload)],
        per_layer=[m for m in spec["per_layer"]
                   if _reported_in(m, workload)])


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The `read(run)` function of `bench/metrics/<name>.py`."""
    path = metric_path(name, bench_dir)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if mod_spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], run,
                 bench_dir: Path = BENCH_DIR) -> Dict[str, dict]:
    """Each metric's reading from `run`; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"], bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
