"""Discovery of cells, configurations, traffic mixes and metric readers by
name, and the consistency of BENCHMARK.json with the files it names."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec as specs  # noqa: E402
from bench.model import arch_of, program_config  # noqa: E402
from bench.record import RunRecord  # noqa: E402

BENCH = specs.load_json(ROOT / "BENCHMARK.json")


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cell = specs.load_cell(w["name"])
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(specs.load_reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_files_state_their_cuts(entry):
    conf = specs.load_json(ROOT / entry["file"])
    assert conf["name"] == entry["name"]
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert sorted(conf["published"]) == sorted(entry["reduced"])
    for key, value in conf["published"].items():
        assert conf[key] != value
    # the program runs the widths and semantics the file states
    program_config(conf)
    assert arch_of(conf).dtype == "bfloat16"


def test_a_new_cell_is_files_and_entries(tmp_path):
    """A configuration, a traffic mix and a metric added as new files and
    BENCHMARK.json entries are found by name; no existing file changes."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "metrics").mkdir()
    conf = specs.load_json(specs.BENCH_DIR / "configs" /
                           "olmoe-1b-7b.8l.json")
    conf["name"] = "new-model"
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = specs.load_json(specs.traffic_path("agent-mix.c1"))
    mix["name"] = "new-mix"
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n    return len(run.steps) / run.window_s\n")
    spec = {
        "configs": [{"name": "new-model", "source": "x",
                     "file": "bench/configs/new-model.json", "reduced": []}],
        "workloads": [{"name": "new.cell", "config": "new-model",
                       "traffic": "new-mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "steps_per_s", "unit": "steps/s",
                       "workloads": ["new.cell"]},
                      {"name": "occupancy", "unit": "rows",
                       "workloads": ["other.cell"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = specs.load_cell("new.cell", root=tmp_path, bench_dir=bench)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["name"] == "new-mix"
    assert [m["name"] for m in cell.per_layer] == ["steps_per_s"]
    run = RunRecord(arch=None, peaks={}, setup_s=2.0, window_s=4.0,
                    tokens=0, gaps=[], ttfts=[], steps=[object()] * 10,
                    step_ctx=[], iterations=[], compiles=0)
    got = specs.read_metrics(cell.per_layer, run, bench_dir=bench)
    assert got == {"steps_per_s": {"value": 2.5, "unit": "steps/s"}}
    with pytest.raises(KeyError):
        specs.load_cell("missing.cell", root=tmp_path, bench_dir=bench)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    run = RunRecord(arch=None, peaks={}, setup_s=2.0, window_s=4.0,
                    tokens=0, gaps=[], ttfts=[], steps=[], step_ctx=[],
                    iterations=[], compiles=0)
    metrics = [{"name": n, "unit": "x"} for n in
               ("device_idle_share", "pass_roofline", "acceptance",
                "itl_p95_ms", "setup_s")]
    assert specs.read_metrics(metrics, run) == {
        "setup_s": {"value": 2.0, "unit": "x"}}


def test_run_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483700",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_benchmark_json_shape():
    """The file keeps the shape later PRs and the checks rely on."""
    import re
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"]) and _line(c["source"])
        assert c["file"].startswith("bench/") and _line(c["why"])
        assert any(w["config"] == c["name"] for w in cells.values())
        for key in c["reduced"]:
            assert re.match(NAME, key)
            assert not key.endswith(("_dim", "_rank", "_size"))
    pairs = set()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.match(NAME, w["name"]) and re.match(NAME, w["traffic"])
        assert w["config"] in configs and w["chips"] == 1 and _line(w["why"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    names = set()
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.add(m["name"])
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        names.add(m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert len(names) == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    assert {"tok_s", "itl_p95_ms", "ttft_p50_ms", "setup_s"} <= names
