"""Run one cell several times, one process after another, and report the
spread of each metric: the runs behind a bound and behind a `correct`
limit. The parent never touches JAX, so each run has the chip alone.

    python3 bench/sets.py --workload olmoe_8l.single --seconds 45 \\
        --seeds 11,12,13 --sets 2 --out chiprun_out/olmoe.jsonl

runs seeds 11, 12, 13, then the same three again (two sets), each as
`python3 bench/run.py --workload ... --seed ... --seconds ... --trace ...`
(`--control` adds the control flag), appends one JSON line per run to
`--out`, and prints per set each metric's median and quartile spread (the
distance between the first and third quartiles of
`statistics.quantiles(values, n=4)`, as a share of the median), and each
compared number with its largest reading.

    python3 bench/sets.py --summary chiprun_out/olmoe.jsonl

prints the same for runs already recorded."""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TAIL_LINES = 12


def run_one(workload: str, seed: int, seconds: float, trace: int,
            control: bool, timeout: float) -> dict:
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)] + (["--control"] if control else [])
    t0 = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
    result = None
    lines = out.strip().splitlines()
    if rc == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "trace": trace, "control": control, "rc": rc,
            "wall_s": time.perf_counter() - t0, "result": result,
            "log": [ln for ln in lines[:-1] if not ln.startswith("{")][-6:],
            "stderr_tail": err.strip().splitlines()[-TAIL_LINES:]}


def spread(values):
    """(median, quartile spread as a share of the median)."""
    med = statistics.median(values)
    if len(values) < 2 or med == 0:
        return med, float("nan")
    q = statistics.quantiles(values, n=4)
    return med, (q[2] - q[0]) / abs(med)


def summarize(records) -> str:
    out = []
    groups = defaultdict(list)
    for r in records:
        groups[(r["workload"], r["trace"], r["control"], r.get("set", 0))
               ].append(r)
    for (wl, trace, control, s), rs in sorted(groups.items()):
        ok = [r for r in rs if r["result"]]
        out.append(f"{wl} trace={trace} control={control} set={s}: "
                   f"{len(rs)} runs, {len(ok)} with a result, correct "
                   f"{sum(bool(r['result']['correct']) for r in ok)}, "
                   f"seeds {[r['seed'] for r in rs]}")
        metrics = defaultdict(list)
        checks = defaultdict(list)
        for r in ok:
            for k, m in r["result"]["metrics"].items():
                metrics[k].append(m["value"])
            for k, c in r["result"].get("checks", {}).items():
                checks[k].append(c["value"])
            metrics["memory_peak_bytes"].append(
                r["result"]["device"]["memory_peak_bytes"])
            dev = r["result"]["device"]
            if dev.get("window_s"):
                metrics["busy_share"].append(dev["busy_s"] / dev["window_s"])
        for k, vs in metrics.items():
            med, sp = spread(vs)
            out.append(f"  {k}: median {med!r} spread {sp:.4f} "
                       f"values {vs}")
        for k, vs in checks.items():
            out.append(f"  check {k}: max {max(vs)!r} values {vs}")
        for r in rs:
            if not r["result"]:
                out.append(f"  seed {r['seed']} rc {r['rc']}: "
                           + " | ".join(r["stderr_tail"][-4:]))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--summary", nargs="*", help="recorded run files")
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.summary:
        recs = [json.loads(ln) for f in args.summary
                for ln in Path(f).read_text().splitlines() if ln.strip()]
        print(summarize(recs))
        return 0
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    recs = []
    for s in range(args.sets):
        for seed in seeds:
            r = run_one(args.workload, seed, args.seconds, args.trace,
                        args.control, args.timeout)
            r["set"] = s
            recs.append(r)
            with out.open("a") as fh:
                fh.write(json.dumps(r) + "\n")
            res = r["result"] or {}
            print(f"{args.workload} set {s} seed {seed} rc {r['rc']} "
                  f"{r['wall_s']:.1f} s correct {res.get('correct')} "
                  f"checks {res.get('checks')}", flush=True)
    print(summarize(recs), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
