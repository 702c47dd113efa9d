"""The spread that sets a bound: quartiles as Python's `statistics`
gives them, over the median; and the summary of recorded runs."""

import statistics
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.sets import spread, summarize  # noqa: E402


def test_spread_is_quartile_distance_over_median():
    vals = [10.0, 10.2, 9.8, 10.1, 9.9, 10.4]
    q = statistics.quantiles(vals, n=4)
    med, sp = spread(vals)
    assert med == statistics.median(vals)
    assert sp == pytest.approx((q[2] - q[0]) / med)


def test_summary_groups_sets_and_reports_checks():
    def rec(seed, s, tok, gap, correct=True):
        return {"workload": "w", "trace": 0, "control": False, "set": s,
                "seed": seed, "rc": 0, "stderr_tail": [],
                "result": {"correct": correct,
                           "metrics": {"tok_s": {"value": tok}},
                           "checks": {"max_gap": {"value": gap}},
                           "device": {"memory_peak_bytes": 1}}}
    recs = [rec(1, 0, 9.0, 0.1), rec(2, 0, 9.5, 0.3),
            rec(1, 1, 9.1, 0.1), rec(2, 1, 9.4, 0.3, correct=False),
            {"workload": "w", "trace": 0, "control": False, "set": 1,
             "seed": 3, "rc": 3, "result": None,
             "stderr_tail": ["bench: JAX found platform 'cpu'"]}]
    text = summarize(recs)
    assert "set=0: 2 runs, 2 with a result, correct 2" in text
    assert "set=1: 3 runs, 2 with a result, correct 1" in text
    assert "check max_gap: max 0.3" in text
    assert "seed 3 rc 3: bench: JAX found platform 'cpu'" in text
