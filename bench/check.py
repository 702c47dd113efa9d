"""Whether what the timed path served is correct: a sample, drawn from the
seed, of the requests the window finished, with the longest among them, is
run once through the configuration's plain reference, teacher-forced over
each prompt and its served tokens. Served tokens are greedy, so each should
be the reference's best token. Over the compared tokens, `_numbers` reads
the widest gap by which a served token's reference logit lies below the
reference's best, the mean gap, and the share of tokens that are not the
best; the configuration's `check` names the ones compared, each with its
limit.

The control (`control=True`) reads the same numbers for the tokens that
the reference computed in the precision below the configuration's puts
first at the same positions, and has to come out not correct."""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Check:
    numbers: Dict[str, float]        # compared, by name
    limits: Dict[str, Optional[float]]
    info: Dict[str, float]           # printed, not compared
    reason: str = ""

    @property
    def correct(self) -> bool:
        return (not self.reason and all(
            self.limits.get(k) is not None and v <= self.limits[k]
            for k, v in self.numbers.items()))

    def lines(self) -> List[str]:
        out = [f"{k} {v!r} limit {self.limits.get(k)!r}"
               for k, v in self.numbers.items()]
        if self.reason:
            out.append(f"not correct: {self.reason}")
        return out


def sample(finished: list, seed: int, n: int) -> list:
    """Up to n finished requests: the one with the most served tokens and
    n - 1 more drawn with the seed, in a fixed order."""
    if not finished:
        return []
    logs = sorted(finished, key=lambda log: log.job.request_id)
    longest = max(logs, key=lambda log: (len(log.tokens), log.job.request_id))
    rest = [log for log in logs if log is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    """Over every compared token: the widest gap, the mean gap, and the
    share of tokens that are not the reference's best."""
    allg = np.concatenate(gaps)
    return {"max_gap": float(np.max(allg)),
            "mean_gap": float(np.mean(allg)),
            "not_greedy": float(np.mean(allg > 0))}


def check_served(conf: dict, arch, seed: int, finished: list,
                 traffic: dict, control: bool = False) -> Check:
    """Compare a sample of `finished` (RequestLogs) with the reference the
    configuration file names. Every key of the file's `check` but
    `requests` names a number (`_numbers`) and its limit; with `control`,
    read the control's tokens in place of the served ones."""
    spec = conf["check"]
    limits = {k: v for k, v in spec.items() if k != "requests"}
    picked = sample(finished, seed, int(spec["requests"]))
    if not picked:
        return Check({}, limits, {}, reason="no request finished in the "
                     "window")
    ref = importlib.import_module(f"bench.reference.{conf['reference']}")
    pad_to = traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
    rows_to = int(spec["requests"]) * traffic["output_len"]["hi"]
    gaps, _, lower = ref.served_gaps(
        arch, seed, [log.job.prompt for log in picked],
        [log.tokens for log in picked], pad_to=pad_to, rows_to=rows_to,
        batch_to=int(spec["requests"]),
        lower=ref.LOWER[arch.dtype] if control else None)
    served = _numbers(gaps)
    compared = _numbers(lower) if control else served
    info = {"requests": len(picked),
            "tokens": int(sum(len(g) for g in gaps)),
            "longest": len(picked[0].tokens)}
    info.update({f"served_{k}": v for k, v in served.items()})
    if control:
        info.update({f"control_{k}": v for k, v in compared.items()})
    return Check(numbers={k: compared[k] for k in limits}, limits=limits,
                 info=info)
