"""The one traffic generator: it reads a traffic file
(`bench/traffic/<mix>.json`) and hands each client its next request.

A closed-loop mix (`"loop": "closed"`) has `clients` clients, each sending
its next request as soon as the last one finished, with no think time.
Lengths come from the file's own `length_seed`, so every run seed serves
the same sequence of sizes; the run seed draws the token ids. Client c's
j-th request takes entry (j * clients + c) of that sequence.

Token ids follow `repro.data.workloads.make_sample` (copied below, so that
no change to the program moves the yardstick): code-like, math-like and
extraction-like streams, cycling through the file's `tasks`."""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

BOS, SEP = 1, 2
_BASE = 3


def sample_length(rng: np.random.Generator, dist: dict) -> int:
    """One draw of a lognormal length: exp(N(ln median, sigma^2)),
    rounded and clamped to [lo, hi]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = dist["median"] * float(np.exp(dist["sigma"]
                                      * rng.standard_normal()))
    return int(min(max(round(x), dist["lo"]), dist["hi"]))


def _code_like(rng, vocab, length):
    toks: List[int] = []
    n_templates = rng.integers(2, 5)
    templates = [list(rng.integers(_BASE, vocab, rng.integers(4, 9)))
                 for _ in range(n_templates)]
    while len(toks) < length:
        t = list(templates[rng.integers(0, n_templates)])
        if rng.random() < 0.4:
            t[rng.integers(0, len(t))] = int(rng.integers(_BASE, vocab))
        toks.extend(t + [SEP])
    return toks[:length]


def _math_like(rng, vocab, length):
    ops = list(rng.integers(_BASE, _BASE + 6, 4))
    toks: List[int] = []
    while len(toks) < length:
        expr = [int(rng.integers(_BASE + 6, vocab))
                for _ in range(rng.integers(2, 5))]
        toks.extend([expr[0], int(rng.choice(ops))] + expr[1:] + [SEP])
    return toks[:length]


def prompt_tokens(task: str, rng: np.random.Generator, vocab: int,
                  length: int) -> List[int]:
    """A `length`-token prompt (BOS first) of the task's kind."""
    n = length - 1
    if task == "code":
        body = _code_like(rng, vocab, n)
    elif task == "math":
        body = _math_like(rng, vocab, n)
    elif task == "extract":
        body = [int(t) for t in rng.integers(_BASE, vocab, n)]
    else:
        raise ValueError(f"unknown task {task!r}")
    return [BOS] + [int(t) for t in body]


@dataclass
class Job:
    request_id: str
    client: int
    task: str
    prompt: List[int]
    max_new: int


class ClosedLoopTraffic:
    """Requests of a closed-loop traffic file, for one run seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["loop"] != "closed":
            raise ValueError(f"{spec['name']}: loop {spec['loop']!r} is "
                             "not a closed loop")
        self.spec = spec
        self.clients = int(spec["clients"])
        self.seed = int(seed)
        self.vocab = int(vocab)
        n = int(spec["length_cycle"])
        rng = np.random.default_rng(int(spec["length_seed"]))
        self.prompt_lens = [sample_length(rng, spec["prompt_len"])
                            for _ in range(n)]
        self.output_lens = [sample_length(rng, spec["output_len"])
                            for _ in range(n)]
        self._sent = [0] * self.clients

    def next_job(self, client: int) -> Job:
        j = self._sent[client]
        self._sent[client] += 1
        i = j * self.clients + client
        tasks = self.spec["tasks"]
        task = tasks[i % len(tasks)]
        n = i % len(self.prompt_lens)
        rng = np.random.default_rng([self.seed, client, j])
        return Job(request_id=f"c{client}.{j}", client=client, task=task,
                   prompt=prompt_tokens(task, rng, self.vocab,
                                        self.prompt_lens[n]),
                   max_new=self.output_lens[n])
