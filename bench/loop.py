"""The client loop that drives the program's scheduler on the host's wall
clock, and what it recorded, which the metric readers read.

Every time here is `time.perf_counter()` around calls the loop makes
itself: a request is submitted at a host time, and each scheduler step
that hands it tokens stamps them with the time that step returned. The
engine's own clock is not read."""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from .traffic import Job


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest element covering a q-fraction
    of the sorted sample (copied from `repro.serving.telemetry`)."""
    if not values:
        return 0.0
    vs = sorted(values)
    rank = math.ceil(q * len(vs))
    return vs[min(max(rank, 1), len(vs)) - 1]


def supported_percentile(n: int, beyond: int = 10) -> Optional[float]:
    """The highest percentile (in %) of n samples with `beyond` samples
    above it; None where n is too small."""
    if n <= beyond:
        return None
    return 100.0 * (n - beyond) / n


@dataclass
class RequestLog:
    job: Job
    t_submit: float
    in_window: bool
    slot: object = None                  # the engine's slot object
    deliveries: List[Tuple[float, int]] = field(default_factory=list)
    n_delivered: int = 0
    t_done: Optional[float] = None
    tokens: List[int] = field(default_factory=list)   # served, when done
    iters_at_close: Optional[int] = None  # decode iterations by window end


class ClosedLoop:
    """Closed-loop clients over a `ContinuousBatchingScheduler`: each
    client submits its next request when the last one finishes."""

    def __init__(self, sched, traffic, request_cls,
                 span: Callable = None, clock: Callable = None):
        self.sched = sched
        self.engine = sched.engine
        self.traffic = traffic
        self.request_cls = request_cls
        self.span = span or (lambda name: contextlib.nullcontext())
        self.clock = clock or time.perf_counter
        self.logs: Dict[str, RequestLog] = {}
        self.live: Dict[str, RequestLog] = {}
        #: per engine step in the window: the cache length of every row
        #: live before it (rows admitted by the step itself hold 0)
        self.step_ctx: List[List[int]] = []
        self.t0 = self.t_end = None

    def submit(self, client: int, t: float, in_window: bool) -> None:
        with self.span("bench.submit"):
            job = self.traffic.next_job(client)
            self.sched.submit(self.request_cls(
                request_id=job.request_id, prompt=job.prompt,
                max_new=job.max_new, task=job.task))
        log = RequestLog(job=job, t_submit=t, in_window=in_window)
        self.logs[job.request_id] = log
        self.live[job.request_id] = log

    def _row_lengths(self) -> List[int]:
        return [s.prefill_pos if s.phase == "prefill" else len(s.history) - 1
                for s in self.engine.slots if s is not None and not s.done]

    def step(self, record_ctx: bool) -> Tuple[float, List[int]]:
        """One scheduler step; returns its end time and the clients whose
        request finished in it."""
        ctx = self._row_lengths() if record_ctx else None
        n_steps = len(self.engine.telemetry.steps)
        with self.span("bench.step"):
            self.sched.step()
        t = self.clock()
        with self.span("bench.deliver"):
            if record_ctx and len(self.engine.telemetry.steps) > n_steps:
                self.step_ctx.append(ctx)
            for s in self.engine.slots:
                if s is not None and s.request_id in self.live:
                    self.live[s.request_id].slot = s
            finished = []
            for rid, log in list(self.live.items()):
                s = log.slot
                if s is None:
                    continue
                n = min(len(s.out), s.max_new)
                if n > log.n_delivered:
                    log.deliveries.append((t, n - log.n_delivered))
                    log.n_delivered = n
                if s.done:
                    log.t_done = t
                    log.tokens = [int(x) for x in s.out[:s.max_new]]
                    del self.live[rid]
                    finished.append(log.job.client)
        return t, finished

    def run_window(self, seconds: float) -> Tuple[float, float]:
        """Serve from now for `seconds`: every client starts at once, and
        the window closes at the end of the first step that ends
        `seconds` or more after it opened."""
        self.t0 = t0 = self.clock()
        for c in range(self.traffic.clients):
            self.submit(c, t0, in_window=True)
        while True:
            t, finished = self.step(record_ctx=True)
            if t - t0 >= seconds:
                break
            for c in finished:
                self.submit(c, t, in_window=True)
        self.t_end = t
        for log in self.logs.values():
            if log.slot is not None:
                log.iters_at_close = len(log.slot.tel.iterations)
        return t0, t

    def finish_first_tokens(self, limit_s: float) -> None:
        """After the window: step on, submitting nothing, until every
        request submitted in the window has its first token."""
        t_stop = self.clock() + limit_s
        while any(log.in_window and not log.deliveries
                  for log in self.live.values()):
            if self.clock() > t_stop:
                raise TimeoutError("requests submitted in the window had "
                                   f"no first token {limit_s} s after it")
            self.step(record_ctx=False)

    # -- what the window recorded ------------------------------------ #

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def window_logs(self) -> List[RequestLog]:
        return [log for log in self.logs.values() if log.in_window]

    def tokens_in_window(self) -> int:
        return sum(n for log in self.logs.values()
                   for t, n in log.deliveries if t <= self.t_end)

    def gaps(self) -> List[float]:
        """Every inter-delivery gap of every request inside the window."""
        out = []
        for log in self.logs.values():
            times = [t for t, _ in log.deliveries if t <= self.t_end]
            out.extend(b - a for a, b in zip(times, times[1:]))
        return out

    def ttfts(self) -> List[float]:
        """Submission to first token, of each request submitted in the
        window (submissions at the closing instant excluded)."""
        return [log.deliveries[0][0] - log.t_submit
                for log in self.window_logs()
                if log.t_submit < self.t_end and log.deliveries]

    def finished(self) -> List[RequestLog]:
        """Requests submitted and finished inside the window."""
        return [log for log in self.window_logs()
                if log.t_done is not None and log.t_done <= self.t_end]


def warm_up(engine, sched_cls, request_cls, clients: int, chunk: int,
            prompt: Callable[[int], List[int]]) -> int:
    """Compile every pass shape a window can run, through the program's
    own scheduler: rounds of `clients` concurrent requests whose prompts
    end in a chunk of each bucketed span length (1, 2, 4, ..., chunk),
    each followed by a one-token decode step. Returns the steps run."""
    n0 = len(engine.telemetry.steps)
    rests, b = [], 1
    while b < chunk:
        rests.append(b // 2 + 1)     # the last chunk rounds up to span b
        b *= 2
    for r, rest in enumerate(rests):
        sched = sched_cls(engine)
        sched.run([request_cls(request_id=f"warm{r}.{c}",
                               prompt=prompt(chunk + rest), max_new=2)
                   for c in range(clients)])
    return len(engine.telemetry.steps) - n0
