"""What one run measured, as the metric readers see it."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional

from .tracereduce import Reduction


@dataclass
class RunRecord:
    arch: Any                    # the configuration's architecture's Arch
    peaks: dict                  # the chip's entry of bench/peaks.json
    setup_s: float               # process start to window start
    window_s: float              # the window, host clock
    tokens: int                  # output tokens delivered in the window
    gaps: List[float]            # inter-delivery gaps in the window, s
    ttfts: List[float]           # submit to first token, s
    steps: list                  # the engine's StepTelemetry, window steps
    step_ctx: List[List[int]]    # cache lengths of the rows before each
    iterations: list             # IterationTelemetry of window decode rows
    compiles: int                # programs lowered inside the window
    trace: Optional[Reduction] = None

    @property
    def prefill_tokens(self) -> int:
        return sum(s.prefill_tokens for s in self.steps)

    @property
    def mean_context(self) -> float:
        rows = [c for ctx in self.step_ctx for c in ctx]
        return sum(rows) / len(rows) if rows else 0.0
