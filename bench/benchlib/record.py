"""What one run leaves for the metric readers in bench/metrics/."""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .tracing import TraceSummary


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


@dataclasses.dataclass
class RequestLog:
    uid: int
    t_gen: float
    arrival: float  # t_gen + t_comm: at the compute node
    b_total: float
    n_output: int
    admitted: Optional[float] = None  # server clock at submit; None: dropped
    token_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def served(self) -> bool:
        return self.admitted is not None and len(self.token_times) == self.n_output

    @property
    def e2e(self) -> float:
        return self.token_times[-1] - self.t_gen

    @property
    def ttft(self) -> float:
        return self.token_times[0] - self.arrival

    @property
    def tpot(self) -> Optional[float]:
        if self.n_output < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) / (self.n_output - 1)


@dataclasses.dataclass
class RunRecord:
    cell: str
    seconds: float  # the window: arrivals at the UE span [0, seconds]
    model: dict  # the configuration's "model" block
    ref: ModuleType  # its reference module: prefill_counts, decode_counts
    peaks: dict
    setup_s: float
    sent: int
    dropped: int  # ServeStats.n_dropped
    requests: List[RequestLog]
    # (host seconds, prompt length, inside the traced window)
    prefill_calls: List[Tuple[float, int, bool]]
    # (host seconds, positions of the slots stepped, inside the traced window)
    decode_calls: List[Tuple[float, Tuple[int, ...], bool]]
    prefill_s_program: List[float]  # GenResult.prefill_s of served requests
    trace: Optional[TraceSummary] = None

    def served(self) -> List[RequestLog]:
        return [r for r in self.requests if r.served]

    def prefill_flops(self) -> Tuple[float, float]:
        """(model FLOPs, host seconds) summed over the prefill calls."""
        return (sum(self.ref.prefill_counts(self.model, s)[0]
                    for _, s, _ in self.prefill_calls),
                sum(t for t, _, _ in self.prefill_calls))

    def decode_flops(self) -> Tuple[float, float]:
        """(model FLOPs, host seconds) summed over the decode steps."""
        return (sum(self.ref.decode_counts(self.model, p)[0]
                    for _, p, _ in self.decode_calls),
                sum(t for t, _, _ in self.decode_calls))

    def least_time(self, kind: str) -> float:
        """Summed least time of the traced calls of `kind`."""
        calls = self.prefill_calls if kind == "prefill" else self.decode_calls
        fn = self.ref.prefill_counts if kind == "prefill" else self.ref.decode_counts
        return sum(least_time(*fn(self.model, arg), self.peaks)
                   for _, arg, traced in calls if traced)


def p95(values: Sequence[float]) -> Optional[float]:
    """95th percentile (numpy's linear interpolation); None when empty."""
    vals = [v for v in values if v is not None]
    return float(np.percentile(vals, 95)) if vals else None
