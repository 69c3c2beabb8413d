"""The one traffic generator. A mix is a file of parameters:

    rate_rps        mean arrival rate (open loop, Poisson)
    n_input         prompt length of every request
    n_output        tokens served per request
    t_comm_s        [lo, hi]: UE -> compute latency, uniform (SLS-like spread)
    b_total_s       end-to-end budget of every request

Seeds reorder the work and do not change it. The requests of a window
(inter-arrival gap, t_comm) are drawn once for the mix and the
window: the gaps are the quantiles of the exponential distribution, so
arrivals stay Poisson in distribution, in an order shuffled with a fixed
salt. A seed then permutes BLOCKS equal runs of that sequence and draws
the token ids. So every seed sends the same requests, with the same
bursts, in another order of blocks: the load and the number of requests
are fixed by the mix and the window, and seeds differ in where the bursts
fall and in what the tokens are.

The arrival process and the t_comm spread are those of
`repro.launch.serve.build_trace`; the stratification is the benchmark's.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

BLOCKS = 8  # runs of requests a seed permutes


@dataclasses.dataclass
class Request:
    uid: int
    t_gen: float
    t_comm: float
    b_total: float
    prompt: np.ndarray  # (n_input,) int32
    n_output: int

    @property
    def arrival(self) -> float:
        return self.t_gen + self.t_comm


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), salt])


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["rate_rps"]) * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The window's requests, sorted by arrival at the UE (t_gen)."""
    n = n_requests(mix, seconds)
    rng = _rng(0, 0)  # the window's requests: the same for every seed
    # stratified exponential gaps, normalised so n arrivals span the window
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= n / gaps.sum()
    rng.shuffle(gaps)
    lo, hi = mix["t_comm_s"]
    t_comm = lo + (hi - lo) * (np.arange(n) + 0.5) / n
    rng.shuffle(t_comm)

    # the seed permutes runs of requests
    blocks = np.array_split(np.arange(n), min(BLOCKS, n))
    order = np.concatenate([blocks[i] for i in _rng(seed, 0).permutation(len(blocks))])
    gaps, t_comm = gaps[order], t_comm[order]
    t_gen = np.cumsum(gaps) / n * seconds

    tok_rng = _rng(seed, 1)
    return [Request(uid=i, t_gen=float(t_gen[i]), t_comm=float(t_comm[i]),
                    b_total=float(mix["b_total_s"]),
                    prompt=tok_rng.integers(0, vocab, int(mix["n_input"]),
                                            dtype=np.int32),
                    n_output=int(mix["n_output"]))
            for i in range(n)]
