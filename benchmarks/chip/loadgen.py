"""Open-loop request schedules, and the accounting of how late they ran.

Popularity is Zipf(``a``) over a fixed random relabelling of the node ids,
arrivals are Poisson: the arithmetic of the repository's
``serve.batcher.zipfian_trace``, copied.  One change keeps the work of a run
independent of its seed: the multiset of requested nodes and of gaps between
arrivals is drawn once from the traffic's own ``popularity_seed``, and the
run's seed only shuffles both.  Every seed then asks for the same nodes as
often, with the same gaps, in another order; the gaps are scaled so that
the last arrival falls at the end of the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    t: np.ndarray          # (R,) float64 seconds after the window opens
    nodes: np.ndarray      # (R,) int64 requested node ids

    def __len__(self) -> int:
        return int(self.t.shape[0])


def zipf_poisson(num_nodes: int, rate: float, seconds: float, a: float,
                 popularity_seed: int, seed: int) -> Schedule:
    count = max(int(round(rate * seconds)), 1)
    fixed = np.random.default_rng(popularity_seed)
    p = np.arange(1, num_nodes + 1, dtype=np.float64) ** (-float(a))
    p /= p.sum()
    relabel = fixed.permutation(num_nodes)
    picks = relabel[fixed.choice(num_nodes, size=count, p=p)]
    gaps = fixed.exponential(1.0 / rate, size=count)
    gaps *= seconds / gaps.sum()
    run = np.random.default_rng(seed)
    return Schedule(t=np.cumsum(run.permutation(gaps)),
                    nodes=run.permutation(picks).astype(np.int64))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all values, interpolated linearly between
    the two nearest ranks; infinite where either of them is (a request that
    was never answered counts as infinitely late)."""
    v = np.sort(np.asarray(values, np.float64))
    rank = q / 100.0 * (v.shape[0] - 1)
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    if np.isinf(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (rank - lo))


def lateness(scheduled: np.ndarray, submitted: np.ndarray) -> np.ndarray:
    """Seconds by which each request was handed to the server after its
    scheduled arrival (never negative: the generator does not send early)."""
    late = np.asarray(submitted, np.float64) - np.asarray(scheduled,
                                                          np.float64)
    if np.any(late < -1e-9):
        raise ValueError("a request was sent before its scheduled arrival")
    return np.maximum(late, 0.0)
