"""The one general generator of serve traffic. A mix is a data file of
parameters (``qbench/workloads/<cell>.json``): offered rate, popularity skew,
how the arrivals are spaced. Every seed gets the SAME multiset of gaps and of
popularity ranks, in another order and mapped onto other nodes: the amount
of work does not change with the seed, only which nodes are asked for when.

Copied, and cut to what a cell needs, from `quiver_tpu.serve.trace_gen`
(`zipfian_trace`, `poisson_arrivals`): the yardstick must not move when the
program does."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import graphgen

SHAPE_SEED = graphgen.SHAPE_SEED  # the seed of the multisets every run shares


class Requests(NamedTuple):
    due_s: np.ndarray   # [n] float64, seconds from the window's start, ascending
    nodes: np.ndarray   # [n] int64 node ids


def arrival_gaps(rate: float, seconds: float, arrivals: str, burst: int = 1) -> np.ndarray:
    """Gaps whose running sum stays inside ``seconds``. ``poisson``:
    independent users (exponential gaps at ``rate``). ``bursty``: the same
    mean rate with ``burst`` requests arriving together, the bursts Poisson."""
    rng = graphgen.stream(SHAPE_SEED, 1)
    n = int(rate * seconds * 1.2) + 64
    if arrivals == "poisson":
        gaps = rng.exponential(1.0 / rate, n)
    elif arrivals == "bursty":
        gaps = np.zeros(n)
        gaps[::burst] = rng.exponential(burst / rate, len(gaps[::burst]))
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    return gaps[: int(np.searchsorted(np.cumsum(gaps), seconds))]


def zipf_ranks(n_nodes: int, n_requests: int, alpha: float) -> np.ndarray:
    """[n_requests] popularity ranks (0 = hottest), P(rank r) ~ 1/(r+1)**alpha."""
    rng = graphgen.stream(SHAPE_SEED, 2)
    p = np.arange(1, n_nodes + 1, dtype=np.float64) ** (-float(alpha))
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, rng.random(n_requests)), n_nodes - 1)


def requests(n_nodes: int, seed: int, *, rate: float, seconds: float, alpha: float,
             arrivals: str = "poisson", burst: int = 1) -> Requests:
    gaps = arrival_gaps(rate, seconds, arrivals, burst)
    ranks = zipf_ranks(n_nodes, gaps.shape[0], alpha)
    rng = graphgen.stream(seed, 9)
    if arrivals == "bursty":  # keep each burst whole: permute the bursts
        order = rng.permutation(gaps.shape[0] // burst)
        idx = (order[:, None] * burst + np.arange(burst)[None, :]).reshape(-1)
        gaps = np.concatenate([gaps[idx], gaps[idx.shape[0]:]])
    else:
        gaps = rng.permutation(gaps)
    node_of_rank = rng.permutation(n_nodes).astype(np.int64)
    return Requests(np.cumsum(gaps), node_of_rank[rng.permutation(ranks)])
