"""The benchmark's stand-in data, made from ``--seed``: a skewed graph in CSR
form at a configuration's published shape, its feature table, labels and
train split. The sandbox and the chip machine hold no dataset, so every run
makes its own; the same seed gives the same bytes.

Degrees are a capped Lomax profile scaled to the edge count (the shape of
`quiver_tpu.datasets._powerlaw_csr_arrays` with its ``+ 1`` shift and its
1%-of-all-edges cap replaced by the configuration's ``shift`` and
``max_degree``). Destinations are degree-proportional: a uniformly drawn edge
slot's SOURCE is a degree-proportional node, so one `randint` and one take
replace an inverse-CDF search per edge.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

SHAPE_SEED = 20250925  # of what every run shares: the degree multiset
GEN_THREADS = 8  # numpy's generators release the GIL; chunks are fixed, so
#                  the bytes do not depend on how many threads really ran


class Graph(NamedTuple):
    indptr: np.ndarray   # [N+1] int64
    indices: np.ndarray  # [E] int64


def stream(seed: int, *path: int) -> np.random.Generator:
    """Independent generator for one purpose (``path``) of one run seed. Any
    non-negative whole number is a seed: the driver's pass 2**31."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


def powerlaw_degrees(n_nodes: int, n_edges: int, alpha: float, shift: float,
                     max_degree: int, rng: np.random.Generator) -> np.ndarray:
    """[N] int64 out-degrees summing to exactly ``n_edges``, every node at
    least 1, none above ``max_degree``."""
    raw = rng.pareto(alpha, n_nodes) + shift
    for _ in range(16):  # capping shrinks the sum, which raises the scale
        scale = n_edges / raw.sum()
        if not (raw * scale > max_degree).any():
            break
        np.minimum(raw, max_degree / scale, out=raw)
    deg = np.maximum((raw * (n_edges / raw.sum())).astype(np.int64), 1)
    np.minimum(deg, max_degree, out=deg)
    diff = int(deg.sum()) - n_edges
    while diff != 0:  # spread the rounding remainder over random nodes
        if diff > 0:
            idx = rng.choice(np.flatnonzero(deg > 1), min(diff, n_nodes // 2),
                             replace=False)
            deg[idx] -= 1
        else:
            idx = rng.choice(np.flatnonzero(deg < max_degree),
                             min(-diff, n_nodes // 2), replace=False)
            deg[idx] += 1
        diff = int(deg.sum()) - n_edges
    return deg


def powerlaw_graph(n_nodes: int, n_edges: int, seed: int, *, alpha: float,
                   shift: float, max_degree: int) -> Graph:
    """CSR of ``n_edges`` directed edges over ``n_nodes`` nodes. The degree
    MULTISET is the same for every seed (drawn from `SHAPE_SEED`) and the
    seed deals it out to other nodes: the 128-lane tile table the sampler
    builds then has the same number of rows on every seed, so every seed runs
    the same compiled shapes (with per-seed degrees the first three steps
    recompiled for 7 s on every new seed; my chip run, PR 25)."""
    deg = powerlaw_degrees(n_nodes, n_edges, alpha, shift, max_degree,
                           stream(SHAPE_SEED, 1))
    deg = deg[stream(seed, 1).permutation(n_nodes)]
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    # owner[slot] = the node whose row holds that edge slot. Every degree is
    # at least 1, so row starts are distinct: mark them and count (a third
    # of np.repeat's time at 124M slots)
    marks = np.zeros(n_edges, np.int32)
    marks[indptr[1:-1]] = 1
    owner = np.cumsum(marks, dtype=np.int32)
    del marks
    indices = np.empty(n_edges, np.int64)
    bounds = np.linspace(0, n_edges, GEN_THREADS + 1).astype(np.int64)

    def fill(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        slots = stream(seed, 2, i).integers(0, n_edges, hi - lo)
        indices[lo:hi] = owner[slots]

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(GEN_THREADS)))
    return Graph(indptr, indices)


def skew(indptr: np.ndarray) -> dict:
    """The two shares the source documents for its graph, and the tail."""
    deg = np.diff(indptr)
    above = deg > deg.mean()
    return {"nodes_above_mean_share": float(above.mean()),
            "edges_on_them_share": float(deg[above].sum() / deg.sum()),
            "max_degree": int(deg.max()), "median_degree": float(np.median(deg))}


def features_and_labels(n_nodes: int, dim: int, classes: int, seed: int,
                        label_signal: float = 1.5):
    """([N, dim] float32 table, [N] int32 labels): unit normals nudged along
    a per-class direction, so that the task can be learned and gradients are
    not those of pure noise. Made in fixed row chunks on a few threads."""
    rng = stream(seed, 3)
    labels = rng.integers(0, classes, n_nodes).astype(np.int32)
    basis = rng.standard_normal((classes, dim), dtype=np.float32)
    basis *= np.float32(label_signal)
    table = np.empty((n_nodes, dim), np.float32)
    bounds = np.linspace(0, n_nodes, 4 * GEN_THREADS + 1).astype(np.int64)

    def fill(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        stream(seed, 4, i).standard_normal(out=table[lo:hi], dtype=np.float32)
        table[lo:hi] += basis[labels[lo:hi]]

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(len(bounds) - 1)))
    return table, labels


def train_split(n_nodes: int, n_train: int, seed: int) -> np.ndarray:
    return np.sort(stream(seed, 5).choice(n_nodes, n_train, replace=False))
