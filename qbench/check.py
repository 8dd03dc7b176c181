"""The comparison that decides ``correct``: what the timed path produced,
held against the host graph, the host feature table and the plain reference.
Every number compared has a limit of its own (from the cell's file; PERF.md
section 2 gives the readings each was set from); `verdict` prints each beside
its limit."""

from __future__ import annotations

import sys
from typing import Dict, List, NamedTuple, Sequence

import numpy as np


class Block(NamedTuple):
    cols: np.ndarray   # [W, k] positions in this hop's source rows
    mask: np.ndarray   # [W, k] bool
    n_src: int         # valid source rows (dedup layouts: a prefix)


class Compared(NamedTuple):
    name: str
    value: float
    limit: float


class EdgeOracle:
    """Is (u, v) an edge of the host CSR? One sorted array of ``u * N + v``
    keys, membership by binary search."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.n = int(indptr.shape[0] - 1)
        self.degree = np.diff(indptr)
        keys = np.repeat(np.arange(self.n, dtype=np.int64), self.degree)
        keys *= self.n
        keys += indices
        keys.sort()
        self.keys = keys

    def has_edges(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        q = src.astype(np.int64) * self.n + dst.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.shape[0] - 1)
        return self.keys[pos] == q


def structural_cols(w: int, k: int) -> np.ndarray:
    """Explicit positions of the fused pipeline's structural layout:
    neighbour (i, j) of target i sits at source row ``w + j*w + i``."""
    return (w + np.arange(k, dtype=np.int32)[None, :] * w
            + np.arange(w, dtype=np.int32)[:, None])


def sample_faults(oracle: EdgeOracle, n_id: np.ndarray, blocks: Sequence[Block],
                  structural: bool, batch: int) -> Dict[str, int]:
    """Hold one sampled batch against the host CSR. ``blocks`` outermost hop
    first (as the model consumes them), so the seeds' block is the last.
    Counts sampled pairs that are no edge, and valid targets whose number of
    sampled neighbours is not min(degree, fan-out) (padding targets: not 0)."""
    n_id = n_id.astype(np.int64)
    not_edges = wrong_fanout = pairs = 0
    valid = np.ones(batch, bool)  # the seeds are all valid targets
    for blk in reversed(blocks):
        w, k = blk.mask.shape
        if valid is None:  # dedup layouts: the valid rows are a prefix
            valid = np.arange(w) < n_valid
        ids = np.clip(n_id[:w], 0, oracle.n - 1)
        want = np.where(valid, np.minimum(oracle.degree[ids], k), 0)
        wrong_fanout += int((blk.mask.sum(axis=1) != want).sum())
        ti, tj = np.nonzero(blk.mask)
        ok = oracle.has_edges(n_id[ti], n_id[blk.cols[ti, tj]])
        not_edges += int((~ok).sum())
        pairs += int(ok.size)
        if structural:
            valid = np.concatenate([valid, blk.mask.T.reshape(-1)])
        else:
            valid, n_valid = None, blk.n_src
    return {"sampled_pairs": pairs, "not_edges": not_edges,
            "wrong_fanout": wrong_fanout}


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, np.asarray(tree, np.float32)


def leaf_norms(tree) -> Dict[str, float]:
    return {name: float(np.linalg.norm(leaf.astype(np.float64)))
            for name, leaf in _leaves(tree)}


def worst_norm_gap(got: Dict[str, float], want: Dict[str, float],
                   skip: Sequence[str] = ()) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    names = [n for n in want if n not in skip]
    median = float(np.median([want[n] for n in names]))
    return max(abs(got[n] - want[n]) / max(want[n], median) for n in names)


def quiet_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: under Adam they move by round-off alone, so their change is not
    compared."""
    median = float(np.median(list(ref_grad_norms.values())))
    return [n for n, v in ref_grad_norms.items() if v < 1e-3 * median]


def tree_diff(a, b):
    la, lb = dict(_leaves(a)), dict(_leaves(b))
    return {n: la[n].astype(np.float64) - lb[n].astype(np.float64) for n in la}


def verdict(compared: Sequence[Compared]) -> bool:
    """Print each number beside its limit as the last lines of standard
    error; ``correct`` is that none passes its limit (a NaN fails)."""
    ok = True
    for c in compared:
        passed = bool(c.value <= c.limit)
        ok = ok and passed
        print(f"check {c.name}: {c.value:.6g} (limit {c.limit:.6g})"
              f"{'' if passed else '  FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return ok


def as_record(compared: Sequence[Compared]) -> Dict[str, Dict[str, float]]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in compared}
