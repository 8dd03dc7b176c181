"""`qbench.check`'s comparisons for a typed sample: the sampled blocks held
against each relation's host CSR, and the gathered rows against the host
table, bit for bit. Every count here has the limit 0. And which leaf of the
parameters gives `check.worst_norm_gap` its reading, so that a reading near
its limit names where it comes from."""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from . import check


class RelationOracle:
    """`check.EdgeOracle` for a relation between two types: is source ``v``
    an in-neighbour of destination ``u``? Keys ``u * n_src + v``."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, n_src: int):
        self.n = int(indptr.shape[0] - 1)
        self.n_src = int(n_src)
        self.degree = np.diff(indptr)
        keys = np.repeat(np.arange(self.n, dtype=np.int64), self.degree)
        keys *= self.n_src
        keys += indices
        keys.sort()
        self.keys = keys

    def has_edges(self, dst: np.ndarray, src: np.ndarray) -> np.ndarray:
        q = dst.astype(np.int64) * self.n_src + src.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.shape[0] - 1)
        return self.keys[pos] == q


class TypedStep(NamedTuple):
    """One sampled step as plain arrays (`kinds.train_rgat.collect`)."""
    type_ids: Dict[str, np.ndarray]     # last hop's local ids per type (valid prefix)
    type_counts: Dict[str, np.ndarray]  # [L + 1] valid count per hop
    layers: List[Dict[str, Tuple[np.ndarray, np.ndarray]]]  # outermost first: key -> (cols, mask)


def sample_faults(oracles: Dict[str, RelationOracle], step: TypedStep) -> Dict[str, int]:
    """Count, over every layer and relation: sampled pairs that are no edge
    of the relation (``not_edges``); valid targets whose number of valid
    slots is not min(in-degree, fan-out), and padding targets with any
    (``wrong_fanout``); segments whose valid part repeats a node or whose
    counts fall from one hop to the next, the dedup and the prefix contract
    (``not_unique``). ``sampled_pairs`` counts the pairs held."""
    out = {"sampled_pairs": 0, "not_edges": 0, "wrong_fanout": 0, "not_unique": 0}
    hops = len(step.layers)
    for t, ids in step.type_ids.items():
        counts = step.type_counts[t]
        valid = ids[: counts[-1]]
        out["not_unique"] += int(np.unique(valid).shape[0] != valid.shape[0])
        out["not_unique"] += int((np.diff(counts) < 0).sum())
    for l, layer in enumerate(step.layers):
        hop = hops - 1 - l  # the hop whose draws this layer reads
        for key, (cols, mask) in layer.items():
            src, _, dst = key.split("__")
            oracle = oracles[key]
            w, k = mask.shape
            n_valid = int(step.type_counts[dst][hop])
            targets = step.type_ids[dst][:w].astype(np.int64)
            want = np.where(np.arange(w) < n_valid,
                            np.minimum(oracle.degree[np.clip(targets, 0, oracle.n - 1)], k), 0)
            out["wrong_fanout"] += int((mask.sum(axis=1) != want).sum())
            ti, tj = np.nonzero(mask)
            sources = step.type_ids[src][cols[ti, tj]].astype(np.int64)
            ok = oracle.has_edges(targets[ti], sources)
            out["not_edges"] += int((~ok).sum())
            out["sampled_pairs"] += int(ok.size)
    return out


def gather_faults(table: np.ndarray, ids: np.ndarray, sel: np.ndarray, rows: np.ndarray) -> int:
    """Gathered rows at positions ``sel`` of the global ids ``ids`` that
    differ from the host table's, bit for bit."""
    want = table[np.clip(ids[sel].astype(np.int64), 0, table.shape[0] - 1)]
    bits = np.dtype(f"u{table.dtype.itemsize}")
    return int((rows.view(bits) != want.view(bits)).any(axis=1).sum())


def worst_leaves(got: Dict[str, object], ref: Dict[str, object]) -> Dict[str, str]:
    """The leaf behind ``grad1_norm_gap`` and ``dparam3_norm_gap``
    (`qbench.kinds.train.readings`' numbers): the one whose norm gap, in
    `check.worst_norm_gap`'s measure, is the largest."""
    ref_grad = check.leaf_norms(ref["grad1"])
    pairs = {"grad1": (check.leaf_norms(got["grad1"]), ref_grad, ()),
             "dparam3": (check.leaf_norms(check.tree_diff(got["params3"], got["params0"])),
                         check.leaf_norms(check.tree_diff(ref["params3"], ref["params0"])),
                         check.quiet_leaves(ref_grad))}
    out = {}
    for name, (mine, want, skip) in pairs.items():
        names = [n for n in want if n not in skip]
        median = float(np.median([want[n] for n in names]))
        out[name] = max(names, key=lambda n: abs(mine[n] - want[n]) / max(want[n], median))
    return out
