"""The stand-in of a typed (heterogeneous) configuration, made from
``--seed``: node counts per type, one CSR per relation, a feature table of
every type's rows stacked, labels and a train split of the seed type.

- A relation with a ``graph`` block between one type and itself is
  `graphgen.powerlaw_graph`'s graph at that block and seed, as the
  homogeneous configurations make theirs (the cites relation of IGBH-small
  is igb-small-sage's stand-in, seed for seed).
- A relation between two types is a configuration-model bipartite graph:
  each side's degrees a capped Lomax profile (`graphgen.powerlaw_degrees`,
  the multiset from `graphgen.SHAPE_SEED` and dealt out by the seed, every
  node at least 1), the destination side's edge slots in order and the
  source side's shuffled onto them. Its reverse (``reverse_of``) is the
  same edges indexed by the other side, laid out without a sort.
- The table is made in row blocks, each drawn in float32 and rounded to the
  configuration's ``feature_dtype`` as it is stored: at IGBH-small a float32
  copy of it all would be 12.8 GB of a one-chip host's 40 GiB.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, NamedTuple, Tuple

import numpy as np

from . import graphgen

ROW_BLOCK = 32768  # table rows drawn at a time on one thread (128 MB of float32 at 1024 lanes)


class Relation(NamedTuple):
    indptr: np.ndarray   # [n_dst + 1] int64
    indices: np.ndarray  # [E] int32 source-type local ids


class HeteroGraph(NamedTuple):
    num_nodes: Dict[str, int]
    relations: Dict[Tuple[str, str, str], Relation]


def _degrees(n: int, n_edges: int, profile: Dict[str, Any], path: Tuple[int, ...], seed: int):
    deg = graphgen.powerlaw_degrees(n, n_edges, profile["alpha"], profile["shift"],
                                    profile["max_degree"], graphgen.stream(graphgen.SHAPE_SEED, *path))
    return deg[graphgen.stream(seed, *path).permutation(n)]


def _owners(deg: np.ndarray) -> np.ndarray:
    """[sum(deg)] int32: the node each slot belongs to, slots in node order."""
    return np.repeat(np.arange(deg.shape[0], dtype=np.int32), deg)


def _indptr(deg: np.ndarray) -> np.ndarray:
    out = np.zeros(deg.shape[0] + 1, np.int64)
    np.cumsum(deg, out=out[1:])
    return out


def bipartite(n_dst: int, n_src: int, n_edges: int, dst_degree: Dict[str, Any],
              src_degree: Dict[str, Any], seed: int, index: int) -> Tuple[Relation, Relation]:
    """(the relation indexed by destination, its reverse indexed by source)."""
    deg_dst = _degrees(n_dst, n_edges, dst_degree, (30, index, 1), seed)
    deg_src = _degrees(n_src, n_edges, src_degree, (30, index, 2), seed)
    # slot q of the destination side (in destination order) takes source slot perm[q]
    perm = graphgen.stream(seed, 31, index).permutation(n_edges)
    forward = Relation(_indptr(deg_dst), _owners(deg_src)[perm])
    inverse = np.empty(n_edges, np.int64)
    inverse[perm] = np.arange(n_edges)
    reverse = Relation(_indptr(deg_src), _owners(deg_dst)[inverse])
    return forward, reverse


def hetero_graph(config: Dict[str, Any], seed: int) -> HeteroGraph:
    """Every relation of ``config["relations"]``, in its order."""
    nodes = {t: int(n) for t, n in config["node_types"].items()}
    made: Dict[Tuple[str, str, str], Relation] = {}
    for i, spec in enumerate(config["relations"]):
        rel = tuple(spec["relation"])
        src, _, dst = rel
        if "reverse_of" in spec:
            continue
        if "graph" in spec:
            g = spec["graph"]
            if src != dst:
                raise ValueError(f"{rel}: a powerlaw graph block is a relation of one type")
            graph = graphgen.powerlaw_graph(nodes[dst], spec["n_edges"], seed, alpha=g["alpha"],
                                            shift=g["shift"], max_degree=g["max_degree"])
            made[rel] = Relation(graph.indptr, graph.indices.astype(np.int32))
            continue
        forward, reverse = bipartite(nodes[dst], nodes[src], spec["n_edges"], spec["dst_degree"],
                                     spec["src_degree"], seed, i)
        made[rel] = forward
        for other in config["relations"]:
            if other.get("reverse_of") == list(rel):
                made[tuple(other["relation"])] = reverse
    missing = [tuple(s["relation"]) for s in config["relations"] if tuple(s["relation"]) not in made]
    if missing:
        raise ValueError(f"relations with nothing to make them from: {missing}")
    return HeteroGraph(nodes, {tuple(s["relation"]): made[tuple(s["relation"])]
                               for s in config["relations"]})


def table_dtype(name: str) -> np.dtype:
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16) if name == "bfloat16" else np.dtype(name)


def features_and_labels(config: Dict[str, Any], seed: int):
    """([sum of node counts, feat_dim] table in ``feature_dtype``, [n_seed_type]
    int32 labels): unit normals, the seed type's rows nudged along a
    per-class direction as `graphgen.features_and_labels` nudges them, in
    row blocks on a few threads."""
    nodes = config["node_types"]
    dim, classes = config["feat_dim"], config["classes"]
    seed_type = config["seed_type"]
    rng = graphgen.stream(seed, 3)
    labels = rng.integers(0, classes, nodes[seed_type]).astype(np.int32)
    basis = rng.standard_normal((classes, dim), dtype=np.float32)
    basis *= np.float32(config["label_signal"])
    total = sum(nodes.values())
    table = np.empty((total, dim), table_dtype(config["feature_dtype"]))
    starts = dict(zip(nodes, np.cumsum([0] + list(nodes.values()))))
    lo_label, hi_label = int(starts[seed_type]), int(starts[seed_type]) + nodes[seed_type]
    bounds = list(range(0, total, ROW_BLOCK)) + [total]

    def fill(i: int) -> None:
        lo, hi = bounds[i], bounds[i + 1]
        block = graphgen.stream(seed, 4, i).standard_normal((hi - lo, dim), dtype=np.float32)
        a, b = max(lo, lo_label), min(hi, hi_label)
        if a < b:
            block[a - lo: b - lo] += basis[labels[a - lo_label: b - lo_label]]
        table[lo:hi] = block.astype(table.dtype)

    with ThreadPoolExecutor(graphgen.GEN_THREADS) as pool:
        list(pool.map(fill, range(len(bounds) - 1)))
    return table, labels
