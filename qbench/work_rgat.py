"""Operations and bytes a relational attention step needs, in `qbench.work`'s
manner: the work of the algorithm (R-GAT over typed sampled blocks,
`qbench.reference_rgat`'s equations) from VALID sizes, not of any
implementation. Padding lanes, recomputation and the sampler's index
arithmetic count nothing, so a later kernel cannot make these numbers stale.

A relation-layer is described by ``(d_in, sources, targets, pairs)``: its
input width, the valid rows of its source type's segment, its valid targets
and its valid sampled (target, neighbour) pairs."""

from __future__ import annotations

from typing import Sequence, Tuple

from .work_gat import PAIR_OPS

RelationLayer = Tuple[int, float, float, float]  # (d_in, sources, targets, pairs)


def project_flops(layers: Sequence[RelationLayer], width: int) -> float:
    """Forward products ``W_src x`` of every valid source row and ``W_dst
    x`` of every valid target, ``[*, d_in] x [d_in, width]`` each."""
    return sum(2.0 * (s + t) * d_in * width for d_in, s, t, _ in layers)


def rgat_flops(layers: Sequence[Sequence[RelationLayer]], width: int, heads: int,
               head: Tuple[float, int, int], backward: bool) -> float:
    """FLOPs of one R-GAT pass. ``layers[l]`` the relation-layers of layer
    l, outermost first; ``head`` (seeds, hidden, classes) of the output
    product. Forward, per relation-layer: both projections, the two halves
    of the scores (``2 width`` a source row and a target), per pair and head
    `PAIR_OPS` and ``2 D`` for the weighted sum; the relations' sum, one add
    a lane per relation and target. Backward: every product's weight
    gradient, its input gradient in every layer but the first (whose input
    is data), and the per-pair work twice."""
    d = width // heads
    total = 0.0
    for l, rels in enumerate(layers):
        for d_in, s, t, e in rels:
            proj = project_flops([(d_in, s, t, e)], width)
            scores = 2.0 * width * (s + t)
            attended = float(e) * heads * (PAIR_OPS + 2.0 * d)
            total += proj + scores + attended + t * width
            if backward:
                total += proj * (1 if l == 0 else 2) + 2 * attended
    seeds, hidden, classes = head
    out = 2.0 * seeds * hidden * classes
    return total + out * (3 if backward else 1)


def edge_bytes(layers: Sequence[Sequence[RelationLayer]], width: int, backward: bool) -> float:
    """Bytes the per-edge part must move: each valid pair's projected source
    row (``width`` float32) read once and each valid target's output written
    once; the backward pass twice that (the rows read again for the shares'
    gradient, the rows' own gradient written)."""
    forward = sum((float(e) + t) * width * 4.0 for rels in layers for _, _, t, e in rels)
    return forward * (3 if backward else 1)
