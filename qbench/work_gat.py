"""Operations and bytes an attention step needs, in `qbench.work`'s manner: the
work of the algorithm (GAT over sampled blocks, `qbench.reference_gat`'s
equations) from VALID sizes, not of any implementation. Padding lanes, the
rows a backward pass gathers again and the sampler's index arithmetic count
nothing, so a later kernel cannot make these numbers stale."""

from __future__ import annotations

from typing import Sequence, Tuple

PAIR_OPS = 4  # a pair and head: the add, the LeakyReLU, the exp and the normalising divide


def project_flops(sources: float, d_in: int, heads: int, dim: int) -> float:
    """``W x`` for every valid source row: one [S, d_in] x [d_in, H D] product."""
    return 2.0 * sources * d_in * heads * dim


def gat_flops(sources: Sequence[float], targets: Sequence[float], pairs: Sequence[float],
              dims: Sequence[Tuple[int, int, int]], backward: bool) -> float:
    """FLOPs of one GAT pass over sampled blocks, outermost hop first.

    ``sources[i]`` valid source rows of layer i, ``targets[i]`` its valid
    targets, ``pairs[i]`` its valid sampled (target, neighbour) pairs (each
    target attends itself besides: ``pairs + targets`` scores), ``dims[i]``
    its (input width, heads H, width of a head D). Forward, per layer: the
    projection; the two halves of the scores, ``2 H D`` a source row and a
    target; per attended pair and head `PAIR_OPS` and ``2 D`` for the weighted
    sum. Backward: the projection's weight gradient, its input gradient in
    every layer but the first (whose input is data), and the per-pair work
    twice."""
    total = 0.0
    for i, (s, t, e, (d_in, h, d)) in enumerate(zip(sources, targets, pairs, dims)):
        proj = project_flops(s, d_in, h, d)
        scores = 2.0 * h * d * (s + t)
        attended = (float(e) + t) * h * (PAIR_OPS + 2.0 * d)
        total += proj + scores + attended
        if backward:
            total += proj * (1 if i == 0 else 2) + 2 * attended
    return total


def edge_bytes(pairs: float, targets: float, heads: int, dim: int, backward: bool) -> float:
    """Bytes the per-edge part of one layer must move: each valid pair's
    projected row (H D float32) read once and each valid target's output
    written once; the backward pass twice that (the rows read for the
    shares' gradient, the rows' own gradient written)."""
    forward = (float(pairs) + float(targets)) * heads * dim * 4.0
    return forward * (3 if backward else 1)
