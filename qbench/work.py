"""Operations and bytes a step needs, from shapes and sampled sizes alone. It
counts the work of the algorithm (GraphSAGE with a mean aggregator, a row
gather), not of any implementation: padding lanes, recomputation and the
sampler's own index arithmetic count nothing, so a later kernel cannot make
these numbers stale."""

from __future__ import annotations

from typing import Sequence, Tuple


def sage_flops(targets: Sequence[int], neighbours: Sequence[int],
               dims: Sequence[Tuple[int, int]], backward: bool) -> float:
    """FLOPs of one GraphSAGE pass over sampled blocks, outermost hop first.

    ``targets[i]`` valid target rows of layer i, ``neighbours[i]`` valid
    sampled (target, neighbour) pairs of layer i, ``dims[i]`` its (in, out)
    widths. Forward, per layer: one add per pair and input lane for the mean,
    and two [targets, in] x [in, out] products (2 FLOPs per multiply-add).
    Backward doubles each product (input and weight gradients) and scatters
    the mean's gradient back, except in the first layer, whose input is data:
    only the weight gradients are needed there."""
    total = 0.0
    for i, (t, e, (d_in, d_out)) in enumerate(zip(targets, neighbours, dims)):
        mean = float(e) * d_in
        products = 2 * 2.0 * t * d_in * d_out
        total += mean + products
        if backward:
            total += products if i == 0 else 2 * products + mean
    return total


def gather_bytes(rows: int, row_bytes: int) -> float:
    """Bytes a row gather must move: every row read once and written once."""
    return 2.0 * rows * row_bytes
