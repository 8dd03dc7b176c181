"""GraphSAGE for TPU — dense padded aggregation.

The reference trains plain PyG ``SAGEConv`` stacks
(examples/pyg/reddit_quiver.py, examples/multi_gpu/pyg/ogb-products/
dist_sampling_ogb_products_quiver.py: 2-3 layer SAGEConv, hidden 256,
accuracy anchor ~0.787 on ogbn-products). On TPU the sampler emits padded
``[S, k]`` neighbor matrices (see ``quiver_tpu.pyg.sage_sampler.DenseAdj``),
which turns the sparse segment-mean aggregation into a dense gather +
masked mean — a reshape away from MXU-friendly matmuls (SURVEY.md 7.1).

Semantics match PyG SAGEConv(mean): ``out = lin_l(mean_j x_j) + lin_r(x_i)``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.gather_sum import gather_masked_sum
from ..pyg.sage_sampler import DenseAdj


def masked_mean_aggregate(x_src: jax.Array, adj: DenseAdj) -> jax.Array:
    """Mean of valid sampled neighbors per target node.

    x_src: [W_src, D] embeddings of this hop's source n_id.
    Returns [W_dst, D]: the sum of the valid slots' rows over
    ``max(count, 1)``. For the fused pipeline's structural layout
    (``adj.cols is None``) the rows come from a slice+reshape — no gather at
    all (2.3x faster than the equivalent take on TPU). For explicit ``cols``
    the ``[W_dst, k, D]`` gather is never laid out: `ops.gather_sum` adds
    the k masked rows of a target in ``x_src``'s dtype as a balanced tree
    (slot j with j + P/2, then halves again), an invalid slot as its row
    times zero — on a TPU, for 4.5 GB-class gathers of rows of 4 KB and
    more, in one kernel that reads each row once.
    """
    if adj.cols is None:
        gathered = adj.gather_src(x_src)              # [W_dst, k, D]
        m = adj.mask[..., None].astype(x_src.dtype)
        s = (gathered * m).sum(axis=1)
    else:
        s = gather_masked_sum(x_src, adj.cols, adj.mask)
    cnt = jnp.maximum(adj.mask.sum(axis=1, keepdims=True), 1).astype(x_src.dtype)
    return s / cnt


class SAGEConv(nn.Module):
    """One GraphSAGE layer (PyG SAGEConv, mean aggregator).

    ``dtype`` is the COMPUTE dtype (e.g. ``jnp.bfloat16`` to run the
    matmuls on the MXU's native precision); params stay float32 (flax
    ``param_dtype`` default) — the standard TPU mixed-precision recipe."""

    out_dim: int
    use_bias: bool = True
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x_src: jax.Array, adj: DenseAdj) -> jax.Array:
        if self.dtype is not None:
            x_src = x_src.astype(self.dtype)
        w_dst = adj.w_dst
        x_dst = x_src[:w_dst]  # targets are the prefix of the source n_id
        agg = masked_mean_aggregate(x_src, adj)
        h = nn.Dense(
            self.out_dim, use_bias=self.use_bias, dtype=self.dtype, name="lin_l"
        )(agg)
        h = h + nn.Dense(
            self.out_dim, use_bias=False, dtype=self.dtype, name="lin_r"
        )(x_dst)
        return h


class GraphSAGE(nn.Module):
    """Multi-layer GraphSAGE matching the reference example models
    (examples/pyg/reddit_quiver.py SAGE class: relu + dropout between
    layers, log_softmax head is left to the loss).

    ``dtype=jnp.bfloat16`` runs every layer's compute in bf16 (params and
    returned logits stay float32, so losses/optimizers are unchanged) —
    the feature gather itself is row-rate-bound and dtype-invariant
    (PERF.md (earlier claims)), so this buys matmul time and activation memory, not
    gather time."""

    hidden_dim: int
    out_dim: int
    num_layers: int = 2
    dropout: float = 0.5
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        adjs: Tuple[DenseAdj, ...],
        *,
        train: bool = False,
    ) -> jax.Array:
        assert len(adjs) == self.num_layers, (len(adjs), self.num_layers)
        for i, adj in enumerate(adjs):
            dim = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
            x = SAGEConv(dim, dtype=self.dtype, name=f"conv{i}")(x, adj)
            if i != self.num_layers - 1:
                x = jax.nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x.astype(jnp.float32)
