"""GAT for TPU — dense padded attention over sampled neighbors.

Parity with the reference's GAT training example
(examples/multi_gpu/pyg/reddit/dist_sampling_reddit_gat.py uses PyG GATConv).
The padded ``[S, k]`` sampler output makes attention a dense masked softmax
over the k sampled neighbors — batched [S, k, H] scores feed the VPU with no
segment ops. Over explicit ``cols`` the scores, the softmax and the weighted
sum of the neighbours' rows go through `ops.gather_sum.gather_attention_sum`,
which never lays ``[W_dst, k, H, D]`` out (2.3 GB at 73,728 targets, k 15 and
4 x 128: the step would not leave room for it beside a 4 GB feature table).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.gather_sum import attention_block, gather_attention_sum
from ..pyg.sage_sampler import DenseAdj


class GATConv(nn.Module):
    """Single GAT layer (PyG GATConv semantics, mean of heads optional).

    out[i] = sum_j alpha_ij * (W x_j) + bias, alpha over the valid sampled
    neighbors + self; heads concatenated, or averaged with ``concat=False``.
    ``dtype`` is the compute dtype (params stay float32; attention softmax
    always runs float32 for stability). With float32 arrays only the
    projection ``W x`` is a matrix product (JAX's default precision); scores,
    softmax and the weighted sum are elementwise float32.
    """

    out_dim: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x_src: jax.Array, adj: DenseAdj) -> jax.Array:
        h, d = self.heads, self.out_dim
        if self.dtype is not None:
            x_src = x_src.astype(self.dtype)
        w_dst = adj.w_dst

        proj = nn.Dense(h * d, use_bias=False, dtype=self.dtype, name="lin")
        hs = proj(x_src)                             # [W_src, H * D]
        hd = hs[:w_dst].reshape(w_dst, h, d)         # [W_dst, H, D]

        a_src = self.param("att_src", nn.initializers.glorot_uniform(), (1, h, d))
        a_dst = self.param("att_dst", nn.initializers.glorot_uniform(), (1, h, d))
        bias = self.param("bias", nn.initializers.zeros, (h * d,))
        # the targets' half of every score; the sources' half, the softmax
        # over the valid slots and the target itself (PyG adds self loops; the
        # sampler's target node is its own extra neighbor here) and the
        # weighted sum are `attention_block`'s
        t = (hd * a_dst.astype(hs.dtype)).sum(-1)           # [W_dst, H]
        if adj.cols is None:  # structural layout: the rows are a slice of hs
            k = adj.mask.shape[1]
            rows = hs[w_dst: w_dst * (1 + k)].reshape(k, w_dst, h, d)
            out = attention_block(rows, hd, adj.mask.T[..., None], a_src[0], t,
                                  self.negative_slope).reshape(w_dst, h * d)
        else:
            out = gather_attention_sum(hs, adj.cols, adj.mask, a_src[0], t, self.negative_slope)
        out = out + bias.astype(out.dtype)           # [W_dst, H * D]
        if self.concat:
            return out
        return out.reshape(w_dst, h, d).mean(axis=1)


class GAT(nn.Module):
    """Multi-layer GAT matching the reference example shape: concat heads on
    hidden layers, mean of the ``out_heads`` heads on the output layer,
    ``activation`` (and dropout) between layers."""

    hidden_dim: int
    out_dim: int
    heads: int = 4
    num_layers: int = 2
    dropout: float = 0.5
    dtype: Optional[Any] = None
    out_heads: int = 1
    activation: Callable[[jax.Array], jax.Array] = jax.nn.elu
    negative_slope: float = 0.2

    @nn.compact
    def __call__(
        self, x: jax.Array, adjs: Tuple[DenseAdj, ...], *, train: bool = False
    ) -> jax.Array:
        assert len(adjs) == self.num_layers
        for i, adj in enumerate(adjs):
            last = i == self.num_layers - 1
            x = GATConv(
                out_dim=self.out_dim if last else self.hidden_dim,
                heads=self.out_heads if last else self.heads,
                concat=not last,
                negative_slope=self.negative_slope,
                dtype=self.dtype,
                name=f"gat{i}",
            )(x, adj)
            if not last:
                x = self.activation(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x.astype(jnp.float32)
