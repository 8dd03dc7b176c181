"""R-GAT: relational graph attention over a typed sample
(`pyg.hetero_sampler.HeteroSampler`), the model of MLPerf Training's GNN
benchmark (the MLCommons reference's ``RGAT``: a PyG ``HeteroConv`` of one
``GATConv((-1, -1), D, heads=H, add_self_loops=False)`` per relation).

Per layer, relation r = (s, r, t), target i of type t with sampled
in-neighbours N_r(i) and heads h of width D:

    z_j = W_src x_j,  y_i = W_dst x_i                   (no bias)
    e_ij = LeakyReLU_0.2(a_src . z_j + a_dst . y_i),    j in N_r(i), no self slot
    o_i^r = concat_h sum_j softmax_j(e_ij) z_j + b      (empty N_r(i): b alone)
    x_i' = sum (or mean) over the relations into t of o_i^r

LeakyReLU (slope 0.01) between layers; a linear head on the seeds' rows
after the last. The sum over a target's slots is
`ops.gather_sum.bipartite_attention_sum`, which never lays ``[W_dst, k, H,
D]`` out.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.gather_sum import bipartite_attention_sum
from ..pyg.hetero_sampler import HeteroBlocks
from ..pyg.sage_sampler import DenseAdj

ACTIVATION_SLOPE = 0.01  # between layers: torch.nn.functional.leaky_relu's default, the reference's


def _combine(outs, aggr: str) -> jax.Array:
    total = outs[0]
    for o in outs[1:]:
        total = total + o
    return total / len(outs) if aggr == "mean" else total


class RGATConv(nn.Module):
    """One relation's bipartite GAT layer: ``x_src`` the source type's rows,
    ``x_dst`` the destination type's (the targets are its first
    ``adj.w_dst``), heads concatenated. With float32 arrays only the two
    projections are matrix products (JAX's default precision); scores,
    softmax and the weighted sum are elementwise float32."""

    out_dim: int
    heads: int
    negative_slope: float = 0.2
    dtype: Optional[Any] = None
    src_rows: Optional[Tuple[int, int]] = None  # (start, width) of x_src's rows to read
    dst_rows: Optional[Tuple[int, int]] = None  # likewise of x_dst's

    @nn.compact
    def __call__(self, x_src: jax.Array, x_dst: jax.Array, adj: DenseAdj) -> jax.Array:
        h, d, w = self.heads, self.out_dim, adj.w_dst
        if self.src_rows is not None:
            x_src = x_src[self.src_rows[0]: sum(self.src_rows)]
        if self.dst_rows is not None:
            x_dst = x_dst[self.dst_rows[0]: sum(self.dst_rows)]
        if self.dtype is not None:
            x_src, x_dst = x_src.astype(self.dtype), x_dst.astype(self.dtype)
        z = nn.Dense(h * d, use_bias=False, dtype=self.dtype, name="lin_src")(x_src)
        y = nn.Dense(h * d, use_bias=False, dtype=self.dtype, name="lin_dst")(x_dst[:w])
        a_src = self.param("att_src", nn.initializers.glorot_uniform(), (1, h, d))
        a_dst = self.param("att_dst", nn.initializers.glorot_uniform(), (1, h, d))
        bias = self.param("bias", nn.initializers.zeros, (h * d,))
        t = (y.reshape(w, h, d) * a_dst.astype(y.dtype)).sum(-1)  # [W_dst, H]
        out = bipartite_attention_sum(z, adj.cols, adj.mask, a_src[0], t, self.negative_slope)
        return out + bias.astype(out.dtype)


class RGAT(nn.Module):
    """``num_layers`` relational layers of ``heads`` x ``hidden_dim //
    heads`` over `HeteroBlocks`, the relations into a type combined by
    ``relation_aggr`` ("sum" or "mean"), LeakyReLU(0.01) and dropout
    between layers (``negative_slope`` is the attention scores' LeakyReLU),
    then ``Linear(hidden_dim, out_dim)`` on the one type the last layer
    reaches (the seeds'). Parameters are named ``layer{l}_{relation_key}``
    and ``head``."""

    out_dim: int
    hidden_dim: int = 512
    heads: int = 4
    num_layers: int = 3
    relation_aggr: str = "sum"
    negative_slope: float = 0.2
    dropout: float = 0.0
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jax.Array, blocks: HeteroBlocks, *, train: bool = False) -> jax.Array:
        if len(blocks.layers) != self.num_layers:
            raise ValueError(f"{len(blocks.layers)} sampled hops for {self.num_layers} layers")
        if self.relation_aggr not in ("sum", "mean"):
            raise ValueError(f"unknown relation_aggr {self.relation_aggr!r}")
        # the first layer reads its types' rows out of the stacked input inside
        # each relation's (rematerialised) layer: sliced outside, the slices
        # were copies held from the forward pass to the backward (2 GB at IGBH)
        rows = blocks.spans()
        xs = {t: x for t in rows}
        for l, layer in enumerate(blocks.layers):
            outs = {}
            for key in sorted(layer):
                src, _, dst = key.split("__")
                conv = nn.remat(RGATConv)(
                    self.hidden_dim // self.heads, self.heads, self.negative_slope, self.dtype,
                    src_rows=rows[src] if l == 0 else None,
                    dst_rows=rows[dst] if l == 0 else None, name=f"layer{l}_{key}")
                outs.setdefault(dst, []).append(conv(xs[src], xs[dst], layer[key]))
            xs = {t: _combine(os, self.relation_aggr) for t, os in outs.items()}
            if l < self.num_layers - 1:
                xs = {t: nn.Dropout(self.dropout, deterministic=not train)(
                    jax.nn.leaky_relu(v, ACTIVATION_SLOPE)) for t, v in xs.items()}
        (out,) = xs.values()
        return nn.Dense(self.out_dim, dtype=self.dtype, name="head")(out).astype(jnp.float32)
