"""GCN for TPU — dense padded graph convolution over sampled neighbors.

Rounds out the model zoo (SAGE, GAT, GCN) for users coming from the
reference's PyG/DGL ecosystems (the reference's own examples train SAGE and
GAT; GCN is the third standard consumer of the same sampler output —
`dgl.nn.GraphConv` / `torch_geometric.nn.GCNConv`).

Mini-batch GCN on sampled blocks follows DGL's GraphConv conventions:

- ``norm="right"`` (default): mean over incoming messages including the
  self-loop — on TPU this is the cheap form (mask + sum + divide; no
  scatter at all).
- ``norm="both"``: symmetric 1/sqrt(d_i d_j) with degrees counted WITHIN
  the sampled block (DGL's block semantics ON THE DEDUP LAYOUT; the fused
  structural layout duplicates src nodes per edge, so out-degrees are all
  1 there — see the in-code note). The src-side out-degree count needs one
  scatter-add per layer over the hop's source width; scatters are the
  expensive primitive on TPU (PERF.md (earlier claims)) — prefer "right" unless
  parity with a DGL norm='both' training run matters.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..pyg.sage_sampler import DenseAdj


class GCNConv(nn.Module):
    """One GCN layer over a :class:`DenseAdj` (self-loop included)."""

    out_dim: int
    norm: str = "right"
    use_bias: bool = True
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x_src: jax.Array, adj: DenseAdj) -> jax.Array:
        if self.norm not in ("right", "both"):
            raise ValueError(f"unknown norm: {self.norm!r}")
        if self.dtype is not None:
            x_src = x_src.astype(self.dtype)
        w_dst = adj.w_dst
        x_dst = x_src[:w_dst]
        gathered = adj.gather_src(x_src)              # [W_dst, k, D]
        m = adj.mask[..., None].astype(x_src.dtype)
        deg_in = adj.mask.sum(axis=1).astype(x_src.dtype)  # sampled in-degree
        if self.norm == "right":
            # mean over {self} + sampled in-neighbors
            s = (gathered * m).sum(axis=1) + x_dst
            agg = s / (deg_in + 1.0)[:, None]
        else:
            # within-block symmetric norm: src out-degree by scatter count,
            # accumulated in f32 ALWAYS (a bf16 accumulator saturates at 256,
            # silently under-counting hub nodes)
            if adj.cols is None:
                # structural layout: every src lane is a per-edge COPY, so
                # its within-block out-degree is exactly 1. NOTE this makes
                # norm="both" normalize differently than the dedup layout
                # (where a node feeding many dst rows counts them all) —
                # use the dedup pipeline when DGL-block norm='both'
                # semantics matter.
                deg_out = jnp.ones(x_src.shape[0], jnp.float32)
            else:
                deg_out = jnp.zeros(x_src.shape[0], jnp.float32).at[
                    adj.cols.reshape(-1)
                ].add(adj.mask.reshape(-1).astype(jnp.float32), mode="drop")
            deg_out = deg_out.astype(x_src.dtype)
            # self-loops count on both sides
            inv_dst = jax.lax.rsqrt(deg_in + 1.0)
            inv_src_all = jax.lax.rsqrt(deg_out + 1.0)
            inv_src = adj.gather_src(inv_src_all[:, None])[..., 0]  # [W_dst, k]
            s = (gathered * m * inv_src[..., None]).sum(axis=1)
            # self edge contributes x_i / d_i: one rsqrt here, one in the
            # final dst scaling below
            s = s + x_dst * inv_dst[:, None]
            agg = s * inv_dst[:, None]
        return nn.Dense(
            self.out_dim, use_bias=self.use_bias, dtype=self.dtype, name="lin"
        )(agg)


class GCN(nn.Module):
    """Multi-layer GCN with the zoo's conventions (relu + dropout between
    layers; bf16 compute via ``dtype``; f32 logits out)."""

    hidden_dim: int
    out_dim: int
    num_layers: int = 2
    dropout: float = 0.5
    norm: str = "right"
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
            x = GCNConv(dim, norm=self.norm, dtype=self.dtype, name=f"conv{i}")(x, adj)
            if i != self.num_layers - 1:
                x = jax.nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x.astype(jnp.float32)
