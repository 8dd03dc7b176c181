"""The plain reference of the relational attention configuration: R-GAT
(the MLCommons training reference's ``RGAT``, a PyG ``HeteroConv`` of one
``GATConv((-1, -1), D, heads=H, add_self_loops=False)`` per relation; GAT:
arXiv:1710.10903) over typed sampled blocks, forward, softmax cross-entropy,
gradients and Adam in straightforward float32 `jax.numpy`, in
`qbench.reference`'s manner (whose Adam, loss and rounded product it
reuses). It imports nothing of `quiver_tpu` and takes nothing the program
made: weights come from the seed (`init_params`), feature rows from the
benchmark's host table, and the sampled blocks as plain arrays that
`qbench.check_rgat` has first held against the host CSRs.

Equations, per layer l, relation r = (s, r, t), target i of type t with
valid sampled in-neighbours N_r(i) (the block's masked slots), heads h:

    z_j^h  = W_src^h x_j   (j of type s),   y_i^h = W_dst^h x_i   (i of type t)
    e_ij   = LeakyReLU_slope( a_src^h . z_j + a_dst^h . y_i ),   j in N_r(i)
    alpha  = softmax over j in N_r(i) of e_ij   (padded slots take no mass)
    o_i^r  = concat_h sum_j alpha_ij z_j^h + b^r   (empty N_r(i): b^r alone)
    x_i'   = sum (or mean: ``relation_aggr``) over the relations into t of o_i^r,
             in the order of their sorted keys
    between layers LeakyReLU with slope 0.01; logits = W_out x_seed + b_out;
    loss = mean over the batch's seeds of -log softmax(logits)[label].

Only ``W x`` is a matrix product: it goes through `reference.DOTS[operands]`
(exact, or both operands rounded to bfloat16 as the configuration states,
forward and backward alike). Scores, softmax, weighted sums and the relation
aggregate are exact float32 elementwise arithmetic and sums.

Departures from the published layer and from the source's model:
- block-wise message passing (a DGL-style message-flow graph): layer l reads
  only the edges hop ``L - 1 - l`` sampled, and computes outputs only for
  the types that hop reaches; PyG's ``HeteroConv`` over a sampled subgraph
  runs every layer over every sampled edge, which changes nothing the seeds'
  logits depend on when the subgraph is a tree of hops, and adds the edges
  between nodes of the same hop otherwise;
- no feature, attention or edge dropout (dropout is 0 in every configuration:
  PERF.md section 4), no residual connection, no normalisation layer;
- each relation's layer is recomputed in the backward pass (`jax.checkpoint`)
  and its sum over slots taken `GATHER_BLOCK` targets at a time, so that the
  reference fits on the chip once the program's state is freed: the same
  sums, less memory.

A layer's blocks are a dict ``relation key -> (cols, mask)``: ``cols[i, j]``
is the row, in the source type's input rows, of target i's j-th sampled
neighbour; targets are the first ``mask.shape[0]`` rows of the destination
type's. Layers come outermost hop first; ``x`` is a dict type -> rows.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

GATHER_BLOCK = 4096      # target rows whose neighbours' rows are gathered at a time
ACTIVATION_SLOPE = 0.01  # torch.nn.functional.leaky_relu's default, between layers

Relation = Tuple[str, str, str]


def relation_key(rel: Relation) -> str:
    return "__".join(rel)


def layer_relations(relations: Sequence[Relation], seed_type: str,
                    num_layers: int) -> List[List[str]]:
    """The relation keys each layer runs, outermost first: hop 0 holds the
    seeds' type alone, hop ``h + 1`` adds the source type of every relation
    into a type of hop ``h``; layer ``l`` runs the relations into the types
    of hop ``num_layers - 1 - l``."""
    hops = [{seed_type}]
    for _ in range(num_layers - 1):
        hops.append(hops[-1] | {s for s, _, d in relations if d in hops[-1]})
    return [sorted(relation_key(r) for r in relations if r[2] in hops[num_layers - 1 - l])
            for l in range(num_layers)]


def schema_of(cfg):
    """(relations, seed type) of a configuration file."""
    return [tuple(r["relation"]) for r in cfg["relations"]], cfg["seed_type"]


def init_params(seed: int, relations: Sequence[Relation], seed_type: str, feat_dim: int,
                hidden_dim: int, heads: int, classes: int, num_layers: int):
    """Weights from the seed, on the device, in one jitted call: projections
    normal with variance 1/fan_in, attention vectors normal with variance
    1/D, zero biases, float32. The tree has the names `models.RGAT` gives
    its parameters, so the program can be handed it as its initial state."""
    layers = layer_relations(relations, seed_type, num_layers)
    d = hidden_dim // heads

    @jax.jit
    def make(key):
        out = {}
        for l, keys in enumerate(layers):
            d_in = feat_dim if l == 0 else hidden_dim
            for j, rk in enumerate(keys):
                k_src, k_dst, k_as, k_ad = jax.random.split(jax.random.fold_in(key, 1000 * l + j), 4)
                scale, att = np.float32(1.0 / np.sqrt(d_in)), np.float32(1.0 / np.sqrt(d))
                out[f"layer{l}_{rk}"] = {
                    "lin_src": {"kernel": jax.random.normal(k_src, (d_in, hidden_dim)) * scale},
                    "lin_dst": {"kernel": jax.random.normal(k_dst, (d_in, hidden_dim)) * scale},
                    "att_src": jax.random.normal(k_as, (1, heads, d)) * att,
                    "att_dst": jax.random.normal(k_ad, (1, heads, d)) * att,
                    "bias": jnp.zeros((hidden_dim,), jnp.float32),
                }
        k_out = jax.random.fold_in(key, 999_999)
        out["head"] = {"kernel": jax.random.normal(k_out, (hidden_dim, classes))
                       * np.float32(1.0 / np.sqrt(hidden_dim)),
                       "bias": jnp.zeros((classes,), jnp.float32)}
        return {"params": out}

    return make(jax.random.key(int(seed) % (2**31 - 1)))


def params_of(cfg, seed: int):
    """`init_params` for a configuration file."""
    relations, seed_type = schema_of(cfg)
    return init_params(seed, relations, seed_type, cfg["feat_dim"], cfg["hidden_dim"],
                       cfg["heads"], cfg["classes"], cfg["num_layers"])


def relation_layer(p, x_src: jax.Array, x_dst: jax.Array, cols: jax.Array, mask: jax.Array,
                   operands: str, slope: float = 0.2, block: int = GATHER_BLOCK) -> jax.Array:
    """``[W_dst, H * D]``: one relation's output for its targets."""
    _, h, d = p["att_src"].shape
    w, k = mask.shape
    block = min(block, w)
    dot = reference.DOTS[operands]
    z = dot(x_src, p["lin_src"]["kernel"]).reshape(-1, h, d)
    y = dot(x_dst[:w], p["lin_dst"]["kernel"]).reshape(w, h, d)
    s_src = (z * p["att_src"]).sum(axis=-1)           # [W_src, H]
    s_dst = (y * p["att_dst"]).sum(axis=-1)           # [W_dst, H]
    pad = (-w) % block
    rows = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(  # noqa: E731
        (-1, block) + a.shape[1:])

    @jax.checkpoint
    def one(args):
        c, m, t_i = args  # a block's slots and its targets' score halves
        e = s_src[c] + t_i[:, None]                   # [B, k, H]
        e = jnp.where(e > 0, e, slope * e)
        valid = m[..., None]
        top = jax.lax.stop_gradient(jnp.where(valid, e, -jnp.inf).max(axis=1, keepdims=True))
        p_ = jnp.where(valid, jnp.exp(e - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        total = p_.sum(axis=1, keepdims=True)
        alpha = p_ / jnp.where(total > 0, total, 1.0)  # no valid slot: every share 0
        return (alpha[..., None] * z[c]).sum(axis=1)

    out = jax.lax.map(one, (rows(jnp.clip(cols, 0, x_src.shape[0] - 1)), rows(mask), rows(s_dst)))
    return out.reshape(-1, h * d)[:w] + p["bias"]


def forward(params, x: Dict[str, jax.Array], blocks: Sequence[Dict[str, Tuple]],
            operands: str = "float32", relation_aggr: str = "sum", slope: float = 0.2) -> jax.Array:
    """Logits of the batch's seeds."""
    p = params["params"]
    for l, layer in enumerate(blocks):
        outs: Dict[str, List[jax.Array]] = {}
        for rk in sorted(layer):
            src, _, dst = rk.split("__")
            cols, mask = layer[rk]
            fn = jax.checkpoint(functools.partial(relation_layer, operands=operands, slope=slope))
            outs.setdefault(dst, []).append(fn(p[f"layer{l}_{rk}"], x[src], x[dst], cols, mask))
        x = {}
        for t, os in outs.items():
            total = os[0]
            for o in os[1:]:
                total = total + o
            x[t] = total / len(os) if relation_aggr == "mean" else total
        if l < len(blocks) - 1:
            x = {t: jnp.where(v > 0, v, ACTIVATION_SLOPE * v) for t, v in x.items()}
    (seeds,) = x.values()
    return reference.DOTS[operands](seeds, p["head"]["kernel"]) + p["head"]["bias"]


@functools.partial(jax.jit, static_argnames=("operands", "relation_aggr", "slope"))
def loss_and_grad(params, x, blocks, labels: jax.Array, operands: str = "float32",
                  relation_aggr: str = "sum", slope: float = 0.2):
    """(loss, gradient tree) of one batch."""

    def loss_fn(p):
        return reference.cross_entropy(forward(p, x, blocks, operands, relation_aggr, slope),
                                       labels)

    return jax.value_and_grad(loss_fn)(params)


def follow_steps(params, batches, lr: float, operands: str = "float32",
                 relation_aggr: str = "sum", slope: float = 0.2):
    """`reference.follow_steps` for this model: ``batches`` yields ``(x,
    blocks, labels)`` one step at a time; returns the losses of the first
    training steps, the first step's gradient tree and the parameters after
    the last step, all as numpy."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for step, (x, blocks, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, x, blocks, labels, operands, relation_aggr, slope)
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, grads)
        params, mu, nu = reference.adam_update(params, grads, mu, nu,
                                               jnp.float32(step), jnp.float32(lr))
        losses.append(float(loss))
    return losses, first_grad, jax.tree.map(np.asarray, params)
