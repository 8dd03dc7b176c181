"""The plain reference of the attention configurations: GAT (Velickovic et
al., arXiv:1710.10903) over sampled blocks, forward, softmax cross-entropy,
gradients and Adam in straightforward float32 `jax.numpy`, in
`qbench.reference`'s manner (whose Adam, loss and rounded product it reuses).
It imports nothing of `quiver_tpu` and takes nothing the program made: weights
come from the seed (`init_params`), feature rows from the benchmark's host
table, and the sampled blocks as plain arrays that `qbench.check` has first
held against the host CSR.

Equations, per layer, heads h = 1..H, for target i with valid sampled
neighbours N(i) (the block's masked slots) and itself:

    z_j   = W^h x_j                                  for every source row j
    e_ij  = LeakyReLU_slope( a_src^h . z_j + a_dst^h . z_i ),   j in N(i) + {i}
    alpha = softmax over j in N(i) + {i} of e_ij     (padded slots take no mass)
    o_i^h = sum_j alpha_ij z_j + b^h
    hidden layers: heads concatenated, then the activation; last layer: the
    mean over its heads; loss = mean over the batch's seeds of
    -log softmax(o_seed)[label].

Only ``W x`` is a matrix product: it goes through `reference.DOTS[operands]`
(exact, or both operands rounded to bfloat16 as the configuration states,
forward and backward alike). Scores, softmax and the weighted sum are exact
float32 elementwise arithmetic and sums.

Departures from the published layer and from the source's model (IBM/IGB-
datasets ``igb/models.py`` ``GAT``, DGL ``GATConv``):
- the target attends itself through a slot of its own, present once for every
  target (PyG's ``add_self_loops=True``); the source adds self-loops to the
  graph and samples them like any edge, so there a target of degree above the
  fan-out may miss itself;
- no feature, attention or edge dropout (dropout is 0 in every configuration:
  PERF.md section 4), no residual connection;
- the sum over a target's slots is taken `GATHER_BLOCK` targets at a time and
  recomputed in the backward pass (`jax.checkpoint`), so that the
  ``[block, k, H, D]`` rows are the largest temporary: the same sums, less
  memory.

A block is ``(cols, mask)`` as in `qbench.reference`; blocks come outermost
hop first, and a layer's targets are the first ``mask.shape[0]`` of its input
rows.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .reference import Block

GATHER_BLOCK = 4096  # target rows whose neighbours' rows are gathered at a time
ACTIVATIONS = {"relu": jax.nn.relu, "elu": jax.nn.elu}

Params = Dict[str, Dict[str, Dict[str, jax.Array]]]


def layer_dims(feat_dim: int, hidden_dim: int, classes: int, num_layers: int,
               heads: int, out_heads: int) -> List[Tuple[int, int, int]]:
    """(input width, heads, width of a head) of each layer: hidden layers
    hand on their heads concatenated."""
    out, d_in = [], feat_dim
    for i in range(num_layers):
        last = i == num_layers - 1
        h, d = (out_heads, classes) if last else (heads, hidden_dim)
        out.append((d_in, h, d))
        d_in = h * d
    return out


def dims_of(cfg) -> List[Tuple[int, int, int]]:
    """`layer_dims` of a configuration file."""
    return layer_dims(cfg["feat_dim"], cfg["hidden_dim"], cfg["classes"], cfg["num_layers"],
                      cfg["heads"], cfg["out_heads"])


def init_params(seed: int, dims: Sequence[Tuple[int, int, int]]) -> Params:
    """Weights from the seed, on the device, in one jitted call: ``lin``
    normal with variance 1/fan_in, ``att_src`` and ``att_dst`` normal with
    variance 1/D, zero bias, float32. The tree has the names a flax `GAT` of
    `GATConv` layers gives its parameters, so the program can be handed it as
    its initial state."""

    @jax.jit
    def make(key):
        out = {}
        for i, (d_in, h, d) in enumerate(dims):
            k_lin, k_src, k_dst = jax.random.split(jax.random.fold_in(key, i), 3)
            att = np.float32(1.0 / np.sqrt(d))
            out[f"gat{i}"] = {
                "lin": {"kernel": jax.random.normal(k_lin, (d_in, h * d), jnp.float32)
                        * np.float32(1.0 / np.sqrt(d_in))},
                "att_src": jax.random.normal(k_src, (1, h, d), jnp.float32) * att,
                "att_dst": jax.random.normal(k_dst, (1, h, d), jnp.float32) * att,
                "bias": jnp.zeros((h * d,), jnp.float32),
            }
        return {"params": out}

    return make(jax.random.key(int(seed) % (2**31 - 1)))


def params_of(cfg, seed: int) -> Params:
    """`init_params` for a configuration file."""
    return init_params(seed, dims_of(cfg))


def attention(e_nbr: jax.Array, e_self: jax.Array, mask: jax.Array) -> jax.Array:
    """``[B, k + 1, H]`` shares of each target's valid neighbours and, last,
    of itself: the softmax of their scores, a padded slot taking exactly 0."""
    valid = jnp.concatenate([mask, jnp.ones_like(mask[:, :1])], axis=1)[..., None]
    e = jnp.where(valid, jnp.concatenate([e_nbr, e_self[:, None]], axis=1), -jnp.inf)
    p = jnp.exp(e - jax.lax.stop_gradient(e.max(axis=1, keepdims=True)))
    return p / p.sum(axis=1, keepdims=True)


def gat_layer(p, x: jax.Array, cols: jax.Array, mask: jax.Array, operands: str,
              slope: float, block: int = GATHER_BLOCK) -> jax.Array:
    """``[W_dst, H, D]``: every head's output for the layer's targets."""
    _, h, d = p["att_src"].shape
    w, k = mask.shape
    block = min(block, w)
    z = reference.DOTS[operands](x, p["lin"]["kernel"]).reshape(-1, h, d)
    s_src = (z * p["att_src"]).sum(axis=-1)           # [W_src, H]
    s_dst = (z[:w] * p["att_dst"]).sum(axis=-1)       # [W_dst, H]
    pad = (-w) % block
    rows = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(  # noqa: E731
        (-1, block) + a.shape[1:])
    leaky = lambda e: jnp.where(e > 0, e, slope * e)  # noqa: E731

    @jax.checkpoint
    def one(args):
        c, m, z_i, s_i, t_i = args  # a block's slots, and its targets' rows and scores
        alpha = attention(leaky(s_src[c] + t_i[:, None]), leaky(s_i + t_i), m)
        return (alpha[:, :-1, :, None] * z[c]).sum(axis=1) + alpha[:, -1, :, None] * z_i

    out = jax.lax.map(one, (rows(jnp.clip(cols, 0, x.shape[0] - 1)), rows(mask),
                            rows(z[:w]), rows(s_src[:w]), rows(s_dst)))
    return out.reshape(-1, h, d)[:w] + p["bias"].reshape(h, d)


def forward(params: Params, x: jax.Array, blocks: Sequence[Block], operands: str = "float32",
            activation: str = "relu", slope: float = 0.2) -> jax.Array:
    """Logits of the batch's seeds."""
    p = params["params"]
    for i, (cols, mask) in enumerate(blocks):
        out = gat_layer(p[f"gat{i}"], x, cols, mask, operands, slope)
        if i == len(blocks) - 1:
            return out.mean(axis=1)
        x = ACTIVATIONS[activation](out.reshape(out.shape[0], -1))


@functools.partial(jax.jit, static_argnames=("operands", "activation", "slope"))
def loss_and_grad(params: Params, x: jax.Array, blocks, labels: jax.Array,
                  operands: str = "float32", activation: str = "relu", slope: float = 0.2):
    """(loss, gradient tree) of one batch."""

    def loss_fn(p):
        return reference.cross_entropy(forward(p, x, blocks, operands, activation, slope), labels)

    return jax.value_and_grad(loss_fn)(params)


def follow_steps(params: Params, batches, lr: float, operands: str = "float32",
                 activation: str = "relu", slope: float = 0.2):
    """`reference.follow_steps` for this model: the losses of the first
    training steps, the first step's gradient tree and the parameters after
    the last step, all as numpy."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for step, (x, blocks, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, x, blocks, labels, operands, activation, slope)
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, grads)
        params, mu, nu = reference.adam_update(params, grads, mu, nu,
                                               jnp.float32(step), jnp.float32(lr))
        losses.append(float(loss))
    return losses, first_grad, jax.tree.map(np.asarray, params)
