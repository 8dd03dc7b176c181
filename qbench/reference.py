"""The plain reference: GraphSAGE (mean aggregator) forward, softmax
cross-entropy, gradients and Adam in straightforward float32 `jax.numpy`.
Every matrix product is exact in float32 (``highest``); with
``operands="bfloat16"`` its two operands are first rounded to bfloat16, in the
forward and in both backward products alike, which is what the configurations
state (float32 everywhere, products at the TPU's default precision: bfloat16
operands, float32 accumulation). It imports nothing of `quiver_tpu`
and takes nothing the program made: weights come from the seed
(`init_params`), feature rows from the benchmark's host table, and the sampled
blocks as plain arrays that `qbench.check` has first held against the host
CSR.

Equations (PyG ``SAGEConv(aggr="mean")``, as the sources' models use):

    h_i' = W_l . mean_{j in sampled N(i)} h_j + b_l + W_r . h_i
    relu between layers (dropout is 0 in every configuration, see PERF.md),
    loss = mean over the batch's seeds of -log softmax(h_seed)[label].

A block is ``(cols, mask)``: ``cols[i, j]`` is the position, in this layer's
input rows, of target i's j-th sampled neighbour; targets are the first
``mask.shape[0]`` input rows. Blocks come outermost hop first.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
AGG_BLOCK = 4096  # target rows aggregated at a time in the widest layer

Params = Dict[str, Dict[str, Dict[str, Dict[str, jax.Array]]]]
Block = Tuple[jax.Array, jax.Array]


def layer_dims(feat_dim: int, hidden_dim: int, classes: int,
               num_layers: int) -> List[Tuple[int, int]]:
    dims = [feat_dim] + [hidden_dim] * (num_layers - 1) + [classes]
    return list(zip(dims[:-1], dims[1:]))


def init_params(seed: int, feat_dim: int, hidden_dim: int, classes: int,
                num_layers: int) -> Params:
    """Weights from the seed, on the device, in one jitted call: normal with
    variance 1/fan_in, zero biases, float32. The tree has the names a flax
    ``GraphSAGE`` of `SAGEConv(lin_l, lin_r)` layers gives its parameters, so
    the program can be handed it as its initial state."""
    dims = layer_dims(feat_dim, hidden_dim, classes, num_layers)

    @jax.jit
    def make(key):
        out = {}
        for i, (d_in, d_out) in enumerate(dims):
            k_l, k_r = jax.random.split(jax.random.fold_in(key, i))
            scale = np.float32(1.0 / np.sqrt(d_in))
            out[f"conv{i}"] = {
                "lin_l": {"kernel": jax.random.normal(k_l, (d_in, d_out), jnp.float32) * scale,
                          "bias": jnp.zeros((d_out,), jnp.float32)},
                "lin_r": {"kernel": jax.random.normal(k_r, (d_in, d_out), jnp.float32) * scale},
            }
        return {"params": out}

    return make(jax.random.key(int(seed) % (2**31 - 1)))


def dims_of(cfg) -> List[Tuple[int, int]]:
    """`layer_dims` of a configuration file."""
    return layer_dims(cfg["feat_dim"], cfg["hidden_dim"], cfg["classes"], cfg["num_layers"])


def params_of(cfg, seed: int) -> Params:
    """`init_params` for a configuration file."""
    return init_params(seed, cfg["feat_dim"], cfg["hidden_dim"], cfg["classes"],
                       cfg["num_layers"])


def masked_mean(x: jax.Array, cols: jax.Array, mask: jax.Array,
                block: int = AGG_BLOCK) -> jax.Array:
    """[W, D] mean of each target's valid sampled neighbours (0 where it has
    none), ``block`` targets at a time so that the [block, k, D] gather is
    the largest temporary."""
    w, k = mask.shape
    pad = (-w) % block
    cols = jnp.pad(jnp.clip(cols, 0, x.shape[0] - 1), ((0, pad), (0, 0)))
    maskf = jnp.pad(mask, ((0, pad), (0, 0))).astype(x.dtype)

    def one(args):
        c, m = args
        total = (x[c] * m[..., None]).sum(axis=1)
        return total / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)

    out = jax.lax.map(one, (cols.reshape(-1, block, k), maskf.reshape(-1, block, k)))
    return out.reshape(-1, x.shape[1])[:w]


def _exact_dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def _round(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _rounded_dot(a, b):
    """a @ b with both operands rounded to bfloat16 and the sum in float32;
    the two products of the backward pass round THEIR operands the same way
    (what a default-precision float32 product does on the TPU's MXU)."""
    return _exact_dot(_round(a), _round(b))


def _rounded_dot_fwd(a, b):
    return _rounded_dot(a, b), (a, b)


def _rounded_dot_bwd(res, g):
    a, b = res
    g = _round(g)
    return _exact_dot(g, _round(b).T), _exact_dot(_round(a).T, g)


_rounded_dot.defvjp(_rounded_dot_fwd, _rounded_dot_bwd)
DOTS = {"float32": _exact_dot, "bfloat16": _rounded_dot}


def _dense(p, agg, x_dst, dot):
    return (dot(agg, p["lin_l"]["kernel"]) + p["lin_l"]["bias"]
            + dot(x_dst, p["lin_r"]["kernel"]))


def forward_from_agg(params: Params, agg0: jax.Array, x_dst0: jax.Array,
                     blocks: Sequence[Block], operands: str = "float32") -> jax.Array:
    """Logits of the batch's seeds, given the first layer's aggregate (which
    no parameter enters) and the remaining blocks."""
    p, dot = params["params"], DOTS[operands]
    h = _dense(p["conv0"], agg0, x_dst0, dot)
    for i, (cols, mask) in enumerate(blocks, start=1):
        h = jax.nn.relu(h)
        agg = masked_mean(h, cols, mask, block=min(AGG_BLOCK, mask.shape[0]))
        h = _dense(p[f"conv{i}"], agg, h[: mask.shape[0]], dot)
    return h


def forward(params: Params, x: jax.Array, blocks: Sequence[Block],
            operands: str = "float32") -> jax.Array:
    cols, mask = blocks[0]
    return forward_from_agg(params, masked_mean(x, cols, mask),
                            x[: mask.shape[0]], blocks[1:], operands)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


@functools.partial(jax.jit, static_argnames=("operands",))
def loss_and_grad(params: Params, x: jax.Array, blocks, labels: jax.Array,
                  operands: str = "float32"):
    """(loss, gradient tree) of one batch."""
    cols, mask = blocks[0]
    agg0 = masked_mean(x, cols, mask)
    x_dst0 = x[: mask.shape[0]]

    def loss_fn(p):
        return cross_entropy(forward_from_agg(p, agg0, x_dst0, blocks[1:], operands), labels)

    return jax.value_and_grad(loss_fn)(params)


@jax.jit
def adam_update(params, grads, mu, nu, step, lr):
    """One Adam step (Kingma & Ba, bias-corrected, no weight decay);
    ``step`` counts from 1."""
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1 = 1 - ADAM_B1 ** step
    c2 = 1 - ADAM_B2 ** step
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return new, mu, nu


def follow_steps(params: Params, batches, lr: float, operands: str = "float32"):
    """Follow the first training steps. ``batches`` yields ``(x, blocks,
    labels)`` one step at a time (each freed before the next is made).
    Returns the losses, the first step's gradient tree and the parameters
    after the last step, all as numpy."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for step, (x, blocks, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, x, blocks, labels, operands)
        if first_grad is None:
            first_grad = jax.tree.map(np.asarray, grads)
        params, mu, nu = adam_update(params, grads, mu, nu,
                                     jnp.float32(step), jnp.float32(lr))
        losses.append(float(loss))
    return losses, first_grad, jax.tree.map(np.asarray, params)
