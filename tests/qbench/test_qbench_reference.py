"""The plain reference agrees with `quiver_tpu.models.GraphSAGE` on seeded
weights at a small size, its gradients with autodiff through the model, and
its Adam with optax's."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from qbench import check, reference
from quiver_tpu.models import GraphSAGE
from quiver_tpu.pyg.sage_sampler import DenseAdj

FEAT, HIDDEN, CLASSES = 12, 16, 5


def _blocks(rng, structural):
    """Two hops over 6 seeds: widths 6 -> 6*(1+3) = 24 -> 24*(1+2) = 72."""
    w1, k1, w0, k0 = 6, 3, 24, 2
    mask1 = rng.random((w1, k1)) < 0.8
    mask0 = rng.random((w0, k0)) < 0.8
    if structural:
        cols1, cols0 = check.structural_cols(w1, k1), check.structural_cols(w0, k0)
        adjs = (DenseAdj(None, jnp.asarray(mask0), jnp.int32(72), jnp.int32(24)),
                DenseAdj(None, jnp.asarray(mask1), jnp.int32(24), jnp.int32(6)))
    else:
        cols1 = rng.integers(0, w0, (w1, k1)).astype(np.int32)
        cols0 = rng.integers(0, 72, (w0, k0)).astype(np.int32)
        adjs = (DenseAdj(jnp.asarray(cols0), jnp.asarray(mask0), jnp.int32(72), jnp.int32(24)),
                DenseAdj(jnp.asarray(cols1), jnp.asarray(mask1), jnp.int32(24), jnp.int32(6)))
    blocks = [(jnp.asarray(cols0), jnp.asarray(mask0)), (jnp.asarray(cols1), jnp.asarray(mask1))]
    x = jnp.asarray(rng.standard_normal((72, FEAT)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, CLASSES, 6).astype(np.int32))
    return x, adjs, blocks, y


@pytest.mark.parametrize("structural", [True, False], ids=["fused-layout", "dedup-layout"])
def test_forward_loss_and_gradients_match_the_model(structural):
    rng = np.random.default_rng(0)
    x, adjs, blocks, y = _blocks(rng, structural)
    params = reference.init_params(7, FEAT, HIDDEN, CLASSES, 2)
    model = GraphSAGE(hidden_dim=HIDDEN, out_dim=CLASSES, num_layers=2, dropout=0.0)
    # the seed-made tree is a valid parameter tree of the flax model
    want_tree = jax.eval_shape(lambda: model.init(jax.random.key(0), x, adjs))
    assert jax.tree.structure(want_tree) == jax.tree.structure(params)
    logits = model.apply(params, x, adjs)
    np.testing.assert_allclose(reference.forward(params, x, blocks), logits, rtol=1e-5, atol=1e-5)

    def loss_fn(p):
        return optax.softmax_cross_entropy_with_integer_labels(model.apply(p, x, adjs), y).mean()

    want_loss, want_grads = jax.value_and_grad(loss_fn)(params)
    loss, grads = reference.loss_and_grad(params, x, blocks, y)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_adam_matches_optax_over_three_steps():
    rng = np.random.default_rng(1)
    x, adjs, blocks, y = _blocks(rng, False)
    params = reference.init_params(3, FEAT, HIDDEN, CLASSES, 2)
    tx = optax.adam(0.01)
    state, p = tx.init(params), params
    for _ in range(3):
        _, g = reference.loss_and_grad(p, x, blocks, y)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
    losses, grad1, p3 = reference.follow_steps(params, [(x, blocks, y)] * 3, 0.01)
    assert losses[2] < losses[0]
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    _, g1 = reference.loss_and_grad(params, x, blocks, y)
    for a, b in zip(jax.tree.leaves(grad1), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_masked_mean_blocks_and_empty_rows():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((50, 4)).astype(np.float32))
    cols = rng.integers(0, 50, (11, 3)).astype(np.int32)
    mask = rng.random((11, 3)) < 0.6
    mask[4] = False  # a target with no sampled neighbour aggregates to 0
    got = np.asarray(reference.masked_mean(x, jnp.asarray(cols), jnp.asarray(mask), block=4))
    xs = np.asarray(x)
    for i in range(11):
        want = xs[cols[i][mask[i]]].mean(axis=0) if mask[i].any() else np.zeros(4)
        np.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-7)


def test_norm_gap_is_a_gap_of_norms_against_the_larger_of_leaf_and_median():
    want = {"a": 10.0, "b": 1.0, "c": 1e-6}
    got = {"a": 10.5, "b": 1.0, "c": 3e-6}
    # leaf c is all but zero: measured against the median leaf (1.0), not itself
    assert check.worst_norm_gap(got, want) == pytest.approx(0.05)
    assert check.worst_norm_gap(got, want, skip=["a"]) == pytest.approx(2e-6 / 0.5000005, rel=1e-3)
    assert check.quiet_leaves({"a": 1.0, "b": 2.0, "c": 1e-5}) == ["c"]
