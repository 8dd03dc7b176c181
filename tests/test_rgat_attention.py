"""`ops.gather_sum.bipartite_attention_sum` (behind `models.RGATConv`) against
plain autodiff of a dense formulation written here, each target's softmax
over its valid slots alone: values and every gradient, with several blocks,
a ragged last block, targets without a valid slot (which get 0, so that the
layer's output is its bias alone) and ids out of range under a false mask.
And `RGATConv` / `RGAT`'s attributes: the bias, the relation aggregate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu.models import RGAT, RGATConv
from quiver_tpu.ops import gather_sum
from quiver_tpu.pyg import HeteroBlocks
from quiver_tpu.pyg.sage_sampler import DenseAdj


def case(w, k, h, d, n_src, seed=0):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(n_src, h * d)).astype(np.float32)  # heads side by side in a row
    cols = rng.integers(0, n_src, (w, k)).astype(np.int32)
    mask = rng.random((w, k)) < 0.6
    mask[:2] = False
    cols[~mask] = rng.choice([-7, n_src, 2**31 - 1], size=int((~mask).sum()))
    att = rng.normal(size=(h, d)).astype(np.float32)
    t = rng.normal(size=(w, h)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (z, cols, mask, att, t))


def dense(z, cols, mask, att, t, slope=0.2):
    """The layer's sum written out over a ``[W, k, H, D]`` gather."""
    w, k = cols.shape
    h, d = att.shape
    rows = z[jnp.clip(cols, 0, z.shape[0] - 1)].reshape(w, k, h, d)
    e = jax.nn.leaky_relu((rows * att).sum(-1) + t[:, None, :], slope)   # [W, k, H]
    e = jnp.where(mask[..., None], e, -jnp.inf)
    top = jnp.max(e, axis=1, keepdims=True)
    p = jnp.where(mask[..., None], jnp.exp(e - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    alpha = p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-30)
    return (alpha[..., None] * rows).sum(axis=1).reshape(w, h * d)


@pytest.mark.parametrize("w,k,h,d,n_src,block", [(40, 5, 3, 8, 70, 16), (33, 15, 4, 128, 9, 8),
                                                 (12, 3, 1, 5, 200, 8192)])
def test_values_and_gradients_match_the_dense_formulation(monkeypatch, w, k, h, d, n_src, block):
    monkeypatch.setattr(gather_sum, "ATTENTION_BLOCK", block)
    z, cols, mask, att, t = case(w, k, h, d, n_src)
    got = gather_sum.bipartite_attention_sum(z, cols, mask, att, t, 0.2)
    want = dense(z, cols, mask, att, t)
    # the same sums in another order: float32 round-off of rows up to ~10
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a target without a valid slot gets exactly 0: no self slot to fall back on
    assert not np.asarray(got[:2]).any()
    weight = jnp.asarray(np.random.default_rng(1).normal(size=want.shape).astype(np.float32))
    grads = [jax.grad(lambda z, att, t: (f(z, cols, mask, att, t) * weight).sum(), argnums=(0, 1, 2))(
        z, att, t) for f in (lambda *a: gather_sum.bipartite_attention_sum(*a, 0.2), dense)]
    for a, b in zip(*grads):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    jitted = jax.jit(lambda *a: gather_sum.bipartite_attention_sum(*a, 0.2))(z, cols, mask, att, t)
    np.testing.assert_allclose(jitted, got, rtol=1e-6, atol=1e-6)


def test_a_masked_slot_takes_exactly_no_mass():
    z, cols, mask, att, t = case(8, 4, 2, 6, 30)
    cols = cols.at[3].set(jnp.asarray([10, 11, 12, 13], jnp.int32))
    mask = mask.at[3].set(jnp.asarray([True, False, True, True]))
    a = gather_sum.bipartite_attention_sum(z, cols, mask, att, t, 0.2)[3]
    b = gather_sum.bipartite_attention_sum(z.at[11].add(100.0), cols, mask, att, t, 0.2)[3]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # the row behind a padded slot
    none = gather_sum.bipartite_attention_sum(z, cols, jnp.zeros_like(mask), att, t, 0.2)
    assert not np.asarray(none).any()
    grad = jax.grad(lambda z: gather_sum.bipartite_attention_sum(
        z, cols, jnp.zeros_like(mask), att, t, 0.2).sum())(z)
    assert not np.asarray(grad).any()


def _blocks(rng):
    """One layer over two types: ``a`` (6 targets, 9 rows) and ``b`` (5 rows),
    relations b -> a and a -> a."""
    ba = DenseAdj(jnp.asarray(rng.integers(0, 5, (6, 3)).astype(np.int32)),
                  jnp.asarray(rng.random((6, 3)) < 0.7), jnp.int32(5), jnp.int32(6))
    aa = DenseAdj(jnp.asarray(rng.integers(0, 9, (6, 2)).astype(np.int32)),
                  jnp.asarray(rng.random((6, 2)) < 0.7), jnp.int32(9), jnp.int32(6))
    return HeteroBlocks(layers=({"b__r1__a": ba, "a__r2__a": aa},), segments=(("a", 9), ("b", 5)))


def test_rgatconv_has_a_bias_after_the_aggregation_and_an_empty_target_reads_it():
    rng = np.random.default_rng(0)
    adj = _blocks(rng).layers[0]["b__r1__a"]
    adj = adj._replace(mask=adj.mask.at[4].set(False))
    x_src = jnp.asarray(rng.normal(size=(5, 7)).astype(np.float32))
    x_dst = jnp.asarray(rng.normal(size=(9, 7)).astype(np.float32))
    conv = RGATConv(out_dim=5, heads=2)
    params = conv.init(jax.random.key(0), x_src, x_dst, adj)["params"]
    assert set(params) == {"lin_src", "lin_dst", "att_src", "att_dst", "bias"}
    bias = jnp.arange(10, dtype=jnp.float32)
    out = conv.apply({"params": dict(params, bias=bias)}, x_src, x_dst, adj)
    assert out.shape == (6, 10)
    np.testing.assert_array_equal(np.asarray(out[4]), np.asarray(bias))


def test_rgat_sums_or_averages_the_relations_into_a_type():
    rng = np.random.default_rng(1)
    blocks = _blocks(rng)
    x = jnp.asarray(rng.normal(size=(14, 7)).astype(np.float32))
    model = RGAT(out_dim=3, hidden_dim=8, heads=2, num_layers=1)
    params = model.init(jax.random.key(0), x, blocks)
    assert set(params["params"]) == {"layer0_a__r2__a", "layer0_b__r1__a", "head"}
    summed = model.apply(params, x, blocks)
    mean = RGAT(out_dim=3, hidden_dim=8, heads=2, num_layers=1, relation_aggr="mean").apply(
        params, x, blocks)
    head = params["params"]["head"]
    # the head is affine: its input halves, its output moves by half of W times the sum
    np.testing.assert_allclose(2 * (mean - head["bias"]), summed - head["bias"], rtol=1e-5,
                               atol=1e-5)
    assert summed.shape == (6, 3) and summed.dtype == jnp.float32
    with pytest.raises(ValueError, match="relation_aggr"):
        RGAT(out_dim=3, hidden_dim=8, heads=2, num_layers=1, relation_aggr="max").apply(
            params, x, blocks)
