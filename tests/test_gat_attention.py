"""`ops.gather_sum.gather_attention_sum` (behind `models.GATConv` over explicit
``cols``) against the plain expression it replaces, the whole ``[k, W, H, D]``
gather handed to `attention_block` and differentiated by JAX: values and every
gradient, with several blocks, a ragged last block, targets without a valid
slot and ids out of range under a false mask. And `GATConv` / `GAT`'s
attributes: the output bias, the output layer's heads, the activation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu.models import GAT, GATConv
from quiver_tpu.ops import gather_sum
from quiver_tpu.pyg.sage_sampler import DenseAdj


def case(w, k, h, d, seed=0):
    rng = np.random.default_rng(seed)
    n_src = 3 * w
    x = rng.normal(size=(n_src, h * d)).astype(np.float32)  # heads side by side in a row
    cols = rng.integers(0, n_src, (w, k)).astype(np.int32)
    mask = rng.random((w, k)) < 0.6
    mask[:2] = False
    cols[~mask] = rng.choice([-7, n_src, 2**31 - 1], size=int((~mask).sum()))
    att = rng.normal(size=(h, d)).astype(np.float32)
    t = rng.normal(size=(w, h)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, cols, mask, att, t))


def plain(x, cols, mask, att, t, slope=0.2):
    w, k = cols.shape
    rows = jnp.take(x, jnp.clip(cols.T, 0, x.shape[0] - 1), axis=0).reshape((k, w) + att.shape)
    return gather_sum.attention_block(rows, x[:w].reshape((w,) + att.shape), mask.T[..., None],
                                      att, t, slope).reshape(w, -1)


@pytest.mark.parametrize("w,k,h,d,block", [(40, 5, 3, 8, 16), (33, 15, 4, 128, 8), (12, 3, 1, 5, 8192)])
def test_values_and_gradients_match_the_plain_expression(monkeypatch, w, k, h, d, block):
    monkeypatch.setattr(gather_sum, "ATTENTION_BLOCK", block)
    x, cols, mask, att, t = case(w, k, h, d)
    got = gather_sum.gather_attention_sum(x, cols, mask, att, t, 0.2)
    want = plain(x, cols, mask, att, t)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # a target without a valid slot attends itself alone: its own row
    np.testing.assert_allclose(got[:2], x[:2], rtol=1e-6, atol=1e-6)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=want.shape).astype(np.float32))
    grads = [jax.grad(lambda x, att, t: (f(x, cols, mask, att, t) * weight).sum(), argnums=(0, 1, 2))(
        x, att, t) for f in (lambda *a: gather_sum.gather_attention_sum(*a, 0.2), plain)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    jitted = jax.jit(lambda *a: gather_sum.gather_attention_sum(*a, 0.2))(x, cols, mask, att, t)
    np.testing.assert_allclose(jitted, got, rtol=1e-6, atol=1e-6)


def test_a_masked_slot_takes_exactly_no_mass():
    x, cols, mask, att, t = case(8, 4, 2, 6)
    cols = cols.at[3].set(jnp.asarray([10, 11, 12, 13], jnp.int32))
    mask = mask.at[3].set(jnp.asarray([True, False, True, True]))
    a = gather_sum.gather_attention_sum(x, cols, mask, att, t, 0.2)[3]
    b = gather_sum.gather_attention_sum(x.at[11].add(100.0), cols, mask, att, t, 0.2)[3]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # the row behind a padded slot
    none = gather_sum.gather_attention_sum(x, cols, jnp.zeros_like(mask), att, t, 0.2)
    np.testing.assert_array_equal(np.asarray(none), np.asarray(x[:8]))


def _adjs(rng):
    mask1, mask0 = rng.random((6, 3)) < 0.7, rng.random((24, 2)) < 0.7
    cols1 = rng.integers(0, 24, (6, 3)).astype(np.int32)
    cols0 = rng.integers(0, 72, (24, 2)).astype(np.int32)
    return (DenseAdj(jnp.asarray(cols0), jnp.asarray(mask0), jnp.int32(72), jnp.int32(24)),
            DenseAdj(jnp.asarray(cols1), jnp.asarray(mask1), jnp.int32(24), jnp.int32(6)))


def test_gatconv_has_an_output_bias_after_the_aggregation():
    rng = np.random.default_rng(0)
    adj = _adjs(rng)[1]
    x = jnp.asarray(rng.normal(size=(24, 7)).astype(np.float32))
    for concat, width in ((True, 10), (False, 5)):
        conv = GATConv(out_dim=5, heads=2, concat=concat)
        params = conv.init(jax.random.key(0), x, adj)
        assert params["params"]["bias"].shape == (10,) and not params["params"]["bias"].any()
        base = conv.apply(params, x, adj)
        assert base.shape == (6, width)
        bias = jnp.arange(10, dtype=jnp.float32)
        shifted = conv.apply({"params": dict(params["params"], bias=bias)}, x, adj)
        want = bias if concat else bias.reshape(2, 5).mean(axis=0)
        np.testing.assert_allclose(shifted - base, jnp.broadcast_to(want, base.shape), atol=1e-5)


def test_gat_takes_the_output_heads_and_the_activation_from_its_attributes():
    rng = np.random.default_rng(1)
    adjs = _adjs(rng)
    x = jnp.asarray(rng.normal(size=(72, 7)).astype(np.float32))
    default = GAT(hidden_dim=4, out_dim=5, heads=2, num_layers=2, dropout=0.0)
    p = default.init(jax.random.key(0), x, adjs)["params"]
    assert p["gat1"]["att_src"].shape == (1, 1, 5) and default.activation is jax.nn.elu
    wide = GAT(hidden_dim=4, out_dim=5, heads=2, num_layers=2, dropout=0.0, out_heads=3,
               activation=jax.nn.relu)
    params = wide.init(jax.random.key(0), x, adjs)
    assert params["params"]["gat1"]["att_src"].shape == (1, 3, 5)
    assert params["params"]["gat1"]["lin"]["kernel"].shape == (8, 15)
    out = wide.apply(params, x, adjs)
    assert out.shape == (6, 5) and out.dtype == jnp.float32
    elu = GAT(hidden_dim=4, out_dim=5, heads=2, num_layers=2, dropout=0.0, out_heads=3)
    assert not np.allclose(np.asarray(elu.apply(params, x, adjs)), np.asarray(out))
