"""The aggregation over explicit ``cols`` (`ops.gather_sum`, behind
`models.masked_mean_aggregate`) against the plain expression it replaces:

    take(x, clip(cols)) -> * mask -> sum(axis=1) -> / max(count, 1)

which lives on here, as the reference. The kernel runs on the CPU under
``interpret=True``; the structural layout keeps the parent's expression and
must give its bits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu.models import SAGEConv, masked_mean_aggregate
from quiver_tpu.ops import gather_sum
from quiver_tpu.pyg.sage_sampler import DenseAdj

SHAPES = [(64, 15, 1024), (40, 10, 128), (8, 5, 100)]  # (W_dst, k, D)


def plain_sum(x, cols, mask):
    g = jnp.take(x, jnp.clip(cols, 0, x.shape[0] - 1), axis=0)
    return (g * mask[..., None].astype(x.dtype)).sum(axis=1)


def plain_mean(x, cols, mask):
    cnt = jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(x.dtype)
    return plain_sum(x, cols, mask) / cnt


def case(w, k, d, seed=0, n_src=None):
    """Rows with no valid slot (the first two), ids out of range under a
    false mask, and a source wider than the targets."""
    rng = np.random.default_rng(seed)
    n_src = n_src or 3 * w
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    cols = rng.integers(0, n_src, (w, k)).astype(np.int32)
    mask = rng.random((w, k)) < 0.6
    mask[:2] = False
    cols[~mask] = rng.choice([-7, n_src, 2**31 - 1], size=int((~mask).sum()))
    return jnp.asarray(x), jnp.asarray(cols), jnp.asarray(mask)


def adj_of(cols, mask):
    return DenseAdj(cols=cols, mask=mask, n_src=jnp.int32(0), n_dst=jnp.int32(0))


@pytest.mark.parametrize("w,k,d", SHAPES)
def test_aggregate_matches_the_plain_expression(w, k, d):
    x, cols, mask = case(w, k, d)
    got = np.asarray(masked_mean_aggregate(x, adj_of(cols, mask)))
    want = np.asarray(plain_mean(x, cols, mask))
    # the same k products in another order of addition: rounding, no more
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-6)
    assert (got[:2] == 0).all()  # no valid slot: exactly zero, not 0 / 0
    jitted = np.asarray(jax.jit(masked_mean_aggregate)(x, adj_of(cols, mask)))
    np.testing.assert_array_equal(jitted, got)


@pytest.mark.parametrize("w,k,d", SHAPES)
def test_gradient_with_respect_to_the_source_rows(w, k, d):
    x, cols, mask = case(w, k, d, seed=1)
    t = jnp.asarray(np.random.default_rng(2).normal(size=(w, d)).astype(np.float32))
    got = jax.grad(lambda v: (masked_mean_aggregate(v, adj_of(cols, mask)) * t).sum())(x)
    want = jax.grad(lambda v: (plain_mean(v, cols, mask) * t).sum())(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
    # a source row no valid slot names gets no gradient at all
    named = np.zeros(x.shape[0], bool)
    named[np.asarray(jnp.clip(cols, 0, x.shape[0] - 1))[np.asarray(mask)]] = True
    assert (np.asarray(got)[~named] == 0).all()


@pytest.mark.parametrize("w,k,d", SHAPES)
def test_gradient_through_sageconv_to_lin_l(w, k, d):
    x, cols, mask = case(w, k, d, seed=3)
    conv = SAGEConv(16)
    params = conv.init(jax.random.key(0), x, adj_of(cols, mask))

    def plain_conv(p, v):
        p = p["params"]
        return (plain_mean(v, cols, mask) @ p["lin_l"]["kernel"] + p["lin_l"]["bias"]
                + v[:w] @ p["lin_r"]["kernel"])

    np.testing.assert_allclose(np.asarray(conv.apply(params, x, adj_of(cols, mask))),
                               np.asarray(plain_conv(params, x)), rtol=0, atol=2e-5)
    got = jax.grad(lambda p: (conv.apply(p, x, adj_of(cols, mask)) ** 2).sum())(params)
    want = jax.grad(lambda p: (plain_conv(p, x) ** 2).sum())(params)
    for name in ("kernel", "bias"):
        a, b = got["params"]["lin_l"][name], want["params"]["lin_l"][name]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("w,k,d", [(64, 15, 1024), (40, 10, 128), (1030, 3, 256)])
def test_kernel_under_interpret_gives_the_plain_forms_bits(w, k, d):
    """The kernel and the `jax.numpy` form add the same k products in the
    same order (`_fold_sum`): bit for bit, rows of several blocks and a
    ragged last block included."""
    x, cols, mask = case(w, k, d, seed=4)
    kernel = np.asarray(gather_sum._fused_sum(x, cols, mask, interpret=True))
    plain = np.asarray(gather_sum._slot_major_sum(x, cols, mask))
    np.testing.assert_array_equal(kernel.view(np.uint32), plain.view(np.uint32))
    np.testing.assert_allclose(kernel, np.asarray(plain_sum(x, cols, mask)), rtol=0, atol=4e-6)


@pytest.mark.parametrize("shape,dtype,takes", [
    ((417792, 1024), jnp.float32, True),     # igb-small layer 1: 4.5 GB of gather
    ((73728, 128), jnp.float32, False),      # igb-small layer 2: 512 B rows
    ((417792, 1024), jnp.bfloat16, False),   # the control's compute dtype
    ((417792, 1000), jnp.float32, False),    # not whole 128-lane tiles
    ((11264, 1024), jnp.float32, False),     # a serve bucket's 43 MB: not worth a kernel load
])
def test_which_rows_the_kernel_takes(shape, dtype, takes):
    cols = jax.ShapeDtypeStruct(((73728 if shape[0] > 20000 else 704), 15), jnp.int32)
    assert gather_sum._kernel_takes(jax.ShapeDtypeStruct(shape, dtype), cols) is takes


@pytest.mark.parametrize("w,k,d", SHAPES)
def test_structural_layout_keeps_the_parents_bits(w, k, d):
    """``cols is None``: slice, reshape, masked sum over axis 1, as before
    this aggregation had a second branch."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(w * (1 + k), d)).astype(np.float32))
    mask = jnp.asarray(rng.random((w, k)) < 0.6)
    adj = DenseAdj(cols=None, mask=mask, n_src=jnp.int32(0), n_dst=jnp.int32(0))

    def parent(x_src):
        gathered = x_src[w: w * (1 + k)].reshape((k, w, d)).swapaxes(0, 1)
        s = (gathered * mask[..., None].astype(x_src.dtype)).sum(axis=1)
        return s / jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(x_src.dtype)

    for run in (lambda f: f(x), lambda f: jax.jit(f)(x)):
        got = np.asarray(run(lambda v: masked_mean_aggregate(v, adj)))
        np.testing.assert_array_equal(got.view(np.uint32), np.asarray(run(parent)).view(np.uint32))
    # and it equals the explicit-cols answer over the same neighbours, to rounding
    cols = jnp.asarray((w + np.arange(k)[None, :] * w + np.arange(w)[:, None]).astype(np.int32))
    np.testing.assert_allclose(np.asarray(masked_mean_aggregate(x, adj)),
                               np.asarray(masked_mean_aggregate(x, adj_of(cols, mask))),
                               rtol=0, atol=4e-6)
