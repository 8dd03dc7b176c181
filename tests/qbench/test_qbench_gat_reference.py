"""The plain reference of the attention configurations (`qbench.reference_gat`)
agrees with `quiver_tpu.models.GAT` on seeded weights at a small size: logits,
loss, every gradient leaf and the parameters after three Adam steps (optax's),
for the structural (fused) and the explicit-``cols`` `DenseAdj` alike, with
padded slots and a target that drew no neighbour. The same comparison fails
for the library's bfloat16 path and for a reference with a fault planted in
its attention; `qbench.work_gat` is held to a hand count."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from qbench import check, reference_gat, work_gat
from quiver_tpu.models import GAT
from quiver_tpu.pyg.sage_sampler import DenseAdj

FEAT, HIDDEN, CLASSES, HEADS, OUT_HEADS = 12, 8, 5, 3, 2
DIMS = reference_gat.layer_dims(FEAT, HIDDEN, CLASSES, 2, HEADS, OUT_HEADS)
LONER = 1  # a target of each hop whose slots are all padding
# float32 against float32, the same sums in another order. The parameters' change is
# compared by its norm, leaf by leaf, as the cell compares it: at 6 seeds one entry of
# ``att_dst`` whose gradient passes through zero moves by Adam's whole step on one side
# (0.0047 of a leaf's 0.114), so that number has the room of such an entry
TOLERANCE = {"logits": 2e-5, "loss": 1e-5, "grads": 2e-4, "params3": 1e-2}


def _model(dtype=None):
    return GAT(hidden_dim=HIDDEN, out_dim=CLASSES, heads=HEADS, out_heads=OUT_HEADS,
               num_layers=2, dropout=0.0, activation=jax.nn.relu, dtype=dtype)


def _blocks(rng, structural):
    """Two hops over 6 seeds: widths 6 -> 6*(1+3) = 24 -> 24*(1+2) = 72; the
    padded slots of the explicit layout hold ids far out of range."""
    w1, k1, w0, k0 = 6, 3, 24, 2
    mask1, mask0 = rng.random((w1, k1)) < 0.7, rng.random((w0, k0)) < 0.7
    mask1[LONER] = mask0[LONER] = False
    if structural:
        cols1, cols0 = check.structural_cols(w1, k1), check.structural_cols(w0, k0)
        lib1 = lib0 = None
    else:
        cols1 = rng.integers(0, w0, (w1, k1)).astype(np.int32)
        cols0 = rng.integers(0, 72, (w0, k0)).astype(np.int32)
        cols1[~mask1], cols0[~mask0] = 2**31 - 1, -5
        lib1, lib0 = jnp.asarray(cols1), jnp.asarray(cols0)
    adjs = (DenseAdj(lib0, jnp.asarray(mask0), jnp.int32(72), jnp.int32(24)),
            DenseAdj(lib1, jnp.asarray(mask1), jnp.int32(24), jnp.int32(6)))
    blocks = [(jnp.asarray(cols0), jnp.asarray(mask0)), (jnp.asarray(cols1), jnp.asarray(mask1))]
    x = jnp.asarray(rng.standard_normal((72, FEAT)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, CLASSES, 6).astype(np.int32))
    return x, adjs, blocks, y


def _worst(got, want):
    """Largest gap of any leaf, over the largest magnitude of that leaf."""
    return max(float(np.abs(np.asarray(a, np.float32) - np.asarray(b)).max()
                     / max(float(np.abs(np.asarray(b)).max()), 1e-30))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def gaps(model, params, x, adjs, blocks, y, forward=reference_gat.forward):
    """The library (the flax model under optax's Adam) against the reference
    (``forward`` and what follows from it): the four numbers of `TOLERANCE`."""
    def lib_loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(model.apply(p, x, adjs), y).mean()

    def ref_loss(p):
        return reference_gat.reference.cross_entropy(forward(p, x, blocks), y)

    out = {"logits": _worst(model.apply(params, x, adjs), forward(params, x, blocks))}
    (loss, grads), (want_loss, want_grads) = (jax.value_and_grad(f)(params)
                                              for f in (lib_loss, ref_loss))
    out["loss"] = abs(float(loss) - float(want_loss)) / abs(float(want_loss))
    out["grads"] = _worst(grads, want_grads)
    if forward is reference_gat.forward:
        tx = optax.adam(0.01)
        state, p = tx.init(params), params
        for _ in range(3):
            updates, state = tx.update(jax.grad(lib_loss)(p), state, p)
            p = optax.apply_updates(p, updates)
        losses, grad1, p3 = reference_gat.follow_steps(params, [(x, blocks, y)] * 3, 0.01)
        assert losses[2] < losses[0] and _worst(grad1, want_grads) < 1e-6
        # as the cell compares them (`kinds.train.readings`): the change's norm, leaf by leaf.
        # Entry by entry a gradient of exactly 0 on one side and of 1e-12 on the other
        # moves an entry by Adam's whole step on one side alone
        ref_grad = check.leaf_norms(want_grads)
        out["params3"] = check.worst_norm_gap(
            check.leaf_norms(check.tree_diff(p, params)), check.leaf_norms(check.tree_diff(p3, params)),
            skip=check.quiet_leaves(ref_grad))
    return out


def beyond(found):
    return {k for k, v in found.items() if not v <= TOLERANCE[k]}


@pytest.mark.parametrize("structural", [True, False], ids=["fused-layout", "dedup-layout"])
def test_logits_loss_gradients_and_three_adam_steps_match_the_model(structural):
    x, adjs, blocks, y = _blocks(np.random.default_rng(0), structural)
    params = reference_gat.init_params(7, DIMS)
    model = _model()
    # the seed-made tree is a valid parameter tree of the flax model
    want_tree = jax.eval_shape(lambda: model.init(jax.random.key(0), x, adjs))
    assert jax.tree.structure(want_tree) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, want_tree) == jax.tree.map(lambda a: a.shape, params)
    found = gaps(model, params, x, adjs, blocks, y)
    assert set(found) == set(TOLERANCE) and not beyond(found), found


@pytest.mark.parametrize("structural", [True, False], ids=["fused-layout", "dedup-layout"])
def test_a_target_without_a_valid_neighbour_attends_itself_alone(structural):
    x, adjs, blocks, _ = _blocks(np.random.default_rng(1), structural)
    p = reference_gat.init_params(3, DIMS)["params"]["gat0"]
    p = dict(p, bias=jnp.arange(HEADS * HIDDEN, dtype=jnp.float32))
    cols, mask = blocks[0]
    out = reference_gat.gat_layer(p, x, cols, mask, "float32", 0.2)
    z = jnp.dot(x, p["lin"]["kernel"], precision="highest").reshape(-1, HEADS, HIDDEN)
    np.testing.assert_allclose(out[LONER], z[LONER] + p["bias"].reshape(HEADS, HIDDEN),
                               rtol=1e-6, atol=1e-6)
    # and a padded slot takes exactly no mass, whatever row its id names
    alpha = reference_gat.attention(jnp.zeros((4, 3, 2)), jnp.zeros((4, 2)),
                                    jnp.asarray([[1, 0, 1], [0, 0, 0], [1, 1, 1], [0, 1, 0]], bool))
    np.testing.assert_array_equal(np.asarray(alpha[..., 0]),
                                  np.asarray([[1 / 3, 0, 1 / 3, 1 / 3], [0, 0, 0, 1],
                                              [.25, .25, .25, .25], [0, .5, 0, .5]], np.float32))


def test_the_bfloat16_control_is_beyond_the_tolerances():
    x, adjs, blocks, y = _blocks(np.random.default_rng(0), False)
    params = reference_gat.init_params(7, DIMS)
    found = gaps(_model(jnp.bfloat16), params, x, adjs, blocks, y)
    assert {"logits", "grads"} <= beyond(found), found


def _padded_slot_given_mass(attention, e_nbr, e_self, mask):
    return attention(e_nbr, e_self, jnp.ones_like(mask))


def _self_edge_dropped(attention, e_nbr, e_self, mask):
    alpha = attention(e_nbr, e_self, mask)
    nbrs = alpha[:, :-1] / jnp.maximum(alpha[:, :-1].sum(axis=1, keepdims=True), 1e-30)
    return jnp.concatenate([nbrs, jnp.zeros_like(alpha[:, -1:])], axis=1)


@pytest.mark.parametrize("fault", [_padded_slot_given_mass, _self_edge_dropped])
@pytest.mark.parametrize("structural", [True, False], ids=["fused-layout", "dedup-layout"])
def test_a_planted_fault_is_beyond_the_tolerances(monkeypatch, structural, fault):
    x, adjs, blocks, y = _blocks(np.random.default_rng(0), structural)
    params = reference_gat.init_params(7, DIMS)
    real = reference_gat.attention

    def faulty_forward(p, x, blocks):
        with monkeypatch.context() as m:  # `gat_layer` looks `attention` up as it is traced
            m.setattr(reference_gat, "attention", functools.partial(fault, real))
            return reference_gat.forward(p, x, blocks)

    found = gaps(_model(), params, x, adjs, blocks, y, forward=faulty_forward)
    assert {"logits", "loss", "grads"} <= beyond(found), found


def test_work_counts_match_a_hand_count():
    # layer 0: 10 valid sources, 4 targets, 6 pairs, 8 -> 2 heads x 3; layer 1: 4, 2, 3, 6 -> 2 x 5
    dims = [(8, 2, 3), (6, 2, 5)]
    proj0, proj1 = 2 * 10 * 8 * 6, 2 * 4 * 6 * 10
    scores0, scores1 = 2 * 6 * (10 + 4), 2 * 10 * (4 + 2)
    att0 = (6 + 4) * 2 * (4 + 2 * 3)     # pairs and the self edges, a head: 4 ops and 2 D
    att1 = (3 + 2) * 2 * (4 + 2 * 5)
    forward = proj0 + scores0 + att0 + proj1 + scores1 + att1
    assert forward == 960 + 168 + 200 + 480 + 120 + 140
    assert work_gat.gat_flops([10, 4], [4, 2], [6, 3], dims, backward=False) == forward
    # backward: weight gradients, the second layer's input gradient, the per-pair work twice
    assert work_gat.gat_flops([10, 4], [4, 2], [6, 3], dims, backward=True) == (
        forward + proj0 + 2 * proj1 + 2 * (att0 + att1))
    assert work_gat.project_flops(10, 8, 2, 3) == proj0
    # 6 pairs' rows of 2 x 3 float32 read and 4 targets' outputs written; backward twice that again
    assert work_gat.edge_bytes(6, 4, 2, 3, backward=False) == (6 + 4) * 6 * 4
    assert work_gat.edge_bytes(6, 4, 2, 3, backward=True) == 3 * (6 + 4) * 6 * 4
    # padding counts nothing: the counts are linear in the valid sizes alone
    assert work_gat.gat_flops([20, 8], [8, 4], [12, 6], dims, True) == 2 * work_gat.gat_flops(
        [10, 4], [4, 2], [6, 3], dims, True)
