"""The plain reference of the relational attention configuration
(`qbench.reference_rgat`) agrees with `quiver_tpu.models.RGAT` on seeded
weights at a small size, over a real typed sample of the tiny stand-in
(tests/qbench/tiny1h, drawn by `pyg.HeteroSampler`, with padded slots and
targets that drew no neighbour): logits, loss, every gradient leaf and the
parameters after three Adam steps (optax's), with relations summed and
averaged. The same comparison fails for the library's bfloat16 path and for
a reference with a fault planted; `qbench.work_rgat` is held to a hand
count."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from qbench import check, graphgen_hetero, manifest, reference_rgat, work_rgat
from quiver_tpu import CSRTopo
from quiver_tpu.models import RGAT
from quiver_tpu.pyg import HeteroCSRTopo, HeteroSampler

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny1h")
CFG = manifest.load_json(os.path.join(TINY, "qbench", "configs", "tiny1h-rgat.json"))
CAPS = manifest.load_json(os.path.join(TINY, "qbench", "workloads",
                                       "tiny1h-rgat.train-dedup.json"))["caps"]
# float32 against float32, the same sums in another order. The change of parameters is
# compared by its norm, leaf by leaf, as the cell compares it: the last layer's attention
# vectors get a tenth of the median leaf's gradient, and Adam's normalised step turns
# their round-off into 1% of their change (0.0101 at seed 7)
TOLERANCE = {"logits": 2e-5, "loss": 1e-5, "grads": 2e-4, "params3": 2e-2}


@pytest.fixture(scope="module")
def sample():
    """(x, blocks as the model takes them, the reference's x and blocks, labels)."""
    g = graphgen_hetero.hetero_graph(CFG, 3)
    topo = HeteroCSRTopo(g.num_nodes, {r: CSRTopo(indptr=v.indptr, indices=v.indices)
                                       for r, v in g.relations.items()})
    ds = HeteroSampler(topo, CFG["fanout"], caps=CAPS, seed=2).sample_dense(np.arange(32) * 13)
    table, labels = graphgen_hetero.features_and_labels(CFG, 3)
    x = jnp.asarray(table[np.asarray(ds.n_id)])
    ref_x = {t: x[at: at + w] for t, (at, w) in ds.adjs.spans().items()}
    ref_blocks = [{k: (a.cols, a.mask) for k, a in layer.items()} for layer in ds.adjs.layers]
    # the sample has what the comparison must cover: padded slots, targets with none
    masks = [np.asarray(a.mask) for layer in ds.adjs.layers for a in layer.values()]
    assert any((~m).any() for m in masks) and any((~m.any(axis=1)).any() for m in masks)
    return x, ds.adjs, ref_x, ref_blocks, jnp.asarray(labels[np.arange(32) * 13])


def _model(dtype=None, aggr="sum"):
    return RGAT(out_dim=CFG["classes"], hidden_dim=CFG["hidden_dim"], heads=CFG["heads"],
                num_layers=CFG["num_layers"], relation_aggr=aggr, dtype=dtype)


def _worst(got, want):
    """Largest gap of any leaf, over the largest magnitude of that leaf."""
    return max(float(np.abs(np.asarray(a, np.float32) - np.asarray(b)).max()
                     / max(float(np.abs(np.asarray(b)).max()), 1e-30))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def gaps(model, params, sample, aggr="sum", forward=reference_rgat.forward):
    x, blocks, ref_x, ref_blocks, y = sample

    def lib_loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(model.apply(p, x, blocks), y).mean()

    def ref_loss(p):
        return reference_rgat.reference.cross_entropy(
            forward(p, ref_x, ref_blocks, "float32", aggr), y)

    out = {"logits": _worst(jax.jit(model.apply)(params, x, blocks),
                            jax.jit(forward, static_argnums=(3, 4))(params, ref_x, ref_blocks,
                                                                      "float32", aggr))}
    (loss, grads), (want_loss, want_grads) = (jax.jit(jax.value_and_grad(f))(params)
                                              for f in (lib_loss, ref_loss))
    out["loss"] = abs(float(loss) - float(want_loss)) / abs(float(want_loss))
    out["grads"] = _worst(grads, want_grads)
    if forward is reference_rgat.forward:
        tx = optax.adam(0.01)
        state, p = tx.init(params), params
        lib_grad = jax.jit(jax.grad(lib_loss))
        for _ in range(3):
            updates, state = tx.update(lib_grad(p), state, p)
            p = optax.apply_updates(p, updates)
        losses, grad1, p3 = reference_rgat.follow_steps(params, [(ref_x, ref_blocks, y)] * 3, 0.01,
                                                        relation_aggr=aggr)
        assert losses[2] < losses[0] and _worst(grad1, want_grads) < 1e-5
        out["params3"] = check.worst_norm_gap(
            check.leaf_norms(check.tree_diff(p, params)),
            check.leaf_norms(check.tree_diff(p3, params)),
            skip=check.quiet_leaves(check.leaf_norms(want_grads)))
    return out


def beyond(found):
    return {k for k, v in found.items() if not v <= TOLERANCE[k]}


@pytest.mark.parametrize("aggr", ["sum", "mean"])
def test_logits_loss_gradients_and_three_adam_steps_match_the_model(sample, aggr):
    params = reference_rgat.params_of(CFG, 7)
    x, blocks = sample[0], sample[1]
    # the seed-made tree is a valid parameter tree of the flax model
    want_tree = jax.eval_shape(lambda: _model(aggr=aggr).init(jax.random.key(0), x, blocks))
    assert jax.tree.structure(want_tree) == jax.tree.structure(params)
    assert jax.tree.map(lambda a: a.shape, want_tree) == jax.tree.map(lambda a: a.shape, params)
    found = gaps(_model(aggr=aggr), params, sample, aggr)
    assert set(found) == set(TOLERANCE) and not beyond(found), found


def test_the_layers_run_the_relations_the_hops_reach():
    relations, seed_type = reference_rgat.schema_of(CFG)
    layers = reference_rgat.layer_relations(relations, seed_type, 3)
    assert len(layers[0]) == 7 and len(layers[1]) == 6 and len(layers[2]) == 3
    assert all(k.endswith("__paper") for k in layers[2])
    assert "author__affiliated_to__institute" not in layers[1]


def test_a_target_without_a_valid_neighbour_gets_the_bias_alone(sample):
    params = reference_rgat.params_of(CFG, 3)["params"]
    _, _, ref_x, ref_blocks, _ = sample
    key = next(k for k, (c, m) in ref_blocks[0].items() if (~np.asarray(m).any(axis=1)).any())
    src, _, dst = key.split("__")
    cols, mask = ref_blocks[0][key]
    p = dict(params[f"layer0_{key}"], bias=jnp.arange(CFG["hidden_dim"], dtype=jnp.float32))
    out = reference_rgat.relation_layer(p, ref_x[src], ref_x[dst], cols, mask, "float32")
    lonely = ~np.asarray(mask).any(axis=1)
    np.testing.assert_array_equal(np.asarray(out)[lonely],
                                  np.broadcast_to(np.asarray(p["bias"]), (lonely.sum(), 12)))


def test_the_bfloat16_control_is_beyond_the_tolerances(sample):
    found = gaps(_model(jnp.bfloat16), reference_rgat.params_of(CFG, 7), sample)
    assert {"logits", "grads"} <= beyond(found), found


def test_a_planted_fault_is_beyond_the_tolerances(sample, monkeypatch):
    """The reference with the relations' outputs averaged where the model sums
    them: every number the comparison reads moves past its tolerance."""
    def faulty(p, x, blocks, operands, aggr):
        return reference_rgat.forward(p, x, blocks, operands, "mean")

    found = gaps(_model(), reference_rgat.params_of(CFG, 7), sample, forward=faulty)
    assert {"logits", "loss", "grads"} <= beyond(found), found


def test_work_counts_match_a_hand_count():
    # layer 0: one relation, 8 -> 2 heads x 3 (width 6), 10 sources, 4 targets, 6 pairs;
    # layer 1: two relations into the seeds, 6 -> 6, (4 sources, 2 targets, 3 pairs) and (5, 2, 4)
    layers = [[(8, 10, 4, 6)], [(6, 4, 2, 3), (6, 5, 2, 4)]]
    proj = [2 * (10 + 4) * 8 * 6, 2 * (4 + 2) * 6 * 6, 2 * (5 + 2) * 6 * 6]
    scores = [2 * 6 * (10 + 4), 2 * 6 * (4 + 2), 2 * 6 * (5 + 2)]
    att = [6 * 2 * (4 + 2 * 3), 3 * 2 * (4 + 2 * 3), 4 * 2 * (4 + 2 * 3)]
    sums = [4 * 6, 2 * 6, 2 * 6]  # one add a lane per relation and target
    head = 2 * 2 * 6 * 5          # 2 seeds, 6 -> 5 classes
    forward = sum(proj) + sum(scores) + sum(att) + sum(sums) + head
    assert work_rgat.rgat_flops(layers, 6, 2, (2, 6, 5), backward=False) == forward
    assert work_rgat.rgat_flops(layers, 6, 2, (2, 6, 5), backward=True) == (
        forward + proj[0] + 2 * (proj[1] + proj[2]) + 2 * sum(att) + 2 * head)
    assert work_rgat.project_flops(layers[0], 6) == proj[0]
    # each valid pair's row of width 6 float32 read, each target's output written; x3 backward
    assert work_rgat.edge_bytes(layers, 6, backward=False) == ((6 + 4) + (3 + 2) + (4 + 2)) * 6 * 4
    assert work_rgat.edge_bytes(layers, 6, backward=True) == 3 * work_rgat.edge_bytes(
        layers, 6, backward=False)
