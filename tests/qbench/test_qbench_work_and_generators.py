"""`qbench.work` against hand-computed operations and bytes, and the
determinism and skew of the benchmark's generators."""

import os

import numpy as np
import pytest

from qbench import graphgen, manifest, reference, traffic, work


def _config(name):
    return manifest.load_json(os.path.join(manifest.HERE, "configs", f"{name}.json"))


def test_sage_flops_products_full_fanout_by_hand():
    # 1024 seeds, fan-out 15-10-5, every neighbour present; layers outermost first
    cfg = _config("products-sage")
    targets = [1024 * 16 * 11, 1024 * 16, 1024]
    pairs = [targets[0] * 5, targets[1] * 10, targets[2] * 15]
    dims = reference.layer_dims(cfg["feat_dim"], cfg["hidden_dim"], cfg["classes"], 3)
    assert dims == [(100, 256), (256, 256), (256, 47)]
    fwd = sum(e * di + 4 * t * di * do for t, e, (di, do) in zip(targets, pairs, dims))
    assert work.sage_flops(targets, pairs, dims, backward=False) == pytest.approx(fwd)
    # backward: weight gradients only in layer 0; both products and the
    # mean's scatter in the others
    bwd = 4 * targets[0] * 100 * 256
    bwd += 2 * 4 * targets[1] * 256 * 256 + pairs[1] * 256
    bwd += 2 * 4 * targets[2] * 256 * 47 + pairs[2] * 256
    assert work.sage_flops(targets, pairs, dims, backward=True) == pytest.approx(fwd + bwd)
    # about 50 GFLOP a step: what PERF.md's mfu arithmetic starts from
    assert 4.5e10 < fwd + bwd < 6.5e10


def test_sage_flops_igb_by_hand():
    cfg = _config("igb-small-sage")
    dims = reference.layer_dims(cfg["feat_dim"], cfg["hidden_dim"], cfg["classes"], 2)
    assert dims == [(1024, 128), (128, 19)]
    targets, pairs = [60000, 10240], [700000, 60000]
    fwd = (700000 * 1024 + 4 * 60000 * 1024 * 128) + (60000 * 128 + 4 * 10240 * 128 * 19)
    bwd = 4 * 60000 * 1024 * 128 + (2 * 4 * 10240 * 128 * 19 + 60000 * 128)
    assert work.sage_flops(targets, pairs, dims, backward=True) == pytest.approx(fwd + bwd)


@pytest.mark.parametrize("rows,row_bytes,want", [
    (1_081_344, 400, 2 * 1_081_344 * 400), (365_000, 4096, 2 * 365_000 * 4096)])
def test_gather_bytes_by_hand(rows, row_bytes, want):
    assert work.gather_bytes(rows, row_bytes) == want


def test_graph_is_deterministic_per_seed_and_exact_in_size():
    a = graphgen.powerlaw_graph(20_000, 400_000, 5, alpha=2.5, shift=0.0, max_degree=2000)
    b = graphgen.powerlaw_graph(20_000, 400_000, 5, alpha=2.5, shift=0.0, max_degree=2000)
    c = graphgen.powerlaw_graph(20_000, 400_000, 6, alpha=2.5, shift=0.0, max_degree=2000)
    assert np.array_equal(a.indptr, b.indptr) and np.array_equal(a.indices, b.indices)
    assert not np.array_equal(a.indices, c.indices)
    # every seed deals out the SAME degrees (to other nodes), so the sampler's
    # 128-lane tile table has the same number of rows and no seed recompiles
    assert not np.array_equal(np.diff(a.indptr), np.diff(c.indptr))
    assert np.array_equal(np.sort(np.diff(a.indptr)), np.sort(np.diff(c.indptr)))
    assert a.indptr[-1] == 400_000 == a.indices.shape[0]
    deg = np.diff(a.indptr)
    assert deg.min() >= 1 and deg.max() <= 2000
    assert a.indices.min() >= 0 and a.indices.max() < 20_000


def test_graph_takes_seeds_past_32_bits():
    a = graphgen.powerlaw_graph(2_000, 20_000, 2**31 + 12345, alpha=2.5, shift=0.0, max_degree=500)
    b = graphgen.powerlaw_graph(2_000, 20_000, 12345, alpha=2.5, shift=0.0, max_degree=500)
    assert not np.array_equal(a.indices, b.indices)


def test_fitted_skew_is_near_the_documented_shares():
    # ogbn-products (the source's Introduction): nodes above the mean degree
    # are 31.3% of nodes and hold 76.8% of edges. The stand-in's profile, at
    # a tenth of the size and the same mean degree, has to stay close.
    g = _config("products-sage")["graph"]
    gr = graphgen.powerlaw_graph(244_903, 12_371_828, 3, alpha=g["alpha"],
                                 shift=g["shift"], max_degree=g["max_degree"])
    s = graphgen.skew(gr.indptr)
    assert abs(s["nodes_above_mean_share"] - 0.313) < 0.05
    assert abs(s["edges_on_them_share"] - 0.768) < 0.05
    # destinations are degree-proportional: in-degree follows out-degree
    indeg = np.bincount(gr.indices, minlength=244_903)
    assert np.corrcoef(np.diff(gr.indptr), indeg)[0, 1] > 0.9


def test_features_labels_split_deterministic():
    t1, l1 = graphgen.features_and_labels(3000, 24, 7, 11)
    t2, l2 = graphgen.features_and_labels(3000, 24, 7, 11)
    assert np.array_equal(t1, t2) and np.array_equal(l1, l2)
    assert t1.dtype == np.float32 and l1.max() < 7
    s1, s2 = graphgen.train_split(3000, 500, 11), graphgen.train_split(3000, 500, 12)
    assert len(np.unique(s1)) == 500 and not np.array_equal(s1, s2)


@pytest.mark.parametrize("arrivals,burst", [("poisson", 1), ("bursty", 8)])
def test_every_seed_gets_the_same_work_in_another_order(arrivals, burst):
    a = traffic.requests(50_000, 1, rate=500, seconds=4, alpha=0.99, arrivals=arrivals, burst=burst)
    b = traffic.requests(50_000, 2, rate=500, seconds=4, alpha=0.99, arrivals=arrivals, burst=burst)
    a2 = traffic.requests(50_000, 1, rate=500, seconds=4, alpha=0.99, arrivals=arrivals, burst=burst)
    assert np.array_equal(a.due_s, a2.due_s) and np.array_equal(a.nodes, a2.nodes)
    assert a.due_s.shape == b.due_s.shape and not np.array_equal(a.nodes, b.nodes)
    assert np.allclose(np.sort(np.diff(a.due_s, prepend=0)), np.sort(np.diff(b.due_s, prepend=0)))
    # same popularity multiset: the same counts of repeated nodes
    ca = np.sort(np.unique(a.nodes, return_counts=True)[1])
    cb = np.sort(np.unique(b.nodes, return_counts=True)[1])
    assert np.array_equal(ca, cb)
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 4
    assert abs(a.due_s.shape[0] / 4 - 500) < 100  # 2000 requests, or 250 bursts


def test_zipf_is_skewed():
    r = traffic.zipf_ranks(100_000, 20_000, 0.99)
    assert (r < 1000).mean() > 0.5  # the hottest 1% of ranks take most requests
