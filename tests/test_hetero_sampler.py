"""`pyg.HeteroSampler` (typed edges, a frontier per node type) against a
host oracle on the tiny typed stand-in of tests/qbench/tiny1h: every valid
slot is a real edge of its relation, each valid target drew min(in-degree,
fan-out), each type's segment is unique and its hops are prefixes of each
other, the relations' targets are the prefix of their type's segment, the
stacked global ids are the segments plus their offsets, and a cap that is
too small is counted, never silent. Also: `ops.reindex.segment_reindex`
against a set oracle, the stand-in's reverse relations as transposes, the
counters a traced call records, and the single-relation sampler's outputs
bit-equal to the parent commit's on the same key (the refactor of
`local_reindex` under it changed no draw)."""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from qbench import graphgen, graphgen_hetero, manifest
from quiver_tpu import CSRTopo, trace
from quiver_tpu.ops.reindex import segment_reindex
from quiver_tpu.pyg import GraphSageSampler, HeteroCSRTopo, HeteroSampler
from quiver_tpu.pyg.hetero_sampler import relation_key

CONFIG = manifest.load_json(os.path.join(os.path.dirname(os.path.abspath(__file__)), "qbench",
                                         "tiny1h", "qbench", "configs", "tiny1h-rgat.json"))
CAPS = manifest.load_json(os.path.join(os.path.dirname(os.path.abspath(__file__)), "qbench", "tiny1h",
                                       "qbench", "workloads", "tiny1h-rgat.train-dedup.json"))["caps"]


@pytest.fixture(scope="module")
def graph():
    return graphgen_hetero.hetero_graph(CONFIG, 5)


def _sampler(graph, caps=CAPS, layout="flat", seed=3):
    topo = HeteroCSRTopo(graph.num_nodes, {rel: CSRTopo(indptr=r.indptr, indices=r.indices)
                                           for rel, r in graph.relations.items()})
    return HeteroSampler(topo, CONFIG["fanout"], caps=caps, seed=seed, seed_type="paper",
                         layout=layout)


def _edges(graph):
    return {relation_key(rel): {(d, int(s)) for d in range(r.indptr.shape[0] - 1)
                                for s in r.indices[r.indptr[d]: r.indptr[d + 1]]}
            for rel, r in graph.relations.items()}


def check_against_oracle(graph, ds, seeds):
    """Every property of the module docstring, on one sample."""
    edges = _edges(graph)
    hops = len(ds.adjs.layers)
    ids = {t: np.asarray(v) for t, v in ds.type_ids.items()}
    counts = {t: np.asarray(v) for t, v in ds.type_counts.items()}
    np.testing.assert_array_equal(ids["paper"][: len(seeds)], seeds)
    for t, v in ids.items():
        valid = v[: counts[t][-1]]
        assert np.unique(valid).shape == valid.shape, t
        assert (np.diff(counts[t]) >= 0).all(), t
    pairs = 0
    for l, layer in enumerate(ds.adjs.layers):
        hop = hops - 1 - l
        for key, adj in layer.items():
            src, _, dst = key.split("__")
            cols, mask = np.asarray(adj.cols), np.asarray(adj.mask)
            rel = graph.relations[tuple(key.split("__"))]
            assert int(adj.n_dst) == counts[dst][hop] and int(adj.n_src) == counts[src][hop + 1]
            for i in range(mask.shape[0]):
                deg = 0 if i >= counts[dst][hop] else int(
                    rel.indptr[ids[dst][i] + 1] - rel.indptr[ids[dst][i]])
                assert mask[i].sum() == min(deg, mask.shape[1]), (key, i)
                for j in np.flatnonzero(mask[i]):
                    assert cols[i, j] < counts[src][hop + 1]
                    assert (int(ids[dst][i]), int(ids[src][cols[i, j]])) in edges[key], (key, i, j)
                    pairs += 1
    assert pairs > 0
    # the stacked global rows of the feature table: segments plus their offsets
    n_id, at = np.asarray(ds.n_id), 0
    offsets = dict(zip(graph.num_nodes, np.cumsum([0] + list(graph.num_nodes.values()))))
    for t, w in ds.adjs.segments:
        c = counts[t][-1]
        np.testing.assert_array_equal(n_id[at: at + c], ids[t][:c] + offsets[t])
        assert (n_id[at + c: at + w] == offsets[t] + graph.num_nodes[t] - 1).all()
        at += w
    assert at == n_id.shape[0]


@pytest.mark.parametrize("layout", ["flat", "tiled"])
def test_every_slot_is_an_edge_and_every_segment_is_unique(graph, layout):
    sampler = _sampler(graph, layout=layout)
    for call in range(2):
        seeds = np.arange(32, dtype=np.int32) * 11 + call
        ds = sampler.sample_dense(seeds)
        assert int(ds.cap_overflow) == 0 and ds.batch_size == 32
        check_against_oracle(graph, ds, seeds)
    # one program a call, whatever the call index
    assert int(ds.valid_slots) == sum(int(np.asarray(a.mask).sum())
                                      for layer in ds.adjs.layers for a in layer.values())


def test_the_layouts_draw_the_same(graph):
    a = _sampler(graph, layout="flat").sample_dense(np.arange(32))
    b = _sampler(graph, layout="tiled").sample_dense(np.arange(32))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_the_targets_are_the_prefix_of_their_types_segment(graph):
    ds = _sampler(graph).sample_dense(np.arange(32))
    widths = dict(ds.adjs.segments)
    for l, layer in enumerate(ds.adjs.layers):
        for key, adj in layer.items():
            src, _, dst = key.split("__")
            # the layer's input for the destination type holds at least its targets
            assert adj.w_dst <= widths[dst]
    assert set(ds.adjs.layers[-1]) == {k for k in map(relation_key, graph.relations)
                                       if k.endswith("__paper")}


def test_a_cap_too_small_is_counted(graph):
    caps = {t: list(c) for t, c in CAPS.items()}
    caps["author"][2] = caps["author"][1] + 8  # hop 3's authors: hardly more than hop 2's
    ds = _sampler(graph, caps=caps).sample_dense(np.arange(32))
    overflow = np.asarray(ds.overflow)
    authors = list(graph.num_nodes).index("author")
    assert int(ds.cap_overflow) == overflow.sum() == overflow[2, authors] > 0
    raw = np.asarray(ds.raw_counts)
    assert raw[2, authors] - overflow[2, authors] == caps["author"][2]
    # nothing past the cap is pointed at: the dropped nodes' slots are masked out
    for layer in ds.adjs.layers:
        for key, adj in layer.items():
            if key.startswith("author__"):
                assert (np.asarray(adj.cols)[np.asarray(adj.mask)] < adj.n_src).all()


def test_calibrated_caps_hold_the_probes(graph):
    sampler = _sampler(graph, caps=None)
    order = graphgen.stream(5, 6, 0).permutation(1500)
    caps = sampler.calibrate_caps([order[i * 32:(i + 1) * 32] for i in range(8)], margin=1.2,
                                  granule=32)
    assert sampler.caps == caps and set(caps) == set(graph.num_nodes)
    for i in range(8):
        assert int(sampler.sample_dense(order[i * 32:(i + 1) * 32]).cap_overflow) == 0
    with pytest.raises(ValueError, match="unknown node types"):
        sampler.set_caps({"venue": [1, 2, 3]})


def test_a_traced_call_records_the_previous_calls_counters(graph, monkeypatch):
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace.trace_report(reset=True)
    sampler = _sampler(graph)
    first = sampler.sample_dense(np.arange(32))
    assert "quiver.sample.hetero.valid_slots" not in trace.trace_report()
    sampler.sample_dense(np.arange(32) + 1)
    report = trace.trace_report(reset=True)
    assert report["quiver.sample"][0] == report["quiver.sample.hetero"][0] == 2
    assert report["quiver.sample.hetero.valid_slots"] == (1, float(first.valid_slots))
    count, slots = report["quiver.sample.hetero.slots"]
    assert count == 1 and slots > float(first.valid_slots)
    assert report["quiver.sample.hetero.valid_slot_pct"][1] == pytest.approx(
        100 * float(first.valid_slots) / slots)
    assert report["quiver.sample.hetero.cap_overflow.h3.author"] == (1, 0.0)
    assert "quiver.sample.hetero.cap_overflow.h1.institute" not in report  # no segment there


def test_segment_reindex_keeps_the_seeds_and_dedups_the_rest():
    rng = np.random.default_rng(0)
    seeds = jnp.asarray([7, 3, 9, 0], jnp.int32)
    valid = jnp.asarray([True, True, True, False])
    flat = rng.integers(0, 20, 50).astype(np.int32)
    flat_valid = rng.random(50) < 0.8
    n_id, count, local = segment_reindex(seeds, valid, jnp.asarray(flat), jnp.asarray(flat_valid))
    n_id, local = np.asarray(n_id), np.asarray(local)
    want = [7, 3, 9] + sorted(set(flat[flat_valid].tolist()) - {7, 3, 9})
    assert int(count) == len(want) and n_id[: len(want)].tolist() == want
    np.testing.assert_array_equal(n_id[local[flat_valid]], flat[flat_valid])
    # no seeds at all: a type the frontier has not reached yet
    n_id, count, local = segment_reindex(jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                                         jnp.asarray(flat), jnp.asarray(flat_valid))
    assert np.asarray(n_id)[: int(count)].tolist() == sorted(set(flat[flat_valid].tolist()))
    np.testing.assert_array_equal(np.asarray(n_id)[np.asarray(local)[flat_valid]], flat[flat_valid])


def test_the_stand_ins_reverse_relations_are_transposes(graph):
    for spec in CONFIG["relations"]:
        if "reverse_of" not in spec:
            continue
        fwd, rev = graph.relations[tuple(spec["reverse_of"])], graph.relations[tuple(spec["relation"])]
        pairs = lambda r: sorted((d, int(s)) for d in range(r.indptr.shape[0] - 1)  # noqa: E731
                                 for s in r.indices[r.indptr[d]: r.indptr[d + 1]])
        assert pairs(fwd) == sorted((s, d) for d, s in pairs(rev))
        assert (np.diff(fwd.indptr) >= 1).all() and (np.diff(rev.indptr) >= 1).all()


def test_a_relation_must_fit_its_types():
    topo = CSRTopo(indptr=np.array([0, 1, 2]), indices=np.array([0, 5]))
    with pytest.raises(ValueError, match="an entry past"):
        HeteroCSRTopo({"a": 2, "b": 3}, {("b", "r", "a"): topo})
    with pytest.raises(ValueError, match="rows for"):
        HeteroCSRTopo({"a": 3, "b": 6}, {("b", "r", "a"): topo})
    with pytest.raises(ValueError, match="joins"):
        HeteroCSRTopo({"a": 2, "b": 6}, {("b", "r__s", "a"): topo})


# sha256 (first 16 hex digits) of every output leaf of two `sample_dense` calls, as
# /root/scratch/sample_hash.py computed them on the parent commit f9d9c48 (PR 38)
PARENT_SAMPLES = {"tiled-dedup": "f425ab732f4ad0fa", "tiled-fused": "727fff96e6b2720c",
                  "flat-dedup": "05a8bb02348762b3", "flat-fused": "eec598b756dccc28"}


@pytest.mark.parametrize("layout,dedup", [("tiled", True), ("tiled", False), ("flat", True),
                                          ("flat", False)])
def test_the_single_relation_sampler_draws_what_the_parent_drew(layout, dedup):
    rng = np.random.default_rng(3)
    topo = CSRTopo(edge_index=rng.integers(0, 400, (2, 5000)), num_nodes=400)
    s = GraphSageSampler(topo, [5, 3], mode="TPU", seed=11, dedup=dedup, layout=layout,
                         caps=(256, 1024) if dedup else None)
    h = hashlib.sha256()
    for call in range(2):
        for leaf in jax.tree.leaves(s.sample_dense(np.arange(32) * 7 + call)):
            h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == PARENT_SAMPLES[f"{layout}-{'dedup' if dedup else 'fused'}"]
