"""Round-19 workloads tests: temporal sampling + link-prediction serving
(quiver_tpu/workloads/) over the tiled sampler and both serve engines.

The acceptance contract (ISSUE 15 / docs/api.md "Temporal &
link-prediction serving"):

- a temporal tile draw is bit-equal to the host-masked oracle (CSR
  windows + the same Gumbel machinery), and at ``t = inf`` bit-equal to
  the frozen weighted sampler over the recency weight tiles;
- multi-hop sampling threads each SEED's own query time down its
  lineage; draws are replayable from ``(key, seeds, t)``;
- `StreamingTiledGraph(edge_ts=)` appends carry timestamps: an arriving
  edge is visible to the next ``t >= ts`` query and invisible below it,
  through pad-lane writes AND spills;
- both temporal engines key caches/coalescing by ``(node, t_bucket)``
  under the params version; `update_graph` drops an affected seed's
  entries at EVERY cached t; hosts=1 degenerates to the single-host
  temporal engine bit for bit; hosts=2 rows bit-match the temporal
  fleet oracle;
- ``submit_pair`` endpoints ride the shared coalescer/cache; pair
  scores are pure seeded functions of the endpoint rows.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_random_graph

from quiver_tpu import CSRTopo
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops.sample import (
    tiled_temporal_sample_layer,
    tiled_weighted_sample_layer,
)
from quiver_tpu.pyg.sage_sampler import GraphSageSampler
from quiver_tpu.serve import (
    DistServeConfig,
    ServeConfig,
    ServeEngine,
    lp_trace,
    temporal_trace,
)
from quiver_tpu.stream import GraphDelta, StreamingTiledGraph
from quiver_tpu.workloads import (
    LinkPredictor,
    PairHead,
    TemporalDistServeEngine,
    TemporalServeEngine,
    TemporalTiledGraph,
    host_masked_oracle,
    quantize_t,
    replay_temporal_fleet_oracle,
    replay_temporal_log,
    temporal_sample_dense,
)

N_NODES = 200
DIM = 12
SIZES = [3, 3]
SEED = 5
MAXD = 128
EDGE_INDEX = make_random_graph(N_NODES, 1400, seed=0)


def make_topo():
    return CSRTopo(edge_index=EDGE_INDEX)


TOPO = make_topo()
BASE_TS = np.random.default_rng(11).uniform(
    0.0, 50.0, TOPO.indices.shape[0]
).astype(np.float32)


def make_temporal_sampler(source=None, recency=0.02):
    s = GraphSageSampler(TOPO, sizes=SIZES, mode="TPU", seed=SEED,
                         dedup=False, max_deg=MAXD)
    if source is None:
        source = TemporalTiledGraph(TOPO, BASE_TS)
    return s.bind_temporal(source, recency=recency)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    model = GraphSAGE(hidden_dim=16, out_dim=5, num_layers=2, dropout=0.0)
    s0 = make_temporal_sampler()
    ds0 = s0.sample_dense(np.arange(8, dtype=np.int64), t=100.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((ds0.n_id.shape[0], DIM)), ds0.adjs
    )
    return model, params, feat


def make_engine(setup, source=None, recency=0.02, t_quantum=4.0, **cfg_kw):
    model, params, feat = setup
    cfg = ServeConfig(max_batch=8, buckets=(4, 8), max_delay_ms=1e9,
                      record_dispatches=True, **cfg_kw)
    return TemporalServeEngine(
        model, params, make_temporal_sampler(source, recency), feat, cfg,
        t_quantum=t_quantum,
    )


# -- the temporal layer -------------------------------------------------------

@pytest.mark.parametrize("recency", [0.0, 0.05])
def test_temporal_layer_matches_host_masked_oracle(recency):
    rng = np.random.default_rng(1)
    B, k = 48, 4
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    bd, tiles, tt = tg.temporal_graph()
    seeds = rng.integers(0, N_NODES, B)
    valid = np.ones(B, bool)
    valid[-3:] = False  # invalid lanes draw nothing on both sides
    tvals = rng.uniform(0.0, 60.0, B).astype(np.float32)
    key = jax.random.key(7)
    nb, vl = tiled_temporal_sample_layer(
        bd, tiles, tt, jnp.asarray(seeds), jnp.asarray(valid), k, key,
        jnp.asarray(tvals), max_deg=MAXD, recency=recency,
    )
    onb, ovl = host_masked_oracle(
        TOPO.indptr, TOPO.indices, BASE_TS, seeds, valid, k, key, tvals,
        max_deg=MAXD, recency=recency,
    )
    assert np.array_equal(np.asarray(vl), ovl)
    assert np.array_equal(np.asarray(nb)[np.asarray(vl)], onb[ovl])


def test_temporal_draws_respect_query_time():
    # every drawn edge of seed b must have some (seed, nbr) edge with
    # ts <= t[b] — checked against the raw CSR timestamps
    rng = np.random.default_rng(2)
    B = 32
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    bd, tiles, tt = tg.temporal_graph()
    seeds = rng.integers(0, N_NODES, B)
    tvals = rng.uniform(0.0, 30.0, B).astype(np.float32)
    nb, vl = tiled_temporal_sample_layer(
        bd, tiles, tt, jnp.asarray(seeds), jnp.ones((B,), bool), 6,
        jax.random.key(3), jnp.asarray(tvals), max_deg=MAXD, recency=0.0,
    )
    indptr, indices = np.asarray(TOPO.indptr), np.asarray(TOPO.indices)
    nb, vl = np.asarray(nb), np.asarray(vl)
    for b in range(B):
        node = int(seeds[b])
        lo, hi = indptr[node], indptr[node + 1]
        ok_nbrs = set(indices[lo:hi][BASE_TS[lo:hi] <= tvals[b]].tolist())
        for x in nb[b][vl[b]]:
            assert int(x) in ok_nbrs


@pytest.mark.parametrize("recency", [0.0, 0.05])
def test_t_inf_bit_equal_weighted_layer(recency):
    # the frozen-graph degeneration: temporal at t=inf IS the weighted
    # sampler over temporal_edge_weights(ttiles), bit for bit
    rng = np.random.default_rng(3)
    B, k = 40, 5
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    bd, tiles, tt = tg.temporal_graph()
    seeds = jnp.asarray(rng.integers(0, N_NODES, B))
    valid = jnp.ones((B,), bool)
    key = jax.random.key(9)
    nb_t, vl_t = tiled_temporal_sample_layer(
        bd, tiles, tt, seeds, valid, k, key,
        jnp.full((B,), np.inf, jnp.float32), max_deg=MAXD, recency=recency,
    )
    nb_w, vl_w = tiled_weighted_sample_layer(
        bd, tiles, tg.recency_wtiles(recency), seeds, valid, k, key,
        max_deg=MAXD,
    )
    assert np.array_equal(np.asarray(vl_t), np.asarray(vl_w))
    assert np.array_equal(
        np.asarray(nb_t)[np.asarray(vl_t)], np.asarray(nb_w)[np.asarray(vl_w)]
    )


def test_temporal_layer_deterministic_same_key():
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    bd, tiles, tt = tg.temporal_graph()
    seeds = jnp.asarray(np.arange(16, dtype=np.int64))
    t = jnp.full((16,), 25.0, jnp.float32)
    a = tiled_temporal_sample_layer(
        bd, tiles, tt, seeds, jnp.ones((16,), bool), 4, jax.random.key(1),
        t, max_deg=MAXD,
    )
    b = tiled_temporal_sample_layer(
        bd, tiles, tt, seeds, jnp.ones((16,), bool), 4, jax.random.key(1),
        t, max_deg=MAXD,
    )
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_per_seed_t_lineage_in_multihop():
    # row draws depend only on the row's own (seed, t): seed A's lineage
    # in a mixed-t batch is bit-equal to the same batch with B's t
    # swapped — per-request temporal correctness at depth
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    g = tg.temporal_graph()
    seeds = jnp.asarray(np.asarray([3, 7], np.int64))
    key = jax.random.key(4)
    ds_mixed = temporal_sample_dense(
        g, key, seeds, jnp.asarray([10.0, 45.0], jnp.float32), tuple(SIZES),
        recency=0.0, max_deg=MAXD,
    )
    ds_a = temporal_sample_dense(
        g, key, seeds, jnp.asarray([10.0, 999.0], jnp.float32), tuple(SIZES),
        recency=0.0, max_deg=MAXD,
    )
    # hop-1 block: neighbor (i, j) of seed i sits at 2 + j*2 + i; seed 0
    # (t=10 in both runs) must draw identically, per hop
    k1 = SIZES[0]
    n_mixed = np.asarray(ds_mixed.n_id)
    n_a = np.asarray(ds_a.n_id)
    hop1_mask_m = np.asarray(ds_mixed.adjs[-1].mask)
    hop1_mask_a = np.asarray(ds_a.adjs[-1].mask)
    assert np.array_equal(hop1_mask_m[0], hop1_mask_a[0])
    for j in range(k1):
        pos = 2 + j * 2 + 0
        if hop1_mask_m[0, j]:
            assert n_mixed[pos] == n_a[pos]


def test_temporal_sample_dense_replayable():
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    g = tg.temporal_graph()
    seeds = jnp.asarray(np.arange(6, dtype=np.int64))
    t = jnp.asarray(np.linspace(5, 45, 6), jnp.float32)
    a = temporal_sample_dense(g, jax.random.key(2), seeds, t, tuple(SIZES),
                              recency=0.01, max_deg=MAXD)
    b = temporal_sample_dense(g, jax.random.key(2), seeds, t, tuple(SIZES),
                              recency=0.01, max_deg=MAXD)
    assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
    for aa, bb in zip(a.adjs, b.adjs):
        assert np.array_equal(np.asarray(aa.mask), np.asarray(bb.mask))


# -- streaming timestamps -----------------------------------------------------

def test_streaming_append_visibility_at_ts_boundary():
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.5, edge_ts=BASE_TS)
    u, v, ets = 3, 177, 80.0
    d = GraphDelta()
    d.add_edges([u], [v], ts=[ets])
    stream.apply(d)
    deg = stream.degree(u)
    bd, tiles, tt = stream.temporal_graph()
    for tq, want in ((ets - 1e-3, False), (ets + 1e-3, True)):
        nb, vl = tiled_temporal_sample_layer(
            bd, tiles, tt, jnp.asarray([u]), jnp.ones((1,), bool), deg,
            jax.random.key(5), jnp.asarray([tq], jnp.float32), max_deg=MAXD,
        )
        drawn = set(np.asarray(nb)[0][np.asarray(vl)[0]].tolist())
        assert (v in drawn) == want


def test_streaming_spill_preserves_ts():
    # enough appends to one node to force a tile spill; draws from the
    # stream then bit-match a fresh TemporalTiledGraph over the
    # materialized (topo, ts)
    stream = StreamingTiledGraph(TOPO, reserve_frac=2.0, edge_ts=BASE_TS)
    u = 9
    rng = np.random.default_rng(6)
    n_add = 200  # > LANE: guarantees at least one relocation
    d = GraphDelta()
    d.add_edges(np.full(n_add, u), rng.integers(0, N_NODES, n_add),
                ts=np.linspace(60, 90, n_add))
    s = stream.apply(d)
    assert s["tile_spills"] >= 1
    topo2, ts2 = stream.adj.to_temporal()
    tg2 = TemporalTiledGraph(topo2, ts2, id_dtype=stream.tiles.dtype)
    g_s, g_r = stream.temporal_graph(), tg2.temporal_graph()
    seeds = jnp.asarray(rng.integers(0, N_NODES, 32))
    key = jax.random.key(8)
    t = jnp.asarray(rng.uniform(0, 100, 32), jnp.float32)
    for tq in (t, jnp.full((32,), 75.0, jnp.float32)):
        a = tiled_temporal_sample_layer(
            g_s[0], g_s[1], g_s[2], seeds, jnp.ones((32,), bool), 5, key,
            tq, max_deg=MAXD,
        )
        # the rebuilt graph has a DIFFERENT tile base map; draws must
        # still be position-identical because both read the same
        # per-node edge order
        b = tiled_temporal_sample_layer(
            g_r[0], g_r[1], g_r[2], seeds, jnp.ones((32,), bool), 5, key,
            tq, max_deg=MAXD,
        )
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert np.array_equal(
            np.asarray(a[0])[np.asarray(a[1])],
            np.asarray(b[0])[np.asarray(b[1])],
        )


def test_ts_arity_contracts():
    d = GraphDelta()
    d.add_edges([1], [2], ts=[3.0])
    with pytest.raises(ValueError):
        d.add_edges([3], [4])  # mixed ts-ness in one buffer
    with pytest.raises(ValueError):
        GraphDelta(src=[1], dst=[2], ts=[1.0, 2.0])  # arity
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.2, edge_ts=BASE_TS)
    with pytest.raises(ValueError):
        stream.apply(GraphDelta(src=[1], dst=[2]))  # temporal needs ts
    plain = StreamingTiledGraph(TOPO, reserve_frac=0.2)
    with pytest.raises(ValueError):
        plain.apply(d)  # ts into a non-temporal stream


def test_install_rows_with_ts():
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.5, edge_ts=BASE_TS)
    # find a degree-0 row or make the install target via a fresh topo
    deg = np.diff(np.asarray(TOPO.indptr))
    zero = np.nonzero(deg == 0)[0]
    if zero.size == 0:
        pytest.skip("random graph has no degree-0 node")
    node = int(zero[0])
    nbrs = np.asarray([1, 2, 3])
    stream.install_rows([(node, nbrs, np.asarray([70.0, 71.0, 72.0]))])
    assert stream.degree(node) == 3
    assert stream.adj.neighbors_ts(node).tolist() == [70.0, 71.0, 72.0]
    bd, tiles, tt = stream.temporal_graph()
    nb, vl = tiled_temporal_sample_layer(
        bd, tiles, tt, jnp.asarray([node]), jnp.ones((1,), bool), 3,
        jax.random.key(1), jnp.asarray([71.5], jnp.float32), max_deg=MAXD,
    )
    assert set(np.asarray(nb)[0][np.asarray(vl)[0]].tolist()) == {1, 2}


# -- the temporal serve engine ------------------------------------------------

@pytest.mark.parametrize("mif", [1, 2])
def test_temporal_engine_replay_parity(setup, mif):
    model, params, feat = setup
    eng = make_engine(setup, max_in_flight=mif)
    eng.warmup()
    # the fused temporal program: (params, key0, call, seeds, ..., t), the
    # seal hands it a call index and the replay's twin draws the same keys
    assert eng._programs is not None and eng._programs.sealed
    rng = np.random.default_rng(13)
    nodes = rng.integers(0, N_NODES, 24)
    tq = rng.uniform(0, 60, 24)
    rows = eng.predict(nodes, t=tq, timeout=60)
    assert eng._sampler._call == len(eng.dispatch_log) > 0
    oracle = replay_temporal_log(
        eng.dispatch_log, model, params, make_temporal_sampler(), feat
    )
    for node, t, row in zip(nodes, tq, rows):
        k = (int(node), float(np.float32(quantize_t(t, 4.0))))
        assert any(np.array_equal(row, c) for c in oracle.get(k, [])), k


def test_temporal_program_folds_the_key_of_the_call_index(setup):
    # one signature for the plain and the temporal program: the call index
    # as an int, then seeds, then the padded query times
    from quiver_tpu.inference import BucketPrograms, _cached_apply, forward_logits

    model, params, feat = setup
    programs = BucketPrograms(model, make_temporal_sampler(), feat)
    seeds = np.arange(8, dtype=np.int64) * 7
    tv = np.linspace(5.0, 60.0, 8).astype(np.float32)
    apply = _cached_apply(model)
    for call in (3, 0):
        twin = make_temporal_sampler()
        twin._call = call
        ds = twin.sample_dense(seeds, t=tv)
        want = np.asarray(forward_logits(apply, params, feat, ds))
        got = np.asarray(programs(8, params, call, seeds, tv))
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    with pytest.raises(TypeError, match="extra"):
        programs(8, params, 0, seeds)


def test_composite_cache_keys_hit_miss_and_params_invalidate(setup):
    # satellite: EmbeddingCache semantics under (node, t_bucket,
    # params_version) keys
    model, params, feat = setup
    eng = make_engine(setup, t_quantum=10.0)
    eng.warmup()
    r1 = eng.predict([7], t=12.0)[0]   # bucket 10.0: computed
    hits0 = eng.stats.cache.hits
    r2 = eng.predict([7], t=17.0)[0]   # same bucket: cache hit
    assert eng.stats.cache.hits == hits0 + 1
    assert np.array_equal(r1, r2)
    d0 = eng.stats.dispatches
    eng.predict([7], t=23.0)           # bucket 20.0: a NEW computation
    assert eng.stats.dispatches == d0 + 1
    assert eng.cache.entry_version((7, 10.0)) == 0
    assert eng.cache.entry_version((7, 20.0)) == 0
    eng.update_params(params)          # version bump drops every entry
    assert eng.cache.entry_version((7, 10.0)) is None
    d1 = eng.stats.dispatches
    eng.predict([7], t=12.0)
    assert eng.stats.dispatches == d1 + 1  # recomputed under v1


def test_update_graph_invalidates_all_t_entries_of_affected_seeds(setup):
    # satellite: invalidate-on-update_graph drops ONLY the
    # closure-touched (node, t) entries — every t of an affected node,
    # no t of an unaffected one
    model, params, feat = setup
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.5, edge_ts=BASE_TS)
    eng = make_engine(setup, source=stream, t_quantum=10.0)
    eng.warmup()
    src = 3
    affected = set(
        int(x) for x in stream.affected_seeds([src], len(SIZES) - 1)
    )
    far = [x for x in range(N_NODES) if x not in affected]
    probe_far = far[0]
    eng.predict([src, src, probe_far], t=[12.0, 23.0, 12.0])
    assert eng.cache.entry_version((src, 10.0)) == 0
    assert eng.cache.entry_version((src, 20.0)) == 0
    assert eng.cache.entry_version((probe_far, 10.0)) == 0
    eng.stage_edges([src], [far[1]], ts=[60.0])
    summary = eng.update_graph()
    assert summary["cache_invalidated"] >= 2
    assert eng.cache.entry_version((src, 10.0)) is None
    assert eng.cache.entry_version((src, 20.0)) is None
    assert eng.cache.entry_version((probe_far, 10.0)) == 0


def test_coalescing_same_t_bucket_only(setup):
    eng = make_engine(setup, t_quantum=10.0)
    eng.warmup()
    h1 = eng.submit(5, t=11.0)
    h2 = eng.submit(5, t=14.0)   # same bucket: coalesces
    h3 = eng.submit(5, t=27.0)   # different bucket: its own slot
    assert eng.stats.coalesced == 1
    while eng._drainable():
        eng.flush()
    assert np.array_equal(h1.result(30), h2.result(30))
    assert h3.result(30) is not None
    assert len(eng._pending) == 0


def test_binding_and_engine_validation():
    tg = TemporalTiledGraph(TOPO, BASE_TS)
    with pytest.raises(TypeError):  # dedup pipelines cannot carry t
        GraphSageSampler(TOPO, sizes=SIZES, mode="TPU",
                         seed=SEED).bind_temporal(tg)
    topo_w = CSRTopo(edge_index=EDGE_INDEX,
                     edge_weights=np.ones(EDGE_INDEX.shape[1], np.float32))
    with pytest.raises(TypeError):  # weighted samplers conflict
        GraphSageSampler(topo_w, sizes=SIZES, mode="TPU", seed=SEED,
                         dedup=False, weighted=True).bind_temporal(tg)
    s = GraphSageSampler(TOPO, sizes=SIZES, mode="TPU", seed=SEED,
                         dedup=False)
    with pytest.raises(TypeError):  # a plain stream has no timestamps
        s.bind_temporal(StreamingTiledGraph(TOPO, reserve_frac=0.2))
    with pytest.raises(TypeError):  # t on a non-temporal sampler
        s.sample_dense(np.arange(4), t=1.0)
    s.bind_temporal(tg)
    with pytest.raises(TypeError):  # temporal sample needs t
        s.sample_dense(np.arange(4))


def test_plain_engine_rejects_temporal_sampler(setup):
    model, params, feat = setup
    with pytest.raises(TypeError):
        ServeEngine(model, params, make_temporal_sampler(), feat,
                    ServeConfig(max_batch=8))


def test_t_inf_engine_bit_equal_frozen_weighted(setup):
    # the serving-grain frozen-graph pin: a temporal engine (recency 0)
    # at t=inf serves BIT-IDENTICAL logits and dispatch composition to
    # the frozen weighted engine over unit weights
    model, params, feat = setup
    topo_w = CSRTopo(edge_index=EDGE_INDEX,
                     edge_weights=np.ones(EDGE_INDEX.shape[1], np.float32))
    sw = GraphSageSampler(topo_w, sizes=SIZES, mode="TPU", seed=SEED,
                          dedup=False, weighted=True, max_deg=MAXD)
    eng_w = ServeEngine(
        model, params, sw, feat,
        ServeConfig(max_batch=8, buckets=(4, 8), max_delay_ms=1e9,
                    record_dispatches=True),
    )
    eng_w.warmup()
    eng_t = make_engine(setup, recency=0.0, t_quantum=0.0)
    eng_t.warmup()
    nodes = np.random.default_rng(17).integers(0, N_NODES, 20)
    rows_w = eng_w.predict(nodes, timeout=60)
    rows_t = eng_t.predict(nodes, t=np.inf, timeout=60)
    assert np.array_equal(rows_w, rows_t)
    assert len(eng_w.dispatch_log) == len(eng_t.dispatch_log)
    for (pw, nw), (pt, nt, _tv) in zip(eng_w.dispatch_log,
                                       eng_t.dispatch_log):
        assert nw == nt and np.array_equal(pw, pt)


def test_frozen_equals_empty_delta_commits(setup):
    model, params, feat = setup
    eng_f = make_engine(setup)
    eng_f.warmup()
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.3, edge_ts=BASE_TS)
    eng_s = make_engine(setup, source=stream)
    eng_s.warmup()
    rng = np.random.default_rng(19)
    nodes = rng.integers(0, N_NODES, 18)
    tq = rng.uniform(0, 50, 18)
    rows_f, rows_s = [], []
    for i, (nd, t) in enumerate(zip(nodes, tq)):
        if i % 6 == 0:
            s = eng_s.update_graph(GraphDelta())
            assert s["edges"] == 0 and eng_s.graph_version == 0
        rows_f.append(eng_f.predict([nd], t=t)[0])
        rows_s.append(eng_s.predict([nd], t=t)[0])
    assert all(np.array_equal(a, b) for a, b in zip(rows_f, rows_s))
    for (pa, na, ta), (pb, nb, tb) in zip(eng_f.dispatch_log,
                                          eng_s.dispatch_log):
        assert na == nb and np.array_equal(pa, pb)
        assert np.array_equal(ta, tb)


# -- link prediction ----------------------------------------------------------

def test_submit_pair_coalesces_shared_endpoints(setup):
    eng = make_engine(setup, t_quantum=10.0)
    eng.warmup()
    p1 = eng.submit_pair(2, 3, t=15.0)
    p2 = eng.submit_pair(2, 4, t=12.0)  # endpoint 2 coalesces (bucket 10)
    assert eng.stats.requests == 4
    assert eng.stats.coalesced == 1
    while not (p1.done() and p2.done()) and eng._drainable():
        eng.flush()
    s1, s2 = p1.result(30), p2.result(30)
    assert 0.0 <= s1 <= 1.0 and 0.0 <= s2 <= 1.0
    # score is a pure function of the endpoint rows
    hu, hv = p1.rows()
    assert np.float32(eng.pair_head.score(hu[None], hv[None])[0]) == \
        np.float32(s1)


def test_pair_head_modes_deterministic():
    rng = np.random.default_rng(23)
    hu = rng.standard_normal((9, 5)).astype(np.float32)
    hv = rng.standard_normal((9, 5)).astype(np.float32)
    dot = PairHead("dot")
    assert np.array_equal(dot.score(hu, hv), dot.score(hu, hv))
    expect = 1.0 / (1.0 + np.exp(-(hu * hv).sum(1)))
    assert np.allclose(dot.score(hu, hv), expect, atol=1e-6)
    m1 = PairHead("mlp", dim=5, seed=4)
    m2 = PairHead("mlp", dim=5, seed=4)
    m3 = PairHead("mlp", dim=5, seed=9)
    assert np.array_equal(m1.score(hu, hv), m2.score(hu, hv))
    assert not np.array_equal(m1.score(hu, hv), m3.score(hu, hv))
    with pytest.raises(ValueError):
        PairHead("mlp")  # needs dim
    with pytest.raises(ValueError):
        PairHead("cosine")


def test_linkpredictor_wrapper_on_plain_engine(setup):
    model, params, feat = setup
    s = GraphSageSampler(TOPO, sizes=SIZES, mode="TPU", seed=SEED)
    eng = ServeEngine(model, params, s, feat,
                      ServeConfig(max_batch=8, buckets=(4, 8),
                                  max_delay_ms=1e9))
    eng.warmup()
    lp = LinkPredictor(eng)
    scores = lp.predict_pairs([[1, 2], [3, 4]])
    assert scores.shape == (2,)
    with pytest.raises(TypeError):
        lp.submit_pair(1, 2, t=5.0)  # plain engines take no query time


# -- the routed temporal engine ----------------------------------------------

def make_dist(setup, hosts, exchange="host", t_quantum=4.0):
    model, params, feat = setup
    return TemporalDistServeEngine.build(
        model, params, TOPO, BASE_TS, feat, SIZES, hosts=hosts,
        config=DistServeConfig(
            hosts=hosts, max_batch=8, max_delay_ms=1e9, exchange=exchange,
            record_dispatches=True,
            shard_config=ServeConfig(max_batch=8, buckets=(4, 8),
                                     max_delay_ms=1e9,
                                     record_dispatches=True),
        ),
        sampler_seed=SEED, recency=0.02, max_deg=MAXD, t_quantum=t_quantum,
    )


def test_temporal_hosts1_bit_equal_single_engine(setup):
    model, params, feat = setup
    dist = make_dist(setup, hosts=1)
    dist.warmup()
    single = make_engine(setup)
    single.warmup()
    rng = np.random.default_rng(29)
    nodes = rng.integers(0, N_NODES, 20)
    tq = rng.uniform(0, 55, 20)
    rows_d = dist.predict(nodes, t=tq, timeout=60)
    rows_s = single.predict(nodes, t=tq, timeout=60)
    assert np.array_equal(rows_d, rows_s)
    own = dist.engines[0]
    assert len(own.dispatch_log) == len(single.dispatch_log)
    for (pa, na, ta), (pb, nb, tb) in zip(own.dispatch_log,
                                          single.dispatch_log):
        assert na == nb and np.array_equal(pa, pb)
        assert np.array_equal(ta, tb)


@pytest.mark.parametrize("exchange", ["host", "collective"])
def test_temporal_hosts2_fleet_oracle_parity(setup, exchange):
    model, params, feat = setup
    dist = make_dist(setup, hosts=2, exchange=exchange)
    dist.warmup()
    rng = np.random.default_rng(31)
    nodes = rng.integers(0, N_NODES, 24)
    tq = rng.uniform(0, 55, 24)
    rows = dist.predict(nodes, t=tq, timeout=120)
    oracle = replay_temporal_fleet_oracle(
        dist, model, params, make_temporal_sampler, feat
    )
    for node, t, row in zip(nodes, tq, rows):
        k = (int(node), float(np.float32(quantize_t(t, 4.0))))
        assert any(np.array_equal(row, c) for c in oracle.get(k, [])), k
    # a split-owner pair goes through the exchange as two sub-batches
    u = int(np.nonzero(dist.global2host == 0)[0][0])
    v = int(np.nonzero(dist.global2host == 1)[0][0])
    pr = dist.submit_pair(u, v, t=40.0)
    while not pr.done() and dist._drainable():
        dist.flush()
    assert 0.0 <= pr.result(60) <= 1.0
    hu, hv = pr.rows()
    for node, row in ((u, hu), (v, hv)):
        k = (node, float(np.float32(quantize_t(40.0, 4.0))))
        oracle = replay_temporal_fleet_oracle(
            dist, model, params, make_temporal_sampler, feat
        )
        assert any(np.array_equal(row, c) for c in oracle.get(k, [])), k


def test_temporal_dist_rejects_fleet_policy_knobs(setup):
    model, params, feat = setup
    with pytest.raises(ValueError, match="unsupported"):
        TemporalDistServeEngine(
            {}, np.zeros(4, np.int32), 5,
            config=DistServeConfig(hosts=1, replicate_top_k=8),
        )
    with pytest.raises(ValueError, match="unsupported"):
        TemporalDistServeEngine(
            {}, np.zeros(4, np.int32), 5,
            config=DistServeConfig(hosts=1, streaming=True),
        )


# -- traces, gauges, pricing --------------------------------------------------

def test_temporal_trace_deterministic_and_time_ordered():
    a = temporal_trace(100, 120, seed=3, qps=500.0, t0=10.0, edge_every=20)
    b = temporal_trace(100, 120, seed=3, qps=500.0, t0=10.0, edge_every=20)
    for fa, fb in zip(a, b):
        assert np.array_equal(fa, fb)
    assert (np.diff(a.t_query) > 0).all()
    assert a.t_query[0] > 10.0
    # every appended edge's ts sits strictly between its neighboring
    # query times: invisible to every earlier query, visible after
    for j in range(a.n_events):
        p = int(a.edge_pos[j])
        assert (a.edge_ts[j] > a.t_query[p - 1]).all()
        assert (a.edge_ts[j] < a.t_query[p]).all()
    c = temporal_trace(100, 120, seed=4, qps=500.0, t0=10.0, edge_every=20)
    assert not np.array_equal(a.requests, c.requests)


def test_lp_trace_deterministic_and_positives_are_edges():
    a = lp_trace(TOPO, 80, seed=7, pos_frac=0.6)
    b = lp_trace(TOPO, 80, seed=7, pos_frac=0.6)
    for fa, fb in zip(a, b):
        assert np.array_equal(fa, fb)
    indptr, indices = np.asarray(TOPO.indptr), np.asarray(TOPO.indices)
    n_pos = 0
    for u, v, lab in zip(a.u, a.v, a.label):
        if lab == 1:
            assert v in indices[indptr[u]:indptr[u + 1]]
            n_pos += 1
        else:
            assert u != v
    assert 0 < n_pos < 80


def test_stream_reserve_gauges_on_both_engines(setup):
    model, params, feat = setup
    stream = StreamingTiledGraph(TOPO, reserve_frac=0.5, edge_ts=BASE_TS)
    eng = make_engine(setup, source=stream)
    text = eng.register_metrics().to_prometheus()
    assert "quiver_serve_stream_reserve_free" in text
    assert "quiver_serve_stream_reserve_projected_commits" in text
    # a frozen engine has no stream: no reserve family registered
    text_f = make_engine(setup).register_metrics().to_prometheus()
    assert "stream_reserve" not in text_f
    # the router labels per-owner streams by host (plain streaming
    # fleet — the round-17 build path)
    from quiver_tpu.serve import DistServeEngine as PlainDist

    dist = PlainDist.build(
        model, params, TOPO, feat, SIZES, hosts=2,
        config=DistServeConfig(hosts=2, max_batch=8, max_delay_ms=1e9,
                               exchange="host", streaming=True),
        sampler_seed=SEED,
    )
    rtext = dist.register_metrics().to_prometheus()
    assert 'quiver_router_stream_reserve_free{host="0"}' in rtext
    assert 'quiver_router_stream_reserve_free{host="1"}' in rtext


def test_lp_table_pricing():
    from quiver_tpu.parallel.scaling import format_lp_markdown, lp_table

    rows = lp_table(2e-3, 64, head_s_per_pair=0.0,
                    buckets=(32,), hit_rates=(0.0, 0.5))
    by_hit = {r.hit_rate: r for r in rows}
    # zero head cost: a pair is exactly two node requests
    assert by_hit[0.0].qps_ratio == pytest.approx(0.5)
    assert by_hit[0.5].pair_qps > by_hit[0.0].pair_qps
    rows_h = lp_table(2e-3, 64, head_s_per_pair=1e-4, buckets=(32,),
                      hit_rates=(0.0,))
    assert rows_h[0].pair_qps < by_hit[0.0].pair_qps
    md = format_lp_markdown(rows)
    assert "pair/node" in md
    with pytest.raises(ValueError):
        lp_table(-1.0, 64)


def test_quantize_t_idempotent_and_exact_mode():
    assert quantize_t(17.3, 0.0) == 17.3
    assert quantize_t(math.inf, 5.0) == math.inf
    q = quantize_t(17.3, 5.0)
    assert q == 15.0
    # idempotent through float32 round-trips (the router->owner path)
    assert quantize_t(float(np.float32(q)), 5.0) == q
