"""Round-18 predictive I/O tests (ISSUE 13): flush-ahead prefetch,
training through the disk tier, and the real-disk measurement helpers.

The contract under test, per docs/api.md "Tiered storage":

- prefetch is STRICTLY OBSERVE-ONLY ON BITS: logits AND dispatch logs
  are identical with prefetch on vs off (pinned at max_in_flight 1/2
  and hosts 1/2), placement never moves, no sampler key is consumed;
- a staged row is byte-identical to an unstaged read (same read path,
  earlier), and a FAILED staged read surfaces the same error the
  prefetch-off run would (error parity);
- the fences that drain in-flight flushes (`update_params`,
  `apply_placement`, `update_graph`, `stop`) also cancel staged
  prefetch rows — no deadlock, no leaked pool workers, every future
  observed;
- a disk-spanning training epoch completes with loss BIT-PARITY against
  the all-DRAM epoch (static 4-tier and adaptive placements), and a
  mid-epoch disk failure surfaces via the r7 error contract (no hang);
- `attribute_gather_tiers` reports a prefetch-staged DRAM hit as
  `disk_prefetched`, never as `disk`;
- O_DIRECT / fadvise(DONTNEED) helpers: direct reads are byte-equal to
  the memmap path where the filesystem allows them, and both helpers
  answer honestly (bool, never raise) where it does not.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from conftest import make_random_graph

from quiver_tpu import CSRTopo, Feature
from quiver_tpu.models import GraphSAGE
from quiver_tpu.pipeline import (
    AsyncReadPool,
    TieredFeaturePipeline,
    TrainPipeline,
    make_tiered_train_step,
)
from quiver_tpu.pyg.sage_sampler import GraphSageSampler
from quiver_tpu.serve import (
    DistServeConfig,
    DistServeEngine,
    ServeConfig,
    ServeEngine,
    zipfian_trace,
)
from quiver_tpu.stream import (
    GraphDelta,
    StreamCapacityError,
    StreamingTiledGraph,
)
from quiver_tpu.tiers import (
    DiskShard,
    PrefetchBuffer,
    drop_page_cache,
    expected_closure,
    o_direct_supported,
)
from quiver_tpu.trace import HitRateCounter, WorkloadConfig

N_NODES = 200
DIM = 12
SIZES = [4, 4]
SAMPLER_SEED = 3
ROW = DIM * 4


def make_topo():
    return CSRTopo(edge_index=make_random_graph(N_NODES, 1500, seed=0))


def make_sampler(stream=None):
    s = GraphSageSampler(make_topo(), sizes=SIZES, mode="TPU",
                         seed=SAMPLER_SEED)
    if stream is not None:
        s.bind_stream(stream)
    return s


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    model = GraphSAGE(hidden_dim=16, out_dim=5, num_layers=2, dropout=0.0)
    sampler = make_sampler()
    ds0 = sampler.sample_dense(np.arange(8, dtype=np.int64))
    x0 = jnp.zeros((ds0.n_id.shape[0], DIM), jnp.float32)
    params = model.init(jax.random.key(0), x0, ds0.adjs)
    return model, params, feat


def tiered_feature(feat, tmpdir, name, adaptive=True, hbm_rows=24,
                   host_rows=48, workers=2):
    f = Feature(
        rank=0,
        device_cache_size=hbm_rows * ROW,
        host_memory_budget=host_rows * ROW,
        disk_path=os.path.join(str(tmpdir), name),
        adaptive_tiers=adaptive,
        read_pool=AsyncReadPool(workers, chunk_rows=64),
    )
    f.from_cpu_tensor(feat)
    return f


def prefetch_engine(setup, tmpdir, name, prefetch, **cfg_kw):
    model, params, feat = setup
    f = tiered_feature(feat, tmpdir, name)
    cfg_kw.setdefault("max_batch", 16)
    cfg_kw.setdefault("record_dispatches", True)
    cfg_kw.setdefault("workload", WorkloadConfig(topk=64))
    eng = ServeEngine(model, params, make_sampler(), f,
                      ServeConfig(tier_prefetch=prefetch, **cfg_kw))
    return eng, f


# -- PrefetchBuffer ----------------------------------------------------------

def test_prefetch_buffer_issue_take_cancel_semantics(tmp_path):
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((300, DIM)).astype(np.float32)
    sh = DiskShard.create(os.path.join(str(tmp_path), "b"), rows)
    events = []
    with AsyncReadPool(2, chunk_rows=32) as pool:
        pf = PrefetchBuffer(sh.read_block, pool, max_rows=64)
        pf.listener = lambda kind, n: events.append((kind, n))
        # issue dedups against in-flight staging
        assert pf.issue(np.arange(20)) == 20
        assert pf.issue(np.arange(30)) == 10  # 0..19 already staged
        assert pf.issued == 30 and len(pf) == 30
        # staged_mask peeks without consuming
        m = pf.staged_mask(np.asarray([0, 29, 30, 250]))
        assert m.tolist() == [True, True, False, False]
        assert len(pf) == 30
        # take consumes exactly the staged subset, bytes equal the file
        ids = np.asarray([5, 250, 7, 290])
        pos, got = pf.take(ids)
        assert sorted(pos.tolist()) == [0, 2]
        for p, r in zip(pos, got):
            assert np.array_equal(r, rows[ids[p]])
        assert pf.hits == 2 and len(pf) == 28
        # max_rows bounds total staging
        assert pf.issue(np.arange(100, 300)) == 64 - 28
        assert len(pf) == 64
        # cancel drops everything staged and counts it wasted
        assert pf.cancel() == 64
        assert len(pf) == 0 and pf.wasted == 64
        assert pf.take(np.arange(10))[1] is None
        # the listener saw every hit/wasted transition
        assert ("hit", 2) in events and ("wasted", 64) in events
        st = pf.stats()
        assert st["issued"] == pf.issued and st["staged"] == 0


def test_prefetch_buffer_failed_read_error_parity():
    """A staged read that FAILED is not a hit: take() drops it so the
    caller re-reads directly and surfaces the prefetch-off error."""
    def flaky(ids):
        if (ids >= 8).any():
            raise OSError("injected read failure")
        return np.ones((ids.shape[0], 4), np.float32)

    with AsyncReadPool(2, chunk_rows=4) as pool:
        pf = PrefetchBuffer(flaky, pool, max_rows=64)
        pf.issue(np.arange(12))         # chunks [0..3] [4..7] [8..11]
        pos, got = pf.take(np.arange(12))
        assert sorted(pos.tolist()) == list(range(8))  # failed chunk absent
        assert np.all(got == 1.0)
        assert pf.errors == 4 and len(pf) == 0  # per ROW, like hits
        # the direct retry the caller now makes raises the SAME error
        with pytest.raises(OSError, match="injected read failure"):
            pool.gather(flaky, np.arange(8, 12))


def test_prefetch_buffer_requires_pool():
    with pytest.raises(ValueError, match="AsyncReadPool"):
        PrefetchBuffer(lambda ids: ids, None)


# -- expected_closure --------------------------------------------------------

def test_expected_closure_frozen_graph_and_truncation():
    sampler = make_sampler()
    topo = sampler.csr_topo
    indptr, indices = np.asarray(topo.indptr), np.asarray(topo.indices)
    seeds = np.asarray([3, 77, 3])
    out = expected_closure(sampler, seeds, hops=2)
    # reference BFS over the frozen CSR
    mask = np.zeros(N_NODES, bool)
    mask[[3, 77]] = True
    frontier = np.asarray([3, 77])
    for _ in range(2):
        nxt = np.unique(np.concatenate(
            [indices[indptr[u]:indptr[u + 1]] for u in frontier]
            or [np.array([], np.int64)]))
        frontier = nxt[~mask[nxt]]
        mask[frontier] = True
    assert set(out.tolist()) == set(np.nonzero(mask)[0].tolist())
    # BFS order: truncation keeps the nearest rows — seeds always first
    cut = expected_closure(sampler, seeds, hops=2, max_nodes=5)
    assert cut.shape[0] <= 5 + max(0, len(np.unique(seeds)) - 5)
    assert set(np.unique(seeds)) <= set(cut.tolist()) | set(out.tolist())
    assert cut[0] in (3, 77) and cut.shape[0] < out.shape[0]
    # out-of-range seeds drop instead of raising (pad lanes reach here)
    assert expected_closure(sampler, np.asarray([-1, N_NODES + 5]), 2).size == 0


def test_expected_closure_sees_committed_stream_edges():
    """A stream-bound sampler's closure walks the CURRENT adjacency:
    a committed delta edge extends the prefetch set immediately."""
    stream = StreamingTiledGraph(make_topo(), reserve_frac=0.5)
    sampler = make_sampler(stream=stream)
    u = int(np.argmin(make_topo().degree))
    before = set(expected_closure(sampler, [u], hops=1).tolist())
    fresh = [v for v in range(N_NODES) if v not in before][0]
    d = GraphDelta()
    d.add_edge(u, fresh)
    stream.apply(d)
    after = set(expected_closure(sampler, [u], hops=1).tolist())
    assert fresh not in before and fresh in after


# -- serve-path bit-neutrality ----------------------------------------------

@pytest.mark.parametrize("mif", [1, 2])
def test_serve_prefetch_bit_parity(setup, tmp_path, mif):
    """ACCEPTANCE PIN: prefetch on vs off serves bit-identical logits
    and dispatch logs at max_in_flight 1 and 2 — and actually hits."""
    trace = zipfian_trace(N_NODES, 160, alpha=1.3, seed=11)
    eng_on, f_on = prefetch_engine(setup, tmp_path, f"on{mif}.npy", True,
                                   max_in_flight=mif, journal_events=4096)
    eng_off, _ = prefetch_engine(setup, tmp_path, f"off{mif}.npy", False,
                                 max_in_flight=mif, journal_events=4096)
    out_on = eng_on.predict(trace)
    out_off = eng_off.predict(trace)
    assert np.array_equal(out_on, out_off)
    assert len(eng_on.dispatch_log) == len(eng_off.dispatch_log)
    for (p1, n1), (p2, n2) in zip(eng_on.dispatch_log, eng_off.dispatch_log):
        assert n1 == n2 and np.array_equal(p1, p2)
    # the ledger moved: reads were issued AND consumed
    assert eng_on.stats.tier_prefetch_issued > 0
    assert eng_on.stats.tier_prefetch_hit > 0
    assert eng_off.stats.tier_prefetch_issued == 0
    # placement untouched: prefetch stages reads, never moves rows
    assert eng_on.stats.tier_promoted == 0 and eng_on.placement_version == 0
    # journal kinds present on the prefetching engine only
    kinds = {e[1] for e in eng_on.journal.snapshot()}
    assert {"prefetch_issue", "prefetch_hit"} <= kinds
    snap = eng_on.stats.snapshot()
    assert snap["tier_prefetch_hit"] == eng_on.stats.tier_prefetch_hit
    eng_on.stop()
    eng_off.stop()


def test_submit_vs_assemble_prefetch_parity(setup, tmp_path):
    """`tier_prefetch_at` moves WHEN reads are issued, never what is
    served: "submit" (default — the bucket-filling submit issues before
    flush) and "assemble" serve bit-identical logits + dispatch logs,
    both actually hit staging, and a bogus spelling raises."""
    trace = zipfian_trace(N_NODES, 120, alpha=1.3, seed=13)
    eng_s, _ = prefetch_engine(setup, tmp_path, "at_s.npy", True)
    eng_a, _ = prefetch_engine(setup, tmp_path, "at_a.npy", True,
                               tier_prefetch_at="assemble")
    assert eng_s.config.tier_prefetch_at == "submit"
    out_s, out_a = eng_s.predict(trace), eng_a.predict(trace)
    assert np.array_equal(out_s, out_a)
    assert len(eng_s.dispatch_log) == len(eng_a.dispatch_log)
    for (p1, n1), (p2, n2) in zip(eng_s.dispatch_log, eng_a.dispatch_log):
        assert n1 == n2 and np.array_equal(p1, p2)
    for eng in (eng_s, eng_a):
        assert eng.stats.tier_prefetch_issued > 0
        assert eng.stats.tier_prefetch_hit > 0
        eng.stop()
    with pytest.raises(ValueError, match="tier_prefetch_at"):
        prefetch_engine(setup, tmp_path, "at_x.npy", True,
                        tier_prefetch_at="sometime")


@pytest.mark.parametrize("hosts", [1, 2])
def test_dist_prefetch_bit_parity(setup, tmp_path, hosts):
    """ACCEPTANCE PIN at hosts 1 and 2: the router's per-owner prefetch
    off the routed sub-batches changes no served bit and no owner
    dispatch-log entry."""
    model, params, feat = setup
    topo = make_topo()

    def build(name, pf):
        cfg = DistServeConfig(
            hosts=hosts, max_batch=16, exchange="host",
            feature_residency="exchange", record_dispatches=True,
            workload=WorkloadConfig(topk=64), tier_prefetch=pf,
        )
        fkw = dict(
            device_cache_size=12 * ROW, host_memory_budget=24 * ROW,
            disk_path=os.path.join(str(tmp_path), name + ".h{host}.npy"),
            adaptive_tiers=True, disk_read_workers=2,
        )
        return DistServeEngine.build(
            model, params, topo, feat, sizes=SIZES, hosts=hosts, config=cfg,
            sampler_seed=SAMPLER_SEED, feature_kw=fkw, out_dim=5,
        )

    trace = zipfian_trace(N_NODES, 160, alpha=1.3, seed=17)
    d_on = build(f"don{hosts}", True)
    d_off = build(f"doff{hosts}", False)
    assert np.array_equal(d_on.predict(trace), d_off.predict(trace))
    for h in range(hosts):
        l_on, l_off = d_on.engines[h].dispatch_log, d_off.engines[h].dispatch_log
        assert len(l_on) == len(l_off)
        for (p1, n1), (p2, n2) in zip(l_on, l_off):
            assert n1 == n2 and np.array_equal(p1, p2)
    assert sum(e.stats.tier_prefetch_issued
               for e in d_on.engines.values()) > 0
    assert sum(e.stats.tier_prefetch_hit for e in d_on.engines.values()) > 0
    d_on.stop()
    d_off.stop()


# -- fence cancellation ------------------------------------------------------

def thread_names():
    return sorted(t.name for t in threading.enumerate())


def test_fences_cancel_staged_prefetch_no_leaks(setup, tmp_path):
    """update_params and apply_placement (via adapt_tiers) both drop
    staged prefetch rows under their fence; thread census is unchanged
    (no leaked pool workers) and the engine keeps serving."""
    model, params, feat = setup
    # cache_entries=0 on both: update_params invalidates the fenced
    # engine's cache but not the twin's, and a cache hit skips a key
    # draw — with the cache off both second passes dispatch identically
    eng, f = prefetch_engine(setup, tmp_path, "fence.npy", True,
                             tier_promote_min=1.0, cache_entries=0)
    # the fence-free twin: serves the same trace twice with NO manual
    # staging and NO fences — my post-fence run must bit-match its
    # second run (fences are bit-neutral; only the key stream advances)
    twin, _ = prefetch_engine(setup, tmp_path, "fence_twin.npy", True,
                              tier_promote_min=1.0, cache_entries=0)
    store = f.tier_store
    trace = zipfian_trace(N_NODES, 60, alpha=1.3, seed=5)
    base = eng.predict(trace)
    assert np.array_equal(twin.predict(trace), base)
    before = thread_names()
    # stage rows nobody will gather, then fence via update_params
    assert eng.prefetch_seeds(trace[:20]) > 0
    assert len(store.prefetch) > 0
    wasted0 = eng.stats.tier_prefetch_wasted
    eng.update_params(params)
    assert len(store.prefetch) == 0
    assert eng.stats.tier_prefetch_wasted > wasted0
    # placement fence: adapt_tiers runs apply_placement underneath
    assert eng.prefetch_seeds(trace[:20]) > 0
    s = eng.adapt_tiers()
    assert s["moves"] > 0
    assert len(store.prefetch) == 0
    assert thread_names() == before
    # bits survive both fences (params unchanged, placement is
    # bit-neutral by the round-14 contract): the re-served trace equals
    # the fence-free twin's second pass bit for bit
    assert np.array_equal(eng.predict(trace), twin.predict(trace))
    eng.stop()
    twin.stop()
    assert len(store.prefetch) == 0


def test_update_graph_fence_cancels_staged_prefetch(setup, tmp_path):
    """The round-17 graph-delta fence is a prefetch consumer too: a
    commit drops staged rows (stale closure intent) without deadlock."""
    model, params, feat = setup
    f = tiered_feature(feat, tmp_path, "ug.npy")
    stream = StreamingTiledGraph(make_topo(), reserve_frac=0.5)
    eng = ServeEngine(
        model, params, make_sampler(stream=stream), f,
        ServeConfig(max_batch=8, buckets=(8,), record_dispatches=True,
                    workload=WorkloadConfig(topk=64), tier_prefetch=True),
    )
    eng.warmup()
    store = f.tier_store
    trace = zipfian_trace(N_NODES, 24, alpha=1.1, seed=9)
    eng.predict(trace)
    assert eng.prefetch_seeds(trace[:10]) > 0
    assert len(store.prefetch) > 0
    d = GraphDelta()
    d.add_edge(int(trace[0]), int((trace[0] + 7) % N_NODES))
    out = eng.update_graph(d)
    assert out["edges"] == 1 and eng.graph_version == 1
    assert len(store.prefetch) == 0
    eng.stop()


def test_stop_drain_deadline_with_inflight_prefetch(setup, tmp_path):
    """A prefetch still in flight when stop(drain=True) hits its drain
    deadline must neither deadlock nor leak workers: stop returns
    promptly, staging is cancelled, futures observed, thread census
    restored."""
    model, params, feat = setup
    eng, f = prefetch_engine(setup, tmp_path, "stop.npy", True,
                             drain_deadline_s=0.5)
    store = f.tier_store
    eng.predict(zipfian_trace(N_NODES, 24, alpha=1.1, seed=3))
    # spin the pool up to its full width first: workers spawn lazily,
    # and a late second worker is growth, not a leak
    store.backing.read_rows(np.arange(150), pool=store.read_pool)
    before = thread_names()
    # make every disk read slow so staged futures outlive the deadline
    orig = store.backing.read_block

    def slow(ids):
        time.sleep(0.2)
        return orig(ids)

    store.backing.read_block = slow
    try:
        assert eng.prefetch_seeds(np.arange(N_NODES)[::3]) > 0
        t0 = time.perf_counter()
        eng.stop(drain=True)
        assert time.perf_counter() - t0 < 5.0
        assert len(store.prefetch) == 0
    finally:
        store.backing.read_block = orig
    # pool workers still alive and serving (owned by the feature, not
    # the engine) — and no extra thread appeared
    ids = np.arange(40)
    assert np.array_equal(np.asarray(store.gather(ids)), feat[ids])
    assert thread_names() == before


# -- train-through-tiers -----------------------------------------------------

def community_setup():
    rng = np.random.default_rng(0)
    n_comm, per_comm, intra = 4, 40, 6
    n = n_comm * per_comm
    src, dst = [], []
    for u in range(n):
        cu = u // per_comm
        for v in rng.choice(per_comm, intra, replace=False) + cu * per_comm:
            src.append(u)
            dst.append(int(v))
    feat = rng.standard_normal((n, 16)).astype(np.float32)
    labels = (np.arange(n) // per_comm).astype(np.int32)
    return CSRTopo(edge_index=np.stack([np.array(src), np.array(dst)])), \
        feat, labels, n


def run_epoch(topo, feat, labels, n, f, prefetch=False, batches=8):
    sampler = GraphSageSampler(topo, sizes=[5, 5], mode="TPU", seed=1)
    model = GraphSAGE(hidden_dim=32, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(5e-3)
    pipe = TieredFeaturePipeline(f, prefetch=prefetch)
    step_fn = make_tiered_train_step(model, tx, jnp.asarray(labels),
                                     pipe.hot_table)
    rng = np.random.default_rng(0)
    seeds = [rng.integers(0, n, 32).astype(np.int64) for _ in range(batches)]
    ds0 = sampler.sample_dense(seeds[0])
    x0 = jnp.zeros((ds0.n_id.shape[0], feat.shape[1]), jnp.float32)
    params = model.init(jax.random.key(0), x0, ds0.adjs)
    tp = TrainPipeline(sampler, f, step_fn, tiered=pipe)
    _, _, losses = tp.run_epoch(seeds, params, tx.init(params),
                                jax.random.key(1))
    return np.asarray(losses), pipe


@pytest.mark.parametrize("adaptive", [False, True])
def test_train_through_disk_loss_bit_parity(tmp_path, adaptive):
    """ACCEPTANCE PIN: a disk-spanning epoch (static 4-tier AND adaptive
    placement, flush-ahead prefetch on) produces a loss curve BIT-EQUAL
    to the all-DRAM epoch, with real disk traffic and prefetch hits."""
    topo, feat, labels, n = community_setup()
    rowb = feat.shape[1] * 4
    f_dram = Feature(rank=0, device_cache_size=24 * rowb)
    f_dram.from_cpu_tensor(feat)
    l_dram, p_dram = run_epoch(topo, feat, labels, n, f_dram)
    assert p_dram.mode == "dram"

    f_disk = Feature(
        rank=0, device_cache_size=24 * rowb, host_memory_budget=48 * rowb,
        disk_path=os.path.join(str(tmp_path), f"t{int(adaptive)}.npy"),
        adaptive_tiers=adaptive, read_pool=AsyncReadPool(2, chunk_rows=32),
    )
    f_disk.from_cpu_tensor(feat)
    l_disk, pipe = run_epoch(topo, feat, labels, n, f_disk, prefetch=True)
    assert pipe.mode == ("adaptive" if adaptive else "disk")
    assert np.array_equal(l_dram, l_disk)
    assert pipe.disk_rows_seen > 0
    st = pipe.prefetch_stats
    assert st["hits"] > 0 and st["issued"] >= st["hits"]
    # prefetch OFF is bit-identical too (the staging layer is inert)
    f2 = Feature(
        rank=0, device_cache_size=24 * rowb, host_memory_budget=48 * rowb,
        disk_path=os.path.join(str(tmp_path), f"o{int(adaptive)}.npy"),
        adaptive_tiers=adaptive, read_pool=AsyncReadPool(2, chunk_rows=32),
    )
    f2.from_cpu_tensor(feat)
    l_off, _ = run_epoch(topo, feat, labels, n, f2, prefetch=False)
    assert np.array_equal(l_dram, l_off)


def test_train_mid_epoch_disk_error_contract(tmp_path):
    """ACCEPTANCE PIN: a disk read failing mid-epoch surfaces the
    ORIGINAL error promptly (r7 contract: failing chunk cancels
    siblings + re-raises, staged prefetch cancelled) — never a hang —
    and the pipeline trains a fresh epoch afterwards."""
    topo, feat, labels, n = community_setup()
    rowb = feat.shape[1] * 4
    f = Feature(
        rank=0, device_cache_size=24 * rowb, host_memory_budget=48 * rowb,
        disk_path=os.path.join(str(tmp_path), "err.npy"),
        read_pool=AsyncReadPool(2, chunk_rows=32, name="qt-err-read"),
    )
    f.from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, sizes=[5, 5], mode="TPU", seed=1)
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(5e-3)
    pipe = TieredFeaturePipeline(f, prefetch=True)
    step_fn = make_tiered_train_step(model, tx, jnp.asarray(labels),
                                     pipe.hot_table)
    rng = np.random.default_rng(0)
    seeds = [rng.integers(0, n, 32).astype(np.int64) for _ in range(8)]
    ds0 = sampler.sample_dense(seeds[0])
    x0 = jnp.zeros((ds0.n_id.shape[0], feat.shape[1]), jnp.float32)
    params = model.init(jax.random.key(0), x0, ds0.adjs)
    tp = TrainPipeline(sampler, f, step_fn, depth=2, tiered=pipe)
    # one clean warm epoch first: the read pool's workers spawn lazily
    # on first submit, so the census must be taken with them already up
    tp.run_epoch(seeds[:2], params, tx.init(params), jax.random.key(3))

    shard = f.shard_tensor.disk_shard
    orig = shard.read_block
    calls = [0]

    def failing(ids):
        calls[0] += 1
        if calls[0] > 2:
            raise OSError("disk died mid-epoch")
        return orig(ids)

    shard.read_block = failing

    def own_threads():
        """Live threads the pipeline under test started: its three stage
        pools (named in `TrainPipeline._run`) and this test's read pool.
        Not the process's whole census: an earlier test's pool workers or
        serve daemons may end at any moment (a collected executor wakes
        its workers to exit) and are none of this test's business."""
        return [t for t in threading.enumerate() if t.name.startswith(
            ("qt-sample", "qt-gather", "qt-upload", "qt-err-read"))]

    before = sorted(t.name for t in own_threads())
    assert before == ["qt-err-read_0", "qt-err-read_1"]
    try:
        t0 = time.perf_counter()
        with pytest.raises(OSError, match="disk died mid-epoch"):
            tp.run_epoch(seeds, params, tx.init(params), jax.random.key(1))
        assert time.perf_counter() - t0 < 30.0  # surfaced, not hung
    finally:
        shard.read_block = orig
    # unwind left no staged rows and no stray threads
    assert len(pipe._prefetch) == 0
    for t in own_threads():
        if t.name not in before:
            t.join(timeout=5.0)  # one still ending is given a moment to
    assert sorted(t.name for t in own_threads()) == before
    # the surviving pipeline trains a clean epoch
    _, _, losses = tp.run_epoch(seeds[:3], params, tx.init(params),
                                jax.random.key(2))
    assert len(losses) == 3 and all(np.isfinite(losses))


# -- attribution honesty -----------------------------------------------------

def test_attribute_gather_tiers_disk_prefetched(tmp_path):
    """A disk-placed row a prefetch staged in DRAM counts as
    `disk_prefetched`; unstaged disk rows stay `disk`. Static (via
    Feature.disk_staged) and adaptive (via TierStore.tier_split)."""
    rng = np.random.default_rng(2)
    feat = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    f = tiered_feature(feat, tmp_path, "attr.npy", adaptive=False)
    ctr = HitRateCounter()
    f.tier_counter = ctr
    st = f.shard_tensor
    start = st.disk_offset.start
    from quiver_tpu.tiers import PrefetchBuffer

    pf = PrefetchBuffer(st.disk_shard.read_block, f.read_pool, max_rows=64)
    f.disk_staged = pf.staged_mask
    disk_ids = np.asarray([start + 1, start + 2, start + 3, start + 9])
    pf.issue(np.asarray([1, 2, 3]))     # stage three of the four (LOCAL)
    np.asarray(f[disk_ids])
    assert ctr.tier_counts("disk_prefetched")["hits"] == 3
    assert ctr.tier_counts("disk")["hits"] == 1
    pf.cancel()

    # adaptive: TierStore.tier_split reports the same split
    fa = tiered_feature(feat, tmp_path, "attr_a.npy", adaptive=True)
    store = fa.tier_store
    store.enable_prefetch(max_rows=64)
    from quiver_tpu.tiers import TIER_DISK

    disk_res = store.placement.residents(TIER_DISK)[:6]
    store.prefetch_rows(disk_res[:4])
    split = store.tier_split(disk_res)
    assert split["disk_prefetched"] == 4 and split["disk"] == 2
    # and the Prometheus tier label set carries the new tier
    from quiver_tpu.obs import WorkloadMonitor
    from quiver_tpu.trace import MetricsRegistry

    mon = WorkloadMonitor(WorkloadConfig(topk=8))
    reg = MetricsRegistry()
    mon.register_metrics(reg, prefix="qt")
    assert 'tier="disk_prefetched"' in reg.to_prometheus()


# -- stream reserve diagnosis (satellite) ------------------------------------

def test_reserve_report_and_capacity_error_diagnosis():
    stream = StreamingTiledGraph(make_topo(), reserve_tiles=4)
    r0 = stream.reserve_report()
    assert r0["reserve_tiles"] == 4 and r0["reserve_used"] == 0
    assert r0["projected_commits_to_exhaustion"] is None  # nothing seen
    # consume some reserve: spill a node's tile by over-appending
    u = int(np.argmax(make_topo().degree))
    d = GraphDelta()
    for k in range(2):
        d.add_edge(u, (u + 1 + k) % N_NODES)
    stream.apply(d)
    r1 = stream.reserve_report()
    assert r1["commits"] == 1
    if r1["reserve_used"] > 0:
        assert r1["rows_per_commit"] > 0
        assert r1["projected_commits_to_exhaustion"] is not None
    # exhaust: the planned hard error names its own runway
    big = GraphDelta()
    hub = u
    for k in range(4 * 128 + 256):
        big.add_edge(hub, (hub + 2 + k) % N_NODES)
    with pytest.raises(StreamCapacityError) as ei:
        stream.apply(big)
    msg = str(ei.value)
    assert "reserve" in msg and "commit" in msg
    assert "reserve_frac" in msg  # remediation named


# -- real-disk helpers -------------------------------------------------------

def test_o_direct_and_drop_cache_helpers(tmp_path):
    rng = np.random.default_rng(3)
    rows = rng.standard_normal((128, DIM)).astype(np.float32)
    sh = DiskShard.create(os.path.join(str(tmp_path), "d"), rows)
    # drop_cache is best-effort bool, never raises
    assert isinstance(sh.drop_cache(), bool)
    assert drop_page_cache(os.path.join(str(tmp_path), "missing")) is False
    if not o_direct_supported(sh.path):
        with pytest.raises(OSError):
            DiskShard(sh.path, direct=True)
        pytest.skip("filesystem refuses O_DIRECT; fadvise path covered")
    dsh = DiskShard(sh.path, direct=True)
    ids = rng.integers(0, 128, 200)
    # byte parity with the memmap path, including repeats
    assert np.array_equal(dsh.read_block(ids), rows[ids])
    assert np.array_equal(dsh.read_block(ids), sh.read_block(ids))
    with AsyncReadPool(2, chunk_rows=16) as pool:
        assert np.array_equal(dsh.read_rows(ids, pool=pool), rows[ids])
    with pytest.raises(ValueError, match="corrupt placement"):
        dsh.read_block(np.asarray([128]))


# -- cost model (satellite) --------------------------------------------------

def test_tier_table_prefetch_hit_rate_column():
    from quiver_tpu.parallel.scaling import format_tier_markdown, tier_table

    kw = dict(
        mixes=[("all_hbm", 1.0, 0.0, 0.0), ("cold", 0.1, 0.2, 0.7)],
        bucket=64, dispatch_s=5e-3,
        hbm_row_s=1e-7, host_row_s=2e-6, disk_row_s=8e-5,
        feature_dim=DIM, read_workers=4,
    )
    off = tier_table(prefetch_hit_rate=0.0, **kw)
    on = tier_table(prefetch_hit_rate=0.8, **kw)
    full = tier_table(prefetch_hit_rate=1.0, **kw)
    # staged rows price at the DRAM consume: monotone cheaper with rate
    assert on[1].flush_s < off[1].flush_s
    assert full[1].flush_s < on[1].flush_s
    # a fully-staged disk mix prices its disk term AT host cost
    expect = 64 * (0.1 * 1e-7 + 0.2 * 2e-6 + 0.7 * 2e-6) + 5e-3
    assert full[1].flush_s == pytest.approx(expect)
    # the all-HBM row is indifferent to the knob
    assert on[0].flush_s == off[0].flush_s
    assert on[1].prefetch_hit_rate == 0.8
    md = format_tier_markdown(on)
    assert "pf hit" in md and "80%" in md
    with pytest.raises(ValueError, match="prefetch_hit_rate"):
        tier_table(prefetch_hit_rate=1.5, **kw)
