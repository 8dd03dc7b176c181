"""Online serving engine tests (quiver_tpu.serve).

Everything runs on the hermetic CPU mesh with tiny graphs. The contract
under test, per docs/api.md "Online serving":

- served logits are BIT-IDENTICAL to the offline `batch_logits` path on the
  same (sampler stream, dispatched batch) — verified by replaying the
  engine's dispatch log through a fresh sampler;
- coalescing is observable: N requests for overlapping seeds produce fewer
  than N dispatches, with the dedup/coalesce/cache counters accounting for
  every request;
- the embedding cache serves repeats host-side, is LRU-bounded, and is
  invalidated by `update_params` (params-versioned: stale entries are never
  served across a weight update);
- the flush policy (max_batch / max_delay_ms) is deterministic under an
  injected clock — this 1-core box pins LOGIC and counters, not wall-clock
  throughput.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_random_graph

from quiver_tpu import CSRTopo, Feature
from quiver_tpu.inference import _cached_apply, batch_logits, pad_seed_batch
from quiver_tpu.models import GraphSAGE
from quiver_tpu.pyg.sage_sampler import GraphSageSampler
from quiver_tpu.serve import (
    EmbeddingCache,
    ServeConfig,
    ServeEngine,
    ServeStats,
    default_buckets,
    poisson_arrivals,
    trace_skew_stats,
    zipfian_trace,
)

N_NODES = 200
DIM = 16
SIZES = [4, 4]
SAMPLER_SEED = 3


def make_sampler():
    """Fresh sampler with a fresh key stream — the engine consumes call
    indices 0,1,2,... so parity replays need an identically-born twin."""
    topo = CSRTopo(edge_index=make_random_graph(N_NODES, 2000, seed=0))
    return GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SAMPLER_SEED)


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


def make_engine(setup, **cfg_kw):
    model, params, feat = setup
    cfg_kw.setdefault("record_dispatches", True)
    return ServeEngine(model, params, make_sampler(), feat, ServeConfig(**cfg_kw))


def replay_oracle(setup, engine):
    """Offline `batch_logits` replay of the engine's dispatch log through a
    FRESH sampler: node_id -> logits under the unbatched eval path."""
    model, params, feat = setup
    apply = _cached_apply(model)
    ref_sampler = make_sampler()
    served = {}
    for padded, nvalid in engine.dispatch_log:
        logits = np.asarray(batch_logits(apply, params, ref_sampler, feat, padded))
        for i in range(nvalid):
            served.setdefault(int(padded[i]), logits[i])
    return served


# -- trace generator ---------------------------------------------------------

def test_zipfian_trace_seeded_and_skewed():
    a = zipfian_trace(1000, 5000, alpha=0.99, seed=7)
    b = zipfian_trace(1000, 5000, alpha=0.99, seed=7)
    assert np.array_equal(a, b)
    assert a.dtype == np.int64 and a.min() >= 0 and a.max() < 1000
    # higher alpha concentrates traffic: top-1% share must grow
    lo = trace_skew_stats(zipfian_trace(1000, 5000, alpha=0.0, seed=1))
    hi = trace_skew_stats(zipfian_trace(1000, 5000, alpha=1.1, seed=1))
    assert hi["top_share"] > lo["top_share"]
    assert hi["unique_frac"] < lo["unique_frac"]
    t = poisson_arrivals(100, qps=1000.0, seed=0)
    assert t.shape == (100,) and np.all(np.diff(t) > 0)
    with pytest.raises(ValueError):
        zipfian_trace(0, 10)


# -- embedding cache ---------------------------------------------------------

def test_embedding_cache_lru_and_versioning():
    c = EmbeddingCache(capacity=2)
    v = lambda x: np.full(3, float(x))
    assert c.get(1, 0) is None            # miss
    c.put(1, 0, v(1))
    c.put(2, 0, v(2))
    assert np.array_equal(c.get(1, 0), v(1))   # hit refreshes recency
    c.put(3, 0, v(3))                          # evicts 2 (LRU), not 1
    assert c.get(2, 0) is None and np.array_equal(c.get(1, 0), v(1))
    assert c.counters.evictions == 1
    # version mismatch: treated as miss AND dropped on touch
    assert c.get(1, 1) is None
    assert c.get(1, 0) is None            # really gone
    # invalidate drops everything and counts
    c.put(4, 1, v(4))
    assert c.invalidate() == 2 and len(c) == 0 and c.invalidations == 1
    # capacity 0 disables caching entirely
    z = EmbeddingCache(0)
    z.put(1, 0, v(1))
    assert len(z) == 0 and z.get(1, 0) is None


def test_embedding_cache_concurrent_readers_during_invalidation():
    """Readers hammering `get` while a writer thread loops the
    `update_params` sequence (version bump + `invalidate`) — the race the
    engine's fence normally narrows but the cache must survive on its own:
    no exception, no torn state, and NO STALE READ — every value handed
    back must belong to exactly the version it was requested at (values
    encode their version, so a cross-version leak is detectable)."""
    import time as _t

    cache = EmbeddingCache(capacity=64)
    n_ids = 32
    version = [0]
    stop = threading.Event()
    errors = []

    def writer():
        try:
            for v in range(1, 40):
                version[0] = v
                cache.invalidate()
                for i in range(n_ids):
                    cache.put(i, v, np.full(4, float(v)))
                _t.sleep(0.001)
        except Exception as exc:
            errors.append(exc)
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                v = version[0]
                got = cache.get(int(np.random.randint(n_ids)), v)
                # a hit must carry EXACTLY the requested version's value —
                # a racing writer may make it a miss, never a stale read
                if got is not None:
                    assert got[0] == float(v), (got[0], v)
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    w = threading.Thread(target=writer)
    [t.start() for t in threads + [w]]
    [t.join() for t in threads + [w]]
    assert not errors
    # counters stayed coherent under the race
    c = cache.counters
    assert c.total == c.hits + c.misses and c.total > 0


# -- bucket ladder ------------------------------------------------------------

def test_default_buckets_and_bucket_for(setup):
    assert default_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    assert default_buckets(48) == (1, 2, 4, 8, 16, 32, 48)
    assert default_buckets(1) == (1,)
    eng = make_engine(setup, max_batch=8)
    assert eng._bucket_for(3) == 4 and eng._bucket_for(8) == 8
    with pytest.raises(ValueError):
        ServeConfig(max_batch=8, buckets=(1, 2, 4)).resolved_buckets()


# -- flush policy (injected clock) -------------------------------------------

def test_flush_policy_deterministic_clock(setup):
    t = [0.0]
    eng = make_engine(setup, max_batch=8, max_delay_ms=5.0, clock=lambda: t[0])
    h = eng.submit(1)
    assert not eng.should_flush() and eng.pump() == 0    # young + underfull
    t[0] += 0.004
    assert not eng.should_flush()                        # 4ms < 5ms
    t[0] += 0.002
    assert eng.should_flush()                            # oldest aged 6ms
    assert eng.pump() == 1 and h.done()
    assert eng.stats.dispatches == 1
    assert eng.pump() == 0                               # empty queue holds
    # latency metrics read the injected clock, not wall time
    assert eng.stats.latency.max_ms == pytest.approx(6.0)


def test_batch_full_flushes_inline(setup):
    eng = make_engine(setup, max_batch=4, max_delay_ms=1e9)
    handles = [eng.submit(i) for i in range(4)]
    # the 4th submit crossed max_batch: flushed inline, no pump needed
    assert eng.stats.dispatches == 1 and all(h.done() for h in handles)
    assert eng.stats.dispatch_buckets == {4: 1}


# -- coalescing + parity (the acceptance test) --------------------------------

def test_overlapping_requests_coalesce_and_match_unbatched_path(setup):
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9, cache_entries=512)
    trace = zipfian_trace(N_NODES, 40, alpha=1.1, seed=7)
    handles = [eng.submit(int(i)) for i in trace]
    while eng._drainable():
        eng.flush()
    n_req = len(trace)
    assert eng.stats.dispatches < n_req            # micro-batching observable
    assert eng.stats.coalesced > 0                 # dedup within windows
    assert eng.stats.dispatched_seeds < n_req      # fewer seeds than requests
    # every submit is accounted exactly once: answered from cache, attached
    # to a pending/in-flight slot, or dispatched as a fresh unique seed
    assert (
        eng.stats.cache.hits + eng.stats.coalesced + eng.stats.dispatched_seeds
        == n_req
    )
    # every request's logits == the unbatched batch_logits path, bit-exact
    # (each node computed exactly once — cached thereafter — so the replay
    # map is well-defined)
    oracle = replay_oracle(setup, eng)
    for nid, h in zip(trace, handles):
        assert np.array_equal(h.result(), oracle[int(nid)])


def test_repeat_trace_hits_cache(setup):
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9, cache_entries=512)
    trace = zipfian_trace(N_NODES, 30, alpha=0.99, seed=11)
    out1 = eng.predict(trace)
    d1 = eng.stats.dispatches
    out2 = eng.predict(trace)                      # replay: all cached
    assert eng.stats.dispatches == d1              # zero new device work
    assert eng.stats.cache.hits >= len(trace)
    assert np.array_equal(out1, out2)


def test_threaded_clients_bit_identical_and_coalesced(setup):
    eng = make_engine(
        setup, max_batch=8, max_delay_ms=2.0, flush_poll_ms=0.5,
        cache_entries=512,
    )
    trace = zipfian_trace(N_NODES, 48, alpha=1.1, seed=13)
    results = {}
    errors = []

    def client(tid):
        try:
            ids = trace[tid * 4 : (tid + 1) * 4]
            out = eng.predict(ids, timeout=60)
            results[tid] = (ids, out)
        except Exception as exc:  # surfaced below; don't hang the join
            errors.append(exc)

    with eng:
        threads = [threading.Thread(target=client, args=(t,)) for t in range(12)]
        [t.start() for t in threads]
        [t.join() for t in threads]
    assert not errors
    n_req = len(trace)
    assert eng.stats.requests == n_req
    assert eng.stats.dispatches < n_req            # coalescing + batching won
    oracle = replay_oracle(setup, eng)
    for ids, out in results.values():
        for nid, row in zip(ids, out):
            assert np.array_equal(row, oracle[int(nid)])
    # replay the same trace: hot nodes now served host-side
    hits_before = eng.stats.cache.hits
    eng.predict(trace)
    assert eng.stats.cache.hits > hits_before


def test_one_compiled_program_per_bucket(setup):
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9)
    next_id = iter(range(N_NODES))                # distinct ids: no cache hits
    for n in (3, 4, 3, 7, 8, 2):                  # buckets: 4, 4, 4, 8, 8, 2
        for _ in range(n):
            eng.submit(next(next_id))
        eng.flush()
    assert set(eng.stats.dispatch_buckets) <= set(default_buckets(8))
    assert eng.stats.dispatch_buckets == {4: 3, 8: 2, 2: 1}
    # fixed buckets mean NO per-request recompiles: more traffic at
    # already-seen bucket shapes must not grow the jitted apply's cache
    # (the jit is shared across engines for the same model value, so the
    # claim is relative, not absolute)
    if hasattr(eng._apply, "_cache_size"):
        before = eng._apply._cache_size()
        for n in (3, 6, 8, 2):                    # buckets 4, 8, 8, 2: all seen
            for _ in range(n):
                eng.submit(next(next_id))
            eng.flush()
        assert eng._apply._cache_size() == before


# -- params versioning --------------------------------------------------------

def test_update_params_invalidates_and_recomputes(setup):
    model, params, feat = setup
    eng = make_engine(setup, max_batch=4, max_delay_ms=1e9)
    node = 17
    out_v0 = eng.predict([node])[0]
    assert len(eng.cache) > 0 and eng.params_version == 0
    # perturb the weights: served logits MUST change after update_params
    params2 = jax.tree_util.tree_map(lambda a: a + 0.25, params)
    eng.update_params(params2)
    assert eng.params_version == 1 and len(eng.cache) == 0
    d = eng.stats.dispatches
    out_v1 = eng.predict([node])[0]
    assert eng.stats.dispatches == d + 1           # recomputed, not served stale
    assert not np.array_equal(out_v0, out_v1)
    # and the new value is cached under the new version
    out_v1b = eng.predict([node])[0]
    assert eng.stats.dispatches == d + 1 and np.array_equal(out_v1, out_v1b)


def test_pending_requests_restamped_on_update(setup):
    model, params, feat = setup
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9)
    h = eng.submit(5)                              # queued under v0
    params2 = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    eng.update_params(params2)                     # restamps pending to v1
    eng.flush()
    assert np.array_equal(h.result(), eng.predict([5])[0])  # cached under v1
    assert eng.stats.dispatches == 1               # the predict was a cache hit


# -- engine with a tiered Feature --------------------------------------------

def test_engine_serves_through_tiered_feature(setup):
    model, params, feat_np = setup
    f = Feature(rank=0, device_list=[0], device_cache_size=0)
    f.from_cpu_tensor(feat_np)
    eng = ServeEngine(
        model, params, make_sampler(), f,
        ServeConfig(max_batch=4, max_delay_ms=1e9, record_dispatches=True),
    )
    ref = make_engine(setup, max_batch=4, max_delay_ms=1e9)
    ids = [3, 9, 3, 42]
    out = eng.predict(ids)
    # the tiered Feature path clips/gathers identically to the raw table
    assert np.allclose(out, ref.predict(ids), atol=0, rtol=0)


def test_predict_empty_batch_is_a_noop(setup):
    eng = make_engine(setup, max_batch=4, max_delay_ms=1e9)
    out = eng.predict([])
    assert out.shape[0] == 0 and eng.stats.requests == 0


def test_served_rows_are_read_only_and_reset_stats_repoints_counters(setup):
    eng = make_engine(setup, max_batch=4, max_delay_ms=1e9)
    h = eng.submit(7)
    eng.flush()
    row = h.result()
    # the row is shared with the cache and coalesced co-waiters: in-place
    # mutation must be a loud error, not silent cache corruption
    assert not row.flags.writeable
    with pytest.raises(ValueError):
        row[0] = 0.0
    # reset_stats zeroes counters AND re-points the cache's counter — a
    # subsequent hit must land in the NEW stats object
    eng.reset_stats()
    assert eng.stats.requests == 0 and eng.stats.cache.total == 0
    eng.predict([7])                              # cache hit, no dispatch
    assert eng.stats.cache.hits == 1 and eng.cache.counters is eng.stats.cache
    assert eng.stats.dispatches == 0


# -- pipelined dispatch (bounded in-flight window, round 9) -------------------

import time as _time


class _GateFeature:
    """Raw-table lookalike whose gather can be slowed per dispatch — the
    lever the pipelining tests use to hold one flush in its DISPATCH stage
    while another assembles and resolves. Value-identical to the plain
    table, so replay parity against the real `feat` still holds."""

    def __init__(self, table):
        self.table = table
        self.delays = []           # seconds per dispatch, consumed FIFO
        self.started = threading.Event()  # set when a dispatch enters
        self._lock = threading.Lock()

    def __getitem__(self, n_id):
        with self._lock:
            delay = self.delays.pop(0) if self.delays else 0.0
        self.started.set()
        if delay:
            _time.sleep(delay)
        ids = np.clip(np.asarray(n_id), 0, self.table.shape[0] - 1)
        return jnp.asarray(self.table[ids])


def make_gated_engine(setup, **cfg_kw):
    model, params, feat = setup
    cfg_kw.setdefault("record_dispatches", True)
    gate = _GateFeature(feat)
    eng = ServeEngine(model, params, make_sampler(), gate, ServeConfig(**cfg_kw))
    return eng, gate


def test_pipelined_out_of_order_resolution_and_replay_parity(setup):
    """The acceptance pin for the bounded in-flight window: flush B
    assembles + dispatches + RESOLVES while flush A is still in its
    dispatch stage, the dispatch log stays in assemble (dispatch-index)
    order, and every served row still replays bit-identical through the
    offline path — out-of-order completion never leaks into results."""
    eng, gate = make_gated_engine(
        setup, max_batch=4, max_delay_ms=1e9, max_in_flight=2, cache_entries=512,
    )
    eng.warmup()                 # compiles off the race-sensitive window
    gate.delays = [3.0]          # first REAL dispatch stalls mid-flight
    gate.started.clear()
    h1 = [eng.submit(i) for i in (0, 1, 2)]
    t_a = threading.Thread(target=eng.flush)
    t_a.start()
    assert gate.started.wait(30)            # flush A is in its dispatch stage
    h2 = [eng.submit(i) for i in (10, 11, 12)]
    eng.flush()                             # flush B: full trip under A
    # B resolved while A is still dispatching: out-of-order completion
    assert all(h.done() for h in h2)
    assert not any(h.done() for h in h1)
    assert eng.stats.inflight_peak == 2     # the window was actually used
    t_a.join()
    assert all(h.done() for h in h1)
    # the dispatch log is in ASSEMBLE order (A first), not completion order
    assert [list(p[:n]) for p, n in eng.dispatch_log] == [[0, 1, 2], [10, 11, 12]]
    # and replays bit-identical through the offline batch_logits path
    oracle = replay_oracle(setup, eng)
    for nid, h in zip((0, 1, 2, 10, 11, 12), h1 + h2):
        assert np.array_equal(h.result(timeout=30), oracle[nid])
    assert eng.stats.dispatches == 2 and eng.stats.dispatched_seeds == 6
    # measured stage spans exist for all three stages
    stages = {s for s, _, _ in eng.stats.spans}
    assert stages == {"assemble", "dispatch", "resolve"}
    ov = eng.stats.spans.overlap_summary()
    assert ov and 0.0 <= ov["overlap_frac"] <= 1.0


def test_serial_and_pipelined_configs_bit_equal_single_threaded(setup):
    """``max_in_flight=1`` reproduces the round-8 serial engine; and for a
    single-threaded caller the window size must not change behavior at all:
    same dispatch log, same served logits, bit for bit."""
    trace = zipfian_trace(N_NODES, 60, alpha=0.9, seed=5)
    outs, logs = [], []
    for mif in (1, 2, 4):
        eng = make_engine(
            setup, max_batch=8, max_delay_ms=1e9, cache_entries=512,
            max_in_flight=mif,
        )
        outs.append(eng.predict(trace))
        logs.append(eng.dispatch_log)
    for out, log in zip(outs[1:], logs[1:]):
        assert np.array_equal(outs[0], out)
        assert len(logs[0]) == len(log)
        for (p0, n0), (p1, n1) in zip(logs[0], log):
            assert n0 == n1 and np.array_equal(p0, p1)


def test_update_params_fences_inflight_dispatch(setup):
    """`update_params` must drain in-flight work before swapping weights:
    it blocks until the stalled flush resolves, the old-version rows are
    never served from cache after the bump, and the post-update predict
    recomputes under the new weights."""
    model, params, feat = setup
    eng, gate = make_gated_engine(
        setup, max_batch=4, max_delay_ms=1e9, max_in_flight=2, cache_entries=512,
    )
    eng.warmup()
    gate.delays = [1.5]
    gate.started.clear()
    h = eng.submit(7)
    t_a = threading.Thread(target=eng.flush)
    t_a.start()
    assert gate.started.wait(30)           # flush in its dispatch stage
    params2 = jax.tree_util.tree_map(lambda a: a + 0.25, params)
    eng.update_params(params2)             # must FENCE: wait for the flush
    assert h.done()                        # drained before the swap landed
    assert eng.params_version == 1 and len(eng.cache) == 0
    t_a.join()
    out_v0 = h.result()
    d = eng.stats.dispatches
    out_v1 = eng.predict([7])[0]
    assert eng.stats.dispatches == d + 1   # recomputed under new weights
    assert not np.array_equal(out_v0, out_v1)


def test_threaded_clients_racing_update_params(setup):
    """Clients hammering `predict` while the trainer thread swaps weights
    repeatedly: no deadlock, no crash, every handle resolves, and the
    engine lands quiescent at the final version with nothing in flight."""
    model, params, feat = setup
    eng = make_engine(
        setup, max_batch=8, max_delay_ms=1.0, flush_poll_ms=0.5,
        cache_entries=512, max_in_flight=2,
    )
    trace = zipfian_trace(N_NODES, 64, alpha=1.1, seed=23)
    errors = []

    def client(tid):
        try:
            out = eng.predict(trace[tid * 8 : (tid + 1) * 8], timeout=60)
            assert np.isfinite(out).all()
        except Exception as exc:
            errors.append(exc)

    with eng:
        threads = [threading.Thread(target=client, args=(t,)) for t in range(8)]
        [t.start() for t in threads]
        for v in range(3):
            _time.sleep(0.05)
            eng.update_params(
                jax.tree_util.tree_map(lambda a: a * 1.01, params)
            )
        [t.join() for t in threads]
    assert not errors
    assert eng.params_version == 3
    assert eng._inflight_flushes == 0 and not eng._inflight
    assert eng.stats.requests == 64


def test_dispatch_index_order_pinned_under_deterministic_clock(setup):
    """Dispatch-index ordering under an injected clock: the dispatch log is
    exactly the assemble sequence the flush policy produced, and the stage
    spans read ONLY the injected clock."""
    t = [0.0]
    eng = make_engine(
        setup, max_batch=4, max_delay_ms=5.0, max_in_flight=2,
        clock=lambda: t[0],
    )
    eng.submit(1)
    eng.submit(2)
    assert eng.pump() == 0                 # young + underfull: policy holds
    t[0] += 0.006
    assert eng.pump() == 2                 # aged out: dispatch index 0
    eng.submit(3)
    t[0] += 0.006
    assert eng.pump() == 1                 # dispatch index 1
    for i in (4, 5, 6, 7):                 # 4th submit fills max_batch:
        eng.submit(i)                      # inline flush, dispatch index 2
    assert [list(p[:n]) for p, n in eng.dispatch_log] == [[1, 2], [3], [4, 5, 6, 7]]
    assert eng._dispatch_index == 3
    assert eng.stats.dispatch_buckets == {2: 1, 1: 1, 4: 1}
    # spans carry injected-clock timestamps only (all within [0, t]);
    # assemble records two pieces per flush (drain, then seal after the
    # window permit) so the window WAIT between them never fakes overlap
    assert len(eng.stats.spans) == 12      # 3 flushes x (2 assemble + 2)
    stages = [s for s, _, _ in eng.stats.spans]
    assert stages.count("assemble") == 6
    assert stages.count("dispatch") == stages.count("resolve") == 3
    for _, t0, t1 in eng.stats.spans:
        assert 0.0 <= t0 <= t1 <= t[0]


def test_warmup_pretraces_buckets_without_touching_key_stream(setup):
    """`warmup()` compiles every bucket's program up front (no compile on
    the first real request) and — when the sampler supports cloning — does
    NOT consume the serving sampler's key stream: the replay parity that
    defines the engine's determinism contract still holds afterwards."""
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9, cache_entries=512)
    times = eng.warmup()
    assert set(times) == {1, 2, 4, 8}
    assert all(v > 0 for v in times.values())
    assert eng.dispatch_log == []          # twin sampler: log untouched
    if hasattr(eng._apply, "_cache_size"):
        before = eng._apply._cache_size()
    next_id = iter(range(N_NODES))
    handles = []
    for n in (3, 8, 2):                    # buckets 4, 8, 2 — all pre-warmed
        ids = [next(next_id) for _ in range(n)]
        handles += [(i, eng.submit(i)) for i in ids]
        eng.flush()
    if hasattr(eng._apply, "_cache_size"):
        assert eng._apply._cache_size() == before   # no post-warmup compile
    oracle = replay_oracle(setup, eng)     # key stream unperturbed by warmup
    for nid, h in handles:
        assert np.array_equal(h.result(), oracle[nid])


# -- fused one-dispatch path (round 11) ---------------------------------------

def test_fused_and_split_paths_bit_identical(setup):
    """THE round-11 parity pin: the fused one-program serve path
    (sample+gather+forward as one pre-bound executable) serves logits and
    a dispatch log BIT-IDENTICAL to the round-9 split path on the same
    trace, and the 2→1 execute-call cut is observable in the ledger."""
    trace = zipfian_trace(N_NODES, 60, alpha=0.9, seed=5)
    outs, logs, engines = [], [], []
    for mode in ("fused", "split"):
        eng = make_engine(
            setup, max_batch=8, max_delay_ms=1e9, cache_entries=512,
            dispatch_mode=mode,
        )
        outs.append(eng.predict(trace))
        logs.append(eng.dispatch_log)
        engines.append(eng)
    fused, split = engines
    assert fused._programs is not None and split._programs is None
    assert np.array_equal(outs[0], outs[1])
    assert len(logs[0]) == len(logs[1])
    for (p0, n0), (p1, n1) in zip(logs[0], logs[1]):
        assert n0 == n1 and np.array_equal(p0, p1)
    # execute-call ledger: exactly ONE device execute per flush fused,
    # two (sample + forward) per flush split
    assert fused.stats.dispatches > 0
    assert fused.stats.execute_calls == fused.stats.dispatches
    assert fused.stats.dispatch_calls == fused.stats.dispatches
    assert split.stats.execute_calls == 2 * split.stats.dispatches
    # and both still replay bit-exact through the offline batch_logits path
    oracle = replay_oracle(setup, fused)
    for i, nid in enumerate(trace):
        assert np.array_equal(outs[0][i], oracle[int(nid)])


@pytest.mark.parametrize("mif", [1, 2])
def test_sealed_fused_engine_derives_no_key_on_the_host(setup, monkeypatch, mif):
    """The seal draws a call INDEX, a host integer; the key is folded inside
    the sealed program. With `jax.random.key` and `jax.random.fold_in` made
    to raise (the sealed programs are compiled: nothing traces them again) a
    run of flushes still answers, and answers what the replay expects."""
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9, max_in_flight=mif)
    eng.warmup()
    assert eng._programs is not None and eng._programs.sealed

    def no_host_key(*a, **kw):
        raise AssertionError("a sampler key was derived on the host")

    with monkeypatch.context() as m:
        m.setattr(jax.random, "key", no_host_key)
        m.setattr(jax.random, "fold_in", no_host_key)
        m.setattr(GraphSageSampler, "next_key", no_host_key)
        next_id = iter(range(N_NODES))
        handles = []
        for n in (3, 8, 1, 2, 5):
            ids = [next(next_id) for _ in range(n)]
            handles += [(i, eng.submit(i)) for i in ids]
            eng.flush()                     # a full batch flushed inline
        rows = [(nid, h.result(timeout=5)) for nid, h in handles]
    assert eng._sampler._call == 5 == len(eng.dispatch_log)
    oracle = replay_oracle(setup, eng)
    for nid, row in rows:
        assert np.array_equal(row, oracle[nid])


@pytest.mark.parametrize("mif", [1, 2])
def test_fused_split_and_a_skipping_twin_give_the_same_rows(setup, mif):
    """Fused engine, split engine and `sample_batch(twin)` + `forward_logits`
    give bit-equal rows over several dispatches; the twin steps over the
    dispatches it does not replay with `next_key()` (as the benchmark's
    check does), and both engines leave the cursor at the same index."""
    from quiver_tpu.inference import forward_logits, sample_batch

    model, params, feat = setup
    batches = [list(range(s, s + n)) for s, n in
               ((0, 3), (10, 8), (30, 1), (40, 5), (60, 2), (70, 8), (90, 4))]
    served = []
    for mode in ("fused", "split"):
        eng = make_engine(setup, max_batch=8, max_delay_ms=1e9,
                          dispatch_mode=mode, max_in_flight=mif)
        eng.warmup()
        rows = []
        for ids in batches:
            hs = [eng.submit(i) for i in ids]
            eng.flush()
            rows.append(np.stack([h.result(timeout=5) for h in hs]))
        served.append((eng, rows))
    (fused, rows_f), (split, rows_s) = served
    assert fused._programs is not None and split._programs is None
    assert fused._sampler._call == split._sampler._call == len(batches)
    for rf, rs in zip(rows_f, rows_s):
        assert np.array_equal(rf.view(np.uint32), rs.view(np.uint32))
    apply = _cached_apply(model)
    twin, at = make_sampler(), 0
    for pos in (1, 4, 6):           # dispatches 0, 2, 3, 5 are stepped over
        while at < pos:
            twin.next_key()
            at += 1
        padded, n_valid = fused.dispatch_log[pos]
        ds = sample_batch(twin, padded)
        at += 1
        replay = np.asarray(forward_logits(apply, params, feat, ds))[:n_valid]
        assert np.array_equal(replay.view(np.uint32), rows_f[pos].view(np.uint32))
    assert twin._call == 7


@pytest.mark.parametrize("seed", [0, 3, 2**31 - 1])
def test_fold_in_call_is_jax_fold_in_bit_for_bit(seed):
    """The head of the serve programs: Threefry-2x32 written in `lax`
    primitives gives `jax.random.fold_in`'s key for every (base key, call)."""
    from quiver_tpu.inference import fold_in_call

    key0 = jax.random.key(seed)
    folded = jax.jit(fold_in_call)
    rng = np.random.default_rng(seed)
    calls = [0, 1, 2, 2**31, 2**32 - 1] + [int(c) for c in rng.integers(0, 2**32, 12)]
    for call in calls:
        want = jax.random.key_data(jax.random.fold_in(key0, np.uint32(call)))
        for got in (folded(key0, np.uint32(call)), fold_in_call(key0, np.uint32(call))):
            assert jax.random.key_impl(got) == jax.random.key_impl(key0)
            assert np.array_equal(np.asarray(jax.random.key_data(got)), np.asarray(want))
    # another key implementation is handed to jax.random.fold_in itself
    rbg = jax.random.key(seed, impl="rbg")
    assert np.array_equal(
        np.asarray(jax.random.key_data(folded(rbg, np.uint32(7)))),
        np.asarray(jax.random.key_data(jax.random.fold_in(rbg, 7))))


def test_bucket_programs_take_the_call_index(setup):
    """`BucketPrograms.__call__(bucket, params, call, seeds)`: the call index
    as a Python int, any order, the rows `batch_logits` gives at that index."""
    from quiver_tpu.inference import BucketPrograms

    model, params, feat = setup
    programs = BucketPrograms(model, make_sampler(), feat)
    apply = _cached_apply(model)
    padded = pad_seed_batch(np.arange(5, dtype=np.int64), 8)
    for call in (4, 0, 2**31 + 7):
        twin = make_sampler()
        twin._call = call
        want = np.asarray(batch_logits(apply, params, twin, feat, padded))
        got = np.asarray(programs(8, params, call, padded))
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert programs._sampler._call == 0     # the program consumes no index


def test_dispatch_mode_validation_and_forced_fused(setup):
    model, params, feat = setup
    with pytest.raises(ValueError, match="dispatch_mode"):
        ServeEngine(model, params, make_sampler(), feat,
                    ServeConfig(dispatch_mode="warp"))
    # a feature with no in-jit gather cannot satisfy dispatch_mode='fused'
    gate = _GateFeature(feat)
    with pytest.raises(ValueError, match="cannot fuse"):
        ServeEngine(model, params, make_sampler(), gate,
                    ServeConfig(dispatch_mode="fused"))
    # ...but 'auto' quietly falls back to the split path for it
    eng = ServeEngine(model, params, make_sampler(), gate, ServeConfig())
    assert eng._programs is None


def test_post_warmup_bucket_miss_is_hard_error(setup):
    """warmup() seals the fused program table: a bucket the fleet didn't
    warm raises RuntimeError (resolved into the waiters like any flush
    error) instead of silently compiling under a live request."""
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9)
    assert eng._programs is not None
    times = eng.warmup(buckets=(4, 8))       # partial warm: 1 and 2 missing
    assert set(times) == {4, 8} and eng._programs.sealed
    for i in range(3):
        eng.submit(i)
    assert eng.flush() == 3                  # bucket 4: pre-bound, fine
    h = eng.submit(50)                       # bucket 1: sealed miss
    with pytest.raises(RuntimeError, match="no pre-bound executable"):
        eng.flush()
    with pytest.raises(RuntimeError, match="no pre-bound executable"):
        h.result(timeout=1)
    assert not eng._drainable() and not eng._inflight
    # a FULL warmup covers the whole ladder — no miss is possible
    eng2 = make_engine(setup, max_batch=8, max_delay_ms=1e9)
    eng2.warmup()
    assert set(eng2._programs.buckets) == set(default_buckets(8))


def test_serve_stats_merge_includes_round11_counters():
    a, b = ServeStats(), ServeStats()
    a.dispatch_calls, a.execute_calls, a.late_admitted = 3, 3, 1
    b.dispatch_calls, b.execute_calls, b.late_admitted = 1, 2, 4
    m = ServeStats().merge(a).merge(b)
    assert (m.dispatch_calls, m.execute_calls, m.late_admitted) == (4, 5, 5)
    snap = m.snapshot()
    assert snap["execute_calls"] == 5 and snap["late_admitted"] == 5


def test_cached_apply_reuses_traced_program_across_evals(setup):
    """Trace-count pin for `inference._cached_apply`: equal model VALUES
    share one jitted apply, and a repeated `sampled_eval` retraces
    nothing — the jit cache size is flat across calls."""
    from quiver_tpu.inference import sampled_eval

    model, params, feat = setup
    twin = GraphSAGE(hidden_dim=16, out_dim=5, num_layers=2, dropout=0.0)
    apply = _cached_apply(model)
    assert apply is _cached_apply(twin)      # value-keyed, not id-keyed
    labels = np.zeros(N_NODES, np.int64)
    nodes = np.arange(32)
    sampled_eval(model, params, make_sampler(), feat, labels, nodes,
                 batch_size=16)
    assert hasattr(apply, "_cache_size")
    before = apply._cache_size()
    for _ in range(2):                       # repeat evals: zero retraces
        sampled_eval(model, params, make_sampler(), feat, labels, nodes,
                     batch_size=16)
    assert apply._cache_size() == before


# -- late admission (continuous seed-level batching, round 11) ----------------

def test_late_admission_replay_determinism(setup):
    """A seed submitted while a flush sits assembled-but-blocked on the
    in-flight window joins that flush's pad lanes: it appears in the
    dispatch log exactly once, repeats of it coalesce, and the served
    logits are bit-equal to a no-late-admission run submitting the same
    final batches — admission never perturbs the key stream."""
    eng, gate = make_gated_engine(
        setup, max_batch=8, max_delay_ms=1e9, max_in_flight=1,
        cache_entries=512,
    )
    eng.warmup()
    gate.delays = [3.0]                      # flush A stalls mid-dispatch
    gate.started.clear()
    h1 = [eng.submit(i) for i in (0, 1, 2)]
    t_a = threading.Thread(target=eng.flush)
    t_a.start()
    assert gate.started.wait(30)             # A holds the only window permit
    h2 = [eng.submit(i) for i in (10, 11, 12)]
    t_b = threading.Thread(target=eng.flush)
    t_b.start()                              # B drains, publishes, blocks
    deadline = _time.time() + 20
    while eng._open is None and _time.time() < deadline:
        _time.sleep(0.005)
    assert eng._open is not None             # B is open for admission
    h_late = eng.submit(13)                  # rides B's pad lane (bucket 4)
    assert eng.stats.late_admitted == 1
    co = eng.stats.coalesced
    h_co = eng.submit(13)                    # coalesces onto the admitted slot
    assert eng.stats.coalesced == co + 1
    t_a.join()
    t_b.join()
    flat = [list(p[:nv]) for p, nv in eng.dispatch_log]
    assert flat == [[0, 1, 2], [10, 11, 12, 13]]
    seeds = [s for f in flat for s in f]     # admitted exactly once, no dupes
    assert len(seeds) == len(set(seeds))
    assert eng.stats.padded_seeds == 1       # only A's slack went to waste
    # bit-equal to a no-late-admission engine fed the same final batches
    ref = make_engine(setup, max_batch=8, max_delay_ms=1e9,
                      late_admission=False)
    ref_out = {}
    for batch in flat:
        hs = [ref.submit(i) for i in batch]
        ref.flush()
        for nid, h in zip(batch, hs):
            ref_out[nid] = h.result(timeout=30)
    assert ref.stats.late_admitted == 0
    for nid, h in zip((0, 1, 2, 10, 11, 12, 13, 13),
                      h1 + h2 + [h_late, h_co]):
        assert np.array_equal(h.result(timeout=30), ref_out[nid])
    # ...and through the offline replay oracle
    oracle = replay_oracle(setup, eng)
    for nid in (0, 1, 2, 10, 11, 12, 13):
        assert np.array_equal(ref_out[nid], oracle[nid])


# -- error propagation --------------------------------------------------------

def test_flush_error_resolves_waiters(setup):
    class Boom(RuntimeError):
        pass

    def broken(*_a, **_k):
        raise Boom("sampler down")

    # split path: the sample_dense leg raises mid-seal
    eng = make_engine(setup, max_batch=8, max_delay_ms=1e9, dispatch_mode="split")
    eng._sampler.sample_dense = broken
    h = eng.submit(1)
    with pytest.raises(Boom):
        eng.flush()
    with pytest.raises(Boom):
        h.result(timeout=1)
    assert not eng._drainable() and not eng._inflight
    # fused path: the call-index draw raises mid-seal — same resolution contract
    eng2 = make_engine(setup, max_batch=8, max_delay_ms=1e9)
    assert eng2._programs is not None
    eng2._sampler.next_call = broken
    h2 = eng2.submit(1)
    with pytest.raises(Boom):
        eng2.flush()
    with pytest.raises(Boom):
        h2.result(timeout=1)
    assert not eng2._drainable() and not eng2._inflight
