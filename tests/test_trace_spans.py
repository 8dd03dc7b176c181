"""The library's one span primitive (`trace.trace_scope` / `trace.observe`)
and the sites that use it (ISSUE 26).

The contracts:

- OFF (no profiler session, no ``QUIVER_ENABLE_TRACE``) a span is one check:
  nothing reaches the registry and no `TraceAnnotation` is built;
- ON whenever a `jax.profiler` session records: the span is in the registry
  AND in the session's ``.xplane.pb`` under the same name, its ids as event
  stats, the two durations of one clock reading apart;
- a site reached while `jax.jit` traces the function records nothing;
- the sampler, `Feature.lookup_padded` and every stage of `ServeEngine` are
  named where the work happens, the per-request stages add up to the latency
  the engine recorded;
- OBSERVE-ONLY (the rule of tests/test_obs.py): a traced and an untraced run
  serve bit-equal rows, log bit-equal dispatches and train bit-equal losses;
- (ISSUE 36) ON, every closed span is also on the timeline with the two stamps
  its duration is the difference of, and a reader lays them on the session's
  trace through an enclosing span both sides see; a watch thread lives while
  spans record and names the stalls of the process; a flush's six spans tile
  it; OFF there is no entry, no thread, no object.
"""

import contextlib
import glob
import os
import threading
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from conftest import make_random_graph

from quiver_tpu import CSRTopo, Feature
from quiver_tpu import trace as qtrace
from quiver_tpu.models import GraphSAGE
from quiver_tpu.pyg.sage_sampler import GraphSageSampler
from quiver_tpu.serve import ServeConfig, ServeEngine, zipfian_trace
from quiver_tpu.trace import observe, trace_report, trace_scope

N_NODES = 200
DIM = 16
SIZES = [4, 4]
SAMPLER_SEED = 3
SERVE_STAGES = ("quiver.serve.queue", "quiver.serve.device", "quiver.serve.resolved")
FLUSH_SPANS = tuple(f"quiver.serve.{s}" for s in (
    "seq_wait", "assemble", "window_wait", "seal", "dispatch", "resolve"))


def watch_ended(timeout=2.0):
    """Whether the stall watch (a thread that lives while spans record) has
    ended itself: it looks every `qtrace.TICK_S`."""
    deadline = time.monotonic() + timeout
    while qtrace._watch is not None and time.monotonic() < deadline:
        time.sleep(qtrace.TICK_S)
    return qtrace._watch is None


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv(qtrace.TRACE_ENV, raising=False)
    assert watch_ended()
    trace_report(reset=True)
    qtrace.trace_timeline(reset=True)
    yield
    trace_report(reset=True)
    qtrace.trace_timeline(reset=True)


@contextlib.contextmanager
def session(tmp_path):
    """A real profiler session, with the options the benchmark's traced runs
    use; yields a dict that holds the trace's path once the session closed."""
    out = {}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    (out["path"],) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))


def host_events(path, prefix="quiver."):
    """(plane, name, duration seconds, {stat: value}) of the trace's events
    named ``prefix*``, in order of their start."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    found.append((e.start_ns, plane.name, e.name,
                                  e.duration_ns * 1e-9, dict(e.stats)))
    return [f[1:] for f in sorted(found, key=lambda f: f[0])]


def make_topo():
    return CSRTopo(edge_index=make_random_graph(N_NODES, 2000, seed=0))


def make_sampler(**kw):
    return GraphSageSampler(make_topo(), sizes=SIZES, mode="TPU",
                            seed=SAMPLER_SEED, **kw)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    model = GraphSAGE(hidden_dim=16, out_dim=5, num_layers=2, dropout=0.0)
    ds0 = make_sampler().sample_dense(np.arange(8, dtype=np.int64))
    x0 = jnp.zeros((ds0.n_id.shape[0], DIM), jnp.float32)
    params = model.init(jax.random.key(0), x0, ds0.adjs)
    return model, params, feat


def make_engine(setup, **cfg_kw):
    model, params, feat = setup
    cfg = dict(record_dispatches=True, max_batch=8, buckets=(8,), cache_entries=0)
    cfg.update(cfg_kw)
    eng = ServeEngine(model, params, make_sampler(), feat, ServeConfig(**cfg))
    eng.warmup()
    return eng


# -- the primitive ------------------------------------------------------------


def test_off_is_one_check_nothing_recorded_nothing_built(monkeypatch):
    built = []
    monkeypatch.setattr(qtrace, "TraceAnnotation", type(
        "Counting", (), {"__init__": lambda self, *a, **k: built.append(a),
                         "is_enabled": staticmethod(lambda: False)}))
    clock = []
    monkeypatch.setattr(qtrace.time, "perf_counter", lambda: clock.append(0) or 0.0)
    with trace_scope("off.span", fid=1) as box:
        box.sync = None
    observe("off.observed", 1.0)
    observe("off.observed", np.ones(4))
    assert trace_report() == {}
    assert built == [] and clock == []
    assert not qtrace.trace_enabled()


def test_env_var_still_turns_it_on_without_a_session(monkeypatch):
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    assert qtrace.trace_enabled()
    with trace_scope("env.span"):
        pass
    assert trace_report()["env.span"][0] == 1


def test_span_in_registry_and_in_xplane_same_name_ids_as_stats(tmp_path):
    with session(tmp_path) as s:
        assert qtrace.trace_enabled()
        for i in range(3):
            with trace_scope("quiver.test.span", fid=i, bucket=8):
                time.sleep(0.005 * (i + 1))
    assert not qtrace.trace_enabled()
    with trace_scope("quiver.test.span", fid=99):  # the session is over
        pass
    count, total, longest = trace_report(with_max=True)["quiver.test.span"]
    assert count == 3
    events = host_events(s["path"], "quiver.test.span")
    assert [e[3] for e in events] == [{"fid": i, "bucket": 8} for i in range(3)]
    assert all(e[0].startswith("/host:") for e in events)
    assert abs(sum(e[2] for e in events) - total) < 1e-3
    assert abs(max(e[2] for e in events) - longest) < 1e-3
    assert 0.015 <= longest <= total
    # what its present readers get is unchanged: (count, total seconds)
    assert trace_report()["quiver.test.span"] == (count, total)


def test_site_under_jit_tracing_records_nothing(tmp_path):
    def site(x):
        with trace_scope("quiver.test.jit_site"):
            return x + 1

    with session(tmp_path) as s:
        jitted = jax.jit(site)
        jitted(jnp.ones(3))   # traces `site`: no span
        jitted(jnp.ones(3))
        jax.vmap(site)(jnp.ones((2, 3)))
        assert "quiver.test.jit_site" not in trace_report()
        site(jnp.ones(3))     # eagerly: one span
    assert trace_report()["quiver.test.jit_site"][0] == 1
    assert len(host_events(s["path"], "quiver.test.jit_site")) == 1


def test_threaded_counts_exact_with_max(monkeypatch):
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    threads, per_thread = 8, 300

    def worker(k):
        for i in range(per_thread):
            with trace_scope("spans.race"):
                pass
            observe("spans.race.observed", np.full(3, float(k)))
        observe("spans.race.observed", float(k) + 0.5)

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    rep = trace_report(reset=True, with_max=True)
    count, total, longest = rep["spans.race"]
    assert count == threads * per_thread and 0.0 < longest <= total
    count, total, longest = rep["spans.race.observed"]
    assert count == threads * (3 * per_thread + 1)
    assert total == sum(3 * per_thread * k + k + 0.5 for k in range(threads))
    assert longest == threads - 0.5
    assert trace_report() == {}


def test_sync_waits_for_the_arrays_it_is_given(monkeypatch):
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    waited = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waited.append(x))
    a = jnp.ones(4)
    with trace_scope("spans.sync") as box:
        box.sync = a
    with trace_scope("spans.sync", sync=a):
        pass
    with trace_scope("spans.sync"):
        pass
    assert waited == [a, a] and trace_report()["spans.sync"][0] == 3


# -- the sites ----------------------------------------------------------------


def test_sampler_and_feature_sites(tmp_path):
    topo = make_topo()
    feat = np.random.default_rng(1).standard_normal((N_NODES, DIM)).astype(np.float32)
    feature = Feature(rank=0, device_list=[0], device_cache_size=feat.nbytes,
                      csr_topo=topo)
    feature.from_cpu_tensor(feat)
    fused, dedup = make_sampler(dedup=False), make_sampler()
    seeds = np.arange(8, dtype=np.int64)
    jax.clear_caches()  # `compiled` below: whatever ran before built nothing
    fused.sample_dense(seeds)  # call 0, no session: not recorded
    with session(tmp_path) as s:
        for sampler in (fused, dedup, fused):
            ds = sampler.sample_dense(seeds)
            feature.lookup_padded(ds.n_id)
    rep = trace_report()
    assert rep["quiver.sample"][0] == 3
    assert rep["quiver.feature.lookup"][0] == 3
    events = host_events(s["path"])
    # `compiled`: the dedup sampler's first call builds its program inside the
    # session; the fused one's was built by the call before it
    assert [e[3] for e in events if e[1] == "quiver.sample"] == [
        {"call": 1, "compiled": 0}, {"call": 0, "compiled": 1},
        {"call": 2, "compiled": 0}]
    assert sum(e[1] == "quiver.feature.lookup" for e in events) == 3


def test_feature_lookup_span_says_which_program_ran(tmp_path):
    """`quiver.feature.lookup` carries ``ordered``: 0 while a wholly hot
    table is stored as given (`_padded_gather`), 1 once an order stands
    between ids and stored rows (`_padded_gather_ordered`, here after
    `set_local_order`); with tracing off nothing is recorded."""
    feat = np.random.default_rng(1).standard_normal((N_NODES, DIM)).astype(np.float32)
    feature = Feature(rank=0, device_list=[0], device_cache_size=feat.nbytes,
                      csr_topo=make_topo())
    feature.from_cpu_tensor(feat)
    ids = jnp.arange(8)
    feature.lookup_padded(ids)  # no session: not recorded
    assert trace_report() == {}
    with session(tmp_path) as s:
        feature.lookup_padded(ids)
        feature.lookup_padded(ids)
        feature.set_local_order(np.arange(N_NODES))
        feature.lookup_padded(ids)
    assert trace_report()["quiver.feature.lookup"][0] == 3
    events = host_events(s["path"], "quiver.feature.lookup")
    assert [e[3] for e in events] == [{"ordered": 0}, {"ordered": 0}, {"ordered": 1}]


def drive(eng, nodes, clients=4):
    """Threaded single-request clients through submit/result (the path the
    benchmark's serve cell drives); rows in request order."""
    rows = [None] * len(nodes)

    def client(k):
        for i in range(k, len(nodes), clients):
            rows[i] = np.array(eng.submit(int(nodes[i])).result(timeout=60.0))

    with eng:
        ts = [threading.Thread(target=client, args=(k,)) for k in range(clients)]
        [t.start() for t in ts]
        [t.join() for t in ts]
    return np.stack(rows)


def test_serve_stages_count_requests_and_add_up_to_latency(setup, tmp_path):
    nodes = zipfian_trace(N_NODES, 96, alpha=1.1, seed=11)
    eng = make_engine(setup, max_delay_ms=1.0)
    with session(tmp_path) as s:
        drive(eng, nodes)
    rep = trace_report(with_max=True)
    assert rep["quiver.serve.submit"][0] == len(nodes)
    for stage in SERVE_STAGES:  # one reading per request answered
        assert rep[stage][0] == len(nodes) == eng.stats.latency.count
    stage_mean_ms = sum(rep[stage][1] for stage in SERVE_STAGES) / len(nodes) * 1e3
    assert stage_mean_ms <= eng.stats.latency.mean_ms * (1 + 1e-9)
    assert stage_mean_ms >= 0.99 * eng.stats.latency.mean_ms
    # a request cannot wait on the device for less than its flush's dispatch
    # took... unless it joined that flush late; the longest did not
    dispatch = [t1 - t0 for stage, t0, t1 in eng.stats.spans if stage == "dispatch"]
    assert rep["quiver.serve.device"][2] == pytest.approx(max(dispatch), abs=1e-9)
    # per-flush spans: one of each stage per dispatch, named by its index
    events = host_events(s["path"], "quiver.serve.")
    n_flush = eng.stats.dispatches
    for stage in ("dispatch", "resolve"):
        fids = [e[3]["fid"] for e in events if e[1] == f"quiver.serve.{stage}"]
        assert sorted(fids) == list(range(1, n_flush + 1))
        assert rep[f"quiver.serve.{stage}"][0] == n_flush
    fids = [e[3]["fid"] for e in events if e[1] == "quiver.serve.assemble"]
    # drain and seal of every dispatch; a poller that found the queue drained
    # by the other leaves one short drain span under the index still to come
    assert set(range(1, n_flush + 1)) <= set(fids) <= set(range(1, n_flush + 2))
    assert len(eng.stats.spans) > 0  # and `stats.spans` records as it did


def test_journal_and_observed_stages_agree(setup, monkeypatch):
    """The observed stages are `EventJournal.request_breakdown`'s, cut at the
    flush's own stamps instead of the journal's events. With synchronous
    flushes nobody joins late, so each request has the same three stages
    in both."""
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    eng = make_engine(setup, journal_events=4096)
    trace_report(reset=True)
    handles = []
    for v in zipfian_trace(N_NODES, 40, alpha=1.1, seed=5):
        handles.append(eng.submit(int(v)))
        if len(handles) % 8 == 0:
            eng.flush()
    eng.flush()
    assert all(h.done() for h in handles)
    bd = eng.journal.request_breakdown()
    rep = trace_report()
    assert bd["requests"] == 40
    mean_ms = {}
    for stage, key in zip(SERVE_STAGES, ("queue_ms", "device_ms", "resolve_ms")):
        assert rep[stage][0] == bd[key]["n"] == 40
        mean_ms[key] = rep[stage][1] / 40 * 1e3
    # the device stage is cut outside the call the journal's two events are
    # emitted inside; the other cuts are a few lines apart, not a stage
    assert mean_ms["device_ms"] >= bd["device_ms"]["mean"]
    for key, mine in mean_ms.items():
        assert mine == pytest.approx(bd[key]["mean"], abs=5.0)


# -- observe-only -------------------------------------------------------------


def test_traced_and_untraced_serve_bit_equal(setup, tmp_path):
    nodes = zipfian_trace(N_NODES, 96, alpha=1.1, seed=11)
    eng_off = make_engine(setup)
    out_off = np.asarray(eng_off.predict(nodes))
    assert trace_report() == {}
    eng_on = make_engine(setup)
    with session(tmp_path):
        out_on = np.asarray(eng_on.predict(nodes))
    rep = trace_report()
    assert rep["quiver.serve.queue"][0] == rep["quiver.serve.pending"][0] == len(nodes)
    n_flush = eng_on.stats.dispatches
    for site in FLUSH_SPANS + ("quiver.serve.pumps",):  # ISSUE 36's sites ran too
        assert rep[site][0] >= n_flush, site
    assert np.array_equal(out_on.view(np.uint32), out_off.view(np.uint32))
    assert len(eng_on.dispatch_log) == len(eng_off.dispatch_log) > 0
    for (p_on, n_on), (p_off, n_off) in zip(eng_on.dispatch_log, eng_off.dispatch_log):
        assert n_on == n_off and np.array_equal(p_on, p_off)


def test_traced_and_untraced_train_losses_bit_equal(tmp_path):
    topo = make_topo()
    rng = np.random.default_rng(2)
    feat = rng.standard_normal((N_NODES, DIM)).astype(np.float32)
    labels = rng.integers(0, 5, N_NODES)
    model = GraphSAGE(hidden_dim=16, out_dim=5, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-2)

    @jax.jit
    def train_step(params, opt_state, x, adjs, y):
        def objective(p):
            ll = jax.nn.log_softmax(model.apply(p, x, adjs))
            return -jnp.take_along_axis(ll, y[:, None], axis=1).mean()

        loss, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def three_steps():
        sampler = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SAMPLER_SEED)
        feature = Feature(rank=0, device_list=[0], device_cache_size=feat.nbytes,
                          csr_topo=topo)
        feature.from_cpu_tensor(feat)
        params = opt_state = None
        losses = []
        for i in range(3):
            seeds = np.arange(16 * i, 16 * (i + 1), dtype=np.int64)
            ds = sampler.sample_dense(seeds)
            x = feature.lookup_padded(ds.n_id)
            if params is None:
                params = model.init(jax.random.key(0), x, ds.adjs)
                opt_state = tx.init(params)
            params, opt_state, loss = train_step(
                params, opt_state, x, ds.adjs, jnp.asarray(labels[seeds]))
            losses.append(np.asarray(loss))
        return np.stack(losses)

    untraced = three_steps()
    assert trace_report() == {}
    with session(tmp_path):
        traced = three_steps()
    rep = trace_report()
    assert rep["quiver.sample"][0] == rep["quiver.feature.lookup"][0] == 3
    assert np.array_equal(traced.view(np.uint32), untraced.view(np.uint32))


# -- the timeline and the stall watch (ISSUE 36) --------------------------------


def timeline_of(*names):
    return [e for e in qtrace.trace_timeline() if e[0] in names]


def test_timeline_stamps_and_the_anchors_offset_reproduce_the_spans_own_event(tmp_path):
    """No Python clock is the trace's, but the two differ by a constant for
    the session: found through an enclosing span that both sides see, it puts
    a span's timeline stamps where the profiler put the span's own event."""
    from jax.profiler import ProfileData, TraceAnnotation
    from qbench.reduce import Event
    from qbench.readers.span_device_gap import anchor_offset

    with session(tmp_path) as s:
        for i in range(24):
            with TraceAnnotation("qbench.outer"):
                with trace_scope("quiver.test.inner", fid=i):
                    time.sleep(0.002)
            time.sleep(0.001)
    events = {"qbench.outer": [], "quiver.test.inner": []}
    for plane in ProfileData.from_file(s["path"]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in events:
                    events[e.name].append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns))
    outer, own = (sorted(events[k], key=lambda e: e.start_ns) for k in events)
    inner = [(t0 * 1e9, t1 * 1e9) for _, t0, t1, _, _ in timeline_of("quiver.test.inner")]
    assert len(outer) == len(own) == len(inner) == 24
    assert [e[4] for e in timeline_of("quiver.test.inner")] == [{"fid": i} for i in range(24)]
    offset = anchor_offset(outer, inner)
    assert offset is not None
    off_by = sorted(max(abs(t0 + offset - e.start_ns), abs(t1 + offset - e.end_ns))
                    for (t0, t1), e in zip(inner, own))
    # to 50 us; a thread taken off its core between the profiler's stamp and
    # the span's own clock read may spoil a span or two on a loaded machine
    assert off_by[len(off_by) // 2] < 50e3 and off_by[int(0.8 * len(off_by))] < 50e3, off_by
    # the registry's duration is the difference of the timeline's two stamps
    total = trace_report()["quiver.test.inner"][1]
    assert total == pytest.approx(
        sum(e[2] - e[1] for e in timeline_of("quiver.test.inner")), rel=1e-12)


def test_a_span_that_does_not_annotate_is_in_registry_and_timeline_and_not_in_the_trace(
        setup, tmp_path):
    """``annotate=False``: what the one per-request site takes. The span is
    counted and stamped like any other; the profiler is not told of it."""
    eng = make_engine(setup)
    with session(tmp_path) as s:
        with trace_scope("quiver.test.quiet", annotate=False, fid=3) as span:
            span.set(late=1)
            time.sleep(0.002)
        with trace_scope("quiver.test.loud", fid=4):
            pass
        for v in range(5):
            eng.submit(v)
        eng.flush()
    assert [e[4] for e in timeline_of("quiver.test.quiet")] == [{"fid": 3, "late": 1}]
    count, total = trace_report()["quiver.test.quiet"]
    assert count == 1 and total >= 0.002
    names = {e[1] for e in host_events(s["path"], "quiver.")}
    assert "quiver.test.loud" in names and "quiver.test.quiet" not in names
    # the submit site: five requests counted and on the timeline (the anchor
    # of `span_device_gap` pairs them with the benchmark's spans), no event
    assert trace_report()["quiver.serve.submit"][0] == 5
    assert len(timeline_of("quiver.serve.submit")) == 5
    assert "quiver.serve.submit" not in names and "quiver.serve.seal" in names


def test_off_no_timeline_entry_no_thread_no_object(setup, monkeypatch):
    import gc

    recorded = []
    monkeypatch.setattr(qtrace._timeline, "record", lambda *a: recorded.append(a))
    monkeypatch.setattr(qtrace, "_start_watch", lambda: recorded.append("watch"))
    with trace_scope("off.span", fid=1):
        pass
    observe("off.observed", 1.0)
    eng = make_engine(setup)
    eng.predict(zipfian_trace(N_NODES, 24, alpha=1.1, seed=3))  # every serve site, off
    assert eng.stats.dispatches > 0
    assert recorded == [] and qtrace.trace_timeline() == () and trace_report() == {}
    assert qtrace._watch is None and qtrace.stall_report() == []
    assert not [t for t in threading.enumerate() if t.name == "quiver-trace-watch"]
    assert not any(cb is qtrace._on_gc for cb in gc.callbacks)


def test_the_watch_lives_while_spans_record_and_ticks_into_the_registry(monkeypatch):
    import gc

    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    observe("watch.starter", 1.0)  # an `observe` starts it as a span does
    watch = qtrace._watch
    assert watch is not None and watch.daemon and watch.name == "quiver-trace-watch"
    time.sleep(0.1)
    gc.collect()
    with trace_scope("watch.second_span"):
        pass
    assert qtrace._watch is watch  # one watch, not one a span
    assert any(cb is qtrace._on_gc for cb in gc.callbacks)
    count, total, longest = trace_report(with_max=True)["quiver.host.tick"]
    assert 2 <= count <= 0.1 / qtrace.TICK_S + 1 and 0.0 <= longest <= total
    (collection,) = [e for e in timeline_of("quiver.host.gc") if e[4]["generation"] == 2]
    assert collection[3] == threading.get_ident() and collection[1] <= collection[2]
    monkeypatch.delenv(qtrace.TRACE_ENV)
    assert watch_ended() and not watch.is_alive()
    assert not any(cb is qtrace._on_gc for cb in gc.callbacks)


def test_a_compile_is_on_the_timeline_by_its_thread(monkeypatch):
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    with trace_scope("compile.around"):
        jax.jit(lambda x: x * 3 + 1).lower(jnp.ones(7)).compile()
    (around,) = timeline_of("compile.around")
    compiles = timeline_of("quiver.host.compile")
    assert compiles and all(c[3] == threading.get_ident() for c in compiles)
    assert all(around[1] <= c[2] <= around[2] for c in compiles)
    assert trace_report()["quiver.host.compile"][0] == len(compiles)


def test_a_thread_that_holds_the_interpreter_is_one_stall_with_its_span_named(monkeypatch):
    """One C call that keeps the interpreter lock (a sort of floats compares
    without releasing it) stops every Python thread, the watch too: ONE tick
    comes late, by the length of the call, the process burned CPU through
    it, and the report names the span that was open around it."""
    values = np.random.default_rng(0).random(2_000_000).tolist()
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    with trace_scope("quiver.test.warm"):
        pass
    time.sleep(4 * qtrace.TICK_S)  # the watch is up and ticking
    with trace_scope("quiver.test.hold", fid=7):
        kept = sorted(values)
    time.sleep(4 * qtrace.TICK_S)
    assert len(kept) == len(values)
    (hold,) = timeline_of("quiver.test.hold")
    assert hold[2] - hold[1] > 2 * qtrace.STALL_S, "the sort was too short to stall anything"
    across = [s for s in qtrace.stall_report() if s["t0"] < hold[2] and s["t1"] > hold[1]]
    assert len(across) == 1, across
    (stall,) = across
    assert stall["wall_s"] == pytest.approx(hold[2] - hold[1], rel=0.25)
    assert stall["cpu_s"] >= 0.5 * stall["wall_s"]
    assert set(stall) == {"t0", "t1", "wall_s", "cpu_s", "runq_wait_s", "minor_faults",
                          "major_faults", "invol_switches", "open"}
    assert [(name, ids) for name, _, _, ids in stall["open"][threading.get_ident()]] == [
        ("quiver.test.hold", {"fid": 7})]
    # the registry has the tick: its longest reading is the stall
    assert trace_report(with_max=True)["quiver.host.tick"][2] == pytest.approx(
        stall["wall_s"], abs=1e-6)


def test_a_flushs_six_spans_tile_it_on_one_thread_with_two_pollers(setup, tmp_path):
    nodes = zipfian_trace(N_NODES, 160, alpha=1.1, seed=13)
    eng = make_engine(setup, max_delay_ms=1.0, max_in_flight=2)
    with session(tmp_path):
        drive(eng, nodes)
    n_flush = eng.stats.dispatches
    by_thread = {}
    for name, t0, t1, tid, ids in timeline_of(*FLUSH_SPANS):
        by_thread.setdefault(tid, []).append((t0, t1, name.rsplit(".", 1)[1], ids["fid"]))
    whole, stages = [], [s.rsplit(".", 1)[1] for s in FLUSH_SPANS]
    for spans in by_thread.values():
        spans.sort()
        # one thread's flushes come one after the other: no span overlaps the next
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
        i = 0
        while i < len(spans):
            assert [s[2] for s in spans[i:i + 2]] == stages[:2]
            if [s[2] for s in spans[i + 2:i + 6]] == stages[2:]:
                assert len({s[3] for s in spans[i:i + 6]}) == 1  # one fid on all six
                whole.append(spans[i:i + 6])
                i += 6
            else:  # the queue was drained by the other thread: waited, found nothing
                i += 2
    assert sorted(f[0][3] for f in whole) == list(range(1, n_flush + 1))
    assert len(by_thread) >= 2  # two pollers (and a client's inline flush at a fill)
    rep = trace_report()
    for stage in ("window_wait", "seal", "dispatch", "resolve"):
        assert rep[f"quiver.serve.{stage}"][0] == n_flush
    assert "quiver.serve.assemble" in rep and rep["quiver.serve.seq_wait"][0] >= n_flush
    # `pump()` calls are counted between flushes: the two pollers made them
    count, total = rep["quiver.serve.pumps"]
    assert count == n_flush and 0 < total <= eng._pumps


def test_pending_and_the_flushs_own_part_add_up_to_the_queue_stage(setup, monkeypatch):
    """A request is pending until the flush that takes it is CALLED and inside
    that flush from then to its dispatch: with synchronous flushes everyone
    is there from the call on, so the queue stage less the pending stage is
    the flush's width times (call -> dispatch), which the spans give."""
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    eng = make_engine(setup)
    trace_report(reset=True)
    qtrace.trace_timeline(reset=True)
    waiters, held = [], 0
    for v in zipfian_trace(N_NODES, 40, alpha=1.1, seed=5):
        eng.submit(int(v))
        held += 1
        if held % 8 == 0:
            time.sleep(0.003)  # something to be pending for
            eng.flush()
            waiters.append(8)
    rep = trace_report()
    assert rep["quiver.serve.pending"][0] == rep["quiver.serve.queue"][0] == 40
    starts = {}
    for name, t0, _, _, ids in timeline_of("quiver.serve.seq_wait", "quiver.serve.dispatch"):
        starts.setdefault(ids["fid"], {})[name] = t0
    in_flush = sum(n * (starts[fid]["quiver.serve.dispatch"] - starts[fid]["quiver.serve.seq_wait"])
                   for fid, n in zip(sorted(starts), waiters))
    assert len(starts) == len(waiters) == 5
    assert rep["quiver.serve.pending"][1] >= 40 * 0.003 * 0.5
    assert rep["quiver.serve.queue"][1] - rep["quiver.serve.pending"][1] == pytest.approx(
        in_flush, abs=40 * 200e-6)


# -- the sampler's one program, by name (ISSUE 31) ------------------------------


def metric_patterns():
    """Every ``include`` / ``exclude`` pattern of the benchmark's metrics that
    is matched against program (XLA module) names."""
    from qbench import manifest

    metrics = os.path.join(manifest.HERE, "metrics")
    found = []
    for f in sorted(os.listdir(metrics)):
        params = manifest.load_json(os.path.join(metrics, f))["params"]
        if params.get("line", "modules") == "modules":
            found += [(f, key, p) for key in ("include", "exclude")
                      for p in params.get(key, ())]
    return found


def test_sample_program_name_is_the_jitted_callable_at_its_site():
    from quiver_tpu.pyg.sage_sampler import sample_dense_program as program

    (name,) = qtrace.SAMPLE_PROGRAM_NAMES
    assert name not in qtrace.PROGRAM_NAMES + qtrace.STEP_PROGRAM_NAMES
    assert hasattr(program, "lower") and program.__name__ == name
    for sampler in (make_sampler(dedup=False), make_sampler(caps=(64, 256))):
        graph, _, id_dtype = sampler._graph_and_bind()
        lowered = program.lower(
            jax.random.key(0), np.uint32(0), np.zeros(8, np.dtype(id_dtype)), graph,
            sizes=sampler.sizes, caps=sampler.caps, dedup=sampler.dedup, hop=sampler._hop())
        assert f"module @jit_{name} " in lowered.as_text()
        # what it is handed is its arguments: base key, call index, seeds, the graph
        assert len(jax.tree_util.tree_leaves(lowered.args_info)) == 3 + len(graph)


@pytest.mark.parametrize("metric_file,key,pattern", metric_patterns())
def test_no_pattern_for_the_gather_or_the_step_matches_the_sample_program(
        metric_file, key, pattern):
    """The sampler's metrics read every program BUT the gather's and the
    step's (``exclude``), `gather_roofline` reads the gather's (``include``):
    the sampler's one program must match none of those patterns, or it is
    counted as the wrong layer's."""
    import re

    for name in qtrace.SAMPLE_PROGRAM_NAMES:
        assert not re.search(pattern, f"jit_{name}"), (metric_file, key, pattern)
