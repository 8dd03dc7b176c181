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
  serve bit-equal rows, log bit-equal dispatches and train bit-equal losses.
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


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv(qtrace.TRACE_ENV, raising=False)
    trace_report(reset=True)
    yield
    trace_report(reset=True)


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
    fused.sample_dense(seeds)  # call 0, no session: not recorded
    with session(tmp_path) as s:
        for sampler in (fused, dedup, fused):
            ds = sampler.sample_dense(seeds)
            feature.lookup_padded(ds.n_id)
    rep = trace_report()
    assert rep["quiver.sample"][0] == 3
    assert rep["quiver.feature.lookup"][0] == 3
    events = host_events(s["path"])
    assert [e[3] for e in events if e[1] == "quiver.sample"] == [
        {"call": 1}, {"call": 0}, {"call": 2}]
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
    assert trace_report()["quiver.serve.queue"][0] == len(nodes)
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
