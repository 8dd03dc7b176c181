"""The standing proof that quiver_tpu's main path runs on a TPU.

    python chip_smoke.py             # one chip: train phase + serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded steps + their
                                     # one-device twin, and no other phase

One process, the public surface a user drives, at the ogbn-products shape
(2,449,029 nodes, 2 x 61,859,140 directed edges, features [N, 100] f32, 47
classes, fanout 15-10-5, batch 1024, GraphSAGE 3 x 256). Data is generated in
the run from ``--seed``; nothing is written into the checkout except the
compile cache (`quiver_tpu.utils.enable_compile_cache`).

- train: `CSRTopo` -> `GraphSageSampler(mode="TPU")` (tiled layout) ->
  `Feature.from_cpu_tensor` (whole table in HBM) -> `models.GraphSAGE` ->
  jitted optax step, both pipelines (fused, and dedup with calibrated caps),
  plus one `MixedGraphSageSampler` epoch (a spawned CPU worker beside the
  process that holds the chip).
- serve: `ServeEngine` over the same graph and the params just trained,
  `warmup()`, 4 client threads x 16 `predict` calls, replay parity, and no
  compile after `warmup()`.

Every earlier line of standard output is one JSON object worth keeping
(versions, wall times on the host's clock, choices the library made from the
backend). The LAST line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Any failed check, any exception in any phase, or a platform other than
``tpu`` is a traceback and a non-zero exit; there is no ``ok: false`` line.

``--small`` shrinks the graph and the batch (not the model's widths) for the
CPU rehearsal, which calls `run` directly: `main` refuses every platform but
``tpu`` before it does any work.
"""

from __future__ import annotations

import argparse
import json
import shutil
import threading
import time
from typing import Dict, List, NamedTuple

import numpy as np

SIZES = (15, 10, 5)
HIDDEN = 256
# ogbn-products (quiver_tpu.datasets.PRODUCTS; edges doubled: the reference
# samples the symmetrized CSR)
PRODUCTS = dict(nodes=2_449_029, edges=2 * 61_859_140, dim=100, classes=47,
                train_nodes=196_615, batch=1024, steps=20)
SMALL = dict(nodes=20_000, edges=500_000, dim=100, classes=47,
             train_nodes=4_000, batch=128, steps=6)
WARM_STEPS = 2          # steps before the timed ones (they compile)
PROBE_BATCHES = 8       # cap calibration (GraphSageSampler.calibrate_caps)
SERVE_CLIENTS, SERVE_CALLS, SERVE_IDS_PER_CALL = 4, 16, 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond, what: str) -> None:
    """A failed check ends the run with a traceback (assert would vanish
    under -O)."""
    if not cond:
        raise AssertionError(what)


class CompileWatch:
    """Counts what the process compiles (or loads from the persistent
    cache) through jax.monitoring: `mark()` returns the programs, compile
    seconds and cache hits since the previous mark."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._programs, self._seconds, self._hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self._programs += 1
                self._seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self._hits += 1

    def mark(self) -> Dict[str, float]:
        with self._lock:
            out = {"programs": self._programs,
                   "compile_s": round(self._seconds, 3),
                   "persistent_cache_hits": self._hits}
            self._programs, self._seconds, self._hits = 0, 0.0, 0
        return out

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class Data(NamedTuple):
    indptr: np.ndarray      # [N+1] int64
    indices: np.ndarray     # [E] int64
    features: np.ndarray    # [N, dim] float32
    labels: np.ndarray      # [N] int32
    train_idx: np.ndarray   # [train_nodes] int64


def make_data(cfg: dict, seed: int) -> Data:
    """Power-law graph at the configured shape plus a learnable task: the
    class-dependent feature nudge of `datasets.synthetic_powerlaw`
    (``label_signal=1.5``), without its [2, E] edge list."""
    from quiver_tpu.datasets import powerlaw_csr

    t0 = time.perf_counter()
    indptr, indices = powerlaw_csr(cfg["nodes"], cfg["edges"], seed=seed)
    t_graph = time.perf_counter() - t0
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 1)
    n, dim, classes = cfg["nodes"], cfg["dim"], cfg["classes"]
    features = rng.standard_normal((n, dim), dtype=np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    basis = rng.standard_normal((classes, dim), dtype=np.float32)
    features += basis[labels] * np.float32(1.5)
    train_idx = rng.choice(n, cfg["train_nodes"], replace=False)
    emit(phase="data", nodes=n, edges=int(indices.shape[0]),
         max_degree=int(np.diff(indptr).max()), graph_gen_s=round(t_graph, 2),
         feature_gen_s=round(time.perf_counter() - t0, 2))
    return Data(indptr, indices, features, labels, train_idx)


class EdgeOracle:
    """Is (u, v) an edge of the host CSR? The oracle of tests/test_sampler.py
    at a size where per-row Python is out of reach: one sorted array of
    ``u * N + v`` keys, membership by binary search."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.n = int(indptr.shape[0] - 1)
        keys = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(indptr))
        keys *= self.n
        keys += indices
        keys.sort()
        self.keys = keys

    def has_edges(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        q = src.astype(np.int64) * self.n + dst.astype(np.int64)
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.shape[0] - 1)
        return self.keys[pos] == q


def check_sample_against_csr(ds, oracle: EdgeOracle, what: str) -> int:
    """Every sampled (target, neighbour) pair of every hop must be an edge of
    the host graph. `dense_to_pyg` gives each hop's local (source, target)
    pairs for both layouts; local ids resolve through the whole ``ds.n_id``
    (each hop's source ids are a prefix of it). Returns the number of sampled
    edges checked."""
    from quiver_tpu.pyg.sage_sampler import dense_to_pyg

    n_id = np.asarray(ds.n_id).astype(np.int64)
    checked = 0
    for hop, adj in enumerate(dense_to_pyg(ds)[2]):
        src, dst = adj.edge_index
        ok = oracle.has_edges(n_id[dst], n_id[src])
        check(ok.all(), f"{what}: {int((~ok).sum())} of {ok.size} sampled "
                        f"neighbours of hop {hop} are not edges of the host CSR")
        checked += int(ok.size)
    check(checked > 0, f"{what}: sampled no edge at all")
    return checked


def ring_sampler(**kwargs):
    """A `GraphSageSampler` over a 64-node ring. GraphSAGE's parameter shapes
    depend on neither the batch nor the graph, so a one-seed sample of it is
    enough to trace `model.init` where the real graph is sharded or only
    described (`four_chip_phase`, tests/test_tpu_compile.py)."""
    from quiver_tpu import CSRTopo
    from quiver_tpu.pyg import GraphSageSampler

    ring = np.arange(64)
    topo = CSRTopo(edge_index=np.stack([ring, np.roll(ring, 1)]))
    return GraphSageSampler(topo, SIZES, mode="TPU", **kwargs)


def make_model(classes: int, dropout: float = 0.5):
    from quiver_tpu.models import GraphSAGE

    return GraphSAGE(hidden_dim=HIDDEN, out_dim=classes,
                     num_layers=len(SIZES), dropout=dropout)


def make_train_step(model, tx):
    """The jitted optax step of examples/reddit_sage.py."""
    import jax
    import optax

    @jax.jit
    def train_step(params, opt_state, key, x, adjs, y):
        def loss_fn(p):
            logits = model.apply(p, x, adjs, train=True, rngs={"dropout": key})
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


def seed_batches(data: Data, batch: int, count: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(data.train_idx, batch, replace=False)
                     for _ in range(count)])


def run_pipeline(name, sampler, feature, model, tx, data, batches, oracle,
                 watch, seed):
    """Warm-up + timed train steps through sampler -> Feature -> jitted step,
    every step ended with block_until_ready; then the checks that do not
    depend on float rounding, on the last batch. Returns the trained params."""
    import jax
    import jax.numpy as jnp

    train_step = make_train_step(model, tx)
    n = data.features.shape[0]
    params = opt_state = None
    losses: List[float] = []
    step_s: List[float] = []
    overflow = 0
    watch.mark()
    for i, seeds in enumerate(batches):
        t0 = time.perf_counter()
        ds = sampler.sample_dense(seeds)
        x = feature.lookup_padded(ds.n_id)
        y = jnp.asarray(data.labels[seeds])
        if params is None:
            params = model.init(
                {"params": jax.random.key(seed), "dropout": jax.random.key(seed + 1)},
                x, ds.adjs, train=True,
            )
            opt_state = tx.init(params)
        params, opt_state, loss = train_step(
            params, opt_state, jax.random.key(seed * 1000 + i), x, ds.adjs, y
        )
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        if i == WARM_STEPS - 1:
            compiles = watch.mark()
        if i >= WARM_STEPS:
            step_s.append(dt)
        losses.append(float(loss))
        if ds.cap_overflow is not None:
            overflow += int(ds.cap_overflow)
    late = watch.mark()

    check(np.isfinite(losses).all(), f"{name}: non-finite loss {losses}")
    head, tail = np.mean(losses[:3]), np.mean(losses[-3:])
    check(tail < head, f"{name}: loss did not fall ({head:.4f} -> {tail:.4f})")
    check(overflow == 0, f"{name}: cap_overflow {overflow} (caps {sampler.caps})")
    check(late["programs"] == 0,
          f"{name}: {late['programs']} programs compiled after warm-up")
    edges = check_sample_against_csr(ds, oracle, name)
    got = np.asarray(x)
    want = data.features[np.clip(np.asarray(ds.n_id).astype(np.int64), 0, n - 1)]
    check(got.shape == want.shape and
          np.array_equal(got.view(np.uint32), want.view(np.uint32)),
          f"{name}: gathered feature rows differ from the host table's")
    emit(phase="train", pipeline=name, caps=sampler.caps,
         n_id_width=int(ds.n_id.shape[0]), steps=len(step_s),
         step_ms_host_clock_median=round(float(np.median(step_s)) * 1e3, 3),
         step_ms_host_clock_min=round(float(np.min(step_s)) * 1e3, 3),
         loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
         cap_overflow=overflow, sampled_edges_checked=edges,
         gathered_rows_bit_equal=int(got.shape[0]), warmup=compiles)
    return params


def mixed_sampler_epoch(topo, data, cfg, caps, oracle, seed) -> None:
    """One `MixedGraphSageSampler` epoch of two tasks: one for the device,
    one for a spawned CPU worker. The worker imports this package (and JAX
    with it) while this process holds the chip; a child that reached for the
    chip would hang here, on the chip run, not later."""
    from quiver_tpu.pyg.mixed_sampler import MixedGraphSageSampler, TrainSampleJob

    shm_need = data.indptr.nbytes + data.indices.nbytes
    shm_free = shutil.disk_usage("/dev/shm").free
    check(shm_free > 2 * shm_need,
          f"/dev/shm has {shm_free} bytes free; the CPU worker's graph "
          f"needs {shm_need}")
    batch = cfg["batch"]
    job = TrainSampleJob(data.train_idx[: 2 * batch], batch, seed=seed)
    mixed = MixedGraphSageSampler(
        job, topo, SIZES, num_workers=1, device=0, mode="TPU_CPU_MIXED",
        caps=caps, seed=seed,
    )
    t0 = time.perf_counter()
    try:
        edges = {task: check_sample_against_csr(ds, oracle, f"mixed task {task}")
                 for task, ds in mixed}
    finally:
        mixed.shutdown()
    check(sorted(edges) == [0, 1], f"mixed sampler yielded tasks {sorted(edges)}")
    check(mixed.avg_cpu_time > 0 and mixed.avg_device_time > 0,
          "mixed sampler: one of the two engines sampled nothing")
    emit(phase="train", pipeline="mixed", tasks=len(edges),
         sampled_edges_checked=sum(edges.values()),
         device_share=mixed.last_device_share,
         epoch_s=round(time.perf_counter() - t0, 2))


def train_phase(cfg: dict, data: Data, seed: int, watch: CompileWatch):
    import jax
    import optax

    from quiver_tpu import CSRTopo, Feature
    from quiver_tpu.ops.cpu_kernels import native_engine_info
    from quiver_tpu.pyg import GraphSageSampler

    dev = jax.local_devices()[0]
    topo = CSRTopo(indptr=data.indptr, indices=data.indices)

    buf = np.ones((64 << 20,), np.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(buf, dev))
    h2d_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bd, tiles = topo.to_device_tiled(dev)
    jax.block_until_ready((bd, tiles))
    t_tiles = time.perf_counter() - t0
    t0 = time.perf_counter()
    feature = Feature(rank=0, device_list=[0],
                      device_cache_size=data.features.nbytes, csr_topo=topo)
    feature.from_cpu_tensor(data.features)
    jax.block_until_ready(feature.shard_tensor.device_shards[0][1])
    t_feat = time.perf_counter() - t0
    check(feature.shard_tensor.cpu_tensor is None,
          "the whole feature table was meant to sit in HBM")
    t0 = time.perf_counter()
    oracle = EdgeOracle(data.indptr, data.indices)
    emit(phase="setup",
         h2d_gbps_host_clock=round(buf.nbytes / h2d_s / 1e9, 3),
         tile_table=list(tiles.shape), tile_table_bytes=int(tiles.nbytes),
         tile_build_and_upload_s=round(t_tiles, 2),
         feature_bytes=int(data.features.nbytes),
         feature_reorder_and_upload_s=round(t_feat, 2),
         edge_oracle_s=round(time.perf_counter() - t0, 2),
         host_engine=native_engine_info())

    model = make_model(cfg["classes"])
    tx = optax.adam(3e-3)
    batch, steps = cfg["batch"], WARM_STEPS + cfg["steps"]

    fused = GraphSageSampler(topo, SIZES, device=0, mode="TPU", dedup=False,
                             seed=seed)
    run_pipeline("fused", fused, feature, model, tx, data,
                 seed_batches(data, batch, steps, seed + 10), oracle, watch, seed)

    dedup = GraphSageSampler(topo, SIZES, device=0, mode="TPU", seed=seed)
    watch.mark()
    t0 = time.perf_counter()
    caps = dedup.calibrate_caps(seed_batches(data, batch, PROBE_BATCHES, seed + 20))
    emit(phase="train", pipeline="dedup", calibrated_caps=caps,
         calibrate_s=round(time.perf_counter() - t0, 2), calibrate=watch.mark())
    params = run_pipeline("dedup", dedup, feature, model, tx, data,
                          seed_batches(data, batch, steps, seed + 30), oracle,
                          watch, seed)

    mixed_sampler_epoch(topo, data, cfg, caps, oracle, seed)
    return topo, model, params


def serve_phase(cfg: dict, data: Data, topo, model, params, seed: int,
                watch: CompileWatch) -> None:
    """`ServeEngine` under 4 client threads; every served row must bit-match
    the offline replay of the dispatch log through a fresh identically
    seeded sampler (`inference.batch_logits`), and nothing may compile after
    `warmup()` sealed the bucket programs."""
    from quiver_tpu.inference import _cached_apply, batch_logits
    from quiver_tpu.pyg import GraphSageSampler
    from quiver_tpu.serve import ServeConfig, ServeEngine, zipfian_trace

    def make_sampler():
        return GraphSageSampler(topo, SIZES, device=0, mode="TPU", seed=seed + 40)

    # dispatch_mode="fused": a drop to the split path (no sealed programs)
    # is a construction-time error, not a quiet fallback
    eng = ServeEngine(
        model, params, make_sampler(), data.features,
        ServeConfig(max_in_flight=2, record_dispatches=True,
                    dispatch_mode="fused"),
    )
    watch.mark()
    warm = eng.warmup()
    check(eng.dispatch_log == [], "warmup() consumed dispatch indices")
    emit(phase="serve", warmup_s={str(b): round(s, 2) for b, s in warm.items()},
         warmup=watch.mark())

    trace = zipfian_trace(
        cfg["nodes"], SERVE_CLIENTS * SERVE_CALLS * SERVE_IDS_PER_CALL,
        alpha=0.99, seed=seed,
    ).reshape(SERVE_CLIENTS, SERVE_CALLS, SERVE_IDS_PER_CALL)
    served: List[List[np.ndarray]] = [[] for _ in range(SERVE_CLIENTS)]
    errors: List[str] = []

    def client(c: int) -> None:
        try:
            for ids in trace[c]:
                served[c].append(np.asarray(eng.predict(ids, 300.0)))
        except Exception as exc:  # re-raised by the main thread below
            errors.append(f"client {c}: {exc!r}")

    t0 = time.perf_counter()
    with eng:
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(SERVE_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900.0)
        check(not any(t.is_alive() for t in threads), "serve clients hung")
    wall = time.perf_counter() - t0
    check(not errors, f"serve clients failed: {errors}")
    live = watch.mark()
    check(live["programs"] == 0,
          f"{live['programs']} programs compiled after warmup() sealed the buckets")

    apply = _cached_apply(model)
    twin = make_sampler()
    oracle: Dict[int, np.ndarray] = {}
    for padded, n_valid in eng.dispatch_log:
        logits = np.asarray(batch_logits(apply, params, twin, data.features, padded))
        for i in range(n_valid):
            oracle.setdefault(int(padded[i]), logits[i])
    rows = 0
    for c in range(SERVE_CLIENTS):
        check(len(served[c]) == SERVE_CALLS, f"client {c} got {len(served[c])} answers")
        for ids, out in zip(trace[c], served[c]):
            check(out.shape == (SERVE_IDS_PER_CALL, cfg["classes"])
                  and np.isfinite(out).all(), f"client {c}: bad logits {out.shape}")
            for node, row in zip(ids, out):
                check(np.array_equal(row.view(np.uint32),
                                     oracle[int(node)].view(np.uint32)),
                      f"served logits of node {int(node)} differ from the replay "
                      f"(max |diff| {np.abs(row - oracle[int(node)]).max():.3e})")
                rows += 1
    s = eng.stats
    emit(phase="serve", requests=int(trace.size), rows_bit_equal_to_replay=rows,
         dispatches=s.dispatches, dispatch_buckets={str(b): c for b, c in
                                                    sorted(s.dispatch_buckets.items())},
         inflight_peak=s.inflight_peak, cache_hit_rate=round(s.cache.hit_rate, 4),
         wall_s_host_clock=round(wall, 3),
         latency_ms_host_clock={k: round(v, 3) for k, v in s.latency.snapshot().items()},
         compiled_after_warmup=live["programs"], replay=watch.mark())


def four_chip_phase(cfg: dict, data: Data, seed: int, watch: CompileWatch) -> None:
    """The sharded train steps on a (dp=1, ici=4) mesh — feature rows striped
    over four chips with the graph replicated, then the graph row-sharded
    too — against the one-device step on the same key and seeds. dp=1 because data-parallel
    groups fold their index into the sampling key: only one group has a
    one-device twin."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quiver_tpu import CSRTopo
    from quiver_tpu.parallel import (
        make_mesh,
        make_sharded_topo_train_step,
        make_sharded_train_step,
        replicate,
        shard_feature_rows,
        shard_topology_rows,
    )
    from quiver_tpu.parallel.topology import ShardedTopology
    from quiver_tpu.serve import resolve_exchange_mode

    platform = jax.devices()[0].platform
    exchange = resolve_exchange_mode("auto", hosts=4)
    check(exchange == "collective", f"auto exchange is {exchange!r} on 4 devices")

    topo = CSRTopo(indptr=data.indptr, indices=data.indices)
    mesh = make_mesh(4, dp=1)
    t0 = time.perf_counter()
    feat = shard_feature_rows(mesh, data.features)
    stopo = shard_topology_rows(mesh, topo)
    jax.block_until_ready((feat, stopo))
    t_shard = time.perf_counter() - t0
    emit(phase="choices", topology_layout=type(stopo).__name__,
         dist_exchange_hosts4=exchange)
    check(isinstance(stopo, ShardedTopology), f"unexpected {type(stopo).__name__}")
    sharded = {"features": feat}
    sharded.update({f"topology.{k}": v for k, v in stopo._asdict().items()
                    if k != "row_start"})
    for name, arr in sharded.items():
        check(len(arr.sharding.device_set) == 4,
              f"{name} lives on {len(arr.sharding.device_set)} devices")
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == 4
              and all(s.data.shape[0] * 4 == arr.shape[0] for s in shards),
              f"{name} is not split four ways: {[s.data.shape for s in shards]}")
    sharded_bytes = sum(int(a.nbytes) for a in sharded.values())
    in_use = None  # the CPU backend keeps no memory statistics
    if platform == "tpu":
        in_use = [int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()[:4]]
        # a quarter each, not all of it on the first chip
        check(all(0.2 * sharded_bytes < b < 0.35 * sharded_bytes for b in in_use),
              f"per-chip bytes_in_use {in_use} vs {sharded_bytes} sharded bytes")
    emit(phase="shard", mesh=dict(mesh.shape), shard_and_upload_s=round(t_shard, 2),
         sharded_bytes=sharded_bytes, bytes_in_use_per_chip=in_use,
         shapes={k: list(v.shape) for k, v in sharded.items()})

    model = make_model(cfg["classes"], dropout=0.0)
    tx = optax.adam(3e-3)
    batch, steps = cfg["batch"], 3
    batches = seed_batches(data, batch, steps, seed + 50).astype(np.int32)
    keys = [jax.random.key(seed * 1000 + i) for i in range(steps)]
    ip32, ix32 = data.indptr.astype(np.int32), data.indices.astype(np.int32)

    ds0 = ring_sampler(dedup=False).sample_dense(np.arange(1))
    x0 = jnp.zeros((ds0.n_id.shape[0], cfg["dim"]), jnp.float32)
    params0 = jax.tree_util.tree_map(
        np.asarray, model.init(jax.random.key(seed + 1), x0, ds0.adjs))

    def run(label, m, step, *graph_and_feat):
        labels = replicate(m, data.labels)
        params = replicate(m, params0)
        opt_state = jax.device_put(tx.init(params0), NamedSharding(m, P()))
        losses, times = [], []
        watch.mark()
        for key, seeds in zip(keys, batches):
            seeds = jax.device_put(seeds, NamedSharding(m, P("dp")))
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, key,
                                           *graph_and_feat, labels, seeds)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
            losses.append(float(loss))
        check(np.isfinite(losses).all(), f"{label}: non-finite loss {losses}")
        emit(phase="sharded", step=label, mesh=dict(m.shape), losses=losses,
             step_ms_host_clock_after_first=round(float(np.min(times[1:])) * 1e3, 3),
             compile=watch.mark())
        return losses

    got = {
        "sharded_features": run(
            "sharded_features", mesh,
            make_sharded_train_step(mesh, model, tx, SIZES, pipeline="fused"),
            replicate(mesh, ip32), replicate(mesh, ix32), feat),
        "sharded_topology": run(
            "sharded_topology", mesh,
            make_sharded_topo_train_step(mesh, model, tx, SIZES, pipeline="fused"),
            stopo, feat),
    }
    # the twin is the row-sharded step on a one-device mesh (one shard = the
    # whole graph): the same code, the same draws from the same key
    mesh1 = make_mesh(1)
    want = run("one_device", mesh1,
               make_sharded_topo_train_step(mesh1, model, tx, SIZES, pipeline="fused"),
               shard_topology_rows(mesh1, topo),
               shard_feature_rows(mesh1, data.features))
    for label, losses in got.items():
        check(np.allclose(losses, want, rtol=1e-3, atol=1e-5),
              f"{label} losses {losses} differ from the one-device {want}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="data and sampling seed")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the sharded steps and their one-device twin only")
    ap.add_argument("--small", action="store_true",
                    help="rehearsal size (not the products shape)")
    return ap.parse_args(argv)


def run(argv=None) -> dict:
    """All phases the arguments select, on whatever platform JAX found (the
    CPU rehearsals call this; `main` lets only a TPU through). Returns the
    device record of the last line."""
    args = parse_args(argv)
    import jax
    import jaxlib

    from quiver_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    check(len(devs) >= args.chips, f"--chips {args.chips} but JAX found {devs}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except ImportError:
        libtpu = None
    cfg = SMALL if args.small else PRODUCTS
    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, device=device, chips=args.chips, seed=args.seed,
         compile_cache_dir=cache_dir, products_shape=not args.small, config=cfg)
    if args.small:
        emit(note="--small is a rehearsal size, NOT the products shape")

    t_start = time.perf_counter()
    watch = CompileWatch()
    try:
        data = make_data(cfg, args.seed)
        if args.chips == 4:
            four_chip_phase(cfg, data, args.seed, watch)
        else:
            topo, model, params = train_phase(cfg, data, args.seed, watch)
            serve_phase(cfg, data, topo, model, params, args.seed, watch)
    finally:
        watch.close()
    stats = [d.memory_stats() for d in devs[: args.chips]]
    emit(phase="done", wall_s=round(time.perf_counter() - t_start, 1),
         peak_bytes_in_use=[None if m is None else int(m["peak_bytes_in_use"])
                            for m in stats])
    return device


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}). There is no CPU pass."
        )
    device = run()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
