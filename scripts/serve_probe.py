"""Synthetic online-serving probe, round 11: ONE-dispatch serving —
fused AOT-pre-bound bucket executables vs the round-9/10 two-dispatch
path, plus late seed admission under an open-loop Poisson trace.

Replays seeded request traces through the REAL serving stack
(`quiver_tpu.serve.DistServeEngine` router + per-owner `ServeEngine`s) on
a community graph whose contiguous partition is k-hop CLOSED (true 1/H
shards, zero halo). Two serve paths per sweep point:

- **fused** — the round-11 default: ``feature_residency="closure"`` owner
  shards, every owner flush is ONE execute call on a pre-bound
  `inference.BucketPrograms` executable (``execute_calls == dispatches``,
  asserted in-run), late admission on.
- **split** — the round-9/10 baseline: ``feature_residency="exchange"`` +
  ``dispatch_mode="split"`` (sample leg + forward leg per flush,
  ``execute_calls == 2 * dispatches``).

Every sweep point runs ``--repeats`` times and reports MEDIAN + min/max
(NEXT.md: single-run numbers on this noisy 1-core box flip run to run —
one-number points are noise; the spread is part of the artifact). In-run
bit-parity still asserts over all rows: hosts=1 fused output == a plain
single-host `ServeEngine` on the same trace, and every served row (both
paths, both host counts) == the offline `batch_logits` replay of the
owning shard's dispatch log through a FULL-graph sampler
(`replay_shard_oracle`).

The LATE-ADMISSION leg paces submits on a Poisson arrival schedule
against a single-host fused engine driven by a few pump threads at
``max_in_flight=1``: partial age-triggered flushes block on the window
while the device runs the previous flush, and seeds arriving during the
wait ride the blocked flush's pad lanes (``late_admitted > 0`` asserted;
the recovered lanes are bucket slack that rounds 8-10 computed and threw
away). Replay parity asserts after, so admission demonstrably never
perturbs the key stream.

Also measures the dispatch costs three ways — eval-shaped split
(`time_eval_split`), the fused one-program step, and their delta (the
per-flush overhead the 2→1 cut removes) — and emits
`scaling.serve_table(dispatches_per_flush=1 vs 2)` priced with that
measured overhead, next to the measured trajectory. Artifact is stamped
with the producing git revision.

Round 12 adds the OBSERVABILITY legs (ISSUE 7): the same saturated sweep
re-run with the request-lifecycle `trace.EventJournal` + fleet
`MetricsRegistry` enabled — the artifact then carries (a) journal-derived
per-request per-stage p50/p99 (queue vs device vs resolve) and per-flush
pad occupancy, (b) a Perfetto-loadable Chrome-trace timeline
(``--timeline out.json``) whose flush lanes show overlapped in-flight
flushes, (c) the Prometheus text exposition of the fleet registry, and
(d) the measured enabled-vs-disabled saturated-QPS delta
(``serve_obs_overhead_frac``, median-of-3 interleaved runs). Parity is
re-asserted WITH the journal on (observation never feeds control flow).

Round 13 adds the WORKLOAD-SKEW leg (ISSUE 8, ``--skew`` ->
SERVE_r06.json): an alpha in {0.8, 1.1, 1.3} Zipf sweep through engines
with the round-13 frequency sketches on (`trace.WorkloadConfig`),
recording per alpha (a) Space-Saving top-64 vs exact-counter overlap
(>= 90% asserted in-run at alpha 1.3), (b) the sketch's predicted LRU
hit rate at the probe's cache capacity vs the MEASURED `EmbeddingCache`
hit rate under an LRU-faithful sequential drive (within 5 points
asserted at alpha 1.3), (c) per-owner routed load + imbalance +
straggler stats at hosts 1 and 2, and (d) an interleaved median-of-3
sketch-on vs sketch-off saturated-QPS comparison (noise-honest spreads,
same discipline as the journal leg). The alpha-1.3 measured
head-concentration curve feeds `scaling.skew_table` — the predicted
hot-shard replication benefit for ROADMAP item 3, priced from
measurement.

Round 14 adds the DISK-TIER leg (ISSUE 9, ``--tiers`` ->
TIER_r01.json): a dedicated 4800-node community graph whose feature
table is 6.7x the configured host-DRAM budget (disk holding the rest),
served static-placement vs SKETCH-ADAPTED placement (the row-access
sketch + `ServeEngine.adapt_tiers` fenced batches) under an alpha-1.3
Zipf trace whose hotness is PERMUTED off the stored prefix. In-run
asserts: capacity ratio >= 5x, disk-tier gathers bit-equal the in-DRAM
oracle (fp32 exact, int8 codec-exact), and adaptive beats static on
saturated QPS or p99 (median-of-3 interleaved, spreads reported).
Cold-read latency is SIMULATED per row (labeled in the artifact —
this box's page cache makes flat-file reads DRAM-speed) and applied
identically to both placements; measured per-row tier costs price
`scaling.tier_table` rows carried in the artifact.

Round 16 adds the ELASTIC-FLEET leg (ISSUE 11, ``--scale`` ->
SERVE_r08.json): a host-mode hosts=1 fleet ramped 1→2→4→2 under a live
alpha-1.1 Zipf trace via `DistServeEngine.scale` — seed-ownership
ranges migrate one bounded fenced batch at a time (build outside the
fence, per-range flip). In-run asserts: ZERO dropped requests on the
clean ramp, bit-parity of every completed row in every wave against the
epoch-aware `replay_fleet_oracle` (retired engines vouch for their
epochs), and a second ramp with an owner KILLED MID-MIGRATION
(`FaultSpec(at="migration")`) whose in-flight ranges roll
forward/back deterministically, still zero-drop (fallback absorbs),
still parity-true, and bit-identical when replayed. The clean ramp's
measured coverage + routed-flush cost price `scaling.fleet_table`
(add-a-host vs replicate-the-head) in the artifact.

Usage: JAX_PLATFORMS=cpu python scripts/serve_probe.py [--requests 400]
       [--hosts 1,2] [--repeats 3] [--out SERVE_r05.json]
       [--timeline SERVE_r05_timeline.json]
       JAX_PLATFORMS=cpu python scripts/serve_probe.py --skew
       [--skew-requests 3000] [--skew-cache 64] [--out SERVE_r06.json]
       JAX_PLATFORMS=cpu python scripts/serve_probe.py --tiers
       [--tier-requests 600] [--tier-disk-us-per-row 20]
       [--out TIER_r01.json]
       JAX_PLATFORMS=cpu python scripts/serve_probe.py --scale
       [--scale-requests 360] [--migrate-batch 120]
       [--out SERVE_r08.json]
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def git_revision() -> str:
    """Best-effort `git rev-parse HEAD` of the repo this probe ran from,
    with a ``-dirty`` suffix when the working tree has uncommitted changes
    (an artifact stamped with a clean-looking revision it wasn't actually
    built from would be worse than no stamp)."""
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=cwd,
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        # tracked changes only: the probe itself writes untracked artifacts
        # (--timeline lands before this stamp is taken), and an untracked
        # file does not change what the probe ran
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=cwd, capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
        return rev + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def community_graph(n_comm=4, per_comm=120, intra=10, dim=32, seed=0):
    rng = np.random.default_rng(seed)
    n = n_comm * per_comm
    src, dst = [], []
    for u in range(n):
        cu = u // per_comm
        for v in rng.choice(per_comm, intra, replace=False) + cu * per_comm:
            src.append(u)
            dst.append(int(v))
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    return np.stack([np.array(src), np.array(dst)]), feat, n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--hosts", default="1,2")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--poisson-requests", type=int, default=300)
    ap.add_argument("--poisson-qps", default="1500,3000")
    ap.add_argument("--timeline", default=None,
                    help="write the Chrome-trace (Perfetto) timeline of "
                         "the instrumented run here")
    ap.add_argument("--journal-events", type=int, default=65536)
    ap.add_argument("--tiers", action="store_true",
                    help="round-14 disk-tier leg: static vs sketch-driven "
                         "adaptive placement -> TIER_r01.json")
    ap.add_argument("--tier-requests", type=int, default=600)
    ap.add_argument("--tier-hbm-rows", type=int, default=480)
    ap.add_argument("--tier-host-rows", type=int, default=720)
    ap.add_argument("--tier-disk-us-per-row", type=float, default=20.0,
                    help="SIMULATED per-row cold-read latency (this box's "
                         "page cache makes flat-file reads DRAM-speed; "
                         "production disk is not; 0 = raw page cache)")
    ap.add_argument("--real-disk", action="store_true",
                    help="round-18 predictive-IO leg (with --tiers): "
                         "page-cache-DEFEATED cold reads (O_DIRECT where "
                         "the filesystem allows, else fadvise DONTNEED "
                         "between legs; method recorded), >=10x-DRAM "
                         "table, mid-run hot-set shift, prefetch-on vs "
                         "prefetch-off vs all-DRAM interleaved "
                         "median-of-3 (-> TIER_r02.json)")
    ap.add_argument("--rd-hbm-rows", type=int, default=240)
    ap.add_argument("--rd-host-rows", type=int, default=360)
    ap.add_argument("--rd-prefetch-rows", type=int, default=2048,
                    help="tier_prefetch_max_rows for the prefetch-on arm "
                         "(closure walk + staging bound — the waste/"
                         "coverage dial; 1024 truncates ~30% of this "
                         "trace's per-burst closure off the staging set)")
    ap.add_argument("--rd-requests", type=int, default=1600,
                    help="real-disk leg trace length (measured window = "
                         "the post-warm two thirds)")
    ap.add_argument("--rd-device-us", type=float, default=250.0,
                    help="RECORDED per-row device-latency model applied "
                         "to every backing read of the measured arms "
                         "(staging reads included — the model can never "
                         "flatter prefetch). This container's backing "
                         "store is hypervisor-cached: even O_DIRECT "
                         "preads land in ~7 us/row, i.e. the guest page "
                         "cache is defeated (evidence recorded) but the "
                         "device itself answers at RAM speed, so a "
                         "latency-hiding claim needs a device latency to "
                         "hide. The sleep is GIL-releasing (IO-shaped: "
                         "pool workers overlap it). 0 disables.")
    ap.add_argument("--stream", action="store_true",
                    help="round-17 streaming-graph leg: serve a Zipf "
                         "trace while appending edges at a fixed rate — "
                         "zero dropped requests, empty-delta bit-parity "
                         "vs the frozen run, closure-touched "
                         "invalidation counts (-> STREAM_r01.json)")
    ap.add_argument("--stream-requests", type=int, default=400)
    ap.add_argument("--stream-edge-every", type=int, default=40,
                    help="requests between edge-arrival events")
    ap.add_argument("--stream-edges-per-event", type=int, default=4)
    ap.add_argument("--stream-stall", action="store_true",
                    help="round-24 zero-stall commit leg: commit storm "
                         "under saturated Zipf traffic, fenced vs "
                         "zero-stall twins — >=10x per-commit stall "
                         "collapse, on-commit p99 <=1.3x frozen-graph, "
                         "run-twice bit-identity, epoch-pinned oracle "
                         "parity (-> STREAM_r02.json)")
    ap.add_argument("--stream-stall-commits", type=int, default=16,
                    help="sequential storm commits per twin")
    ap.add_argument("--stream-stall-requests-per-commit", type=int,
                    default=16)
    ap.add_argument("--stream-stall-edges-per-commit", type=int, default=24)
    ap.add_argument("--stream-stall-traffic-requests", type=int, default=800,
                    help="threaded saturated-traffic requests per twin")
    ap.add_argument("--stream-stall-storm-commits", type=int, default=10,
                    help="commits racing the threaded traffic")
    ap.add_argument("--lifecycle", action="store_true",
                    help="round-21 graph-lifecycle soak: append+expire at "
                         "steady state for ~10^6 edges under live Zipf "
                         "traffic with periodic compaction — flat reserve "
                         "occupancy, zero dropped requests, zero "
                         "StreamCapacityError, in-run temporal oracle "
                         "parity rows (-> LIFECYCLE_r01.json)")
    ap.add_argument("--lifecycle-commits", type=int, default=500)
    ap.add_argument("--lifecycle-edges-per-commit", type=int, default=2000)
    ap.add_argument("--lifecycle-window-commits", type=int, default=8,
                    help="retention window in commit clock ticks — the "
                         "steady-state live set is window*edges_per_commit")
    ap.add_argument("--lifecycle-requests-per-commit", type=int, default=4)
    ap.add_argument("--lifecycle-compact-every", type=int, default=25,
                    help="commits between explicit compaction passes")
    ap.add_argument("--lifecycle-parity-every", type=int, default=100,
                    help="commits between in-run oracle parity checkpoints")
    ap.add_argument("--scale", action="store_true",
                    help="round-16 elastic-fleet leg: ramp a Zipf trace "
                         "1->2->4->2 hosts with live resharding, zero "
                         "dropped requests, epoch-aware oracle parity, "
                         "and an owner kill mid-migration "
                         "(-> SERVE_r08.json)")
    ap.add_argument("--scale-requests", type=int, default=360)
    ap.add_argument("--migrate-batch", type=int, default=120,
                    help="bounded seeds per fenced migration batch")
    ap.add_argument("--faults", action="store_true",
                    help="round-15 fleet-robustness leg: owner-kill "
                         "replay parity, availability/p99 vs hedge "
                         "deadline, replication uplift vs skew_table "
                         "(-> SERVE_r07.json)")
    ap.add_argument("--fault-requests", type=int, default=400)
    ap.add_argument("--hedge-deadlines", default="0,30,120",
                    help="hedge_deadline_ms sweep for the stall leg "
                         "(0 = no deadline)")
    ap.add_argument("--replicate-k", type=int, default=16)
    ap.add_argument("--temporal", action="store_true",
                    help="round-19 workloads leg -> WORKLOAD_r01.json: "
                         "temporal draws vs the host-masked oracle, "
                         "t=inf == frozen weighted engine, streamed-edge "
                         "per-commit visibility, hosts=2 LP pairs "
                         "through the exchange with temporal fleet "
                         "oracle parity, observe-only journal/workload "
                         "parity")
    ap.add_argument("--temporal-requests", type=int, default=320)
    ap.add_argument("--temporal-pairs", type=int, default=120)
    ap.add_argument("--temporal-recency", type=float, default=0.02)
    ap.add_argument("--temporal-quantum", type=float, default=0.05,
                    help="t_quantum in query-time units (the Poisson "
                         "clock runs at --temporal-qps)")
    ap.add_argument("--temporal-qps", type=float, default=2000.0)
    ap.add_argument("--skew", action="store_true",
                    help="run the round-13 workload-skew leg instead of "
                         "the fused/split sweep (-> SERVE_r06.json)")
    ap.add_argument("--skew-requests", type=int, default=3000)
    ap.add_argument("--skew-cache", type=int, default=64)
    ap.add_argument("--skew-alphas", default="0.8,1.1,1.3")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    hosts_sweep = [int(h) for h in args.hosts.split(",")]

    # the collective serve exchange needs one CPU device per simulated
    # host; must land before jax initializes
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(hosts_sweep + [2])}"
    ).strip()

    import jax
    import jax.numpy as jnp

    from quiver_tpu import CSRTopo
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel.scaling import format_serve_markdown, serve_table
    from quiver_tpu.pyg.sage_sampler import GraphSageSampler
    from quiver_tpu.serve import (
        DistServeConfig,
        DistServeEngine,
        FaultInjector,
        FaultSpec,
        REPLICA_HOST,
        ServeConfig,
        ServeEngine,
        poisson_arrivals,
        replay_fleet_oracle,
        replay_shard_oracle,
        trace_skew_stats,
        zipfian_trace,
    )
    from quiver_tpu.trace import WorkloadConfig, median_min_max

    edge_index, feat, n = community_graph()
    topo = CSRTopo(edge_index=edge_index)
    SIZES, SEED = [8, 8], 1
    model = GraphSAGE(hidden_dim=64, out_dim=8, num_layers=2, dropout=0.0)

    def make_full_sampler():
        return GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED)

    s0 = make_full_sampler()
    ds0 = s0.sample_dense(np.arange(args.max_batch, dtype=np.int64))
    params = model.init(
        jax.random.key(0), jnp.zeros((ds0.n_id.shape[0], feat.shape[1])), ds0.adjs
    )

    def build_dist(hosts, path, journal_events=0, workload=None):
        # a 2-bucket ladder per shard keeps compile count down (the sweep's
        # signal doesn't need bucket granularity); fused executables are
        # shared process-wide by shape, so repeats recompile nothing
        shard_cfg = ServeConfig(
            max_batch=args.max_batch,
            buckets=(8, args.max_batch),
            max_delay_ms=2.0,
            record_dispatches=True,
            dispatch_mode="fused" if path == "fused" else "split",
            journal_events=journal_events,
            workload=workload,
        )
        dist = DistServeEngine.build(
            model, params, topo, feat, SIZES, hosts=hosts,
            config=DistServeConfig(
                hosts=hosts, max_batch=args.max_batch, max_delay_ms=2.0,
                record_dispatches=True, shard_config=shard_cfg,
                feature_residency="closure" if path == "fused" else "exchange",
                journal_events=journal_events,
                workload=workload,
            ),
            sampler_seed=SEED,
        )
        dist.warmup()
        dist.reset_stats()
        return dist

    def run_once(alpha, hosts, path, check_parity, journal_events=0,
                 workload=None):
        dist = build_dist(hosts, path, journal_events=journal_events,
                          workload=workload)
        if journal_events or workload is not None:
            # honest overhead accounting: the fleet registry's adapters
            # are installed during the measured run (they are passive
            # readers, but that is the claim being measured)
            dist.fleet_registry()
        trace = zipfian_trace(n, args.requests, alpha=alpha, seed=42)
        chunks = np.array_split(trace, args.clients)
        results, errors = {}, []

        def client(tid, chunk):
            try:
                results[tid] = (chunk, dist.predict(chunk, timeout=300))
            except Exception as exc:
                errors.append(repr(exc))

        t0 = time.perf_counter()
        with dist:
            threads = [
                threading.Thread(target=client, args=(i, c))
                for i, c in enumerate(chunks)
            ]
            [t.start() for t in threads]
            [t.join() for t in threads]
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"client errors at {alpha}/{hosts}/{path}: {errors}")

        merged = dist.aggregate_stats()["shards_merged"]
        # the 2->1 dispatch ledger, asserted in-run on every repeat
        if path == "fused":
            assert merged["execute_calls"] == merged["dispatches"], merged
        else:
            assert merged["execute_calls"] == 2 * merged["dispatches"], merged

        parity_rows = 0
        if check_parity:
            # every served row must bit-match the offline replay of the
            # owning shard's dispatch log through a FULL-graph sampler
            oracle = replay_shard_oracle(dist, model, params, make_full_sampler, feat)
            for ids, out in results.values():
                for nid, row in zip(ids, out):
                    assert np.array_equal(row, oracle[int(nid)]), (
                        f"PARITY VIOLATION at node {int(nid)} "
                        f"(hosts={hosts}, path={path})"
                    )
                    parity_rows += 1
        return dist, trace, wall, parity_rows

    # -- round-19 workloads leg (--temporal -> WORKLOAD_r01.json) ------------
    if args.temporal:
        from quiver_tpu.ops.sample import (
            tiled_temporal_sample_layer,
            tiled_weighted_sample_layer,
        )
        from quiver_tpu.serve import lp_trace, temporal_trace
        from quiver_tpu.stream import StreamingTiledGraph
        from quiver_tpu.workloads import (
            TemporalDistServeEngine,
            TemporalServeEngine,
            TemporalTiledGraph,
            host_masked_oracle,
            quantize_t,
            replay_temporal_fleet_oracle,
            replay_temporal_log,
        )

        REC, QUANT = args.temporal_recency, args.temporal_quantum
        rng_t = np.random.default_rng(77)
        E = topo.indices.shape[0]
        base_ts = rng_t.uniform(0.0, 50.0, E).astype(np.float32)
        T0 = 50.0  # queries start after every base edge
        tg = TemporalTiledGraph(topo, base_ts)
        MAXD = 512

        # (a) LAYER PINS, asserted in-run over many draws: host-masked
        # oracle bit-parity + the frozen degeneration (t=inf draws ==
        # the existing weighted sampler over the recency weight tiles)
        bd_d, tiles_d, tt_d = tg.temporal_graph()
        oracle_rows = inf_rows = 0
        for rep in range(4):
            seeds = rng_t.integers(0, n, 64)
            tvals = rng_t.uniform(0.0, 60.0, 64).astype(np.float32)
            key = jax.random.fold_in(jax.random.key(13), rep)
            nb, vl = tiled_temporal_sample_layer(
                bd_d, tiles_d, tt_d, jnp.asarray(seeds),
                jnp.ones((64,), bool), 8, key, jnp.asarray(tvals),
                max_deg=MAXD, recency=REC,
            )
            onb, ovl = host_masked_oracle(
                topo.indptr, topo.indices, base_ts, seeds,
                np.ones(64, bool), 8, key, tvals, max_deg=MAXD,
                recency=REC,
            )
            assert np.array_equal(np.asarray(vl), ovl), "ORACLE VALID MISMATCH"
            assert np.array_equal(
                np.asarray(nb)[np.asarray(vl)], onb[ovl]
            ), "ORACLE DRAW MISMATCH"
            oracle_rows += int(np.asarray(vl).sum())
            wnb, wvl = tiled_weighted_sample_layer(
                bd_d, tiles_d, tg.recency_wtiles(REC), jnp.asarray(seeds),
                jnp.ones((64,), bool), 8, key, max_deg=MAXD,
            )
            inb, ivl = tiled_temporal_sample_layer(
                bd_d, tiles_d, tt_d, jnp.asarray(seeds),
                jnp.ones((64,), bool), 8, key,
                jnp.full((64,), np.inf, jnp.float32), max_deg=MAXD,
                recency=REC,
            )
            assert np.array_equal(np.asarray(ivl), np.asarray(wvl))
            assert np.array_equal(
                np.asarray(inb)[np.asarray(ivl)],
                np.asarray(wnb)[np.asarray(wvl)],
            ), "T=INF != WEIGHTED DRAW"
            inf_rows += int(np.asarray(ivl).sum())

        # (b) ENGINE t=inf pin: a temporal engine (recency 0) queried at
        # t=inf serves BIT-IDENTICAL logits + dispatch composition to
        # the existing FROZEN weighted engine over unit weights — the
        # frozen-graph run IS temporal-at-t=inf, at the serving grain
        topo_w = CSRTopo(edge_index=edge_index,
                         edge_weights=np.ones(edge_index.shape[1],
                                              np.float32))
        sw = GraphSageSampler(topo_w, sizes=SIZES, mode="TPU", seed=SEED,
                              dedup=False, weighted=True, max_deg=MAXD)
        eng_w = ServeEngine(
            model, params, sw, feat,
            ServeConfig(max_batch=args.max_batch,
                        buckets=(8, args.max_batch), max_delay_ms=1e9,
                        record_dispatches=True),
        )
        eng_w.warmup()
        st0 = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED,
                               dedup=False, max_deg=MAXD)
        st0.bind_temporal(TemporalTiledGraph(topo, base_ts), recency=0.0)
        eng_t0 = TemporalServeEngine(
            model, params, st0, feat,
            ServeConfig(max_batch=args.max_batch,
                        buckets=(8, args.max_batch), max_delay_ms=1e9,
                        record_dispatches=True),
            t_quantum=0.0,
        )
        eng_t0.warmup()
        tr_inf = zipfian_trace(n, 160, alpha=1.1, seed=21)
        rows_w = eng_w.predict(tr_inf, timeout=120)
        rows_t = eng_t0.predict(tr_inf, t=np.inf, timeout=120)
        assert np.array_equal(rows_w, rows_t), "T=INF ENGINE PARITY VIOLATION"
        assert len(eng_w.dispatch_log) == len(eng_t0.dispatch_log)
        for (pw, nw), (pt, nt, _tv) in zip(eng_w.dispatch_log,
                                           eng_t0.dispatch_log):
            assert nw == nt and np.array_equal(pw, pt)
        inf_engine_rows = len(tr_inf)

        # (c) OBSERVE-ONLY pin: journal + workload telemetry on changes
        # no served bit (same trace, instrumented twin)
        tt_trace = temporal_trace(
            n, args.temporal_requests, alpha=1.1, seed=33,
            qps=args.temporal_qps, t0=T0, edge_every=40,
            edges_per_event=4,
        )

        def run_frozen(journal_events=0, workload=None):
            s = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED,
                                 dedup=False, max_deg=MAXD)
            s.bind_temporal(TemporalTiledGraph(topo, base_ts), recency=REC)
            e = TemporalServeEngine(
                model, params, s, feat,
                ServeConfig(max_batch=args.max_batch,
                            buckets=(8, args.max_batch), max_delay_ms=1e9,
                            record_dispatches=True,
                            journal_events=journal_events,
                            workload=workload),
                t_quantum=QUANT,
            )
            e.warmup()
            rows = [
                e.predict([ev[2]], t=ev[3])[0]
                for ev in tt_trace.events() if ev[0] == "request"
            ]
            return e, rows

        eng_plain, rows_plain = run_frozen()
        eng_obs, rows_obs = run_frozen(
            journal_events=args.journal_events,
            workload=WorkloadConfig(topk=64),
        )
        assert all(np.array_equal(a, b)
                   for a, b in zip(rows_plain, rows_obs)), \
            "OBSERVE-ONLY VIOLATION (journal/workload changed bits)"
        assert len(eng_plain.dispatch_log) == len(eng_obs.dispatch_log)
        for (pa, na, ta), (pb, nb_, tb) in zip(eng_plain.dispatch_log,
                                               eng_obs.dispatch_log):
            assert na == nb_ and np.array_equal(pa, pb) \
                and np.array_equal(ta, tb)

        # single-host temporal replay parity against the twin oracle
        def mk_temporal_full():
            s = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED,
                                 dedup=False, max_deg=MAXD)
            return s.bind_temporal(TemporalTiledGraph(topo, base_ts),
                                   recency=REC)

        oracle_f = replay_temporal_log(
            eng_plain.dispatch_log, model, params, mk_temporal_full(), feat
        )
        req_list = [ev for ev in tt_trace.events() if ev[0] == "request"]
        replay_rows = 0
        for (_, _, node, tq), row in zip(req_list, rows_plain):
            k = (int(node), float(np.float32(quantize_t(tq, QUANT))))
            assert any(np.array_equal(row, c)
                       for c in oracle_f.get(k, [])), \
                f"TEMPORAL REPLAY VIOLATION at {k}"
            replay_rows += 1

        # (d) STREAMING leg: frozen == empty-delta commits, then LIVE
        # timestamped appends with per-commit visibility at ts +/- eps
        def make_stream_engine(reserve=0.5):
            stream = StreamingTiledGraph(topo, reserve_frac=reserve,
                                         edge_ts=base_ts)
            s = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED,
                                 dedup=False, max_deg=MAXD)
            s.bind_temporal(stream, recency=REC)
            e = TemporalServeEngine(
                model, params, s, feat,
                ServeConfig(max_batch=args.max_batch,
                            buckets=(8, args.max_batch), max_delay_ms=1e9,
                            record_dispatches=True),
                t_quantum=QUANT,
            )
            e.warmup()
            return e, stream

        from quiver_tpu.stream import GraphDelta

        eng_es, _ = make_stream_engine()
        rows_es = []
        for ev in tt_trace.events():
            if ev[0] == "edges":
                s = eng_es.update_graph(GraphDelta())
                assert s["edges"] == 0 and eng_es.graph_version == 0
            else:
                rows_es.append(eng_es.predict([ev[2]], t=ev[3])[0])
        assert all(np.array_equal(a, b)
                   for a, b in zip(rows_plain, rows_es)), \
            "EMPTY-DELTA TEMPORAL PARITY VIOLATION"
        empty_delta_rows = len(rows_es)

        eng_live, stream_live = make_stream_engine()
        commits = []
        visibility_checked = dropped = 0
        t_wall0 = time.perf_counter()
        for ev in tt_trace.events():
            if ev[0] == "edges":
                eng_live.stage_edges(ev[1], ev[2], ts=ev[3])
                s = eng_live.update_graph()
                commits.append({
                    "edges": s["edges"],
                    "pad_writes": s["pad_writes"],
                    "tile_spills": s["tile_spills"],
                    "cache_invalidated": s["cache_invalidated"],
                })
                # the acceptance pin: the appended edge is INVISIBLE to
                # a query at ts - eps and VISIBLE at ts + eps (copy-all
                # draw at fanout >= current degree must include it)
                u, v = int(ev[1][0]), int(ev[2][0])
                ets = float(ev[3][0])
                deg_u = stream_live.degree(u)
                g = stream_live.temporal_graph()
                for tq, want in ((ets - 1e-3, False), (ets + 1e-3, True)):
                    nb, vl = tiled_temporal_sample_layer(
                        g[0], g[1], g[2], jnp.asarray([u]),
                        jnp.ones((1,), bool), deg_u,
                        jax.random.key(9), jnp.asarray([tq], jnp.float32),
                        max_deg=MAXD, recency=REC,
                    )
                    drawn = set(
                        np.asarray(nb)[0][np.asarray(vl)[0]].tolist()
                    )
                    # v may pre-exist as an OLDER edge of u; only assert
                    # the new arrival's effect when it is the only (u,v)
                    if want:
                        assert v in drawn, "VISIBILITY: edge not drawable"
                    elif v in drawn:
                        older = [
                            w for w, et in zip(
                                stream_live.neighbors(u),
                                stream_live.adj.neighbors_ts(u),
                            ) if w == v and et <= tq
                        ]
                        assert older, "VISIBILITY: future edge drawn"
                visibility_checked += 2
            else:
                try:
                    eng_live.predict([ev[2]], t=ev[3])
                except Exception:
                    dropped += 1
        wall_live = time.perf_counter() - t_wall0
        assert dropped == 0, f"{dropped} dropped temporal requests"
        assert sum(c["cache_invalidated"] for c in commits) > 0

        # (e) hosts=2 LP leg: split-owner pairs THROUGH the exchange
        # (collective mode ships ids + bitcast query times), every
        # completed endpoint row bit-matching the temporal fleet oracle,
        # and the pair scores a pure function of those rows
        dist = TemporalDistServeEngine.build(
            model, params, topo, base_ts, feat, SIZES, hosts=2,
            config=DistServeConfig(
                hosts=2, max_batch=args.max_batch, max_delay_ms=1e9,
                exchange="collective", record_dispatches=True,
                shard_config=ServeConfig(
                    max_batch=args.max_batch,
                    buckets=(8, args.max_batch), max_delay_ms=1e9,
                    record_dispatches=True,
                ),
            ),
            sampler_seed=SEED, recency=REC, max_deg=MAXD,
            t_quantum=QUANT,
        )
        dist.warmup()
        lp = lp_trace(topo, args.temporal_pairs, alpha=1.1, seed=55,
                      qps=args.temporal_qps, t0=T0)
        owners = dist.global2host
        split_owner_pairs = int(
            (owners[lp.u] != owners[lp.v]).sum()
        )
        assert split_owner_pairs > 0, "trace has no split-owner pairs"
        handles = [
            dist.submit_pair(int(lp.u[i]), int(lp.v[i]),
                             t=float(lp.t_query[i]))
            for i in range(len(lp.u))
        ]
        while any(not h.done() for h in handles) and dist._drainable():
            dist.flush()
        scores = np.asarray([h.result(120) for h in handles], np.float32)
        oracle_d = replay_temporal_fleet_oracle(
            dist, model, params, mk_temporal_full, feat
        )
        lp_parity_rows = 0
        for i, h in enumerate(handles):
            hu, hv = h.rows()
            for node, row in ((int(lp.u[i]), hu), (int(lp.v[i]), hv)):
                k = (node, float(np.float32(
                    quantize_t(float(lp.t_query[i]), QUANT)
                )))
                assert any(np.array_equal(row, c)
                           for c in oracle_d.get(k, [])), \
                    f"LP FLEET PARITY VIOLATION at {k}"
                lp_parity_rows += 1
            re_score = dist.pair_head.score(hu[None], hv[None])[0]
            assert np.float32(re_score) == scores[i]
        pos_scores = scores[lp.label == 1]
        neg_scores = scores[lp.label == 0]

        out = {
            "metric": "serve_probe_temporal",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "requests": args.temporal_requests,
                "pairs": args.temporal_pairs, "alpha": 1.1,
                "recency": REC, "t_quantum": QUANT,
                "qps_clock": args.temporal_qps, "max_batch": args.max_batch,
                "sizes": SIZES, "nodes": n, "max_deg": MAXD,
            },
            "note": (
                "sequential deterministic drive (walls are 1-core "
                "loopback, read the structure); every parity claim is "
                "asserted in-run — a written artifact means they held: "
                "host-masked oracle bit-parity, t=inf == frozen weighted "
                "engine (draws AND served logits), observe-only "
                "journal/workload, frozen == empty-delta commits, "
                "per-commit ts+/-eps visibility, hosts=2 LP endpoint "
                "rows == temporal fleet oracle"
            ),
            "layer_oracle_parity_draws": oracle_rows,
            "layer_t_inf_weighted_parity_draws": inf_rows,
            "engine_t_inf_parity_rows": inf_engine_rows,
            "observe_only_parity_rows": len(rows_plain),
            "single_host_replay_parity_rows": replay_rows,
            "empty_delta_parity_rows": empty_delta_rows,
            "streaming_live": {
                "dropped_requests": dropped,
                "commits": len(commits),
                "delta_edges": eng_live.stats.delta_edges,
                "tile_writes": eng_live.stats.delta_tile_writes,
                "tile_spills": eng_live.stats.delta_tile_spills,
                "cache_invalidated": (
                    eng_live.stats.delta_cache_invalidated
                ),
                "visibility_checks": visibility_checked,
                "reserve_report": stream_live.reserve_report(),
                "qps": round(args.temporal_requests / wall_live, 1),
            },
            "lp_hosts2": {
                "pairs": int(len(lp.u)),
                "split_owner_pairs": split_owner_pairs,
                "endpoint_parity_rows": lp_parity_rows,
                "exchange_id_bytes": dist.stats.exchange_id_bytes,
                "exchange_logit_bytes": dist.stats.exchange_logit_bytes,
                "coalesced": dist.stats.coalesced,
                "router_cache_hits": dist.stats.router_cache.hits,
                "mean_pos_score": float(pos_scores.mean())
                if pos_scores.size else None,
                "mean_neg_score": float(neg_scores.mean())
                if neg_scores.size else None,
            },
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-17 streaming-graph leg (--stream -> STREAM_r01.json) ----------
    if args.stream:
        from quiver_tpu.ops.sample import tiled_sample_layer
        from quiver_tpu.serve import delta_interleaved_trace
        from quiver_tpu.stream import GraphDelta, StreamingTiledGraph

        dt = delta_interleaved_trace(
            n, args.stream_requests, alpha=1.1, seed=31,
            edge_every=args.stream_edge_every,
            edges_per_event=args.stream_edges_per_event,
        )
        # cross-community arrivals: half the destinations re-drawn into
        # a DIFFERENT community than their source, so commits exercise
        # real closure extension, not just pad-lane appends
        rng_x = np.random.default_rng(32)
        per_comm = n // 4
        for i in range(dt.n_events):
            for j in range(0, args.stream_edges_per_event, 2):
                cu = int(dt.edge_src[i, j]) // per_comm
                cv = (cu + 1 + rng_x.integers(0, 3)) % 4
                dt.edge_dst[i, j] = cv * per_comm + rng_x.integers(
                    0, per_comm
                )

        def make_single(stream=None):
            smp = GraphSageSampler(topo, sizes=SIZES, mode="TPU",
                                   seed=SEED)
            if stream is not None:
                smp.bind_stream(stream)
            return ServeEngine(
                model, params, smp, feat,
                ServeConfig(max_batch=args.max_batch,
                            max_delay_ms=1e9,
                            record_dispatches=True),
            )

        # (a) PARITY LEG: frozen-graph run vs streaming run committing an
        # EMPTY delta at every event position — bit-identical logits and
        # dispatch logs, asserted in-run
        eng_f = make_single()
        eng_f.warmup()
        rows_f = [eng_f.predict([node])[0]
                  for _, _, node in
                  (e for e in dt.events() if e[0] == "request")]
        stream_e = StreamingTiledGraph(topo, reserve_frac=0.5)
        eng_e = make_single(stream_e)
        eng_e.warmup()
        rows_e = []
        for ev in dt.events():
            if ev[0] == "edges":
                s = eng_e.update_graph(GraphDelta())
                assert s["edges"] == 0 and eng_e.graph_version == 0
            else:
                rows_e.append(eng_e.predict([ev[2]])[0])
        assert all(np.array_equal(a, b) for a, b in zip(rows_f, rows_e)), \
            "EMPTY-DELTA PARITY VIOLATION"
        assert len(eng_f.dispatch_log) == len(eng_e.dispatch_log)
        for (pa, na), (pb, nb) in zip(eng_f.dispatch_log,
                                      eng_e.dispatch_log):
            assert na == nb and np.array_equal(pa, pb)
        parity_rows = len(rows_f)

        # (b) LIVE single-host stream: commit real deltas at the event
        # positions, count closure-touched invalidations, assert
        # per-commit visibility (copy-all draw of the appended source
        # must include the new destination), zero dropped requests
        stream_l = StreamingTiledGraph(topo, reserve_frac=0.5)
        eng_l = make_single(stream_l)
        eng_l.warmup()
        commits = []
        dropped = visibility_checked = 0
        t0 = time.perf_counter()
        for ev in dt.events():
            if ev[0] == "edges":
                d = GraphDelta()
                d.add_edges(ev[1], ev[2])
                s = eng_l.update_graph(d)
                commits.append({
                    "edges": s["edges"],
                    "pad_writes": s["pad_writes"],
                    "tile_spills": s["tile_spills"],
                    "affected_seeds": s["affected_seeds"],
                    "cache_invalidated": s["cache_invalidated"],
                })
                u, v = int(ev[1][0]), int(ev[2][0])
                k = stream_l.degree(u)
                bd_d, tiles_d = stream_l.graph()
                nb, vl = tiled_sample_layer(
                    bd_d, tiles_d, jnp.asarray([u]),
                    jnp.ones((1,), bool), k, jax.random.key(7),
                )
                assert v in set(
                    np.asarray(nb)[0][np.asarray(vl)[0]].tolist()
                ), "VISIBILITY VIOLATION: appended edge not drawable"
                visibility_checked += 1
            else:
                try:
                    eng_l.predict([ev[2]])
                except Exception:
                    dropped += 1
        wall_live = time.perf_counter() - t0
        assert dropped == 0, f"{dropped} dropped requests under streaming"
        assert sum(c["cache_invalidated"] for c in commits) > 0

        # (c) STREAMING FLEET at hosts=2 with replication: same schedule
        # through the routed engine; every completed row must bit-match
        # a pre- or post-delta full-graph oracle candidate
        # reserve 1.0x the built size: cross-community arrivals pull
        # whole communities into an owner's closure, so the fleet plans
        # for up to a full doubling (capacity planning IS the contract —
        # exhaustion is a loud StreamCapacityError, never silent growth)
        cfg2 = DistServeConfig(
            hosts=2, max_batch=args.max_batch, max_delay_ms=1e9,
            exchange="host", record_dispatches=True, streaming=True,
            stream_reserve_frac=1.0,
            replicate_top_k=16, workload=WorkloadConfig(topk=64),
        )
        dist = DistServeEngine.build(
            model, params, topo, feat, SIZES, hosts=2, config=cfg2,
            sampler_seed=SEED,
        )
        dist.warmup()
        rows_d, nodes_d = [], []
        dropped_d = 0
        refreshed = False
        topo_versions = [topo]  # every graph version the fleet served
        t0 = time.perf_counter()
        for ev in dt.events():
            if ev[0] == "edges":
                dist.stage_edges(ev[1], ev[2])
                s = dist.update_graph()
                topo_versions.append(dist._stream_adj.to_csr_topo())
                if not refreshed and dist.workload.hot_set(16).size >= 8:
                    # replicate the live head once telemetry has one
                    dist.refresh_replicas(k=16)
                    refreshed = True
            else:
                h = dist.submit(ev[2])
                while dist._drainable():
                    dist.flush()
                try:
                    rows_d.append(h.result(60))
                    nodes_d.append(ev[2])
                except Exception:
                    dropped_d += 1
        wall_dist = time.perf_counter() - t0
        assert dropped_d == 0, f"{dropped_d} dropped routed requests"
        # parity across graph VERSIONS: a row served between commits v
        # and v+1 was computed on graph version v — it must bit-match a
        # candidate from the fleet replay over SOME version the fleet
        # actually served (the per-version replay is exhaustive because
        # every version's topology was snapshotted at its commit)
        oracles = []
        for tv in topo_versions:
            def mk(tv=tv):
                return GraphSageSampler(tv, sizes=SIZES, mode="TPU",
                                        seed=SEED)
            oracles.append(replay_fleet_oracle(dist, model, params, mk,
                                               feat))
        parity_dist = 0
        for node, row in zip(nodes_d, rows_d):
            cands = [c for o in oracles for c in o.get(int(node), [])]
            assert any(np.array_equal(row, c) for c in cands), \
                f"STREAM-PARITY VIOLATION at node {int(node)}"
            parity_dist += 1

        out = {
            "metric": "serve_probe_stream",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "requests": args.stream_requests, "alpha": 1.1,
                "edge_every": args.stream_edge_every,
                "edges_per_event": args.stream_edges_per_event,
                "max_batch": args.max_batch, "sizes": SIZES,
                "nodes": n, "stream_reserve_frac": 0.5,
            },
            "note": (
                "sequential deterministic drive (QPS numbers are 1-core "
                "loopback walls, read the structure not the absolute); "
                "empty-delta parity, per-commit visibility, zero-drop "
                "and fleet oracle parity are asserted in-run — a "
                "written artifact means they held"
            ),
            "empty_delta_parity_rows": parity_rows,
            "single_host_live": {
                "dropped_requests": dropped,
                "commits": commits,
                "graph_version": eng_l.graph_version,
                "delta_edges": eng_l.stats.delta_edges,
                "tile_writes": eng_l.stats.delta_tile_writes,
                "tile_spills": eng_l.stats.delta_tile_spills,
                "cache_invalidated": eng_l.stats.delta_cache_invalidated,
                "visibility_checks": visibility_checked,
                "free_tile_rows_left": stream_l.free_rows,
                "qps": round(args.stream_requests / wall_live, 1),
            },
            "fleet_hosts2": {
                "dropped_requests": dropped_d,
                "parity_rows_checked": parity_dist,
                "graph_version": dist.graph_version,
                "delta_edges": dist.stats.delta_edges,
                "closure_installs": dist.stats.delta_closure_installs,
                "router_cache_invalidated": (
                    dist.stats.delta_cache_invalidated
                ),
                "replica_delta_invalidations": (
                    dist.stats.replica_delta_invalidations
                ),
                "replica_version": dist.replica_version,
                "qps": round(args.stream_requests / wall_dist, 1),
            },
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-24 zero-stall commit leg (--stream-stall -> STREAM_r02.json) --
    if args.stream_stall:
        COMMITS = args.stream_stall_commits
        RPC = args.stream_stall_requests_per_commit
        EPC = args.stream_stall_edges_per_commit
        rng_s = np.random.default_rng(29)
        req_nodes = zipfian_trace(n, COMMITS * RPC, alpha=1.1, seed=41)
        edge_src = zipfian_trace(n, COMMITS * EPC, alpha=1.1, seed=43)
        edge_dst = rng_s.integers(0, n, COMMITS * EPC)

        def build_storm(fenced):
            # reserve 1.0x the built size: Zipf-src arrivals with random
            # destinations pull foreign communities into owner closures
            # (same capacity-planning contract as the --stream leg)
            cfg = DistServeConfig(
                hosts=2, max_batch=args.max_batch, max_delay_ms=1e9,
                exchange="host", record_dispatches=True, streaming=True,
                stream_reserve_frac=1.0, fenced_commits=fenced,
            )
            d = DistServeEngine.build(
                model, params, topo, feat, SIZES, hosts=2, config=cfg,
                sampler_seed=SEED,
            )
            d.warmup()
            return d

        def run_storm(fenced):
            """Deterministic sequential commit storm: a block of Zipf
            requests drained to completion, then a delta commit, COMMITS
            times over. Every served row's epoch is the graph version
            current at its flush (recorded here and — the round-24 pin —
            stamped on the dispatch log rows by the engines themselves)."""
            d = build_storm(fenced)
            rows, vers, stalls = [], [], []
            topo_vs = [topo]  # version v's full-graph topology snapshot
            dropped = 0
            for k in range(COMMITS):
                nodes_k = req_nodes[k * RPC:(k + 1) * RPC]
                hs = [d.submit(int(x)) for x in nodes_k]
                while any(not h.done() for h in hs) and d._drainable():
                    d.flush()
                for h in hs:
                    try:
                        rows.append(np.asarray(h.result(60)))
                        vers.append(d.graph_version)
                    except Exception:
                        dropped += 1
                lo = k * EPC
                d.stage_edges(edge_src[lo:lo + EPC], edge_dst[lo:lo + EPC])
                s = d.update_graph()
                stalls.append(float(s["commit_stall_us"]))
                topo_vs.append(d._stream_adj.to_csr_topo())
            return d, rows, vers, stalls, topo_vs, dropped

        def log_entries(d):
            """Flatten every array the run's dispatch state is made of —
            router log (padded seeds + owner splits), per-host shard logs,
            and all the epoch stamps — for byte-for-byte comparison."""
            out = [np.asarray(d.dispatch_graph_versions, np.int64)]
            for padded, splits in d.dispatch_log:
                out.append(np.asarray(padded))
                for hid, part in splits:
                    out.append(np.asarray([hid]))
                    out.append(np.asarray(part))
            for h in sorted(d.engines):
                eng = d.engines[h]
                out.append(np.asarray(eng.dispatch_graph_versions, np.int64))
                for padded, nvalid in eng.dispatch_log:
                    out.append(np.asarray(padded))
                    out.append(np.asarray([nvalid]))
            return out

        d_zs, rows_zs, vers_zs, stalls_zs, topo_vs, drop_zs = run_storm(False)
        d_f, rows_f, _, stalls_f, _, drop_f = run_storm(True)
        assert drop_zs == 0 and drop_f == 0, "dropped requests in storm"

        # fenced twin parity: the sequential drive admits no races, so the
        # round-23 drain discipline and the zero-stall flip must serve
        # bit-identical logits over identical dispatch state
        assert len(rows_zs) == len(rows_f)
        for a, b in zip(rows_zs, rows_f):
            assert np.array_equal(a, b), "FENCED/ZERO-STALL TWIN DIVERGENCE"
        ents_zs, ents_f = log_entries(d_zs), log_entries(d_f)
        assert len(ents_zs) == len(ents_f)
        for a, b in zip(ents_zs, ents_f):
            assert np.array_equal(a, b), "TWIN DISPATCH-STATE DIVERGENCE"

        # >=10x per-commit stall collapse: the fenced twin's stall is the
        # whole drain+apply hold, the zero-stall twin's is the flip only
        mean_f, mean_zs = float(np.mean(stalls_f)), float(np.mean(stalls_zs))
        assert mean_zs > 0.0
        stall_ratio = mean_f / mean_zs
        assert stall_ratio >= 10.0, (
            f"STALL REDUCTION {stall_ratio:.1f}x < 10x "
            f"(fenced {mean_f:.0f}us, zero-stall {mean_zs:.0f}us)"
        )

        # 100% epoch-aware oracle parity: every served row bit-matches a
        # candidate from the replay of ITS OWN computation epoch — the
        # per-version fleet oracle over the stamped dispatch logs, each
        # replayed through a full-graph sampler built from that version's
        # topology snapshot. A row served at fleet version v may have
        # been COMPUTED at any epoch <= v (an un-invalidated cache entry
        # is exactly a pre-commit row whose closure the commits never
        # touched), so the candidate set is the union over epochs <= v —
        # never a future epoch, and never a cross-epoch mixture (each
        # oracle only collects rows stamped with its own version).
        oracles = {}
        for v, tv in enumerate(topo_vs):
            def mk(tv=tv):
                return GraphSageSampler(tv, sizes=SIZES, mode="TPU",
                                        seed=SEED)
            oracles[v] = replay_fleet_oracle(d_zs, model, params, mk, feat,
                                             graph_version=v)
        epoch_parity_rows = 0
        for node, row, v in zip(req_nodes, rows_zs, vers_zs):
            assert any(
                any(np.array_equal(row, c)
                    for c in oracles[v2].get(int(node), []))
                for v2 in range(v + 1)
            ), f"EPOCH PARITY VIOLATION at node {int(node)} version {v}"
            epoch_parity_rows += 1

        # run-twice bit-identity on the zero-stall storm: logits, router
        # and shard dispatch logs, and every epoch stamp, byte for byte
        d_zs2, rows_zs2, vers_zs2, _, _, drop2 = run_storm(False)
        assert drop2 == 0
        ident_bytes = 0
        assert vers_zs == vers_zs2
        for a, b in zip(rows_zs, rows_zs2):
            assert a.tobytes() == b.tobytes(), "RUN-TWICE LOGIT DIVERGENCE"
            ident_bytes += a.nbytes
        ents2 = log_entries(d_zs2)
        assert len(ents_zs) == len(ents2)
        for a, b in zip(ents_zs, ents2):
            assert a.tobytes() == b.tobytes(), \
                "RUN-TWICE DISPATCH-STATE DIVERGENCE"
            ident_bytes += a.nbytes

        # (b) SATURATED threaded traffic with a commit storm racing
        # in-flight flushes (max_in_flight=2): on-commit request latency
        # vs a frozen-graph twin, plus the fenced twin for contrast.
        # CONTROL (this is a 1-core loopback box): the commit BUILD is
        # off-fence but still burns CPU the clients would otherwise get,
        # so the frozen twin runs the SAME commit schedule against a
        # detached ballast engine that serves nothing — both twins pay
        # identical build CPU and the on-commit delta isolates the fence
        # discipline, which is the claim under test.
        TRAFFIC = args.stream_stall_traffic_requests
        STORM = args.stream_stall_storm_commits
        t_nodes = zipfian_trace(n, TRAFFIC, alpha=1.1, seed=47)
        storm_src = zipfian_trace(n, STORM * EPC, alpha=1.1, seed=53)
        storm_dst = rng_s.integers(0, n, STORM * EPC)
        warm_src = zipfian_trace(n, 2 * EPC, alpha=1.1, seed=59)
        warm_dst = rng_s.integers(0, n, 2 * EPC)

        def run_traffic(fenced, commits_on):
            d = build_storm(fenced)
            target = d if commits_on else build_storm(False)
            # two unmeasured commits so scatter-shape compiles never land
            # inside a measured window
            for k in range(2):
                target.stage_edges(warm_src[k * EPC:(k + 1) * EPC],
                                   warm_dst[k * EPC:(k + 1) * EPC])
                target.update_graph()
            lat, errs = [], []
            lock = threading.Lock()
            chunks = np.array_split(t_nodes, args.clients)

            def client(chunk):
                for node in chunk:
                    t0 = time.perf_counter()
                    try:
                        h = d.submit(int(node))
                        while not h.done() and d._drainable():
                            d.flush()
                        h.result(120)
                    except Exception as exc:
                        errs.append(repr(exc))
                        continue
                    with lock:
                        lat.append((t0, time.perf_counter()))

            windows = []
            threads = [threading.Thread(target=client, args=(c,))
                       for c in chunks]
            [t.start() for t in threads]
            for k in range(STORM):
                lo = k * EPC
                target.stage_edges(storm_src[lo:lo + EPC],
                                   storm_dst[lo:lo + EPC])
                c0 = time.perf_counter()
                target.update_graph()
                windows.append((c0, time.perf_counter()))
                time.sleep(0.02)
            [t.join() for t in threads]
            assert not errs, f"traffic errors: {errs}"
            return d, lat, windows

        def on_commit_lat(lat, windows):
            return [t1 - t0 for (t0, t1) in lat
                    if any(t0 < we and t1 > wb for (wb, we) in windows)]

        _, lat_fr, win_fr = run_traffic(False, commits_on=False)
        on_fr = on_commit_lat(lat_fr, win_fr)
        assert len(on_fr) >= 8, f"only {len(on_fr)} frozen-twin samples"
        p99_frozen = float(np.percentile(on_fr, 99))
        p99_frozen_all = float(np.percentile(
            [t1 - t0 for t0, t1 in lat_fr], 99))
        _, lat_tz, win_tz = run_traffic(False, commits_on=True)
        on_tz = on_commit_lat(lat_tz, win_tz)
        assert len(on_tz) >= 8, f"only {len(on_tz)} on-commit samples"
        p99_on_zs = float(np.percentile(on_tz, 99))
        _, lat_tf, win_tf = run_traffic(True, commits_on=True)
        on_tf = on_commit_lat(lat_tf, win_tf)
        p99_on_f = float(np.percentile(on_tf, 99)) if on_tf else None
        assert p99_on_zs <= 1.3 * p99_frozen, (
            f"ON-COMMIT P99 {p99_on_zs * 1e3:.2f} ms > 1.3x frozen-graph "
            f"{p99_frozen * 1e3:.2f} ms"
        )

        out = {
            "metric": "serve_probe_stream_stall",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "commits": COMMITS, "requests_per_commit": RPC,
                "edges_per_commit": EPC, "alpha": 1.1, "hosts": 2,
                "max_batch": args.max_batch, "sizes": SIZES, "nodes": n,
                "traffic_requests": TRAFFIC, "storm_commits": STORM,
                "clients": args.clients,
            },
            "note": (
                "sequential storm is a deterministic drive (stall "
                "means are 1-core loopback walls, read the ratio); "
                "fenced-twin bit-parity, >=10x stall collapse, "
                "epoch-aware oracle parity, run-twice bit-identity, "
                "zero drops and on-commit p99 <=1.3x frozen-graph are "
                "asserted in-run — a written artifact means they held. "
                "The frozen twin runs the same commit schedule against "
                "a detached ballast engine (1-core control: both twins "
                "pay identical off-fence build CPU, so the on-commit "
                "delta isolates the fence discipline)"
            ),
            "storm": {
                "commit_stall_us_fenced": {
                    "mean": round(mean_f, 1),
                    "max": round(max(stalls_f), 1),
                },
                "commit_stall_us_zerostall": {
                    "mean": round(mean_zs, 1),
                    "max": round(max(stalls_zs), 1),
                },
                "stall_reduction_x": round(stall_ratio, 1),
                "stall_hist_zerostall": (
                    d_zs.stats.commit_stall.snapshot()
                ),
                "served_rows": len(rows_zs),
                "epoch_parity_rows": epoch_parity_rows,
                "graph_versions_served": sorted(set(vers_zs)),
                "graph_version_end": d_zs.graph_version,
                "run_twice_identical_bytes": ident_bytes,
                "dropped_requests": 0,
            },
            "saturated_traffic": {
                "p99_ms_frozen_ballast_windows": round(p99_frozen * 1e3, 3),
                "p99_ms_frozen_all": round(p99_frozen_all * 1e3, 3),
                "on_commit_p99_ms_zerostall": round(p99_on_zs * 1e3, 3),
                "on_commit_p99_ms_fenced": (
                    round(p99_on_f * 1e3, 3)
                    if p99_on_f is not None else None
                ),
                "on_commit_vs_frozen_x": round(p99_on_zs / p99_frozen, 3),
                "on_commit_samples_frozen": len(on_fr),
                "on_commit_samples_zerostall": len(on_tz),
                "on_commit_samples_fenced": len(on_tf),
            },
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-21 graph-lifecycle soak (--lifecycle -> LIFECYCLE_r01.json) ---
    if args.lifecycle:
        from quiver_tpu.stream import StreamingTiledGraph
        from quiver_tpu.workloads import (
            TemporalServeEngine,
            TemporalTiledGraph,
            quantize_t,
            replay_temporal_log,
        )

        MAXD = 512
        REC, QUANT = 0.02, 0.05
        T0, DT = 50.0, 1.0
        W = args.lifecycle_window_commits * DT
        EPC = args.lifecycle_edges_per_commit
        COMMITS = args.lifecycle_commits

        rng_lc = np.random.default_rng(123)
        E = topo.indices.shape[0]
        base_ts = rng_lc.uniform(0.0, 50.0, E).astype(np.float32)

        stream_lc = StreamingTiledGraph(topo, reserve_frac=1.0,
                                        edge_ts=base_ts)
        # pre-size the reserve for the steady-state live set: the window
        # holds window_commits*EPC streamed lanes, plus one partial tile
        # row per touched node and spill-chain slack. NO auto-provision
        # backstop is configured below — a StreamCapacityError anywhere
        # in the soak fails the probe, which is the acceptance pin.
        live_lanes = args.lifecycle_window_commits * EPC
        want_rows = 4 * (live_lanes // 128 + 1) + 2 * n
        if stream_lc.free_rows < want_rows:
            stream_lc.provision_reserve(want_rows - stream_lc.free_rows)

        s_lc = GraphSageSampler(topo, sizes=SIZES, mode="TPU", seed=SEED,
                                dedup=False, max_deg=MAXD)
        s_lc.bind_temporal(stream_lc, recency=REC)
        eng = TemporalServeEngine(
            model, params, s_lc, feat,
            ServeConfig(max_batch=args.max_batch,
                        buckets=(8, args.max_batch), max_delay_ms=1e9,
                        record_dispatches=True,
                        stream_retention_window=W,
                        stream_compact_min_reclaim=8,
                        stream_provision_tiles=0),
            t_quantum=QUANT,
        )
        eng.warmup()

        total_edges = COMMITS * EPC
        app_src = zipfian_trace(n, total_edges, alpha=1.1, seed=31)
        app_dst = rng_lc.integers(0, n, total_edges)
        qry = zipfian_trace(n, COMMITS * args.lifecycle_requests_per_commit,
                            alpha=1.1, seed=17)
        RM_PER = max(EPC // 100, 1)  # deletes ride along every commit

        occ, dropped, cap_errors, parity_rows = [], 0, 0, 0
        compact_passes = rows_reclaimed = 0
        prev_batch = None
        t_wall0 = time.perf_counter()
        for k in range(COMMITS):
            lo = k * EPC
            src_k = app_src[lo:lo + EPC]
            dst_k = app_dst[lo:lo + EPC]
            # commit-k arrivals land inside (T0+k*DT, T0+(k+1)*DT]
            ts_k = (T0 + k * DT
                    + (np.arange(EPC) + 1.0) / EPC * DT).astype(np.float32)
            eng.stage_edges(src_k, dst_k, ts=ts_k)
            if prev_batch is not None:
                # delete a slice of LAST commit's arrivals — live, well
                # inside the window, exercising lane-shift removal under
                # traffic (each picked index is one appended copy, so
                # existence holds even across duplicate pairs)
                eng.stage_removals(prev_batch[0][:RM_PER],
                                   prev_batch[1][:RM_PER])
            prev_batch = (src_k, dst_k)
            try:
                eng.update_graph()  # retention expires at commit time
            except Exception as exc:
                cap_errors += 1
                raise AssertionError(
                    f"LIFECYCLE: commit {k} failed ({exc!r})"
                ) from exc
            tc = T0 + (k + 1) * DT

            # live Zipf traffic between commits
            qlo = k * args.lifecycle_requests_per_commit
            nodes_k = qry[qlo:qlo + args.lifecycle_requests_per_commit]
            try:
                eng.predict([int(x) for x in nodes_k], t=tc + 0.5 * DT)
            except Exception:
                dropped += 1

            occ.append(int(stream_lc.reserve_report()["reserve_used"]))

            if (k + 1) % args.lifecycle_compact_every == 0:
                cs = eng.compact_graph()
                compact_passes += 1
                rows_reclaimed += cs["tiles_reclaimed"]

            if (k + 1) % args.lifecycle_parity_every == 0:
                # in-run oracle parity at serving grain: rows served NOW
                # must bit-match a fresh rebuild of the live stream
                # ((topo, ts) materialized in tile-lane order) replayed
                # through a twin sampler with a synced key stream
                call0 = s_lc._call
                off = len(eng.dispatch_log)
                tq = tc + 0.25 * DT
                chk_nodes = [int(x) for x in nodes_k]
                rows = eng.predict(chk_nodes, t=tq)
                topo2, ts2 = stream_lc.adj.to_temporal()
                s2 = GraphSageSampler(topo2, sizes=SIZES, mode="TPU",
                                      seed=SEED, dedup=False, max_deg=MAXD)
                s2.bind_temporal(TemporalTiledGraph(
                    topo2, ts2, id_dtype=stream_lc.tiles.dtype), recency=REC)
                s2._call = call0
                oracle = replay_temporal_log(
                    eng.dispatch_log[off:], model, params, s2, feat)
                kq = float(np.float32(quantize_t(tq, QUANT)))
                for node, row in zip(chk_nodes, rows):
                    assert any(np.array_equal(row, c)
                               for c in oracle.get((node, kq), [])), \
                        f"LIFECYCLE PARITY VIOLATION at node {node}"
                    parity_rows += 1
        wall = time.perf_counter() - t_wall0

        assert dropped == 0, f"{dropped} dropped requests under lifecycle"
        assert cap_errors == 0
        assert parity_rows > 0
        # flat occupancy: once the window has filled (plus one compaction
        # period for the first trim), reserve consumption stops trending —
        # expired lanes are reused in place and compaction returns spill
        # waste, so the band stays within 25% of its floor
        warm = 2 * args.lifecycle_window_commits + args.lifecycle_compact_every
        assert warm < COMMITS, "soak too short for a steady-state claim"
        steady = occ[warm:]
        band = max(steady) - min(steady)
        # "flat" means BOUNDED AND NOT LINEARLY TRENDING, not
        # saw-tooth-free: between compaction passes spills accumulate and
        # each pass trims them back, and the per-cycle floor carries the
        # one growth in-place expiry cannot reclaim — a hot node's
        # high-water footprint (interior dead lanes under a live tail
        # stay allocated; shifting live lanes would break the
        # observe-only pin), a running max that creeps ~log(t). A LEAK
        # is linear: appends permanently outrunning expiry+trim would
        # add live_lanes/window rows per window. Pin the distinction
        # three ways: the floor creep over the whole soak stays inside
        # the high-water envelope (<= 50% over the first cycle's floor),
        # occupancy never exceeds the provisioned live-set bound, and
        # the projected reserve runway (from measured creep) is >= 20
        # soaks long.
        per = args.lifecycle_compact_every
        floors = [min(steady[i:i + per]) for i in range(0, len(steady), per)]
        trace = ",".join(str(x) for x in occ[::max(len(occ) // 50, 1)])
        assert floors[-1] <= floors[0] + max(16, int(0.5 * floors[0])), \
            f"LIFECYCLE: occupancy floor climbing {floors} (occ {trace})"
        assert band <= max(32, 2 * per + int(0.5 * min(steady))), \
            f"LIFECYCLE: occupancy not flat (band {band} rows over " \
            f"[{min(steady)}, {max(steady)}]; floors {floors}; occ {trace})"
        assert max(occ) <= want_rows, \
            f"LIFECYCLE: occupancy {max(occ)} exceeded live-set bound " \
            f"{want_rows}"
        runway = stream_lc.reserve_report()["projected_commits_to_exhaustion"]
        assert runway is None or runway >= 20 * COMMITS, \
            f"LIFECYCLE: reserve runway {runway} commits < 20 soaks"

        rep_end = stream_lc.reserve_report()
        out = {
            "metric": "serve_probe_lifecycle",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "commits": COMMITS, "edges_per_commit": EPC,
                "window_commits": args.lifecycle_window_commits,
                "requests_per_commit": args.lifecycle_requests_per_commit,
                "compact_every": args.lifecycle_compact_every,
                "parity_every": args.lifecycle_parity_every,
                "removals_per_commit": RM_PER, "alpha": 1.1,
                "max_batch": args.max_batch, "sizes": SIZES, "nodes": n,
                "recency": REC, "t_quantum": QUANT,
            },
            "note": (
                "sequential deterministic soak (walls are 1-core loopback, "
                "read the structure); zero-drop, zero StreamCapacityError "
                "(no auto-provision backstop configured), bounded "
                "non-trending occupancy (saw-tooth trimmed per compaction "
                "cycle; floor creep inside the hot-node high-water "
                "envelope; >=20-soak projected runway), and fresh-rebuild "
                "oracle parity are asserted in-run — a written artifact "
                "means they held"
            ),
            "edges_appended": int(eng.stats.delta_edges),
            "edges_expired": int(eng.stats.edges_expired),
            "edges_deleted": int(eng.stats.edges_deleted),
            "commits": COMMITS,
            "graph_version": eng.graph_version,
            "compaction_passes": compact_passes,
            "tile_rows_reclaimed": rows_reclaimed,
            "parity_rows": parity_rows,
            "dropped_requests": dropped,
            "capacity_errors": cap_errors,
            "occupancy_rows": {
                "at_warmup": occ[warm - 1], "steady_min": min(steady),
                "steady_max": max(steady), "end": occ[-1],
                "band": band, "cycle_floors": floors,
            },
            "reserve_report": rep_end,
            "edges_per_s": round(total_edges / wall, 1),
            "wall_s": round(wall, 1),
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-16 elastic-fleet leg (--scale -> SERVE_r08.json) --------------
    if args.scale:
        from quiver_tpu.parallel.scaling import (
            fleet_table, format_fleet_markdown, pick_fleet_action,
        )
        from quiver_tpu.trace import WorkloadConfig as _WC

        RAMP = (2, 4, 2)

        def build_elastic(**kw):
            """Host-mode hosts=1 fleet, closure residency (the fused
            owner path live resharding rides), sketches on so the fleet
            can SEE its own load."""
            shard_cfg = ServeConfig(
                max_batch=args.max_batch, buckets=(8, args.max_batch),
                max_delay_ms=2.0, record_dispatches=True,
            )
            cfg = DistServeConfig(
                hosts=1, max_batch=args.max_batch, max_delay_ms=2.0,
                record_dispatches=True, shard_config=shard_cfg,
                exchange="host", migrate_batch_seeds=args.migrate_batch,
                workload=_WC(topk=64), **kw,
            )
            dist = DistServeEngine.build(
                model, params, topo, feat, SIZES, hosts=1, config=cfg,
                sampler_seed=SEED,
            )
            dist.warmup()
            dist.reset_stats()
            return dist

        def serve_seq(dist, trace, timeout=300):
            # array-at-a-time replay (round 20): submit_many makes the
            # same admission decisions as the per-request loop, pinned
            handles = dist.submit_many(np.asarray(trace, np.int64))
            while dist._drainable():
                dist.flush()
            out = []
            for h in handles:
                try:
                    out.append(h.result(timeout))
                except Exception as exc:
                    out.append(exc)
            return out

        trace_s = zipfian_trace(n, args.scale_requests, alpha=1.1, seed=61)

        def ramp(fault_specs=(), **kw):
            """Drive one wave per fleet size across the 1->RAMP ramp,
            scaling live between waves. Returns everything the parity
            and replay comparisons need."""
            inj = FaultInjector(fault_specs) if fault_specs else None
            dist = build_elastic(
                fault_injector=inj,
                full_graph_fallback=bool(fault_specs), **kw,
            )
            waves, walls, summaries = [], [], []
            t0 = time.perf_counter()
            waves.append(serve_seq(dist, trace_s))
            walls.append(time.perf_counter() - t0)
            for h in RAMP:
                summaries.append(dist.scale(h))
                t0 = time.perf_counter()
                waves.append(serve_seq(dist, trace_s))
                walls.append(time.perf_counter() - t0)
            return dist, inj, waves, walls, summaries

        def parity_and_drops(dist, waves):
            oracle = replay_fleet_oracle(
                dist, model, params, make_full_sampler, feat
            )
            dropped = checked = 0
            for w in waves:
                for nid, row in zip(trace_s, w):
                    if isinstance(row, Exception):
                        dropped += 1
                        continue
                    assert any(
                        np.array_equal(row, c) for c in oracle[int(nid)]
                    ), f"SCALE-PARITY VIOLATION at node {int(nid)}"
                    checked += 1
            return checked, dropped

        # (a) THE acceptance leg: clean 1->2->4->2 ramp under the live
        # Zipf trace — ZERO dropped requests, bit-parity of every
        # completed row against the epoch-aware fleet oracle, asserted
        # in-run
        dist_c, _, waves_c, walls_c, summaries_c = ramp()
        # one more wave at the SETTLED hosts=2 fleet with fresh owner
        # clocks: fleet_table (leg c) prices dispatch from the final
        # fleet's per-owner routed-leg mean — a whole-ramp wall would
        # average four fleet sizes and fold in router/submit overhead.
        # Drop the router result cache first or the repeated trace is
        # absorbed before it ever times an owner leg.
        dist_c.cache.invalidate()
        dist_c.workload.owners.clear()
        t0 = time.perf_counter()
        waves_c.append(serve_seq(dist_c, trace_s))
        walls_c.append(time.perf_counter() - t0)
        checked, dropped = parity_and_drops(dist_c, waves_c)
        assert dropped == 0, f"{dropped} dropped requests on a clean ramp"
        assert checked == len(waves_c) * trace_s.size
        assert sum(s["rollbacks"] for s in summaries_c) == 0
        assert sorted(dist_c.engines) == [0, 1]  # shrink retired 2 hosts
        clean_leg = {
            "ramp": [1] + list(RAMP),
            "requests_per_wave": int(trace_s.size),
            "migrate_batch_seeds": args.migrate_batch,
            "migration_batches": dist_c.stats.migration_batches,
            "migrated_seeds": dist_c.stats.migrated_seeds,
            "ownership_epochs": dist_c.ownership_epoch,
            "retired_engines": len(dist_c._retired_engines),
            "dropped_requests": dropped,
            "parity_rows_checked": checked,
            "wave_qps": [
                round(trace_s.size / w, 1) for w in walls_c[:len(RAMP) + 1]
            ],
            "settled_wave_qps": round(trace_s.size / walls_c[-1], 1),
            "scale_summaries": summaries_c,
            "epoch_history_head": dist_c.routing_epochs()[:6],
        }

        # (b) owner kill MID-MIGRATION, replayable by construction: owner
        # 1 dies at migration batch index 3 (a source-side kill during
        # the 2->4 step) — in-flight ranges roll forward/back
        # deterministically, the fallback absorbs the dead owner's
        # traffic (zero dropped), parity still holds, and the identical
        # faulty run replays bit-identically
        KILL = (FaultSpec(owner=1, fid=3, kind="kill", at="migration"),)
        dist_k, inj_k, waves_k, _, summaries_k = ramp(
            KILL, eject_after=1, eject_backoff_flushes=64
        )
        checked_k, dropped_k = parity_and_drops(dist_k, waves_k)
        assert dropped_k == 0, "fallback should absorb the dead owner"
        assert inj_k.migration_events(), "migration fault never fired"
        outcomes_k = [e[-1] for e in dist_k.migration_log]
        assert ("rollforward" in outcomes_k or "rollback" in outcomes_k)
        dist_k2, inj_k2, waves_k2, _, _ = ramp(
            KILL, eject_after=1, eject_backoff_flushes=64
        )
        assert dist_k2.migration_log == dist_k.migration_log
        assert inj_k2.migration_events() == inj_k.migration_events()
        replay_identical = all(
            (isinstance(a, Exception) and isinstance(b, Exception))
            or np.array_equal(a, b)
            for wa, wb in zip(waves_k, waves_k2)
            for a, b in zip(wa, wb)
        )
        assert replay_identical, "faulty ramp did not replay bit-identical"
        kill_leg = {
            "fault": {"owner": 1, "migration_batch": 3, "kind": "kill"},
            "dropped_requests": dropped_k,
            "parity_rows_checked": checked_k,
            "migration_outcomes": outcomes_k,
            "migration_fault_events": inj_k.migration_events(),
            "hedges": dist_k.stats.hedges,
            "migration_rollbacks": dist_k.stats.migration_rollbacks,
            "migration_rollforwards": dist_k.stats.migration_rollforwards,
            "replay_bit_identical": replay_identical,
            "hosts_after": dist_k.hosts,
            "incomplete_hosts": summaries_k[-1].get("incomplete_hosts"),
        }

        # (c) price the next move: add-a-host vs replicate-the-head from
        # the clean ramp's MEASURED coverage curve + the settled fleet's
        # per-owner routed-leg mean (the r15 skew-leg sourcing — the
        # monitor's owner clocks were reset before the settled wave, so
        # only the final hosts=2 legs are in the mean)
        cov = dist_c.workload.skew_report(top_ks=(1, 8, 16, 64))[
            "top_coverage"
        ]
        owner_lat = dist_c.workload_report()["router"]["owners"][
            "per_owner"
        ]
        dispatch_s = (
            sum(v["lat_mean_ms"] for v in owner_lat.values())
            / max(len(owner_lat), 1) / 1e3
        ) or 1e-3
        fleet_rows = fleet_table(
            sorted((int(k), float(v)) for k, v in cov.items()),
            hosts=dist_c.hosts, bucket=args.max_batch,
            out_dim=model.out_dim, dispatch_s=dispatch_s,
            table_rows=n, feature_dim=feat.shape[1],
        )
        # 5% uplift floor: below that the "win" is wire noise on this
        # loopback box, and churn costs more than it buys
        pick = pick_fleet_action(fleet_rows, min_uplift=1.05)
        print(format_fleet_markdown(fleet_rows))

        out = {
            "metric": "serve_probe_scale",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "ramp": [1] + list(RAMP), "alpha": 1.1,
                "requests_per_wave": int(trace_s.size),
                "max_batch": args.max_batch,
                "migrate_batch_seeds": args.migrate_batch,
                "exchange": "host",
            },
            "note": (
                "sequential deterministic drive (QPS numbers are "
                "1-core loopback walls, read the structure not the "
                "absolute); parity/zero-drop asserts are in-run — a "
                "written artifact means they held"
            ),
            "clean_ramp": clean_leg,
            "kill_mid_migration": kill_leg,
            "fleet_table": {
                "measured_dispatch_s": dispatch_s,
                "rows": [r._asdict() for r in fleet_rows],
                "pick": pick._asdict() if pick else None,
            },
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-15 fleet-robustness leg (--faults -> SERVE_r07.json) ----------
    if args.faults:
        from quiver_tpu.parallel.scaling import (
            format_skew_markdown, pick_replication_k, skew_table,
        )
        from quiver_tpu.trace import WorkloadConfig as _WC

        HOSTS = 2
        alpha = 1.3

        def build_fleet(**kw):
            """Host-mode routed fleet (per-owner legs individually
            addressable — the hedging/fault surface) with the standard
            2-bucket shard ladder."""
            shard_cfg = ServeConfig(
                max_batch=args.max_batch, buckets=(8, args.max_batch),
                max_delay_ms=2.0, record_dispatches=True,
            )
            cfg = DistServeConfig(
                hosts=HOSTS, max_batch=args.max_batch, max_delay_ms=2.0,
                record_dispatches=True, shard_config=shard_cfg,
                exchange="host", **kw,
            )
            dist = DistServeEngine.build(
                model, params, topo, feat, SIZES, hosts=HOSTS, config=cfg,
                sampler_seed=SEED,
            )
            dist.warmup()
            dist.reset_stats()
            return dist

        def serve_seq(dist, trace, timeout=300):
            """Deterministic array-at-a-time drive; returns
            (rows|exceptions) per request — predict() would re-raise the
            first per-request error, and the parity comparison wants
            every outcome."""
            handles = dist.submit_many(np.asarray(trace, np.int64))
            while dist._drainable():
                dist.flush()
            out = []
            for h in handles:
                try:
                    out.append(h.result(timeout))
                except Exception as exc:
                    out.append(exc)
            return out

        def oracle_check(dist, trace, rows):
            """Every COMPLETED row must bit-match a fault-free offline
            replay candidate of the fleet's dispatch logs."""
            oracle = replay_fleet_oracle(
                dist, model, params, make_full_sampler, feat
            )
            checked = 0
            for nid, row in zip(trace, rows):
                if isinstance(row, Exception):
                    continue
                assert any(
                    np.array_equal(row, c) for c in oracle[int(nid)]
                ), f"FAULT-PARITY VIOLATION at node {int(nid)}"
                checked += 1
            return checked

        trace_f = zipfian_trace(n, args.fault_requests, alpha=alpha, seed=51)

        # (a) THE acceptance leg: kill owner 0 mid-flush, fallback up.
        # Run the identical faulty run twice: completed rows bit-identical
        # across runs AND bit-identical to the offline replay; hedges > 0;
        # errors (there are none here — the fallback absorbs) per-request.
        def kill_run():
            inj = FaultInjector([FaultSpec(owner=0, fid=3, kind="kill")])
            dist = build_fleet(fault_injector=inj, full_graph_fallback=True,
                               eject_after=1, eject_backoff_flushes=8)
            rows = serve_seq(dist, trace_f)
            return dist, rows, inj

        dist_k, rows_k, inj_k = kill_run()
        assert not any(isinstance(r, Exception) for r in rows_k)
        parity_rows = oracle_check(dist_k, trace_f, rows_k)
        sk = dist_k.stats
        assert sk.hedges > 0, "hedged re-route path not exercised"
        assert sk.owner_ejections >= 1, sk.snapshot()
        assert inj_k.events() and inj_k.events()[0][1] == 0
        dist_k2, rows_k2, inj_k2 = kill_run()
        assert dist_k2.hedge_events() == dist_k.hedge_events()
        assert inj_k2.events() == inj_k.events()
        replay_identical = all(
            np.array_equal(a, b) for a, b in zip(rows_k, rows_k2)
        )
        assert replay_identical, "faulty run did not replay bit-identical"
        kill_leg = {
            "fault": {"owner": 0, "fid": 3, "kind": "kill"},
            "requests": int(trace_f.size),
            "alpha": alpha,
            "parity_rows_checked": parity_rows,
            "completed": int(trace_f.size),
            "hedges": sk.hedges,
            "hedged_seeds": sk.hedged_seeds,
            "hedge_ejected": sk.hedge_ejected,
            "owner_ejections": sk.owner_ejections,
            "request_errors": sk.request_errors,
            "replay_bit_identical": replay_identical,
            "hedge_events_head": dist_k.hedge_events()[:8],
        }

        # (a') error isolation with NO failover target: the dead owner's
        # requests error per-request, everything else completes, the
        # engine never dies — availability is the surviving share
        inj_iso = FaultInjector([FaultSpec(owner=0, fid=1, kind="kill")])
        dist_iso = build_fleet(fault_injector=inj_iso, eject_after=1,
                               eject_backoff_flushes=8)
        rows_iso = serve_seq(dist_iso, trace_f)
        n_err = sum(1 for r in rows_iso if isinstance(r, Exception))
        assert 0 < n_err < trace_f.size, (n_err, trace_f.size)
        oracle_check(dist_iso, trace_f, rows_iso)
        iso_leg = {
            "fault": {"owner": 0, "fid": 1, "kind": "kill"},
            "no_failover_target": True,
            "requests": int(trace_f.size),
            "errored_per_request": n_err,
            "completed": int(trace_f.size) - n_err,
            "availability": round(1.0 - n_err / trace_f.size, 4),
            "hedge_failed": dist_iso.stats.hedge_failed,
            "engine_survived": True,  # serve_seq finished every flush
        }

        # (b) availability + p99 vs hedge deadline under STALL faults
        # (seeded stalls of 150 ms), fallback up, threaded saturated
        # drive, median-of-3 per point (NEXT.md noise discipline)
        stall_s = 0.15
        deadlines = [float(d) for d in args.hedge_deadlines.split(",")]

        def stall_run(deadline_ms):
            inj = FaultInjector.seeded(
                owners=range(HOSTS), n_faults=6, seed=23,
                fid_range=(2, 14), kinds=("stall",), stall_s=stall_s,
            )
            dist = build_fleet(fault_injector=inj, full_graph_fallback=True,
                               hedge_deadline_ms=deadline_ms)
            chunks = np.array_split(trace_f, args.clients)
            results = {}

            def client(tid, chunk):
                rows = []
                for nid in chunk:
                    try:
                        rows.append(dist.submit(int(nid)).result(300))
                    except Exception as exc:
                        rows.append(exc)
                results[tid] = rows

            t0 = time.perf_counter()
            with dist:
                threads = [threading.Thread(target=client, args=(i, c))
                           for i, c in enumerate(chunks)]
                [t.start() for t in threads]
                [t.join() for t in threads]
            wall = time.perf_counter() - t0
            all_rows = [r for tid in sorted(results) for r in results[tid]]
            ok = sum(1 for r in all_rows if not isinstance(r, Exception))
            s = dist.stats
            return {
                "qps": round(trace_f.size / wall, 1),
                "availability": round(ok / trace_f.size, 4),
                "p99_ms": round(s.latency.percentile(99), 3),
                "p50_ms": round(s.latency.percentile(50), 3),
                "hedge_timeouts": s.hedge_timeouts,
                "hedges": s.hedges,
            }

        stall_points = []
        for d in deadlines:
            reps = [stall_run(d) for _ in range(args.repeats)]
            stall_points.append({
                "hedge_deadline_ms": d,
                "stall_s": stall_s,
                "p99_ms": median_min_max([r["p99_ms"] for r in reps]),
                "availability": min(r["availability"] for r in reps),
                "qps": median_min_max([r["qps"] for r in reps]),
                "hedge_timeouts": max(r["hedge_timeouts"] for r in reps),
                "runs": reps,
            })
        # availability holds at 1.0 everywhere (fallback absorbs), and a
        # live deadline must actually fire hedges on timeouts
        assert all(p["availability"] == 1.0 for p in stall_points)
        armed = [p for p in stall_points if p["hedge_deadline_ms"] > 0
                 and p["hedge_deadline_ms"] < stall_s * 1e3]
        assert all(p["hedge_timeouts"] > 0 for p in armed), stall_points

        # (c) hot-set replication uplift vs the skew_table prediction:
        # warm the router sketch, replicate the measured head, interleaved
        # median-of-3 off/on saturated runs; the structural claim (head
        # seeds leave the owner legs) asserts deterministically, the QPS
        # medians report with spread
        def repl_run(replicate):
            dist = build_fleet(router_cache_entries=0,
                               workload=_WC(topk=256))
            # sketch warm-up on the SAME trace the measured window
            # serves (steady-state assumption: the head the sketch saw
            # is the head the replica will face; zipfian_trace permutes
            # the node mapping per seed, so a different seed would hand
            # the replica the wrong head)
            dist.predict(trace_f, timeout=300)
            rep_info = None
            if replicate:
                rep_info = dist.refresh_replicas(k=args.replicate_k)
            cov_meas = dist.workload.skew_report(
                top_ks=(1, 8, args.replicate_k, 64)
            )["top_coverage"]
            dist.reset_stats()
            log_start = len(dist.dispatch_log)
            chunks = np.array_split(trace_f, args.clients)
            errors = []

            def client(chunk):
                try:
                    dist.predict(chunk, timeout=300)
                except Exception as exc:
                    errors.append(repr(exc))

            t0 = time.perf_counter()
            with dist:
                threads = [threading.Thread(target=client, args=(c,))
                           for c in chunks]
                [t.start() for t in threads]
                [t.join() for t in threads]
            wall = time.perf_counter() - t0
            if errors:
                raise RuntimeError(f"replication clients failed: {errors}")
            if replicate:
                # THE structural claim, exact and deterministic: after
                # the refresh, no owner sub-batch ever carried a
                # replicated seed — head traffic never reached the
                # exchange path
                rep_set = dist.replica.id_set
                for _, split in dist.dispatch_log[log_start:]:
                    for h, ids in split:
                        if h != REPLICA_HOST:
                            leaked = [i for i in ids if int(i) in rep_set]
                            assert not leaked, (h, leaked)
            s = dist.stats
            owner_seeds = sum(
                v for h, v in s.sub_batch_seeds.items() if h != REPLICA_HOST
            )
            return {
                "qps": round(trace_f.size / wall, 1),
                "p99_ms": round(s.latency.percentile(99), 3),
                "replica_hits": s.replica_hits,
                "owner_routed_seeds": owner_seeds,
                "routed_seeds": s.routed_seeds,
                "coverage": cov_meas,
                "replica": rep_info,
            }

        runs_off, runs_on = [], []
        for _ in range(args.repeats):
            runs_off.append(repl_run(False))
            runs_on.append(repl_run(True))
        qps_off = median_min_max([r["qps"] for r in runs_off])
        qps_on = median_min_max([r["qps"] for r in runs_on])
        measured_uplift = qps_on["median"] / qps_off["median"]
        # the replica actually absorbed traffic (the exact head-seeds-
        # never-reach-an-owner claim asserted per dispatch-log entry
        # inside repl_run; REQUEST-grain coverage is the sketch number,
        # ROUTED-seed share is structurally flatter — router coalescing
        # collapses the head's repeats into single routed seeds)
        on = runs_on[-1]
        head_share = on["replica_hits"] / max(on["routed_seeds"], 1)
        assert on["replica_hits"] > 0
        # the skew_table prediction from the SAME measured coverage curve
        # (wire-term model: exchange seconds saved per routed flush); in
        # host mode there is no DCN, so report the prediction beside the
        # measurement rather than asserting equality
        dispatch_s = 2e-3
        rep_rows = skew_table(
            sorted((int(k), float(v)) for k, v in on["coverage"].items()),
            hosts=HOSTS, bucket=args.max_batch, out_dim=model.out_dim,
            dispatch_s=dispatch_s, feature_dim=feat.shape[1],
        )
        pick = pick_replication_k(rep_rows, min_uplift=1.0)
        print(format_skew_markdown(rep_rows))
        repl_leg = {
            "replicate_k": args.replicate_k,
            "qps_off": qps_off, "qps_on": qps_on,
            "qps_runs_off": [r["qps"] for r in runs_off],
            "qps_runs_on": [r["qps"] for r in runs_on],
            "measured_uplift_median": round(measured_uplift, 4),
            "replica_head_share_of_routed": round(head_share, 4),
            "measured_topk_coverage": on["coverage"],
            "p99_off_ms": median_min_max([r["p99_ms"] for r in runs_off]),
            "p99_on_ms": median_min_max([r["p99_ms"] for r in runs_on]),
            "replica_hits": on["replica_hits"],
            "skew_table_predicted": [r._asdict() for r in rep_rows],
            "skew_table_pick": pick._asdict() if pick else None,
            "note": (
                "skew_table prices the WIRE term (DCN exchange seconds "
                "saved); this loopback host-mode box has no wire, so the "
                "honest read is the structural head-share assert + the "
                "QPS medians with spread — the predicted uplift is what "
                "a real pod's exchange would add on top"
            ),
        }

        out = {
            "metric": "serve_probe_faults",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "hosts": HOSTS, "alpha": alpha,
                "requests": int(trace_f.size),
                "max_batch": args.max_batch, "clients": args.clients,
                "repeats": args.repeats, "exchange": "host",
            },
            "note": (
                "median-of-N with min/max per point (NEXT.md noise "
                "discipline); parity/availability asserts are in-run — a "
                "written artifact means they held"
            ),
            "owner_kill": kill_leg,
            "error_isolation_no_target": iso_leg,
            "hedge_deadline_sweep": stall_points,
            "replication": repl_leg,
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-18 real-disk predictive-IO leg (--tiers --real-disk ->
    # TIER_r02.json) ---------------------------------------------------------
    if args.tiers and args.real_disk:
        import tempfile

        from quiver_tpu import Feature
        from quiver_tpu.pipeline import AsyncReadPool
        from quiver_tpu.tiers import (
            DiskShard,
            drop_page_cache,
            o_direct_supported,
        )

        # the r01 tier graph: 32 communities x 150 nodes, [4, 4] fanout —
        # row-level access head compact enough for the fast tiers to hold
        t_edges, tfeat, tn = community_graph(
            n_comm=32, per_comm=150, intra=6, dim=32, seed=5
        )
        ttopo = CSRTopo(edge_index=t_edges)
        T_SIZES = [4, 4]

        def make_tier_sampler():
            return GraphSageSampler(ttopo, sizes=T_SIZES, mode="TPU",
                                    seed=SEED)

        ROWB = tfeat.shape[1] * 4
        HBM_B = args.rd_hbm_rows * ROWB
        HOST_B = args.rd_host_rows * ROWB
        READ_WORKERS = 4
        tdir = tempfile.mkdtemp(prefix="qt_realdisk_")
        rng = np.random.default_rng(7)

        # capacity acceptance: the r02 claim is >=10x the DRAM budget
        table_bytes = tn * ROWB
        capacity_ratio = table_bytes / HOST_B
        assert capacity_ratio >= 10.0, (
            f"table {table_bytes}B is only {capacity_ratio:.1f}x the "
            f"host budget {HOST_B}B — raise n or shrink --rd-host-rows"
        )

        # alpha-1.3 trace whose HOT SET SHIFTS mid-run: two independent
        # hotness permutations, spliced at the halfway mark. The warm
        # third (placement adaptation) sees only the FIRST head, so the
        # frozen placement is misaligned with the second — the drift
        # regime flush-ahead prefetch exists for (the reactive r14 tier
        # pays the new head's disk reads inside the serve path).
        reqs = args.rd_requests
        half = reqs // 2
        perm_a, perm_b = rng.permutation(tn), rng.permutation(tn)
        trace = np.concatenate([
            perm_a[zipfian_trace(tn, half, alpha=1.3, seed=31)],
            perm_b[zipfian_trace(tn, reqs - half, alpha=1.3, seed=32)],
        ]).astype(np.int64)
        warm_n = reqs // 3
        assert warm_n < half, "warm window must end before the shift"

        # -- page-cache defeat: method probed EMPIRICALLY, recorded ------
        probe_rows = rng.standard_normal((256, tfeat.shape[1])) \
            .astype(np.float32)
        probe_sh = DiskShard.create(os.path.join(tdir, "probe.npy"),
                                    probe_rows)
        use_direct = o_direct_supported(probe_sh.path)
        method = ("o_direct" if use_direct
                  else "posix_fadvise_dontneed_between_legs")

        class _DeviceModelShard:
            """Defeated backing + the RECORDED per-row device-latency
            model (--rd-device-us): every read_block sleeps rows*us on
            the calling pool worker (GIL-releasing, so reads overlap
            like real IO). Applied identically to in-path gathers AND
            prefetch staging reads — the model can never flatter the
            prefetch arm. Bytes untouched."""

            def __init__(self, shard, us_per_row):
                self._shard = shard
                self._us = float(us_per_row)

            def __getattr__(self, name):
                return getattr(self._shard, name)

            def read_block(self, ids):
                out = self._shard.read_block(ids)
                n = np.asarray(ids).size
                if n and self._us > 0:
                    time.sleep(n * self._us * 1e-6)
                return out

            def read_rows(self, local_ids, pool=None):
                ids = np.asarray(local_ids, np.int64).reshape(-1)
                if pool is None or ids.size == 0:
                    return self.read_block(ids)
                return pool.gather(self.read_block, ids)

        def defeat(shard, device_us=0.0):
            """Swap a store backing onto the defeated read path (or, on
            filesystems refusing O_DIRECT, drop its pages — best-effort,
            recorded as such), plus the device model when asked."""
            out = DiskShard(shard.path, direct=True) if use_direct else shard
            if not use_direct:
                shard.drop_cache()
            if device_us > 0:
                out = _DeviceModelShard(out, device_us)
            return out

        # defeat EVIDENCE: per-row cold cost vs the page-cache-warm
        # memmap read of the same rows — the artifact must show the
        # defeat actually defeated something on this box
        pids = rng.integers(0, 256, 512)
        probe_sh.read_block(pids)
        t0 = time.perf_counter()
        for _ in range(3):
            probe_sh.read_block(pids)
        warm_us = (time.perf_counter() - t0) / 3 / pids.size * 1e6
        cold_sh = defeat(probe_sh)
        cold_sh.read_block(pids[:8])
        t0 = time.perf_counter()
        for _ in range(3):
            cold_sh.read_block(pids)
        cold_us = (time.perf_counter() - t0) / 3 / pids.size * 1e6

        def build_feature(name, device_us=0.0):
            f = Feature(
                rank=0, device_cache_size=HBM_B, host_memory_budget=HOST_B,
                disk_path=os.path.join(tdir, name), adaptive_tiers=True,
                read_pool=AsyncReadPool(READ_WORKERS, chunk_rows=64),
            )
            f.from_cpu_tensor(tfeat)
            # bit-parity first (through the page cache — bytes are the
            # point here, not latency), then defeat the cache for keeps
            ids = rng.integers(0, tn, 256)
            assert np.array_equal(np.asarray(f[ids]), tfeat[ids]), name
            f.tier_store.backing = defeat(f.tier_store.backing, device_us)
            return f

        def make_config(prefetch, mif=2):
            # split dispatch + cache_entries=0 in EVERY arm: the fused
            # path (plain features) and the embedding cache would both
            # hide exactly the tier traffic this leg measures
            return ServeConfig(
                max_batch=args.max_batch, buckets=(8, args.max_batch),
                max_delay_ms=2.0, cache_entries=0, dispatch_mode="split",
                max_in_flight=mif, record_dispatches=True,
                workload=WorkloadConfig(
                    topk=256,
                    row_topk=2 * (args.rd_hbm_rows + args.rd_host_rows),
                ),
                tier_promote_min=1.0,
                tier_promote_batch=2 * (args.rd_hbm_rows
                                        + args.rd_host_rows),
                tier_prefetch=prefetch,
                tier_prefetch_max_rows=args.rd_prefetch_rows,
            )

        def warmed_engine(feature, prefetch):
            """Engine with the r02 adaptation schedule: sketch-warm on
            the pre-shift third, fenced adapt passes until the plan is
            empty, then the placement FREEZES for the measured window
            (no background daemon — the drift is the scenario)."""
            eng = ServeEngine(model, params, make_tier_sampler(), feature,
                              make_config(prefetch))
            eng.warmup()
            eng.predict(trace[:warm_n], timeout=600)
            passes = moves = 0
            while passes < 8:
                s = eng.adapt_tiers()
                passes += 1
                moves += s["moves"]
                if s["moves"] == 0:
                    break
            if not use_direct:  # re-drop pages the warm phase pulled in
                feature.tier_store.backing.drop_cache()
            eng.reset_stats()
            return eng, passes, moves

        measured = trace[warm_n:]
        bursts = [measured[lo: lo + args.max_batch]
                  for lo in range(0, measured.size, args.max_batch)]

        def build_arm(kind, label):
            if kind == "dram":
                f = Feature(rank=0, device_cache_size=HBM_B)
                f.from_cpu_tensor(tfeat)
                eng = ServeEngine(model, params, make_tier_sampler(), f,
                                  make_config(False))
                eng.warmup()
                eng.predict(trace[:warm_n], timeout=600)
                eng.reset_stats()
                return eng
            eng, _, _ = warmed_engine(
                build_feature(f"{label}.npy", args.rd_device_us),
                prefetch=(kind == "on"),
            )
            return eng

        def run_round(tag):
            """One BURST-INTERLEAVED measured round over the post-warm
            window (the hot-set shift lands mid-window): each max-batch
            burst runs on the dram, prefetch-off, then prefetch-on arm
            back to back, so machine drift hits all three identically
            and the arms are load-matched by construction (a closed-loop
            flood would measure disk BANDWIDTH — queueing delay — where
            prefetch can only lose, since it spends reads it may waste;
            latency hiding is a below-saturation property). The ON arm
            gets the ANNOUNCE-AHEAD call after each burst — the
            flush-ahead contract (`prefetch_seeds` on the next window's
            seeds, exactly what `DistServeEngine` does per owner at
            route time), so its staging reads land during the other
            arms' service time. Latencies are exact per-burst walls (the
            latency histogram's buckets are too coarse for a 1.2x
            verdict)."""
            engs = {k: build_arm(k, f"{k}_{tag}")
                    for k in ("dram", "off", "on")}
            lats = {k: [] for k in engs}
            for j, b in enumerate(bursts):
                for k, eng in engs.items():
                    t0 = time.perf_counter()
                    eng.predict(b, timeout=600)
                    lats[k].append((time.perf_counter() - t0) * 1e3)
                    if k == "on" and j + 1 < len(bursts):
                        eng.prefetch_seeds(bursts[j + 1])
            out = {}
            for k, eng in engs.items():
                res = {
                    "p50_ms": float(np.percentile(lats[k], 50)),
                    "p99_ms": float(np.percentile(lats[k], 99)),
                    "bursts": len(bursts),
                }
                if k != "dram":
                    mix = eng.workload.skew_report()["tiers"]
                    total = sum(v["hits"] for v in mix.values()) or 1
                    res["gather_mix"] = {t: round(v["hits"] / total, 4)
                                         for t, v in mix.items()}
                    st = eng.stats
                    res["prefetch"] = {
                        "issued": st.tier_prefetch_issued,
                        "hit": st.tier_prefetch_hit,
                        "wasted": st.tier_prefetch_wasted,
                        "hit_rate": round(
                            st.tier_prefetch_hit
                            / max(st.tier_prefetch_issued, 1), 4),
                    }
                eng.stop(drain=True)
                out[k] = res
            return out

        # -- in-run BIT-PARITY: prefetch on vs off, deterministic
        # burst-sequential drive WITH announce-ahead on the on-engine
        # (the acceptance pin is logits AND dispatch log identical; the
        # device model is off here — bytes are the point, not latency)
        e_par_on = ServeEngine(model, params, make_tier_sampler(),
                               build_feature("par_on.npy"),
                               make_config(True))
        e_par_off = ServeEngine(model, params, make_tier_sampler(),
                                build_feature("par_off.npy"),
                                make_config(False))
        par_bursts = [trace[lo: lo + args.max_batch]
                      for lo in range(0, trace.size, args.max_batch)]
        rows_on, rows_off = [], []
        for j, b in enumerate(par_bursts):
            rows_on.append(e_par_on.predict(b, timeout=600))
            if j + 1 < len(par_bursts):
                e_par_on.prefetch_seeds(par_bursts[j + 1])
            rows_off.append(e_par_off.predict(b, timeout=600))
        rows_on = np.concatenate(rows_on)
        rows_off = np.concatenate(rows_off)
        assert np.array_equal(rows_on, rows_off), "prefetch changed bits!"
        log_on, log_off = e_par_on.dispatch_log, e_par_off.dispatch_log
        assert len(log_on) == len(log_off)
        for (p1, n1), (p2, n2) in zip(log_on, log_off):
            assert n1 == n2 and np.array_equal(p1, p2), \
                "prefetch changed the dispatch log!"
        parity_rows = int(rows_on.shape[0])
        parity_prefetch_hits = e_par_on.stats.tier_prefetch_hit
        assert parity_prefetch_hits > 0, "parity leg never hit staging"
        e_par_on.stop()
        e_par_off.stop()

        # -- interleaved median-of-3 (NEXT.md noise discipline), one
        # discarded warm round first (bucket compiles + first-touch) ----
        run_round("w")
        rounds = [run_round(f"r{r}") for r in range(args.repeats)]
        runs = {k: [rd[k] for rd in rounds] for k in ("dram", "off", "on")}

        def agg(kind, key):
            return median_min_max([x[key] for x in runs[kind]])

        p99 = {k: agg(k, "p99_ms") for k in runs}
        p50 = {k: agg(k, "p50_ms") for k in runs}
        p99_on_vs_off = p99["on"]["median"] / p99["off"]["median"]
        p99_on_vs_dram = p99["on"]["median"] / p99["dram"]["median"]
        hit_rates = [x["prefetch"]["hit_rate"] for x in runs["on"]]
        # diagnostics BEFORE the acceptance asserts: a failed target must
        # leave the numbers it failed on (the artifact write stays gated)
        print("REAL-DISK-DIAG "
              + json.dumps({"p99_ms": p99, "p50_ms": p50,
                            "hit_rates": hit_rates,
                            "gather_mix_on": runs["on"][-1]["gather_mix"],
                            "gather_mix_off": runs["off"][-1]["gather_mix"],
                            "prefetch_last": runs["on"][-1]["prefetch"]}),
              file=sys.stderr)
        assert p99_on_vs_off < 1.0, (
            f"prefetch-on did not beat prefetch-off on p99: "
            f"x{p99_on_vs_off:.3f}"
        )
        assert p99_on_vs_dram <= 1.2, (
            f"prefetch-on p99 is {p99_on_vs_dram:.2f}x all-DRAM "
            f"(target <= 1.2x)"
        )

        out = {
            "metric": "serve_probe_tiers_real_disk",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "nodes": tn, "dim": tfeat.shape[1],
                "hbm_rows": args.rd_hbm_rows,
                "host_rows": args.rd_host_rows,
                "host_budget_bytes": HOST_B,
                "table_bytes": table_bytes,
                "capacity_ratio_vs_dram_budget": round(capacity_ratio, 2),
                "alpha": 1.3, "requests": reqs,
                "hot_set_shift_at_request": half,
                "warm_requests": warm_n,
                "max_batch": args.max_batch,
                "repeats": args.repeats, "cache_entries": 0,
                "dispatch_mode": "split",
                "drive": (
                    "burst-interleaved arms (each max-batch burst runs "
                    "dram/off/on back to back: machine drift hits all "
                    "three identically, load-matched by construction; a "
                    "closed-loop flood measures disk bandwidth — "
                    "queueing delay — not latency hiding), exact "
                    "per-burst wall latencies, announce-ahead on the ON "
                    "arm (prefetch_seeds on the next window's seeds — "
                    "the flush-ahead contract DistServeEngine implements "
                    "per owner at route time)"
                ),
                "device_model_us_per_row": args.rd_device_us,
                "device_model_note": (
                    "recorded per-row latency slept (GIL-releasing) "
                    "inside every MEASURED-ARM backing read, staging "
                    "reads included, on top of the defeated read path — "
                    "this container's backing store is hypervisor-cached "
                    "(see page_cache_defeat: the defeat is real but the "
                    "'device' answers at RAM speed), so a latency-hiding "
                    "claim needs a recorded device latency to hide; 0 "
                    "for the parity legs and defeat evidence (real path "
                    "only)"
                ),
                "read_workers": READ_WORKERS,
                "tier_prefetch_max_rows": args.rd_prefetch_rows,
            },
            "page_cache_defeat": {
                "method": method,
                "o_direct_supported": bool(use_direct),
                "memmap_warm_us_per_row": round(warm_us, 3),
                "defeated_us_per_row": round(cold_us, 3),
                "defeat_factor": round(cold_us / max(warm_us, 1e-9), 1),
                "note": (
                    "method probed empirically on the artifact dir's "
                    "filesystem. o_direct: every cold read is an aligned "
                    "pread through an O_DIRECT descriptor (page cache "
                    "bypassed entirely). fadvise fallback: pages dropped "
                    "between legs only — BEST-EFFORT (some filesystems "
                    "ignore it; the defeat_factor above is the honest "
                    "evidence either way)."
                ),
            },
            "parity": {
                "rows_checked": parity_rows,
                "dispatch_log_flushes": len(log_on),
                "prefetch_hits_during_parity": parity_prefetch_hits,
            },
            "all_dram": {"p50_ms": p50["dram"], "p99_ms": p99["dram"],
                         "runs": runs["dram"]},
            "prefetch_off": {"p50_ms": p50["off"], "p99_ms": p99["off"],
                             "runs": runs["off"]},
            "prefetch_on": {"p50_ms": p50["on"], "p99_ms": p99["on"],
                            "runs": runs["on"]},
            "prefetch_hit_rate_measured": median_min_max(hit_rates),
            "p99_on_vs_off": round(p99_on_vs_off, 4),
            "p99_on_vs_all_dram": round(p99_on_vs_dram, 4),
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-14 disk-tier leg (--tiers -> TIER_r01.json) -------------------
    if args.tiers:
        import tempfile

        from quiver_tpu import Feature, QuantizedFeature
        from quiver_tpu.inference import _cached_apply, forward_logits, sample_batch
        from quiver_tpu.parallel.scaling import format_tier_markdown, tier_table
        from quiver_tpu.pipeline import AsyncReadPool
        from quiver_tpu.tiers import TIER_DISK, TIER_HBM, TIER_HOST

        # a DEDICATED graph, 10x the sweep graph: the tier claim needs
        # each flush's n_id to touch a SMALL fraction of the table (on
        # the 480-node sweep graph one flush gathers most of the graph,
        # so row access is flat and placement cannot matter). 32 small
        # communities x 150 nodes with modest degree + a [4, 4] fanout:
        # a Zipf head seed's sampled 2-hop closure is a few dozen rows
        # inside its community, so gather traffic has a row-level head
        # compact enough for the fast tiers to HOLD — the regime tier
        # placement exists for (row skew, not just seed skew).
        t_edges, tfeat, tn = community_graph(
            n_comm=32, per_comm=150, intra=6, dim=32, seed=5
        )
        ttopo = CSRTopo(edge_index=t_edges)
        T_SIZES = [4, 4]

        def make_tier_sampler():
            return GraphSageSampler(ttopo, sizes=T_SIZES, mode="TPU", seed=SEED)

        ROWB = tfeat.shape[1] * 4
        HBM_B = args.tier_hbm_rows * ROWB
        HOST_B = args.tier_host_rows * ROWB
        READ_WORKERS = 4
        tdir = tempfile.mkdtemp(prefix="qt_tiers_")
        rng = np.random.default_rng(7)
        # decorrelate the Zipf head from the stored prefix: without a
        # csr_topo reorder the static prefix is id-order, so a permuted
        # trace makes the head land anywhere — the placement-misalignment
        # every static tiering suffers when traffic drifts from ingest
        # assumptions, and exactly what the sketch-driven consumer fixes
        perm = rng.permutation(tn)
        trace = perm[zipfian_trace(tn, args.tier_requests, alpha=1.3,
                                   seed=31)].astype(np.int64)
        warm_n = len(trace) // 3
        sim_s = args.tier_disk_us_per_row * 1e-6

        def build_feature(name, adaptive):
            f = Feature(
                rank=0, device_cache_size=HBM_B, host_memory_budget=HOST_B,
                disk_path=os.path.join(tdir, name), adaptive_tiers=adaptive,
                read_pool=AsyncReadPool(READ_WORKERS, chunk_rows=128),
            )
            f.from_cpu_tensor(tfeat)
            return f

        def wrap_sim(f):
            """Add the simulated per-row cold-read latency to the disk
            tier's read_block (per chunk, so pool workers overlap the
            sleeps — modeled IO queue depth). Identical wrapper on both
            placements: the comparison isolates WHERE rows live."""
            obj = (f.tier_store.backing if f.tier_store is not None
                   else f.shard_tensor.disk_shard)
            orig = obj.read_block

            def slow(ids):
                if sim_s > 0 and ids.size:
                    time.sleep(sim_s * ids.size)
                return orig(ids)

            obj.read_block = slow

        # capacity acceptance: stored bytes >= 5x the DRAM budget
        table_bytes = tn * ROWB
        capacity_ratio = table_bytes / HOST_B
        assert capacity_ratio >= 5.0, (
            f"table {table_bytes}B is only {capacity_ratio:.1f}x the "
            f"host budget {HOST_B}B — raise n or shrink the budget"
        )

        # bit-parity acceptance: disk-tier gathers == in-DRAM gathers
        full = Feature(rank=0, device_cache_size=0)
        full.from_cpu_tensor(tfeat)
        ids = rng.integers(0, tn, 512)
        fa0 = build_feature("parity_a.npy", True)
        fs0 = build_feature("parity_s.npy", False)
        want = np.asarray(full[ids])
        assert np.array_equal(np.asarray(fa0[ids]), want), "adaptive parity"
        assert np.array_equal(np.asarray(fs0[ids]), want), "static parity"
        fq = QuantizedFeature(
            "int8", device_cache_size=8 * tn + HBM_B // 4,
            host_memory_budget=HOST_B // 4,
            disk_path=os.path.join(tdir, "q.npy"), adaptive_tiers=True,
        )
        fq.from_cpu_tensor(tfeat)
        assert np.array_equal(np.asarray(fq[ids]), fq.decode_rows(ids)), (
            "int8 disk tier not codec-exact"
        )
        parity = {"fp32_rows": int(ids.size) * 2, "int8_rows": int(ids.size)}

        # measured per-row tier costs (tier_table inputs), sim installed
        wrap_sim(fa0)
        store0 = fa0.tier_store

        def time_rows(tier, reps=5):
            res = store0.placement.residents(tier)
            batch = np.tile(res, -(-256 // max(res.size, 1)))[:256]
            np.asarray(store0.gather(batch))  # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                np.asarray(store0.gather(batch))
            return (time.perf_counter() - t0) / reps / batch.size

        hbm_row_s = time_rows(TIER_HBM)
        host_row_s = time_rows(TIER_HOST)
        disk_row_s = time_rows(TIER_DISK)

        # measured per-flush device dispatch (full-DRAM forward at the
        # probe bucket — the all-HBM reference term of the cost model)
        apply = _cached_apply(model)
        ds_b = sample_batch(make_tier_sampler(), np.zeros(args.max_batch, np.int64))
        np.asarray(forward_logits(apply, params, full, ds_b))
        t0 = time.perf_counter()
        for _ in range(10):
            np.asarray(forward_logits(apply, params, full, ds_b))
        dispatch_s = (time.perf_counter() - t0) / 10

        def run_serve(adaptive, label):
            """One saturated closed-loop run. cache_entries=0: the
            embedding cache would serve the Zipf head host-side and hide
            the tier path this leg measures (cache sizing is SERVE_r06's
            question). Adaptive runs warm the sketch on the first third,
            apply fenced adapt passes until the plan is empty, then
            measure with the placement frozen."""
            f = build_feature(f"{label}.npy", adaptive)
            wrap_sim(f)
            eng = ServeEngine(
                model, params, make_tier_sampler(), f,
                ServeConfig(
                    max_batch=args.max_batch, buckets=(8, args.max_batch),
                    max_delay_ms=2.0, cache_entries=0,
                    # the row sketch must SEE at least as many rows as
                    # the fast tiers can hold, or the planner is blind
                    # to most of its own capacity
                    workload=WorkloadConfig(
                        topk=256,
                        row_topk=2 * (args.tier_hbm_rows
                                      + args.tier_host_rows),
                    ),
                    tier_promote_min=1.0,
                    tier_promote_batch=2 * (args.tier_hbm_rows
                                            + args.tier_host_rows),
                ),
            )
            eng.warmup()
            eng.predict(trace[:warm_n], timeout=600)  # sketch warm-up
            passes = moves = 0
            t_adapt0 = time.perf_counter()
            if adaptive:
                while passes < 8:
                    s = eng.adapt_tiers()
                    passes += 1
                    moves += s["moves"]
                    if s["moves"] == 0:
                        break
            adapt_wall = time.perf_counter() - t_adapt0
            promoted = eng.stats.tier_promoted  # before the stats reset
            eng.reset_stats()  # measured window only (sketches re-fill)
            chunks = np.array_split(trace[warm_n:], args.clients)
            errors = []

            def client(chunk):
                try:
                    eng.predict(chunk, timeout=600)
                except Exception as exc:
                    errors.append(repr(exc))

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in chunks]
            [t.start() for t in threads]
            [t.join() for t in threads]
            wall = time.perf_counter() - t0
            if errors:
                raise RuntimeError(f"tier clients failed ({label}): {errors}")
            tiers_mix = eng.workload.skew_report()["tiers"]
            total = sum(v["hits"] for v in tiers_mix.values()) or 1
            mix = {t: v["hits"] / total for t, v in tiers_mix.items()}
            f.tier_store.placement.check() if f.tier_store is not None else None
            return {
                "qps": (len(trace) - warm_n) / wall,
                "p99_ms": eng.stats.latency.percentile(99),
                "p50_ms": eng.stats.latency.percentile(50),
                "gather_mix": {t: round(v, 4) for t, v in mix.items()},
                "adapt_passes": passes,
                "adapt_moves": moves,
                "adapt_wall_s": round(adapt_wall, 4),
                "placement": (
                    f.tier_store.placement.counts()
                    if f.tier_store is not None else None
                ),
                "tier_promoted": promoted,
            }

        # one DISCARDED warm pair first: the first run of each arm pays
        # the bucket compiles + page-cache warm-up (measured ~4x slower
        # than steady state), which would poison an interleaved median
        # at repeats=3
        run_serve(False, "warm_s")
        run_serve(True, "warm_a")
        # interleaved median-of-3 (NEXT.md noise discipline)
        runs_s, runs_a = [], []
        for r in range(args.repeats):
            runs_s.append(run_serve(False, f"run_s{r}"))
            runs_a.append(run_serve(True, f"run_a{r}"))

        def agg(runs, key):
            return median_min_max([r[key] for r in runs])

        qps_s, qps_a = agg(runs_s, "qps"), agg(runs_a, "qps")
        p99_s, p99_a = agg(runs_s, "p99_ms"), agg(runs_a, "p99_ms")
        qps_uplift = qps_a["median"] / qps_s["median"]
        p99_ratio = p99_a["median"] / p99_s["median"] if p99_s["median"] else 1.0
        assert qps_uplift > 1.0 or p99_ratio < 1.0, (
            f"adaptive placement did not beat static: qps x{qps_uplift:.3f}, "
            f"p99 x{p99_ratio:.3f}"
        )

        # the cost model, priced with the measured inputs above
        def as_mix(run, name):
            m = run["gather_mix"]
            hbm = m.get("hbm", 0.0)
            host = m.get("host", 0.0)
            disk = max(1.0 - hbm - host, 0.0)
            return (name, hbm, host, disk)

        tt_rows = tier_table(
            mixes=[("all_hbm", 1.0, 0.0, 0.0),
                   as_mix(runs_s[-1], "static_measured"),
                   as_mix(runs_a[-1], "adaptive_measured")],
            bucket=args.max_batch, dispatch_s=dispatch_s,
            hbm_row_s=hbm_row_s, host_row_s=host_row_s,
            # the model wants the SINGLE-THREAD disk cost (it divides by
            # read_workers itself); reconstruct it from the pooled
            # measurement above
            disk_row_s=disk_row_s * READ_WORKERS,
            feature_dim=tfeat.shape[1], read_workers=READ_WORKERS,
        )
        print(format_tier_markdown(tt_rows))

        out = {
            "metric": "serve_probe_tiers",
            "git_revision": git_revision(),
            "backend": jax.devices()[0].platform,
            "config": {
                "nodes": tn, "dim": tfeat.shape[1],
                "hbm_rows": args.tier_hbm_rows,
                "host_rows": args.tier_host_rows,
                "host_budget_bytes": HOST_B,
                "table_bytes": table_bytes,
                "capacity_ratio_vs_dram_budget": round(capacity_ratio, 2),
                "alpha": 1.3, "requests": args.tier_requests,
                "max_batch": args.max_batch,
                "clients": args.clients, "repeats": args.repeats,
                "cache_entries": 0,
                "disk_us_per_row_simulated": args.tier_disk_us_per_row,
                "read_workers": READ_WORKERS,
            },
            "note": (
                "disk reads carry a SIMULATED per-row latency (labeled in "
                "config): this box's page cache makes flat-file reads "
                "DRAM-speed, production cold storage is not — the sim "
                "applies identically to both placements, so the uplift "
                "isolates WHERE rows live, which is the claim under test. "
                "cache_entries=0 so the embedding cache cannot hide the "
                "tier path. Trace hotness is PERMUTED off the stored "
                "prefix (static placement misaligned by construction — "
                "the drift scenario adaptation exists for)."
            ),
            "parity_rows_checked": parity,
            "measured_row_costs_s": {
                "hbm": hbm_row_s, "host": host_row_s,
                "disk_pooled": disk_row_s, "dispatch_s": dispatch_s,
            },
            "static": {"qps": qps_s, "p99_ms": p99_s,
                       "runs": runs_s},
            "adaptive": {"qps": qps_a, "p99_ms": p99_a,
                         "runs": runs_a},
            "adaptive_vs_static": {
                "qps_uplift_median": round(qps_uplift, 4),
                "p99_ratio_median": round(p99_ratio, 4),
            },
            "tier_table": [r._asdict() for r in tt_rows],
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # -- round-13 workload-skew leg (--skew -> SERVE_r06.json) ---------------
    if args.skew:
        from quiver_tpu.parallel.scaling import skew_table

        CAP = args.skew_cache
        skew_points = []
        for alpha in (float(a) for a in args.skew_alphas.split(",")):
            trace = zipfian_trace(n, args.skew_requests, alpha=alpha, seed=29)

            # (a+b) accuracy leg: a single-host fused engine driven
            # SEQUENTIALLY (submit -> flush -> result per request), so the
            # EmbeddingCache evolves as a pure LRU — the apples-to-apples
            # measured counterpart of the sketch's Che-model prediction.
            # Threaded saturation would conflate coalescing with cache
            # behavior; the saturated cost question is the separate
            # on-vs-off leg below.
            eng = ServeEngine(
                model, params, make_full_sampler(), feat,
                ServeConfig(max_batch=8, buckets=(8,), max_delay_ms=2.0,
                            cache_entries=CAP,
                            workload=WorkloadConfig(topk=256)),
            )
            eng.warmup()
            for nid in trace:
                h = eng.submit(int(nid))
                if eng._drainable():
                    eng.flush()
                h.result(timeout=300)
            rep = eng.workload.skew_report(
                capacities=(CAP,), top_ks=(1, 8, 16, 64, 256)
            )
            measured_hit = eng.stats.cache.hit_rate
            predicted_hit = rep["predicted_hit_rate"][str(CAP)]
            # sketch top-64 vs exact counters (same count-desc/key-asc
            # tie rule on both sides)
            keys, counts = np.unique(trace, return_counts=True)
            order = np.lexsort((keys, -counts))
            exact64 = set(int(k) for k in keys[order[:64]])
            sketch64 = set(k for k, _, _ in eng.workload.topk.topk(64))
            overlap64 = len(exact64 & sketch64) / 64.0

            # (c) owner imbalance + straggler at hosts 1 and 2: the routed
            # engine's ROUTER monitor, deterministic single-threaded drive
            owner_stats = {}
            for hosts in (1, 2):
                dist = build_dist(hosts, "fused",
                                  workload=WorkloadConfig(topk=256))
                dist.predict(trace[:600])
                wr = dist.workload_report(capacities=(CAP,))
                ro = wr["router"]["owners"]
                owner_stats[str(hosts)] = {
                    "per_owner_seeds": {
                        h: v["seeds"] for h, v in ro["per_owner"].items()
                    },
                    "per_owner_lat_ms": {
                        h: {
                            "mean": round(v["lat_mean_ms"], 3),
                            "p50": round(v["lat_p50_ms"], 3),
                            "p99": round(v["lat_p99_ms"], 3),
                        }
                        for h, v in ro["per_owner"].items()
                    },
                    "imbalance": ro["imbalance"],
                    "straggler": ro["straggler"],
                }
                assert ro["imbalance"]["owners"] == hosts, ro
            point = {
                "alpha": alpha,
                "requests": args.skew_requests,
                "cache_entries": CAP,
                "distinct": int(keys.size),
                "skew": trace_skew_stats(trace),
                "top64_overlap": round(overlap64, 4),
                "measured_hit_rate": round(measured_hit, 4),
                "predicted_hit_rate": predicted_hit,
                "predicted_hit_rate_lfu_bound": (
                    rep["predicted_hit_rate_lfu_bound"][str(CAP)]
                ),
                "predicted_vs_measured_diff": round(
                    abs(predicted_hit - measured_hit), 4
                ),
                "dispatches": eng.stats.dispatches,
                "skew_report": {
                    k: rep[k]
                    for k in ("observed_events", "distinct_tracked",
                              "ticks", "top_coverage", "error_bound",
                              "cache")
                },
                "owners": owner_stats,
            }
            skew_points.append(point)
            if alpha >= 1.25:
                # the ISSUE acceptance bounds, asserted in-run at the
                # heavy-skew point
                assert overlap64 >= 0.90, (alpha, overlap64)
                assert abs(predicted_hit - measured_hit) <= 0.05, (
                    alpha, predicted_hit, measured_hit
                )

        # (d) sketch-on vs sketch-off saturated QPS, median-of-3
        # INTERLEAVED (off/on pairs back to back — same noise-honest form
        # as the round-12 journal leg): the "cheap enough to leave on"
        # claim for the sketches, measured on the threaded routed engine
        qps_skew_on, qps_skew_off = [], []
        for _ in range(3):
            _, _, w_off, _ = run_once(1.1, hosts_sweep[0], "fused", False)
            _, _, w_on, _ = run_once(
                1.1, hosts_sweep[0], "fused", False,
                workload=WorkloadConfig(topk=256),
            )
            qps_skew_off.append(round(args.requests / w_off, 1))
            qps_skew_on.append(round(args.requests / w_on, 1))
        skew_overhead_frac = 1.0 - (
            median_min_max(qps_skew_on)["median"]
            / median_min_max(qps_skew_off)["median"]
        )
        skew_ranges_overlap = (
            min(qps_skew_on) <= max(qps_skew_off)
            and min(qps_skew_off) <= max(qps_skew_on)
        )
        assert skew_overhead_frac < 0.03 or skew_ranges_overlap, (
            skew_overhead_frac, qps_skew_on, qps_skew_off
        )

        # the measured alpha-1.3 head feeds the item-3 replication table,
        # priced with the MEASURED per-owner routed-leg latency from the
        # hosts=2 run (the monitor's owner flush mean)
        heavy = max(skew_points, key=lambda p: p["alpha"])
        cov = sorted(
            (int(k), float(v))
            for k, v in heavy["skew_report"]["top_coverage"].items()
        )
        owner_lat = heavy["owners"]["2"]["per_owner_lat_ms"]
        dispatch_s = (
            sum(v["mean"] for v in owner_lat.values())
            / max(len(owner_lat), 1) / 1e3
        ) or 1e-3
        rep_rows = skew_table(
            cov, hosts=2, bucket=args.max_batch, out_dim=model.out_dim,
            dispatch_s=dispatch_s, feature_dim=feat.shape[1],
        )
        out = {
            "metric": "serve_probe_skew",
            "git_revision": git_revision(),
            "requests": args.skew_requests,
            "cache_entries": CAP,
            "max_batch": args.max_batch,
            "backend": jax.devices()[0].platform,
            "note": (
                "accuracy legs are sequential LRU-faithful drives (the "
                "predicted-vs-measured close needs the cache to be an "
                "LRU, not a coalescing race); the on-vs-off QPS leg is "
                "the threaded saturated engine, median-of-3 interleaved "
                "with min/max spreads per the noise discipline"
            ),
            "points": skew_points,
            "asserted": {
                "top64_overlap_min_at_alpha13": 0.90,
                "hit_rate_max_diff_at_alpha13": 0.05,
            },
            "sketch_overhead": {
                "qps_on": qps_skew_on,
                "qps_off": qps_skew_off,
                "frac": round(skew_overhead_frac, 4),
                "ranges_overlap": skew_ranges_overlap,
            },
            "serve_skew_overhead_frac": round(skew_overhead_frac, 4),
            "skew_table_dispatch_s": round(dispatch_s, 6),
            "skew_table_hosts2": [r._asdict() for r in rep_rows],
        }
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return

    # hosts=1 vs a plain single-host engine, bit for bit: a deterministic
    # single-threaded pass (flush composition under concurrent clients is
    # interleaving-dependent by design, so the bitwise claim is pinned on
    # the deterministic driver — the threaded runs pin parity against the
    # replay oracle instead)
    dist1 = build_dist(1, "fused")
    trace1 = zipfian_trace(n, args.requests, alpha=1.1, seed=43)
    out1 = np.asarray(dist1.predict(trace1))
    plain = ServeEngine(
        model, params, make_full_sampler(), feat,
        ServeConfig(max_batch=args.max_batch, buckets=(8, args.max_batch),
                    max_delay_ms=2.0, record_dispatches=True),
    )
    ref1 = np.asarray(plain.predict(trace1))
    assert np.array_equal(out1, ref1), (
        "hosts=1 engine diverged from the single-host engine"
    )
    hosts1_parity_rows = int(trace1.shape[0])

    # same deterministic trace WITH the lifecycle journal on: enabling
    # observability must change no served bit (the observe-only rule; the
    # engine-grain pin lives in tests/test_obs.py, this is the probe-level
    # in-run version against the same reference rows)
    dist1j = build_dist(1, "fused", journal_events=args.journal_events)
    out1j = np.asarray(dist1j.predict(trace1))
    assert np.array_equal(out1j, ref1), (
        "journal-enabled hosts=1 engine diverged — observation leaked "
        "into control flow"
    )

    points = []
    for alpha in (0.0, 1.1):
        for hosts in hosts_sweep:
            for path in ("fused", "split"):
                qps_runs, parity_rows, keep = [], 0, None
                for rep in range(args.repeats):
                    dist, trace, wall, pr = run_once(
                        alpha, hosts, path, check_parity=(rep == 0)
                    )
                    qps_runs.append(round(args.requests / wall, 1))
                    parity_rows += pr
                    if rep == 0:
                        keep = dist
                s = keep.stats
                widths = s.mean_sub_batch_width()
                router_mean = s.routed_seeds / max(s.router_dispatches, 1)
                if hosts > 1 and s.router_dispatches:
                    assert all(
                        w <= router_mean / hosts * 1.6 + 1 for w in widths.values()
                    ), (widths, router_mean, hosts)
                merged = keep.aggregate_stats()["shards_merged"]
                lat = s.latency.snapshot()
                points.append({
                    "alpha": alpha,
                    "hosts": hosts,
                    "path": path,
                    "exchange_mode": keep.exchange_mode,
                    "clients": args.clients,
                    "skew": trace_skew_stats(trace),
                    "qps": median_min_max(qps_runs),
                    "qps_runs": qps_runs,
                    "p50_ms": round(lat["p50_ms"], 3),
                    "p99_ms": round(lat["p99_ms"], 3),
                    "router_dispatches": s.router_dispatches,
                    "routed_seeds": s.routed_seeds,
                    "coalesced": s.coalesced,
                    "router_late_admitted": s.late_admitted,
                    "mean_router_flush_width": round(router_mean, 2),
                    "mean_sub_batch_width": {
                        str(h): round(w, 2) for h, w in widths.items()
                    },
                    "exchange_id_bytes": s.exchange_id_bytes,
                    "exchange_logit_bytes": s.exchange_logit_bytes,
                    "shard_edge_frac": {
                        str(h): round(st["edge_frac"], 4)
                        for h, st in keep.shard_topo_stats.items()
                    },
                    "shards_merged": {
                        k: merged[k]
                        for k in ("dispatches", "dispatch_calls",
                                  "execute_calls", "late_admitted",
                                  "dispatched_seeds", "padded_seeds",
                                  "coalesced")
                    },
                    "parity_rows_checked": parity_rows,
                })

    # saturated aggregate per (hosts, path): requests/s over the summed
    # walls across skews, from the per-repeat medians
    saturated = {}
    for hosts in hosts_sweep:
        for path in ("fused", "split"):
            ps = [p for p in points if p["hosts"] == hosts and p["path"] == path]
            wall = sum(args.requests / p["qps"]["median"] for p in ps)
            saturated[f"hosts{hosts}_{path}"] = round(
                len(ps) * args.requests / wall, 1
            )
    fused_beats_split = {
        str(h): saturated[f"hosts{h}_fused"] > saturated[f"hosts{h}_split"]
        for h in hosts_sweep
    }
    # the headline claim, restated with the NEXT.md noise discipline. At
    # hosts > 1 one-dispatch must beat two-dispatch OUTRIGHT: the split
    # path pays the per-flush feature exchange there, a structural ~5x
    # gap far above this box's noise. At hosts = 1 the two paths differ
    # by one eager dispatch per flush — a delta the 1-core box's
    # run-to-run drift exceeds in either direction (observed: the
    # saturated medians flip sign across whole probe runs), so the honest
    # per-point assert is median-wins OR overlapping per-run spreads;
    # pretending the median ordering is stable would make the artifact a
    # coin flip.
    for h in hosts_sweep:
        if h > 1:
            assert fused_beats_split[str(h)], saturated
        else:
            for alpha in (0.0, 1.1):
                pf = next(p for p in points
                          if p["hosts"] == h and p["path"] == "fused"
                          and p["alpha"] == alpha)
                ps = next(p for p in points
                          if p["hosts"] == h and p["path"] == "split"
                          and p["alpha"] == alpha)
                assert (
                    pf["qps"]["median"] > ps["qps"]["median"]
                    or (pf["qps"]["min"] <= ps["qps"]["max"]
                        and ps["qps"]["min"] <= pf["qps"]["max"])
                ), (alpha, pf["qps"], ps["qps"])

    # -- late admission under an open-loop Poisson trace ----------------------
    def run_poisson(target_qps):
        eng = ServeEngine(
            model, params, make_full_sampler(), feat,
            ServeConfig(max_batch=args.max_batch, buckets=(8, args.max_batch),
                        max_delay_ms=1.0, max_in_flight=1,
                        record_dispatches=True),
        )
        eng.warmup()
        trace = zipfian_trace(n, args.poisson_requests, alpha=0.9, seed=7)
        arrivals = poisson_arrivals(args.poisson_requests, qps=target_qps, seed=3)
        handles = []
        stop = threading.Event()

        def pump_loop():
            while not stop.is_set():
                try:
                    eng.pump()
                except Exception:
                    pass
                time.sleep(2e-4)

        # 3 pump threads against a window of 1: an age-triggered partial
        # flush blocks on the window while the device runs the previous
        # one, and arrivals during the wait ride its pad lanes
        pumps = [threading.Thread(target=pump_loop) for _ in range(3)]
        [t.start() for t in pumps]
        t0 = time.perf_counter()
        for i, nid in enumerate(trace):
            dt = arrivals[i] - (time.perf_counter() - t0)
            if dt > 0:
                time.sleep(dt)
            handles.append(eng.submit(int(nid)))
        rows = [np.asarray(h.result(timeout=300)) for h in handles]
        stop.set()
        [t.join() for t in pumps]
        while eng._drainable():
            eng.flush()
        # replay determinism: admission never perturbed the key stream
        from quiver_tpu.inference import _cached_apply, batch_logits

        apply = _cached_apply(model)
        ref_sampler = make_full_sampler()
        oracle = {}
        for padded, nvalid in eng.dispatch_log:
            logits = np.asarray(
                batch_logits(apply, params, ref_sampler, feat, padded)
            )
            for i in range(nvalid):
                oracle.setdefault(int(padded[i]), logits[i])
        for nid, row in zip(trace, rows):
            assert np.array_equal(row, oracle[int(nid)]), (
                f"POISSON PARITY VIOLATION at node {int(nid)}"
            )
        st = eng.stats
        assert st.execute_calls == st.dispatches  # fused single-host engine
        return {
            "target_qps": target_qps,
            "requests": args.poisson_requests,
            "late_admitted": st.late_admitted,
            "dispatches": st.dispatches,
            "execute_calls": st.execute_calls,
            "dispatched_seeds": st.dispatched_seeds,
            "padded_seeds": st.padded_seeds,
            "coalesced": st.coalesced,
            "parity_rows_checked": len(rows),
        }

    poisson_points = [
        run_poisson(float(q)) for q in args.poisson_qps.split(",")
    ]
    # the acceptance claim: pad slack retired real requests under Poisson
    assert sum(p["late_admitted"] for p in poisson_points) > 0, poisson_points

    # -- observability: instrumented saturated run + enabled-vs-disabled cost --
    from quiver_tpu import comm as comm_mod
    from quiver_tpu.trace import SpanRecorder

    # (a+b+c) one saturated threaded run with the journal + fleet registry
    # + comm exchange spans ON: journal-derived per-stage breakdown,
    # Perfetto timeline, Prometheus dump — parity re-asserted in-run by
    # run_once (the replay oracle does not care that the journal watched)
    obs_hosts = hosts_sweep[-1]
    comm_rec = comm_mod.record_exchange_spans(SpanRecorder())
    dist_obs, _, wall_obs, obs_parity_rows = run_once(
        1.1, obs_hosts, "fused", check_parity=True,
        journal_events=args.journal_events,
    )
    fleet = dist_obs.fleet_snapshot()
    prom_text = dist_obs.fleet_registry().to_prometheus()
    timeline_doc = dist_obs.export_chrome_trace(args.timeline or "")
    comm_mod.record_exchange_spans(None)
    rb = fleet["router"]
    assert rb["requests"] > 0 and rb["flushes"] > 0, rb
    assert any(
        fleet["per_shard"][h]["device_ms"]["n"] > 0 for h in fleet["per_shard"]
    ), fleet["per_shard"]
    assert rb["pad_frac"]["n"] == rb["flushes"], rb
    # overlap CONSISTENCY, not an overlap demand: whether two flushes
    # ever sat in flight together is a scheduling fact (the engines'
    # inflight_peak counters record it); the structural invariant is that
    # the timeline must not HIDE overlap that happened — a second flush
    # lane exists iff two flushes' assemble->resolve intervals overlapped
    lane_names = [
        e["args"]["name"]
        for e in timeline_doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    ]
    timeline_overlapped = any(tn.startswith("flushes/") for tn in lane_names)
    ran_overlapped = dist_obs.stats.inflight_peak > 1 or any(
        e.stats.inflight_peak > 1 for e in dist_obs.engines.values()
    )
    if ran_overlapped:
        assert timeline_overlapped, (
            "in-flight overlap happened (inflight_peak > 1) but the "
            "timeline shows no second flush lane", lane_names
        )
    assert prom_text.count("# TYPE") > 20, "fleet exposition suspiciously thin"

    # (d) enabled-vs-disabled saturated QPS, median-of-3 INTERLEAVED runs
    # (off/on pairs back to back so box drift hits both sides): the
    # "cheap enough to leave on" claim, measured. Under 3% — or
    # indistinguishable from this box's run-to-run spread (ranges
    # overlap), which is the honest reading when the true delta is
    # smaller than the noise floor.
    qps_obs_on, qps_obs_off = [], []
    for _ in range(3):
        _, _, w_off, _ = run_once(1.1, hosts_sweep[0], "fused", False)
        _, _, w_on, _ = run_once(
            1.1, hosts_sweep[0], "fused", False,
            journal_events=args.journal_events,
        )
        qps_obs_off.append(round(args.requests / w_off, 1))
        qps_obs_on.append(round(args.requests / w_on, 1))
    obs_overhead_frac = 1.0 - (
        median_min_max(qps_obs_on)["median"]
        / median_min_max(qps_obs_off)["median"]
    )
    obs_ranges_overlap = (
        min(qps_obs_on) <= max(qps_obs_off)
        and min(qps_obs_off) <= max(qps_obs_on)
    )
    assert obs_overhead_frac < 0.03 or obs_ranges_overlap, (
        obs_overhead_frac, qps_obs_on, qps_obs_off
    )

    # -- measured dispatch costs: split legs, fused step, and the delta -------
    from quiver_tpu.inference import _cached_apply, time_eval_split

    apply = _cached_apply(model)
    t_sample, t_forward = time_eval_split(
        apply, params, make_full_sampler(), feat,
        np.arange(args.max_batch, dtype=np.int64), iters=20,
    )
    timer_eng = ServeEngine(
        model, params, make_full_sampler(), feat,
        ServeConfig(max_batch=args.max_batch, buckets=(args.max_batch,)),
    )
    timer_eng.warmup()
    twin = make_full_sampler()
    seeds = np.arange(args.max_batch, dtype=np.int64)
    np.asarray(timer_eng._programs(args.max_batch, params, twin.next_call(), seeds))
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        out = timer_eng._programs(args.max_batch, params, twin.next_call(), seeds)
    np.asarray(out)
    t_fused = (time.perf_counter() - t0) / iters
    overhead = max((t_sample + t_forward) - t_fused, 0.0)

    tables = {}
    for dpf in (1, 2):
        pred = serve_table(
            0.0, 0.0, t_fused, ref_batch=args.max_batch,
            buckets=(8, args.max_batch), hit_rates=(0.0, 0.5),
            unique_frac=0.8, max_delay_ms=2.0, out_dim=model.out_dim,
            dispatches_per_flush=dpf, dispatch_overhead_s=overhead,
        )
        tables[str(dpf)] = {
            "rows": [p._asdict() for p in pred],
            "md": format_serve_markdown(pred),
        }

    out = {
        "metric": "serve_probe_obs",
        "git_revision": git_revision(),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "repeats": args.repeats,
        "backend": jax.devices()[0].platform,
        "note": (
            "median-of-N with min/max per point: per-run numbers on this "
            "noisy 1-core box flip run to run (NEXT.md); read the medians "
            "and the spread together"
        ),
        "points": points,
        "hosts1_vs_single_host_parity_rows": hosts1_parity_rows,
        "saturated_qps": saturated,
        "fused_beats_split": fused_beats_split,
        "poisson_late_admission": poisson_points,
        "measured_sample_s": round(t_sample, 6),
        "measured_forward_s": round(t_forward, 6),
        "measured_fused_step_s": round(t_fused, 6),
        "measured_split_minus_fused_s": round(overhead, 6),
        "cost_source": "eval_split+fused_step",
        "serve_table_by_dispatches_per_flush": tables,
        "obs": {
            "journal_events": args.journal_events,
            "hosts": obs_hosts,
            "qps": round(args.requests / wall_obs, 1),
            "parity_rows_checked_with_journal_on": obs_parity_rows,
            # journal-derived per-request per-stage medians/tails + the
            # per-flush pad occupancy the QoS work will be judged by
            "router_breakdown": fleet["router"],
            "per_shard_breakdown": {
                str(h): fleet["per_shard"][h] for h in fleet["per_shard"]
            },
            "timeline_path": args.timeline,
            "timeline_events": len(timeline_doc["traceEvents"]),
            "timeline_overlapped_flush_lanes": timeline_overlapped,
            "prometheus_families": prom_text.count("# TYPE"),
            "prometheus": prom_text,
            "overhead": {
                "qps_on": qps_obs_on,
                "qps_off": qps_obs_off,
                "frac": round(obs_overhead_frac, 4),
                "ranges_overlap": obs_ranges_overlap,
            },
        },
        "serve_obs_overhead_frac": round(obs_overhead_frac, 4),
    }
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")


if __name__ == "__main__":
    main()
