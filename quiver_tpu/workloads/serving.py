"""Temporal & link-prediction SERVING — the workloads subsystem's engine
layer (ROADMAP item 4, round 19).

`TemporalServeEngine` / `TemporalDistServeEngine` serve the two workloads
production graph systems actually run — feed ranking (temporal neighbor
sampling) and retrieval (link-prediction scoring) — over every serving
layer rounds 8-18 built, changing none of their contracts:

- **per-request query time** ``t`` joins the request key: coalescing and
  both caches key by ``(node, t_bucket)`` under the params version — the
  first real exercise of versioned-cache semantics beyond weight bumps
  (two requests for one node at different times are DIFFERENT
  computations; two inside one ``t_quantum`` window share one). A graph
  delta invalidates an affected seed at EVERY cached t
  (`EmbeddingCache.invalidate_nodes`).
- **one dispatch** per flush still: the padded query-time vector is an
  ARGUMENT of the sealed AOT bucket executables
  (`inference.make_temporal_serve_step` — t is never a closure constant,
  per the NEXT.md rule), padded exactly like the seeds and logged beside
  them, so replay determinism survives untouched.
- **pairs ride the same path**: ``submit_pair(u, v, t=)`` submits both
  endpoints through the shared coalescer/cache (split-owner pairs become
  two sub-batches through `comm.exchange_serve` — with the query times
  bitcast alongside the seed ids, a payload the exchange never carried
  before) and scores completed rows through a seeded `PairHead`
  (`workloads.linkpred`).

Parity discipline: every dispatch-log entry records ``(padded_seeds,
n_valid, padded_t)``; `replay_temporal_log` / `replay_temporal_fleet_oracle`
replay them through a twin temporal sampler over the FULL graph + table,
and every served row must bit-match a candidate — the same oracle shape
rounds 10-17 pinned, extended by the t axis. ``hosts=1`` degenerates to
the single-host temporal engine bit for bit (same submit sequence, same
key stream, same quantization arithmetic — pinned in
tests/test_temporal.py).

Scope note (v1): the temporal ROUTER serves a frozen temporal graph
(owner shards built once by `TemporalDistServeEngine.build`); streaming
temporal commits are a SINGLE-HOST capability this round
(`TemporalServeEngine` over a ``StreamingTiledGraph(edge_ts=...)`` —
`update_graph` carries timestamps through the whole fence). Fleet-wide
temporal deltas ride the round-17 incremental-closure machinery and are
the named remaining leverage in ROADMAP item 4's DONE note, as are the
round-15 fleet policies (replica/hedging) for temporal traffic.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..comm import TpuComm
import threading

from ..serve.dist import (
    ClosureFeature,
    DistServeConfig,
    DistServeEngine,
    _LegRun,
    _RoutedFlush,
    _bounded_leg_schedule,
    closure_masks,
    contiguous_partition,
    resolve_exchange_mode,
    shard_from_mask,
)
from ..serve.engine import (
    DEFAULT_TENANT,
    ServeConfig,
    ServeEngine,
    ServeResult,
)
from ..utils import CSRTopo
from .linkpred import LinkPredictor, PairHead, PairResult
from .temporal import TemporalTiledGraph

__all__ = [
    "TemporalDistServeEngine",
    "TemporalServeEngine",
    "quantize_t",
    "quantize_t_many",
    "replay_temporal_fleet_oracle",
    "replay_temporal_log",
]


def quantize_t(t: float, quantum: float) -> float:
    """The ONE t-bucketing rule both engines (and every cache key) share:
    ``floor(t / quantum) * quantum`` snapped to the FLOAT32 grid — a
    query is served AS OF its bucket's floor, so a cached row is t-AGED
    by at most one quantum but never sees an edge from the query's
    future (conservative staleness, the same direction as cache aging).
    ``quantum = 0`` keys exact query times (every distinct t is its own
    computation).

    Two float details are load-bearing (the hosts=1 parity pin and the
    fleet-oracle key lookups ride them): the returned bucket value is
    float32-ROUNDED, because query times travel the serve exchange as
    float32 (bitcast beside the ids) and the owner re-quantizes what it
    receives — an f64 bucket value would change under that round-trip.
    And idempotence is handled EXACTLY, not by an epsilon nudge: an
    on-grid bucket value degraded through float32 can sit below its own
    boundary (at ``t/quantum ~ 1e3`` by ~1e-5 absolute — a fixed 1e-9
    nudge provably mis-floors it, and a relative nudge grows into whole
    buckets at epoch-second timestamps; both shipped briefly), so the
    NEAREST bucket is checked first: when ``t`` is float32-equal to a
    bucket value, it IS that bucket (a re-quantization returns its input
    bit for bit). Fresh query times take the plain floor — only a t
    within float32 ULP of a boundary can land in the upper bucket, and
    at that distance the two are the same float on the wire anyway."""
    t = float(t)
    if quantum <= 0 or not math.isfinite(t):
        return t
    x = t / quantum
    j = round(x)
    snapped = float(np.float32(j * quantum))
    if snapped == float(np.float32(t)):
        return snapped  # t is (a float32 round-trip of) a bucket value
    return float(np.float32(math.floor(x) * quantum))


def quantize_t_many(t, quantum: float) -> np.ndarray:
    """`quantize_t` over an ARRAY of query times (round 20): the batch
    submit path quantizes its whole t vector in a handful of numpy ops
    instead of one scalar float32 round-trip per request. Element-wise
    EQUAL to the scalar rule — same banker's rounding (`np.rint` ==
    Python `round`), same float32 grid snap, same nearest-bucket-first
    idempotence check (NEVER an epsilon nudge — the docstring above
    explains why both nudges mis-bucket), same non-finite/`quantum <= 0`
    passthrough — pinned across the f32 grid in tests/test_frontend.py.
    Returns float64 ``[n]`` (bucket values, float32-rounded like the
    scalar's return)."""
    tv = np.asarray(t, np.float64).reshape(-1).copy()
    if quantum <= 0:
        return tv
    finite = np.isfinite(tv)
    if not finite.any():
        return tv
    tf = tv[finite]
    x = tf / quantum
    j = np.rint(x)  # round-half-to-even, bit-matching Python round()
    snapped = (j * quantum).astype(np.float32).astype(np.float64)
    t32 = tf.astype(np.float32).astype(np.float64)
    floored = (np.floor(x) * quantum).astype(np.float32).astype(np.float64)
    tv[finite] = np.where(snapped == t32, snapped, floored)
    return tv


class _PairServing:
    """``submit_pair`` / ``predict_pairs`` on both temporal engines —
    thin delegations to ONE `linkpred.LinkPredictor` over ``self`` (the
    engine-level spelling exists so pair serving reads as a first-class
    workload; the logic lives in linkpred.py once)."""

    def _linkpred(self) -> LinkPredictor:
        lp = getattr(self, "_lp", None)
        if lp is None or lp.head is not self.pair_head:
            lp = self._lp = LinkPredictor(self, self.pair_head)
        return lp

    def submit_pair(self, u: int, v: int, t: Optional[float] = None,
                    tenant: Optional[str] = None) -> PairResult:
        """Score candidate edge ``(u, v)`` as of time ``t``: two seed
        lookups through the shared coalescer/cache (+ exchange on the
        routed engine), combined by this engine's `PairHead`. Endpoints
        coalesce with ANY concurrent request for the same ``(node,
        t_bucket)`` — including the other half of another pair."""
        return self._linkpred().submit_pair(u, v, t=t, tenant=tenant)

    def predict_pairs(self, pairs, t=None, timeout: Optional[float] = None,
                      tenants=None) -> np.ndarray:
        """Blocking batch scoring: submit every pair, drive flushes
        inline when no pollers run, score ALL completed pairs in one
        jitted head dispatch. Returns ``[P]`` float32 scores in request
        order."""
        return self._linkpred().predict_pairs(pairs, t=t, timeout=timeout,
                                              tenants=tenants)


class TemporalServeEngine(_PairServing, ServeEngine):
    """`ServeEngine` for a temporal-bound sampler: every request carries
    a query time, every flush dispatches the padded t vector through the
    sealed one-program path. See the module docstring; construction::

        sampler = GraphSageSampler(topo, sizes, dedup=False, seed=SEED)
        sampler.bind_temporal(tgraph, recency=0.02)
        eng = TemporalServeEngine(model, params, sampler, feat,
                                  ServeConfig(max_batch=32), t_quantum=8.0)
        eng.warmup()
        row = eng.predict([node], t=now)[0]
        score = eng.submit_pair(u, v, t=now).result()

    ``t=None`` means "no time bound" (``t = +inf`` — the frozen-graph
    degeneration). Temporal engines are FUSED-only: the split path would
    re-thread t through the eager sample, and one-dispatch is the point.
    """

    _temporal_capable = True

    def __init__(self, model, params, sampler, feature,
                 config: Optional[ServeConfig] = None,
                 t_quantum: float = 0.0,
                 pair_head: Optional[PairHead] = None):
        if getattr(sampler, "temporal", None) is None:
            raise TypeError(
                "TemporalServeEngine needs a temporal-bound sampler "
                "(GraphSageSampler.bind_temporal)"
            )
        self.t_quantum = float(t_quantum)
        self.pair_head = pair_head or PairHead("dot")
        super().__init__(model, params, sampler, feature, config)
        if self._programs is None:
            raise ValueError(
                "temporal serving is fused-only (dispatch_mode='split' "
                "or a host-gather feature cannot carry the query-time "
                "argument through one program)"
            )

    # -- request path (composite (node, t_bucket) keys) -------------------

    def _tq(self, t: Optional[float]) -> float:
        return quantize_t(math.inf if t is None else t, self.t_quantum)

    def submit(self, node_id: int, t: Optional[float] = None,
               tenant: Optional[str] = None) -> ServeResult:
        """`ServeEngine.submit` with the request key extended by the
        query-time bucket: cache hits, coalescing, shedding, and late
        admission all happen per ``(node, t_bucket)`` — `submit_many` of
        ONE through the shared `_admit_one_locked` body, so the pinned
        admission sequence can never drift between workloads."""
        return self.submit_many(
            (node_id,), t=None if t is None else (t,), tenant=tenant
        )[0]

    def submit_many(self, node_ids, t=None, tenant=None
                    ) -> List[ServeResult]:
        """`ServeEngine.submit_many` with the t axis: the whole batch's
        query times quantize in ONE vectorized `quantize_t_many` pass
        (bit-equal to per-request `quantize_t` — the composite keys, and
        therefore cache/coalesce decisions and the dispatch log, are
        identical to N scalar submits). ``t`` is None (+inf), scalar, or
        aligned with ``node_ids``."""
        ids = np.asarray(node_ids, np.int64).reshape(-1)
        tq = quantize_t_many(_aligned_t(t, ids.shape[0]), self.t_quantum)
        nodes = ids.tolist()
        keys = list(zip(nodes, tq.tolist()))
        return self._submit_keyed_many(
            keys, nodes, tenant, uniq_arr=_composite_uniq_arr(ids, tq)
        )

    def predict(self, node_ids, t=None, timeout: Optional[float] = None,
                tenants: Optional[Sequence[str]] = None) -> np.ndarray:
        """Blocking convenience (`ServeEngine.predict` + the t axis):
        ``t`` is scalar or aligned with ``node_ids``; None = +inf."""
        ids = np.asarray(node_ids).reshape(-1)
        tv = _aligned_t(t, ids.shape[0])
        if tenants is not None and len(tenants) != ids.shape[0]:
            raise ValueError(
                f"tenants has {len(tenants)} entries for {ids.shape[0]} ids"
            )
        handles = self.submit_many(ids, t=tv, tenant=tenants)
        if not handles:
            return np.zeros((0, 0), np.float32)
        if not self._running:
            while not handles.done() and self._drainable():
                self.flush()
        return self.results_many(handles, timeout)

    # -- flush hooks (the (node, t) key -> dispatch-array split) -----------

    def _flush_arrays(self, fl):
        nodes = np.asarray([k[0] for k in fl.keys], np.int64)
        ts = np.asarray([k[1] for k in fl.keys], np.float32)
        return nodes, (ts,)

    def _dispatch_log_entry(self, fl, padded):
        # (padded seeds, n_valid, padded t): everything a temporal replay
        # needs — replay_temporal_log consumes exactly this shape
        return (padded.copy(), len(fl.keys), fl.extra[0].copy())

    def _split_sample(self, fl, padded):
        raise RuntimeError("temporal serving is fused-only")  # unreachable

    def _prefetch_pending(self) -> None:
        # base walks the pending keys as seed ids; temporal keys are
        # (node, t) pairs — walk the nodes, memo the composite keys
        keys = self._pending.ordered_keys()
        if not keys:
            return
        try:
            self.prefetch_seeds(np.asarray([k[0] for k in keys], np.int64))
            self._pf_walked = frozenset(keys)
        except Exception:
            pass


def _aligned_t(t, n: int) -> np.ndarray:
    """Per-request float64 query times from a scalar/array/None ``t``."""
    if t is None:
        return np.full((n,), np.inf)
    tv = np.asarray(t, np.float64).reshape(-1)
    if tv.shape[0] == 1 and n != 1:
        tv = np.broadcast_to(tv, (n,)).copy()
    if tv.shape[0] != n:
        raise ValueError(f"t has {tv.shape[0]} entries for {n} requests")
    return tv


# structured dtype mirroring the composite (node, t_bucket) key: np.unique
# over it compares lexicographically by (node, t), which matches tuple-key
# dict equality exactly (the one divergence — NaN — is gated inside
# `_batch_uniq`), so the round-22 whole-batch vectorized admission works
# per unique COMPOSITE key on the temporal engines
_COMPOSITE_KEY_DTYPE = np.dtype([("n", np.int64), ("t", np.float64)])


def _composite_uniq_arr(ids: np.ndarray, tq: np.ndarray) -> np.ndarray:
    """The batch's ``(node, t_bucket)`` keys as ONE structured array —
    the `uniq_arr` the base `_submit_keyed_many` feeds `_batch_uniq`.
    ``tq`` is `quantize_t_many`'s float64 output, whose values are
    exactly the python floats ``tq.tolist()`` puts in the tuple keys."""
    uq = np.empty(ids.shape[0], dtype=_COMPOSITE_KEY_DTYPE)
    uq["n"] = ids
    uq["t"] = tq
    return uq


class TemporalDistServeEngine(_PairServing, DistServeEngine):
    """The routed temporal engine: `DistServeEngine`'s owner-sharded
    front end with the query time riding every hop — the router keys and
    coalesces by ``(node, t_bucket)``, the owner split ships each
    sub-batch's times beside its seed ids (bitcast through the id
    all_to_all in collective mode — `comm.exchange_serve(host2ts=)`; a
    ``t=`` keyword on the direct owner legs in host mode), and each
    owner is a full `TemporalServeEngine` over its halo-closure temporal
    shard. Split-owner pairs (``submit_pair`` endpoints owned by
    different hosts) become two sub-batches through the exchange — the
    shape the acceptance probe pins against `replay_temporal_fleet_oracle`.

    Build with :meth:`build` (frozen temporal graph; see the module
    docstring's scope note). Round-15/16/17 fleet policies (replication,
    hedging, fault injection, elastic scale, streaming commits) are not
    wired for temporal traffic yet and their knobs are rejected loudly.
    """

    def __init__(self, engines, global2host, out_dim,
                 config: Optional[DistServeConfig] = None,
                 comm: Optional[TpuComm] = None,
                 shard_topo_stats=None,
                 t_quantum: float = 0.0,
                 pair_head: Optional[PairHead] = None):
        config = config or DistServeConfig()
        unsupported = [
            name for name, bad in (
                ("replicate_top_k", config.replicate_top_k),
                ("hedge_deadline_ms", config.hedge_deadline_ms),
                ("full_graph_fallback", config.full_graph_fallback),
                ("fault_injector", config.fault_injector is not None),
                ("streaming", config.streaming),
            ) if bad
        ]
        if unsupported:
            raise ValueError(
                "TemporalDistServeEngine v1 routes plainly — unsupported "
                f"config knobs set: {unsupported} (see ROADMAP item 4's "
                "remaining-leverage note)"
            )
        self.t_quantum = float(t_quantum)
        self.pair_head = pair_head or PairHead("dot")
        super().__init__(engines, global2host, out_dim, config=config,
                         comm=comm, shard_topo_stats=shard_topo_stats)

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, model, params, csr_topo: CSRTopo, edge_ts, feat,
              sizes: Sequence[int], *, hosts: int,
              config: Optional[DistServeConfig] = None,
              global2host: Optional[np.ndarray] = None,
              sampler_seed: int = 0, recency: float = 0.0,
              max_deg: int = 512, t_quantum: float = 0.0,
              out_dim: Optional[int] = None,
              pair_head: Optional[PairHead] = None, mesh=None,
              ) -> "TemporalDistServeEngine":
        """Partition a frozen temporal graph by seed ownership: per host,
        the halo-closure topology shard (`closure_masks` +
        `shard_from_mask`, the round-10 machinery) with its edge
        TIMESTAMPS sliced by the same kept-edge mask — a closure shard's
        rows are bit-identical to the full graph's, timestamps included,
        so an owner's temporal draws for owned seeds match a full-graph
        temporal sampler on the same key stream (the oracle contract) —
        a `ClosureFeature` over the feature closure, and a fused
        `TemporalServeEngine` per owner. Every shard sampler is born
        with the same ``sampler_seed``, like every build since round
        10."""
        import jax

        from ..pyg.sage_sampler import GraphSageSampler

        config = config or DistServeConfig(hosts=hosts)
        if config.hosts != hosts:
            raise ValueError(f"config.hosts={config.hosts} != hosts={hosts}")
        if config.feature_residency != "closure":
            raise ValueError(
                "temporal owners are fused-only: feature_residency must "
                "be 'closure'"
            )
        feat = np.asarray(feat, np.float32)
        edge_ts = np.asarray(edge_ts, np.float32).reshape(-1)
        indptr = np.asarray(csr_topo.indptr, np.int64)
        indices = np.asarray(csr_topo.indices, np.int64)
        n = indptr.shape[0] - 1
        if edge_ts.shape[0] != indices.shape[0]:
            raise ValueError(
                f"edge_ts has {edge_ts.shape[0]} entries for "
                f"{indices.shape[0]} edges"
            )
        if global2host is None:
            global2host = contiguous_partition(n, hosts)
        out_dim = (out_dim if out_dim is not None
                   else getattr(model, "out_dim", None))
        if out_dim is None:
            raise ValueError("pass out_dim= (model has no out_dim attribute)")
        mode = resolve_exchange_mode(config.exchange, hosts)
        comm = None
        if mode == "collective":
            if mesh is None:
                from jax.sharding import Mesh

                devs = jax.devices()
                if len(devs) < hosts:
                    raise ValueError(
                        f"exchange='collective' needs >= {hosts} devices"
                    )
                mesh = Mesh(np.array(devs[:hosts]), ("serve_host",))
            comm = TpuComm(rank=0, world_size=hosts, hosts=hosts, mesh=mesh,
                           axis="serve_host")
        shard_cfg = config.resolved_shard_config()
        src_per_edge = np.repeat(
            np.arange(n, dtype=np.int64), (indptr[1:] - indptr[:-1])
        )
        engines: Dict[int, TemporalServeEngine] = {}
        topo_stats: Dict[int, Dict[str, float]] = {}
        for h in range(hosts):
            seed_mask = np.asarray(global2host) == h
            topo_mask, feat_mask = closure_masks(
                indptr, indices, seed_mask,
                hops=len(sizes) - 1, feat_hops=len(sizes),
                src_per_edge=src_per_edge,
            )
            topo_h, edge_stats = shard_from_mask(
                csr_topo, topo_mask, src_per_edge=src_per_edge
            )
            # the SAME kept-edge rule shard_from_mask applies internally:
            # timestamps of dropped rows drop with their edges, kept rows
            # keep theirs bit for bit
            ts_h = edge_ts[topo_mask[src_per_edge]]
            closure_ids = np.nonzero(feat_mask)[0]
            topo_stats[h] = {
                "owned_nodes": int(seed_mask.sum()),
                "closure_nodes": int(topo_mask.sum()),
                "feature_closure_nodes": int(feat_mask.sum()),
                **edge_stats,
            }
            sampler = GraphSageSampler(
                topo_h, sizes=sizes, mode="TPU", seed=sampler_seed,
                dedup=False, max_deg=max_deg,
            )
            sampler.bind_temporal(
                TemporalTiledGraph(topo_h, ts_h), recency=recency
            )
            local_map = np.full(n, -1, np.int32)
            local_map[closure_ids] = np.arange(
                closure_ids.shape[0], dtype=np.int32
            )
            shard_feat = ClosureFeature(feat[closure_ids], local_map)
            engines[h] = TemporalServeEngine(
                model, params, sampler, shard_feat, shard_cfg,
                t_quantum=t_quantum, pair_head=pair_head,
            )
        return cls(
            engines, global2host, out_dim, config=config, comm=comm,
            shard_topo_stats=topo_stats, t_quantum=t_quantum,
            pair_head=pair_head,
        )

    def _make_answerer(self, host: int):
        """The temporal serve-exchange hook: query times arrive bitcast
        beside the ids (``ts=`` keyword, requester-major like the ids)
        and thread into the owner's temporal predict."""

        def answer(recv_ids: np.ndarray,
                   recv_tenants: Optional[np.ndarray] = None,
                   ts: Optional[np.ndarray] = None) -> np.ndarray:
            recv_ids = np.asarray(recv_ids)
            out = np.zeros(
                (recv_ids.shape[0], recv_ids.shape[1], self.out_dim),
                np.float32,
            )
            for req in range(recv_ids.shape[0]):
                valid = recv_ids[req] >= 0
                if valid.any():
                    ids = recv_ids[req][valid].astype(np.int64)
                    tvals = (None if ts is None
                             else np.asarray(ts[req])[valid])
                    tenants = None
                    if recv_tenants is not None:
                        tenants = [
                            self._tenant_names[x] if 0 <= x < len(
                                self._tenant_names
                            ) else DEFAULT_TENANT
                            for x in np.asarray(recv_tenants[req])[valid]
                        ]
                    out[req, valid] = np.asarray(
                        self.engines[host].predict(ids, t=tvals,
                                                   tenants=tenants)
                    )
            return out

        return answer

    # -- request path ------------------------------------------------------

    def _tq(self, t: Optional[float]) -> float:
        return quantize_t(math.inf if t is None else t, self.t_quantum)

    def submit(self, node_id: int, t: Optional[float] = None,
               tenant: Optional[str] = None) -> ServeResult:
        """`DistServeEngine.submit` keyed by ``(node, t_bucket)`` —
        `submit_many` of ONE through the base `_admit_one_locked` body,
        so router and single-host temporal admission can never drift
        (the hosts=1 parity pin)."""
        return self.submit_many(
            (node_id,), t=None if t is None else (t,), tenant=tenant
        )[0]

    def submit_many(self, node_ids, t=None, tenant=None
                    ) -> List[ServeResult]:
        """`DistServeEngine.submit_many` with the t axis: vectorized
        id-range validation up front, then one `quantize_t_many` pass
        over the batch's query times — composite keys (and the router
        dispatch log) bit-identical to N scalar submits."""
        ids = np.asarray(node_ids, np.int64).reshape(-1)
        n_ids = self.global2host.shape[0]
        bad = (ids < 0) | (ids >= n_ids)
        if bad.any():
            raise ValueError(
                f"node id {int(ids[bad][0])} outside [0, {n_ids})"
            )
        tq = quantize_t_many(_aligned_t(t, ids.shape[0]), self.t_quantum)
        nodes = ids.tolist()
        keys = list(zip(nodes, tq.tolist()))
        return self._submit_keyed_many(
            keys, nodes, tenant, uniq_arr=_composite_uniq_arr(ids, tq)
        )

    def predict(self, node_ids, t=None, timeout: Optional[float] = None,
                tenants: Optional[Sequence[str]] = None) -> np.ndarray:
        ids = np.asarray(node_ids).reshape(-1)
        tv = _aligned_t(t, ids.shape[0])
        if tenants is not None and len(tenants) != ids.shape[0]:
            raise ValueError(
                f"tenants has {len(tenants)} entries for {ids.shape[0]} ids"
            )
        handles = self.submit_many(ids, t=tv, tenant=tenants)
        if not handles:
            return np.zeros((0, self.out_dim), np.float32)
        if not self._running:
            while not handles.done() and self._drainable():
                self.flush()
        return self.results_many(handles, timeout)

    # -- routed flush stages ----------------------------------------------

    def _seal_assembled(self, fl: _RoutedFlush) -> None:
        """The temporal owner split: nodes/ts arrays from the composite
        keys, split by ``global2host[node]``, each sub-batch's times kept
        position-aligned (mirrors the base seal minus the replica
        re-route — no temporal replicas in v1)."""
        with self._lock:
            self._open = None
        self._flush_index += 1
        if self.workload is not None:
            self.workload.tick()
        self.journal.emit("seal", -1, fl.fid, len(fl.keys), fl.bucket)
        # epoch pin (round 24), mirroring the base seal — the temporal
        # router is frozen-graph in v1 so the stamp is constant 0, but
        # the aligned-list invariant holds fleet-wide
        fl.graph_version = self.graph_version
        try:
            arr = np.asarray([k[0] for k in fl.keys], np.int64)
            tvec = np.asarray([k[1] for k in fl.keys], np.float32)
            fl.extra = tvec
            fl.tenants = [s.tenant for s in fl.slots]
            fl.ids = arr
            fl.rids = np.fromiter(
                (s.rid for s in fl.slots), np.int64, len(fl.slots)
            )
            tix = self._tenant_index
            fl.tenant_ix = np.fromiter(
                (tix.get(tn, -1) for tn in fl.tenants), np.int32,
                len(fl.tenants),
            )
            owners = self.global2host[arr].astype(np.int64)
            # one owner partition via stable argsort (round 20), mirroring
            # the base seal: hosts ascending, positions ascending within
            if arr.size:
                order = np.argsort(owners, kind="stable")
                so = owners[order]
                cuts = np.nonzero(np.diff(so))[0] + 1
                for pos in np.split(order, cuts):
                    h = int(owners[pos[0]])
                    if 0 <= h < self.hosts:
                        fl.split.append((h, arr[pos], pos))
            if self.config.record_dispatches:
                self.dispatch_log.append(
                    (arr.copy(),
                     [(h, ids.copy()) for h, ids, _ in fl.split],
                     tvec.copy())
                )
                self.dispatch_graph_versions.append(fl.graph_version)
            if self.config.tier_prefetch:
                for h, ids, _ in fl.split:
                    eng = self.engines.get(h)
                    if eng is None:
                        continue
                    try:
                        eng.prefetch_seeds(ids, fid=fl.fid)
                    except Exception:
                        pass
        except BaseException as exc:
            fl.error = exc

    def _dispatch(self, fl: _RoutedFlush) -> Optional[np.ndarray]:
        """Plain temporal routing: ship each owner sub-batch with its
        query times — `comm.exchange_serve(host2ts=)` in collective mode
        (the ts lanes ride the id all_to_all bitcast), direct
        ``predict(ids, t=)`` legs in host mode. An owner failure poisons
        the whole flush (v1: no hedging/failover for temporal traffic —
        module docstring scope note)."""
        self.journal.emit("dispatch", -1, fl.fid, fl.bucket)
        wl = self.workload
        out = np.zeros((len(fl.keys), self.out_dim), np.float32)
        tvec = fl.extra
        if self.exchange_mode == "collective":
            by_host = {h: (ids, pos) for h, ids, pos in fl.split}
            if by_host:
                host2ids = [
                    by_host[h][0] if h in by_host else np.array([], np.int64)
                    for h in range(self.hosts)
                ]
                host2ts = [
                    (tvec[by_host[h][1]] if h in by_host else [])
                    for h in range(self.hosts)
                ]
                host2tenants = None
                if self._tenant_names and fl.tenants:
                    host2tenants = [
                        (
                            [self._tenant_index.get(fl.tenants[int(p)], -1)
                             for p in by_host[h][1]]
                            if h in by_host else []
                        )
                        for h in range(self.hosts)
                    ]
                t_x0 = self._clock() if wl is not None else 0.0
                res = self.comm.exchange_serve(
                    host2ids, out_dim=self.out_dim, budget=self._budget,
                    host2tenants=host2tenants, host2ts=host2ts,
                )
                if wl is not None:
                    dt = self._clock() - t_x0
                    for h, ids, _ in fl.split:
                        wl.observe_flush(h, len(ids), dt)
                L = self._budget
                with self._lock:
                    # ids + the bitcast ts lanes: both are id-shaped
                    # int32 collectives (2x the round-10 id payload)
                    self.stats.exchange_id_bytes += (
                        2 * self.hosts * self.hosts * L * 4
                    )
                    self.stats.exchange_logit_bytes += (
                        self.hosts * self.hosts * L * self.out_dim * 4
                    )
                for h, (ids, pos) in by_host.items():
                    out[pos] = res[h]
        elif self.config.sequential_legs or len(fl.split) <= 1:
            for h, ids, pos in fl.split:
                t0 = self._clock()
                rows = np.asarray(
                    self.engines[h].predict(
                        ids, t=tvec[pos],
                        tenants=self._leg_tenants(fl, pos),
                    )
                )
                if wl is not None:
                    wl.observe_flush(h, len(ids), self._clock() - t0)
                out[pos] = rows
                self.journal.emit("leg_done", -1, fl.fid, h, len(ids))
        else:
            self._fanout_temporal_legs(fl, tvec, out)
        out.setflags(write=False)
        self.journal.emit("execute_done", -1, fl.fid, len(fl.split))
        return out

    def _fanout_temporal_legs(self, fl: _RoutedFlush, tvec, out) -> None:
        """Round-23 fan-out for the PLAIN temporal legs: the base
        router's start-in-order / join-in-split-order machinery
        (`_bounded_leg_schedule`, honoring ``leg_fanout``), minus the
        fleet policies temporal v1 doesn't have — no fault hook, no
        deadline, no failover. A leg error still poisons the whole
        flush, raised at ITS join so every earlier leg's effects land
        exactly as the sequential pass's would; later legs may already
        have run on their workers by then, but their effects are never
        applied — the flush is poisoned either way, and temporal owner
        engines are stateless per leg (predict-only), so the extra
        worker-side work is observable only in wall time."""
        wl = self.workload

        def body(r: _LegRun) -> None:
            box = r.box
            t0 = self._clock()
            try:
                box["rows"] = np.asarray(
                    self.engines[r.h].predict(
                        r.ids, t=tvec[r.pos], tenants=r.tenants,
                    )
                )
            except BaseException as exc:
                box["err"] = exc
            finally:
                box["dt"] = self._clock() - t0

        runs = [
            _LegRun(h, ids, pos, self._leg_tenants(fl, pos))
            for h, ids, pos in fl.split
        ]
        cap = (self.config.leg_fanout if self.config.leg_fanout > 0
               else len(runs))

        def start_leg(r: _LegRun) -> bool:
            r.t_start = self._clock()
            r.thread = threading.Thread(
                target=body, args=(r,), daemon=True,
                name=f"quiver-temporal-leg-{r.h}",
            )
            r.thread.start()
            return True

        for r in _bounded_leg_schedule(runs, cap, start_leg):
            r.thread.join()
            if "err" in r.box:
                raise r.box["err"]
            if wl is not None:
                wl.observe_flush(r.h, len(r.ids), r.box["dt"])
            out[r.pos] = r.box["rows"]
            self.journal.emit("leg_done", -1, fl.fid, r.h, len(r.ids))


# -- temporal replay oracles --------------------------------------------


def replay_temporal_log(log, model, params, sampler, feature,
                        served: Optional[Dict] = None,
                        versions: Optional[Sequence[int]] = None,
                        only_version: Optional[int] = None) -> Dict:
    """Replay one temporal dispatch log — entries ``(padded_seeds,
    n_valid, padded_t)`` — through a FRESH temporal-bound ``sampler``
    (same seed as the serving one: its key stream then matches draw for
    draw) and the offline gather+forward. Returns ``{(node, t):
    [candidate rows]}`` with ``t`` the float32 query time the dispatch
    actually carried.

    Round 24 — epoch-aware replay: ``versions`` is the engine's aligned
    ``dispatch_graph_versions`` list and ``only_version`` selects which
    epoch's rows to COLLECT. Every entry still computes (the key stream
    must advance exactly as the live run's did); entries stamped with a
    different epoch are skipped at collection. ``sampler`` must then be
    bound to the graph AS OF ``only_version``."""
    from ..inference import _cached_apply, lookup_features

    apply = _cached_apply(model)
    served = {} if served is None else served
    for ix, (padded, nvalid, tvec) in enumerate(log):
        ds = sampler.sample_dense(padded, t=tvec)
        x = lookup_features(feature, ds.n_id)
        logits = np.asarray(apply(params, x, ds.adjs))
        if only_version is not None and (
                versions is None or ix >= len(versions)
                or versions[ix] != only_version):
            continue
        for i in range(nvalid):
            served.setdefault(
                (int(padded[i]), float(np.float32(tvec[i]))), []
            ).append(logits[i])
    return served


def replay_temporal_fleet_oracle(dist: TemporalDistServeEngine, model,
                                 params, full_sampler_factory,
                                 full_feature,
                                 graph_version: Optional[int] = None
                                 ) -> Dict:
    """`replay_fleet_oracle`'s temporal shape: every owner engine's
    temporal dispatch log replays through a fresh FULL-graph temporal
    sampler (``full_sampler_factory`` must birth it with the serving
    seed and the full-graph `TemporalTiledGraph` binding) over the full
    feature table. A served row is correct iff it bit-matches a
    candidate at its ``(node, t)`` — the acceptance pin
    ``serve_probe --temporal`` asserts for the split-owner LP leg.
    ``graph_version`` filters collection to one fleet epoch's rows (see
    `replay_temporal_log`); the factory must then produce the sampler
    of that epoch's graph."""
    served: Dict = {}
    for h in sorted(dist.engines):
        eng = dist.engines[h]
        replay_temporal_log(
            eng.dispatch_log, model, params,
            full_sampler_factory(), full_feature, served=served,
            versions=(getattr(eng, "dispatch_graph_versions", None)
                      if graph_version is not None else None),
            only_version=graph_version,
        )
    return served
