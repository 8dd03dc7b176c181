"""Online serving engine: dynamic micro-batching, request coalescing, a
params-versioned embedding cache, and pipelined dispatch.

`inference.sampled_eval` is an OFFLINE loop: it owns its batch composition
and pays one sample + gather + forward per 1024 seeds. Online traffic
inverts every assumption — requests arrive one at a time, skewed toward hot
nodes, and each caller wants ONE row of logits at low latency. Paying a
full dispatch per request would burn the whole device budget on padding;
this engine turns the request stream back into efficient fixed-shape device
work with three levers, applied in order of cheapness:

1. **Embedding cache** (:class:`quiver_tpu.serve.cache.EmbeddingCache`):
   repeat requests for a node already computed under the CURRENT
   ``params_version`` are answered from host memory — no device work at
   all. `update_params` bumps the version and invalidates, so a served
   result may be cache-aged but never crosses a weight update.
2. **Cross-request coalescing**: within a flush window, identical seed ids
   collapse to ONE slot — 50 concurrent callers asking for the same hot
   node cost one sample/gather/forward and share the result. Requests
   arriving while that node is in flight attach to the in-flight slot.
3. **Dynamic micro-batching**: cache-missing unique seeds queue until
   ``max_batch`` are waiting or the oldest has aged ``max_delay_ms``, then
   flush as one batch padded to a fixed BUCKET size (powers of two up to
   ``max_batch`` by default). Fixed buckets mean one compiled program per
   bucket serves all traffic — no per-request recompiles, ever.

The device path comes in two BIT-IDENTICAL flavors. The **fused
one-dispatch path** (round 11, the default wherever the sampler/feature
pair supports it — see ``ServeConfig.dispatch_mode``) runs
sample + gather + forward as ONE pre-bound AOT executable per bucket
(`inference.make_serve_step` / `inference.BucketPrograms`): a flush costs
one execute call, `warmup()` compiles-and-seals the program table so a
retrace after warmup is structurally impossible (miss = hard error), and
the per-flush seed buffer is donated. The **split path** is the exact
`sampled_eval` inner step split in two (`inference.sample_batch` +
`inference.forward_logits` == `inference.batch_logits`) and survives for
offline eval, cost attribution, and features that must gather host-side
(tiered `Feature`). Both consume the same sampler key stream, which is
what makes served logits BIT-IDENTICAL to offline eval on the same
(sampler state, batch) pair; the parity tests replay the engine's dispatch
log through a fresh sampler and compare exactly (tests/test_serve.py).

**Pipelined dispatch (round 9) + late admission (round 11).** A flush runs
three stages:

- **assemble** — drain up to ``max_batch`` pending slots and fix the
  bucket; then, with the drained flush PUBLISHED for late admission,
  take an in-flight window permit (while the flush waits for a slot,
  `submit` keeps admitting new seeds into its pad lanes — continuous
  seed-level batching, recovering slack that round 8–10 computed and
  discarded); finally SEAL: close admission, draw the monotonic dispatch
  index, append the dispatch-log entry, and consume the sampler's next
  key. The whole stage is serialized under a small sequencing lock, so the
  sampler's key stream and the replay log are identical IN DISPATCH ORDER
  no matter how many flushes are in flight or how admissions interleave
  (``dispatch_log[i]`` is the i-th seal and consumed the sampler's i-th
  call — the determinism contract the parity replay rides).
- **dispatch** — the device work + the blocking D2H: one pre-bound
  execute on the fused path, `forward_logits` on the split path. Runs
  OUTSIDE the sequencing lock, so the next flush assembles (and the host
  batches/coalesces) while the device executes this one.
- **resolve** — unpad, cache writeback (version-checked), per-flush slot
  resolution, latency/stat accounting. Completions may land out of
  dispatch order; each flush resolves only its OWN slots, so ordering
  never leaks into results.

``ServeConfig.max_in_flight`` bounds how many flushes may sit between
assemble and resolve at once (a semaphore window). `flush()` itself stays
fully synchronous — a lone caller thread behaves exactly like the round-8
serial engine, and ``max_in_flight=1`` reproduces it bit-for-bit even under
thread races. Overlap comes from CONCURRENT flush callers: submit-filled
inline flushes on client threads, and `start()`'s ``max_in_flight`` poller
threads. Per-stage spans land in ``stats.spans``
(:class:`quiver_tpu.trace.SpanRecorder`), so measured overlap is reported
the same honest way the tiered training pipeline reports it
(``overlap_frac`` = fraction of wall with >= 2 stages active).

`update_params` FENCES: it blocks new assembles, drains every in-flight
flush, then swaps the weights and bumps the version — so no served logit is
ever computed from a params tree that changed under it mid-flush, and no
two in-flight flushes ever straddle a version (which also keeps the
in-flight coalescing map collision-free). `warmup()` pre-binds every
bucket's executable (fused: AOT lower+compile, zero keys consumed, then
SEALED — a later miss is a hard error; split: one warm dispatch through a
twin sampler where supported) so first-request latency doesn't eat a
compile.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import operator
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..inference import (
    BucketPrograms,
    _cached_apply,
    forward_logits,
    pad_seed_batch,
    sample_batch,
)
from ..trace import (
    NULL_JOURNAL,
    EventJournal,
    HitRateCounter,
    LatencyHistogram,
    MetricsRegistry,
    SpanRecorder,
    WorkloadConfig,
    WorkloadMonitor,
    export_chrome_trace as _export_chrome_trace,
    observe,
    register_hit_rate,
    trace_enabled,
    trace_scope,
)
from .cache import EmbeddingCache


DEFAULT_TENANT = "default"


class ShedError(RuntimeError):
    """Request refused at admission: the engine's queue-depth bound was
    hit and the submitting tenant is at or over its weighted quota
    (``ServeConfig.max_queue_depth`` / ``tenant_weights``). Per-request
    and deterministic — the decision reads only queue state, never wall
    time — and delivered through the returned `ServeResult`, never
    raised out of ``submit`` itself."""


class DrainTimeout(RuntimeError):
    """``stop(drain=True)`` could not retire every queued request within
    ``ServeConfig.drain_deadline_s`` (e.g. a poller or owner died
    mid-flush). Undrained slots are resolved with this error so waiters
    unblock instead of hanging, and counted in ``stats.undrained``."""


def weighted_drain_keys(pending: Dict[int, "_Slot"], cap: int,
                        tenant_weights: Optional[Dict[str, float]],
                        ) -> List[int]:
    """A flush's drain set (caller holds the owning engine's lock): FIFO
    prefix of the pending queue, except when ``tenant_weights`` is set and
    the queue overflows ``cap`` — then each tenant gets its
    largest-remainder share of the flush (FIFO within a tenant), unused
    quota refills FIFO, and the picked keys keep their queue order so
    batch composition stays deterministic. Shared by `ServeEngine` and
    `DistServeEngine` so the two front ends make identical QoS
    decisions."""
    if not tenant_weights or len(pending) <= cap:
        return list(pending)[:cap]
    by_tenant: Dict[str, List[int]] = {}
    for k, slot in pending.items():
        by_tenant.setdefault(slot.tenant, []).append(k)
    tenants = sorted(by_tenant)
    weights = {t: float(tenant_weights.get(t, 1.0)) for t in tenants}
    total = sum(weights.values()) or 1.0
    shares = {t: cap * weights[t] / total for t in tenants}
    quota = {t: int(shares[t]) for t in tenants}
    rem = cap - sum(quota.values())
    for t in sorted(tenants, key=lambda t: (-(shares[t] - quota[t]), t))[:rem]:
        quota[t] += 1
    picked = set()
    for t in tenants:
        picked.update(by_tenant[t][: quota[t]])
    keys: List[int] = [k for k in pending if k in picked]
    if len(keys) < cap:  # a tenant under-filled its quota: FIFO refill
        for k in pending:
            if k not in picked:
                keys.append(k)
                if len(keys) == cap:
                    break
        order = {k: i for i, k in enumerate(pending)}
        keys.sort(key=order.__getitem__)
    return keys


def shed_decision(pending_len: int, tenant_pending: int, tenant: str,
                  max_queue_depth: int,
                  tenant_weights: Optional[Dict[str, float]]) -> bool:
    """The deterministic shed rule shared by both front ends: shed iff
    the pending queue is at ``max_queue_depth`` AND the tenant already
    holds its weighted share of it. A tenant under quota is admitted
    even at a full queue (the bound protects light tenants from heavy
    ones, not the queue from light tenants)."""
    if max_queue_depth <= 0 or pending_len < max_queue_depth:
        return False
    if not tenant_weights:
        return True  # single implicit tenant: plain depth bound
    w = float(tenant_weights.get(tenant, 1.0))
    total = sum(float(v) for v in tenant_weights.values())
    if tenant not in tenant_weights:
        total += w
    # all-zero weights (every tenant "blocked"): fall back to the plain
    # depth bound with a 1-slot floor per tenant — never divide by zero
    quota = max(1, int(max_queue_depth * w / (total or 1.0)))
    return tenant_pending >= quota


def resolve_tenants(tenant, n: int) -> List[str]:
    """Per-request tenant names from a None / scalar / aligned-sequence
    spelling — the one normalization behind every ``submit_many``
    (single-host, router, temporal), so batch tenant semantics can never
    drift between front ends."""
    if tenant is None:
        return [DEFAULT_TENANT] * n
    if isinstance(tenant, str):
        return [tenant] * n
    tenants = [DEFAULT_TENANT if x is None else str(x) for x in tenant]
    if len(tenants) != n:
        raise ValueError(f"tenants has {len(tenants)} entries for {n} ids")
    return tenants


class _PendingStripes:
    """Striped pending-queue state shared by both serve front ends
    (round 20): ``n`` insertion-ordered dicts (key -> `_Slot`), each under
    its own lock, so concurrent submit threads for keys in different
    stripes never serialize on one engine-wide lock. A global GIL-atomic
    arrival counter stamps every inserted slot (``_Slot.seq``), and every
    ordered view merges the stripes by it — so the merged queue IS the
    single-dict FIFO of rounds 8–19 bit for bit: `weighted_drain_keys`
    over the merged view, and therefore batch composition and the
    dispatch log, cannot tell the stripes exist.

    ``stripe_key`` maps a request key to a stable stripe hint — `hash`
    on the single-host engine, the BUILD-TIME owner partition on the
    router (per-owner pending queues; the hint must never move with live
    placement, or a coalesce probe could miss its own pending slot).

    LOCK HIERARCHY: stripe locks are taken BEFORE the engine's ``_lock``,
    never after. Admission holds ONE stripe lock (or `all_locks` on the
    batch path) and takes ``_lock`` only for the brief rid/late-admission
    window inside it; drain and fence paths (`_assemble`,
    ``update_params``, `abandon_undrained`) enter `all_locks` (ascending
    index) first and only then ``_lock``. ``*_unlocked`` accessors are
    for callers already inside `all_locks`. Per-tenant pending counts
    live per stripe and SUM on read — exact whenever the caller holds
    the relevant locks or a single thread submits (the determinism
    contract's cases); unlocked reads (`__len__`, metrics gauges) are
    GIL-consistent snapshots."""

    __slots__ = ("n", "locks", "maps", "tenants", "stripe_key", "_arrival")

    def __init__(self, n: int, stripe_key: Optional[Callable] = None):
        self.n = max(1, int(n))
        self.locks = tuple(threading.Lock() for _ in range(self.n))
        self.maps: Tuple[Dict, ...] = tuple({} for _ in range(self.n))
        self.tenants: Tuple[Dict[str, int], ...] = tuple(
            {} for _ in range(self.n)
        )
        self.stripe_key = stripe_key if stripe_key is not None else hash
        self._arrival = itertools.count()  # next() is GIL-atomic

    def stripe_of(self, key) -> int:
        return self.stripe_key(key) % self.n

    def lock_for(self, key) -> threading.Lock:
        return self.locks[self.stripe_of(key)]

    @contextlib.contextmanager
    def all_locks(self):
        for lk in self.locks:
            lk.acquire()
        try:
            yield
        finally:
            for lk in reversed(self.locks):
                lk.release()

    # -- unlocked views (GIL-consistent; exact under the locks) -----------

    def __len__(self) -> int:
        return sum(len(m) for m in self.maps)

    def __bool__(self) -> bool:
        return any(self.maps)

    def get(self, key):
        return self.maps[self.stripe_of(key)].get(key)

    def tenant_count(self, tenant: str) -> int:
        return sum(t.get(tenant, 0) for t in self.tenants)

    # -- mutations (caller holds the key's stripe lock / all_locks) -------

    def insert_unlocked(self, key, slot, tenant: str) -> None:
        s = self.stripe_of(key)
        slot.seq = next(self._arrival)
        self.maps[s][key] = slot
        t = self.tenants[s]
        t[tenant] = t.get(tenant, 0) + 1

    def pop_unlocked(self, key):
        s = self.stripe_of(key)
        slot = self.maps[s].pop(key)
        t = self.tenants[s]
        n = t.get(slot.tenant, 1) - 1
        if n > 0:
            t[slot.tenant] = n
        else:
            t.pop(slot.tenant, None)
        return slot

    def clear_unlocked(self) -> None:
        for m in self.maps:
            m.clear()
        for t in self.tenants:
            t.clear()

    def values_unlocked(self):
        for m in self.maps:
            yield from m.values()

    def ordered_items_unlocked(self) -> List[Tuple[object, "_Slot"]]:
        """(key, slot) pairs in global arrival order — the exact
        single-dict insertion order striping replaced."""
        items = [kv for m in self.maps for kv in m.items()]
        items.sort(key=lambda kv: kv[1].seq)
        return items

    def ordered_dict_unlocked(self) -> Dict:
        return dict(self.ordered_items_unlocked())

    # -- self-locking views (caller must NOT hold engine._lock) -----------

    def ordered_keys(self) -> List:
        with self.all_locks():
            return [k for k, _ in self.ordered_items_unlocked()]

    def oldest_enqueue_t(self) -> Optional[float]:
        """Enqueue time of the globally oldest pending slot (None when
        empty) — the flush-age policy input. Per stripe, the head of the
        insertion-ordered dict is that stripe's oldest; the global oldest
        is the min-seq head across stripes."""
        best = None
        best_seq = None
        for lk, m in zip(self.locks, self.maps):
            with lk:
                it = iter(m.values())
                head = next(it, None)
            if head is not None and (best_seq is None or head.seq < best_seq):
                best, best_seq = head.enqueue_t, head.seq
        return best


def tenant_latency_hist(tenant_latency: Dict[str, LatencyHistogram],
                        tenant: str) -> LatencyHistogram:
    """Get-or-create a tenant's latency histogram — the one creation
    path shared by `ServeStats.tenant_hist` and
    `DistServeStats.tenant_hist`, so the router's per-tenant tails can
    never diverge from the single-host engine's in construction."""
    h = tenant_latency.get(tenant)
    if h is None:
        h = tenant_latency[tenant] = LatencyHistogram()
    return h


def register_tenant_latency(reg, prefix: str, help_text: str, get_stats,
                            tenant_weights: Optional[Dict[str, float]],
                            labels: Optional[Dict[str, str]] = None) -> None:
    """Register the per-tenant latency histogram family (``tenant``
    label): tenants known from the QoS config plus any observed so far
    (later tenants appear on the next registration call). ``get_stats``
    is a zero-arg resolver so `reset_stats` swaps are followed. Shared
    by `ServeEngine.register_metrics` and the router."""
    for t in sorted(set(tenant_weights or ())
                    | set(get_stats().tenant_latency)):
        reg.histogram(
            f"{prefix}_tenant_latency_ms", help_text,
            dict(labels or {}, tenant=str(t)),
            fn=(lambda t=t: get_stats().tenant_latency.get(t)
                or LatencyHistogram()),
        )


def register_stream_reserve(reg, prefix: str, get_stream,
                            labels: Optional[Dict[str, str]] = None) -> None:
    """Expose a bound `stream.StreamingTiledGraph`'s `reserve_report()`
    as Prometheus gauges (round-19 satellite — the r18 leftover: reserve
    runway was only visible as a `StreamCapacityError` hard failure;
    these gauges make it an alertable curve). ``get_stream`` is a
    zero-arg resolver (None = not stream-bound, gauges are skipped), so
    the family follows rebinds. Shared by `ServeEngine.register_metrics`
    and the router's per-owner registration — one naming scheme
    fleet-wide. ``projected_commits_to_exhaustion`` exports -1 while no
    consumption has been observed (None in the report: nothing honest to
    project from)."""
    if get_stream() is None:
        return

    def field(name):
        def read(name=name):
            stream = get_stream()
            if stream is None:
                return 0
            v = stream.reserve_report()[name]
            return -1 if v is None else v

        return read

    reg.gauge_fn(f"{prefix}_stream_reserve_tiles", field("reserve_tiles"),
                 "spare tile rows planned for streaming appends", labels)
    reg.gauge_fn(f"{prefix}_stream_reserve_used", field("reserve_used"),
                 "reserve tile rows consumed by spills/installs", labels)
    reg.gauge_fn(f"{prefix}_stream_reserve_free", field("reserve_free"),
                 "reserve tile rows remaining", labels)
    reg.gauge_fn(f"{prefix}_stream_reserve_rows_per_commit",
                 field("rows_per_commit"),
                 "mean reserve rows consumed per delta commit", labels)
    reg.gauge_fn(f"{prefix}_stream_reserve_projected_commits",
                 field("projected_commits_to_exhaustion"),
                 "commits of runway left at the observed consumption "
                 "rate (-1 = no consumption observed yet)", labels)
    # round-21 lifecycle gauges: the compaction planner's inputs, so the
    # "is the working set actually flat" question is alertable
    reg.gauge_fn(f"{prefix}_stream_fragmented_lanes",
                 field("fragmented_lanes"),
                 "slack lanes inside held tile rows (spill growth + "
                 "deletions) — the compaction trim target", labels)
    reg.gauge_fn(f"{prefix}_stream_reclaimable_tiles",
                 field("reclaimable_tiles"),
                 "tile rows a compaction pass could reclaim now "
                 "(spill-retired ranges + trimmable tails)", labels)
    reg.gauge_fn(f"{prefix}_stream_dead_lane_frac",
                 field("dead_lane_frac"),
                 "expired (masked) lanes as a fraction of live lane "
                 "content — appends re-use these before consuming "
                 "reserve rows", labels)


def abandon_undrained(engine, drained: bool = True) -> None:
    """Resolve whatever a bounded ``stop`` left behind with
    `DrainTimeout` and count it in ``stats.undrained`` — shared by
    `ServeEngine` and `DistServeEngine` (both expose the queue state and
    stats fields this reads). ``drained`` distinguishes the message: a
    deliberate ``stop(drain=False)`` with queued work is not a deadline
    failure and must not read like one."""
    with engine._pending.all_locks(), engine._lock:
        leftover = len(engine._pending) + len(engine._inflight)
        if not leftover and not engine._inflight_flushes:
            return
        if drained:
            msg = (
                f"stop(drain=True) abandoned {leftover} slot(s) after "
                f"{engine.config.drain_deadline_s}s "
                f"({engine._inflight_flushes} flush(es) still in flight)"
            )
        else:
            msg = (
                f"stop(drain=False) left {leftover} queued slot(s) "
                f"unserved (no drain was requested)"
            )
        err = DrainTimeout(msg)
        for slot in list(engine._pending.values_unlocked()):
            slot.resolve(None, error=err)
        for slot in list(engine._inflight.values()):
            if not slot.resolved:
                slot.resolve(None, error=err)
        # clear BOTH maps: a later submit must never coalesce onto an
        # abandoned (errored) slot, and the wedged flush's eventual
        # _resolve skips already-set slots (resolve-once rule)
        engine._pending.clear_unlocked()
        engine._inflight.clear()
        engine.stats.undrained += leftover
        engine.stats.request_errors += leftover


def default_buckets(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to ``max_batch`` (inclusive, appended if it is not
    itself a power of two): the bucket ladder that bounds padding waste at
    2x while keeping the compiled-program count at ``log2(max_batch)``."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    out: List[int] = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


@dataclass
class ServeConfig:
    """Engine knobs (see docs/api.md "Online serving").

    max_batch      : flush as soon as this many unique cache-missing seeds
                     are pending (also the largest bucket).
    max_delay_ms   : flush a non-empty queue once its OLDEST request has
                     waited this long — the latency/throughput trade knob.
    buckets        : fixed batch shapes; a flush pads up to the smallest
                     bucket >= its unique-seed count. Default: powers of
                     two up to ``max_batch``. One compiled program per
                     bucket actually used.
    max_in_flight  : bounded in-flight window — how many flushes may sit
                     between assemble and resolve at once. 1 reproduces the
                     round-8 serial engine bit-for-bit; 2 (default) lets
                     the host assemble/coalesce the next batch while the
                     device runs the current one. Overlap requires
                     concurrent flush callers (inline submit flushes,
                     `start()`'s pollers); `flush()` itself is synchronous.
    cache_entries  : embedding-cache capacity in rows (0 disables caching).
    clock          : injectable monotonic clock (seconds) — latency metrics,
                     stage spans, and the delay policy read ONLY this, so
                     tests drive flush timing deterministically with a fake
                     clock.
    flush_poll_ms  : background flusher poll period (`start()` mode only).
    record_dispatches : keep a log of (padded_batch, n_valid) per dispatch
                     for parity replay/debugging (off by default: it grows
                     with traffic). Log order == dispatch-index order ==
                     sampler key-stream order, even with in-flight > 1.
    dispatch_mode  : "auto" (default) serves through the FUSED one-program
                     path (`inference.make_serve_step` + AOT-pre-bound
                     `BucketPrograms`) whenever the sampler and feature
                     support it (TPU-mode sampler, dense in-jit-gatherable
                     feature — `inference.feature_gather_spec`), falling
                     back to the split sample/forward path otherwise
                     (tiered `Feature`, HOST/CPU samplers). "fused" makes
                     that fallback a construction-time error; "split"
                     forces the round-9 two-dispatch path (baselines,
                     features that must gather host-side). Fused and split
                     serve BIT-IDENTICAL logits on the same key stream.
    late_admission : admit seeds submitted AFTER a flush assembled into
                     that flush's pad lanes, up to its bucket, while it
                     waits for an in-flight window slot — continuous
                     seed-level batching: the pad slack was computed-and-
                     discarded waste, now it retires real requests.
                     Admission closes before the dispatch index and the
                     sampler key are drawn, so the dispatch log and key
                     stream stay deterministic and replayable
                     (``stats.late_admitted`` counts recovered lanes).
    journal_events : capacity of the request-lifecycle `trace.EventJournal`
                     (0 = disabled, the default). When on, the engine
                     stamps submit/coalesce/cache-hit/late-admit/assemble/
                     window-wait/dispatch/execute-done/resolve events on
                     its clock — `journal.request_breakdown()` then yields
                     per-stage p50/p99 and per-flush pad occupancy.
                     OBSERVE-ONLY: events never feed control flow, so
                     enabling it changes no served bit (pinned in
                     tests/test_obs.py); cost is one deque append per
                     event, cheap enough to leave on.
    workload       : a `trace.WorkloadConfig` enables the round-13
                     workload telemetry (None = off, zero cost): a
                     `trace.WorkloadMonitor` taps every submitted seed
                     (frequency sketches), every `EmbeddingCache` get
                     outcome, per-flush width/latency, and — when the
                     feature is a tiered `Feature`/`QuantizedFeature` —
                     per-tier gather attribution. Decay ticks ride flush
                     SEALS (the dispatch index, under the sequencing
                     lock), never wall time, so sketch state is
                     replay-bit-stable. Same OBSERVE-ONLY contract as the
                     journal: enabling it changes no served bit (pinned
                     in tests/test_skew.py).
                     ``engine.workload.skew_report()`` is the read side.
    tier_promote_batch : max row MOVES per adaptation pass (round 14;
                     bounds the apply batch's disk read + device
                     row-scatter, so a pass can never stall the fence
                     for long). Only read when the engine's feature has
                     an adaptive `tiers.TierStore` under it.
    tier_promote_min : minimum err-corrected sketch weight a row needs
                     to be CONSIDERED for promotion (the absolute floor
                     of the planner's hysteresis band — one-hit wonders
                     never buy a slot).
    tier_hysteresis : a candidate must beat its eviction victim's
                     estimate by this factor (keeps near-tied rows from
                     ping-ponging between adaptation passes).
    tier_adapt_every_s : background promote/demote consumer period in
                     seconds (`start()` spawns it when > 0 and the
                     feature is adaptive + workload telemetry is on;
                     0 = manual `adapt_tiers()` only — what the
                     deterministic tests drive). Placement application
                     is ALWAYS fenced like `update_params` regardless
                     of who calls it.
    tenant_weights : round-15 per-tenant admission: {tenant: weight}
                     flush-quota shares (None = no QoS, the pre-round-15
                     engine byte for byte). When the pending queue
                     exceeds ``max_batch``, `flush` drains tenants in
                     weighted proportion (largest-remainder apportioning,
                     FIFO within a tenant, unused quota refilled FIFO) —
                     a heavy tenant can saturate its share, never the
                     whole flush. Tenants absent from the dict weigh 1.0.
    max_queue_depth : queue-depth-bounded load shedding (0 = never shed).
                     A NEW request whose tenant is at/over its weighted
                     share of this bound while the queue is full is
                     refused with a `ShedError` carried in its
                     `ServeResult` (per-request, never engine-fatal).
                     The decision reads only queue state — deterministic
                     and logged (``ServeEngine.shed_log``). Cache hits
                     and coalesces never shed (they add no queue entry).
    drain_deadline_s : bound on ``stop(drain=True)``: if queued work
                     cannot be retired within this budget (a poller or
                     owner died mid-flush), remaining slots resolve with
                     `DrainTimeout` and are counted in
                     ``stats.undrained`` instead of hanging the caller.
    stream_invalidate_hops : round-17 streaming graphs — reverse-closure
                     depth of the delta cache invalidation (every cached
                     seed within this many hops of a changed row is
                     dropped at ``update_graph``). None (default) =
                     ``len(sampler.sizes) - 1``, the exact number of
                     EXPANSION hops: a changed row only alters a seed's
                     draws if the seed can expand it, and the final
                     hop's frontier is gathered but never expanded.
    stream_adapt_tiers : run one fenced `adapt_tiers` pass right after a
                     delta commit when the engine has an adaptive tier
                     store + workload telemetry (round-17 consumer (c):
                     a delta-hot subgraph pulls its rows off disk at the
                     commit, not at the next background timer tick).
                     False = timer/manual adaptation only.
    tier_prefetch  : round-18 flush-ahead prefetch (ROADMAP item 3a).
                     At assemble time the engine knows a flush's seed
                     set one window before dispatch — it walks the
                     EXPECTED k-hop closure (`tiers.expected_closure`
                     over the sampler's current graph) and issues
                     `AsyncReadPool` reads for the disk-resident rows,
                     so by the time the gather runs the bytes sit in
                     DRAM staging. STRICTLY OBSERVE-ONLY ON BITS:
                     staged rows are the same backing-file bytes the
                     direct read returns, no key is consumed, placement
                     never moves, and flush composition is untouched —
                     prefetch on/off serve bit-identical logits and
                     dispatch logs (pinned at mif 1/2 and hosts 1/2 in
                     tests/test_prefetch.py). Needs an adaptive
                     `tiers.TierStore` with a read pool under the
                     feature; silently inert otherwise. Counters:
                     ``stats.tier_prefetch_{issued,hit,wasted}``,
                     journal kinds ``prefetch_issue``/``prefetch_hit``.
    tier_prefetch_hops : closure depth of the prefetch walk. None
                     (default) = ``len(sampler.sizes)`` — the GATHERED
                     closure is one hop deeper than the expansion
                     closure (the round-11 closure-hops rule: the final
                     frontier is gathered, never expanded).
    tier_prefetch_max_rows : bound on closure rows walked AND rows
                     staged at once (BFS order, so truncation keeps the
                     nearest rows) — a super-hub seed can never turn
                     one flush's prefetch into a full-table scan.
    tier_prefetch_at : when the walk+issue runs. ``"submit"`` (default):
                     the submit that fills a bucket issues the pending
                     keys' closure reads BEFORE calling flush, so when
                     another flush is already in the dispatch path the
                     reads overlap that flush's ENTIRE service time —
                     genuinely one window before dispatch — and the
                     assemble-time pass only walks seeds the submit
                     batch missed (late admits, window flushes).
                     ``"assemble"``: walk only at assemble time (the
                     overlap is the window wait + sample stage). Both
                     spellings serve identical bits — the knob moves
                     WHEN reads are issued, never what is served.
    """

    max_batch: int = 64
    max_delay_ms: float = 2.0
    buckets: Optional[Sequence[int]] = None
    max_in_flight: int = 2
    cache_entries: int = 100_000
    clock: Callable[[], float] = time.monotonic
    flush_poll_ms: float = 0.2
    record_dispatches: bool = False
    dispatch_mode: str = "auto"
    late_admission: bool = True
    journal_events: int = 0
    workload: Optional[WorkloadConfig] = None
    tier_promote_batch: int = 64
    tier_promote_min: float = 2.0
    tier_hysteresis: float = 1.25
    tier_adapt_every_s: float = 0.0
    tenant_weights: Optional[Dict[str, float]] = None
    max_queue_depth: int = 0
    drain_deadline_s: float = 30.0
    stream_invalidate_hops: Optional[int] = None
    stream_adapt_tiers: bool = True
    tier_prefetch: bool = False
    tier_prefetch_hops: Optional[int] = None
    tier_prefetch_max_rows: int = 4096
    tier_prefetch_at: str = "submit"
    # round-20 vectorized host path: stripe count of the pending queue
    # (`_PendingStripes`) — concurrent submit threads for keys in
    # different stripes never share a lock. 1 reproduces the single-dict
    # engine's locking exactly; batch composition and dispatch logs are
    # stripe-count-invariant either way (arrival-order merge).
    submit_stripes: int = 8
    # round-21 graph lifecycle (`quiver_tpu.lifecycle`):
    # >0 = sliding-window TTL on a temporal stream — every update_graph
    # commit expires edges older than (max committed ts - window) under
    # the same fence, as masked ts->+inf lane writes (see
    # lifecycle.RetentionPolicy; window arithmetic on the f32 grid)
    stream_retention_window: float = 0.0
    # >0 = background compaction: a timer thread plans off-fence and
    # applies under the fence every this-many seconds, when at least
    # stream_compact_min_reclaim tile rows are reclaimable. Strictly
    # observe-only on bits (pinned).
    stream_compact_every_s: float = 0.0
    stream_compact_min_reclaim: int = 8
    stream_compact_max_moves: int = 0
    # >0 = auto re-provisioning: a commit that would raise
    # StreamCapacityError first grows the tile bank by this many rows
    # (one sealed-program rebuild via BucketPrograms.reprovision) and
    # retries once. 0 = capacity stays a planned hard error (r17).
    stream_provision_tiles: int = 0
    # round-23 wall-clock TTL daemon (the round-21 leftover): >0 = a
    # timer thread runs `expire_edges` every this-many seconds BETWEEN
    # commits, so a quiet stream's sliding window keeps expiring without
    # waiting for the next delta. Each pass is exactly a manual
    # `expire_edges` call — same update_graph fence, same version bumps,
    # same closure-exact cache invalidation. Off by default; start()
    # leaves it off unless retention is configured on a temporal
    # stream-bound sampler.
    stream_retention_every_s: float = 0.0
    # injectable wall-clock -> event-time map for the daemon: each pass
    # expires at ``cutoff_for(stream_retention_clock())``. None (the
    # default) keeps the deterministic commit-driven retention clock
    # where the last commit left it — a daemon pass then only re-applies
    # the last commit's cutoff (a catch-up, usually a no-op). Tests
    # inject a deterministic sequence here; production maps wall time to
    # stream event time.
    stream_retention_clock: Optional[Callable[[], float]] = None
    # round-24 zero-stall commits: False (default) = `update_graph` and
    # the lifecycle commits build the post-commit device arrays OFF the
    # fence and flip them under _seq only — no in-flight drain; flushes
    # are epoch-pinned (each seals against the graph arrays of its
    # dispatch index, logs its graph_version) and the fence's three
    # consumers go version-aware (cache graph-version floors, post-flip
    # replica retire, post-flip adapt_tiers). True = the round-17..23
    # drain-ordered fence, bit-identical, kept as the parity twin.
    # Re-provisioning (a shape change) always drains in either mode.
    fenced_commits: bool = False

    def resolved_buckets(self) -> Tuple[int, ...]:
        if self.buckets is None:
            return default_buckets(self.max_batch)
        bs = tuple(sorted(int(b) for b in self.buckets))
        if not bs or bs[0] < 1:
            raise ValueError("buckets must be positive")
        if bs[-1] < self.max_batch:
            raise ValueError(
                f"largest bucket {bs[-1]} < max_batch {self.max_batch}: "
                "a full flush would not fit any bucket"
            )
        return bs


# guards lazy per-slot Event creation (contended only when two waiters
# race to be a slot's FIRST blocking waiter — never on the submit path)
_SLOT_EVENT_LOCK = threading.Lock()


class _Slot:
    """One unique (node_id, params_version) computation; every coalesced
    request for it holds a reference and blocks via :meth:`wait`. ``rid``
    is the slot's journal request id (engine-monotonic; -1 when the
    engine isn't journaling) — the key the lifecycle events thread
    through. ``seq`` is the global arrival stamp `_PendingStripes` orders
    the striped queue by.

    The completion `threading.Event` is LAZY (round 20): submit-path
    throughput is bounded by per-slot construction cost, and most slots
    under `predict`/`submit_many` are polled (``done()``) then read after
    their flush resolves — they never block, so they never pay the Event
    (three allocations + a lock). ``resolved`` is the plain-bool fast
    path (GIL-ordered against `resolve`); the first waiter that actually
    needs to BLOCK installs the event under `_SLOT_EVENT_LOCK` and
    re-checks ``resolved`` after installing, which closes the
    install/resolve race in either interleaving."""

    __slots__ = ("node_id", "version", "_event", "resolved", "value",
                 "error", "enqueue_t", "waiters", "rid", "tenant", "seq")

    def __init__(self, node_id: int, version: int, enqueue_t: float,
                 rid: int = -1, tenant: str = DEFAULT_TENANT):
        self.node_id = node_id
        self.version = version
        self._event: Optional[threading.Event] = None
        self.resolved = False
        self.value: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.enqueue_t = enqueue_t
        # (submit timestamp, tenant) per attached request: latency lands
        # in the global histogram AND the submitting tenant's
        self.waiters: List[Tuple[float, str]] = []
        self.rid = rid
        self.tenant = tenant  # admitting tenant (quota accounting)
        self.seq = -1  # arrival order within the striped pending queue

    def resolve(self, value: Optional[np.ndarray], error=None) -> None:
        self.value = value
        self.error = error
        self.resolved = True
        ev = self._event
        if ev is not None:
            ev.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        if self.resolved:
            return True
        ev = self._event
        if ev is None:
            with _SLOT_EVENT_LOCK:
                ev = self._event
                if ev is None:
                    ev = self._event = threading.Event()
            if self.resolved:
                # resolve() may have read _event before the install; its
                # write to ``resolved`` precedes this read under the GIL
                return True
        return ev.wait(timeout)


class ServeResult:
    """Handle returned by :meth:`ServeEngine.submit`. May carry a value
    (cache hit), a slot (queued computation), or a per-request error
    (e.g. `ShedError` at admission, an owner failure isolated to this
    request's sub-batch)."""

    __slots__ = ("_slot", "_value", "_error")

    def __init__(self, slot: Optional[_Slot] = None,
                 value: Optional[np.ndarray] = None,
                 error: Optional[BaseException] = None):
        self._slot = slot
        self._value = value
        self._error = error

    def done(self) -> bool:
        return self._slot is None or self._slot.resolved

    def error(self) -> Optional[BaseException]:
        """The request's exception without raising (None if none yet;
        a queued request's error is known only after it resolves)."""
        if self._error is not None:
            return self._error
        if self._slot is not None and self._slot.resolved:
            return self._slot.error
        return None

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Logits row for the requested node (blocks until its flush
        lands; raises the request's exception if it was shed at
        admission or its dispatch failed — per-request: co-flushed
        requests of a healthy sub-batch resolve normally).

        The row is READ-ONLY — it is shared with the embedding cache and
        every coalesced co-waiter. Copy before mutating."""
        if self._error is not None:
            raise self._error
        if self._slot is None:
            return self._value
        if not self._slot.wait(timeout):
            raise TimeoutError("serve request not resolved in time")
        if self._slot.error is not None:
            raise self._slot.error
        return self._slot.value


def _slot_row(slot: "_Slot", timeout: Optional[float]) -> np.ndarray:
    """`ServeResult.result` against a bare slot (the lazy batch path
    skips the handle object entirely) — same wait/raise/return
    sequence, same timeout message."""
    if not slot.wait(timeout):
        raise TimeoutError("serve request not resolved in time")
    if slot.error is not None:
        raise slot.error
    return slot.value


class ResultBatch(collections.abc.Sequence):
    """The handle sequence ``submit_many`` returns (round 22): admission
    keeps the RAW per-request outcome — a `_Slot`, or a ready
    `ServeResult` (cache hit / shed / per-request error) — and builds a
    `ServeResult` only when a caller actually indexes or iterates, so
    per-request handle construction moves off the submit path onto the
    consumer that wants handles. Fully list-compatible for existing
    callers (``len``/index/slice/iterate/truthiness); the batch
    consumers (`ServeEngine.results_many`, ``predict``) read the raw
    entries array-at-a-time and never materialize handles at all.

    The whole-batch vectorized admission path stores one slot per
    UNIQUE key plus the batch's coalesce map (``inv[i]`` = the unique
    index serving request ``i``), so delivery is a per-unique gather
    expanded by ONE fancy-index instead of N per-request reads."""

    __slots__ = ("_items", "_uniq", "_inv")

    def __init__(self, items: Optional[List] = None,
                 uniq: Optional[List] = None,
                 inv: Optional[np.ndarray] = None):
        self._items = items
        self._uniq = uniq
        self._inv = inv

    def __len__(self) -> int:
        if self._items is not None:
            return len(self._items)
        return len(self._inv)

    def _raw(self, i: int):
        if self._items is not None:
            return self._items[i]
        return self._uniq[self._inv[i]]

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        it = self._raw(i)
        return it if isinstance(it, ServeResult) else ServeResult(slot=it)

    def __iter__(self):
        if self._items is not None:
            raws = self._items
        else:
            uniq = self._uniq
            raws = [uniq[j] for j in self._inv.tolist()]
        for it in raws:
            yield it if isinstance(it, ServeResult) else ServeResult(slot=it)

    def __eq__(self, other):
        # list-compatibility: handle wrappers materialize per access, so
        # equality is positional identity of the RAW outcomes (two views
        # of the same admission compare equal; `submit_many([]) == []`
        # stays true)
        if isinstance(other, (list, tuple, ResultBatch)):
            if len(self) != len(other):
                return False
            return all(
                a is b or (isinstance(a, ServeResult)
                           and isinstance(b, ServeResult)
                           and a._slot is not None
                           and a._slot is b._slot)
                for a, b in zip(self, other)
            )
        return NotImplemented

    __hash__ = None  # mutable-sequence convention, like list

    def done(self) -> bool:
        """True when every request's handle would report ``done()`` —
        checked per UNIQUE slot on the vectorized path."""
        raws = self._items if self._items is not None else self._uniq
        for it in raws:
            if isinstance(it, ServeResult):
                if not it.done():
                    return False
            elif not it.resolved:
                return False
        return True

    def gather(self, timeout: Optional[float] = None) -> np.ndarray:
        """All rows as one ``[N, C]`` array in request order — the batch
        twin of ``np.stack([h.result(timeout) for h in handles])``,
        including its error order: the first REQUEST whose handle would
        raise is the one raised here."""
        n = len(self)
        if n == 0:
            return np.zeros((0, 0), np.float32)
        if self._items is None:
            uniq = self._uniq
            errs = None
            for j, slot in enumerate(uniq):
                if not slot.wait(timeout):
                    raise TimeoutError("serve request not resolved in time")
                if slot.error is not None:
                    if errs is None:
                        errs = {}
                    errs[j] = slot.error
            if errs is not None:
                for j in self._inv.tolist():  # request order
                    if j in errs:
                        raise errs[j]
            rows = np.stack([slot.value for slot in uniq])
            return rows[self._inv]
        return np.stack([
            it.result(timeout) if isinstance(it, ServeResult)
            else _slot_row(it, timeout)
            for it in self._items
        ])


@dataclass
class ServeStats:
    """Engine counters. ``requests`` counts every submit; ``coalesced``
    the subset answered by attaching to an existing pending/in-flight slot;
    the cache's own hit/miss/eviction counters live in ``cache``.
    ``dispatches`` is the number of device batches actually launched —
    the acceptance metric "dispatch count < N" reads this.
    ``inflight_peak`` is the largest number of flushes observed between
    assemble and resolve at once (> 1 is direct evidence the window was
    used; bounded by ``max_in_flight + 1`` — a drained flush waiting for
    its window permit, i.e. the one admitting late seeds, is between
    assemble and resolve too). ``spans`` records per-stage
    (assemble/dispatch/resolve) spans on the engine's clock —
    ``spans.overlap_summary()`` is the measured-overlap evidence.

    ``dispatch_calls`` counts dispatch-STAGE entries (including ones that
    errored; ``dispatches`` counts only resolved successes) and
    ``execute_calls`` the device program legs those stages ran — 1 per
    flush on the fused one-program path, 2 on the split path (the round-9
    sample + forward ledger; the split sample leg is itself op-by-op
    eager dispatch, so 2 is that ledger's floor, not an op count). The 2→1
    dispatch claim is OBSERVABLE as ``execute_calls == dispatches`` on a
    fused engine, not inferred. Both are taken when the flush resolves,
    from what its dispatch stage recorded, so the stage itself takes no
    lock (a flush still in its dispatch is not counted yet; a routed
    engine's own dispatch counts neither). ``late_admitted`` counts seeds
    admitted into an assembled flush's pad lanes (recovered bucket
    slack)."""

    requests: int = 0
    coalesced: int = 0
    dispatches: int = 0
    dispatched_seeds: int = 0   # unique seeds sent to the device
    padded_seeds: int = 0       # bucket slack rows computed and discarded
    dispatch_calls: int = 0
    execute_calls: int = 0
    late_admitted: int = 0
    tier_promoted: int = 0      # rows moved UP a tier (round 14)
    tier_demoted: int = 0       # rows moved DOWN a tier
    placement_batches: int = 0  # fenced placement applies
    # round-18 flush-ahead prefetch ledger: issued counts disk rows
    # submitted to the read pool ahead of their gather, hit the rows a
    # gather consumed from staging, wasted the rows staged but dropped
    # (fence cancels, failed reads, closure rows the draw never touched)
    tier_prefetch_issued: int = 0
    tier_prefetch_hit: int = 0
    tier_prefetch_wasted: int = 0
    shed: int = 0               # requests refused at admission (round 15)
    request_errors: int = 0     # slots resolved with a per-request error
    undrained: int = 0          # slots abandoned by a bounded stop() drain
    # round-17 streaming-graph counters: graph_deltas counts fenced
    # update_graph commits, delta_edges the edges they appended,
    # delta_tile_writes/spills the pad-lane vs relocation split (the
    # layout-health signal: spills rising means the reserve is being
    # eaten), delta_cache_invalidated the closure-touched cache drops
    graph_deltas: int = 0
    delta_edges: int = 0
    delta_tile_writes: int = 0
    delta_tile_spills: int = 0
    delta_cache_invalidated: int = 0
    # round-21 graph lifecycle: deletions/expiries are masked lane work,
    # reclaims/compactions are the background row economy — together they
    # are the "does the stream actually live forever" signal (expired +
    # reclaimed keeping pace with appended = flat reserve occupancy)
    edges_deleted: int = 0
    edges_expired: int = 0
    tiles_reclaimed: int = 0
    compactions: int = 0
    inflight_peak: int = 0
    dispatch_buckets: Dict[int, int] = field(default_factory=dict)
    cache: HitRateCounter = field(default_factory=HitRateCounter)
    latency: LatencyHistogram = field(default_factory=LatencyHistogram)
    # per-tenant end-to-end latency (round 15): one histogram per tenant
    # that ever submitted — `tenant_latency["t"].percentile(99)` is the
    # per-tenant p99 the admission work is judged by
    tenant_latency: Dict[str, LatencyHistogram] = field(default_factory=dict)
    spans: SpanRecorder = field(default_factory=SpanRecorder)
    # round-24 per-commit serving stall, in MICROSECONDS (the histogram
    # is unit-agnostic; µs keeps flip-only stalls resolvable): fenced
    # mode records the whole drain+fenced-work hold, zero-stall mode the
    # _seq flip hold — the drain-vs-flip evidence `delta_table` prices
    commit_stall: LatencyHistogram = field(
        default_factory=lambda: LatencyHistogram(min_ms=1e-2, max_ms=1e9)
    )

    def tenant_hist(self, tenant: str) -> LatencyHistogram:
        """The tenant's latency histogram, created on first use. Callers
        mutate it under the owning engine's lock; readers snapshot."""
        return tenant_latency_hist(self.tenant_latency, tenant)

    def merge(self, other: "ServeStats") -> "ServeStats":
        """Fold another engine's stats into this one — the cross-shard
        aggregation hook the distributed serve engine uses (one merged view
        over H shard engines: counters add, ``inflight_peak`` is the max
        across shards, histograms/counters/spans merge via their own
        `merge` methods in `quiver_tpu.trace`). Merge into a FRESH
        `ServeStats`, not a live engine's — the source engines keep
        counting into their own objects. Safe against a LIVE source: the
        int fields read atomically under the GIL, the bucket dict is
        snapshotted with the atomic C-level ``.copy()`` (a bare
        ``.items()`` loop would raise RuntimeError if a flush lands a new
        bucket mid-iteration), and the histogram/counter/span merges take
        their own locks — the result is a consistent-enough snapshot, not
        a fence. Returns self for chaining."""
        self.requests += other.requests
        self.coalesced += other.coalesced
        self.dispatches += other.dispatches
        self.dispatched_seeds += other.dispatched_seeds
        self.padded_seeds += other.padded_seeds
        self.dispatch_calls += other.dispatch_calls
        self.execute_calls += other.execute_calls
        self.late_admitted += other.late_admitted
        self.tier_promoted += other.tier_promoted
        self.tier_demoted += other.tier_demoted
        self.placement_batches += other.placement_batches
        self.tier_prefetch_issued += other.tier_prefetch_issued
        self.tier_prefetch_hit += other.tier_prefetch_hit
        self.tier_prefetch_wasted += other.tier_prefetch_wasted
        self.shed += other.shed
        self.request_errors += other.request_errors
        self.undrained += other.undrained
        self.graph_deltas += other.graph_deltas
        self.delta_edges += other.delta_edges
        self.delta_tile_writes += other.delta_tile_writes
        self.delta_tile_spills += other.delta_tile_spills
        self.delta_cache_invalidated += other.delta_cache_invalidated
        self.edges_deleted += other.edges_deleted
        self.edges_expired += other.edges_expired
        self.tiles_reclaimed += other.tiles_reclaimed
        self.compactions += other.compactions
        self.inflight_peak = max(self.inflight_peak, other.inflight_peak)
        for b, n in other.dispatch_buckets.copy().items():
            self.dispatch_buckets[b] = self.dispatch_buckets.get(b, 0) + n
        for t, h in other.tenant_latency.copy().items():
            self.tenant_hist(t).merge(h)
        self.cache.merge(other.cache)
        self.latency.merge(other.latency)
        self.spans.merge(other.spans)
        self.commit_stall.merge(other.commit_stall)
        return self

    def snapshot(self) -> Dict[str, object]:
        return {
            "requests": self.requests,
            "coalesced": self.coalesced,
            "dispatches": self.dispatches,
            "dispatched_seeds": self.dispatched_seeds,
            "padded_seeds": self.padded_seeds,
            "dispatch_calls": self.dispatch_calls,
            "execute_calls": self.execute_calls,
            "late_admitted": self.late_admitted,
            "tier_promoted": self.tier_promoted,
            "tier_demoted": self.tier_demoted,
            "placement_batches": self.placement_batches,
            "tier_prefetch_issued": self.tier_prefetch_issued,
            "tier_prefetch_hit": self.tier_prefetch_hit,
            "tier_prefetch_wasted": self.tier_prefetch_wasted,
            "shed": self.shed,
            "request_errors": self.request_errors,
            "undrained": self.undrained,
            "graph_deltas": self.graph_deltas,
            "delta_edges": self.delta_edges,
            "delta_tile_writes": self.delta_tile_writes,
            "delta_tile_spills": self.delta_tile_spills,
            "delta_cache_invalidated": self.delta_cache_invalidated,
            "edges_deleted": self.edges_deleted,
            "edges_expired": self.edges_expired,
            "tiles_reclaimed": self.tiles_reclaimed,
            "compactions": self.compactions,
            "inflight_peak": self.inflight_peak,
            "dispatch_buckets": dict(self.dispatch_buckets),
            "cache": self.cache.snapshot(),
            "latency": self.latency.snapshot(),
            "tenant_latency": {
                t: self.tenant_latency[t].snapshot()
                for t in sorted(self.tenant_latency)
            },
            "overlap": self.spans.overlap_summary(),
            "commit_stall_us": self.commit_stall.snapshot(),
        }


class _Flush:
    """Per-flush state between assemble and resolve: the drained slots and
    the params snapshot the dispatch will run under. Dispatch ORDER is not
    carried here — it is the log-append/key-draw order the sequencing lock
    imposes (`ServeEngine._dispatch_index` counts it). ``bucket`` is fixed
    at drain time; late admission may append to ``keys``/``slots`` up to it
    until `_seal_assembled` closes the flush. The fused path carries the
    drawn sampler ``call`` index + the ``padded`` seed batch into its
    one-program dispatch; the split path carries the pre-run sample ``ds``.

    Round 20 (array-native internals): once sealed, the flush also carries
    SLOT ARRAYS — ``ids`` (int64 seed ids), ``rids`` (int64 journal
    request ids, -1 when the journal is off) and ``tenant_ix`` (int32
    indices into the engine's interned tenant table, built on first
    sight) — aligned with ``slots`` so downstream consumers (result
    delivery, replay tooling, the frontend bench) address the batch by
    slot INDEX instead of walking per-request objects. ``slots`` itself
    stays: waiters/version/resolution state is per-request by nature."""

    __slots__ = ("keys", "slots", "params", "seeds", "bucket", "ds", "call",
                 "padded", "extra", "error", "fid", "ids", "rids",
                 "tenant_ix", "graph_version", "binding", "t_begin",
                 "t_dispatch", "t_done", "n_exec")

    def __init__(self, keys, slots, params):
        self.keys = keys
        self.slots = slots
        self.params = params
        self.seeds = None
        self.bucket = 0
        self.ds = None
        self.call = None
        self.padded = None
        # round-24 epoch pin, stamped at seal (under _seq): the graph
        # version this flush dispatches against, plus the fused program's
        # persistent-argument snapshot (table, map, graph) of that epoch —
        # a zero-stall commit rebinding mid-flight cannot retarget it
        self.graph_version = 0
        self.binding = None
        self.ids = None        # int64 [n] seed ids (sealed)
        self.rids = None       # int64 [n] journal rids (sealed)
        self.tenant_ix = None  # int32 [n] interned tenant indices (sealed)
        # extra padded per-seed dispatch arguments (round 19: the temporal
        # workload's query-time vector); None on the plain engine
        self.extra = None
        self.error: Optional[BaseException] = None
        # journal flush id == the dispatch index `_seal_assembled` will
        # draw (assemble and seal happen under one _seq hold, so nothing
        # can interleave an increment between them)
        self.fid = -1
        # `ServeEngine.flush`'s stamps, kept for the per-request stage
        # split `_observe_stages` adds when tracing: the call of `flush()`
        # (read only while tracing) and the dispatch stage's two
        self.t_begin = self.t_dispatch = self.t_done = 0.0
        # what `ServeEngine._dispatch` ran, counted into ServeStats at
        # resolve: None = the stage was not entered, else the device
        # program legs that returned (0 when the stage raised first)
        self.n_exec: Optional[int] = None


def _admit_chunk_fast(eng, keys, nodes, tenants, i, now, events,
                      results) -> Tuple[int, bool]:
    """Vectorized chunk admission (round 20 tentpole) — the fast body
    behind `ServeEngine._submit_keyed_many` and its router twin. The
    caller holds ALL stripe locks and has checked the per-request slow
    triggers are off (no workload tap, no queue-depth shedding); this
    body then admits requests ``[i, n)`` with ONE engine-lock hold, ONE
    batched cache probe per block (`EmbeddingCache.get_many`), C-level
    dict ops for the coalesce probe/insert, and bulk stats/rid updates.
    The per-request DECISION sequence (cache hit -> coalesce -> fresh
    insert, in request order) is identical to `_admit_one_locked`; only
    the mechanics are amortized, so dispatch logs, journal streams, rid
    values and counters stay bit-identical to the scalar path.

    Cache probes run ahead of admission in blocks no larger than the
    guaranteed-consumable room ``max_batch - len(pending)``: a fill
    needs that many fresh inserts, so it can only land on a block's
    LAST entry — probe side effects (LRU touches, hit/miss counters)
    never outrun the requests actually admitted before an inline flush.

    Returns ``(i, need_flush)``. Stops early (``need_flush`` False,
    ``i < n``) when a late-admission window is open — the caller's
    per-request loop handles pad-slack admission; a window cannot OPEN
    mid-chunk because publishing one needs the stripe locks the caller
    holds, and it cannot CLOSE because sealing takes the engine lock
    held here."""
    n = len(keys)
    pend = eng._pending
    maps = pend.maps
    tmaps = pend.tenants
    ns = pend.n
    skey = pend.stripe_key
    arrival = pend._arrival
    infl_get = eng._inflight.get
    cache_many = eng.cache.get_many
    stats = eng.stats
    clock = eng._clock
    max_batch = eng.config.max_batch
    plen = len(pend)
    requests = 0
    coalesced = 0
    ev_append = events.append
    with eng._lock:
        if eng._open is not None:
            return i, False
        ver = eng.params_version
        jr_on = eng.journal.enabled
        rid = eng._next_rid
        while i < n:
            room = max_batch - plen
            if room < 1:
                room = 1
            j = i + room
            if j > n:
                j = n
            for v in cache_many(keys[i:j], ver):
                k = keys[i]
                node = nodes[i]
                ten = tenants[i]
                requests += 1
                if v is not None:  # cache hit: served on the spot
                    ms = (clock() - now) * 1e3
                    stats.latency.record_ms(ms)
                    stats.tenant_hist(ten).record_ms(ms)
                    if jr_on:
                        ev_append(("cache_hit", -1, -1, node, 0))
                    results[i] = ServeResult(value=v)
                    i += 1
                    continue
                s = skey(k) % ns
                slot = maps[s].get(k) or infl_get(k)
                if slot is not None and slot.version == ver:
                    coalesced += 1
                    if jr_on:
                        ev_append(("coalesce", slot.rid, -1, node, 0))
                else:
                    r = -1
                    if jr_on:
                        r = rid
                        rid += 1
                    slot = _Slot(k, ver, now, rid=r, tenant=ten)
                    slot.seq = next(arrival)
                    maps[s][k] = slot
                    t = tmaps[s]
                    t[ten] = t.get(ten, 0) + 1
                    if jr_on:
                        ev_append(("submit", r, -1, node, 0))
                    plen += 1
                slot.waiters.append((now, ten))
                results[i] = slot  # handle built lazily by ResultBatch
                i += 1
                if plen >= max_batch:
                    eng._next_rid = rid
                    stats.requests += requests
                    stats.coalesced += coalesced
                    return i, True
        eng._next_rid = rid
    stats.requests += requests
    stats.coalesced += coalesced
    return i, False


def _batch_uniq(arr: np.ndarray):
    """First-occurrence unique decomposition of a submit batch:
    ``(uniq_ix, inv, counts)`` where ``uniq_ix`` indexes the batch's
    unique keys in ARRIVAL (first-occurrence) order, ``inv[i]`` is the
    unique index serving request ``i``, and ``counts`` the per-unique
    request multiplicity. Works on int id arrays and on structured
    (node, t) arrays alike. Returns None when the array holds NaNs —
    ``np.unique`` collapses equal NaNs while dict keys built from
    distinct float objects do not, so those batches take the
    per-request path."""
    if arr.dtype.kind == "f" and np.isnan(arr).any():
        return None
    if arr.dtype.names is not None:
        for name in arr.dtype.names:
            f = arr[name]
            if f.dtype.kind == "f" and np.isnan(f).any():
                return None
    _, first, inv, counts = np.unique(
        arr, return_index=True, return_inverse=True, return_counts=True
    )
    order = np.argsort(first)  # sorted-unique -> arrival order
    rank = np.empty(order.shape[0], np.int64)
    rank[order] = np.arange(order.shape[0])
    return first[order], rank[inv], counts[order]


def _admit_batch_vector(eng, keys, tenant: str, now: float, uniq_ix,
                        inv, counts) -> Optional[ResultBatch]:
    """WHOLE-batch vectorized admission (round 22) — the per-UNIQUE-key
    admission body behind `submit_many` when nothing per-request can
    happen: the journal is off (no rid draws, no per-request events),
    the cache is empty-by-config (no hit can short-circuit), one tenant
    covers the batch, and the whole batch fits the pending queue without
    an inline fill-flush. Under those gates the scalar decision sequence
    collapses to "coalesce or insert, per unique key": duplicates inside
    the batch attach to the first occurrence's slot exactly as the
    per-request loop would attach them, so slots, arrival stamps,
    waiter lists and counters are bit-identical to N scalar submits —
    while the per-REQUEST work drops to one np.unique.

    Caller holds ALL stripe locks and has checked the engine-shape
    gates; this checks the state gates (open window, room) under
    ``_lock`` and returns None to fall back. Shared by the single-host
    engine and the router (stripe mapping via ``pend.stripe_of`` keeps
    it owner-partition-correct there)."""
    pend = eng._pending
    maps = pend.maps
    tmaps = pend.tenants
    stripe_of = pend.stripe_of
    infl_get = eng._inflight.get
    n_uniq = uniq_ix.shape[0]
    with eng._lock:
        if eng._open is not None:
            return None
        if len(pend) + n_uniq >= eng.config.max_batch:
            # an inline fill-flush could land mid-batch; the per-request
            # path owns that interleaving
            return None
        ver = eng.params_version
        arrival = pend._arrival
        w = (now, tenant)
        uniq_slots = [None] * n_uniq
        new = 0
        ux = uniq_ix.tolist()
        cts = counts.tolist()
        for j in range(n_uniq):
            k = keys[ux[j]]
            s = stripe_of(k)
            slot = maps[s].get(k) or infl_get(k)
            if slot is None or slot.version != ver:
                slot = _Slot(k, ver, now, rid=-1, tenant=tenant)
                slot.seq = next(arrival)
                maps[s][k] = slot
                t = tmaps[s]
                t[tenant] = t.get(tenant, 0) + 1
                new += 1
            c = cts[j]
            if c == 1:
                slot.waiters.append(w)
            else:
                slot.waiters.extend([w] * c)
            uniq_slots[j] = slot
    n = len(keys)
    stats = eng.stats
    stats.requests += n
    stats.coalesced += n - new
    # the scalar path probes the (empty, untapped) cache per request and
    # counts a miss each time — same evidence, one bulk move
    eng.cache.counters.miss(n)
    return ResultBatch(uniq=uniq_slots, inv=inv)


_REPEAT_NONE = itertools.repeat(None)
_WAITER_T0 = operator.itemgetter(0)
_WAITER_TENANT = operator.itemgetter(1)


def _pop_inflight_many(eng, keys) -> None:
    """C-level batched ``_inflight.pop(k, None)`` over a flush's keys
    (the deque(maxlen=0) idiom consumes the map object without a
    Python-level loop)."""
    if eng._inflight:
        collections.deque(
            map(eng._inflight.pop, keys, _REPEAT_NONE), maxlen=0
        )


def _record_waiter_latency(eng, slots, now: float) -> None:
    """The per-waiter latency recording of `_resolve`, vectorized: one
    flatten of the flush's waiter lists, one ``(now - t0) * 1e3`` vector
    (element-for-element the scalar expression), one bulk histogram
    fold for the global histogram and one per tenant. Bucket counts are
    bit-identical to the scalar loop (`LatencyHistogram.record_ms_many`);
    only ``sum_ms`` accumulates in vector order."""
    ws = list(itertools.chain.from_iterable([s.waiters for s in slots]))
    if not ws:
        return
    t0s = np.fromiter(map(_WAITER_T0, ws), np.float64, len(ws))
    ms = (now - t0s) * 1e3
    eng.stats.latency.record_ms_many(ms)
    tenants = set(map(_WAITER_TENANT, ws))
    if len(tenants) == 1:
        eng.stats.tenant_hist(tenants.pop()).record_ms_many(ms)
    else:
        by: Dict[str, List[int]] = {}
        for ix, wt in enumerate(ws):
            by.setdefault(wt[1], []).append(ix)
        for ten, ixs in by.items():
            eng.stats.tenant_hist(ten).record_ms_many(ms[ixs])


def _observe_stages(fl, t_res: float) -> None:
    """The three stages of every request that rode the flush, from stamps
    the engine holds already, added to the trace registry (`trace.observe`;
    the caller has asked `trace_enabled`): ``quiver.serve.queue`` (the
    waiter's submit stamp -> its flush's dispatch stamp),
    ``quiver.serve.device`` (dispatch -> execute done) and
    ``quiver.serve.resolved`` (execute done -> the stamp `_resolve` takes
    under the lock, the end of the waiter's recorded latency). The stages
    of `EventJournal.request_breakdown`, with one difference: a waiter that
    coalesced onto the flush after a stage began is charged that stage
    from its own arrival, so a request's three stages add up to the
    latency ``stats.latency`` recorded for it. ``quiver.serve.pending`` is
    the first part of the queue stage: submit stamp -> the call of the
    `flush()` that took the request (0 for a waiter that came later); what
    is left of the queue stage is spent inside that flush, under the
    per-flush spans ``seq_wait``, ``assemble``, ``window_wait``, ``seal``."""
    t0s = np.fromiter((w[0] for s in fl.slots for w in s.waiters), np.float64)
    t_disp, t_done = fl.t_dispatch, fl.t_done
    observe("quiver.serve.pending", np.maximum(fl.t_begin - t0s, 0.0))
    observe("quiver.serve.queue", np.maximum(t_disp - t0s, 0.0))
    observe("quiver.serve.device",
            np.maximum(t_done - np.maximum(t0s, t_disp), 0.0))
    observe("quiver.serve.resolved",
            np.maximum(t_res - np.maximum(t0s, t_done), 0.0))


def _resolve_block(eng, fl, logits: np.ndarray, now: float) -> None:
    """Stage-3 fast path (round 22 tentpole), caller holds ``_lock`` and
    has checked the guards: no flush/slot errors, no slot already
    resolved (abandonment by a bounded stop() resolves a flush's slots
    all-or-nothing, so ``slots[0]`` answers for the flush), versions
    uniform at the live ``params_version`` (the update_params fence).
    The scalar loop then collapses to: one batched inflight pop, ONE
    contiguous logits slice handed out as per-slot row views (the same
    row object goes to the slot AND the cache, as in the scalar path),
    one `EmbeddingCache.put_many`, one per-slot publication pass with
    the lazy-Event wake, and one vectorized waiter-latency fold. Shared
    by `ServeEngine._resolve` and `DistServeEngine._resolve`."""
    slots = fl.slots
    n = len(slots)
    _pop_inflight_many(eng, fl.keys)
    rows = list(logits[:n])  # n row views, made at C speed
    if eng.cache.capacity != 0:
        eng.cache.put_many(fl.keys, eng.params_version, rows,
                           gv=fl.graph_version)
    for slot, row in zip(slots, rows):
        slot.value = row
        slot.resolved = True
        ev = slot._event
        if ev is not None:
            ev.set()
    _record_waiter_latency(eng, slots, now)


class _CommitCounterSource:
    """`counter_samples()` adapter over an engine's per-commit sample
    ring — `trace.chrome_trace_events` renders any source bearing
    ``counter_samples()`` as ``ph:"C"`` counter tracks, so the
    graph-version staircase and the per-commit stall ride the trace's
    counter lane (observe-only; round 24)."""

    def __init__(self, samples):
        self._samples = samples

    def counter_samples(self):
        return list(self._samples)


class ServeEngine:
    """See the module docstring for the design; docs/api.md for the
    contract. Typical use::

        engine = ServeEngine(model, params, sampler, feature,
                             ServeConfig(max_batch=32, max_delay_ms=2.0))
        engine.warmup()                   # pre-trace every bucket shape
        with engine:                      # starts the background flushers
            logits = engine.predict([node_id])[0]

    or fully synchronous (no thread)::

        h = engine.submit(node_id)
        engine.flush()
        logits = h.result()
    """

    # subclasses that understand the temporal dispatch shape (the extra
    # query-time argument, composite (node, t) keys) set this — see
    # quiver_tpu.workloads.serving.TemporalServeEngine
    _temporal_capable = False

    def __init__(self, model, params, sampler, feature,
                 config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig()
        if (getattr(sampler, "temporal", None) is not None
                and not self._temporal_capable):
            raise TypeError(
                "temporal-bound samplers need the temporal engine — use "
                "quiver_tpu.workloads.TemporalServeEngine (this engine "
                "would dispatch without a query time)"
            )
        if self.config.max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        if self.config.dispatch_mode not in ("auto", "fused", "split"):
            raise ValueError(
                f"unknown dispatch_mode {self.config.dispatch_mode!r}"
            )
        if self.config.tier_prefetch_at not in ("submit", "assemble"):
            raise ValueError(
                f"unknown tier_prefetch_at {self.config.tier_prefetch_at!r}"
            )
        self._buckets = self.config.resolved_buckets()
        self._apply = _cached_apply(model)
        self._params = params
        self._sampler = sampler
        self._feature = feature
        # fused one-dispatch path: one pre-bindable program per bucket when
        # the sampler/feature pair supports it (see ServeConfig.dispatch_mode)
        self._programs: Optional[BucketPrograms] = None
        if self.config.dispatch_mode != "split":
            try:
                self._programs = BucketPrograms(model, sampler, feature)
            except (TypeError, AttributeError) as exc:
                if self.config.dispatch_mode == "fused":
                    raise ValueError(
                        f"dispatch_mode='fused' but the serve step cannot "
                        f"fuse: {exc}"
                    ) from exc
        self._clock = self.config.clock
        self.stats = ServeStats()
        # request-lifecycle journal (ServeConfig.journal_events; the
        # shared NULL_JOURNAL's emit is one attribute check when off)
        self.journal = (
            EventJournal(self.config.journal_events, clock=self._clock)
            if self.config.journal_events > 0
            else NULL_JOURNAL
        )
        self._next_rid = 0  # journal request ids (guarded by _lock)
        # round-13 workload telemetry (ServeConfig.workload; observe-only)
        self.workload = (
            WorkloadMonitor(self.config.workload, clock=self._clock)
            if self.config.workload is not None
            else None
        )
        self.cache = EmbeddingCache(self.config.cache_entries,
                                    counters=self.stats.cache)
        if self.workload is not None:
            self.cache.workload = self.workload
        if hasattr(feature, "tier_counter"):
            # tiered features attribute gathered rows per tier into the
            # monitor (Feature/QuantizedFeature; raw tables and in-jit
            # fused gathers are single-tier by construction). The LAST
            # engine built over a feature owns its tap: a workload-less
            # engine explicitly DETACHES any stale counter a previous
            # engine left behind, so a reused feature never pays the
            # attribution scan for (or counts into) a dead monitor.
            feature.tier_counter = (
                self.workload.gathers if self.workload is not None else None
            )
        if hasattr(feature, "row_tap"):
            # round-14 row-access sketch tap (WorkloadConfig.row_topk):
            # same last-engine-owns-the-tap rule as tier_counter
            feature.row_tap = (
                self.workload.observe_rows
                if self.workload is not None
                and self.workload.row_sketch is not None
                else None
            )
        # round-14 adaptive tiers: the feature owning a TierStore under
        # the serve wrappers, or None (static placement — nothing to
        # adapt). placement_version counts fenced placement batches, the
        # exact analog of params_version for tier moves.
        from ..tiers import find_tiered_feature

        self._tier_feature = find_tiered_feature(feature)
        self.placement_version = 0
        self.tier_adapt_errors = 0  # failed background adapt passes
        self.compact_errors = 0     # failed background compaction passes
        self.retention_errors = 0   # failed wall-clock TTL passes (r23)
        self.retention_passes = 0   # completed wall-clock TTL passes
        # round-18 flush-ahead prefetch: bind the tier store's staging
        # buffer when the config asks for it AND the feature can serve it
        # (adaptive store + read pool); inert otherwise — a prefetch-on
        # config over a DRAM-resident feature costs nothing
        self._prefetch_store = None
        # seeds the last submit-time walk covered (tier_prefetch_at=
        # "submit"): the assemble-time catch-all only walks what the
        # submit batch missed. Safe across flushes — staged rows outlive
        # their issuer until consumed, and every fence clears both.
        self._pf_walked: frozenset = frozenset()
        if self.config.tier_prefetch and self._tier_feature is not None:
            store = self._tier_feature.tier_store
            if store.read_pool is not None:
                store.enable_prefetch(
                    max_rows=self.config.tier_prefetch_max_rows,
                    listener=self._on_prefetch_event,
                )
                self._prefetch_store = store
        self.params_version = 0
        # round-17 streaming graphs: graph_version counts fenced delta
        # commits (the analog of params_version for topology);
        # pending_delta accumulates staged edge arrivals (stage_edges)
        # until update_graph commits them — both guarded by _lock
        self.graph_version = 0
        self.pending_delta = None
        # round-21 lifecycle: the deterministic retention clock (None when
        # retention is off) — a pure function of committed timestamps, so
        # two replicas fed the same commit stream expire identical lanes
        if self.config.stream_retention_window > 0:
            from ..lifecycle import RetentionPolicy

            self.retention = RetentionPolicy(
                self.config.stream_retention_window
            )
        else:
            self.retention = None
        self.dispatch_log: List[Tuple[np.ndarray, int]] = []
        # round-24 epoch stamps, index-aligned with dispatch_log: entry i
        # is the graph_version flush i sealed (and dispatched) against —
        # the replay tooling's per-epoch filter. A parallel list, not a
        # tuple-shape change: the log entry tuples are pinned by tests
        # and the round-21 CI smoke.
        self.dispatch_graph_versions: List[int] = []
        # queue state (round 20): _pending is the STRIPED pending store —
        # per-stripe dicts of slots not yet flushed (merged arrival order
        # = the rounds-8–19 FIFO, bit for bit), per-stripe locks so
        # concurrent submitters don't serialize; _inflight (guarded by
        # _lock) holds slots snapshot-ed by a running flush. Per-tenant
        # pending counts live inside the store (insert/pop maintain them)
        self._pending = _PendingStripes(self.config.submit_stripes)
        self._inflight: Dict[int, _Slot] = {}
        import collections

        # round-15 deterministic shed decisions log [(request_seq,
        # tenant, node_id)] — a bounded ring: sustained overload (when it
        # fills) must not leak
        self.shed_log = collections.deque(maxlen=65536)
        # round-24 per-commit counter samples (name, t, value) for the
        # Chrome-trace counter lane: graph_version + commit_stall_us at
        # every commit flip. Bounded ring; observe-only.
        self._commit_samples = collections.deque(maxlen=4096)
        # round-20 array-native flush internals: per-engine tenant-name
        # interning for the flush-level tenant-index arrays (grown on
        # demand at seal; order = first-seen)
        self._tenant_ids: Dict[str, int] = {}
        # the assembled-but-not-yet-sealed flush accepting late admissions
        # (guarded by _lock; non-None only while its flusher holds _seq)
        self._open: Optional[_Flush] = None
        self._lock = threading.Lock()          # queue + cache-version state
        # fence condition over _lock: update_params waits here for every
        # in-flight flush to resolve before swapping the weights
        self._fence = threading.Condition(self._lock)
        # sequencing lock: orders queue drain + dispatch-index assignment +
        # dispatch-log append + the sampler's call-index draw, so the key
        # stream and the replay log stay deterministic in dispatch order
        self._seq = threading.Lock()
        # bounded in-flight window: at most max_in_flight flushes between
        # assemble and resolve (blocking acquire = backpressure on callers)
        self._window = threading.BoundedSemaphore(self.config.max_in_flight)
        self._inflight_flushes = 0             # guarded by _lock
        self._dispatch_index = 0               # guarded by _seq
        # `pump()` calls ever, and how many of them came before the newest
        # flush resolved: `quiver.serve.pumps` observes the difference
        # (plain ints: a lost update costs a count, no lock)
        self._pumps = self._pumps_observed = 0
        # round-24 commit serialization: one zero-stall commit at a time
        # (update_graph / expire_edges / compact_graph / the lifecycle
        # daemons) — the off-fence build phase must not interleave with
        # another commit's. RLock: a commit's retention pass may re-enter.
        # Traffic never takes it; it orders only commit vs commit.
        self._commit_lock = threading.RLock()
        # parity escape hatch: True forces the pre-round-22 per-slot
        # resolve loop — the reference the bit-parity tests (and
        # bench_frontend's in-run parity legs) compare the block
        # resolution against. Never set on a serving path.
        self._scalar_resolve = False
        self._seed_bufs: Dict[Tuple[int, object], np.ndarray] = {}
        self._threads: List[threading.Thread] = []
        self._running = False

    # -- request path -----------------------------------------------------

    def submit(self, node_id: int,
               tenant: Optional[str] = None) -> ServeResult:
        """Enqueue one node-prediction request; returns a handle. Fills of
        ``max_batch`` flush inline on the submitting thread. A seed
        arriving while a flush sits assembled-but-not-yet-dispatched (late
        admission enabled, pad slack left) rides that flush's pad lanes
        instead of waiting a whole extra flush.

        Round 20: this is `submit_many` of ONE — the scalar spelling
        stays the public API, but the cache-check/coalesce/shed/admit/
        flush-at-fill sequence lives once in `_admit_one_locked`, so
        scalar and batch admission are bit-identical by construction
        (pinned in tests/test_frontend.py).

        ``tenant`` names the submitting tenant (round 15): its latency
        lands in ``stats.tenant_latency[tenant]``, its queue share is
        bounded by ``tenant_weights``/``max_queue_depth`` (an over-quota
        submit at a full queue returns a `ShedError`-carrying result —
        deterministic, logged in ``shed_log``), and flush quotas drain
        tenants in weighted proportion. Cache hits and coalesces never
        shed. KEEP IN LOCKSTEP with `DistServeEngine.submit`
        (serve/dist.py): the distributed router's hosts=1 bit-parity
        contract rides this exact admission sequence."""
        if not trace_enabled():  # the one per-request site: off, no object
            return self.submit_many((node_id,), tenant=tenant)[0]
        # registry and timeline, no annotation: 64k profiler events a window
        # were most of what tracing cost the median, and nothing read them
        with trace_scope("quiver.serve.submit", annotate=False):
            return self.submit_many((node_id,), tenant=tenant)[0]

    def submit_many(self, node_ids, t=None,
                    tenant: Union[None, str, Sequence[str]] = None,
                    ) -> ResultBatch:
        """Vectorized batch submit (round 20): admit N requests array-at-
        a-time — one stripe-lock acquisition per admission chunk, one
        clock read, one batched journal append (`EventJournal.
        record_many`), a list-compatible `ResultBatch` of handles back
        in request order (round 22: handle objects materialize lazily;
        `results_many` consumes the batch without them, and on the
        production-shaped config — journal off, cache 0, no shedding —
        the whole batch admits per UNIQUE key in one np.unique). The
        admission DECISIONS (cache probe order, coalescing, shedding,
        late admission, flush-at-fill) are made per request in request
        order — by the vectorized `_admit_chunk_fast` body in the
        common case (no shedding, no workload tap, no open
        late-admission window), by the same `_admit_one_locked` body
        the scalar path runs otherwise; the two are decision-for-
        decision identical, so dispatch logs are bit-identical to N
        scalar ``submit`` calls — the batch path amortizes the host
        mechanics, never the semantics. Fills of ``max_batch`` flush
        INLINE mid-batch, exactly where the scalar sequence would
        flush.

        ``t`` is rejected here (temporal engines override with vectorized
        query-time quantization); ``tenant`` is None, one tenant name for
        the whole batch, or a per-request sequence aligned with
        ``node_ids``."""
        if t is not None:
            raise TypeError(
                "t= is a temporal-serving argument (TemporalServeEngine / "
                "TemporalDistServeEngine); this engine serves untimed nodes"
            )
        ids = np.asarray(node_ids, dtype=np.int64).reshape(-1)
        keys = ids.tolist()  # python ints: dict keys + journal payloads
        return self._submit_keyed_many(keys, keys, tenant, uniq_arr=ids)

    def _vector_admissible(self, tenant) -> bool:
        """Engine-shape gates for the whole-batch vectorized admission
        (`_admit_batch_vector`): nothing configured that makes admission
        inherently per-request — no workload tap, no shedding, no
        journal (rid draws + per-request events), no cache that could
        hit, one tenant name. State gates (open late-admission window,
        queue room) are checked under the locks."""
        return (self.workload is None
                and self.config.max_queue_depth == 0
                and not self.journal.enabled
                and self.cache.capacity == 0
                and self.cache.workload is None
                and (tenant is None or isinstance(tenant, str)))

    def _submit_keyed_many(self, keys: List, nodes: List[int],
                           tenant, uniq_arr: Optional[np.ndarray] = None,
                           ) -> ResultBatch:
        """The batch admission loop behind `submit_many` (and, at N=1,
        `submit`/`_submit_keyed`): chunked single-lock holds over the
        striped pending store, per-request decisions in request order,
        one journal append per chunk, inline flush at every fill — the
        scalar admission sequence, amortized. KEEP IN LOCKSTEP with
        `DistServeEngine._submit_keyed_many`.

        When the caller supplies ``uniq_arr`` (the batch's keys as one
        np array) and the `_vector_admissible` gates pass, the whole
        batch is admitted per UNIQUE key by `_admit_batch_vector` —
        one np.unique, no per-request Python work — falling back here
        whenever a per-request decision could arise."""
        n = len(keys)
        if n and uniq_arr is not None and self._vector_admissible(tenant):
            pre = _batch_uniq(uniq_arr)
            if pre is not None:
                ten = DEFAULT_TENANT if tenant is None else str(tenant)
                now = self._clock()
                with self._pending.all_locks():
                    rb = _admit_batch_vector(self, keys, ten, now, *pre)
                if rb is not None:
                    return rb
        tenants = resolve_tenants(tenant, n)
        results: List[Optional[ServeResult]] = [None] * n
        max_batch = self.config.max_batch
        jr = self.journal
        i = 0
        while i < n:
            events: List[Tuple] = []
            need_flush = False
            now = self._clock()
            with self._pending.all_locks():
                if (self.workload is None
                        and self.config.max_queue_depth == 0):
                    # the round-20 tentpole: vectorized chunk admission
                    # (one _lock hold, blocked cache probes, bulk
                    # stats). Falls through to the per-request body
                    # when a decision needs it (an open late-admission
                    # window) or when shedding / the workload tap are
                    # configured (checked above — those are inherently
                    # per-request).
                    i, need_flush = _admit_chunk_fast(
                        self, keys, nodes, tenants, i, now, events,
                        results,
                    )
                while i < n and not need_flush:
                    res = self._admit_one_locked(
                        keys[i], nodes[i], tenants[i], now, events
                    )
                    results[i] = res
                    i += 1
                    if (res._slot is not None
                            and len(self._pending) >= max_batch):
                        need_flush = True
            jr.record_many(events)
            if need_flush:
                # flush-ahead prefetch at SUBMIT time (round 18): issue
                # the filled bucket's closure reads on THIS thread before
                # the flush work starts — when another flush already
                # holds the dispatch path, the reads overlap its whole
                # service time. Observe-only: never reorders admission,
                # never fails a submit (the assemble-time pass is the
                # catch-all).
                if (self._prefetch_store is not None
                        and self.config.tier_prefetch_at == "submit"):
                    self._prefetch_pending()
                self.flush()
        return ResultBatch(items=results)

    def _submit_keyed(self, key, node: int,
                      tenant: Optional[str]) -> ServeResult:
        """Single-key admission under ONE stripe lock (the concurrent-
        scalar-submit fast path: threads submitting keys in different
        stripes never share a lock). Same `_admit_one_locked` body as the
        batch path. ``key`` is the coalescing/cache identity (the plain
        node id on this engine; ``(node, t_bucket)`` on the round-19
        temporal engine; a pair-endpoint composite via `_PairServing`)
        and ``node`` the seed id telemetry/journal/shed entries carry."""
        tenant = DEFAULT_TENANT if tenant is None else str(tenant)
        now = self._clock()
        events: List[Tuple] = []
        with self._pending.lock_for(key):
            res = self._admit_one_locked(key, node, tenant, now, events)
            need_flush = (res._slot is not None
                          and len(self._pending) >= self.config.max_batch)
        self.journal.record_many(events)
        if need_flush:
            if (self._prefetch_store is not None
                    and self.config.tier_prefetch_at == "submit"):
                self._prefetch_pending()
            self.flush()
        return res

    def _admit_one_locked(self, key, node: int, tenant: str, now: float,
                          events: List[Tuple]) -> ServeResult:
        """The ONE cache-check/coalesce/shed/admit sequence behind every
        submit spelling, scalar or batch (round 20: extracted so the two
        can never drift). Caller holds ``key``'s stripe lock (or all
        stripe locks on the batch path); ``_lock`` is taken here only for
        the rid draw + late-admission window (stripe-before-_lock, per
        the `_PendingStripes` hierarchy). Journal events append to
        ``events`` as ``(kind, rid, fid, a, b)`` for the caller's batched
        `record_many`. One body, so a future change to shedding or
        admission can never silently skip a workload."""
        self.stats.requests += 1
        wl = self.workload
        if wl is not None:
            wl.observe_seed(node)  # observe-only frequency tap
        cached = self.cache.get(key, self.params_version)
        if cached is not None:
            ms = (self._clock() - now) * 1e3
            self.stats.latency.record_ms(ms)
            self.stats.tenant_hist(tenant).record_ms(ms)
            events.append(("cache_hit", -1, -1, node, 0))
            return ServeResult(value=cached)
        slot = self._pending.get(key) or self._inflight.get(key)
        if slot is not None and slot.version == self.params_version:
            self.stats.coalesced += 1
            events.append(("coalesce", slot.rid, -1, node, 0))
        else:
            if self._shed_locked(tenant):
                self.stats.shed += 1
                self.shed_log.append((self.stats.requests, tenant, node))
                events.append(("shed", -1, -1, node, 0))
                return ServeResult(error=ShedError(
                    f"queue depth {len(self._pending)} >= "
                    f"{self.config.max_queue_depth} and tenant "
                    f"{tenant!r} is at its weighted quota"
                ))
            admitted_late = False
            with self._lock:
                rid = -1
                if self.journal.enabled:
                    rid = self._next_rid
                    self._next_rid += 1
                slot = _Slot(key, self.params_version, now, rid=rid,
                             tenant=tenant)
                fl = self._open
                if fl is not None and len(fl.keys) < fl.bucket:
                    # late admission into the open flush's pad slack (its
                    # update_params fence guarantees the versions agree:
                    # _open only exists while its flusher holds _seq)
                    fl.keys.append(key)
                    fl.slots.append(slot)
                    self._inflight[key] = slot
                    self.stats.late_admitted += 1
                    events.append(("late_admit", rid, fl.fid, node, 0))
                    admitted_late = True
            if not admitted_late:
                # still under the stripe lock: the probe-above/insert-
                # here pair is atomic per key, and no drain can land in
                # between (assemble needs every stripe lock)
                self._pending.insert_unlocked(key, slot, tenant)
                events.append(("submit", rid, -1, node, 0))
        slot.waiters.append((now, tenant))
        return ServeResult(slot=slot)

    def _prefetch_pending(self) -> None:
        """Walk+issue the current pending keys' expected closure and
        remember them so the assemble-time pass skips the repeat walk
        (`PrefetchBuffer` dedups the READS either way; this skips the
        redundant closure BFS on the serve path)."""
        keys = self._pending.ordered_keys()
        if not keys:
            return
        try:
            self.prefetch_seeds(np.asarray(keys, np.int64))
            # REPLACE the memo (never union): it must mean "walked and
            # certainly still staged" — keys from older batches may have
            # been consumed already, and skipping their re-walk would
            # quietly zero their hit rate on a later arrival
            self._pf_walked = frozenset(keys)
        except Exception:
            pass

    def _shed_locked(self, tenant: str) -> bool:
        return shed_decision(
            len(self._pending), self._pending.tenant_count(tenant), tenant,
            self.config.max_queue_depth, self.config.tenant_weights,
        )

    def predict(self, node_ids, timeout: Optional[float] = None,
                tenants: Optional[Sequence[str]] = None) -> np.ndarray:
        """Blocking convenience: submit every id, make sure they flush
        (inline when no background thread is running), return ``[len(ids),
        C]`` logits in request order. ``tenants`` (aligned with
        ``node_ids``) stamps each submission's tenant — the round-16
        owner-side QoS hook: a router forwarding a sub-batch passes the
        submitting tenants through, so this engine's
        ``tenant_weights`` flush quotas hold END-TO-END, not just at
        router admission."""
        ids = np.asarray(node_ids).reshape(-1)
        if tenants is not None and len(tenants) != ids.shape[0]:
            raise ValueError(
                f"tenants has {len(tenants)} entries for {ids.shape[0]} ids"
            )
        handles = self.submit_many(ids, tenant=tenants)
        if not handles:  # empty batch is a valid no-op (np.stack would raise)
            return np.zeros((0, 0), np.float32)
        if not self._running:
            while not handles.done() and self._drainable():
                self.flush()
        return self.results_many(handles, timeout)

    def results_many(self, handles, timeout: Optional[float] = None,
                     ) -> np.ndarray:
        """Batch consumption surface (round 22): gather a `submit_many`
        batch's rows as ONE ``[len(handles), C]`` array — the delivery
        half of the array-at-a-time host path. On a `ResultBatch` this
        waits per UNIQUE slot and broadcasts rows through the batch's
        stored inverse map (coalesced requests never re-wait, rows are
        views into the flush's logits block); any other sequence of
        handles degrades to the per-handle `result()` stack `predict`
        always did. Errors surface exactly as the scalar path would:
        the first failed request in REQUEST order raises its error."""
        if isinstance(handles, ResultBatch):
            return handles.gather(timeout)
        if not len(handles):
            return np.zeros((0, 0), np.float32)
        return np.stack([h.result(timeout) for h in handles])

    # -- flush policy -----------------------------------------------------

    def should_flush(self) -> bool:
        # lock-free probe (round 20): len() over the stripes is a sum of
        # dict lens (GIL-consistent), the head slot comes from a per-
        # stripe-locked min-arrival scan; a racing submit just makes the
        # next poll flush — the policy is a timer, not an invariant
        if not self._pending:
            return False
        if len(self._pending) >= self.config.max_batch:
            return True
        oldest = self._pending.oldest_enqueue_t()
        if oldest is None:
            return False
        return (self._clock() - oldest) * 1e3 >= self.config.max_delay_ms

    def pump(self) -> int:
        """Apply the flush policy once: flush iff ``max_batch`` or
        ``max_delay_ms`` demands it. Returns seeds dispatched (0 if the
        policy held). This is the deterministic-test / external-event-loop
        surface; the background threads just call it on a poll timer."""
        self._pumps += 1  # a plain int, no lock: `quiver.serve.pumps` reads it
        return self.flush() if self.should_flush() else 0

    # -- the three flush stages -------------------------------------------

    def _assemble(self) -> Optional[_Flush]:
        """Stage 1a (caller must hold ``_seq``): drain up to ``max_batch``
        pending slots into a new flush, fix its bucket, and — when late
        admission is on and the bucket left pad slack — PUBLISH it so
        `submit` can fill the slack until `_seal_assembled` closes it
        (typically while this flush waits for an in-flight window slot).

        Lock order (round 20): every stripe lock, THEN ``_lock`` — the
        drain must see a frozen pending queue across all stripes, and the
        striped hierarchy puts stripes strictly before the engine lock."""
        with self._pending.all_locks(), self._lock:
            if not self._pending:
                return None
            if len(self._pending) <= self.config.max_batch:
                # whole-queue drain (round 22): when everything pending
                # fits the batch, `weighted_drain_keys` is the identity
                # on the arrival-ordered queue (weights only bite on
                # overflow) and every pop's tenant bookkeeping nets to
                # empty — so one sorted merge + wholesale clear replaces
                # the per-key pop loop, bit-identically
                items = self._pending.ordered_items_unlocked()
                keys = [kv[0] for kv in items]
                slots = [kv[1] for kv in items]
                self._pending.clear_unlocked()
                self._inflight.update(items)
            else:
                keys = self._drain_keys_locked()
                slots = [self._pending.pop_unlocked(k) for k in keys]
                self._inflight.update(zip(keys, slots))
            # params snapshot: the fence in update_params guarantees no
            # swap lands while this flush is in flight, so the snapshot and
            # every drained slot's version agree
            fl = _Flush(keys, slots, self._params)
            fl.bucket = self._bucket_for(len(keys))
            self._inflight_flushes += 1
            self.stats.inflight_peak = max(
                self.stats.inflight_peak, self._inflight_flushes
            )
            # the caller holds _seq, so the index _seal_assembled will
            # draw is exactly the next one
            fl.fid = self._dispatch_index + 1
            jr = self.journal
            if jr.enabled:
                # a = the NODE id per the EVENT_KINDS contract (a
                # temporal key is a (node, t_bucket) tuple); one batched
                # ring append for the whole drain (round 20)
                jr.record_many([
                    ("assemble", slot.rid, fl.fid,
                     k[0] if isinstance(k, tuple) else k, 0)
                    for k, slot in zip(keys, slots)
                ])
                jr.emit("flush", -1, fl.fid, len(keys), fl.bucket)
            if self.config.late_admission and len(keys) < fl.bucket:
                self._open = fl
        return fl

    def _seal_assembled(self, fl: _Flush) -> None:
        """Stage 1b (caller holds ``_seq`` and a window permit): close late
        admission, then draw the dispatch index, append the dispatch-log
        entry, and consume the sampler's next call index (a host integer:
        the fused program derives the key from it on the device, as the
        split path's `sample_dense` does). Everything that must be ordered
        by dispatch index happens HERE — admitted seeds are already in
        ``fl.keys``, so the log and the key stream see the final batch
        composition exactly once."""
        with self._lock:
            self._open = None
        self._dispatch_index += 1
        if self.workload is not None:
            # decay-window tick on the dispatch index (caller holds _seq,
            # so tick order == seal order — replay-deterministic)
            self.workload.tick()
        self.journal.emit("seal", -1, fl.fid, len(fl.keys), fl.bucket)
        try:
            fl.seeds, extras = self._flush_arrays(fl)
            # array-native slot views (round 20): sealed composition as
            # int arrays — late admits included, addressed by slot index
            fl.ids = fl.seeds
            n_slots = len(fl.slots)
            if self.journal.enabled:
                fl.rids = np.fromiter(
                    (s.rid for s in fl.slots), np.int64, n_slots
                )
            else:
                # no journal, no rid draws: every slot carries -1
                fl.rids = np.full(n_slots, -1, np.int64)
            tix = self._tenant_ids
            tens = [s.tenant for s in fl.slots]
            uniq_tens = set(tens)
            if len(uniq_tens) == 1:
                fl.tenant_ix = np.full(
                    n_slots, tix.setdefault(uniq_tens.pop(), len(tix)),
                    np.int32,
                )
            else:
                # id assignment order == slot order, as the scalar pass
                fl.tenant_ix = np.fromiter(
                    (tix.setdefault(t, len(tix)) for t in tens),
                    np.int32, n_slots,
                )
            if self.config.max_in_flight == 1 and not extras:
                # serial mode: reuse one pad buffer per bucket (round-8
                # behavior); with in-flight > 1 each flush owns its buffer
                buf = self._seed_bufs.get((fl.bucket, fl.seeds.dtype.str))
                padded = pad_seed_batch(fl.seeds, fl.bucket, out=buf)
                self._seed_bufs[(fl.bucket, fl.seeds.dtype.str)] = padded
            else:
                padded = pad_seed_batch(fl.seeds, fl.bucket)
            if extras:
                fl.extra = tuple(
                    pad_seed_batch(e, fl.bucket) for e in extras
                )
            # round-24 epoch pin (caller holds _seq — the commit flip
            # also runs under _seq, so the stamp, the binding snapshot,
            # and the upcoming call-index draw are all of ONE epoch)
            fl.graph_version = self.graph_version
            if self.config.record_dispatches:
                self.dispatch_log.append(self._dispatch_log_entry(fl, padded))
                self.dispatch_graph_versions.append(fl.graph_version)
            if self._programs is not None:
                # fused path: draw the call index in dispatch order, defer
                # the key and the sample into the one-program dispatch
                # stage; the binding snapshot pins the graph arrays this
                # flush will execute against even if a zero-stall commit
                # rebinds mid-flight
                fl.call = self._sampler.next_call()
                fl.padded = padded
                fl.binding = self._programs.binding()
            else:
                fl.ds = self._split_sample(fl, padded)
        except BaseException as exc:  # resolved (with the error) by stage 3
            fl.error = exc

    # hooks the round-19 workloads subsystem overrides (base behavior is
    # byte-identical to round 18): how flush keys become dispatch arrays,
    # what a dispatch-log entry records, and how the split path samples
    def _flush_arrays(self, fl: _Flush):
        """``(seeds int64 [n], extra per-seed arrays)`` from ``fl.keys``.
        The temporal engine's keys are ``(node, t)`` pairs and its extra
        is the query-time vector; here keys ARE the seeds."""
        return np.asarray(fl.keys, dtype=np.int64), ()

    def _dispatch_log_entry(self, fl: _Flush, padded: np.ndarray):
        return (padded.copy(), len(fl.keys))

    def _split_sample(self, fl: _Flush, padded: np.ndarray):
        return sample_batch(self._sampler, padded)

    def _dispatch(self, fl: _Flush) -> Optional[np.ndarray]:
        """Stage 2 (no engine lock held, none taken): the device work +
        blocking D2H — ONE pre-bound execute call on the fused path, the
        round-9 sample(-in-assemble) + forward pair on the split path.
        Concurrent across flushes up to the window bound. What it ran is
        recorded on the flush (``fl.n_exec``) and counted into
        ``stats.dispatch_calls`` / ``execute_calls`` by `_resolve`, under
        the lock it holds anyway."""
        fl.n_exec = 0
        self.journal.emit("dispatch", -1, fl.fid, fl.bucket)
        if fl.ds is None and self._programs is not None:
            logits = np.asarray(
                self._programs(fl.bucket, fl.params, fl.call, fl.padded,
                               *(fl.extra or ()), binding=fl.binding)
            )
            fl.n_exec = 1
        else:
            logits = np.asarray(
                forward_logits(self._apply, fl.params, self._feature, fl.ds)
            )
            fl.n_exec = 2  # the sample leg ran in _seal_assembled
        self.journal.emit("execute_done", -1, fl.fid, fl.n_exec)
        # rows of this array are handed to every waiter AND the cache;
        # read-only makes an in-place mutation by one caller a loud
        # ValueError instead of silently corrupting every later cache hit
        if logits.flags.writeable:
            logits.setflags(write=False)
        return logits

    def _resolve(self, fl: _Flush, logits: Optional[np.ndarray]) -> None:
        """Stage 3: per-flush slot resolution + cache writeback + stats.
        Safe out of dispatch order — only this flush's slots are touched.
        Always decrements the in-flight count and wakes the fence."""
        with self._lock, trace_scope("quiver.serve.resolve", fid=fl.fid):
            # one clock sample taken AFTER the lock is held: as the span
            # start it keeps lock-wait out of stage-overlap evidence, and
            # as the latency endpoint it keeps lock-wait IN each waiter's
            # recorded latency (their events are set after this point)
            now = t_res0 = self._clock()
            slots = fl.slots
            if (fl.error is None and slots and not slots[0].resolved
                    and slots[0].version == self.params_version
                    and not self._scalar_resolve):
                # the round-22 tentpole: whole-flush block resolution.
                # The guard is per-FLUSH, not per-slot, because both of
                # its disqualifiers are all-or-nothing: a bounded stop()
                # abandon resolves EVERY slot of the flush or none
                # (abandon_undrained clears pending+inflight under all
                # locks), and the update_params fence re-stamps versions
                # only while no flush is in flight — so slot[0] answers
                # for the batch.
                _resolve_block(self, fl, logits, now)
            else:
                for i, (k, slot) in enumerate(zip(fl.keys, fl.slots)):
                    self._inflight.pop(k, None)
                    if slot.resolved:
                        # abandoned by a bounded stop() drain: the error
                        # was delivered and the waiters counted — a late
                        # completion must not overwrite it or double-count
                        continue
                    if fl.error is None:
                        row = logits[i]
                        if slot.version == self.params_version:
                            self.cache.put(k, slot.version, row,
                                           gv=fl.graph_version)
                        slot.resolve(row)
                    else:
                        slot.resolve(None, error=fl.error)
                        self.stats.request_errors += 1
                    for t0, tenant in slot.waiters:
                        ms = (now - t0) * 1e3
                        self.stats.latency.record_ms(ms)
                        self.stats.tenant_hist(tenant).record_ms(ms)
            if fl.n_exec is not None:
                self.stats.dispatch_calls += 1
                self.stats.execute_calls += fl.n_exec
            if fl.error is None:
                self.stats.dispatches += 1
                self.stats.dispatched_seeds += len(fl.keys)
                self.stats.padded_seeds += fl.bucket - len(fl.keys)
                self.stats.dispatch_buckets[fl.bucket] = (
                    self.stats.dispatch_buckets.get(fl.bucket, 0) + 1
                )
            self._inflight_flushes -= 1
            self._fence.notify_all()
            self.stats.spans.record("resolve", t_res0, self._clock())
            self.journal.record_many((("resolve", -1, fl.fid,
                                       len(fl.keys), 0),))
        # `pump()` calls since the flush before this one, traced or not (two
        # flushes resolving at once split the count between them)
        pumps = self._pumps
        if fl.error is None and trace_enabled():
            _observe_stages(fl, now)
            observe("quiver.serve.pumps", pumps - self._pumps_observed)
        self._pumps_observed = pumps

    def flush(self) -> int:
        """Dispatch up to ``max_batch`` pending unique seeds as one bucket-
        padded device batch NOW (policy bypassed). Returns the number of
        unique seeds dispatched (late-admitted ones included).

        Synchronous: assemble -> dispatch -> resolve run on the calling
        thread, and any stage error re-raises here (after resolving every
        drained slot with it). Pipelining comes from concurrent callers —
        up to ``max_in_flight`` flushes may overlap, with assembles (and
        the sampler key stream) serialized in dispatch order. The in-flight
        window permit is taken UNDER the sequencing lock, AFTER the drain:
        while a flush waits for a slot (device saturated), late-arriving
        seeds join its pad lanes; admission closes in `_seal_assembled`
        before the dispatch index and sampler key are drawn, so the log and
        key stream stay deterministic at any admission interleaving."""
        fl = None
        have_permit = False
        # while tracing, the call's own stamp: a request is PENDING until
        # here and inside this flush from here (`_observe_stages`)
        t_begin = self._clock() if trace_enabled() else 0.0
        # entered and left by hand: it ends INSIDE `with self._seq`, which
        # stays a `with` so that no interrupt can leave the lock held
        seq_wait = trace_scope("quiver.serve.seq_wait").__enter__()
        try:
            with self._seq:
                seq_wait.set(fid=self._dispatch_index + 1)
                seq_wait.__exit__(None, None, None)
                # `stats.spans` open AFTER _seq is held, and leave the
                # window wait out: a caller blocked behind another flush
                # (or a full window) is idle, not working, and counting the
                # wait there would fake stage overlap. The two waits have
                # spans of their own (`seq_wait`, `window_wait`): they are
                # part of the queue a request sits in
                t0 = self._clock()
                with trace_scope("quiver.serve.assemble",
                                 fid=self._dispatch_index + 1):
                    fl = self._assemble()
                if fl is not None:
                    self.stats.spans.record("assemble", t0, self._clock())
                if fl is None:
                    return 0
                fl.t_begin = t_begin
                # flush-ahead prefetch: issue the expected closure's disk
                # reads NOW, before the window wait — they land while the
                # previous flush's dispatch (and this one's window wait)
                # runs, so the gather below finds them in DRAM
                if self._prefetch_store is not None:
                    t0p = self._clock()
                    self._prefetch_flush(fl)
                    self.stats.spans.record("prefetch", t0p, self._clock())
                try:
                    jr = self.journal
                    t_w0 = self._clock() if jr.enabled else 0.0
                    with trace_scope("quiver.serve.window_wait", fid=fl.fid):
                        self._window.acquire()
                        have_permit = True
                    if jr.enabled:
                        jr.emit("window_wait", -1, fl.fid,
                                self._clock() - t_w0)
                    t0 = self._clock()
                    with trace_scope("quiver.serve.seal", fid=fl.fid):
                        self._seal_assembled(fl)  # errors land in fl.error
                    self.stats.spans.record("assemble", t0, self._clock())
                finally:
                    # _seal_assembled's first act already closed admission
                    # (it MUST happen under _lock before the index draw);
                    # this repeat only covers an interrupt landing between
                    # the window acquire and the seal
                    with self._lock:
                        self._open = None
            logits = None
            if fl.error is None:
                t0 = self._clock()
                try:
                    with trace_scope("quiver.serve.dispatch", fid=fl.fid):
                        logits = self._dispatch(fl)
                except BaseException as exc:
                    fl.error = exc
                t1 = self._clock()
                fl.t_dispatch, fl.t_done = t0, t1
                self.stats.spans.record("dispatch", t0, t1)
                if self.workload is not None:
                    # per-flush width + latency (owner 0: this engine is
                    # the only "owner" at single-host grain)
                    self.workload.observe_flush(0, len(fl.keys), t1 - t0)
            self._resolve(fl, logits)  # records its own post-lock span
            if fl.error is not None:
                raise fl.error
            return len(fl.keys)
        finally:
            if have_permit:
                self._window.release()

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _drain_keys_locked(self) -> List[int]:
        # materialize the striped store as one arrival-ordered dict: the
        # weighted drain sees exactly the FIFO the round-15 single-dict
        # queue presented (slot.seq is the global arrival stamp)
        return weighted_drain_keys(
            self._pending.ordered_dict_unlocked(),
            self.config.max_batch, self.config.tenant_weights,
        )

    def _drainable(self) -> bool:
        return bool(self._pending)

    # -- flush-ahead prefetch (round 18, ROADMAP item 3a) ------------------

    def _on_prefetch_event(self, kind: str, n: int) -> None:
        """Staging-buffer tap: mirrors consumption/waste into ServeStats
        and the journal (plain ints under the GIL — the ServeStats
        discipline). ``hit`` fires at gather time, which may be a
        different flush than the issuer, so the event carries no fid."""
        if kind == "hit":
            self.stats.tier_prefetch_hit += n
            self.journal.emit("prefetch_hit", -1, -1, n)
        elif kind == "wasted":
            self.stats.tier_prefetch_wasted += n

    def prefetch_seeds(self, seed_ids, fid: int = -1) -> int:
        """Issue flush-ahead disk reads for the expected k-hop closure
        of ``seed_ids`` (OBSERVE-ONLY: no key consumed, no placement
        moved, no served bit changed — see ``ServeConfig.tier_prefetch``).
        Returns rows issued. The engine calls this itself at assemble
        time; `DistServeEngine` calls it per owner off the routed
        sub-batches, one window earlier still. Dedup in the staging
        buffer makes the double-issue free."""
        store = self._prefetch_store
        if store is None:
            return 0
        from ..tiers import expected_closure

        hops = self.config.tier_prefetch_hops
        if hops is None:
            hops = len(self._sampler.sizes)
        nodes = expected_closure(
            self._sampler, np.asarray(seed_ids, np.int64), hops,
            max_nodes=self.config.tier_prefetch_max_rows,
        )
        if nodes.size == 0:
            return 0
        stored = self._tier_feature.stored_rows_of(nodes)
        issued = store.prefetch_rows(stored[stored >= 0])
        if issued:
            self.stats.tier_prefetch_issued += issued
            self.journal.emit("prefetch_issue", -1, fid, issued,
                              int(nodes.size))
        return issued

    def _prefetch_flush(self, fl: "_Flush") -> None:
        """Assemble-time prefetch for a drained flush (called under
        ``_seq``, before the window wait — the reads overlap the
        PREVIOUS flush's dispatch). With ``tier_prefetch_at="submit"``
        this is the catch-all for seeds the submit-time walk missed
        (late admits, window flushes). Never fails a flush: prefetch is
        a hint, and any error here would break the on/off parity pin."""
        if self._prefetch_store is None:
            return
        keys = fl.keys
        if self._pf_walked:
            missed = [k for k in keys if k not in self._pf_walked]
            if not missed:
                return
            keys = missed
        try:
            self.prefetch_seeds(keys, fid=fl.fid)
        except Exception:
            pass

    def _cancel_prefetch(self) -> None:
        """Fence hook: drop staged prefetch rows (counted as wasted).
        Callers hold the fence (no gather in flight), so nothing races
        the staging map. The submit-walk memo clears with it — staged
        rows are gone, so "already walked" no longer implies "already
        staged"."""
        self._pf_walked = frozenset()
        if self._prefetch_store is not None:
            self._prefetch_store.cancel_prefetch()

    def reset_stats(self) -> None:
        """Zero every counter/histogram AND re-point the embedding cache's
        counter at the fresh `ServeStats` (the two must move together — a
        bare ``stats.__init__()`` would leave the cache counting into the
        detached old object). The journal ring is cleared with it — stale
        lifecycle events would make breakdowns straddle the reset. Benches
        call this after their warm-up pass; cache CONTENTS are untouched
        (use `cache.invalidate()` for that). Registry adapters registered
        by `register_metrics` follow the swap (they resolve through
        ``self.stats`` at read time)."""
        with self._lock:
            self.stats = ServeStats()
            self.cache.counters = self.stats.cache
            if self.journal.enabled:
                self.journal.clear()
            if self.workload is not None:
                # same straddle rule as the journal: sketch/owner state
                # from before the reset would skew every report after it
                self.workload.clear()

    # -- observability surface --------------------------------------------

    def register_metrics(self, registry: Optional[MetricsRegistry] = None,
                         prefix: str = "quiver_serve",
                         labels: Optional[Dict[str, str]] = None,
                         ) -> MetricsRegistry:
        """Adapt this engine's live state into a `trace.MetricsRegistry`
        (created when not given): every `ServeStats` counter as a
        callback-backed counter, the engine's QUEUE-STATE gauges (pending
        depth, in-flight flushes/window/peak, cache rows, params version),
        per-bucket dispatch counts (``bucket`` label), the embedding
        cache's hit/miss/eviction family, and the live latency histogram.
        Adapters READ the engine at exposition time — nothing is counted
        twice, and `reset_stats` swaps are followed. Returns the
        registry (``registry.to_prometheus()`` /
        ``registry.snapshot()`` are the export surfaces)."""
        reg = registry if registry is not None else MetricsRegistry()
        for f in ("requests", "coalesced", "dispatches", "dispatched_seeds",
                  "padded_seeds", "dispatch_calls", "execute_calls",
                  "late_admitted", "tier_promoted", "tier_demoted",
                  "placement_batches", "tier_prefetch_issued",
                  "tier_prefetch_hit", "tier_prefetch_wasted",
                  "shed", "request_errors",
                  "undrained", "graph_deltas", "delta_edges",
                  "delta_tile_writes", "delta_tile_spills",
                  "delta_cache_invalidated", "edges_deleted",
                  "edges_expired", "tiles_reclaimed", "compactions"):
            reg.counter_fn(f"{prefix}_{f}_total",
                           (lambda f=f: getattr(self.stats, f)),
                           f"ServeStats.{f}", labels)
        register_tenant_latency(
            reg, prefix, "end-to-end request latency by submitting tenant",
            lambda: self.stats, self.config.tenant_weights, labels,
        )
        reg.gauge_fn(f"{prefix}_pending_depth",
                     lambda: len(self._pending),
                     "unique seeds queued and not yet drained", labels)
        reg.gauge_fn(f"{prefix}_inflight_flushes",
                     lambda: self._inflight_flushes,
                     "flushes between assemble and resolve now", labels)
        reg.gauge_fn(f"{prefix}_inflight_window",
                     lambda: self.config.max_in_flight,
                     "configured max_in_flight bound", labels)
        reg.gauge_fn(f"{prefix}_inflight_peak",
                     lambda: self.stats.inflight_peak,
                     "largest in-flight occupancy observed", labels)
        reg.gauge_fn(f"{prefix}_cache_rows", lambda: len(self.cache),
                     "embedding-cache resident rows", labels)
        reg.gauge_fn(f"{prefix}_params_version",
                     lambda: self.params_version,
                     "current weights version", labels)
        reg.gauge_fn(f"{prefix}_graph_version",
                     lambda: self.graph_version,
                     "fenced streaming-graph delta commits applied",
                     labels)
        reg.gauge_fn(f"{prefix}_delta_pending_edges",
                     lambda: (len(self.pending_delta)
                              if self.pending_delta is not None else 0),
                     "edge arrivals staged and not yet committed", labels)
        register_stream_reserve(
            reg, prefix, lambda: getattr(self._sampler, "stream", None),
            labels,
        )
        reg.gauge_fn(f"{prefix}_placement_version",
                     lambda: self.placement_version,
                     "fenced tier-placement batches applied", labels)
        reg.gauge_fn(f"{prefix}_tier_adapt_errors",
                     lambda: self.tier_adapt_errors,
                     "failed background tier-adaptation passes", labels)
        reg.gauge_fn(f"{prefix}_compact_errors",
                     lambda: self.compact_errors,
                     "failed background compaction passes", labels)
        reg.gauge_fn(f"{prefix}_retention_errors",
                     lambda: self.retention_errors,
                     "failed wall-clock TTL retention passes", labels)
        reg.gauge_fn(f"{prefix}_retention_passes",
                     lambda: self.retention_passes,
                     "completed wall-clock TTL retention passes", labels)
        reg.gauge_fn(
            f"{prefix}_tier_prefetch_hit_rate",
            lambda: (self.stats.tier_prefetch_hit
                     / max(self.stats.tier_prefetch_issued, 1)),
            "flush-ahead prefetch rows consumed over rows issued", labels)
        if self._tier_feature is not None:
            reg.gauge_fn(
                f"{prefix}_tier_hbm_rows",
                lambda: self._tier_feature.tier_store.placement.counts()["hbm"],
                "rows resident in HBM under the adaptive placement", labels)
            reg.gauge_fn(
                f"{prefix}_tier_host_rows",
                lambda: self._tier_feature.tier_store.placement.counts()["host"],
                "rows resident in host DRAM under the adaptive placement",
                labels)
        reg.gauge_fn(f"{prefix}_journal_events", lambda: len(self.journal),
                     "lifecycle events in the journal ring", labels)
        for b in self._buckets:
            reg.counter_fn(
                f"{prefix}_bucket_dispatches_total",
                (lambda b=b: self.stats.dispatch_buckets.get(b, 0)),
                "resolved dispatches by bucket shape",
                dict(labels or {}, bucket=str(b)),
            )
        register_hit_rate(reg, f"{prefix}_cache", lambda: self.stats.cache,
                          labels)
        reg.histogram(f"{prefix}_latency_ms",
                      "end-to-end request latency (submit -> resolve)",
                      labels, fn=lambda: self.stats.latency)
        reg.histogram(f"{prefix}_commit_stall_us",
                      "per-commit serving stall, µs (fenced: whole "
                      "drain; zero-stall: the _seq flip hold)",
                      labels, fn=lambda: self.stats.commit_stall)
        if self.workload is not None:
            self.workload.register_metrics(
                reg, prefix=f"{prefix}_workload", labels=labels, owners=(0,)
            )
        return reg

    def export_chrome_trace(self, path: str, extra_sources: Sequence = (),
                            metadata: Optional[Dict[str, object]] = None,
                            ) -> Dict[str, object]:
        """Write a Perfetto/chrome://tracing-loadable ``trace_events``
        timeline merging the engine's stage spans (``stats.spans``) and —
        when journaling is on — the request-lifecycle journal (per-flush
        lanes show overlapped in-flight flushes side by side). Spans and
        journal share the engine clock, so the merge is one timeline, not
        two guesses. ``extra_sources`` appends more (name, SpanRecorder |
        EventJournal) pairs recorded on the same clock (e.g.
        `comm` exchange spans)."""
        sources: List = [("serve.spans", self.stats.spans)]
        if self.journal.enabled:
            sources.append(("serve.journal", self.journal))
        if self._commit_samples:
            # round-24 counter lane: graph_version staircase + per-commit
            # stall alongside the flush lanes
            sources.append(
                ("serve.commits",
                 _CommitCounterSource(self._commit_samples))
            )
        if self.workload is not None and self.workload.counters is not None:
            # the round-13 counter lane: sampled workload series (head
            # coverage, observed seeds) graph under the flush lanes
            sources.append(("serve.workload", self.workload.counters))
        sources.extend(extra_sources)
        return _export_chrome_trace(path, sources, metadata)

    # -- warmup -----------------------------------------------------------

    def _warmup_sampler(self):
        """A twin of the serving sampler (same topology/seed/config) for
        warmup traffic, so pre-tracing consumes the TWIN's key stream and
        the serving stream + replay log stay untouched. None when the
        sampler doesn't support the share_ipc/lazy_from_ipc_handle clone
        protocol."""
        s = self._sampler
        try:
            return type(s).lazy_from_ipc_handle(s.share_ipc())
        except Exception:
            return None

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> Dict[int, float]:
        """Bind the compiled program for every bucket shape so the first
        REAL request at each bucket doesn't eat a compile. Returns
        {bucket: seconds}.

        Fused engines AOT-compile one LOADED executable per bucket
        (``jax.jit(...).lower(...).compile()`` via
        `inference.BucketPrograms`) — no jit cache warmed, no dispatch
        executed, NO key consumed (lowering traces abstract values only) —
        and then SEAL the program table: a post-warmup bucket miss raises
        RuntimeError instead of silently compiling for 12–60 s under a live
        request. Split engines keep the round-9 behavior: one warm dispatch
        per bucket through a twin sampler when the sampler supports cloning
        (key stream untouched); otherwise through the serving sampler under
        the sequencing lock with an ``n_valid=0`` dispatch-log entry, so a
        parity replay still consumes the same key indices."""
        buckets = self._buckets if buckets is None else tuple(
            sorted(int(b) for b in buckets)
        )
        with self._lock:
            params = self._params
        times: Dict[int, float] = {}
        if self._programs is not None:
            for b in buckets:
                t0 = time.perf_counter()
                self._programs.compile_bucket(b, params)
                times[b] = time.perf_counter() - t0
            self._programs.seal()
            return times
        twin = self._warmup_sampler()
        for b in buckets:
            padded = np.zeros(b, np.int64)
            t0 = time.perf_counter()
            if twin is not None:
                ds = sample_batch(twin, padded)
            else:
                with self._seq:
                    self._dispatch_index += 1
                    if self.config.record_dispatches:
                        self.dispatch_log.append((padded.copy(), 0))
                        self.dispatch_graph_versions.append(
                            self.graph_version)
                    ds = sample_batch(self._sampler, padded)
            np.asarray(forward_logits(self._apply, params, self._feature, ds))
            times[b] = time.perf_counter() - t0
        return times

    # -- weight updates ---------------------------------------------------

    def update_params(self, params) -> None:
        """Install new weights behind a FENCE: block new assembles (the
        sequencing lock), wait for every in-flight flush to resolve, then
        bump ``params_version`` and invalidate the embedding cache — so no
        served logit ever crosses a weight update mid-flush. Pending (not
        yet dispatched) slots are re-stamped to the new version — their
        flush will compute under the new weights. Requests resolved by the
        drained in-flight flushes were accepted under the old weights and
        keep their old-version results (never cached past the bump).

        Lock order (round 20): stripes before ``_lock`` — the fence wait
        releases only ``_lock`` while the stripe locks stay held, so
        submits park at stripe acquire (holding nothing) and resolves
        (which need only ``_lock``) drain freely: no cycle."""
        with self._seq:
            with self._pending.all_locks():
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    # a prefetch issued for a pre-fence flush may still be
                    # in flight: drop the staging (bytes stay valid
                    # forever, but the rows' consumers are gone — holding
                    # them would only skew waste accounting). Never blocks
                    # on the pool.
                    self._cancel_prefetch()
                    self._params = params
                    self.params_version += 1
                    self.cache.invalidate()
                    for slot in self._pending.values_unlocked():
                        slot.version = self.params_version

    # -- streaming graph deltas (round 17; quiver_tpu.stream) --------------

    def stage_edges(self, src, dst, ts=None) -> int:
        """Accumulate edge arrivals host-side into ``pending_delta``
        (observe-only until a commit: no device state, no fence, no
        served bit moves). Edge ids are validated HERE, against the
        bound stream's node range, so one bad arrival raises at the
        staging call site and never poisons the pending buffer (a commit
        failure re-stages the delta — an unvalidated bad edge would
        wedge every future ``update_graph``). Returns the pending-edge
        count — the ``delta_pending_edges`` gauge reads the same
        number."""
        from ..stream import GraphDelta, validate_edge_ids

        stream = getattr(self._sampler, "stream", None)
        if stream is not None:
            n = stream.n
        else:
            # not stream-bound (yet): validate against the sampler's own
            # graph so a bad arrival still cannot poison the buffer — a
            # later bind_stream + commit would otherwise wedge on it
            topo = getattr(self._sampler, "csr_topo", None)
            n = topo.node_count if topo is not None else None
        src, dst = validate_edge_ids(src, dst, n, "staged")
        if stream is not None:
            # the temporal-arity contract holds AT THE STAGING CALL SITE
            # in BOTH directions: a ts-less arrival on a temporal stream
            # — or a timestamped one on a plain stream — must raise here,
            # because a delta that can never commit would re-stage on
            # every update_graph failure and wedge the pending buffer
            # forever (and poison later correct stagings via GraphDelta's
            # homogeneity check)
            if getattr(stream, "temporal", False):
                if (ts is None
                        or np.asarray(ts).reshape(-1).shape != src.shape):
                    raise ValueError(
                        "temporal stream needs one ts per staged edge"
                    )
            elif ts is not None:
                raise ValueError(
                    "edge timestamps staged into a non-temporal stream — "
                    "build StreamingTiledGraph(edge_ts=...) to carry them"
                )
        with self._lock:
            if self.pending_delta is None:
                self.pending_delta = GraphDelta()
            self.pending_delta.add_edges(src, dst, ts=ts)
            n = len(self.pending_delta)
        self.journal.emit("graph_delta", -1, -1, n)
        return n

    def stage_removals(self, src, dst) -> int:
        """Accumulate edge DELETIONS host-side into ``pending_delta``
        (round 21) — the removal side of `stage_edges`: validated here
        against the bound stream's node range so one bad id raises at
        the call site, applied at the next `update_graph` commit as
        masked lane rewrites (survivors shift left — a delete-then-
        replay is bit-identical to a graph built without the edge).
        EXISTENCE is checked at commit preflight, not here: the edge may
        legitimately be in the same pending batch (append then remove in
        one commit is valid and nets out). Returns the pending count."""
        from ..stream import GraphDelta, validate_edge_ids

        stream = getattr(self._sampler, "stream", None)
        if stream is not None:
            n = stream.n
        else:
            topo = getattr(self._sampler, "csr_topo", None)
            n = topo.node_count if topo is not None else None
        src, dst = validate_edge_ids(src, dst, n, "removed")
        with self._lock:
            if self.pending_delta is None:
                self.pending_delta = GraphDelta()
            self.pending_delta.remove_edges(src, dst)
            n = len(self.pending_delta)
        self.journal.emit("graph_delta", -1, -1, n)
        return n

    def stage_updates(self, src, dst, ts) -> int:
        """Accumulate per-edge TIMESTAMP REWRITES into ``pending_delta``
        (round 21): each (src, dst) must exist at commit time and gets
        its ts lane overwritten in place — no lane moves, no degree
        change, so only the recency weighting of future draws shifts.
        Temporal streams only (the ts lane is the one mutable per-edge
        payload); ``ts`` must be finite (+inf is the retention expiry
        sentinel). Returns the pending count."""
        from ..stream import GraphDelta, validate_edge_ids

        stream = getattr(self._sampler, "stream", None)
        if stream is not None:
            n = stream.n
            if not getattr(stream, "temporal", False):
                raise ValueError(
                    "timestamp updates need a temporal stream "
                    "(StreamingTiledGraph(edge_ts=...)) — plain streamed "
                    "tiles carry no per-edge payload to rewrite"
                )
        else:
            topo = getattr(self._sampler, "csr_topo", None)
            n = topo.node_count if topo is not None else None
        src, dst = validate_edge_ids(src, dst, n, "updated")
        with self._lock:
            if self.pending_delta is None:
                self.pending_delta = GraphDelta()
            self.pending_delta.update_edges(src, dst, ts)
            n = len(self.pending_delta)
        self.journal.emit("graph_delta", -1, -1, n)
        return n

    def update_graph(self, delta=None, *, installs=None,
                     invalidate=None) -> Dict[str, object]:
        """Commit a graph delta behind the SAME fence as `update_params`:
        block new assembles (the sequencing lock), drain every in-flight
        flush, apply the batch to the bound `stream.StreamingTiledGraph`
        (host pad-lane writes / tile spills + ONE batched device tile
        swap), bump ``graph_version``, rebind the sealed AOT programs'
        graph/table arguments (`BucketPrograms.rebind` — same shapes, no
        recompile), and invalidate exactly the embedding-cache entries
        whose k-hop closure touched a delta row (the versioned-node-stamp
        rule; ``invalidate=`` overrides with a precomputed set — the dist
        router passes the fleet-global closure). After the fence, when
        the engine has an adaptive tier store + workload telemetry and
        ``stream_adapt_tiers`` is on, one `adapt_tiers` pass runs so a
        delta-hot subgraph pulls its rows off disk NOW (round-17
        consumer (c)).

        ``delta=None`` commits (and clears) ``pending_delta``. An empty
        commit is a strict no-op — no fence, no version bump, no bit
        moves: frozen-graph replay == delta-replay with an empty delta,
        pinned in tests/test_stream.py. The appended edges are visible to
        the next sample after this returns (copy-all semantics: a draw
        with fanout >= degree must include them).

        Round 21 — the same fenced commit also carries the LIFECYCLE
        flows: staged removals rewrite their nodes' lanes in place
        (delete-then-replay == built-without-the-edge, bit for bit),
        staged ts updates overwrite payload lanes, TTL retention (when
        ``stream_retention_window`` > 0 on a temporal stream) expires
        every edge older than the commit clock minus the window as
        masked ``ts -> +inf`` lane writes, and a `StreamCapacityError`
        triggers one reactive bank grow + sealed-program rebuild when
        ``stream_provision_tiles`` > 0. All under ONE fence, one version
        bump, one closure-exact invalidation pass.

        Round 24 — with ``fenced_commits=False`` (the default) the same
        commit is ZERO-STALL: the post-commit device arrays build fully
        off-fence (``stream.apply(defer_publish=True)``), then flip under
        ``_seq`` only — no in-flight drain. Flushes already in flight
        complete against the immutable old arrays their seal pinned
        (epoch pinning); the fence's three consumers go version-aware
        (cache graph-version floors via `EmbeddingCache.raise_floor`,
        post-flip replica retire in the router, post-flip adapt_tiers).
        The visibility contract is unchanged: the delta is visible to
        every flush sealed after this returns; a flush racing the commit
        legitimately serves whichever epoch its seal landed in, and logs
        it in ``dispatch_graph_versions``. Re-provisioning (a shape
        change) always takes the full fenced path — a sealed executable
        rebuild cannot overlap an in-flight flush bound to the old
        shapes."""
        stream = getattr(self._sampler, "stream", None)
        if stream is None:
            raise ValueError(
                "update_graph needs a stream-bound sampler — build a "
                "stream.StreamingTiledGraph over the topology and call "
                "sampler.bind_stream(stream) before constructing the "
                "engine"
            )
        from_pending = delta is None
        with self._lock:
            if delta is None:
                delta, self.pending_delta = self.pending_delta, None
        n_edges = 0 if delta is None else len(delta)
        if n_edges == 0 and not installs:
            return {"edges": 0, "installs": 0, "cache_invalidated": 0,
                    "affected_seeds": 0, "graph_version": self.graph_version}
        if self.config.fenced_commits:
            return self._update_graph_fenced(stream, delta, installs,
                                             invalidate, n_edges,
                                             from_pending)
        return self._update_graph_zerostall(stream, delta, installs,
                                            invalidate, n_edges,
                                            from_pending)

    def _update_graph_fenced(self, stream, delta, installs, invalidate,
                             n_edges, from_pending) -> Dict[str, object]:
        """The round-17..23 drain-ordered commit, bit-identical — the
        ``fenced_commits=True`` parity twin (and the fallback every
        re-provisioning commit takes in either mode)."""
        from ..stream import StreamCapacityError

        applied = False
        provisioned = False
        expired = None
        try:
            with self._seq:
                t_stall0 = self._clock()
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    # graph deltas change the expected closure: staged
                    # prefetch rows keep valid bytes but stale intent —
                    # drop them with the other fence consumers
                    self._cancel_prefetch()
                    try:
                        summary = stream.apply(delta, installs=installs)
                    except StreamCapacityError:
                        if self.config.stream_provision_tiles <= 0:
                            raise
                        # reactive re-provisioning (round 21): grow the
                        # bank by one configured increment and retry the
                        # SAME batch once — one sealed-program rebuild
                        # below, never recompile-per-commit. A second
                        # failure propagates (the batch outgrows even the
                        # grown bank; the caller sizes the increment).
                        stream.provision_reserve(
                            self.config.stream_provision_tiles
                        )
                        provisioned = True
                        summary = stream.apply(delta, installs=installs)
                    applied = True
                    self.graph_version += 1
                    # TTL retention (round 21): expire at the commit
                    # clock, under the SAME fence as the delta it rides —
                    # the cutoff is a pure f32 function of committed
                    # timestamps (lifecycle.RetentionPolicy), so replicas
                    # fed the same commit stream expire identical lanes
                    if (self.retention is not None
                            and getattr(stream, "temporal", False)):
                        cut = self.retention.cutoff_for(delta.max_ts())
                        if cut is not None:
                            exp = stream.expire_edges(cut)
                            self.retention.mark_expired(cut)
                            if exp["edges_expired"]:
                                expired = exp
                                self.stats.edges_expired += (
                                    exp["edges_expired"]
                                )
                            summary["edges_expired"] = exp["edges_expired"]
                            summary["retention_cutoff"] = cut
                    if self._programs is not None:
                        # sealed executables take the graph/table as
                        # ARGUMENTS: swap same-shaped arrays, never
                        # recompile. The table is re-read only for
                        # features with a dynamic jit spec
                        # (ClosureFeature installs); a plain table never
                        # changes under a topology delta.
                        table = imap = None
                        if hasattr(self._feature, "jit_gather_spec"):
                            from ..inference import feature_gather_spec

                            table, imap = feature_gather_spec(self._feature)
                        if provisioned:
                            # shapes changed at the provision event: the
                            # one sanctioned rebuild (reprovision swaps
                            # the spec's graph avals and recompiles the
                            # warmed buckets through the process cache).
                            # _params is read bare: the fence Condition
                            # wraps _lock, so it is already held here
                            self._programs.reprovision(
                                self._sampler.fused_graph_arrays(),
                                params=self._params,
                            )
                            if table is not None:
                                self._programs.rebind(table=table,
                                                      index_map=imap)
                        else:
                            self._programs.rebind(
                                graph=self._sampler.fused_graph_arrays(),
                                table=table, index_map=imap,
                            )
                    # invalidation seeds: every staged source (appends +
                    # removals + updates via delta.sources()) UNION the
                    # retention-expired sources — expiry changed those
                    # rows' draws under this same fence, so their reverse
                    # closure is stale too
                    if invalidate is not None:
                        affected = np.asarray(list(invalidate), np.int64)
                        if expired is not None:
                            hops = self.config.stream_invalidate_hops
                            if hops is None:
                                hops = max(len(self._sampler.sizes) - 1, 0)
                            affected = np.union1d(
                                affected,
                                stream.affected_seeds(expired["sources"],
                                                      hops),
                            )
                    else:
                        srcs = (np.asarray(delta.sources(), np.int64)
                                if n_edges else np.array([], np.int64))
                        if expired is not None:
                            srcs = np.union1d(srcs, expired["sources"])
                        if srcs.size:
                            hops = self.config.stream_invalidate_hops
                            if hops is None:
                                hops = max(len(self._sampler.sizes) - 1, 0)
                            affected = stream.affected_seeds(srcs, hops)
                        else:
                            affected = np.array([], np.int64)
                    # invalidate by NODE, not exact key: temporal cache
                    # entries are (node, t)-keyed, and a changed row
                    # staleness-taints every cached t of an affected seed
                    # (for plain int keys this is behavior-identical to
                    # the round-17 invalidate_keys)
                    invalidated = self.cache.invalidate_nodes(
                        int(x) for x in affected
                    )
                    self.stats.graph_deltas += 1
                    self.stats.delta_edges += n_edges
                    self.stats.delta_tile_writes += summary["pad_writes"]
                    self.stats.delta_tile_spills += summary["tile_spills"]
                    self.stats.delta_cache_invalidated += invalidated
                    self.stats.edges_deleted += summary.get(
                        "edges_deleted", 0
                    )
                    # µs, observe-only: the whole drain + fenced work is
                    # serving stall in this mode (nothing seals under it)
                    t_now = self._clock()
                    stall_us = (t_now - t_stall0) * 1e6
                    self.stats.commit_stall.record_ms(stall_us)
                    self._commit_samples.append(
                        ("graph_version", t_now, self.graph_version))
                    self._commit_samples.append(
                        ("commit_stall_us", t_now, stall_us))
        except BaseException:
            # `stream.apply` is atomic (preflight before any mutation),
            # so a commit that raised BEFORE apply returned left the
            # graph untouched — re-stage a pending-sourced delta so the
            # staged edges survive the failure (ahead of anything staged
            # meanwhile: arrival order is the replay order). A failure
            # AFTER apply (e.g. an interrupt mid-invalidation) must NOT
            # re-stage: the edges are committed, and replaying them
            # would double-append
            if from_pending and n_edges and not applied:
                with self._lock:
                    if self.pending_delta is not None:
                        delta.extend(self.pending_delta)
                    self.pending_delta = delta
            raise
        self.journal.emit("delta_commit", -1, self.graph_version,
                          n_edges, invalidated)
        if summary.get("edges_deleted"):
            self.journal.emit("edge_delete", -1, self.graph_version,
                              summary["edges_deleted"])
        if expired is not None:
            self.journal.emit("retention_expire", -1, self.graph_version,
                              expired["edges_expired"], expired["nodes"])
        summary["cache_invalidated"] = invalidated
        summary["provisioned"] = provisioned
        summary["affected_seeds"] = int(affected.size)
        summary["graph_version"] = self.graph_version
        if (self.config.stream_adapt_tiers
                and self._tier_feature is not None
                and self.workload is not None):
            # consumer (c): re-place tiers at the commit (adapt_tiers
            # takes its own fence; a failing pass is counted, never fatal
            # — the tier-daemon contract)
            try:
                summary["tier_adapt"] = self.adapt_tiers()
            except Exception:
                self.tier_adapt_errors += 1
        return summary

    def _update_graph_zerostall(self, stream, delta, installs, invalidate,
                                n_edges, from_pending) -> Dict[str, object]:
        """Round-24 tentpole: build everything off-fence, flip under
        ``_seq`` only. Phases:

        1. BUILD (commit lock, no fence): ``stream.apply(...,
           defer_publish=True)`` mutates host mirrors and stages the
           post-commit device arrays without touching what `graph()`
           serves; retention expiry stages into the same flip; the
           affected-closure set is computed from the updated host
           adjacency. Traffic seals and dispatches throughout.
        2. FLIP (``_seq`` only — the measured stall): `stream.publish()`
           (an O(1) ref swap), the ``graph_version`` bump, `rebind` of
           the sealed programs' graph arguments, prefetch-intent drop.
           A flush sealing before the flip pinned the old binding and
           stamped the old version; one sealing after gets the new —
           never a mix (the stamp, the binding snapshot and the index draw
           share one ``_seq`` hold in `_seal_assembled`).
        3. POST-FLIP (no fence): the closure-touched nodes' cache
           graph-version floors rise (`EmbeddingCache.raise_floor` —
           eager drop of resident old-epoch entries plus the writeback
           gate that stops an old-epoch in-flight flush from
           re-inserting a stale row after it resolves), stats/journal,
           and the deferred adapt_tiers pass.

        In-flight correctness is the round-11 jit-argument rule: sealed
        executables take the graph as ARGUMENTS and the stream's device
        sync copies on write (`_scatter_rows`), so the old array objects
        a flush pinned are immutable — it completes bit-exactly against
        its epoch, and `replay_fleet_oracle(graph_version=...)` proves
        it row by row. A `StreamCapacityError` (shape change needed)
        falls back to the FULL fenced commit: reprovisioning swaps the
        executables' graph avals, which an in-flight flush bound to the
        old shapes must not straddle."""
        from ..stream import StreamCapacityError

        applied = False
        expired = None
        try:
            with self._commit_lock:
                try:
                    summary = stream.apply(delta, installs=installs,
                                           defer_publish=True)
                except StreamCapacityError:
                    # atomic apply: nothing moved — re-run the whole
                    # commit fenced (it provisions + retries when
                    # configured, or re-raises the capacity error)
                    return self._update_graph_fenced(
                        stream, delta, installs, invalidate, n_edges,
                        from_pending,
                    )
                applied = True
                new_version = self.graph_version + 1
                if (self.retention is not None
                        and getattr(stream, "temporal", False)):
                    cut = self.retention.cutoff_for(delta.max_ts())
                    if cut is not None:
                        exp = stream.expire_edges(cut, defer_publish=True)
                        self.retention.mark_expired(cut)
                        if exp["edges_expired"]:
                            expired = exp
                        summary["edges_expired"] = exp["edges_expired"]
                        summary["retention_cutoff"] = cut
                # invalidation closure, off-fence: the host adjacency is
                # already post-commit (only the device publish defers),
                # so this is the same set the fenced twin computes
                if invalidate is not None:
                    affected = np.asarray(list(invalidate), np.int64)
                    if expired is not None:
                        hops = self.config.stream_invalidate_hops
                        if hops is None:
                            hops = max(len(self._sampler.sizes) - 1, 0)
                        affected = np.union1d(
                            affected,
                            stream.affected_seeds(expired["sources"],
                                                  hops),
                        )
                else:
                    srcs = (np.asarray(delta.sources(), np.int64)
                            if n_edges else np.array([], np.int64))
                    if expired is not None:
                        srcs = np.union1d(srcs, expired["sources"])
                    if srcs.size:
                        hops = self.config.stream_invalidate_hops
                        if hops is None:
                            hops = max(len(self._sampler.sizes) - 1, 0)
                        affected = stream.affected_seeds(srcs, hops)
                    else:
                        affected = np.array([], np.int64)
                table = imap = None
                if (self._programs is not None
                        and hasattr(self._feature, "jit_gather_spec")):
                    from ..inference import feature_gather_spec

                    table, imap = feature_gather_spec(self._feature)
                # ---- the flip: the only serving-visible moment
                with self._seq:
                    t_stall0 = self._clock()
                    stream.publish()
                    self.graph_version = new_version
                    if self._programs is not None:
                        self._programs.rebind(
                            graph=self._sampler.fused_graph_arrays(),
                            table=table, index_map=imap,
                        )
                    self._cancel_prefetch()
                    stall_us = (self._clock() - t_stall0) * 1e6
                # ---- post-flip deferred passes
                invalidated = self.cache.raise_floor(
                    (int(x) for x in affected), new_version
                )
                with self._lock:
                    if expired is not None:
                        self.stats.edges_expired += (
                            expired["edges_expired"]
                        )
                    self.stats.graph_deltas += 1
                    self.stats.delta_edges += n_edges
                    self.stats.delta_tile_writes += summary["pad_writes"]
                    self.stats.delta_tile_spills += summary["tile_spills"]
                    self.stats.delta_cache_invalidated += invalidated
                    self.stats.edges_deleted += summary.get(
                        "edges_deleted", 0
                    )
                    self.stats.commit_stall.record_ms(stall_us)
                    t_now = self._clock()
                    self._commit_samples.append(
                        ("graph_version", t_now, new_version))
                    self._commit_samples.append(
                        ("commit_stall_us", t_now, stall_us))
        except BaseException:
            # same re-stage rule as the fenced twin: apply is atomic, so
            # a pre-apply failure leaves the staged edges recoverable
            if from_pending and n_edges and not applied:
                with self._lock:
                    if self.pending_delta is not None:
                        delta.extend(self.pending_delta)
                    self.pending_delta = delta
            raise
        self.journal.emit("delta_commit", -1, self.graph_version,
                          n_edges, invalidated)
        if summary.get("edges_deleted"):
            self.journal.emit("edge_delete", -1, self.graph_version,
                              summary["edges_deleted"])
        if expired is not None:
            self.journal.emit("retention_expire", -1, self.graph_version,
                              expired["edges_expired"], expired["nodes"])
        summary["cache_invalidated"] = invalidated
        summary["provisioned"] = False
        summary["affected_seeds"] = int(affected.size)
        summary["graph_version"] = self.graph_version
        summary["commit_stall_us"] = stall_us
        if (self.config.stream_adapt_tiers
                and self._tier_feature is not None
                and self.workload is not None):
            # consumer (c), now an explicitly post-flip deferred pass
            try:
                summary["tier_adapt"] = self.adapt_tiers()
            except Exception:
                self.tier_adapt_errors += 1
        return summary

    # -- graph lifecycle (round 21; quiver_tpu.lifecycle) ------------------

    def expire_edges(self, t_commit=None) -> Dict[str, object]:
        """Run TTL retention NOW, off the commit path: advance the
        retention clock to ``t_commit`` (None keeps the clock where the
        last commit left it) and expire every edge older than
        ``clock - window`` behind the `update_params` fence — masked
        ``ts -> +inf`` lane writes, one version bump, closure-exact
        invalidation of the expired rows' reverse k-hop closure. The
        commit path runs this automatically; this entry point is for
        wall-clock-driven expiry between commits (e.g. a quiet stream
        whose window keeps sliding). Returns the stream's expiry summary
        plus ``cache_invalidated``/``graph_version``."""
        stream = getattr(self._sampler, "stream", None)
        if stream is None or not getattr(stream, "temporal", False):
            raise ValueError(
                "retention expiry needs a temporal stream-bound sampler "
                "(StreamingTiledGraph(edge_ts=...) + bind_stream)"
            )
        if self.retention is None:
            raise ValueError(
                "retention is off — set "
                "ServeConfig(stream_retention_window=W)"
            )
        cut = self.retention.cutoff_for(t_commit)
        if cut is None:
            return {"edges_expired": 0, "nodes": 0,
                    "cache_invalidated": 0,
                    "graph_version": self.graph_version}
        if self.config.fenced_commits:
            with self._seq:
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    self._cancel_prefetch()
                    exp = stream.expire_edges(cut)
                    self.retention.mark_expired(cut)
                    invalidated = 0
                    if exp["edges_expired"]:
                        self.graph_version += 1
                        if self._programs is not None:
                            self._programs.rebind(
                                graph=self._sampler.fused_graph_arrays()
                            )
                        hops = self.config.stream_invalidate_hops
                        if hops is None:
                            hops = max(len(self._sampler.sizes) - 1, 0)
                        affected = stream.affected_seeds(exp["sources"],
                                                         hops)
                        invalidated = self.cache.invalidate_nodes(
                            int(x) for x in affected
                        )
                        self.stats.edges_expired += exp["edges_expired"]
                        self.stats.delta_cache_invalidated += invalidated
        else:
            # zero-stall retention (round 24): stage the masked lane
            # writes off-fence, flip + rebind under _seq only, raise the
            # expired closure's cache floors post-flip
            with self._commit_lock:
                exp = stream.expire_edges(cut, defer_publish=True)
                self.retention.mark_expired(cut)
                invalidated = 0
                if exp["edges_expired"]:
                    new_version = self.graph_version + 1
                    hops = self.config.stream_invalidate_hops
                    if hops is None:
                        hops = max(len(self._sampler.sizes) - 1, 0)
                    affected = stream.affected_seeds(exp["sources"], hops)
                    with self._seq:
                        t_stall0 = self._clock()
                        stream.publish()
                        self.graph_version = new_version
                        if self._programs is not None:
                            self._programs.rebind(
                                graph=self._sampler.fused_graph_arrays()
                            )
                        self._cancel_prefetch()
                        stall_us = (self._clock() - t_stall0) * 1e6
                    invalidated = self.cache.raise_floor(
                        (int(x) for x in affected), new_version
                    )
                    with self._lock:
                        self.stats.edges_expired += exp["edges_expired"]
                        self.stats.delta_cache_invalidated += invalidated
                        self.stats.commit_stall.record_ms(stall_us)
        if exp["edges_expired"]:
            self.journal.emit("retention_expire", -1, self.graph_version,
                              exp["edges_expired"], exp["nodes"])
        exp["cache_invalidated"] = invalidated
        exp["graph_version"] = self.graph_version
        exp["retention_cutoff"] = cut
        return exp

    def compact_graph(self, max_moves=None) -> Dict[str, object]:
        """One background compaction pass, LSM-style: PLAN off-fence
        (reads under the stream lock only — live traffic keeps flowing),
        then flip under the `update_params` fence like an r16 migration
        (`plan_compaction` stamped the plan with version/node_version, so
        `apply_compaction` skips anything a racing commit moved first).
        Strictly observe-only on served bits: row reclaims and base-
        indirection moves never change a draw, so there is NO version
        bump and NO cache invalidation — pinned (logits + dispatch logs
        identical with compaction racing an in-flight flush) in
        tests/test_lifecycle.py. Returns the apply summary."""
        stream = getattr(self._sampler, "stream", None)
        if stream is None:
            raise ValueError(
                "compaction needs a stream-bound sampler"
            )
        if max_moves is None:
            max_moves = self.config.stream_compact_max_moves
        plan = stream.plan_compaction(max_moves=max_moves)
        self.journal.emit("compact_begin", -1, self.graph_version,
                          len(plan["retired"]) + len(plan["trims"]),
                          len(plan["moves"]))
        if self.config.fenced_commits:
            with self._seq:
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    # staged prefetch intent survives a compaction (bytes
                    # and closures are untouched) — no _cancel_prefetch
                    summary = stream.apply_compaction(plan)
                    self.stats.tiles_reclaimed += (
                        summary["tiles_reclaimed"]
                    )
                    self.stats.compactions += 1
        else:
            # zero-stall (round 24): stage the relocated rows off-fence,
            # flip under _seq. Compaction is observe-only on bits (no
            # version bump), so there is nothing to invalidate and no
            # rebind of contents beyond the array refs themselves.
            with self._commit_lock:
                summary = stream.apply_compaction(plan,
                                                  defer_publish=True)
                with self._seq:
                    t_stall0 = self._clock()
                    stream.publish()
                    if self._programs is not None:
                        self._programs.rebind(
                            graph=self._sampler.fused_graph_arrays()
                        )
                    stall_us = (self._clock() - t_stall0) * 1e6
                with self._lock:
                    self.stats.tiles_reclaimed += (
                        summary["tiles_reclaimed"]
                    )
                    self.stats.compactions += 1
                    self.stats.commit_stall.record_ms(stall_us)
        self.journal.emit("compact_commit", -1, self.graph_version,
                          summary["tiles_reclaimed"], summary["moves"])
        summary["graph_version"] = self.graph_version
        return summary

    def provision_reserve(self, tiles=None) -> Dict[str, object]:
        """Grow the tile bank by ``tiles`` whole rows (default: the
        ``stream_provision_tiles`` knob) behind the fence, then pay the
        ONE sanctioned sealed-program rebuild
        (`inference.BucketPrograms.reprovision`) — shapes change at
        provision events only; the per-commit path still never
        recompiles. Served bits are untouched (fresh rows are free
        rows). Returns the post-grow reserve report."""
        stream = getattr(self._sampler, "stream", None)
        if stream is None:
            raise ValueError(
                "provisioning needs a stream-bound sampler"
            )
        if tiles is None:
            tiles = self.config.stream_provision_tiles
        if int(tiles) <= 0:
            raise ValueError(
                f"provision_reserve needs a positive tile count, got "
                f"{tiles} (set ServeConfig(stream_provision_tiles=...) "
                "or pass tiles=)"
            )
        with self._seq:
            with self._fence:
                while self._inflight_flushes:
                    self._fence.wait()
                self._cancel_prefetch()
                report = stream.provision_reserve(int(tiles))
                if self._programs is not None:
                    # the fence Condition wraps _lock (already held)
                    self._programs.reprovision(
                        self._sampler.fused_graph_arrays(),
                        params=self._params,
                    )
        return report

    def _compact_loop(self) -> None:
        """The background compaction daemon body: on a
        ``stream_compact_every_s`` timer, read the reserve report (no
        fence) and run `compact_graph` when `lifecycle.CompactionPolicy`
        says the reclaimable mass crossed ``stream_compact_min_reclaim``.
        A failing pass is counted in ``tier_adapt_errors``' sibling
        pattern — never fatal to serving."""
        from ..lifecycle import CompactionPolicy

        policy = CompactionPolicy(
            min_reclaimable=self.config.stream_compact_min_reclaim,
            max_moves=self.config.stream_compact_max_moves,
        )
        while self._running:
            time.sleep(self.config.stream_compact_every_s)
            if not self._running:
                return
            try:
                stream = getattr(self._sampler, "stream", None)
                if stream is None:
                    continue
                if policy.should_compact(stream.reserve_report()):
                    self.compact_graph()
            except Exception:
                self.compact_errors += 1

    def _retention_loop(self) -> None:
        """The round-23 wall-clock TTL daemon body: on a
        ``stream_retention_every_s`` timer, run one `expire_edges` pass
        — the fenced round-21 entry point, so a daemon pass IS a manual
        expiry call (fenced like update_graph; deterministic given the
        injected clock's readings, which is what the deterministic-clock
        test replays). A failing pass counts in ``retention_errors`` —
        never fatal to serving (the `_compact_loop` discipline)."""
        while self._running:
            time.sleep(self.config.stream_retention_every_s)
            if not self._running:
                return
            try:
                self._retention_pass()
            except Exception:
                self.retention_errors += 1

    def _retention_pass(self) -> Dict[str, object]:
        """One daemon pass, callable directly (tests drive it with a
        deterministic clock instead of sleeping): advance event time to
        ``stream_retention_clock()`` when a clock is configured (None =
        re-check the commit-driven retention clock's standing cutoff)
        and expire behind the fence."""
        clk = self.config.stream_retention_clock
        exp = self.expire_edges(
            t_commit=clk() if clk is not None else None
        )
        self.retention_passes += 1
        return exp

    # -- adaptive tier placement (round 14) --------------------------------

    def apply_placement(self, plan) -> Dict[str, object]:
        """Move rows between disk <-> DRAM <-> HBM behind the SAME fence
        as `update_params`: block new assembles (the sequencing lock),
        drain every in-flight flush, apply the batch, bump
        ``placement_version``, and invalidate the moved rows' embedding-
        cache entries. No flush ever straddles a placement batch, so a
        frozen placement replays bit-identically — and because every
        row's bytes live on the disk backing permanently, the move
        itself changes no gathered byte (the bit-parity pin in
        tests/test_tiers.py). Returns the `TierStore.apply` summary."""
        feat = self._tier_feature
        if feat is None:
            raise ValueError(
                "no adaptive tier store under this engine's feature "
                "(build it with Feature(disk_path=..., adaptive_tiers=True))"
            )
        with self._seq:
            with self._fence:
                while self._inflight_flushes:
                    self._fence.wait()
                # TierStore.apply cancels the staged rows itself, but the
                # ENGINE's submit-walk memo must clear with them: after a
                # placement batch "already walked" no longer implies
                # "already staged", and a stale memo would quietly skip
                # re-staging at the next assemble (hit-rate loss, not a
                # bit error)
                self._cancel_prefetch()
                summary = feat.tier_store.apply(plan)
                self.placement_version += 1
                self.stats.tier_promoted += summary["promoted_rows"]
                self.stats.tier_demoted += summary["demoted_rows"]
                self.stats.placement_batches += 1
                moved = summary["moved_stored"]
                if moved.size:
                    nodes = feat.node_ids_of_stored(moved)
                    summary["cache_invalidated"] = self.cache.invalidate_keys(
                        int(x) for x in nodes[nodes >= 0]
                    )
                else:
                    summary["cache_invalidated"] = 0
        return summary

    def adapt_tiers(self, max_moves: Optional[int] = None) -> Dict[str, object]:
        """ONE sketch-driven promote/demote pass: read the live frequency
        sketch (`WorkloadMonitor.promotion_candidates`, err-corrected),
        map the hot head into stored-row space, price current residents
        against the Count-Min estimate, plan a bounded batch
        (`tiers.plan_adaptive` — hysteresis keeps near-ties from
        ping-ponging), and apply it behind the placement fence. Safe to
        call any time; a no-move plan skips the fence entirely. This is
        the consumer ROADMAP item 2 names — `start()` runs it on a timer
        when ``tier_adapt_every_s`` > 0, tests call it synchronously."""
        from ..tiers import plan_adaptive

        feat = self._tier_feature
        if feat is None:
            raise ValueError(
                "no adaptive tier store under this engine's feature"
            )
        if self.workload is None:
            raise ValueError(
                "tier adaptation reads the frequency sketch — pass "
                "ServeConfig(workload=WorkloadConfig(...))"
            )
        wl = self.workload
        store = feat.tier_store
        empty = {"moves": 0, "promoted_rows": 0, "demoted_rows": 0,
                 "version": store.placement_version,
                 "counts": store.placement.counts()}
        if wl.row_sketch is not None:
            # preferred input: the ROW sketch measures what the tiers
            # actually serve (seeds + sampled neighbors), already keyed
            # by stored row
            cand = wl.row_promotion_candidates(
                min_weight=self.config.tier_promote_min
            )
            if not cand:
                return empty
            stored = np.asarray([k for k, _ in cand], np.int64)
            weights = np.asarray([w for _, w in cand], np.float64)
            ok = (stored >= 0) & (stored < store.n_rows)
            rcms = wl.row_cms

            def resident_weight(stored_ids: np.ndarray) -> np.ndarray:
                return np.asarray(
                    [rcms.estimate(int(s)) for s in stored_ids], np.float64
                )
        else:
            # fallback: the seed sketch (what clients ASK), mapped into
            # stored-row space — blind to neighbor gathers, so prefer
            # row_topk when tier adaptation is the point
            cand = wl.promotion_candidates(
                min_weight=self.config.tier_promote_min
            )
            if not cand:
                return empty
            nodes = np.asarray([k for k, _ in cand], np.int64)
            weights = np.asarray([w for _, w in cand], np.float64)
            stored = feat.stored_rows_of(nodes)
            ok = stored >= 0  # unowned/out-of-range keys (dist shards)
            cms = wl.cms

            def resident_weight(stored_ids: np.ndarray) -> np.ndarray:
                res_nodes = feat.node_ids_of_stored(stored_ids)
                return np.asarray(
                    [cms.estimate(int(x)) if x >= 0 else 0.0
                     for x in res_nodes],
                    np.float64,
                )

        plan = plan_adaptive(
            store.placement, stored[ok], weights[ok],
            resident_weight=resident_weight,
            max_moves=max_moves or self.config.tier_promote_batch,
            min_weight=self.config.tier_promote_min,
            hysteresis=self.config.tier_hysteresis,
        )
        if not len(plan):
            return {"moves": 0, "promoted_rows": 0, "demoted_rows": 0,
                    "version": store.placement_version,
                    "counts": store.placement.counts()}
        return self.apply_placement(plan)

    def _tier_loop(self) -> None:
        from ..tiers import tier_daemon_loop

        tier_daemon_loop(self)

    # -- background flushers ----------------------------------------------

    def start(self) -> "ServeEngine":
        """Start ``max_in_flight`` poller threads, each applying the flush
        policy on a timer. With a window > 1 the pollers (plus inline
        submit flushes) are what actually overlap assemble with device
        execution for single-threaded clients."""
        if self._running:
            return self
        self._running = True
        self._threads = [
            threading.Thread(
                target=self._poll_loop,
                name=f"quiver-serve-flusher-{i}",
                daemon=True,
            )
            for i in range(self.config.max_in_flight)
        ]
        if (
            self.config.tier_adapt_every_s > 0
            and self._tier_feature is not None
            and self.workload is not None
        ):
            # the round-14 promote/demote consumer: reads the sketch on a
            # timer, applies bounded fenced batches (see adapt_tiers)
            self._threads.append(
                threading.Thread(
                    target=self._tier_loop,
                    name="quiver-serve-tiers",
                    daemon=True,
                )
            )
        if (
            self.config.stream_compact_every_s > 0
            and getattr(self._sampler, "stream", None) is not None
        ):
            # the round-21 background compactor: plans off-fence, flips
            # under the fence, observe-only on bits (see compact_graph)
            self._threads.append(
                threading.Thread(
                    target=self._compact_loop,
                    name="quiver-serve-compactor",
                    daemon=True,
                )
            )
        if (
            self.config.stream_retention_every_s > 0
            and self.retention is not None
            and getattr(self._sampler, "stream", None) is not None
            and getattr(self._sampler.stream, "temporal", False)
        ):
            # the round-23 wall-clock TTL daemon: keeps a QUIET temporal
            # stream's sliding window expiring between commits (see
            # _retention_loop); fenced like update_graph, off by default
            self._threads.append(
                threading.Thread(
                    target=self._retention_loop,
                    name="quiver-serve-retention",
                    daemon=True,
                )
            )
        for t in self._threads:
            t.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the background threads and retire queued work, BOUNDED by
        ``config.drain_deadline_s``: a poller or owner thread that died
        mid-flush must not hang the caller forever. Work not retired by
        the deadline resolves with `DrainTimeout` (waiters unblock, never
        hang) and is counted in ``stats.undrained`` — visible in the
        stats snapshot, never silently dropped."""
        self._running = False
        # the WHOLE stop — poller joins included — shares one deadline: a
        # poller wedged mid-flush (owner blocked in predict) would defeat
        # the bound if joined without a timeout
        deadline = self._clock() + self.config.drain_deadline_s
        for t in self._threads:
            t.join(timeout=max(deadline - self._clock(), 0.05))
        self._threads = []
        if drain:
            while self._drainable() and self._clock() < deadline:
                try:
                    self.flush()
                except Exception:
                    pass  # the failing flush resolved its own waiters
        # even without drain, leave no flush mid-air: callers expect stats
        # and handles quiescent after stop()
        with self._fence:
            while self._inflight_flushes and self._clock() < deadline:
                self._fence.wait(timeout=0.05)
        # staged prefetch rows outlive their flushes at stop: cancel so
        # the pool's futures are observed (no GC log spam) and the waste
        # ledger closes — pinned leak-free in tests/test_prefetch.py
        self._cancel_prefetch()
        abandon_undrained(self, drained=drain)

    def _poll_loop(self) -> None:
        while self._running:
            try:
                self.pump()
            except Exception:
                # the failing flush already resolved its waiters with the
                # error; keep serving subsequent requests
                pass
            time.sleep(self.config.flush_poll_ms / 1e3)

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
