"""Tracing, timing and metrics.

Re-design of the reference's observability surface (SURVEY.md section 5):

- RAII scope timer (include/quiver/timer.hpp:7-28) -> :class:`timer`;
- compile-time TRACE_SCOPE macros gated by QUIVER_ENABLE_TRACE
  (include/quiver/trace.hpp:6-14, setup.py:45-46) -> :class:`trace_scope`,
  the library's one span primitive: on when the same env var is set or a
  `jax.profiler` session is recording, each span written to the profiler
  (on the device lines' clock) and aggregated in a process-local registry
  (:func:`trace_report`); :func:`observe` adds durations a caller computed
  from stamps it already held;
- ad-hoc benchmark metrics (SEPS, benchmarks/sample/bench_sampler.py:14-16;
  GB/s, benchmarks/feature/bench_feature.py:44-46) -> :func:`seps` /
  :func:`gbps` helpers so every bench reports identically.
"""

from __future__ import annotations

import bisect
import math
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

TRACE_ENV = "QUIVER_ENABLE_TRACE"

# The jitted callables whose XLA module names (``jit_<__name__>``) outside
# readers match by pattern (qbench/metrics/*.json: ``padded_gather``,
# ``train_step``, ...). A rename at the site renames the program in every
# device trace and silences those readers without an error, so the names
# are held at both ends by tests/qbench/test_qbench_program_names.py.
PROGRAM_NAMES = (
    "tiled_sample_layer",       # ops/sample.py
    "local_reindex",            # ops/reindex.py
    "_padded_gather",           # feature.py
    "_padded_gather_ordered",   # feature.py
    "serve_step",               # inference.make_serve_step
)

# Whole-step programs of `parallel/`: sampling, the row exchange, the model
# and the optimizer in ONE program a step. Named so that the benchmark's
# patterns for "the step" ("train_step") match them and its patterns for
# the sampler's and the gather's own programs do not. Held against the
# jitted callable by tests/qbench/test_qbench_sharded_manifest.py.
STEP_PROGRAM_NAMES = (
    "sharded_topo_train_step",  # parallel/train.make_sharded_topo_train_step
)

# name -> [count, total seconds, longest seconds]
_registry: Dict[str, list] = {}
# aggregation is a read-modify-write on _registry[name]; serve pollers and
# client threads trace concurrently, so an unlocked update loses counts
# (two threads read the same entry and one increment vanishes). One
# process-wide lock covers the update AND the trace_report(reset=True)
# snapshot-then-clear, which would otherwise drop scopes landing between
# the dict copy and the clear.
_registry_lock = threading.Lock()


# The gate runs once per request on the serve path, where the interpreter
# lock is the saturated resource: a microsecond a request read as 3% of the
# median latency on the chip (PERF.md, PR 26). `os.environ.get` of a name
# that is NOT set costs that microsecond (it raises and catches KeyError
# twice inside); the mapping behind it answers in 40 ns, and is what
# `os.environ[...] = ...` and `del` (so `monkeypatch.setenv`) write.
_ENV_DATA = os.environ._data
_ENV_KEY = os.environ.encodekey(TRACE_ENV)
_ENV_OFF = frozenset(os.environ.encodevalue(v) for v in ("0", "", "false", "False"))


def trace_enabled() -> bool:
    """Whether spans record: a profiler session is open or
    QUIVER_ENABLE_TRACE is set (read on every call)."""
    return TraceAnnotation.is_enabled() or (
        _ENV_DATA.get(_ENV_KEY, b"0") not in _ENV_OFF)


class timer:
    """Scope timer (reference quiver::timer, timer.hpp:7-28).

    >>> with timer("sample") as t: ...
    >>> t.elapsed  # seconds
    """

    def __init__(self, name: str = "", verbose: bool = False):
        self.name = name
        self.verbose = verbose
        self.elapsed = 0.0

    def __enter__(self) -> "timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = time.perf_counter() - self._t0
        if self.verbose:
            print(f"[quiver-tpu] {self.name}: {self.elapsed*1e3:.3f} ms")


def _add(name: str, n: int, total: float, longest: float) -> None:
    with _registry_lock:
        entry = _registry.get(name)
        if entry is None:
            _registry[name] = [n, total, longest]
        else:
            entry[0] += n
            entry[1] += total
            if longest > entry[2]:
                entry[2] = longest


class trace_scope:
    """The span primitive (TRACE_SCOPE analog, trace.hpp:6-14). Off, which
    is unless `trace_enabled`, entering and leaving is that one check: no
    clock is read and nothing is recorded. On, the span is written to the
    profiler as a ``TraceAnnotation(name, **ids)`` (it lands on the host
    plane of the session's ``.xplane.pb``, on the clock of the device
    lines, with ``ids`` as the event's stats) and ``(1, duration)`` is
    added to the registry under ``name``. While `jax.jit` (or any other
    transformation) traces the enclosing function nothing is recorded:
    that would time the tracing, once, and not the work.

    JAX dispatch is asynchronous, so a bare wall clock measures *enqueue*
    time, not device time. Pass the scope's output arrays via ``sync=`` (or
    assign them inside: ``with trace_scope("s") as b: b.sync = out``) and
    the scope calls ``jax.block_until_ready`` before stopping the clock.
    No hot path does: a wait changes what it measures."""

    __slots__ = ("name", "sync", "_ids", "_span", "_t0")

    def __init__(self, name: str, sync=None, **ids):
        self.name = name
        self.sync = sync
        self._ids = ids
        self._span = None

    def __enter__(self) -> "trace_scope":
        if trace_enabled() and jax.core.trace_ctx.is_top_level():
            self._span = TraceAnnotation(self.name, **self._ids)
            self._span.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        span = self._span
        if span is None:
            return
        if self.sync is not None:
            jax.block_until_ready(self.sync)
        dt = time.perf_counter() - self._t0
        span.__exit__(*exc)
        self._span = None
        _add(self.name, 1, dt, dt)


def observe(name: str, seconds) -> None:
    """Add durations the caller computed itself, from stamps it already
    holds, to the registry under ``name``: one number, or an array of them
    (one per request of a flush). Same gate as `trace_scope`; a caller
    whose durations cost something to compute asks `trace_enabled` first."""
    if not trace_enabled():
        return
    arr = np.asarray(seconds, np.float64).reshape(-1)
    if arr.size:
        _add(name, arr.size, float(arr.sum()), float(arr.max()))


def trace_report(reset: bool = False, with_max: bool = False) -> Dict[str, Tuple]:
    """Snapshot of aggregated spans: {name: (count, total_seconds)}, or
    with ``with_max`` {name: (count, total_seconds, longest_seconds)}.
    ``reset=True`` snapshots and clears ATOMICALLY (same lock as the scope
    updates), so no concurrently-finishing scope falls between the copy
    and the clear."""
    with _registry_lock:
        out = {k: tuple(v) if with_max else (v[0], v[1])
               for k, v in _registry.items()}
        if reset:
            _registry.clear()
    return out


# -- benchmark metric helpers -------------------------------------------------

def median_min_max(values) -> Dict[str, float]:
    """``{"median", "min", "max", "n"}`` of a numeric sequence — the
    repeated-run summary probe scripts report. Single-run numbers on a
    noisy shared box flip run to run (NEXT.md operational reminders), so
    the honest headline is the median of N repeats WITH the spread next to
    it; a probe that prints one number is reporting noise. Median of an
    even count is the mean of the two middle values."""
    import statistics

    vals = [float(v) for v in values]
    if not vals:
        raise ValueError("median_min_max needs at least one value")
    return {
        "median": statistics.median(vals),
        "min": min(vals),
        "max": max(vals),
        "n": len(vals),
    }


def seps(sampled_edges: int, seconds: float) -> float:
    """Sampled edges per second (reference bench_sampler.py:14-16)."""
    return sampled_edges / max(seconds, 1e-12)


def dtype_bytes(dtype) -> int:
    """Bytes per element for a dtype spelling ("float32", "bfloat16",
    np.int8, a numpy dtype, ...) — the helper quantized benches use so
    `gbps` reports WIRE bytes, not fp32-equivalent bytes. For a codec,
    pass ``codec.bytes_per_elem`` directly instead (int8 payload = 1)."""
    if str(dtype) in ("bfloat16", "bf16"):
        import jax.numpy as jnp

        return np.dtype(jnp.bfloat16).itemsize
    return np.dtype(dtype).itemsize


def gbps(
    num_rows: int, feature_dim: int, seconds: float, bytes_per_elem: float = 4
) -> float:
    """Feature-collection throughput in GB/s (reference bench_feature.py:44-46).

    ``bytes_per_elem`` must be the TRUE stored/wire width of the gathered
    rows — `dtype_bytes(table.dtype)` for plain tables, the codec's
    ``bytes_per_elem`` for quantized ones (may be fractional for packed
    codecs). The fp32 default exists for reference parity only; a quant
    bench that leaves it at 4 reports fantasy bandwidth."""
    return num_rows * feature_dim * bytes_per_elem / max(seconds, 1e-12) / 1e9


# -- stage-span overlap evidence ----------------------------------------------

def _snapshot_deque(dq) -> tuple:
    """Consistent tuple copy of a deque under concurrent appends:
    iterating a deque being mutated raises RuntimeError, so retry — a
    handful of attempts always wins because each copy is a single C-level
    pass. Shared by `SpanRecorder` and `EventJournal` so the retry
    discipline has exactly one home."""
    for _ in range(64):
        try:
            return tuple(dq)
        except RuntimeError:
            continue
    return ()


class SpanRecorder:
    """Bounded recorder of (stage, t0, t1) monotonic spans + the measured
    concurrency summary — THE falsifiable overlap evidence for any staged
    pipeline here (the tiered `TrainPipeline` and the pipelined
    `ServeEngine` both record into one of these; unlike a seq-minus-pipe
    subtraction against a separately-timed probe, every span shares one
    clock over one run).

    Bounded (deque) so a long-running pipeline doesn't accumulate spans
    forever; the summary then covers the most recent window. Appends are
    thread-safe (deque.append is atomic); `overlap_summary` snapshots the
    deque with ``tuple()`` FIRST — stage threads may still be appending,
    and iterating a deque being mutated raises RuntimeError.

    Iterable/len/bool behave like the underlying span sequence, so callers
    can keep treating it as a list of (stage, t0, t1) triples.
    """

    def __init__(self, maxlen: int = 100_000):
        import collections

        self._spans = collections.deque(maxlen=maxlen)

    def record(self, stage: str, t0: float, t1: float) -> None:
        self._spans.append((stage, t0, t1))

    def _snapshot(self) -> tuple:
        return _snapshot_deque(self._spans)

    def __iter__(self):
        return iter(self._snapshot())

    def __len__(self) -> int:
        return len(self._spans)

    def __bool__(self) -> bool:
        return bool(self._spans)

    def clear(self) -> None:
        self._spans.clear()

    def merge(self, other: "SpanRecorder") -> "SpanRecorder":
        """Append ``other``'s spans into this recorder (cross-shard stats
        aggregation for the distributed serve engine). Meaningful overlap
        summaries require the two recorders to share a clock — shard
        engines driven by one router do (they all read the router's
        process-wide monotonic clock); spans from different PROCESSES only
        merge honestly for per-stage busy totals, not overlap_frac.
        Returns self for chaining."""
        for span in other._snapshot() if isinstance(other, SpanRecorder) else tuple(other):
            self._spans.append(span)
        return self

    def overlap_summary(self) -> dict:
        """Measured concurrency of the recorded spans.

        Returns busy seconds per stage, the union-covered wall, and:

        - ``overlap_frac``: fraction of covered wall during which >= 2
          stages were active — DIRECT evidence the stages overlap;
        - ``hidden_frac_measured``: (sum of busy - covered) / sum of
          busy — the share of total stage work hidden under another
          stage. 0 = fully serial; (S-1)/S = S stages perfectly stacked.
        """
        spans = self._snapshot()  # stages may still be appending
        if not spans:
            return {}
        busy: dict = {}
        events = []
        for stage, t0, t1 in spans:
            busy[stage] = busy.get(stage, 0.0) + (t1 - t0)
            events.append((t0, 1))
            events.append((t1, -1))
        events.sort()
        covered = multi = 0.0
        depth = 0
        prev = events[0][0]
        for t, d in events:
            if depth >= 1:
                covered += t - prev
            if depth >= 2:
                multi += t - prev
            depth += d
            prev = t
        total_busy = sum(busy.values())
        return {
            "busy_s": {k: round(v, 4) for k, v in busy.items()},
            "covered_wall_s": round(covered, 4),
            "overlap_frac": round(multi / covered, 4) if covered else 0.0,
            "hidden_frac_measured": (
                round((total_busy - covered) / total_busy, 4) if total_busy else 0.0
            ),
        }


# -- serving metrics ----------------------------------------------------------


class LatencyHistogram:
    """Log-bucketed latency histogram for the serving path.

    Bounded memory regardless of request count: ``record_ms`` lands each
    sample in one of ~``log(max/min)/log(growth)`` geometric buckets, so the
    serve engine can keep one of these per metric forever without growing
    per-request state. ``percentile`` answers within one bucket's resolution
    (``growth`` = 1.25 -> ~12% worst case), which is the honest precision for
    tail-latency reporting anyway; exact ``min``/``max`` are tracked on the
    side and clamp the answer, so single-sample and extreme queries are
    exact. Thread-safe: the engine's flusher and client threads record
    concurrently.
    """

    def __init__(self, min_ms: float = 1e-3, max_ms: float = 6e4,
                 growth: float = 1.25):
        if not (min_ms > 0 and max_ms > min_ms and growth > 1):
            raise ValueError("need 0 < min_ms < max_ms and growth > 1")
        nb = int(math.ceil(math.log(max_ms / min_ms) / math.log(growth))) + 1
        # bucket i covers (edges[i-1], edges[i]]; bucket 0 is (0, min_ms]
        self._edges = [min_ms * growth ** i for i in range(nb)]
        # float64 copy for the bulk path's one searchsorted (same values,
        # so np side="left" lands every sample in bisect_left's bucket)
        self._edges_arr = np.asarray(self._edges, np.float64)
        self._counts = [0] * (nb + 1)  # +1: overflow bucket above max_ms
        self._lock = threading.Lock()
        self.count = 0
        self.sum_ms = 0.0
        self.min_ms = math.inf
        self.max_ms = 0.0

    def record_ms(self, ms: float) -> None:
        ms = float(ms)
        i = bisect.bisect_left(self._edges, ms)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum_ms += ms
            self.min_ms = min(self.min_ms, ms)
            self.max_ms = max(self.max_ms, ms)

    def record_ms_many(self, ms) -> None:
        """Bulk :meth:`record_ms` (round 22): N samples binned with one
        ``searchsorted`` + one ``bincount`` and folded in under ONE lock
        hold — the vectorized resolve path records a whole flush's waiter
        latencies through here. Bucket counts, ``count``, ``min_ms`` and
        ``max_ms`` are bit-identical to N scalar calls (``side="left"``
        is ``bisect_left``); ``sum_ms`` accumulates as one vector sum,
        so it may differ from the scalar running sum only by float
        reassociation (same samples, last-ulp)."""
        arr = np.asarray(ms, np.float64).reshape(-1)
        n = arr.shape[0]
        if n == 0:
            return
        binned = np.bincount(
            np.searchsorted(self._edges_arr, arr, side="left"),
            minlength=len(self._counts),
        )
        hot = np.flatnonzero(binned)
        total = float(arr.sum())
        lo = float(arr.min())
        hi = float(arr.max())
        with self._lock:
            counts = self._counts
            for i in hot.tolist():
                counts[i] += int(binned[i])
            self.count += n
            self.sum_ms += total
            self.min_ms = min(self.min_ms, lo)
            self.max_ms = max(self.max_ms, hi)

    @property
    def mean_ms(self) -> float:
        return self.sum_ms / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        """p in [0, 100]. Returns the geometric midpoint of the bucket the
        p-th sample falls in, clamped to the observed [min, max]."""
        if not 0 <= p <= 100:
            raise ValueError("percentile wants p in [0, 100]")
        with self._lock:
            if not self.count:
                return 0.0
            rank = max(1, math.ceil(p / 100.0 * self.count))
            acc = 0
            for i, c in enumerate(self._counts):
                acc += c
                if acc >= rank:
                    if i == 0:
                        # underflow bucket (0, min edge]: the exact observed
                        # minimum is the only honest answer down here
                        mid = self.min_ms
                    elif i == len(self._edges):
                        # overflow bucket has no upper edge: report observed max
                        mid = self.max_ms
                    else:
                        mid = math.sqrt(self._edges[i - 1] * self._edges[i])
                    return min(max(mid, self.min_ms), self.max_ms)
            return self.max_ms  # unreachable; guards float drift

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Fold ``other``'s samples into this histogram (multi-shard /
        multi-run aggregation: the distributed serve engine merges per-shard
        latency into one router-level view, and probe scripts merge repeated
        runs). Requires identical bucketization — merging histograms with
        different edges would silently mis-bin ``other``'s counts, so it
        raises instead. Locks both (self first, then other — call sites must
        keep that order consistent to stay deadlock-free; the aggregation
        paths here only ever merge INTO a fresh local histogram). Returns
        self for chaining."""
        if not isinstance(other, LatencyHistogram):
            raise TypeError(f"cannot merge {type(other).__name__}")
        if self._edges != other._edges:
            raise ValueError(
                "LatencyHistogram.merge needs identical bucket edges "
                f"(self: {len(self._edges)} edges [{self._edges[0]:g}, "
                f"{self._edges[-1]:g}], other: {len(other._edges)} edges "
                f"[{other._edges[0]:g}, {other._edges[-1]:g}])"
            )
        with self._lock:
            with other._lock:
                for i, c in enumerate(other._counts):
                    self._counts[i] += c
                self.count += other.count
                self.sum_ms += other.sum_ms
                if other.count:
                    self.min_ms = min(self.min_ms, other.min_ms)
                    self.max_ms = max(self.max_ms, other.max_ms)
        return self

    def snapshot(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "mean_ms": self.mean_ms,
            "p50_ms": self.percentile(50),
            "p95_ms": self.percentile(95),
            "p99_ms": self.percentile(99),
            "min_ms": self.min_ms if self.count else 0.0,
            "max_ms": self.max_ms,
        }


class HitRateCounter:
    """Hit/miss/eviction counters for the serving caches (thread-safe).

    Round 13 adds optional PER-TIER attribution (``hit(n, tier="hbm")``):
    the aggregate fields keep their exact round-8 semantics — every
    existing merge/snapshot consumer is untouched — while ``tiers`` holds
    a per-tier {hits, misses, evictions} breakdown on the side, so cache
    hits vs HBM / ICI-stripe / host-tail / disk gathers are
    distinguishable in snapshots and Prometheus (`register_hit_rate`
    ``tiers=``). A tier-attributed count ALWAYS lands in the aggregate
    too (the tier split is a refinement, never a fork)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # tier -> [hits, misses, evictions]; empty until a tier= call
        self.tiers: Dict[str, List[int]] = {}

    def _tier(self, tier: str) -> List[int]:
        t = self.tiers.get(tier)
        if t is None:
            t = self.tiers[tier] = [0, 0, 0]
        return t

    def hit(self, n: int = 1, tier: Optional[str] = None) -> None:
        with self._lock:
            self.hits += n
            if tier is not None:
                self._tier(tier)[0] += n

    def miss(self, n: int = 1, tier: Optional[str] = None) -> None:
        with self._lock:
            self.misses += n
            if tier is not None:
                self._tier(tier)[1] += n

    def evict(self, n: int = 1, tier: Optional[str] = None) -> None:
        with self._lock:
            self.evictions += n
            if tier is not None:
                self._tier(tier)[2] += n

    def tier_counts(self, tier: str) -> Dict[str, int]:
        with self._lock:
            h, m, e = self.tiers.get(tier, (0, 0, 0))
        return {"hits": h, "misses": m, "evictions": e}

    def reset(self) -> None:
        """Zero every count IN PLACE (holders keep their reference — the
        workload monitor's clear() relies on this, since tiered features
        hold the counter as their tap)."""
        with self._lock:
            self.hits = self.misses = self.evictions = 0
            self.tiers.clear()

    def merge(self, other: "HitRateCounter") -> "HitRateCounter":
        """Fold ``other``'s counts into this counter (cross-shard cache
        stats for the distributed serve engine; multi-run aggregation for
        probes), per-tier breakdowns included. Same lock-order note as
        `LatencyHistogram.merge`. Returns self for chaining."""
        if not isinstance(other, HitRateCounter):
            raise TypeError(f"cannot merge {type(other).__name__}")
        with self._lock:
            with other._lock:
                self.hits += other.hits
                self.misses += other.misses
                self.evictions += other.evictions
                for tier, (h, m, e) in other.tiers.items():
                    t = self._tier(tier)
                    t[0] += h
                    t[1] += m
                    t[2] += e
        return self

    @property
    def total(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        t = self.total
        return self.hits / t if t else 0.0

    def snapshot(self) -> Dict[str, float]:
        out = {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": self.hit_rate,
        }
        with self._lock:
            if self.tiers:
                # only when tier attribution is in use: existing consumers
                # comparing snapshots of untiered counters see the exact
                # round-8 dict
                out["tiers"] = {
                    t: {"hits": v[0], "misses": v[1], "evictions": v[2]}
                    for t, v in sorted(self.tiers.items())
                }
        return out


# -- request-scoped lifecycle journal -----------------------------------------

# One journal event is a fixed-arity tuple (t, kind, rid, fid, a, b):
#   t    : seconds on the journal's monotonic clock (the engine's clock)
#   kind : event name from EVENT_KINDS
#   rid  : request/slot id (-1 when the event is per-flush)
#   fid  : flush id == the engine's dispatch index (-1 when per-request
#          and not yet attached to a flush)
#   a, b : numeric payload (node id, bucket, counts, durations — per kind)
# Fixed arity keeps emit() to one tuple build + one deque append, which is
# what lets the journal stay ON in production serving.
EVENT_KINDS = (
    "submit",        # rid, -, a=node            new pending slot created
    "cache_hit",     # -,   -, a=node            answered from the embedding cache
    "coalesce",      # rid, -, a=node            waiter attached to an existing slot
    "late_admit",    # rid, fid, a=node          rode an assembled flush's pad lane
    "assemble",      # rid, fid, a=node          slot drained into flush fid
    "flush",         # -, fid, a=n_drained, b=bucket   flush assembled (pre-seal)
    "window_wait",   # -, fid, a=wait_seconds    in-flight window permit acquired
    "seal",          # -, fid, a=n_final, b=bucket     admission closed, index drawn
    "dispatch",      # -, fid, a=bucket          device work begins
    "execute_done",  # -, fid, a=execute_calls   device work + D2H returned
    "resolve",       # -, fid, a=n_resolved      slots resolved, stats landed
    # round-15 fleet-policy events (policy markers, not stage boundaries:
    # the per-flush state machine below ignores them)
    "shed",          # -,   -, a=node            refused at tenant admission
    "hedge",         # -, fid, a=owner           sub-batch re-routed to a target
    "eject",         # -, fid, a=owner           owner entered backoff
    # round-23 concurrent owner fan-out (policy marker like the three
    # above — the flush fold ignores it): one event per HOST-MODE
    # dispatch leg at its JOIN, emitted in split order by both the
    # fan-out and the `sequential_legs=True` parity twin, so the journal
    # streams stay bit-comparable across the two schedulers. a is the
    # owner host (REPLICA_HOST = -2 for the replica leg), b the
    # sub-batch width.
    "leg_done",      # -, fid, a=owner, b=seeds   dispatch leg joined/applied
    # round-16 migration journal (policy markers like the three above;
    # fid carries the MIGRATION batch index, not a flush id — the fold
    # below ignores these kinds entirely, so the collision is harmless)
    "migrate",           # -, mig, a=lo, b=hi     range handoff began (build)
    "migrate_commit",    # -, mig, a=src, b=dst   routing flipped to dst
    "migrate_rollback",  # -, mig, a=src, b=dst   range stayed with src
    # round-17 streaming-graph journal (policy markers; fid carries the
    # engine's GRAPH VERSION for delta_commit, -1 for staged arrivals —
    # the flush fold ignores both kinds, so the collision is harmless).
    # OBSERVE-ONLY like every journal event: the observe-only parity rule
    # stays pinned — journal on changes no served bit.
    "graph_delta",       # -,  -,  a=pending      edges staged host-side
    "delta_commit",      # -, ver, a=edges, b=invalidated   fenced commit
    # round-18 predictive-IO journal (policy markers; observe-only —
    # prefetch changes WHEN a disk byte is read, never which byte, so
    # the journal-on parity rule carries over unchanged). prefetch_issue
    # rides the issuing flush's fid; prefetch_hit is emitted at gather
    # consumption, which may serve a different flush than the issuer
    # (fid -1 — staging is engine-global, not per-flush).
    "prefetch_issue",    # -, fid, a=rows_issued, b=closure_rows
    "prefetch_hit",      # -,  -,  a=rows_consumed_from_staging
    # round-21 graph-lifecycle journal (policy markers; fid carries the
    # engine's GRAPH VERSION — the flush fold ignores all four kinds.
    # Observe-only pinned bit-neutral in tests/test_lifecycle.py: journal
    # on changes no served bit, including across deletes/expiry/compaction)
    "edge_delete",       # -, ver, a=edges_deleted   fenced lane rewrites
    "retention_expire",  # -, ver, a=edges_expired, b=nodes   TTL masking
    "compact_begin",     # -, ver, a=reclaims_planned, b=moves_planned
    "compact_commit",    # -, ver, a=tiles_reclaimed, b=moves_applied
)

# rough per-event host bytes: 6-slot tuple + boxed floats/small ints. Used
# only for the approx_bytes bound the rollover test pins — the real bound
# is the event COUNT (deque maxlen).
_EVENT_APPROX_BYTES = 160


def _fold_flush_events(events) -> Dict[int, Dict[str, float]]:
    """Fold a journal event stream's PER-FLUSH events into one dict per
    fid — the single state machine both `EventJournal.request_breakdown`
    and :func:`chrome_trace_events` consume, so a new event kind threads
    through every consumer at once instead of drifting between hand-rolled
    copies. Per-request kinds (submit/cache_hit/coalesce/late_admit/
    assemble) are ignored here; callers fold those themselves."""
    flushes: Dict[int, Dict[str, float]] = {}
    for (t, kind, rid, fid, a, b) in events:
        if fid < 0 or kind in (
            "submit", "cache_hit", "coalesce", "late_admit", "assemble",
            "shed", "hedge", "eject", "leg_done",
            "migrate", "migrate_commit", "migrate_rollback",
            "graph_delta", "delta_commit",
            "prefetch_issue", "prefetch_hit",
            "edge_delete", "retention_expire",
            "compact_begin", "compact_commit",
        ):
            continue
        f = flushes.setdefault(fid, {})
        if kind == "flush":
            f["assemble_t"], f["n_drained"], f["bucket"] = t, a, b
        elif kind == "seal":
            f["seal_t"], f["n_final"], f["bucket"] = t, a, b
        elif kind == "window_wait":
            f["window_wait_s"] = a
        elif kind == "dispatch":
            f["dispatch_t"] = t
        elif kind == "execute_done":
            f["execute_done_t"] = t
        elif kind == "resolve":
            f["resolve_t"] = t
    return flushes


def _stage_stats(values: Sequence[float]) -> Dict[str, float]:
    """{"p50", "p99", "mean", "n"} of a value list (empirical percentiles:
    the k-th sorted sample at rank ceil(p/100*n)). The journal is bounded,
    so materializing the sorted list is bounded too."""
    vals = sorted(float(v) for v in values)
    n = len(vals)
    if not n:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}

    def pick(p: float) -> float:
        return vals[min(n - 1, max(0, math.ceil(p / 100.0 * n) - 1))]

    return {
        "p50": pick(50),
        "p99": pick(99),
        "mean": sum(vals) / n,
        "n": n,
    }


class EventJournal:
    """Bounded, lock-cheap ring buffer of structured lifecycle events on a
    shared monotonic clock — the per-request observability spine of the
    serve stack (ISSUE 7 tentpole).

    The write path is ONE conditional + one tuple build + one
    ``deque.append`` (atomic under the GIL), so serving threads never
    contend on a lock to journal; the ring (``maxlen=capacity``) bounds
    memory no matter how long the engine runs — the newest ``capacity``
    events win, ``dropped`` counts what rolled off. ``snapshot()`` uses the
    same retry-on-mutation discipline as `SpanRecorder.overlap_summary`:
    emitters may append mid-copy and the copy retries.

    OBSERVE-ONLY RULE: nothing in the engine reads the journal to make a
    decision — events never feed control flow, which is why enabling the
    journal provably changes no served bit (the replay-parity pin in
    tests/test_obs.py). Keep it that way: a policy that wants these
    numbers must consume them through an explicit, separately-tested knob.

    ``enabled=False`` (or the shared :data:`NULL_JOURNAL`) makes ``emit``
    a single attribute check — the near-zero disabled cost the serve
    engines rely on.
    """

    __slots__ = ("capacity", "clock", "enabled", "dropped", "_events")

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = time.monotonic,
                 enabled: bool = True):
        import collections

        if capacity < 1:
            raise ValueError("EventJournal capacity must be >= 1")
        self.capacity = int(capacity)
        self.clock = clock
        self.enabled = bool(enabled)
        self.dropped = 0  # events rolled off the ring (approximate: unlocked)
        self._events = collections.deque(maxlen=self.capacity)

    @property
    def approx_bytes(self) -> int:
        """Upper bound on the ring's event storage (capacity * per-event
        estimate) — the byte half of the rollover bound."""
        return self.capacity * _EVENT_APPROX_BYTES

    def emit(self, kind: str, rid: int = -1, fid: int = -1,
             a: float = 0, b: float = 0) -> None:
        if not self.enabled:
            return
        ev = self._events
        if len(ev) == self.capacity:
            self.dropped += 1
        ev.append((self.clock(), kind, rid, fid, a, b))

    def record_many(self, events) -> None:
        """Batched append (round 20): one clock read + one ``deque.extend``
        covering N events — the journal half of the vectorized submit
        path (`ServeEngine.submit_many` journals a whole admission chunk
        through here instead of N ``emit`` calls). ``events`` is a
        sequence of ``(kind, rid, fid, a, b)`` tuples; every entry lands
        with the SAME timestamp (they are one host-path action).
        `request_breakdown` reads these identically to emitted events —
        per-stage deltas just collapse to zero within a chunk, exactly
        what one batched admission costs."""
        if not self.enabled or not events:
            return
        ev = self._events
        overflow = len(ev) + len(events) - self.capacity
        if overflow > 0:
            self.dropped += overflow
        t = self.clock()
        ev.extend(
            (t, kind, rid, fid, a, b) for kind, rid, fid, a, b in events
        )

    def __len__(self) -> int:
        return len(self._events)

    def __bool__(self) -> bool:
        return bool(self._events)

    def __iter__(self):
        return iter(self.snapshot())

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0

    def snapshot(self) -> Tuple:
        """Consistent tuple copy of the ring (`_snapshot_deque`: the
        retry-on-concurrent-append discipline shared with
        `SpanRecorder`)."""
        return _snapshot_deque(self._events)

    def request_breakdown(self) -> Dict[str, object]:
        """Per-request per-stage latency percentiles + per-flush pad
        occupancy, computed from the journaled lifecycle events — the
        numbers late admission and QoS policies are judged by.

        Stages (per request, ms): ``queue_ms`` (submit/coalesce/late-admit
        -> its flush's dispatch), ``device_ms`` (dispatch -> execute-done
        of the flush it rode), ``resolve_ms`` (execute-done -> resolve).
        Per-flush: ``pad_frac`` ((bucket - n_final)/bucket — the slack
        late admission exists to recover), ``window_wait_ms``. Requests
        whose flush rolled off the ring (or never dispatched yet) are
        skipped, not guessed."""
        events = self.snapshot()
        flushes = _fold_flush_events(events)
        reqs: List[Tuple[float, int]] = []  # (submit_t, fid) once linked
        pending_rid: Dict[int, float] = {}  # rid -> earliest submit_t seen
        rid_extra: Dict[int, List[float]] = {}  # rid -> later waiter times
        rid_fid: Dict[int, int] = {}  # rid -> flush once assembled/admitted
        cache_hits = 0
        for (t, kind, rid, fid, a, b) in events:
            if kind in ("submit", "coalesce"):
                linked = rid_fid.get(rid)
                if linked is not None:
                    # coalesced onto an ALREADY-assembled (in-flight) slot:
                    # link straight to its flush — these are exactly the
                    # hot-key waiters saturated load produces, and dropping
                    # them would bias queue_ms low (their queue wait clamps
                    # to 0 below when they attached after the dispatch)
                    reqs.append((t, linked))
                elif rid in pending_rid or rid in rid_extra:
                    rid_extra.setdefault(rid, []).append(t)
                else:
                    pending_rid[rid] = t
            elif kind == "cache_hit":
                cache_hits += 1
            elif kind in ("late_admit", "assemble"):
                rid_fid[rid] = fid
                if kind == "late_admit" and rid not in pending_rid:
                    pending_rid[rid] = t
                t0 = pending_rid.pop(rid, None)
                if t0 is not None:
                    reqs.append((t0, fid))
                for tw in rid_extra.pop(rid, ()):  # coalesced co-waiters
                    reqs.append((tw, fid))
        queue_ms: List[float] = []
        device_ms: List[float] = []
        resolve_ms: List[float] = []
        for t0, fid in reqs:
            f = flushes.get(fid)
            if not f or "dispatch_t" not in f:
                continue  # flush rolled off the ring or still in flight
            # clamp: a waiter that coalesced onto a flush already past its
            # dispatch point waited zero queue time, not negative
            queue_ms.append(max(f["dispatch_t"] - t0, 0.0) * 1e3)
            if "execute_done_t" in f:
                device_ms.append((f["execute_done_t"] - f["dispatch_t"]) * 1e3)
                if "resolve_t" in f:
                    resolve_ms.append(
                        (f["resolve_t"] - f["execute_done_t"]) * 1e3
                    )
        pad_fracs = [
            (f["bucket"] - f["n_final"]) / f["bucket"]
            for f in flushes.values()
            if f.get("bucket") and "n_final" in f
        ]
        waits_ms = [
            f["window_wait_s"] * 1e3
            for f in flushes.values()
            if "window_wait_s" in f
        ]
        return {
            "requests": len(queue_ms),
            "cache_hits": cache_hits,
            "flushes": len([f for f in flushes.values() if "dispatch_t" in f]),
            "queue_ms": _stage_stats(queue_ms),
            "device_ms": _stage_stats(device_ms),
            "resolve_ms": _stage_stats(resolve_ms),
            "window_wait_ms": _stage_stats(waits_ms),
            "pad_frac": _stage_stats(pad_fracs),
            "dropped_events": self.dropped,
        }


class _NullJournal(EventJournal):
    """Shared disabled journal: ``emit`` is one attribute check. Engines
    hold this when journaling is off, so the hot path never branches on
    None."""

    def __init__(self):
        super().__init__(capacity=1, enabled=False)

    def emit(self, *_a, **_k) -> None:
        return

    def record_many(self, *_a, **_k) -> None:
        return


NULL_JOURNAL = _NullJournal()


# -- unified metrics registry --------------------------------------------------


def _prom_name(name: str) -> str:
    """Prometheus metric names allow [a-zA-Z_:][a-zA-Z0-9_:]*; map the
    registry's dotted spellings onto it."""
    s = "".join(ch if ch.isalnum() or ch in "_:" else "_" for ch in name)
    return "_" + s if s and s[0].isdigit() else s


def _prom_value(v) -> str:
    """Full-precision Prometheus sample value: integers verbatim, floats
    via repr. ``%g`` would round to 6 significant digits — a byte counter
    past 1e6 would expose stale rounded values and break rate()."""
    f = float(v)
    if f.is_integer() and abs(f) < 2**63:
        return str(int(f))
    return repr(f)


def _prom_label_value(v) -> str:
    """Escape a label value per the Prometheus text format (backslash,
    double quote, newline) — one bad value must not invalidate the whole
    exposition."""
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _prom_labels(labels: Optional[Dict[str, str]]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_prom_name(k)}="{_prom_label_value(v)}"'
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class CounterMetric:
    """Monotonic counter. ``inc`` is locked (multi-thread emitters);
    callback-backed counters (``fn``) read a live source at snapshot time
    instead — that is how existing `ServeStats` counts are ADAPTED into
    the registry without double-counting state."""

    kind = "counter"
    __slots__ = ("name", "help", "labels", "_lock", "_value", "_fn")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None,
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn = fn

    def inc(self, n: float = 1) -> None:
        if self._fn is not None:
            raise ValueError(f"counter {self.name} is callback-backed")
        if n < 0:
            raise ValueError("counters only go up; use a gauge")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return float(self._fn()) if self._fn is not None else self._value

    def expose(self) -> List[str]:
        return [f"{_prom_name(self.name)}{_prom_labels(self.labels)} "
                f"{_prom_value(self.value)}"]


class GaugeMetric:
    """Point-in-time value: ``set`` stores, or a callback reads the live
    source at snapshot time (queue depths, cache sizes — state the engine
    already holds; the adapter registers a reader, never a copy)."""

    kind = "gauge"
    __slots__ = ("name", "help", "labels", "_value", "_fn")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None,
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self._value = 0.0
        self._fn = fn

    def set(self, v: float) -> None:
        if self._fn is not None:
            raise ValueError(f"gauge {self.name} is callback-backed")
        self._value = float(v)

    @property
    def value(self) -> float:
        return float(self._fn()) if self._fn is not None else self._value

    def expose(self) -> List[str]:
        return [f"{_prom_name(self.name)}{_prom_labels(self.labels)} "
                f"{_prom_value(self.value)}"]


class HistogramMetric:
    """A `LatencyHistogram` under a registry name. ``observe`` records
    into it; an ADAPTED histogram (``hist=`` an existing engine histogram,
    or ``fn=`` a callable resolving one — engines whose ``reset_stats``
    swaps the stats object register a resolver so the exposition always
    reads the LIVE histogram) exposes that object — one set of buckets,
    two views."""

    kind = "histogram"
    __slots__ = ("name", "help", "labels", "_hist", "_fn")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None,
                 hist: Optional[LatencyHistogram] = None,
                 fn: Optional[Callable[[], LatencyHistogram]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self._fn = fn
        self._hist = (
            None if fn is not None
            else (hist if hist is not None else LatencyHistogram())
        )

    @property
    def hist(self) -> LatencyHistogram:
        return self._fn() if self._fn is not None else self._hist

    def observe(self, v: float) -> None:
        self.hist.record_ms(v)

    @property
    def value(self) -> Dict[str, float]:
        return self.hist.snapshot()

    def expose(self) -> List[str]:
        """Prometheus histogram exposition: CUMULATIVE bucket counts by
        upper edge, then sum and count. Taken under the histogram's lock
        so the three agree."""
        h = self.hist
        base = _prom_name(self.name)
        lab = self.labels or {}
        with h._lock:
            counts = list(h._counts)
            total = h.count
            s = h.sum_ms
        lines = []
        acc = 0
        for edge, c in zip(h._edges, counts):
            acc += c
            le = dict(lab, le=f"{edge:g}")
            lines.append(f"{base}_bucket{_prom_labels(le)} {acc}")
        lines.append(
            f"{base}_bucket{_prom_labels(dict(lab, le='+Inf'))} {total}"
        )
        lines.append(f"{base}_sum{_prom_labels(lab or None)} {_prom_value(s)}")
        lines.append(f"{base}_count{_prom_labels(lab or None)} {total}")
        return lines


class MetricsRegistry:
    """Named counters/gauges/histograms with one JSON snapshot and one
    Prometheus text exposition — the single pane the serve stack's
    scattered stat objects (`ServeStats`, `DistServeStats`,
    `PipelineStats`, `HitRateCounter`) adapt INTO (adapters register
    callback-backed metrics reading the live objects; nothing is counted
    twice).

    Naming convention (docs/api.md "Observability"):
    ``quiver_<subsystem>_<metric>`` with ``_total`` for counters and a
    unit suffix (``_ms``, ``_bytes``, ``_rows``) elsewhere; instance
    dimensions (shard host, bucket) ride LABELS, not name suffixes.
    Registration is idempotent for an identical (name, labels, kind) and
    a hard error for a kind clash — two subsystems silently sharing a
    name is how dashboards lie."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, Tuple], object] = {}

    @staticmethod
    def _key(name: str, labels: Optional[Dict[str, str]]) -> Tuple[str, Tuple]:
        return (name, tuple(sorted((labels or {}).items())))

    def _register(self, cls, name, help, labels, **kw):
        key = self._key(name, labels)
        with self._lock:
            existing = self._metrics.get(key)
            if existing is not None:
                if existing.kind != cls.kind:
                    raise ValueError(
                        f"metric {name!r}{labels or ''} already registered "
                        f"as {existing.kind}, not {cls.kind}"
                    )
                # re-registering a callback/adapted metric RE-POINTS it at
                # the new source (last writer wins): an operator who
                # rebuilds an engine and re-registers into a long-lived
                # registry must not keep scraping the dead engine's frozen
                # closures. Stored-value metrics keep their state.
                fn = kw.get("fn")
                if fn is not None:
                    existing._fn = fn
                    if cls is HistogramMetric:
                        existing._hist = None
                elif cls is HistogramMetric and kw.get("hist") is not None:
                    existing._hist = kw["hist"]
                    existing._fn = None
                return existing
            m = cls(name, help=help, labels=labels, **kw)
            self._metrics[key] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> CounterMetric:
        return self._register(CounterMetric, name, help, labels)

    def counter_fn(self, name: str, fn: Callable[[], float], help: str = "",
                   labels: Optional[Dict[str, str]] = None) -> CounterMetric:
        return self._register(CounterMetric, name, help, labels, fn=fn)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> GaugeMetric:
        return self._register(GaugeMetric, name, help, labels)

    def gauge_fn(self, name: str, fn: Callable[[], float], help: str = "",
                 labels: Optional[Dict[str, str]] = None) -> GaugeMetric:
        return self._register(GaugeMetric, name, help, labels, fn=fn)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  hist: Optional[LatencyHistogram] = None,
                  fn: Optional[Callable[[], LatencyHistogram]] = None,
                  ) -> HistogramMetric:
        return self._register(
            HistogramMetric, name, help, labels, hist=hist, fn=fn
        )

    def metrics(self) -> List[object]:
        """All registered metrics in registration order (dict order is
        insertion order — DETERMINISTIC, which is what makes two
        expositions of one registry diff cleanly)."""
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> Dict[str, object]:
        """JSON-able {name or name{labels}: value} — histograms expand to
        their summary dicts."""
        out: Dict[str, object] = {}
        for m in self.metrics():
            out[f"{m.name}{_prom_labels(m.labels)}"] = m.value
        return out

    def to_prometheus(self) -> str:
        """Prometheus text exposition (one # HELP/# TYPE header per metric
        family, families in registration order, label rows grouped under
        their family)."""
        lines: List[str] = []
        by_family: Dict[str, List[object]] = {}
        order: List[str] = []
        for m in self.metrics():
            if m.name not in by_family:
                by_family[m.name] = []
                order.append(m.name)
            by_family[m.name].append(m)
        for name in order:
            family = by_family[name]
            kinds = {m.kind for m in family}
            if len(kinds) > 1:  # _register forbids this; belt and braces
                raise ValueError(f"metric family {name!r} mixes kinds {kinds}")
            if family[0].help:
                lines.append(f"# HELP {_prom_name(name)} {family[0].help}")
            lines.append(f"# TYPE {_prom_name(name)} {family[0].kind}")
            for m in family:
                lines.extend(m.expose())
        return "\n".join(lines) + ("\n" if lines else "")


def register_hit_rate(registry: MetricsRegistry, name: str,
                      counter,
                      labels: Optional[Dict[str, str]] = None,
                      tiers: Sequence[str] = ()) -> None:
    """Adapt a live `HitRateCounter` into ``registry`` as
    ``<name>_{hits,misses,evictions}_total`` + ``<name>_hit_rate`` —
    callback-backed, so the counter keeps counting into itself and the
    registry reads it at snapshot time. ``counter`` may be the counter
    itself or a zero-arg resolver (engines whose ``reset_stats`` swaps
    the stats object pass a resolver so the registry follows the swap).
    ``tiers`` additionally registers the per-tier attribution families
    (``<name>_tier_{hits,misses}_total`` under a ``tier`` label) for the
    named tiers — HBM vs ICI vs host-tail vs disk gathers become separate
    Prometheus series (round-13 tier attribution)."""
    get = counter if callable(counter) else (lambda: counter)
    registry.counter_fn(f"{name}_hits_total", lambda: get().hits,
                        "cache hits", labels)
    registry.counter_fn(f"{name}_misses_total", lambda: get().misses,
                        "cache misses", labels)
    registry.counter_fn(f"{name}_evictions_total", lambda: get().evictions,
                        "cache evictions", labels)
    registry.gauge_fn(f"{name}_hit_rate", lambda: get().hit_rate,
                      "hits / (hits + misses)", labels)
    for tier in tiers:
        lab = dict(labels or {}, tier=str(tier))
        registry.counter_fn(
            f"{name}_tier_hits_total",
            (lambda tier=tier: get().tier_counts(tier)["hits"]),
            "per-tier attributed hits (rows served from this tier)", lab,
        )
        registry.counter_fn(
            f"{name}_tier_misses_total",
            (lambda tier=tier: get().tier_counts(tier)["misses"]),
            "per-tier attributed misses", lab,
        )


# -- Chrome-trace (Perfetto) export -------------------------------------------


def _assign_lanes(intervals: Sequence[Tuple[float, float]]) -> List[int]:
    """Greedy interval-graph coloring: lane of each (t0, t1) such that
    overlapping intervals get distinct lanes. This is what renders
    OVERLAPPED in-flight flushes as parallel tracks instead of nested
    slices — the timeline's whole point."""
    order = sorted(range(len(intervals)), key=lambda i: intervals[i][0])
    lane_free: List[float] = []  # lane -> time it frees up
    lanes = [0] * len(intervals)
    for i in order:
        t0, t1 = intervals[i]
        for ln, free in enumerate(lane_free):
            if free <= t0:
                lane_free[ln] = t1
                lanes[i] = ln
                break
        else:
            lanes[i] = len(lane_free)
            lane_free.append(t1)
    return lanes


def chrome_trace_events(
    sources: Sequence[Tuple[str, object]],
    time_origin: Optional[float] = None,
) -> List[Dict[str, object]]:
    """Merge span/journal sources into Chrome ``trace_events`` dicts.

    ``sources`` is [(process_name, source)] where a source is a
    `SpanRecorder` (or any iterable of (stage, t0, t1) triples), an
    `EventJournal`, or a COUNTER source — any object with a
    ``counter_samples()`` method yielding (name, t, value) tuples
    (`quiver_tpu.obs.CounterSeries`): each counter name renders as a
    Chrome ``ph: "C"`` track, so sampled series (workload head coverage,
    owner imbalance, and — round 24 — the engines' per-commit
    ``graph_version`` staircase / ``commit_stall_us`` lane under the
    ``serve.commits`` / ``router.commits`` pids) graph alongside the
    flush lanes. Each source becomes
    one pid; stage names (and journal flush lanes) become named tids. All
    sources must share one monotonic clock (the serve stack's
    engines/journals/comm spans all do); ``time_origin`` (default:
    earliest timestamp seen) rebases ts to 0.

    Journal rendering: per-flush lifecycle becomes complete ("X") slices —
    ``flush <fid>`` spanning seal->resolve on a per-overlap lane (so
    concurrent in-flight flushes sit side by side), with ``device`` and
    ``resolve`` sub-slices — and per-request events (submit / cache_hit /
    coalesce / late_admit) become instants ("i") on one requests track.
    """
    spans_by_pid: List[Tuple[int, str, List[Tuple[str, float, float]]]] = []
    instants: List[Tuple[int, float, str, Dict[str, object]]] = []
    flush_slices: List[Tuple[int, float, float, str, Dict[str, object], int]] = []
    counter_rows: List[Tuple[int, float, str, float]] = []
    # an EXPLICIT origin is honored verbatim (callers aligning several
    # exports on one shared clock); only when absent is the earliest
    # timestamp used
    explicit_origin = time_origin is not None
    t_min = time_origin
    for pid, (pname, src) in enumerate(sources):
        if isinstance(src, EventJournal):
            events = src.snapshot()
            flushes = _fold_flush_events(events)
            for (t, kind, rid, fid, a, b) in events:
                if not explicit_origin and (t_min is None or t < t_min):
                    t_min = t
                if kind in ("submit", "cache_hit", "coalesce", "late_admit"):
                    instants.append(
                        (pid, t, kind, {"rid": rid, "node": a, "fid": fid})
                    )
                elif kind in ("migrate", "migrate_commit",
                              "migrate_rollback"):
                    # migration markers: fid carries the migration batch
                    # index, a/b the range or src/dst per EVENT_KINDS
                    instants.append(
                        (pid, t, kind, {"mig": fid, "a": a, "b": b})
                    )
                elif kind in ("graph_delta", "delta_commit"):
                    # streaming-graph markers: fid carries the graph
                    # version for commits (EVENT_KINDS)
                    instants.append(
                        (pid, t, kind, {"version": fid, "a": a, "b": b})
                    )
                elif kind in ("prefetch_issue", "prefetch_hit"):
                    # round-18 predictive-IO markers (rows per EVENT_KINDS)
                    instants.append(
                        (pid, t, kind, {"fid": fid, "rows": a, "b": b})
                    )
                elif kind in ("edge_delete", "retention_expire",
                              "compact_begin", "compact_commit"):
                    # round-21 lifecycle markers: fid carries the graph
                    # version, a/b counts per EVENT_KINDS
                    instants.append(
                        (pid, t, kind, {"version": fid, "a": a, "b": b})
                    )
            items = []
            for fid, f in sorted(flushes.items()):
                t0 = f.get("assemble_t", f.get("seal_t"))
                t1 = f.get("resolve_t", f.get("execute_done_t"))
                if t0 is None or t1 is None:
                    continue  # incomplete at snapshot time / rolled off
                args = {
                    "fid": fid,
                    "n": f.get("n_final", f.get("n_drained", 0)),
                    "bucket": f.get("bucket", 0),
                    "window_wait_ms": round(
                        f.get("window_wait_s", 0.0) * 1e3, 3
                    ),
                }
                subs = []
                if "dispatch_t" in f and "execute_done_t" in f:
                    subs.append(
                        ("device", f["dispatch_t"], f["execute_done_t"])
                    )
                if "execute_done_t" in f and "resolve_t" in f:
                    subs.append(
                        ("resolve", f["execute_done_t"], f["resolve_t"])
                    )
                items.append((fid, t0, t1, args, subs))
            lanes = _assign_lanes([(t0, t1) for _, t0, t1, _, _ in items])
            for (fid, t0, t1, args, subs), lane in zip(items, lanes):
                flush_slices.append(
                    (pid, t0, t1, f"flush {fid}", args, lane)
                )
                for sname, st0, st1 in subs:
                    flush_slices.append((pid, st0, st1, sname, {}, lane))
            spans_by_pid.append((pid, pname, []))
        elif hasattr(src, "counter_samples"):
            # the counter lane (round 13): sampled (name, t, value) series
            # rendered as Chrome "C" counter tracks
            for cname, t, v in src.counter_samples():
                if not explicit_origin and (t_min is None or t < t_min):
                    t_min = t
                counter_rows.append((pid, t, cname, v))
            spans_by_pid.append((pid, pname, []))
        else:
            triples = [tuple(s) for s in src]
            if not explicit_origin:
                for _, t0, _t1 in triples:
                    if t_min is None or t0 < t_min:
                        t_min = t0
            spans_by_pid.append((pid, pname, triples))
    t_min = t_min or 0.0

    def us(t: float) -> float:
        return round((t - t_min) * 1e6, 3)

    events: List[Dict[str, object]] = []
    tids: Dict[Tuple[int, str], int] = {}

    def tid_for(pid: int, track: str) -> int:
        key = (pid, track)
        if key not in tids:
            tids[key] = len([k for k in tids if k[0] == pid])
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid,
                "tid": tids[key], "args": {"name": track},
            })
        return tids[key]

    for pid, pname, _ in spans_by_pid:
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": pname},
        })
    for pid, pname, triples in spans_by_pid:
        # per-stage tracks; same-stage spans that overlap (concurrent
        # flush callers) fan out to numbered lanes
        by_stage: Dict[str, List[Tuple[float, float]]] = {}
        for stage, t0, t1 in triples:
            by_stage.setdefault(stage, []).append((t0, t1))
        for stage, iv in by_stage.items():
            lanes = _assign_lanes(iv)
            for (t0, t1), lane in zip(iv, lanes):
                track = stage if lane == 0 else f"{stage}/{lane}"
                events.append({
                    "name": stage, "ph": "X", "ts": us(t0),
                    "dur": round(max(t1 - t0, 0.0) * 1e6, 3),
                    "pid": pid, "tid": tid_for(pid, track), "cat": "span",
                })
    for pid, t0, t1, name, args, lane in flush_slices:
        track = "flushes" if lane == 0 else f"flushes/{lane}"
        events.append({
            "name": name, "ph": "X", "ts": us(t0),
            "dur": round(max(t1 - t0, 0.0) * 1e6, 3),
            "pid": pid, "tid": tid_for(pid, track), "cat": "flush",
            "args": args,
        })
    for pid, t, kind, args in instants:
        events.append({
            "name": kind, "ph": "i", "ts": us(t), "s": "t",
            "pid": pid, "tid": tid_for(pid, "requests"), "cat": "request",
            "args": args,
        })
    for pid, t, cname, v in counter_rows:
        events.append({
            "name": cname, "ph": "C", "ts": us(t),
            "pid": pid, "tid": tid_for(pid, cname), "cat": "counter",
            "args": {"value": v},
        })
    return events


def export_chrome_trace(
    path: str,
    sources: Sequence[Tuple[str, object]],
    metadata: Optional[Dict[str, object]] = None,
) -> Dict[str, object]:
    """Write a Chrome ``trace_events`` JSON (Perfetto / chrome://tracing
    loadable) merging the given span/journal sources — see
    :func:`chrome_trace_events` for the source contract. Returns the
    document (also written to ``path`` when non-empty)."""
    import json

    doc: Dict[str, object] = {
        "traceEvents": chrome_trace_events(sources),
        "displayTimeUnit": "ms",
    }
    if metadata:
        doc["metadata"] = metadata
    if path:
        with open(path, "w") as fh:
            json.dump(doc, fh)
    return doc


# -- workload telemetry (quiver_tpu.obs) re-export ----------------------------
# The round-13 sketches/monitor live in their own subsystem but are part
# of the one observability surface this module is; re-exporting here keeps
# "import the trace module, get the telemetry" true. obs imports nothing
# from trace at module level (lazy method-local imports only), so this
# bottom-of-module import is cycle-safe in either import order.

from .obs import (  # noqa: E402
    CounterSeries,
    CountMinSketch,
    OwnerLoadStats,
    P2Quantile,
    SpaceSaving,
    WorkloadConfig,
    WorkloadMonitor,
    lru_hit_rate_che,
)
