"""Double-buffered sample -> tiered gather -> train pipeline.

The reference hides its host<->device latency in two ways the TPU cannot
copy: UVA kernels read pinned host memory directly (quiver.cu.hpp:16-26) and
CUDA streams overlap transfers with compute (stream_pool.hpp). The TPU-native
replacement (SURVEY.md section 7.3 item 5) is an explicit software pipeline:

- the jitted train step fuses the HOT gather (HBM-resident feature prefix)
  with the model fwd/bwd — one XLA program, nothing leaves the chip;
- COLD rows (the host-DRAM tail) are gathered by the native C++ engine
  (`qt_gather_rows`, csrc/quiver_cpu.cpp) and shipped with ONE async H2D
  copy per batch;
- a THREE-stage prefetch pipeline (sample+n_id-fetch thread, host cold-gather
  thread, H2D upload thread) runs batches i+1..i+3 while the device executes
  batch i's train step — the staged overlap that replaces CUDA streams. With
  the stages split, the per-batch wall clock converges to the SLOWEST stage
  (usually the H2D link) instead of the sum of all of them, which is what a
  single prefetch worker delivered (round-3 bench: 11% of non-link latency
  hidden).

The merge is in-jit. A table of one hot shard over a host tail (the layout
`Feature.lookup_padded` serves too) goes through the feature's own two
halves: `Feature.stage_tiered` on the gather thread, `Feature.upload_tiered`
on the upload thread, `feature.tiered_gather` in the step: a cold block of the
fixed width ``Feature.cold_cap``, so the step has ONE shape whatever a
batch's cold count. The disk-backed, adaptive and quantized stores keep the
older staging: ``x = hot_gather(mapped) * is_hot`` then a scatter of the cold
rows into their slots (`mode="drop"` makes the padding self-discarding), the
cold batch length bucketed to powers of two (bounded recompiles).
"""

from __future__ import annotations

import concurrent.futures
from dataclasses import dataclass, field
from typing import Iterator, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .comm import round_up_pow2
from .feature import Feature, TieredStage, tiered_gather
from .pyg.sage_sampler import DenseSample, GraphSageSampler
from .trace import SpanRecorder, trace_scope

KEY_BLOCK = 4096  # steps whose dropout keys `_key_chain` makes in one launch


@jax.jit
def _key_chain(key):
    """``KEY_BLOCK`` turns of ``key, sub = jax.random.split(key)`` in one
    program: the key to go on from and the subkeys, in order. Split eagerly,
    each turn is a program of its own between two steps."""
    def turn(k, _):
        both = jax.random.split(k)
        return both[0], both[1]

    return jax.lax.scan(turn, key, None, length=KEY_BLOCK)


class AsyncReadPool:
    """Bounded worker pool for cold-tier DISK reads (round 14).

    The train pipeline's stage pools are one-worker-per-stage because the
    stages are inherently serial; disk reads are the opposite — each
    chunk is an independent page-cache/disk access, so a batch split
    across ``workers`` threads overlaps the page faults (the C read loop
    and the memmap fault path both release the GIL). `gather` is the
    synchronous surface the tier stores call per batch; `submit` returns
    a future for prefetch-shaped callers.

    Error contract (the mirror of this module's mid-epoch fix, round 7):
    a failing chunk read CANCELS every queued sibling chunk, observes
    every future (no "exception was never retrieved" at GC), and
    re-raises the first failure by submission order at the caller — a
    deterministic raise, never a hang. The pool survives the failure and
    keeps serving subsequent gathers.
    """

    def __init__(self, workers: int = 4, chunk_rows: int = 4096,
                 name: str = "qt-diskread"):
        if workers < 1:
            raise ValueError("AsyncReadPool needs >= 1 worker")
        if chunk_rows < 1:
            raise ValueError("chunk_rows must be >= 1")
        self.workers = int(workers)
        self.chunk_rows = int(chunk_rows)
        self._pool = concurrent.futures.ThreadPoolExecutor(workers, name)
        # plain ints under the GIL (same discipline as ServeStats fields)
        self.reads = 0       # chunk reads issued
        self.gathers = 0     # gather() batches served
        self.rows = 0
        self.bytes = 0
        self.errors = 0
        self.seconds = 0.0

    def _chunks(self, ids: np.ndarray):
        n = ids.shape[0]
        per = max(
            self.chunk_rows if n > self.workers * self.chunk_rows
            else -(-n // self.workers),
            1,
        )
        return [ids[i : i + per] for i in range(0, n, per)]

    def gather(self, read_block, local_ids: np.ndarray) -> np.ndarray:
        """``read_block(ids_chunk) -> rows`` fanned across the workers;
        returns the concatenated rows in input order."""
        import time as _time

        ids = np.asarray(local_ids, np.int64).reshape(-1)
        t0 = _time.monotonic()
        self.gathers += 1
        if ids.shape[0] == 0:
            return read_block(ids)
        chunks = self._chunks(ids)
        if len(chunks) == 1:
            # no pool hop for a batch one worker would serve anyway
            self.reads += 1
            out = read_block(chunks[0])
            self.rows += out.shape[0]
            self.bytes += out.nbytes
            self.seconds += _time.monotonic() - t0
            return out
        futs = [self._pool.submit(read_block, c) for c in chunks]
        self.reads += len(futs)
        error: Optional[BaseException] = None
        parts = []
        for f in futs:
            if error is not None:
                # first failure wins: cancel what has not started and
                # observe the rest so nothing logs at GC
                f.cancel()
                f.add_done_callback(
                    lambda fut: fut.cancelled() or fut.exception()
                )
                continue
            try:
                parts.append(f.result())
            except BaseException as exc:
                error = exc
        if error is not None:
            self.errors += 1
            raise error
        out = np.concatenate(parts, axis=0)
        self.rows += out.shape[0]
        self.bytes += out.nbytes
        self.seconds += _time.monotonic() - t0
        return out

    def submit(self, read_block, local_ids: np.ndarray):
        """Async single-chunk read (prefetch-shaped callers); the future
        resolves to the rows or raises the read's error."""
        return self._pool.submit(read_block, np.asarray(local_ids, np.int64))

    def stats(self) -> dict:
        return {
            "workers": self.workers,
            "gathers": self.gathers,
            "reads": self.reads,
            "rows": self.rows,
            "bytes": self.bytes,
            "errors": self.errors,
            "seconds": self.seconds,
        }

    def shutdown(self, wait: bool = True) -> None:
        self._pool.shutdown(wait=wait, cancel_futures=True)

    def __enter__(self) -> "AsyncReadPool":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


class TieredBatch(NamedTuple):
    """Device-ready inputs for one pipelined step."""

    ds: DenseSample        # padded sample (adjs consumed by the model)
    mapped: jax.Array      # [W] int32 row ids in reordered (cache) space; -1 invalid
    cold_rows: jax.Array   # [C_b, D] prefetched host-tier rows (padded bucket)
    # [C_b] int32 slot in [0, W) for each cold row, W pads; None: ``mapped``
    # indexes the view [hot table; cold_rows] (`feature.tiered_gather`)
    cold_pos: Optional[jax.Array]
    seeds: jax.Array       # [B] the batch's seed node ids (for labels)


class HostStaged(NamedTuple):
    """Host-side staging result (prepare_host) awaiting its H2D upload."""

    mapped: np.ndarray               # [W] int32, -1 invalid
    rows: Optional[np.ndarray]       # [C_b, D] cold rows, or None (no cold)
    pos: Optional[np.ndarray]        # [C_b] int32 slots, or None


def tiered_lookup(
    hot_table: jax.Array,
    mapped: jax.Array,
    cold_rows: jax.Array,
    cold_pos: Optional[jax.Array],
) -> jax.Array:
    """Jit-safe tiered feature assembly: HBM gather for hot rows + scatter of
    prefetched cold rows. The in-jit half of the reference's multi-pointer
    gather kernel (shard_tensor.cu.hpp:16-58) — the host-pointer branch
    arrives as ``cold_rows`` instead of being read through UVA. Without
    ``cold_pos`` the batch was staged by `Feature.stage_tiered`: the
    feature's own device half answers it."""
    if cold_pos is None:
        return tiered_gather(hot_table, mapped, cold_rows)
    hot_n = hot_table.shape[0]
    is_hot = (mapped >= 0) & (mapped < hot_n)
    x = jnp.take(hot_table, jnp.clip(mapped, 0, hot_n - 1), axis=0)
    x = x * is_hot[:, None].astype(x.dtype)
    if cold_rows.shape[0]:
        x = x.at[cold_pos].set(cold_rows, mode="drop")
    return x


class TieredFeaturePipeline:
    """Prepares :class:`TieredBatch` inputs for a tiered :class:`Feature`.

    Host-side per batch: remap ids through ``feature_order``, split hot/cold
    by the cache boundary, native-gather the cold rows, enqueue ONE async H2D
    copy. All device work this object dispatches is async; the caller's train
    step consumes the arrays without further host syncs.

    Round 18 (ROADMAP item 3b — train THROUGH the disk tier): the cold
    stage now spans the whole hierarchy. A static 4-tier feature
    (``disk_path`` without ``adaptive_tiers``) gathers its DRAM middle
    from the host tail and its cold tail from the flat-file
    `tiers.DiskShard` (through the feature's `AsyncReadPool`); an
    adaptive feature (``adaptive_tiers=True``) snapshots its
    `tiers.TierStore` placement at construction and routes each batch by
    it — HBM-resident rows ride the fused in-jit gather exactly like the
    round-3 hot prefix (``mapped`` then carries HBM SLOTS), DRAM/disk
    rows assemble host-side. Bytes are identical to an all-DRAM epoch by
    construction (the backing file is the same stored table), so epoch
    loss curves are bit-parity-pinned in tests/test_prefetch.py.

    ``prefetch=True`` adds the flush-ahead leg: the SAMPLE stage issues
    `AsyncReadPool` reads for a batch's disk-resident rows one stage
    before the gather stage consumes them (`tiers.PrefetchBuffer` — the
    exact ids, no closure walk needed: the sample already materialized
    ``n_id``), so the gather finds the bytes in DRAM staging. Strictly
    observe-only on bits, same contract as the serve engines.

    PLACEMENT FREEZE: an adaptive pipeline reads a placement SNAPSHOT
    (maps copied, table references pinned — jax arrays are immutable, so
    promotions cannot corrupt the pinned HBM view) taken at
    construction. Do not run `adapt_tiers`/`apply_placement` against the
    same store mid-epoch: a host-DRAM promotion mutates the store's
    ``host_cache`` in place, which the snapshot cannot defend against.
    Build a fresh pipeline after a placement batch instead.
    """

    def __init__(self, feature: Feature, device=None, prefetch: bool = False,
                 prefetch_max_rows: int = 8192):
        from .tiers import TIER_HBM, TIER_HOST

        self.feature = feature
        self.device = device or jax.local_devices()[0]
        self.dtype = getattr(feature, "dtype", np.dtype(np.float32))
        self._order = feature.feature_order  # old id -> stored row (or None)
        from .shard_tensor import host_gather

        self._gather = host_gather
        # true tier traffic (padding excluded), accumulated across prepare()
        self.cold_rows_seen = 0
        self.rows_seen = 0
        self.disk_rows_seen = 0
        self._prefetch = None  # tiers.PrefetchBuffer when enabled
        store = getattr(feature, "tier_store", None)
        if store is not None:
            # adaptive: freeze the placement (see docstring). The HBM
            # table reference is pinned — placement applies build NEW
            # arrays, never mutate this one.
            self.mode = "adaptive"
            self._store = store
            self._tier_of = store.placement.tier_of.copy()
            self._slot_of = store.placement.slot_of.copy()
            self._tier_hbm, self._tier_host = TIER_HBM, TIER_HOST
            self.hot_rows = store.placement.hbm_rows
            self.hot_table = (
                store.hbm_table if store.hbm_table is not None
                else jnp.zeros((0, feature.dim), self.dtype,
                               device=self.device)
            )
            self._host_cache = store.host_cache
            self._disk_read = None  # adaptive reads go through the store
            if prefetch:
                self._prefetch = store.enable_prefetch(
                    max_rows=prefetch_max_rows
                )
            return
        st = feature.shard_tensor
        if st is None:
            raise ValueError("feature not built; call from_cpu_tensor first")
        if len(st.device_shards) > 1:
            raise ValueError(
                "tiered pipeline expects one hot shard + optional host tail; "
                "use the mesh-sharded gather for clique-striped features"
            )
        self._store = None
        if st.device_shards:
            _, self.hot_table, off = st.device_shards[0]
            self.hot_rows = off.end - off.start
        else:
            self.hot_table = jnp.zeros((0, feature.dim), self.dtype, device=self.device)
            self.hot_rows = 0
        self.cold_np = st.cpu_tensor  # may be None (fully resident)
        self._disk = getattr(st, "disk_shard", None)
        if self._disk is not None:
            self.mode = "disk"
            self._disk_start = st.disk_offset.start
            self._disk_pool = getattr(st, "read_pool", None) \
                or getattr(feature, "read_pool", None)
            if prefetch:
                if self._disk_pool is None:
                    raise ValueError(
                        "prefetch needs an AsyncReadPool (build the "
                        "Feature with read_pool=/disk_read_workers=)"
                    )
                from .tiers import PrefetchBuffer

                self._prefetch = PrefetchBuffer(
                    lambda ids: self._disk.read_block(ids),
                    self._disk_pool, max_rows=prefetch_max_rows,
                )
                # attribution honesty (round-18 satellite): the feature's
                # observe-only tier counter reports staged disk rows as
                # `disk_prefetched`
                if hasattr(feature, "disk_staged"):
                    feature.disk_staged = self._prefetch.staged_mask
        else:
            self.mode = "dram"
            # one hot shard over a host tail of a plain `Feature`: the
            # feature's own halves stage the batch, in one fixed shape
            self._own_halves = isinstance(feature, Feature) and self.cold_np is not None

    _own_halves = False

    def prepare_host(
        self, ids: np.ndarray, valid_count: Optional[int] = None
    ) -> "HostStaged":
        """Pure-host half of staging: id remap + hot/cold split + native cold
        gather. No device calls — safe to run in a gather thread concurrently
        with another batch's H2D upload (:meth:`upload`).

        ``valid_count`` (= ``ds.count``) marks the padding tail: padding
        lanes carry garbage ids whose rows the model masks out anyway, so
        fetching them wastes cold-tier H2D — at products scale ~15% of the
        capped width.
        """
        if self._own_halves:
            stage = self.feature.stage_tiered(ids, valid_count, clip=False)
            self.rows_seen += stage.mapped.shape[0]
            self.cold_rows_seen += stage.n_cold
            return stage
        with trace_scope("quiver.feature.lookup"):
            ids = np.asarray(ids).astype(np.int64).reshape(-1)
            W = ids.shape[0]
            n_total = self.feature.shape[0]
            invalid = (ids < 0) | (ids >= n_total)
            if valid_count is not None and valid_count < W:
                invalid[valid_count:] = True
            safe = np.where(invalid, 0, ids)
            stored = self._order[safe] if self._order is not None else safe
            stored = np.where(invalid, -1, stored)
            self.rows_seen += W
            if self.mode == "adaptive":
                return self._prepare_adaptive(stored, W)
            mapped = stored.astype(np.int32)
            if self.cold_np is None and self.mode != "disk":
                return HostStaged(mapped, None, None)
            (cold_sel,) = np.nonzero(mapped >= self.hot_rows)
            if cold_sel.size == 0:
                # hot-dominated batch: skip the 256-row padded upload entirely
                # (the step program already specializes on the 0-size shape)
                return HostStaged(mapped, None, None)
            self.cold_rows_seen += int(cold_sel.shape[0])
            b = round_up_pow2(cold_sel.shape[0], floor=256)
            pos = np.full(b, W, np.int32)  # W == out-of-range -> dropped
            pos[: cold_sel.shape[0]] = cold_sel
            rows = np.zeros((b, self.feature.dim), self.dtype)
            cold_ids = mapped[cold_sel].astype(np.int64)
            with trace_scope("quiver.feature.cold_gather"):
                if self.mode == "disk":
                    host_sel = np.nonzero(cold_ids < self._disk_start)[0]
                    if host_sel.size and self.cold_np is not None:
                        rows[host_sel] = self._gather(
                            self.cold_np, cold_ids[host_sel] - self.hot_rows
                        )
                    disk_sel = np.nonzero(cold_ids >= self._disk_start)[0]
                    if disk_sel.size:
                        self.disk_rows_seen += int(disk_sel.size)
                        rows[disk_sel] = self._read_disk(
                            cold_ids[disk_sel] - self._disk_start
                        )
                else:
                    rows[: cold_sel.size] = self._gather(
                        self.cold_np, cold_ids - self.hot_rows
                    )
            return HostStaged(mapped, rows, pos)

    def _read_disk(self, local_ids: np.ndarray) -> np.ndarray:
        """Disk-tier rows for the static layout, staging-aware: rows the
        sample stage prefetched come out of DRAM, the rest through the
        pooled flat-file read — byte-identical either way."""
        def read(ids):
            return self._disk.read_rows(ids, pool=self._disk_pool)

        pf = self._prefetch
        if pf is None:
            return read(local_ids)
        return pf.take_or_read(local_ids, read)

    def _prepare_adaptive(self, stored: np.ndarray, W: int) -> "HostStaged":
        """Adaptive-placement staging against the frozen snapshot:
        ``mapped`` carries HBM SLOTS (the pinned hot table is
        slot-indexed), -1 elsewhere; DRAM/disk rows assemble host-side
        — DRAM from the store's cache slots, disk through
        `TierStore.gather`'s own staging-aware read path semantics
        (prefetched rows out of DRAM, the rest from the backing file)."""
        valid = stored >= 0
        safe = np.where(valid, stored, 0)
        tiers = self._tier_of[safe]
        is_hbm = valid & (tiers == self._tier_hbm)
        mapped = np.where(is_hbm, self._slot_of[safe], -1).astype(np.int32)
        (cold_sel,) = np.nonzero(valid & ~is_hbm)
        if cold_sel.size == 0:
            return HostStaged(mapped, None, None)
        self.cold_rows_seen += int(cold_sel.shape[0])
        b = round_up_pow2(cold_sel.shape[0], floor=256)
        pos = np.full(b, W, np.int32)
        pos[: cold_sel.shape[0]] = cold_sel
        rows = np.zeros((b, self.feature.dim), self.dtype)
        cold_ids = stored[cold_sel]
        cold_tiers = tiers[cold_sel]
        with trace_scope("quiver.feature.cold_gather"):
            host_sel = np.nonzero(cold_tiers == self._tier_host)[0]
            if host_sel.size and self._host_cache is not None:
                rows[host_sel] = self._gather(
                    self._host_cache, self._slot_of[cold_ids[host_sel]]
                )
            disk_sel = np.nonzero(cold_tiers != self._tier_host)[0]
            if disk_sel.size:
                self.disk_rows_seen += int(disk_sel.size)
                rows[disk_sel] = self._read_backing(cold_ids[disk_sel])
        return HostStaged(mapped, rows, pos)

    def _read_backing(self, stored_ids: np.ndarray) -> np.ndarray:
        """Adaptive disk rows: staged prefetch bytes first, backing-file
        reads for the rest (the store's read pool chunks them)."""
        store = self._store

        def read(ids):
            return store.backing.read_rows(ids, pool=store.read_pool)

        pf = self._prefetch
        if pf is None:
            return read(stored_ids)
        return pf.take_or_read(stored_ids, read)

    # -- flush-ahead prefetch (round 18; issued by the SAMPLE stage) -------

    @property
    def prefetch_stats(self) -> dict:
        return self._prefetch.stats() if self._prefetch is not None else {}

    def prefetch(self, ids: np.ndarray,
                 valid_count: Optional[int] = None) -> int:
        """Issue `AsyncReadPool` reads for the DISK-resident rows of a
        batch's ``n_id`` — called by the sample stage, one stage before
        the gather consumes them. Exact ids (the sample already
        materialized them), so nothing here is speculative; returns rows
        issued. Observe-only on bits."""
        pf = self._prefetch
        if pf is None:
            return 0
        ids = np.asarray(ids).astype(np.int64).reshape(-1)
        if valid_count is not None and valid_count < ids.shape[0]:
            ids = ids[:valid_count]
        n_total = self.feature.shape[0]
        ids = ids[(ids >= 0) & (ids < n_total)]
        if ids.size == 0:
            return 0
        stored = self._order[ids] if self._order is not None else ids
        if self.mode == "adaptive":
            disk = stored[self._tier_of[stored] > self._tier_host]
            return pf.issue(disk) if disk.size else 0
        local = stored[stored >= self._disk_start] - self._disk_start
        return pf.issue(local) if local.size else 0

    def cancel_prefetch(self) -> int:
        """Drop staged rows (mid-epoch error unwind / epoch end): see
        `tiers.PrefetchBuffer.cancel`."""
        return self._prefetch.cancel() if self._prefetch is not None else 0

    def upload(
        self, staged: "HostStaged"
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Device half of staging: the H2D copies. Runs in the upload thread
        so a 10-100 MB cold transfer overlaps the NEXT batch's host gather
        and the CURRENT batch's device step."""
        if isinstance(staged, TieredStage):
            return self.feature.upload_tiered(staged) + (None,)
        with trace_scope("quiver.feature.h2d"):
            mapped_dev = jax.device_put(staged.mapped, self.device)
            if staged.rows is None:
                cold_rows = jnp.zeros(
                    (0, self.feature.dim), self.dtype, device=self.device
                )
                cold_pos = jnp.zeros((0,), jnp.int32, device=self.device)
            else:
                cold_rows = jax.device_put(staged.rows, self.device)
                cold_pos = jax.device_put(staged.pos, self.device)
            return mapped_dev, cold_rows, cold_pos

    def prepare(
        self, n_id: jax.Array, valid_count: Optional[int] = None
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(mapped, cold_rows, cold_pos) for a padded n_id array — the
        single-threaded composition of :meth:`prepare_host` + :meth:`upload`
        (kept for direct callers; :class:`TrainPipeline` stages them on
        separate threads)."""
        return self.upload(self.prepare_host(np.asarray(n_id), valid_count))


@dataclass
class PipelineStats:
    batches: int = 0
    cold_rows: int = 0
    hot_rows: int = 0
    # mixed-sampler feedback (populated by run_epoch_iter when the source
    # is a MixedGraphSageSampler): measured per-task averages + the split
    # the sampler chose — the inputs to suggest_num_workers
    avg_device_sample_s: float = 0.0
    avg_cpu_sample_s: float = 0.0
    device_share: Optional[float] = None
    # measured stage spans (trace.SpanRecorder): (stage_name, t0, t1)
    # monotonic triples recorded around every stage body and every device
    # step. THE falsifiable overlap evidence — summarize with
    # `overlap_summary()`. The recorder snapshots before iterating, so the
    # summary is safe to read mid-epoch while stage threads still append.
    # Eagerly constructed: record() is called from all four stage threads,
    # and a lazy None-check init could race at the first batch and drop
    # the winner's early spans
    spans: SpanRecorder = field(default_factory=SpanRecorder)

    def record(self, stage: str, t0: float, t1: float) -> None:
        self.spans.record(stage, t0, t1)

    def overlap_summary(self) -> dict:
        """Measured concurrency of the recorded spans — see
        :meth:`quiver_tpu.trace.SpanRecorder.overlap_summary` (overlap_frac,
        hidden_frac_measured, per-stage busy seconds)."""
        return self.spans.overlap_summary() if self.spans else {}

    def register_metrics(self, registry=None,
                         prefix: str = "quiver_pipeline", labels=None):
        """Adapt these live pipeline counters into a
        `trace.MetricsRegistry` (created when not given) — the same
        adapter discipline as `ServeEngine.register_metrics`: callback-
        backed readers, nothing counted twice. ``overlap_frac`` is
        computed from the span recorder at exposition time (bounded ring,
        so a scrape stays cheap)."""
        from .trace import MetricsRegistry

        reg = registry if registry is not None else MetricsRegistry()
        reg.counter_fn(f"{prefix}_batches_total", lambda: self.batches,
                       "pipelined train batches", labels)
        reg.counter_fn(f"{prefix}_cold_rows_total", lambda: self.cold_rows,
                       "cold-tier rows fetched", labels)
        reg.counter_fn(f"{prefix}_hot_rows_total", lambda: self.hot_rows,
                       "hot-tier rows gathered", labels)
        reg.gauge_fn(f"{prefix}_overlap_frac",
                     lambda: self.overlap_summary().get("overlap_frac", 0.0),
                     "fraction of covered wall with >= 2 stages active",
                     labels)
        reg.gauge_fn(f"{prefix}_span_count", lambda: len(self.spans),
                     "stage spans in the recorder ring", labels)
        return reg


class TrainPipeline:
    """sample -> tiered gather -> step, with staged prefetch threads.

    ``step_fn(params, opt_state, key, batch: TieredBatch) -> (params,
    opt_state, loss)`` must be jitted by the caller (see
    :func:`make_tiered_train_step`). Three single-thread stages run ahead of
    the consuming step:

      1. sample: device sampling dispatch + the n_id/count D2H fetches
      2. gather: id remap + native host cold gather (pure host, GIL released
         inside the C engine)
      3. upload: the H2D copies (the link-bound leg)

    Each stage is its own one-worker executor processing batches FIFO, so
    batch i's upload, batch i+1's host gather, batch i+2's sampling, and
    batch i-1's device step all run concurrently — per-batch wall time
    converges to the slowest stage instead of their sum. ``depth`` extra
    chains are kept in flight beyond the 3 stage buffers to absorb jitter.
    """

    def __init__(
        self,
        sampler: GraphSageSampler,
        feature: Feature,
        step_fn,
        depth: int = 2,
        tiered: "TieredFeaturePipeline" = None,
        checkpoint=None,
        checkpoint_every: int = 0,
        measure_overlap: bool = False,
    ):
        self.sampler = sampler
        # callers that already built a TieredFeaturePipeline (e.g. to hand
        # its hot_table to make_tiered_train_step) pass it in — two
        # instances over one Feature would drift apart on stats
        self.tiered = tiered if tiered is not None else TieredFeaturePipeline(feature)
        self.step_fn = step_fn
        self._chain = None  # (key, key after its first block, the block's subkeys)
        self.depth = max(depth, 1)
        self.stats = PipelineStats()
        # measure_overlap=True: sync each step's loss so the recorded
        # "step" span covers device execution — the falsifiable overlap
        # evidence (stats.overlap_summary). Costs one D2H sync per step,
        # so it is opt-in; when off, steps stay async and the recorded
        # span ("step_dispatch") covers only the dispatch.
        self.measure_overlap = bool(measure_overlap)
        # periodic preemption-safe state saves (checkpoint.CheckpointManager;
        # the reference has no library-level recovery story, SURVEY.md §5).
        # Saves are ASYNC (orbax background thread) so the train loop never
        # stalls on IO; _run flushes before returning.
        self.checkpoint = checkpoint
        self.checkpoint_every = int(checkpoint_every)
        if checkpoint is not None and self.checkpoint_every <= 0:
            raise ValueError("checkpoint given but checkpoint_every not set")
        if checkpoint is None and self.checkpoint_every > 0:
            raise ValueError("checkpoint_every set but no checkpoint manager")
        # resume numbering where the store left off: a fresh pipeline after
        # preemption must NOT re-save steps below the stored latest (orbax
        # accepts them silently and latest_step() would keep returning the
        # stale pre-crash state)
        self.global_step = (
            int(checkpoint.latest_step() or 0) if checkpoint is not None else 0
        )

    # --- the three stage bodies (each runs on its own single worker thread)

    def _sample_body(self, ds: DenseSample, seeds):
        """Stage 1: the D2H fetches that sync on device sampling."""
        # valid lanes form the n_id PREFIX only in the fully-deduped layout
        # (every adj carries explicit cols); structural (fused) samples
        # interleave invalid lanes, so the padding cut must be skipped there
        prefix_valid = all(a.cols is not None for a in ds.adjs)
        ids = np.asarray(ds.n_id)
        vc = int(ds.count) if prefix_valid else None
        if seeds is None:
            # the seed batch is always the n_id prefix (both pipelines)
            seeds = ids[: ds.batch_size]
        # flush-ahead prefetch (round 18): issue this batch's disk reads
        # NOW — the gather stage consumes them one stage later, so the
        # reads overlap the PREVIOUS batch's gather/upload/step instead
        # of sitting on the cold-gather critical path
        self.tiered.prefetch(ids, valid_count=vc)
        return ds, seeds, ids, vc

    def _gather_body(self, ds, seeds, ids, vc):
        """Stage 2: host remap + native cold gather (no device calls)."""
        before = self.tiered.cold_rows_seen
        host = self.tiered.prepare_host(ids, valid_count=vc)
        cold = self.tiered.cold_rows_seen - before
        self.stats.batches += 1
        self.stats.cold_rows += cold
        self.stats.hot_rows += host.mapped.shape[0] - cold
        return ds, seeds, host

    def _upload_body(self, ds, seeds, host) -> TieredBatch:
        """Stage 3: the H2D copies."""
        mapped, cold_rows, cold_pos = self.tiered.upload(host)
        return TieredBatch(
            ds=ds,
            mapped=mapped,
            cold_rows=cold_rows,
            cold_pos=cold_pos,
            # a copy, not a program: the cast happens on the host
            seeds=jax.device_put(np.asarray(seeds).astype(np.int32, copy=False),
                                 self.tiered.device),
        )

    def _stage_ds(self, ds: DenseSample, seeds=None) -> TieredBatch:
        """Single-threaded composition of all three stages (bootstrap and
        direct callers; the epoch loop stages them on separate threads)."""
        return self._upload_body(*self._gather_body(*self._sample_body(ds, seeds)))

    def _stage(self, seeds: np.ndarray) -> TieredBatch:
        return self._stage_ds(self.sampler.sample_dense(seeds), seeds)

    def register_metrics(self, registry=None,
                         prefix: str = "quiver_pipeline", labels=None):
        """`PipelineStats.register_metrics` plus the tiered feature
        pipeline's true-traffic counters (padding excluded)."""
        reg = self.stats.register_metrics(registry, prefix, labels)
        reg.counter_fn(f"{prefix}_tier_rows_seen_total",
                       lambda: self.tiered.rows_seen,
                       "rows through the tiered gather", labels)
        reg.counter_fn(f"{prefix}_tier_cold_rows_seen_total",
                       lambda: self.tiered.cold_rows_seen,
                       "rows answered by the cold tier", labels)
        reg.counter_fn(f"{prefix}_tier_disk_rows_seen_total",
                       lambda: self.tiered.disk_rows_seen,
                       "cold rows answered by the disk tier", labels)
        reg.counter_fn(
            f"{prefix}_tier_prefetch_issued_total",
            lambda: self.tiered.prefetch_stats.get("issued", 0),
            "disk rows issued flush-ahead by the sample stage", labels)
        reg.counter_fn(
            f"{prefix}_tier_prefetch_hits_total",
            lambda: self.tiered.prefetch_stats.get("hits", 0),
            "prefetched rows the gather stage consumed from staging",
            labels)
        return reg

    def export_chrome_trace(self, path: str, metadata=None):
        """Perfetto-loadable timeline of the recorded stage spans
        (sample / gather / upload / step lanes — the staged-overlap
        evidence as a picture instead of a fraction)."""
        from .trace import export_chrome_trace

        return export_chrome_trace(
            path, [("train_pipeline", self.stats.spans)], metadata
        )

    def run_epoch(
        self,
        seed_batches: Sequence[np.ndarray],
        params,
        opt_state,
        key: jax.Array,
    ):
        """Run one epoch over seed batches; returns (params, opt_state,
        losses list). Sampling, cold gather, and H2D for upcoming batches
        run on the stage threads while the device steps batch i."""
        return self._run(
            ((self.sampler.sample_dense(s), s) for s in seed_batches),
            params,
            opt_state,
            key,
        )

    def run_epoch_iter(self, samples, params, opt_state, key: jax.Array):
        """Train over an iterator of :class:`DenseSample`s — e.g. a
        `MixedGraphSageSampler` epoch, whose CPU worker processes then
        overlap with BOTH the cold-tier prefetch and the device steps.
        Accepts bare DenseSamples or the mixed sampler's
        ``(task_idx, DenseSample)`` pairs. All samples must share one padded
        shape (same sizes/batch/caps) so the step program is reused."""

        def pairs():
            for item in samples:
                # NB DenseSample is itself a (named) tuple — check it first
                ds = item if isinstance(item, DenseSample) else item[1]
                yield ds, None

        out = self._run(pairs(), params, opt_state, key)
        # feed the mixed sampler's measurements back into the stats so
        # callers can auto-tune (suggest_num_workers / auto_tune_workers)
        for attr, field in (
            ("avg_device_time", "avg_device_sample_s"),
            ("avg_cpu_time", "avg_cpu_sample_s"),
            ("last_device_share", "device_share"),
        ):
            if hasattr(samples, attr):
                setattr(self.stats, field, getattr(samples, attr))
        return out

    def _run(self, sample_pairs, params, opt_state, key: jax.Array):
        """The staged loop. ``sample_pairs`` yields (DenseSample, seeds)
        lazily; its work (the sampling dispatch) happens inside the SAMPLE
        thread's next() — generators refuse concurrent next(), and one
        thread per stage keeps delivery order FIFO. Each batch is a chain of
        three futures (sample -> gather -> upload); ``depth`` chains beyond
        the three stage buffers are kept in flight."""
        import collections

        it = iter(sample_pairs)
        losses = []
        spool = concurrent.futures.ThreadPoolExecutor(1, "qt-sample")
        gpool = concurrent.futures.ThreadPoolExecutor(1, "qt-gather")
        upool = concurrent.futures.ThreadPoolExecutor(1, "qt-upload")

        import time as _time

        def sample_next():
            t0 = _time.monotonic()
            item = next(it, None)
            if item is None:
                return None
            out = self._sample_body(*item)
            self.stats.record("sample", t0, _time.monotonic())
            return out

        def gather(fut):
            r = fut.result()
            if r is None:
                return None
            t0 = _time.monotonic()
            out = self._gather_body(*r)
            self.stats.record("gather", t0, _time.monotonic())
            return out

        def upload(fut):
            r = fut.result()
            if r is None:
                return None
            t0 = _time.monotonic()
            out = self._upload_body(*r)
            self.stats.record("upload", t0, _time.monotonic())
            return out

        # the keys ``key, sub = split(key)`` would hand out step by step,
        # made KEY_BLOCK at a time and wrapped from their words on the host
        typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
        base, subs, used = key, None, KEY_BLOCK
        # the state a step returns is committed to the chip; state handed in
        # fresh (an init's output) is not, and the step would compile once
        # for each. No copy: the arrays are where they are put
        params, opt_state = jax.device_put((params, opt_state), self.tiered.device)

        q = collections.deque()
        failed = False
        try:

            def launch():
                f1 = spool.submit(sample_next)
                f2 = gpool.submit(gather, f1)
                q.append((f1, f2, upool.submit(upload, f2)))

            for _ in range(self.depth + 2):
                launch()
            while True:
                batch = q.popleft()[-1].result()
                if batch is None:
                    break
                launch()
                if used == KEY_BLOCK:
                    # a run on the key object of the run before (an epoch
                    # after its warm-up) starts on the block already made
                    if key is base and self._chain is not None and self._chain[0] is key:
                        _, key, subs = self._chain
                    else:
                        first, (key, block) = key is base, _key_chain(key)
                        subs = np.asarray(jax.random.key_data(block) if typed else block)
                        if first:
                            self._chain = (base, key, subs)
                    used = 0
                sub = (jax.random.wrap_key_data(subs[used], impl=jax.random.key_impl(key))
                       if typed else subs[used])
                used += 1
                t0 = _time.monotonic()
                params, opt_state, loss = self.step_fn(params, opt_state, sub, batch)
                if self.measure_overlap:
                    # the span must cover device EXECUTION, not just the
                    # async dispatch — sync on the loss before closing it
                    loss = float(loss)
                    self.stats.record("step", t0, _time.monotonic())
                else:
                    self.stats.record("step_dispatch", t0, _time.monotonic())
                losses.append(loss)
                self.global_step += 1
                if (
                    self.checkpoint is not None
                    and self.global_step % self.checkpoint_every == 0
                ):
                    self.checkpoint.save(
                        self.global_step,
                        {"params": params, "opt_state": opt_state},
                        wait=False,
                    )
        except BaseException:
            # a stage (or the step) raised mid-epoch: cancel every QUEUED
            # stage future on all three pools so the blocking shutdown below
            # cannot sit behind batches nobody will consume, and mark EVERY
            # future of every in-flight chain as observed — including the
            # sample/gather futures, which can fail on their own (not just
            # unwind via CancelledError from a cancelled upstream) and
            # would otherwise log "exception was never retrieved" at GC.
            # The ORIGINAL exception then re-raises — the clean path's
            # shutdown alone would leave prefetched chains queued and the
            # caller guessing why the iterator died
            for pool in (spool, gpool, upool):
                pool.shutdown(wait=False, cancel_futures=True)
            while q:
                for f in q.popleft():
                    f.cancel()
                    f.add_done_callback(
                        lambda fut: fut.cancelled() or fut.exception()
                    )
            # flush-ahead reads issued for batches nobody will gather:
            # cancel + observe them so the unwind leaves no pool zombies
            # (the r7/r14 error contract extended to the prefetch leg)
            self.tiered.cancel_prefetch()
            failed = True
            raise
        finally:
            spool.shutdown(wait=True)
            gpool.shutdown(wait=True)
            upool.shutdown(wait=True)
            if failed:
                # a sample task that was RUNNING when the queue was cancelled
                # may have issued its batch's reads after the cancel above
                self.tiered.cancel_prefetch()
            if self.checkpoint is not None:
                self.checkpoint.flush()
        return params, opt_state, [float(l) for l in losses]


def make_tiered_train_step(model, tx, labels: jax.Array, hot_table: jax.Array):
    """Jitted ``step(params, opt_state, key, batch)`` fusing the hot gather
    and the merge of the batch's cold block into fwd/bwd: ONE program a step,
    XLA module ``jit_tiered_train_step`` (`trace.TIERED_PROGRAM_NAMES`).
    ``labels``/``hot_table`` enter the jitted program as ARGUMENTS (closure
    capture would embed a million-row table as an XLA constant — minutes of
    compile)."""
    import optax

    hot_table = jnp.asarray(hot_table)
    labels = jnp.asarray(labels)

    @jax.jit
    def tiered_train_step(params, opt_state, key, hot, lab, batch: TieredBatch):
        x = tiered_lookup(hot, batch.mapped, batch.cold_rows, batch.cold_pos)
        y = jnp.take(lab, jnp.clip(batch.seeds, 0, lab.shape[0] - 1))

        def objective(p):
            logits = model.apply(
                p, x, batch.ds.adjs, train=True, rngs={"dropout": key}
            )
            ll = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(ll, y[:, None].astype(jnp.int32), axis=1)[:, 0]
            return nll.mean()

        loss, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def bound(params, opt_state, key, batch: TieredBatch):
        return tiered_train_step(params, opt_state, key, hot_table, labels, batch)

    bound.program = tiered_train_step  # to lower it, or to hold its name
    return bound
