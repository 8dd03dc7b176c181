"""Feature — tiered feature cache with power-law-aware placement.

TPU-native re-design of the reference's ``srcs/python/quiver/feature.py``:
``Feature`` (feature.py:17-458), ``DeviceConfig`` (feature.py:11-14),
``PartitionInfo`` (feature.py:461-526), ``DistFeature`` (feature.py:529-567).

Cache policies (reference feature.py:43-45, docs/Introduction_en.md:104-119):

- ``device_replicate``: the hot (high-degree) prefix is replicated into every
  chip's HBM; the cold tail lives once in host DRAM.  On TPU the "every GPU"
  replication becomes "every local chip" — one jax.Array per chip.
- ``p2p_clique_replicate`` (alias ``ici_replicate``): the hot set is striped
  across all chips of an ICI clique (a TPU slice is one all-to-all clique, so
  the NVLink-clique detection degenerates — see utils.IciTopo); reads off-chip
  rows over ICI.  The eager path ships rows with device_put; the jit path
  uses ``quiver_tpu.parallel.collectives.sharded_gather`` inside shard_map.

The degree-descending hot ordering comes from ``reindex_feature``
(reference utils.py:230-248) when a ``csr_topo`` is attached AND a colder
tier exists (host tail or disk); lookups then remap through
``feature_order`` exactly like reference feature.py:296-333. A table the hot
tier holds whole is stored in the caller's row order: ``feature_order`` stays
``None`` and a lookup is the row gather alone.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from .ops import cpu_kernels, gather_sum
from .shard_tensor import (
    CPU_DEVICE,
    HostRows,
    ShardTensor,
    ShardTensorConfig,
    _device_of,
    host_gather,
    normalize_dtype,
)
from .trace import observe, trace_scope
from .utils import CSRTopo, IciTopo, degree_order, parse_size, reindex_feature


@dataclass
class DeviceConfig:
    """Reference feature.py:11-14."""

    device_list: List[int]
    device_cache_size: Union[int, str] = 0


def validate_lookup_ids(
    node_idx, n: int, feature_order: Optional[np.ndarray] = None,
    local_order_applied: bool = False,
) -> np.ndarray:
    """Opt-in STRICT id validation for feature lookups (host-side, not
    jittable). The jit gather paths (`lookup_padded`, `tiered_lookup`)
    deliberately ``jnp.clip`` out-of-range ids into the table — negative
    ids land on row 0, ids ``>= N`` on the last row — because a data-
    dependent raise cannot exist inside an XLA program; the eager paths
    zero-fill instead. Both are silent by design (sampler sentinel padding
    must flow through). Call this at ingest boundaries where an
    out-of-range id means corrupt input, not padding.

    Returns the flattened int64 ids; raises ValueError naming the bad
    count and examples. With ``local_order_applied`` (distributed path),
    ids whose remap entry is negative — globals this host does not own —
    are invalid too.
    """
    ids = np.asarray(node_idx).astype(np.int64).reshape(-1)
    if local_order_applied:
        if feature_order is None:
            raise ValueError("local-order validation needs the feature_order map")
        oob = (ids < 0) | (ids >= feature_order.shape[0])
        bad = oob | (feature_order[np.where(oob, 0, ids)] < 0)
        domain = f"owned global ids (map size {feature_order.shape[0]})"
    else:
        bad = (ids < 0) | (ids >= n)
        domain = f"[0, {n})"
    if bad.any():
        examples = ids[bad][:8].tolist()
        raise ValueError(
            f"{int(bad.sum())} of {ids.size} lookup ids outside {domain}; "
            f"examples: {examples} (jit lookups would clip these, eager "
            "lookups would zero-fill — see Feature.validate_ids)"
        )
    return ids


def attribute_gather_tiers(shard_tensor, rank, stored_ids, counter,
                           valid=None, staged=None) -> None:
    """OBSERVE-ONLY per-tier attribution of a tiered gather (round-13
    workload telemetry): count how many of ``stored_ids`` resolve in each
    tier — ``hbm`` (this rank's own device shard), ``ici`` (another
    chip's shard in the clique stripe), ``host`` (the DRAM tail) — into a
    tier-aware `trace.HitRateCounter` (``counter.hit(n, tier=...)``).

    Pure counting over the shard book's offsets (one vectorized compare
    per shard); never touches the gather itself, so attaching a counter
    changes no gathered byte. ``valid`` masks out pad/invalid lanes —
    those gather row 0 physically but are not real feature requests, and
    counting them would inflate the hot tier.

    ``staged`` (round 18): a callable ``stored_ids -> bool mask`` naming
    disk-tier rows a flush-ahead prefetch already landed in DRAM (e.g.
    ``PrefetchBuffer.staged_mask`` over the disk shard's LOCAL ids) —
    those count as ``disk_prefetched`` instead of ``disk``, so the tier
    labels report where bytes actually come from, not just where the
    placement says they live."""
    if counter is None or shard_tensor is None:
        return
    ids = np.asarray(stored_ids).reshape(-1)
    if valid is not None:
        ids = ids[np.asarray(valid).reshape(-1)]
    if ids.size == 0:
        return
    for dev_rank, _, off in shard_tensor.device_shards:
        n = int(((ids >= off.start) & (ids < off.end)).sum())
        if n:
            counter.hit(n, tier="hbm" if dev_rank == rank else "ici")
    off = shard_tensor.cpu_offset
    if shard_tensor.cpu_tensor is not None and off is not None:
        n = int(((ids >= off.start) & (ids < off.end)).sum())
        if n:
            counter.hit(n, tier="host")
    off = getattr(shard_tensor, "disk_offset", None)
    if getattr(shard_tensor, "disk_shard", None) is not None and off is not None:
        # the round-14 flat-file tail: REAL disk-hit counts (the "disk"
        # label register_hit_rate has carried since round 13, now fed)
        sel = (ids >= off.start) & (ids < off.end)
        n = int(sel.sum())
        pre = 0
        if n and staged is not None:
            pre = int(np.asarray(staged(ids[sel] - off.start)).sum())
            if pre:
                counter.hit(pre, tier="disk_prefetched")
        if n - pre:
            counter.hit(n - pre, tier="disk")


# mode="clip", not clip-then-take: `jnp.take` defaults to mode="fill", whose
# NaN fill is a select over the whole output that XLA keeps even when the ids
# were clipped the line before (5 ms a step on 1.7 GB of igb-small rows)
@jax.jit
def _padded_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0, mode="clip")


@jax.jit
def _padded_gather_ordered(table: jax.Array, order: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, jnp.take(order, ids, mode="clip"), axis=0, mode="clip")


class TieredStage(NamedTuple):
    """The host half of a tiered lookup (`Feature.stage_tiered`), awaiting
    its copy to the device and `tiered_gather`."""

    mapped: np.ndarray     # [W] int32 row of the view [hot table; cold block]; -1: a zero row
    cold_rows: np.ndarray  # [C, D] the cold block; rows past ``n_cold`` are stale
    n_cold: int            # valid rows of the block


def tiered_gather(hot_table: jax.Array, mapped: jax.Array, cold_rows: jax.Array) -> jax.Array:
    """The device half of a tiered lookup, jit-safe: row ``mapped[i]`` of
    the view ``[hot_table; cold_rows]`` without the concatenation (two row
    gathers and a select; a scatter of the cold block into the hot gather
    costs more, PERF.md section 6, PR 32), a zero row where ``mapped`` is
    negative. No shape depends on how many rows of the block are valid."""
    hot_n = hot_table.shape[0]
    if hot_n == 0:  # nothing hot: the block is the table's answer
        x = jnp.take(cold_rows, mapped, axis=0, mode="clip")
    else:
        x = jnp.take(hot_table, mapped, axis=0, mode="clip")
        if cold_rows.shape[0]:
            cold = jnp.take(cold_rows, mapped - hot_n, axis=0, mode="clip")
            x = jnp.where((mapped >= hot_n)[:, None], cold, x)
    return jnp.where((mapped >= 0)[:, None], x, jnp.zeros((), x.dtype))


@jax.jit
def _padded_gather_tiered(hot_table: jax.Array, mapped: jax.Array,
                          cold_rows: jax.Array) -> jax.Array:
    return tiered_gather(hot_table, mapped, cold_rows)


class Feature:
    """Tiered [N, D] float feature store (reference feature.py:17).

    Parameters mirror the reference constructor (feature.py:25-45):

    rank : local chip index whose HBM serves this handle's gathers
    device_list : chips participating in caching
    device_cache_size : per-chip hot bytes (int or "200M"/"4G" strings)
    cache_policy : "device_replicate" | "p2p_clique_replicate" | "ici_replicate"
    csr_topo : optional CSRTopo — enables degree-ordered hot placement
        when the hot tier cannot hold every row (a wholly hot table with no
        disk tier is stored as given: ``feature_order`` and
        ``csr_topo.feature_order`` stay ``None``)

    Round 14 (disk tier — docs/api.md "Tiered storage"):

    host_memory_budget : host-DRAM byte budget for the middle tier when a
        disk tier is configured (int or "200M" strings; 0 = no DRAM tier
        — HBM misses go straight to disk). WITHOUT ``disk_path`` this
        knob is ignored and the host tail is unbounded (the legacy
        3-tier layout).
    disk_path : flat-file ``.npy`` path for the 4th tier. Static mode
        spills rows beyond ``device_cache_size + host_memory_budget``
        there; adaptive mode writes the FULL stored table (the backing
        file placement moves never have to rewrite).
    adaptive_tiers : overlay a `tiers.TierStore` placement map instead
        of the static shard book — rows then promote/demote between
        disk <-> DRAM <-> HBM in fenced batches (the serve engines'
        ``adapt_tiers``/``apply_placement``). Placement is bit-neutral:
        gathers return identical bytes under any placement.
    disk_read_workers : `pipeline.AsyncReadPool` width for disk reads
        (used when no ``read_pool`` is passed).
    read_pool : share an existing `AsyncReadPool` across features.
    """

    def __init__(
        self,
        rank: int = 0,
        device_list: Optional[Sequence[int]] = None,
        device_cache_size: Union[int, str] = 0,
        cache_policy: str = "device_replicate",
        csr_topo: Optional[CSRTopo] = None,
        dtype=np.float32,
        host_memory_budget: Union[int, str] = 0,
        disk_path: Optional[str] = None,
        adaptive_tiers: bool = False,
        disk_read_workers: int = 4,
        read_pool=None,
    ):
        if cache_policy == "ici_replicate":
            cache_policy = "p2p_clique_replicate"
        if cache_policy not in ("device_replicate", "p2p_clique_replicate"):
            raise ValueError(f"unknown cache_policy: {cache_policy}")
        if adaptive_tiers and disk_path is None:
            raise ValueError(
                "adaptive_tiers needs a disk_path (the full-table backing "
                "file is what makes placement moves bit-neutral)"
            )
        if disk_path is not None and cache_policy != "device_replicate":
            raise ValueError(
                "disk tiers support cache_policy='device_replicate' only "
                "(the clique stripe has no per-rank disk story yet)"
            )
        # dtype of the in-memory tiers: bfloat16 doubles the rows every HBM
        # byte buys (the reference is float32-only, quiver_feature.cu:65-69).
        # The mmap disk tier keeps its on-disk dtype.
        self.dtype = normalize_dtype(dtype)
        self.rank = rank
        self.device_list = list(device_list) if device_list else [rank]
        self.device_cache_size = parse_size(device_cache_size)
        self.cache_policy = cache_policy
        self.csr_topo = csr_topo
        self.feature_order: Optional[np.ndarray] = None  # old id -> stored row
        self._order_dev: Optional[jax.Array] = None
        self.shard_tensor: Optional[ShardTensor] = None
        self.topo = IciTopo.detect()
        self._dim: Optional[int] = None
        self._n: int = 0
        self._local_order_applied = False
        self.mmap_handle_ = None  # disk tier (reference feature.py:84-93)
        self.disk_map: Optional[np.ndarray] = None
        # round-14 disk tier + adaptive placement
        self.host_memory_budget = parse_size(host_memory_budget)
        self.disk_path = disk_path
        self.adaptive_tiers = bool(adaptive_tiers)
        self.disk_read_workers = int(disk_read_workers)
        self.read_pool = read_pool
        self.tier_store = None  # tiers.TierStore when adaptive
        self._inv_order: Optional[np.ndarray] = None
        # width of a tiered lookup's cold block, in rows (`calibrate_cold_cap`,
        # or set it as `GraphSageSampler.caps` is set); None: the width of
        # the lookup itself, which every batch fits
        self.cold_cap: Optional[int] = None
        self.cold_overflow = 0  # tiered lookups whose cold rows passed cold_cap
        self._free_blocks: collections.deque = collections.deque()
        # observe-only workload tap (round 13): when a tier-aware
        # HitRateCounter is attached, every eager gather attributes its
        # rows per tier (attribute_gather_tiers) — placement telemetry,
        # never control flow
        self.tier_counter = None
        # round-14 row-access tap: a callable fed every VALID gathered
        # STORED row id (`WorkloadMonitor.observe_rows`) — the gather-
        # frequency sketch the tier planner reads. Observe-only too.
        self.row_tap = None
        # round-18: a callable (disk-LOCAL ids -> bool mask) naming rows
        # a flush-ahead prefetch staged in DRAM — installed by whoever
        # runs the prefetch (the train pipeline for static disk tails;
        # adaptive stores carry their own PrefetchBuffer) so attribution
        # can report `disk_prefetched` honestly. Observe-only.
        self.disk_staged = None

    # ------------------------------------------------------------------ build
    def from_cpu_tensor(self, cpu_tensor) -> None:
        """Ingest the full feature table and tier it (reference
        feature.py:195-281)."""
        arr = np.asarray(cpu_tensor)
        if arr.ndim != 2:
            raise ValueError("features must be [N, D]")
        arr = arr.astype(self.dtype, copy=False)
        self._n, self._dim = arr.shape
        row_bytes = self._dim * self.dtype.itemsize
        cache_rows = min(self.device_cache_size // row_bytes, self._n)
        # chips whose HBM holds the hot set: this one, or the striped clique
        clique = (list(self.topo.get_clique(self.rank))
                  if self.cache_policy == "p2p_clique_replicate" else [self.rank])
        hot_total = min(cache_rows * len(clique), self._n)
        wholly_hot = hot_total >= self._n and self.disk_path is None
        if wholly_hot:
            # rows `lookup_padded` will hand a model: if they are the
            # aggregation kernel's, its imports start beside the upload
            gather_sum.prefetch_pallas(self.dtype, self._dim)

        prev_order = None  # stored row -> caller's row, when reordered
        if (self.csr_topo is not None and not self._local_order_applied
                and not wholly_hot):
            # degree-descending reorder so the cache prefix is hot
            # (reference feature.py:211-215). With every row hot there is no
            # prefix to choose: the reorder would only permute the table and
            # make each lookup gather through `feature_order` to undo it.
            ratio = hot_total / max(self._n, 1)
            if self.disk_path is not None:
                arr, order = reindex_feature(self.csr_topo, arr, ratio)
            else:
                prev_order, order = degree_order(self.csr_topo, ratio)
            self.feature_order = order
            self.csr_topo.feature_order = order
            self._inv_order = None

        if self.disk_path is not None:
            self._build_disk_tiers(arr, cache_rows)
            return

        st = ShardTensor(self.rank, ShardTensorConfig({}), dtype=self.dtype)
        if not wholly_hot:
            # a table with a host tier: the stored order is never made on
            # the host (it would be a second table). Each device shard goes
            # up in pieces gathered from the caller's array, and the host
            # tier is that array, read through the permutation
            rows_of = (lambda lo, hi: slice(lo, hi)) if prev_order is None else (
                lambda lo, hi: prev_order[lo:hi])
            per = cache_rows if self.cache_policy == "device_replicate" else (
                hot_total // max(len(clique), 1))
            cursor = 0
            for dev in clique:
                rows = min(per, hot_total - cursor)
                if rows <= 0:
                    break
                st.append_rows(arr, rows_of(cursor, cursor + rows), dev)
                cursor += rows
            st.append_rows(arr, rows_of(cursor, self._n), CPU_DEVICE)
        elif self.cache_policy == "device_replicate":
            # hot prefix replicated per chip: each rank's Feature handle is
            # built with its own `rank` and stores its own replica, so this
            # handle's shard book holds one device shard + the shared host
            # tail (reference feature.py:219-223,268-274)
            st.append(arr, self.rank)
        else:
            # hot set striped across the ICI clique (reference feature.py:225-265)
            per = hot_total // max(len(clique), 1)
            cursor = 0
            for dev in clique:
                rows = min(per, hot_total - cursor)
                if rows <= 0:
                    break
                st.append(arr[cursor : cursor + rows], dev)
                cursor += rows
            if cursor < self._n:  # what the stripes' equal shares left over
                st.append(arr[cursor:], CPU_DEVICE)
        self.shard_tensor = st

    def _build_disk_tiers(self, arr: np.ndarray, cache_rows: int) -> None:
        """4-tier build (round 14): HBM prefix -> DRAM middle (bounded by
        ``host_memory_budget``) -> flat-file disk tail. ``arr`` is the
        STORED order (degree-reordered when a csr_topo is attached), so
        the prefix placement is the hot head either way. Adaptive mode
        overlays a `tiers.TierStore` with the IDENTICAL initial
        placement — a frozen adaptive store and a static one serve
        bit-identical bytes from the same tiers."""
        row_bytes = self._dim * self.dtype.itemsize
        host_rows = 0
        if self.host_memory_budget > 0:
            host_rows = min(
                self.host_memory_budget // row_bytes, self._n - cache_rows
            )
        if self.read_pool is None:
            from .pipeline import AsyncReadPool

            self.read_pool = AsyncReadPool(self.disk_read_workers)
        if self.adaptive_tiers:
            from .tiers import TierStore

            self.tier_store = TierStore.build(
                arr, self.disk_path, hbm_rows=cache_rows,
                host_rows=host_rows, rank=self.rank,
                read_pool=self.read_pool,
            )
            self.shard_tensor = None
            return
        st = ShardTensor(self.rank, ShardTensorConfig({}), dtype=self.dtype)
        if cache_rows > 0:
            st.append(arr[:cache_rows], self.rank)
        if host_rows > 0:
            st.append(arr[cache_rows : cache_rows + host_rows], CPU_DEVICE)
        if cache_rows + host_rows < self._n:
            st.append_disk(
                arr[cache_rows + host_rows :], self.disk_path,
                read_pool=self.read_pool,
            )
        self.shard_tensor = st

    @classmethod
    def from_mmap(cls, mmap_array, device_config: DeviceConfig, **kwargs) -> "Feature":
        """Build from an np.memmap without materialising it (reference
        from_mmap feature.py:84-192 — the disk tier). The hot prefix is read
        into HBM; the cold tail stays mmap-backed (reads hit page cache/disk)."""
        self = cls(
            rank=device_config.device_list[0] if device_config.device_list else 0,
            device_list=device_config.device_list,
            device_cache_size=device_config.device_cache_size,
            **kwargs,
        )
        n, d = mmap_array.shape
        self._n, self._dim = n, d
        cache_rows = min(
            parse_size(device_config.device_cache_size) // (d * self.dtype.itemsize), n
        )
        st = ShardTensor(self.rank, ShardTensorConfig({}), dtype=self.dtype)
        if cache_rows > 0:
            # cast on host BEFORE the device_put: uploading f32 then casting
            # on device would double the uploaded bytes
            st.append(np.asarray(mmap_array[:cache_rows]).astype(self.dtype), self.rank)
        if cache_rows < n:
            cold = mmap_array[cache_rows:]
            if isinstance(cold, np.memmap) or cold.dtype != np.float32:
                # keep the memmap as the cold tier without copying when possible
                cold = cold if isinstance(cold, np.memmap) else np.asarray(cold, np.float32)
            st.cpu_tensor = cold
            from .shard_tensor import Offset

            st.cpu_offset = Offset(cache_rows, n)
            st._n_rows = n
            st._dim = d
        self.shard_tensor = st
        return self

    def set_mmap_file(self, path: str, disk_map) -> None:
        """Attach a disk tier (reference feature.py:84-88): ``path`` is an
        ``np.save``'d [N_total, D] array opened with ``mmap_mode='r'``;
        ``disk_map[global_id]`` is the in-memory row for cached ids and
        ``< 0`` for ids resident only on disk."""
        self.mmap_handle_ = np.load(path, mmap_mode="r")
        self.disk_map = np.asarray(disk_map).astype(np.int64).reshape(-1)
        if self._dim is None:
            self._dim = int(self.mmap_handle_.shape[1])

    def read_mmap(self, ids) -> jax.Array:
        """Read rows from the disk tier by GLOBAL node id (reference
        feature.py:89-93); one page-cache-friendly host read + one H2D.
        Out-of-range ids (sampler sentinel padding) yield zero rows, same
        as every other lookup path (numpy would silently wrap negatives)."""
        ids = np.asarray(ids).astype(np.int64).reshape(-1)
        oob = (ids < 0) | (ids >= self.mmap_handle_.shape[0])
        rows = np.asarray(self.mmap_handle_[np.where(oob, 0, ids)], dtype=np.float32)
        if oob.any():
            rows[oob] = 0.0
        return jnp.asarray(rows)

    # ----------------------------------------------------------------- lookup
    def __getitem__(self, node_idx) -> jax.Array:
        """Gather features for (original) node ids; remaps through
        feature_order then hits the tiered ShardTensor (reference
        feature.py:296-333). Out-of-range ids (e.g. the sampler's
        sentinel padding) yield zero rows. With a disk tier attached
        (:meth:`set_mmap_file`), ids whose ``disk_map`` entry is negative
        are read from the mmap and merged (reference feature.py:309-333)."""
        if self.mmap_handle_ is not None:
            return self._getitem_with_disk(node_idx)
        ids, invalid = self._map_ids(node_idx)
        if self.tier_counter is not None:
            self._attribute(ids, valid=~invalid)
        if self.row_tap is not None:
            self.row_tap(ids[~invalid])
        rows = self.gather_stored(ids)
        if invalid.any():
            rows = rows * jnp.asarray(~invalid, rows.dtype)[:, None]
        return rows

    def _map_ids(self, node_idx):
        """(stored_rows, invalid_mask) for a lookup batch — the id remap
        every gather path shares. Invalid lanes map to stored row 0 and
        are zeroed by the caller."""
        ids = np.asarray(node_idx).astype(np.int64).reshape(-1)
        if self._local_order_applied:
            # distributed path: ids are GLOBAL but self._n is the LOCAL row
            # count, so validity must come from the remap itself —
            # feature_order[gid] < 0 means this host does not own gid
            oob = (ids < 0) | (ids >= self.feature_order.shape[0])
            mapped = self.feature_order[np.where(oob, 0, ids)]
            invalid = oob | (mapped < 0)
            ids = np.where(invalid, 0, mapped)
        else:
            invalid = (ids < 0) | (ids >= self._n)
            if invalid.any():
                ids = np.where(invalid, 0, ids)
            if self.feature_order is not None:
                ids = self.feature_order[ids]
        return ids, invalid

    def _attribute(self, stored: np.ndarray, valid: np.ndarray) -> None:
        """Observe-only per-tier attribution of a gather (round 13/14):
        static shard books count by offset range; adaptive stores by the
        LIVE placement map (hbm/host/disk as placed right now)."""
        tc = self.tier_counter
        if self.tier_store is not None:
            split = self.tier_store.tier_split(stored[valid])
            for tier, n in split.items():
                if n:
                    tc.hit(n, tier=tier)
            return
        attribute_gather_tiers(
            self.shard_tensor, self.rank, stored, tc, valid=valid,
            staged=self.disk_staged,
        )

    def gather_stored(self, stored) -> jax.Array:
        """Gather by STORED row id through whichever store backs this
        feature (static shard book or adaptive tier store) — the surface
        `QuantizedFeature` and the tests' oracles share."""
        if self.tier_store is not None:
            return self.tier_store.gather(stored)
        return self.shard_tensor[stored]

    def tier_bytes(self) -> Dict[str, int]:
        """Live per-tier byte footprint (adaptive stores report the
        CURRENT placement — a demotion batch shrinks ``device``
        immediately; the honest-accounting pin in tests/test_tiers.py)."""
        if self.tier_store is not None:
            return self.tier_store.tier_bytes()
        if self.shard_tensor is not None:
            return self.shard_tensor.tier_bytes()
        return {}

    def stored_rows_of(self, node_ids) -> np.ndarray:
        """Node id -> stored row (-1 for out-of-range / unowned ids) —
        how the tier planner maps sketch keys into placement space."""
        ids = np.asarray(node_ids).astype(np.int64).reshape(-1)
        stored, invalid = self._map_ids(ids)
        return np.where(invalid, -1, stored)

    def node_ids_of_stored(self, stored) -> np.ndarray:
        """Stored row -> node id (inverse of ``feature_order``; identity
        without a reorder) — how a placement batch names the embedding-
        cache entries it must invalidate."""
        stored = np.asarray(stored, np.int64).reshape(-1)
        if self.feature_order is None:
            return stored
        if self._inv_order is None:
            order = self.feature_order
            valid = order >= 0
            size = int(order[valid].max()) + 1 if valid.any() else 0
            inv = np.full(size, -1, np.int64)
            inv[order[valid]] = np.nonzero(valid)[0]
            self._inv_order = inv
        return self._inv_order[stored]

    def _getitem_with_disk(self, node_idx) -> jax.Array:
        """Disk-mask merge (reference feature.py:309-333): ``disk_map`` splits
        the batch into mmap reads (entry < 0, read by global id) and
        in-memory rows (entry = local row into the shard book)."""
        ids = np.asarray(node_idx).astype(np.int64).reshape(-1)
        oob = (ids < 0) | (ids >= self.disk_map.shape[0])
        safe = np.where(oob, 0, ids)
        disk_index = self.disk_map[safe]
        disk_mask = (disk_index < 0) & ~oob
        mem_mask = (disk_index >= 0) & ~oob
        out = np.zeros((ids.shape[0], self.dim), np.float32)
        tc = self.tier_counter
        if disk_mask.any():
            if tc is not None:
                tc.hit(int(disk_mask.sum()), tier="disk")
            out[disk_mask] = np.asarray(self.mmap_handle_[ids[disk_mask]], np.float32)
        if mem_mask.any():
            if tc is not None:
                attribute_gather_tiers(
                    self.shard_tensor, self.rank, disk_index[mem_mask], tc
                )
            out[mem_mask] = np.asarray(self.shard_tensor[disk_index[mem_mask]])
        return jnp.asarray(out)

    # ---------------------------------------------------- tiered padded lookup
    def tiered_tables(self):
        """``(hot_table, hot_rows, host_shard)`` of a table this chip's HBM
        and the host's DRAM hold between them (one device shard at most, a
        host shard, no disk tier, a static shard book): what the tiered
        padded lookup serves. Anything else raises."""
        st = self.shard_tensor
        if (st is None or st.cpu_tensor is None or st.disk_shard is not None
                or len(st.device_shards) > 1 or self._local_order_applied
                or any(dev != self.rank for dev, _, _ in st.device_shards)):
            raise ValueError(
                "the tiered padded lookup serves one hot shard on this chip over "
                "a host tail; use __getitem__ or the mesh-sharded gather"
            )
        if not st.device_shards:
            return jnp.zeros((0, self.dim), self.dtype, device=_device_of(self.rank)), 0, st.cpu_tensor
        _, table, off = st.device_shards[0]
        return table, off.end - off.start, st.cpu_tensor

    def stage_tiered(self, node_idx, count: Optional[int] = None,
                     clip: bool = True) -> TieredStage:
        """The HOST half of the tiered padded lookup, no device call: remap
        through ``feature_order``, split hot from cold at the cache
        boundary, gather the cold rows (native, threaded) into a block of
        ``cold_cap`` rows. Lanes from ``count`` on (a dedup sample's padding
        tail) ask for nothing. Out-of-range ids are clipped into the table
        as `lookup_padded` clips them, or with ``clip=False`` answered with
        zero rows as the eager lookups answer them.

        A batch with more cold rows than ``cold_cap`` is counted
        (``quiver.feature.cold_overflow``) and answered all the same, in a
        block of the next multiple of ``cold_cap`` rows: a shape the device
        half has not seen, so a program is built for it. The block comes
        from a free list; `release_block` hands it back once copied."""
        _, hot_rows, host = self.tiered_tables()
        order = self.feature_order
        with trace_scope("quiver.feature.lookup", ordered=int(order is not None)) as span:
            ids = np.asarray(node_idx).reshape(-1)
            width = ids.shape[0]
            live = width if count is None else min(int(count), width)
            ids = ids[:live]
            mapped = np.full(width, -1, np.int32)
            if clip:
                ids, stored = self._clipped_stored(ids)
            else:
                ids = ids.astype(np.int64)
                bad = (ids < 0) | (ids >= self._n)
                ids[bad] = 0
                stored = np.where(bad, -1, ids if order is None else order[ids])
            mapped[:live] = stored
            (cold_at,) = np.nonzero(stored >= hot_rows)
            n_cold = int(cold_at.shape[0])
            mapped[cold_at] = hot_rows + np.arange(n_cold, dtype=np.int32)
            cap = width if self.cold_cap is None else int(self.cold_cap)
            if n_cold > cap:
                self.cold_overflow += 1
                observe("quiver.feature.cold_overflow", n_cold - cap)
                cap *= -(-n_cold // cap)
            span.set(cold=n_cold)
            block = self._take_block(cap)
            with trace_scope("quiver.feature.cold_gather"):
                if isinstance(host, HostRows):
                    # the caller's own rows: no detour through the stored order
                    cpu_kernels.gather_rows(host.base, ids[cold_at], out=block)
                else:
                    host_gather(host, stored[cold_at] - hot_rows, out=block)
            observe("quiver.feature.cold_rows", n_cold)
        return TieredStage(mapped, block, n_cold)

    def _clipped_stored(self, ids):
        """``(ids, stored rows)`` of lookup ids clipped into the table, as
        the padded gathers clip them."""
        ids = np.clip(np.asarray(ids).reshape(-1), 0, self._n - 1).astype(np.int64)
        return ids, ids if self.feature_order is None else self.feature_order[ids]

    def _take_block(self, rows: int) -> np.ndarray:
        while self._free_blocks:
            block = self._free_blocks.pop()
            if block.shape[0] == rows:
                return block
        return np.zeros((rows, self.dim), self.dtype)

    def release_block(self, stage: TieredStage) -> None:
        """``stage``'s cold block may be written again: its copy is on the
        device (`jax.device_put` reads the host's memory until then)."""
        self._free_blocks.append(stage.cold_rows)

    def calibrate_cold_cap(self, probe_ids, counts=None, margin: float = 1.2,
                           granule: int = 4096, set_cap: bool = True) -> int:
        """Probe-batch calibration of ``cold_cap``, as
        `GraphSageSampler.calibrate_caps` calibrates the sampler's caps:
        the most cold rows any probe batch asks for (``probe_ids``: the
        ``n_id`` of >= 8 representative samples, ``counts`` their valid
        lengths), times ``margin``, rounded up to ``granule``."""
        _, hot_rows, _ = self.tiered_tables()
        worst = 0
        for i, ids in enumerate(probe_ids):
            ids = np.asarray(ids).reshape(-1)
            _, stored = self._clipped_stored(ids if counts is None else ids[: int(counts[i])])
            worst = max(worst, int((stored >= hot_rows).sum()))
        cap = int(-(-worst * margin // granule) * granule)
        if set_cap:
            self.cold_cap = cap
        return cap

    def upload_tiered(self, stage: TieredStage):
        """``(mapped, cold_rows)`` of ``stage`` on this chip. The span
        ``quiver.feature.h2d`` runs from the first copy's start until both
        arrays are READY: a wait, so that the span is the link's time and
        the block can go back to the free list. On `TrainPipeline`'s upload
        thread it delays nothing."""
        device = _device_of(self.rank)
        with trace_scope("quiver.feature.h2d", bytes=int(stage.cold_rows.nbytes)):
            placed = jax.block_until_ready((jax.device_put(stage.mapped, device),
                                            jax.device_put(stage.cold_rows, device)))
        if device.platform != "cpu":  # the CPU backend may alias the host's block
            self.release_block(stage)
        return placed

    def _lookup_padded_tiered(self, node_idx, valid, count) -> jax.Array:
        hot, _, _ = self.tiered_tables()
        mapped, cold = self.upload_tiered(self.stage_tiered(node_idx, count))
        rows = _padded_gather_tiered(hot, mapped, cold)
        if valid is not None:
            rows = rows * valid[:, None].astype(rows.dtype)
        return rows

    def lookup_padded(self, node_idx: jax.Array, valid: Optional[jax.Array] = None,
                      count: Optional[int] = None) -> jax.Array:
        """Jit-friendly gather for padded id arrays; already jitted
        internally (the table is passed as an ARGUMENT to the jitted
        program — never ``jax.jit`` a bound method of this class, or the
        table becomes a baked-in compile-time constant).

        A table this chip's HBM holds whole is one gather program. A table
        with a host tier (one hot shard here, the rest in host DRAM) is the
        synchronous composition of the tiered lookup's two halves:
        `stage_tiered` on the host (it reads ``node_idx`` back), the copy
        (`upload_tiered`) and `tiered_gather` on the chip as the program
        `_padded_gather_tiered`; `pipeline.TrainPipeline` runs the same
        halves on its threads. ``count`` (tiered tables only) is the length
        of the valid prefix: lanes past it fetch nothing and read zero.
        Striped and disk-backed tables go through ``__getitem__`` or
        `quiver_tpu.parallel.collectives.sharded_gather` on a mesh.

        Out-of-range ids are CLIPPED into the table (negative -> id 0,
        ``>= N`` -> id N-1), never filled: see `validate_lookup_ids`. The
        program is `_padded_gather`, or `_padded_gather_ordered` when a
        ``feature_order`` (`set_local_order`) stands between ids and stored
        rows; the span carries which as ``ordered``.
        """
        st = self.shard_tensor
        if st is not None and st.cpu_tensor is not None:
            return self._lookup_padded_tiered(node_idx, valid, count)
        if st is None or len(st.device_shards) != 1:
            raise ValueError(
                "lookup_padded needs a fully HBM-resident feature; "
                "use __getitem__ (tiered) or the mesh-sharded gather"
            )
        table = st.device_shards[0][1]
        ordered = self.feature_order is not None
        with trace_scope("quiver.feature.lookup", ordered=int(ordered)):
            if ordered:
                if self._order_dev is None:
                    self._order_dev = jnp.asarray(self.feature_order)
                rows = _padded_gather_ordered(table, self._order_dev, node_idx)
            else:
                rows = _padded_gather(table, node_idx)
            if valid is not None:
                rows = rows * valid[:, None].astype(rows.dtype)
        return rows

    def validate_ids(self, node_idx) -> np.ndarray:
        """Strict opt-in id check: raise instead of the lookup paths'
        silent clip/zero-fill. See :func:`validate_lookup_ids`."""
        return validate_lookup_ids(
            node_idx, self._n, self.feature_order, self._local_order_applied
        )

    # ------------------------------------------------------------------ misc
    @property
    def shape(self):
        return (self._n, self._dim)

    @property
    def dim(self) -> int:
        return self._dim or 0

    def size(self, axis: int) -> int:
        return self.shape[axis]

    def set_local_order(self, local_order) -> None:
        """Distributed local remap (reference feature.py:283-294): after
        cross-host partitioning, this host stores only its rows; map
        global id -> local row."""
        local_order = np.asarray(local_order, dtype=np.int64)
        order = np.full(int(local_order.max()) + 1 if local_order.size else 0, -1, np.int64)
        order[local_order] = np.arange(local_order.shape[0], dtype=np.int64)
        self.feature_order = order
        self._order_dev = None
        self._inv_order = None
        self._local_order_applied = True

    # ------------------------------------------------------- ipc-compat shims
    def share_ipc(self):
        """Reference feature.py:383-445; a pickleable handle."""
        return dict(
            rank=self.rank,
            device_list=self.device_list,
            device_cache_size=self.device_cache_size,
            cache_policy=self.cache_policy,
            shard_ipc=None if self.shard_tensor is None else self.shard_tensor.share_ipc(),
            feature_order=self.feature_order,
            shape=(self._n, self._dim),
            dtype=str(self.dtype),
        )

    @classmethod
    def new_from_ipc_handle(cls, rank: int, ipc_handle) -> "Feature":
        self = cls(
            rank=rank,
            device_list=ipc_handle["device_list"],
            device_cache_size=ipc_handle["device_cache_size"],
            cache_policy=ipc_handle["cache_policy"],
            dtype=ipc_handle.get("dtype", np.float32),
        )
        self._n, self._dim = ipc_handle["shape"]
        self.feature_order = ipc_handle["feature_order"]
        if ipc_handle["shard_ipc"] is not None:
            self.shard_tensor = ShardTensor.new_from_share_ipc(ipc_handle["shard_ipc"], rank)
        return self

    lazy_from_ipc_handle = new_from_ipc_handle


class PartitionInfo:
    """Cross-host partition metadata (reference feature.py:461-526).

    global2host maps node id -> owning host; an optional replicate set marks
    ids this host also holds locally.
    """

    def __init__(self, device, host: int, hosts: int, global2host, replicate=None):
        self.device = device
        self.host = host
        self.hosts = hosts
        self.global2host = np.asarray(global2host, dtype=np.int32)
        self.replicate = None if replicate is None else np.asarray(replicate, dtype=np.int64)
        self._build_global2local()

    def _build_global2local(self):
        """global id -> owner-local row, for EVERY host (reference
        feature.py:484-508 ranks each host's owned ids 0..n_h-1)."""
        n = self.global2host.shape[0]
        self.global2local = np.zeros(n, dtype=np.int64)
        for h in range(self.hosts):
            owned = np.nonzero(self.global2host == h)[0]
            self.global2local[owned] = np.arange(owned.shape[0])
        local_mask = self.global2host == self.host
        if self.replicate is not None:
            # replicated ids live after this host's owned rows, in the order
            # given (reference feature.py:497-505)
            local_mask = local_mask.copy()
            owned_count = int(local_mask.sum())
            rep = self.replicate[~local_mask[self.replicate]]
            self.global2local[rep] = owned_count + np.arange(rep.shape[0])
            local_mask[rep] = True
        local_ids = np.nonzero(local_mask)[0]
        self.local_ids = local_ids
        self.local_mask = local_mask

    def dispatch(self, ids: np.ndarray):
        """Split a request batch by owning host (reference feature.py:510-526).
        Returns (per_host_ids list, local_ids, orig_pos_per_host, local_pos)."""
        ids = np.asarray(ids).astype(np.int64)
        local = self.local_mask[ids]
        local_pos = np.nonzero(local)[0]
        remote_pos = np.nonzero(~local)[0]
        owner = self.global2host[ids[remote_pos]]
        per_host, per_pos = [], []
        for h in range(self.hosts):
            sel = remote_pos[owner == h]
            per_host.append(ids[sel])
            per_pos.append(sel)
        return per_host, ids[local_pos], per_pos, local_pos


class DistFeature:
    """Multi-host feature collection (reference feature.py:529-567): dispatch
    ids by owner, exchange over the communication backend, merge with the
    local gather. Synchronous/collective across hosts — every host must call
    ``__getitem__`` together (reference docstring feature.py:530-535)."""

    def __init__(self, feature: Feature, info: PartitionInfo, comm):
        self.feature = feature
        self.info = info
        self.comm = comm

    def __getitem__(self, ids) -> jax.Array:
        ids = np.asarray(ids).astype(np.int64)
        per_host, local_ids, per_pos, local_pos = self.info.dispatch(ids)
        # owners answer in their local row space (reference set_local_order
        # remap, feature.py:283-294 + comm.py:165-168 local gather)
        per_host_local = [self.info.global2local[h_ids] for h_ids in per_host]
        if jax.process_count() == 1 and not any(len(h) for h in per_host_local):
            # fully shard-local lookup: nothing to exchange, skip the
            # collective. Single-controller ONLY — in multi-process mode
            # every host must enter the collective together, so a
            # data-dependent skip would desync it (the serve engines hit
            # this path on every flush when the partition is k-hop closed,
            # e.g. community-partitioned serving shards)
            remote_feats: List[Optional[jax.Array]] = [None] * self.info.hosts
        else:
            remote_feats = self.comm.exchange(per_host_local)
        out = np.zeros((ids.shape[0], self.feature.dim), np.float32)
        if local_ids.size:
            # a Feature with set_local_order applied remaps global ids itself
            # (reference feature.py:283-294); otherwise localize here
            if self.feature._local_order_applied:
                out[local_pos] = np.asarray(self.feature[local_ids])
            else:
                out[local_pos] = np.asarray(self.feature[self.info.global2local[local_ids]])
        for h, feats in enumerate(remote_feats):
            if feats is not None and per_pos[h].size:
                out[per_pos[h]] = np.asarray(feats)
        return jnp.asarray(out)
