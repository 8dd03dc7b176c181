"""Cross-host sharded serving: seed-ownership routing over the
`HostRankTable` exchange.

The single-host `ServeEngine` (rounds 8-9) turns a request stream into
efficient fixed-shape device work, but its QPS ceiling is one chip's
sample+forward throughput and one host's feature tier. The training side
already scales past one host by PARTITIONING the data and moving requests
to their owners (`HostRankTable` / `DistFeature` / `TpuComm.exchange` —
the reference's ``PartitionInfo``+``DistFeature`` multi-host layer); this
module applies the same owner-compute-then-exchange shape to serving, the
pattern the PyTorch-Direct / GPU-initiated-access line uses to keep
feature fetch off the slow path: **move the request to the data, not the
rows to the request.**

Topology of a request:

1. A front-end **router** (`DistServeEngine`) accepts single-node
   requests, dedupes/coalesces them within a flush window, and applies the
   same max_batch / max_delay_ms flush policy as the single-host engine.
2. Each router flush **splits its (deduped) seed batch by owner**
   (``global2host[seed]``, `HostRankTable` host ids) and forwards the
   per-owner sub-batches through the serve-shaped exchange
   (`TpuComm.exchange_serve`: seed ids ship out over the same all_to_all
   the feature exchange rides; LOGITS rows come back instead of feature
   rows).
3. Each **owner** runs its local pipelined `ServeEngine` — micro-batching,
   bucketed shapes, embedding cache, bounded ``max_in_flight`` window —
   against only its shard of topology + features. Aggregate QPS scales
   with hosts because each shard samples/forwards a batch ~1/H as wide,
   and per-host HBM holds ~1/H of the tables (exact 1/H when the
   partition is k-hop closed, e.g. community partitions; the halo the
   closure adds on other partitions is reported, never hidden — see
   `shard_topology_by_owner`). Under the default
   ``feature_residency="closure"`` each owner materializes its closure's
   feature rows at build time (`ClosureFeature`) so the whole shard
   dispatch is the FUSED one-program serve step — one execute call per
   owner flush; ``"exchange"`` keeps the round-10 per-flush on-demand
   feature exchange (`DistFeature`) and the split dispatch.
4. Results **scatter back by request id** and re-interleave into the
   router's dispatch-log order.

Bit-parity contract (the round-8/9 contract, extended): every served
logits row is bit-identical to the offline `inference.batch_logits` replay
of the OWNING shard's dispatch log — through a sampler over the FULL graph
(`replay_shard_oracle`), because a shard's halo-closed topology produces
draws bit-equal to the full graph's for owned seeds. At ``hosts=1`` the
engine degenerates to the single-host `ServeEngine` bit-for-bit (same
dispatch log, same key stream, same logits) at any ``max_in_flight``.

Execution modes:

- ``exchange="collective"``: sub-batches and logits ride the real
  `_a2a_ids_jit`/`_a2a_rows_jit` collectives over an H-device mesh (the
  hermetic CPU-mesh simulation of an H-host pod; on a real pod each
  process drives its own shard — `TpuComm.exchange_serve` multi-process
  mode, exercised by tests/dist_worker.py's lockstep serve mode).
- ``exchange="host"``: the router calls owner engines directly (and the
  shard features exchange through a host-side loopback). Value-identical;
  for environments without H devices.

Round 16 — the fleet is ELASTIC: ``scale(hosts=H±k)`` / ``rebalance()``
migrate seed ownership live, one bounded contiguous range at a time
(`plan_migration_ranges` x ``migrate_batch_seeds``). Per range: the
destination's halo-closure shard and feature rows build OUTSIDE any
fence (`closure_masks` is incremental — k-hop closures are
union-homomorphic, so the destination's new masks are old-OR-range)
while the old owner keeps serving; then a per-range fence (the
`update_params` drain, held only for the pointer flip) swaps the
destination engine, flips ``global2host[lo:hi]``, bumps
``ownership_epoch``, and invalidates exactly the migrated seeds'
router-cache/old-owner-cache entries. Replaced engines retire with
their dispatch logs and `replay_fleet_oracle` replays them like live
owners, so completed rows stay bit-identical to offline replay across
every epoch. `FaultSpec(at="migration")` kills mid-handoff: a dead
destination rolls the range back, a dead source rolls it forward —
deterministically. ``stop(drain=True)`` settles an open range before
the drain deadline starts. See docs/api.md "Elastic fleet".
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import comm as comm_mod
from ..comm import HostRankTable, TpuComm, round_up_pow2
from ..feature import DistFeature, Feature, PartitionInfo
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
    register_hit_rate,
)
from ..utils import CSRTopo
from .cache import EmbeddingCache
from .faults import OwnerFault
from .engine import (
    DEFAULT_TENANT,
    ResultBatch,
    ServeConfig,
    ServeEngine,
    ServeResult,
    ServeStats,
    ShedError,
    _PendingStripes,
    _Slot,
    _admit_batch_vector,
    _admit_chunk_fast,
    _batch_uniq,
    _resolve_block,
    abandon_undrained,
    register_tenant_latency,
    resolve_tenants,
    shed_decision,
    weighted_drain_keys,
)

# pseudo-owner id for the local hot-set replica in a routed flush's owner
# split / dispatch log: seeds routed here are answered on the router's own
# host and never enter the serve exchange (round 15, ROADMAP item 3a)
REPLICA_HOST = -2

# bound on the hedge/shed policy logs (ring semantics, newest win): the
# conditions that fill them — sustained overload, a long-dead owner — are
# exactly when an unbounded list would leak until OOM
POLICY_LOG_CAP = 65536


class OwnerTimeout(RuntimeError):
    """A routed owner sub-batch missed its ``hedge_deadline_ms`` — the
    hedge machinery re-routes the sub-batch; the slow owner's eventual
    answer is discarded."""


def resolve_exchange_mode(exchange: str, hosts: int) -> str:
    """The exchange mode a fleet build runs: ``"auto"`` means
    ``"collective"`` when this process sees at least ``hosts`` devices and
    ``"host"`` otherwise. The ONE place that choice is made — it follows
    the device count, so the same call gives another answer on another
    machine; a built engine records the outcome as ``exchange_mode``."""
    if exchange != "auto":
        return exchange
    import jax

    return "collective" if len(jax.devices()) >= hosts else "host"


def contiguous_partition(n_nodes: int, hosts: int) -> np.ndarray:
    """Balanced contiguous ``global2host`` map: host h owns rows
    ``[h*ceil(N/H), ...)`` (the same contiguous-range convention the
    row-sharded topology uses). int32 [N]."""
    if hosts < 1 or n_nodes < 1:
        raise ValueError("need hosts >= 1 and n_nodes >= 1")
    per = -(-n_nodes // hosts)
    return np.minimum(np.arange(n_nodes, dtype=np.int64) // per, hosts - 1).astype(
        np.int32
    )


def plan_migration_ranges(
    current: np.ndarray, target: np.ndarray, batch_seeds: int
) -> List[Tuple[int, int, int, int]]:
    """Cut the ownership delta ``current != target`` into the round-16
    migration units: ``[(lo, hi, src, dst)]`` contiguous id ranges, each
    with ONE (src, dst) pair and at most ``batch_seeds`` seeds — the
    bounded batches `DistServeEngine.rebalance` hands off one fenced
    flip at a time. Deterministic (ascending id order) so two runs of
    the same plan migrate identical batches in identical order."""
    current = np.asarray(current)
    target = np.asarray(target)
    if current.shape != target.shape:
        raise ValueError("current/target ownership shapes differ")
    batch_seeds = max(int(batch_seeds), 1)
    diff = np.nonzero(current != target)[0]
    ranges: List[Tuple[int, int, int, int]] = []
    if diff.size == 0:
        return ranges
    start = 0
    for i in range(1, diff.size + 1):
        at_boundary = (
            i == diff.size
            or diff[i] != diff[i - 1] + 1
            or current[diff[i]] != current[diff[start]]
            or target[diff[i]] != target[diff[start]]
        )
        if at_boundary:
            lo, hi = int(diff[start]), int(diff[i - 1]) + 1
            src, dst = int(current[lo]), int(target[lo])
            for b in range(lo, hi, batch_seeds):
                ranges.append((b, min(b + batch_seeds, hi), src, dst))
            start = i
    return ranges


def shard_topology_by_owner(
    csr_topo: CSRTopo,
    global2host: np.ndarray,
    host: int,
    hops: int,
    return_closure: bool = False,
    closure_hops: Optional[int] = None,
):
    """Host ``host``'s serving topology shard: the full-id-space CSR with
    adjacency kept ONLY for the ``hops``-hop closure of its owned nodes
    (every other row reads degree 0).

    ``hops`` is the number of EXPANSION hops whose adjacency the shard's
    sampler reads — ``len(sizes) - 1`` for an L-layer sampler, because the
    final hop's frontier is feature-gathered but never expanded. Keeping
    the closure rows bit-identical to the full graph is what makes a shard
    engine's draws for owned seeds bit-equal to a full-graph sampler on
    the same key stream (the parity contract `replay_shard_oracle` tests);
    rows outside the closure are unreachable from owned seeds, so zeroing
    them changes nothing.

    The id space stays GLOBAL (indptr keeps all N+1 rows — ~8 bytes/node,
    small next to edges and features); only the EDGE table shrinks. On a
    k-hop-closed partition (e.g. community partitions, where serving
    shards naturally align with communities) the closure adds nothing and
    each shard holds exactly its 1/H of the edges; on other partitions the
    halo is real replication and ``edge_frac`` reports it honestly.

    Returns ``(shard_topo, stats)`` with stats keys ``owned_nodes``,
    ``closure_nodes``, ``edges_kept``, ``edges_total``, ``edge_frac``;
    with ``return_closure=True``, ``(shard_topo, stats, closure_ids)`` —
    the sorted global ids of the ``closure_hops``-hop closure (default:
    ``hops``). `ClosureFeature` wants ``closure_hops = hops + 1``: the
    final hop's LEAF frontier is feature-gathered but never expanded, so
    leaves live one hop beyond the adjacency closure — that deeper set is
    exactly every node a shard engine can ever gather a row for.
    """
    indptr = np.asarray(csr_topo.indptr, np.int64)
    indices = np.asarray(csr_topo.indices, np.int64)
    g2h = np.asarray(global2host)
    n = indptr.shape[0] - 1
    if g2h.shape[0] != n:
        raise ValueError(f"global2host has {g2h.shape[0]} rows, graph has {n}")
    owned = np.nonzero(g2h == host)[0]
    seed_mask = np.zeros(n, bool)
    seed_mask[owned] = True
    hops = max(int(hops), 0)
    feat_hops = hops if closure_hops is None else max(int(closure_hops), hops)
    topo_closure, closure = closure_masks(
        indptr, indices, seed_mask, hops, feat_hops
    )
    shard, edge_stats = shard_from_mask(csr_topo, topo_closure)
    stats = {
        "owned_nodes": int(owned.shape[0]),
        "closure_nodes": int(topo_closure.sum()),
        "feature_closure_nodes": int(closure.sum()),
        **edge_stats,
    }
    if return_closure:
        return shard, stats, np.nonzero(closure)[0]
    return shard, stats


def closure_masks(
    indptr: np.ndarray,
    indices: np.ndarray,
    seed_mask: np.ndarray,
    hops: int,
    feat_hops: int,
    src_per_edge: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """The closure BFS shared by `shard_topology_by_owner` and the
    round-16 INCREMENTAL migration path: ``(topo_mask, feat_mask)`` bool
    [N] — the ``hops``-hop adjacency closure and the ``feat_hops``-hop
    feature closure of ``seed_mask``. Edge-parallel and vectorized (a
    per-frontier-node python loop is O(minutes) at products scale): src
    id per CSR slot built once (pass ``src_per_edge`` to amortize it
    across calls — the migration loop does), each hop masks the
    frontier's edges and uniques their endpoints.

    k-hop reachability is union-homomorphic — ``closure(A | B) ==
    closure(A) | closure(B)`` at any fixed depth — which is exactly what
    makes a RANGE handoff incremental: the destination's new masks are
    its old masks OR'd with the migrated range's, no BFS over the rows
    it already held."""
    n = indptr.shape[0] - 1
    if src_per_edge is None:
        src_per_edge = np.repeat(
            np.arange(n, dtype=np.int64), (indptr[1:] - indptr[:-1])
        )
    closure = seed_mask.copy()
    frontier_mask = closure.copy()
    topo_closure = closure.copy() if hops == 0 else None
    for hop in range(feat_hops):
        if not frontier_mask.any():
            break
        nxt = np.unique(indices[frontier_mask[src_per_edge]])
        nxt = nxt[~closure[nxt]]
        if nxt.size == 0:
            break
        closure[nxt] = True
        frontier_mask = np.zeros(n, bool)
        frontier_mask[nxt] = True
        if hop + 1 == hops:
            topo_closure = closure.copy()
    if topo_closure is None:  # BFS exhausted the graph before `hops`
        topo_closure = closure.copy()
    return topo_closure, closure


def shard_from_mask(
    csr_topo: CSRTopo, topo_mask: np.ndarray,
    src_per_edge: Optional[np.ndarray] = None,
) -> Tuple[CSRTopo, Dict[str, float]]:
    """Materialize the global-id-space shard CSR keeping adjacency only
    for rows in ``topo_mask`` (every other row reads degree 0) — the
    build half of `shard_topology_by_owner`, shared with the migration
    path so an extended owner shard is constructed by the byte-for-byte
    same code as a built one. Pass ``src_per_edge`` to amortize the
    O(E) repeat across calls, exactly like `closure_masks`."""
    indptr = np.asarray(csr_topo.indptr, np.int64)
    indices = np.asarray(csr_topo.indices, np.int64)
    n = indptr.shape[0] - 1
    if src_per_edge is None:
        src_per_edge = np.repeat(
            np.arange(n, dtype=np.int64), (indptr[1:] - indptr[:-1])
        )
    deg = np.where(topo_mask, indptr[1:] - indptr[:-1], 0)
    new_indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=new_indptr[1:])
    keep_edge = topo_mask[src_per_edge]
    new_indices = indices[keep_edge]
    new_weights = (
        None
        if csr_topo.edge_weights is None
        else np.asarray(csr_topo.edge_weights, np.float32)[keep_edge]
    )
    shard = CSRTopo(indptr=new_indptr, indices=new_indices, edge_weights=new_weights)
    stats = {
        "edges_kept": int(new_indices.shape[0]),
        "edges_total": int(indices.shape[0]),
        "edge_frac": (
            float(new_indices.shape[0]) / float(max(indices.shape[0], 1))
        ),
    }
    return shard, stats


def shard_topology_for_seeds(
    csr_topo: CSRTopo,
    seed_ids: np.ndarray,
    hops: int,
    closure_hops: Optional[int] = None,
):
    """`shard_topology_by_owner` for an EXPLICIT seed set instead of an
    ownership map: the hops-hop halo-closure topology of ``seed_ids``
    (every other row reads degree 0), in the GLOBAL id space. This is the
    hot-set replica's topology (round 15): a sampler over it draws
    bit-identically to a full-graph sampler for the replicated seeds —
    the same closure argument the owner shards ride. Returns
    ``(shard_topo, stats, closure_ids)``."""
    n = csr_topo.indptr.shape[0] - 1
    seed_ids = np.asarray(seed_ids, np.int64)
    if seed_ids.size and (seed_ids.min() < 0 or seed_ids.max() >= n):
        raise ValueError(f"seed ids outside [0, {n})")
    mask = np.ones(n, np.int32)  # host 1 = everyone else
    mask[seed_ids] = 0           # host 0 = the replicated set
    return shard_topology_by_owner(
        csr_topo, mask, 0, hops, return_closure=True,
        closure_hops=closure_hops,
    )


class LoopbackComm:
    """Host-side stand-in for `TpuComm` in ``exchange="host"`` mode: the
    same `register_local_table` / `exchange` surface, answered by direct
    numpy indexing instead of collectives. Value-identical to the wire
    path (the collectives move bytes, they never transform them), so shard
    features built over it serve bit-identical rows — it just measures
    nothing about the interconnect."""

    def __init__(self, hosts: int):
        self.table = HostRankTable(hosts, 1)
        self._blocks: Dict[int, np.ndarray] = {}

    def register_local_table(self, host: int, rows: np.ndarray) -> None:
        self._blocks[host] = np.asarray(rows, np.float32)

    def exchange(self, host2ids, budget=None):
        res = []
        for j, ids in enumerate(host2ids):
            ids = np.asarray(ids, np.int64)
            res.append(self._blocks[j][ids] if ids.size else None)
        return res


class _ShardFeature:
    """The shard engine's feature view: clip global ids like the raw-table
    `inference.lookup_features` path (sampled ``n_id`` may carry padding
    lanes), then answer owned rows from the local 1/H block and halo rows
    through the feature exchange (`DistFeature`). The clip is what keeps a
    shard engine's forward bit-identical to a raw-full-table engine's on
    the same sample."""

    def __init__(self, dist: DistFeature, n_nodes: int):
        self._dist = dist
        self._n = n_nodes

    @property
    def shape(self):
        return (self._n, self._dist.feature.dim)

    @property
    def dim(self) -> int:
        return self._dist.feature.dim

    @property
    def tier_counter(self):
        """Delegate the observe-only tier tap to the LOCAL feature shard
        (round 14): the owner engine's workload monitor then attributes
        the owned-rows gather per tier — hbm/host/disk of the shard's
        own store; exchanged halo rows are the peer's tiers to count."""
        return self._dist.feature.tier_counter

    @tier_counter.setter
    def tier_counter(self, counter) -> None:
        self._dist.feature.tier_counter = counter

    @property
    def row_tap(self):
        return self._dist.feature.row_tap

    @row_tap.setter
    def row_tap(self, tap) -> None:
        self._dist.feature.row_tap = tap

    def __getitem__(self, n_id):
        ids = np.clip(np.asarray(n_id), 0, self._n - 1)
        return self._dist[ids]


class ClosureFeature:
    """Owner-resident serve features over GLOBAL ids — the fusable shard
    feature (``feature_residency="closure"``).

    Holds the feature rows of the shard's whole ``hops``-hop closure
    (owned + halo — exactly the rows the per-flush `DistFeature` exchange
    would have fetched, materialized ONCE at build time) plus an ``[N]``
    int32 global→row map, so the owner's gather is a pure in-jit
    take-of-take and the FUSED one-dispatch serve program applies
    (`inference.feature_gather_spec` reads `jit_gather_spec`). On a
    k-hop-closed partition the closure adds nothing and residency is
    exactly 1/H of the table; elsewhere the halo is real replication,
    reported in ``shard_topo_stats`` (``closure_nodes`` vs ``owned_nodes``)
    — never hidden.

    Out-of-closure ids map to -1 and clip to row 0: such lanes are
    unreachable from owned seeds (the closure IS the sampler's reachable
    set), so they only ever occur in masked pad lanes the model's
    aggregation zeroes out — the same guarantee every padded pipeline here
    rides. Host ``__getitem__`` runs the identical clip/map/clip/take
    arithmetic, so split-path dispatches and parity replays are
    value-identical to the fused gather.

    ``reserve_rows`` (round-17 streaming graphs) appends zeroed slack
    rows so `install_rows` can land feature rows for nodes that ENTER the
    closure under a graph delta without changing the table's shape —
    sealed AOT executables take the table as an argument, so same-shape
    swaps never recompile. Exhausting the reserve raises
    `stream.StreamCapacityError` (capacity is planned, never silently
    grown)."""

    def __init__(self, rows: np.ndarray, local_map: np.ndarray,
                 reserve_rows: int = 0):
        rows = np.asarray(rows, np.float32)
        if rows.ndim != 2:
            raise ValueError("ClosureFeature wants rows [C, D] and map [N]")
        self._used = rows.shape[0]
        if reserve_rows:
            rows = np.concatenate(
                [rows, np.zeros((int(reserve_rows), rows.shape[1]),
                                np.float32)]
            )
        self._rows = np.ascontiguousarray(rows)
        self._map = np.asarray(local_map, np.int32)
        if self._map.ndim != 1:
            raise ValueError("ClosureFeature wants rows [C, D] and map [N]")
        # hosts=1 (closure == everything): the map is the identity, so the
        # fused gather collapses to the plain-table program — the hosts=1
        # engine then runs the EXACT executable the single-host engine
        # runs (bitwise degeneration by construction, and one fewer
        # compiled program shape)
        self._identity = self._map.shape[0] == self._rows.shape[0] and bool(
            np.array_equal(self._map, np.arange(self._map.shape[0], dtype=np.int32))
        )
        self._dev: Optional[Tuple] = None

    @property
    def shape(self):
        return (self._map.shape[0], self._rows.shape[1])

    @property
    def dim(self) -> int:
        return self._rows.shape[1]

    @property
    def resident_rows(self) -> int:
        """Rows holding real feature data (reserve slack excluded)."""
        return self._used

    @property
    def capacity_rows(self) -> int:
        return self._rows.shape[0]

    def preflight_install(self, node_ids) -> int:
        """Reserve-capacity check for a batch of `install_rows` ids
        WITHOUT mutating: raises the same `StreamCapacityError` an
        install would, so multi-consumer commits (the dist router's
        fleet-wide `update_graph`) can validate every owner before
        mutating any. Returns the fresh slots the batch would take."""
        from ..stream import StreamCapacityError

        node_ids = np.asarray(node_ids, np.int64).reshape(-1)
        if node_ids.size == 0:
            return 0
        fresh = int(np.count_nonzero(
            self._map[np.unique(node_ids)] < 0
        ))
        if self._used + fresh > self._rows.shape[0]:
            raise StreamCapacityError(
                f"ClosureFeature reserve exhausted: batch installs "
                f"{fresh} new rows, {self._rows.shape[0] - self._used} "
                f"free of {self._rows.shape[0]} — rebuild with a larger "
                "reserve_rows"
            )
        return fresh

    def install_rows(self, node_ids, rows) -> int:
        """Land feature rows for nodes newly entering the closure (the
        round-17 incremental extension): each node takes the next free
        reserve slot (a node already mapped is overwritten in place —
        feature rows are static under topology deltas, so this only
        happens on a re-install). ATOMIC: capacity is preflighted before
        any slot moves, so a raising install leaves map, rows, and
        device state untouched. Device state updates as a batched
        same-shape row scatter, exactly like the tile swaps. Callers
        must hold the owning engine's fence (the serve engines do)."""
        from ..stream import _bucketed, _swap_rows

        node_ids = np.asarray(node_ids, np.int64).reshape(-1)
        rows = np.asarray(rows, np.float32)
        if rows.shape[0] != node_ids.shape[0] or rows.shape[1] != self.dim:
            raise ValueError(
                f"install rows {rows.shape} do not match "
                f"{node_ids.shape[0]} nodes x dim {self.dim}"
            )
        if node_ids.size == 0:
            return 0
        self.preflight_install(node_ids)
        slots = np.empty(node_ids.shape[0], np.int64)
        for i, node in enumerate(node_ids):
            node = int(node)
            slot = int(self._map[node])
            if slot < 0:
                slot = self._used
                self._used += 1
                self._map[node] = slot
            slots[i] = slot
            self._rows[slot] = rows[i]
        if self._dev is not None:
            import jax.numpy as jnp

            dev_rows, dev_map = self._dev
            pos, vals = _bucketed(slots, rows, self._rows.shape[0])
            dev_rows = _swap_rows(dev_rows, jnp.asarray(pos),
                                  jnp.asarray(vals))
            if dev_map is not None:
                pos, vals = _bucketed(
                    node_ids, self._map[node_ids], self._map.shape[0]
                )
                dev_map = _swap_rows(dev_map, jnp.asarray(pos),
                                     jnp.asarray(vals))
            self._dev = (dev_rows, dev_map)
        return int(node_ids.size)

    def jit_gather_spec(self):
        import jax.numpy as jnp

        if self._dev is None:
            self._dev = (
                jnp.asarray(self._rows),
                None if self._identity else jnp.asarray(self._map),
            )
        return self._dev

    def __getitem__(self, n_id):
        import jax.numpy as jnp

        ids = np.clip(np.asarray(n_id), 0, self._map.shape[0] - 1)
        loc = np.clip(self._map[ids], 0, self._rows.shape[0] - 1)
        return jnp.asarray(self._rows[loc])


def _feat_reserve(config, n_closure: int) -> int:
    """`ClosureFeature` reserve rows for a closure shard of ``n_closure``
    nodes: room for rows ENTERING the closure under streaming deltas
    (sized like the tile reserve, off the same knob; 0 = frozen graph).
    One formula for every shard build site — initial owners and
    migration engines must agree or a migrated-in owner would exhaust
    its reserve earlier than the fleet it joined."""
    if not config.streaming:
        return 0
    return max(64, int(config.stream_reserve_frac * n_closure))


@dataclass
class DistServeConfig:
    """Router knobs (per-shard engine knobs ride ``shard_config``).

    hosts          : number of serving shards (HostRankTable hosts).
    max_batch      : router flush width — unique seeds drained per flush,
                     BEFORE the owner split (per-shard sub-batches are
                     ~max_batch/hosts on uniform traffic; the probe's
                     width-shrink acceptance reads this).
    max_delay_ms   : router flush-age policy, same semantics as
                     `ServeConfig.max_delay_ms`.
    max_in_flight  : router in-flight window (concurrent routed flushes).
    exchange       : "collective" (ids/logits ride the mesh all_to_all),
                     "host" (direct owner calls + loopback feature
                     exchange), or "auto" (collective when the backend has
                     >= hosts devices).
    budget         : per-owner seed-id budget of the serve exchange (static
                     collective shape); default pow2(max_batch) — a whole
                     router flush to one owner always fits.
    shard_config   : template `ServeConfig` for the per-shard engines
                     (default: the router's max_batch/max_in_flight with
                     the delay policy irrelevant — the router drives shard
                     flushes synchronously). ``record_dispatches`` on the
                     shard engines is what the parity replay reads.
    cache_entries  : per-shard embedding-cache rows at the OWNERS (so the
                     backing cache splits by ownership).
    router_cache_entries : front-end result-cache rows (default: same as
                     ``cache_entries``; 0 disables). Repeat requests for a
                     node already served under the current params version
                     are answered AT THE ROUTER — no routing, no exchange
                     bytes, no owner work. Same get-at-submit /
                     put-at-resolve / invalidate-on-update sequencing as
                     `ServeEngine`'s cache, which is what makes the
                     ``hosts=1`` engine bit-identical to the single-host
                     engine INCLUDING cache behavior (identical LRU
                     evolution -> identical flush composition -> identical
                     key stream) — PROVIDED the cache never evicts (working
                     set <= capacity). Under eviction pressure the router
                     and owner caches can diverge in LRU state (the owner
                     cache only sees router misses), so an owner may answer
                     a router-missed repeat from ITS cache where the
                     single-host engine would re-dispatch — flush
                     composition then differs. Served rows stay bit-equal
                     to the owning shard's replay oracle either way (a
                     cached row was computed by a logged dispatch).
    clock          : injectable monotonic clock shared with shard engines.
    record_dispatches : keep the router's (seeds, per-owner split) log.
    feature_residency : "closure" (default) materializes each owner's
                     feature rows for its whole k-hop closure at BUILD time
                     (`ClosureFeature`: the rows the per-flush DistFeature
                     exchange would have fetched, fetched once), making the
                     owner gather in-jit so shard engines run the FUSED
                     one-dispatch serve program; "exchange" keeps the
                     round-10 on-demand feature exchange (owned rows local,
                     halo rows over the wire per flush — shard engines then
                     serve on the split path). Value-identical; residency
                     trades halo-row memory for per-flush exchange work.
    late_admission : admit late-arriving seeds into a routed flush that is
                     assembled but still waiting for a window slot (up to
                     ``max_batch``), mirroring `ServeConfig.late_admission`.
    journal_events : router-side `trace.EventJournal` capacity (0 =
                     disabled). The default shard config inherits it, so
                     every owner engine journals too; `fleet_snapshot` /
                     `export_chrome_trace` merge the owner journals
                     deterministically (sorted host, dispatch-index order
                     within — the same discipline as the stats merges).
                     Observe-only, same contract as
                     `ServeConfig.journal_events`.
    workload       : a `trace.WorkloadConfig` enables round-13 workload
                     telemetry at the ROUTER (access-frequency sketches
                     over every submitted seed, per-owner routed
                     sub-batch widths + flush/exchange latency quantiles,
                     imbalance + straggler stats) and — via the default
                     shard config — at every owner engine (owner-side
                     sketches, cache taps, tier attribution).
                     `workload_report()` / `fleet_registry()` are the
                     read side. Observe-only, replay-deterministic decay
                     ticks on the router's dispatch index, same contract
                     as `ServeConfig.workload`.
    """

    hosts: int = 2
    max_batch: int = 64
    max_delay_ms: float = 2.0
    max_in_flight: int = 2
    exchange: str = "auto"
    budget: Optional[int] = None
    shard_config: Optional[ServeConfig] = None
    cache_entries: int = 100_000
    router_cache_entries: Optional[int] = None
    clock: Callable[[], float] = time.monotonic
    flush_poll_ms: float = 0.2
    record_dispatches: bool = False
    feature_residency: str = "closure"
    late_admission: bool = True
    journal_events: int = 0
    workload: Optional[WorkloadConfig] = None
    # -- round-15 fleet policies (ROADMAP item 3; docs/api.md "Fleet
    # serving") -----------------------------------------------------------
    # replicate_top_k: hot-set replication head size — `refresh_replicas()`
    # mirrors the k hottest seeds (router workload sketch; k priced by
    # scaling.skew_table) onto the router's own host, so head traffic is
    # answered locally and never enters comm.exchange_serve. 0 = off.
    replicate_top_k: int = 0
    # hedge_deadline_ms: per-owner deadline on routed sub-batches
    # (exchange="host" mode, where owner legs are individually
    # addressable). A leg that misses it re-routes to the full-graph
    # fallback / the replica; the slow owner's answer is discarded.
    # 0 = no deadline (errors still fail over when a target exists).
    hedge_deadline_ms: float = 0.0
    # full_graph_fallback: build() keeps one full-topology/full-feature
    # engine on the router's host as the degraded-mode hedge target — any
    # seed can fail over to it (the replica covers only the hot head).
    full_graph_fallback: bool = False
    # eject_after / eject_backoff_flushes: an owner failing this many
    # CONSECUTIVE sub-batches is ejected (routed straight to the hedge
    # target, no deadline burned) until this many router dispatch indices
    # pass — then it is probed again (half-open). Flush-indexed, never
    # wall time, so ejection decisions replay deterministically.
    eject_after: int = 2
    eject_backoff_flushes: int = 16
    # fault_injector: a `serve.faults.FaultInjector` exercising the
    # host-mode owner legs — deterministic (owner, dispatch-index) keyed
    # kill/error/stall, the proof harness for everything above.
    fault_injector: Optional[object] = None
    # per-tenant admission (same semantics as the ServeConfig fields;
    # applied at the ROUTER — the fleet's admission point)
    tenant_weights: Optional[Dict[str, float]] = None
    max_queue_depth: int = 0
    drain_deadline_s: float = 30.0
    # round-14 adaptive tier knobs, inherited by every owner engine via
    # the default shard config (same semantics as the ServeConfig
    # fields); `DistServeEngine.adapt_tiers` drives one fenced pass per
    # owner, `start()` runs it fleet-wide when tier_adapt_every_s > 0
    tier_promote_batch: int = 64
    tier_promote_min: float = 2.0
    tier_hysteresis: float = 1.25
    tier_adapt_every_s: float = 0.0
    # round-18 flush-ahead prefetch (same semantics as the ServeConfig
    # fields, inherited by the default shard config). The ROUTER
    # additionally prefetches per owner off the routed sub-batches at
    # its own seal — one window EARLIER than the owner's assemble; the
    # staging buffer dedups, so router + owner double-issue is free.
    tier_prefetch: bool = False
    tier_prefetch_hops: Optional[int] = None
    tier_prefetch_max_rows: int = 4096
    # -- round-16 elastic fleet (ROADMAP item 2; docs/api.md "Elastic
    # fleet") --------------------------------------------------------------
    # migrate_batch_seeds: the BOUNDED migration unit — a range handoff
    # moves at most this many seeds per fenced flip. The expensive work
    # (range closure BFS, feature materialization, AOT warmup) runs
    # OUTSIDE the fence with the old owner still serving; only the
    # routing flip + range-scoped cache invalidation sit under it, so a
    # migration batch never stalls serving for longer than a weight swap.
    migrate_batch_seeds: int = 256
    # rebalance_imbalance: OwnerLoadStats max/mean routed-load ratio at
    # which `maybe_rebalance()` migrates ranges off the hottest owner
    # (requires workload telemetry). rebalance_max_seeds bounds one
    # pass; rebalance_every_s > 0 runs the check on a background timer.
    rebalance_imbalance: float = 1.5
    rebalance_max_seeds: int = 1024
    rebalance_every_s: float = 0.0
    # replica_refresh_every_s: the r15 remaining-leverage note — a
    # background timer re-runs `refresh_replicas()` when the router
    # sketch's hot set has drifted more than replica_drift_frac away
    # from what the live replica holds (WorkloadMonitor.hot_set_drift).
    # Fenced and observe-parity pinned exactly like the manual path;
    # 0 = manual refreshes only.
    replica_refresh_every_s: float = 0.0
    replica_drift_frac: float = 0.5
    # -- round-17 streaming graphs (ROADMAP item 1; docs/api.md
    # "Streaming graphs") -------------------------------------------------
    # streaming: build() binds every owner shard (and the full-graph
    # fallback) to a `stream.StreamingTiledGraph` so
    # `update_graph(delta)` can commit live edge appends — in-place
    # pad-lane tile writes + batched device tile swaps, the owner shards'
    # halo closures extended INCREMENTALLY (never resharded). Requires
    # feature_residency="closure" (owner feature rows install into the
    # ClosureFeature reserve; the exchange residency's DistFeature
    # partition already spans the full id space but its owners gather
    # host-side — stream them by rebuilding). False = the frozen-graph
    # engine, byte-for-byte round 16.
    streaming: bool = False
    # stream_reserve_frac: slack planned per owner at build, as a
    # fraction of the built size — tile rows for spills/installs AND
    # ClosureFeature rows for closure growth. Exhaustion raises
    # stream.StreamCapacityError (plan capacity like sampler caps;
    # shapes are frozen so sealed executables never recompile).
    stream_reserve_frac: float = 0.5
    # stream_invalidate_hops: reverse-closure depth of the delta cache
    # invalidation (None = len(sizes) - 1, the expansion-hop count —
    # see ServeConfig.stream_invalidate_hops).
    stream_invalidate_hops: Optional[int] = None
    # stream_replica_rebuild: when a delta's closure touches the live
    # hot-set replica, the replica is DROPPED under the commit fence
    # (its shard topology went stale — serving from it would draw from
    # the pre-delta graph); True rebuilds it over the updated graph
    # right after the fence, False leaves replication off until the
    # next manual/drift refresh.
    stream_replica_rebuild: bool = True
    # -- round-23 concurrent owner fan-out (docs/api.md "Concurrent owner
    # fan-out") ------------------------------------------------------------
    # sequential_legs: run host-mode dispatch legs one after another on
    # the flushing thread — the pre-round-23 router, kept verbatim as
    # the bit-parity twin of the concurrent fan-out (exactly like
    # `_scalar_resolve`). False = fan the legs out on per-flush worker
    # threads (owner `predict` blocks in XLA with the GIL released, so
    # the overlap is real even on one core) and JOIN IN SPLIT ORDER,
    # applying every leg's side effects at join — logits, dispatch
    # logs, `hedge_events()`, owner health, and the journal stay
    # bit-identical to the sequential pass; only wall time changes
    # (max(legs) + merge instead of sum(legs)). Collective mode is
    # untouched either way: one launch under the collective lock —
    # concurrent collective launches deadlock XLA's rendezvous.
    sequential_legs: bool = False
    # leg_fanout: bound on CONCURRENTLY RUNNING legs per routed flush
    # (0 = all at once). Legs start in split order and join in split
    # order regardless, so the bound changes scheduling, never results
    # — leg_fanout=1 is the sequential pass on a worker thread.
    leg_fanout: int = 0
    # -- round-24 zero-stall commits (see ServeConfig.fenced_commits) ------
    # False (default) = fleet update_graph plans/preflights outside the
    # router fence, owner engines run their own zero-stall commits, and
    # the router-grain flip (graph_version bump + replica retire) runs
    # under the router _seq only. True = the drain-ordered round-17..23
    # fence, bit-identical, propagated to every owner engine.
    fenced_commits: bool = False

    def resolved_shard_config(self) -> ServeConfig:
        if self.shard_config is not None:
            return self.shard_config
        return ServeConfig(
            max_batch=self.max_batch,
            max_delay_ms=self.max_delay_ms,
            max_in_flight=self.max_in_flight,
            cache_entries=self.cache_entries,
            clock=self.clock,
            record_dispatches=self.record_dispatches,
            late_admission=self.late_admission,
            journal_events=self.journal_events,
            workload=self.workload,
            tier_promote_batch=self.tier_promote_batch,
            tier_promote_min=self.tier_promote_min,
            tier_hysteresis=self.tier_hysteresis,
            tier_prefetch=self.tier_prefetch,
            tier_prefetch_hops=self.tier_prefetch_hops,
            tier_prefetch_max_rows=self.tier_prefetch_max_rows,
            # round-16 owner-side tenant scheduling: the router forwards
            # each sub-batch's submitting tenants, and owner engines
            # apply the SAME weighted flush quotas — a tenant's share
            # holds end-to-end, not just at router admission. None (no
            # QoS) leaves owner engines byte-identical to round 15.
            tenant_weights=self.tenant_weights,
            fenced_commits=self.fenced_commits,
        )


@dataclass
class DistServeStats:
    """Router-side counters; `DistServeEngine.aggregate_stats` merges the
    per-shard `ServeStats` on top (via the ``merge`` family in
    `quiver_tpu.trace`). ``exchange_id_bytes``/``exchange_logit_bytes``
    count the GLOBAL collective payloads (H*H*L ids, H*H*L*C logits per
    routed flush in collective mode) — the wire term
    `scaling.serve_table(hosts=...)` prices."""

    requests: int = 0
    coalesced: int = 0
    router_dispatches: int = 0
    routed_seeds: int = 0
    late_admitted: int = 0
    # round-15 fleet-policy counters: replica_hits counts seeds answered
    # by the local hot-set replica (never entered the exchange); hedges /
    # hedged_seeds count owner sub-batches (and their seeds) re-routed to
    # a failover target, split by cause (deadline miss vs owner error vs
    # routed-while-ejected); owner_ejections counts backoff entries;
    # shed / request_errors / undrained mirror the ServeStats fields.
    replica_hits: int = 0
    hedges: int = 0
    hedged_seeds: int = 0
    hedge_timeouts: int = 0
    hedge_errors: int = 0
    hedge_ejected: int = 0
    hedge_failed: int = 0       # failovers with no (working) target
    owner_ejections: int = 0
    shed: int = 0
    request_errors: int = 0
    undrained: int = 0
    # round-16 elastic-fleet counters: migration_batches counts fenced
    # range flips COMMITTED (roll-forwards included — the range landed),
    # migration_rollbacks the ranges that stayed with their old owner
    # after a destination died mid-handoff; migrated_seeds sums committed
    # range widths; replica_refreshes counts background drift-triggered
    # replica rebuilds (manual refresh_replicas calls ride
    # replica_version, not this).
    migration_batches: int = 0
    migration_rollbacks: int = 0
    migration_rollforwards: int = 0
    migrated_seeds: int = 0
    replica_refreshes: int = 0
    # round-17 streaming-graph counters: graph_deltas counts fenced
    # update_graph commits, delta_edges the edges they appended,
    # delta_cache_invalidated the closure-touched ROUTER cache drops,
    # delta_closure_installs the owner-shard rows (topology installs)
    # landed by incremental halo extension, replica_delta_invalidations
    # the hot-set replicas dropped because a delta touched their closure
    graph_deltas: int = 0
    delta_edges: int = 0
    delta_cache_invalidated: int = 0
    delta_closure_installs: int = 0
    replica_delta_invalidations: int = 0
    # round-21 lifecycle: removals committed fleet-wide (expiry and
    # compaction are per-owner-engine — they ride the merged ServeStats)
    edges_deleted: int = 0
    inflight_peak: int = 0
    sub_batches: Dict[int, int] = field(default_factory=dict)
    sub_batch_seeds: Dict[int, int] = field(default_factory=dict)
    exchange_id_bytes: int = 0
    exchange_logit_bytes: int = 0
    router_cache: HitRateCounter = field(default_factory=HitRateCounter)
    latency: LatencyHistogram = field(default_factory=LatencyHistogram)
    tenant_latency: Dict[str, LatencyHistogram] = field(default_factory=dict)
    spans: SpanRecorder = field(default_factory=SpanRecorder)
    # round-24: per-commit routed-serving stall in MICROSECONDS (the
    # histogram is unit-agnostic; µs keeps sub-ms flips resolvable).
    # Fenced: the whole drain+apply hold; zero-stall: the _seq flip.
    commit_stall: LatencyHistogram = field(
        default_factory=lambda: LatencyHistogram(min_ms=1e-2, max_ms=1e9)
    )

    def tenant_hist(self, tenant: str) -> LatencyHistogram:
        from .engine import tenant_latency_hist

        return tenant_latency_hist(self.tenant_latency, tenant)

    def mean_sub_batch_width(self) -> Dict[int, float]:
        return {
            h: self.sub_batch_seeds[h] / n
            for h, n in self.sub_batches.items()
            if n
        }

    def snapshot(self) -> Dict[str, object]:
        return {
            "requests": self.requests,
            "coalesced": self.coalesced,
            "router_dispatches": self.router_dispatches,
            "routed_seeds": self.routed_seeds,
            "late_admitted": self.late_admitted,
            "replica_hits": self.replica_hits,
            "hedges": self.hedges,
            "hedged_seeds": self.hedged_seeds,
            "hedge_timeouts": self.hedge_timeouts,
            "hedge_errors": self.hedge_errors,
            "hedge_ejected": self.hedge_ejected,
            "hedge_failed": self.hedge_failed,
            "owner_ejections": self.owner_ejections,
            "shed": self.shed,
            "request_errors": self.request_errors,
            "undrained": self.undrained,
            "migration_batches": self.migration_batches,
            "migration_rollbacks": self.migration_rollbacks,
            "migration_rollforwards": self.migration_rollforwards,
            "migrated_seeds": self.migrated_seeds,
            "replica_refreshes": self.replica_refreshes,
            "graph_deltas": self.graph_deltas,
            "delta_edges": self.delta_edges,
            "delta_cache_invalidated": self.delta_cache_invalidated,
            "delta_closure_installs": self.delta_closure_installs,
            "replica_delta_invalidations": self.replica_delta_invalidations,
            "edges_deleted": self.edges_deleted,
            "inflight_peak": self.inflight_peak,
            "sub_batches": dict(self.sub_batches),
            "mean_sub_batch_width": self.mean_sub_batch_width(),
            "exchange_id_bytes": self.exchange_id_bytes,
            "exchange_logit_bytes": self.exchange_logit_bytes,
            "router_cache": self.router_cache.snapshot(),
            "latency": self.latency.snapshot(),
            "commit_stall_us": self.commit_stall.snapshot(),
            "tenant_latency": {
                t: self.tenant_latency[t].snapshot()
                for t in sorted(self.tenant_latency)
            },
            "overlap": self.spans.overlap_summary(),
        }


class _RoutedFlush:
    """Per-flush router state between assemble and resolve. ``bucket`` is
    the admission cap (the router pads nothing, so its "pad slack" is the
    drained width up to ``max_batch``); the owner split is computed at SEAL
    time so late-admitted seeds route with their flush.

    ``error`` poisons the WHOLE flush (assemble/seal failures, a
    collective-exchange abort); ``slot_errors`` maps key POSITIONS to
    per-request exceptions — the round-15 isolation contract: a failed
    owner sub-batch resolves only its own slots with the error, every
    other slot resolves normally, and `flush()` does not re-raise."""

    __slots__ = ("keys", "slots", "split", "bucket", "error", "slot_errors",
                 "fid", "tenants", "extra", "ids", "rids", "tenant_ix",
                 "graph_version")

    def __init__(self, keys, slots, split):
        self.keys = keys
        self.slots = slots
        self.split = split  # [(host, ids ndarray, positions ndarray)]
        # ROUTER graph epoch this flush sealed against (round 24): stamped
        # under _seq at seal, so a zero-stall fleet commit flipping the
        # router version mid-flight never mixes epochs within one flush.
        # Cache writebacks carry it as their floor-gate stamp.
        self.graph_version = 0
        # array-native slot views (round 20, sealed — see _Flush): seed
        # ids (int64), journal rids (int64, -1 = journal off) and wire
        # tenant indices (int32, the collective's registry; -1 =
        # unregistered tenant), aligned with ``slots``
        self.ids = None
        self.rids = None
        self.tenant_ix = None
        self.bucket = 0
        self.error: Optional[BaseException] = None
        self.slot_errors: Dict[int, BaseException] = {}
        self.fid = -1  # journal flush id (router dispatch-log index)
        # per-key submitting tenant (filled at seal, aligned with keys):
        # owner legs forward these so owner-side quotas hold end-to-end
        self.tenants: List[str] = []
        # extra per-key dispatch payload aligned with keys (round 19:
        # the temporal router's query-time vector); None on the plain
        # router
        self.extra = None


class _LegRun:
    """One host-mode dispatch leg in flight (round 23). The worker half
    fills ``box`` only — {"rows", "err", "dt"}; never ``out``, never
    stats — so an abandoned (timed-out) worker can finish whenever it
    likes without touching anything the joiner already settled. The
    joiner half applies every side effect in split order."""

    __slots__ = ("h", "ids", "pos", "tenants", "ejected", "thread",
                 "t_start", "box")

    def __init__(self, h, ids, pos, tenants):
        self.h = h
        self.ids = ids
        self.pos = pos
        self.tenants = tenants
        self.ejected = False
        self.thread: Optional[threading.Thread] = None
        self.t_start = 0.0
        self.box: Dict[str, object] = {}


def _bounded_leg_schedule(runs, cap, start_leg):
    """Start fan-out legs STRICTLY IN SPLIT ORDER with at most ``cap``
    running at once, yielding each run in order for its join — the
    joiner runs between yields, so starts interleave with joins and the
    pipeline stays full up to the bound. ``start_leg(run)`` returns
    True when it spawned a thread (ejected/wedged legs never spawn and
    never count). The bound changes scheduling, never results: joins
    happen in split order regardless."""
    started = 0
    active = 0
    for r in runs:
        while started < len(runs) and active < cap:
            nxt = runs[started]
            started += 1
            if start_leg(nxt):
                active += 1
        yield r
        if r.thread is not None:
            active -= 1


class _HotReplica:
    """The router-local hot-set replica (round 15): a full `ServeEngine`
    over the replicated seeds' halo-closure topology + feature rows —
    the mirror of Quiver's ``p2p_clique_replicate`` hot-prefix applied to
    serving. ``ids`` is the sorted replicated seed set; ``id_set`` the
    O(1) membership view the hedge path consults."""

    __slots__ = ("engine", "ids", "id_set", "version", "stats")

    def __init__(self, engine: ServeEngine, ids: np.ndarray, version: int,
                 stats: Dict[str, float]):
        self.engine = engine
        self.ids = np.asarray(ids, np.int64)
        self.id_set = frozenset(int(x) for x in self.ids)
        self.version = version
        self.stats = stats


class DistServeEngine:
    """Seed-ownership-sharded serving front end (module docstring has the
    design; docs/api.md "Distributed serving" the contract). Typical use::

        dist = DistServeEngine.build(
            model, params, csr_topo, feat, sizes=[8, 8], hosts=2,
            config=DistServeConfig(max_batch=32),
        )
        dist.warmup()
        out = dist.predict(node_ids)     # routed, owner-served, re-merged

    The constructor takes prebuilt shard engines keyed by host (`build`
    does the partitioning); multi-process deployments construct with only
    their own host's engine and a `TpuComm` whose serve answerer is
    registered, then drive lockstep flushes (tests/dist_worker.py serve
    mode)."""

    def __init__(
        self,
        engines: Dict[int, ServeEngine],
        global2host: np.ndarray,
        out_dim: int,
        config: Optional[DistServeConfig] = None,
        comm: Optional[TpuComm] = None,
        shard_topo_stats: Optional[Dict[int, Dict[str, float]]] = None,
    ):
        self.config = config or DistServeConfig()
        if self.config.max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        mode = self.config.exchange
        if mode not in ("auto", "collective", "host"):
            raise ValueError(f"unknown exchange mode {mode!r}")
        if mode == "auto":
            mode = "collective" if comm is not None else "host"
        if mode == "collective" and comm is None:
            raise ValueError("exchange='collective' needs a TpuComm")
        if self.config.fault_injector is not None and mode != "host":
            raise ValueError(
                "fault_injector exercises the per-owner host-mode dispatch "
                "legs (the collective is one launch and cannot fail "
                "per-owner); build with exchange='host'"
            )
        self.exchange_mode = mode
        self.engines = dict(engines)
        self.hosts = self.config.hosts
        # a COPY: scale()/rebalance() mutate ownership in place under the
        # per-range fence, and the caller's array must not move under it
        self.global2host = np.array(global2host, np.int32, copy=True)
        self.out_dim = int(out_dim)
        self.comm = comm
        self.shard_topo_stats = shard_topo_stats or {}
        self._budget = self.config.budget or round_up_pow2(self.config.max_batch)
        self._clock = self.config.clock
        self.stats = DistServeStats()
        self.journal = (
            EventJournal(self.config.journal_events, clock=self._clock)
            if self.config.journal_events > 0
            else NULL_JOURNAL
        )
        self._next_rid = 0     # journal request ids (guarded by _lock)
        self._flush_index = 0  # router dispatch-log index (guarded by _seq)
        self.tier_adapt_errors = 0  # failed fleet tier-adaptation passes
        # round-13 router-side workload telemetry (observe-only): the
        # router sees EVERY submitted seed, so its sketch is the fleet's
        # access-frequency view; per-owner load/latency land here too
        self.workload = (
            WorkloadMonitor(self.config.workload, clock=self._clock)
            if self.config.workload is not None
            else None
        )
        rc = self.config.router_cache_entries
        self.cache = EmbeddingCache(
            self.config.cache_entries if rc is None else rc,
            counters=self.stats.router_cache,
        )
        if self.workload is not None:
            self.cache.workload = self.workload
        self.params_version = 0
        self.dispatch_log: List[Tuple[np.ndarray, List[Tuple[int, np.ndarray]]]] = []
        # ROUTER graph epoch per dispatch-log entry (round 24), a parallel
        # aligned list (the log's tuple shape is pinned by tests and the
        # round-21 CI smoke): dispatch_graph_versions[i] is the router
        # graph_version entry i sealed against — the epoch filter
        # `replay_fleet_oracle(graph_version=...)` selects rows by
        self.dispatch_graph_versions: List[int] = []
        # per-OWNER pending queues (round 20): the stripe hint is the
        # BUILD-TIME ownership snapshot, deliberately NOT the live
        # global2host — scale()/rebalance() mutate placement in place, and
        # a key whose stripe moved mid-flight would dodge its own coalesce
        # probe / pop. Routing always reads the live array at seal; the
        # stripe is only a lock-contention partition, so staleness is free.
        g2h_build = self.global2host.copy()
        n_ids = g2h_build.shape[0]

        def _stripe_hint(k, _g2h=g2h_build, _n=n_ids):
            # temporal routers key by (node, t_bucket): stripe by the node
            node = k[0] if type(k) is tuple else k
            return int(_g2h[node]) if 0 <= node < _n else hash(k)

        self._pending = _PendingStripes(self.hosts, stripe_key=_stripe_hint)
        self._inflight: Dict[int, _Slot] = {}
        import collections

        # round-15 fleet-policy state -------------------------------------
        # per-tenant admission rides the striped store's per-stripe counts
        # (mirrors ServeEngine). Policy logs are BOUNDED rings (newest
        # win) — sustained overload or a long-dead owner is exactly when
        # they fill, and an unbounded list there would leak until OOM
        self.shed_log = collections.deque(maxlen=POLICY_LOG_CAP)
        # hot-set replica (swapped only under the update_params fence) +
        # the full-graph failover engine (built by `build` on request)
        self.replica: Optional[_HotReplica] = None
        self.replica_version = 0
        # retired replica engines keep their dispatch logs so the fleet
        # replay oracle can still vouch for rows they served pre-refresh
        self._retired_replicas: List[ServeEngine] = []
        self.fallback: Optional[ServeEngine] = None
        self._params = None                # tracked for replica rebuilds
        self._replica_materials: Optional[Dict[str, object]] = None
        # -- round-16 elastic-fleet state ---------------------------------
        # owner engines replaced by a range handoff (and engines of
        # shrunk-away hosts) keep their dispatch logs for the replay
        # oracle, exactly like retired replicas. Engines retired WITHOUT
        # dispatch recording are dropped (a production fleet must not
        # accumulate dead device state), but their counters fold into
        # _retired_stats first so the merged fleet view never goes
        # backwards across a range flip.
        self._retired_engines: List[ServeEngine] = []
        self._retired_stats = ServeStats()
        # per-owner (adjacency-closure mask, feature-closure mask) over
        # the GLOBAL id space — the incremental-extension state: a range
        # handoff ORs the migrated range's closure into the destination's
        # masks instead of re-BFS-ing its whole owned set
        self._owner_masks: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._src_per_edge: Optional[np.ndarray] = None  # BFS amortizer
        # ownership_epoch bumps once per COMMITTED range flip; the
        # migration log [(mig, epoch, lo, hi, src, dst, n, outcome)] is
        # the deterministic routing-epoch history replay comparisons read
        self.ownership_epoch = 0
        self.migration_log: List[Tuple[int, int, int, int, int, int, int,
                                       str]] = []
        self._mig_index = 0          # monotonic handoff-batch counter
        # -- round-17 streaming-graph state -------------------------------
        # graph_version counts fenced delta commits at the ROUTER grain;
        # pending_delta accumulates staged arrivals (stage_edges);
        # _stream_adj is the host-side full-graph adjacency view (base
        # CSR + appended edges — closures and materialization, no device
        # bytes); _owner_streams/_owner_feats hold each owner's
        # StreamingTiledGraph / ClosureFeature for the in-place apply;
        # _materials_stale marks the build() materials' csr_topo as
        # behind the stream (re-materialized lazily by
        # `_current_full_topo` before a replica rebuild / migration
        # build — NEVER on the serving path).
        self.graph_version = 0
        self.pending_delta = None
        self._stream_adj = None
        self._owner_streams: Dict[int, object] = {}
        self._owner_feats: Dict[int, ClosureFeature] = {}
        self._materials_stale = False
        # serializes _stream_adj WRITES (update_graph's add/rollback)
        # against the lazy re-materialize — replica/migration builds run
        # OUTSIDE the router fence by design (AOT warmup costs seconds),
        # so without this a background build could iterate the adjacency
        # dicts mid-mutation or capture a mid-rollback graph. Ordering:
        # router fence lock -> _mat_lock, never the reverse.
        self._mat_lock = threading.Lock()
        # zero-stall commits (round 24): serializes WHOLE fleet commits
        # (plan + preflight + owner flips) against each other without
        # fencing traffic — the flip itself happens under _seq only.
        # Ordering: _commit_lock -> _mat_lock and _commit_lock -> _seq;
        # never taken while holding _seq.
        self._commit_lock = threading.RLock()
        # per-commit counter samples for the Chrome-trace counter lane
        # (graph_version staircase + commit_stall_us), observe-only
        self._commit_samples = collections.deque(maxlen=4096)
        # one range handoff is atomic under this lock; stop() takes it
        # before draining, so an open range always completes or rolls
        # back first and no seed is ever stranded ownerless
        self._migration_lock = threading.Lock()
        self._draining = False       # rebalance loops stop between batches
        self.replica_refresh_errors = 0  # failed background refresh passes
        self.rebalance_errors = 0        # failed background rebalance passes
        # owner-side tenant scheduling: tenant name <-> wire index (the
        # collective ships int32 indices; every host derives the same
        # registry from the sorted QoS config keys)
        tw = self.config.tenant_weights
        self._tenant_names: List[str] = sorted(tw) if tw else []
        self._tenant_index: Dict[str, int] = {
            t: i for i, t in enumerate(self._tenant_names)
        }
        # per-owner health for hedged dispatch: consecutive failures +
        # the dispatch index an ejection started at (-1 = serving);
        # flush-indexed backoff keeps the state machine replayable
        self._owner_health: Dict[int, Dict[str, int]] = {}
        # deterministic hedge log [(fid, owner, reason, target)] — append
        # order may interleave across in-flight flushes, read the sorted
        # `hedge_events()` view for replay comparison; bounded like
        # shed_log (a dead owner with no failover appends per flush)
        self.hedge_log = collections.deque(maxlen=POLICY_LOG_CAP)
        # abandoned (deadline-missed) leg threads per owner, guarded by
        # _lock: while any is still alive the owner is treated as wedged
        # and no new leg is spawned — growth is bounded by max_in_flight
        # per wedge episode, never the life of the router
        self._abandoned_legs: Dict[int, List[threading.Thread]] = {}
        self.faults = self.config.fault_injector
        self._open: Optional[_RoutedFlush] = None
        self._lock = threading.Lock()
        self._fence = threading.Condition(self._lock)
        self._seq = threading.Lock()
        self._window = threading.BoundedSemaphore(self.config.max_in_flight)
        self._inflight_flushes = 0
        # parity escape hatch (round 22): True forces the per-slot
        # resolve loop the block resolution is pinned against
        self._scalar_resolve = False
        self._threads: List[threading.Thread] = []
        self._running = False
        if mode == "collective":
            # the serve exchange's static shape: every host must agree
            self.comm.static_budget = self._budget
            for h, eng in self.engines.items():
                self.comm.register_serve_answerer(h, self._make_answerer(h))

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        model,
        params,
        csr_topo: CSRTopo,
        feat: np.ndarray,
        sizes: Sequence[int],
        *,
        hosts: int,
        config: Optional[DistServeConfig] = None,
        global2host: Optional[np.ndarray] = None,
        sampler_seed: int = 0,
        sampler_mode: str = "TPU",
        sampler_kw: Optional[dict] = None,
        out_dim: Optional[int] = None,
        mesh=None,
        feature_kw: Optional[dict] = None,
    ) -> "DistServeEngine":
        """Partition ``csr_topo``/``feat`` by seed ownership and assemble
        the router + H shard engines in one process (the hermetic pod
        simulation). Every shard sampler is born with the SAME
        ``sampler_seed`` — each shard's key stream then matches a freshly
        born single-host sampler's, which is what lets the parity oracle
        replay any shard's dispatch log through a full-graph sampler."""
        import jax

        from ..pyg.sage_sampler import GraphSageSampler

        config = config or DistServeConfig(hosts=hosts)
        if config.hosts != hosts:
            raise ValueError(f"config.hosts={config.hosts} != hosts={hosts}")
        feat = np.asarray(feat, np.float32)
        n = csr_topo.indptr.shape[0] - 1
        if global2host is None:
            global2host = contiguous_partition(n, hosts)
        out_dim = out_dim if out_dim is not None else getattr(model, "out_dim", None)
        if out_dim is None:
            raise ValueError("pass out_dim= (model has no out_dim attribute)")
        mode = resolve_exchange_mode(config.exchange, hosts)
        comm = None
        feat_comms: List[object] = []
        if mode == "collective":
            if mesh is None:
                from jax.sharding import Mesh

                devs = jax.devices()
                if len(devs) < hosts:
                    raise ValueError(
                        f"exchange='collective' needs >= {hosts} devices "
                        f"(got {len(devs)}); use exchange='host'"
                    )
                mesh = Mesh(np.array(devs[:hosts]), ("serve_host",))
            comm = TpuComm(
                rank=0, world_size=hosts, hosts=hosts, mesh=mesh, axis="serve_host"
            )
        residency = config.feature_residency
        if residency not in ("closure", "exchange"):
            raise ValueError(f"unknown feature_residency {residency!r}")
        if feature_kw and residency != "exchange":
            # tiered owner features (disk/adaptive knobs) gather host-side
            # through Feature; the closure residency is a dense in-jit
            # table by construction, so the knobs would be silently dead
            raise ValueError(
                "feature_kw (tiered owner features) requires "
                "feature_residency='exchange'"
            )
        if config.streaming and residency != "closure":
            raise ValueError(
                "streaming graphs require feature_residency='closure' — "
                "closure-entering nodes install into the ClosureFeature "
                "reserve; the exchange residency's owners gather "
                "host-side (rebuild to stream them)"
            )
        # feature-exchange budget ("exchange" residency only): a shard
        # forward gathers up to the final padded n_id width of the largest
        # bucket, all of which could be remote in the worst case
        from ..ops.sample import pad_widths

        shard_cfg = config.resolved_shard_config()
        kw = dict(sampler_kw or {})
        widths = pad_widths(
            max(shard_cfg.resolved_buckets()), sizes, kw.get("caps")
        )
        feat_budget = round_up_pow2(widths[-1])
        engines: Dict[int, ServeEngine] = {}
        topo_stats: Dict[int, Dict[str, float]] = {}
        owner_masks: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        owner_streams: Dict[int, object] = {}
        owner_feats: Dict[int, ClosureFeature] = {}
        indptr_full = np.asarray(csr_topo.indptr, np.int64)
        indices_full = np.asarray(csr_topo.indices, np.int64)
        src_per_edge = np.repeat(
            np.arange(indptr_full.shape[0] - 1, dtype=np.int64),
            (indptr_full[1:] - indptr_full[:-1]),
        )
        for h in range(hosts):
            # adjacency closure: len(sizes)-1 expansion hops; FEATURE
            # closure one deeper — the last hop's leaves are gathered but
            # never expanded (shard_topology_by_owner docstring). The
            # masks are KEPT per owner: a later range handoff extends
            # them incrementally instead of re-BFS-ing the owned set.
            seed_mask = np.asarray(global2host) == h
            topo_mask, feat_mask = closure_masks(
                indptr_full, indices_full, seed_mask,
                hops=len(sizes) - 1, feat_hops=len(sizes),
                src_per_edge=src_per_edge,
            )
            topo_h, edge_stats = shard_from_mask(
                csr_topo, topo_mask, src_per_edge=src_per_edge
            )
            closure_ids = np.nonzero(feat_mask)[0]
            owner_masks[h] = (topo_mask, feat_mask)
            st = {
                "owned_nodes": int(seed_mask.sum()),
                "closure_nodes": int(topo_mask.sum()),
                "feature_closure_nodes": int(feat_mask.sum()),
                **edge_stats,
            }
            topo_stats[h] = st
            sampler = GraphSageSampler(
                topo_h, sizes=sizes, mode=sampler_mode, seed=sampler_seed, **kw
            )
            if config.streaming:
                # round 17: the owner shard becomes a streaming tile
                # layout — update_graph commits land as in-place pad-lane
                # writes + batched device tile swaps, never a reshard
                from ..stream import StreamingTiledGraph

                owner_streams[h] = StreamingTiledGraph(
                    topo_h, reserve_frac=config.stream_reserve_frac
                )
                sampler.bind_stream(owner_streams[h])
            if residency == "closure":
                # materialize the closure's rows ONCE (the rows the
                # per-flush exchange would fetch) — the owner gather is
                # then in-jit, so the shard engine serves on the FUSED
                # one-dispatch program; residency is honest: closure ==
                # owned (exactly 1/H) on k-hop-closed partitions, the halo
                # elsewhere is already reported in topo_stats
                local_map = np.full(n, -1, np.int32)
                local_map[closure_ids] = np.arange(
                    closure_ids.shape[0], dtype=np.int32
                )
                shard_feat = ClosureFeature(
                    feat[closure_ids], local_map,
                    reserve_rows=_feat_reserve(config,
                                               closure_ids.shape[0]),
                )
                owner_feats[h] = shard_feat
            else:
                owned = np.nonzero(global2host == h)[0]
                fkw = dict(feature_kw or {})
                if fkw.get("disk_path"):
                    # per-owner flat files: "{host}" in the template keeps
                    # H shards from clobbering one backing file
                    fkw["disk_path"] = fkw["disk_path"].format(host=h)
                f = Feature(rank=0, device_list=[0],
                            **{"device_cache_size": 0, **fkw})
                f.from_cpu_tensor(feat[owned])
                f.set_local_order(owned)
                if mode == "collective":
                    fcomm = TpuComm(
                        rank=h, world_size=hosts, hosts=hosts, mesh=mesh,
                        axis="serve_host",
                    )
                    fcomm.static_budget = feat_budget
                else:
                    fcomm = LoopbackComm(hosts)
                feat_comms.append(fcomm)
                info = PartitionInfo(
                    device=0, host=h, hosts=hosts, global2host=global2host
                )
                shard_feat = _ShardFeature(DistFeature(f, info, fcomm), n)
            engines[h] = ServeEngine(model, params, sampler, shard_feat, shard_cfg)
        # single-controller mode: every feature comm holds every block (a
        # real pod registers only its own — the 1/H HBM claim is about the
        # per-process resident set, which IS one block per host there)
        for h in range(hosts):
            block = np.asarray(feat[np.nonzero(global2host == h)[0]], np.float32)
            for fcomm in feat_comms:
                fcomm.register_local_table(h, block)
        dist = cls(
            engines, global2host, out_dim, config=config, comm=comm,
            shard_topo_stats=topo_stats,
        )
        # round-15 fleet policies need build-time materials: the replica
        # is rebuilt from the full graph/table on every refresh, and the
        # fallback engine IS a full-graph single-host engine (the degraded
        # path any seed can fail over to). Multi-process constructions
        # (bare __init__) have neither — they hold only their own shard.
        dist._params = params
        dist._replica_materials = {
            "model": model, "csr_topo": csr_topo, "feat": feat,
            "sizes": tuple(sizes), "sampler_mode": sampler_mode,
            "sampler_seed": sampler_seed, "sampler_kw": dict(kw),
            "shard_config": shard_cfg,
        }
        dist._owner_masks = owner_masks
        dist._src_per_edge = src_per_edge
        if config.streaming:
            from ..stream import StreamingAdjacency

            dist._stream_adj = StreamingAdjacency(csr_topo)
            dist._owner_streams = owner_streams
            dist._owner_feats = owner_feats
        if config.full_graph_fallback:
            fb_sampler = GraphSageSampler(
                csr_topo, sizes=sizes, mode=sampler_mode, seed=sampler_seed,
                **kw,
            )
            if config.streaming:
                # the degraded-mode hedge target must see deltas too — a
                # frozen fallback would serve pre-delta draws for any
                # failed-over seed
                from ..stream import StreamingTiledGraph

                fb_sampler.bind_stream(StreamingTiledGraph(
                    csr_topo, reserve_frac=config.stream_reserve_frac
                ))
            dist.fallback = ServeEngine(model, params, fb_sampler, feat,
                                        shard_cfg)
        return dist

    def _make_answerer(self, host: int):
        """The owner-side hook of the serve exchange: ids arrive
        requester-major [H, L] (-1-padded), each requester's valid lanes go
        through the owner engine's FULL local path (cache, coalescing,
        micro-batching, window), invalid lanes return zeros.
        ``recv_tenants`` (same shape, int32 indices into the sorted QoS
        registry, -1 = default) arrives when the router ships tenants —
        the owner engine then applies the submitting tenants' flush
        quotas (round 16)."""

        def answer(recv_ids: np.ndarray,
                   recv_tenants: Optional[np.ndarray] = None) -> np.ndarray:
            recv_ids = np.asarray(recv_ids)
            out = np.zeros(
                (recv_ids.shape[0], recv_ids.shape[1], self.out_dim), np.float32
            )
            for req in range(recv_ids.shape[0]):
                valid = recv_ids[req] >= 0
                if valid.any():
                    ids = recv_ids[req][valid].astype(np.int64)
                    tenants = None
                    if recv_tenants is not None:
                        tenants = [
                            self._tenant_names[t] if 0 <= t < len(
                                self._tenant_names
                            ) else DEFAULT_TENANT
                            for t in np.asarray(recv_tenants[req])[valid]
                        ]
                    out[req, valid] = np.asarray(
                        self._predict_leg(self.engines[host], ids, tenants)
                    )
            return out

        return answer

    # -- request path ------------------------------------------------------

    def submit(self, node_id: int,
               tenant: Optional[str] = None) -> ServeResult:
        """Enqueue one request: the front-end result cache answers repeats
        of already-served nodes outright (no routing, no exchange bytes),
        then the same dedup/coalesce semantics as `ServeEngine.submit`
        apply to the rest. ``tenant`` drives the round-15 per-tenant
        admission exactly as on the single-host engine (weighted flush
        quotas, deterministic queue-depth shedding, per-tenant latency).
        Round 20: `submit_many` of ONE, like `ServeEngine.submit`.
        KEEP IN LOCKSTEP with `ServeEngine.submit` — the hosts=1
        bit-parity contract depends on the two front ends making
        identical cache/coalesce decisions per request, and
        `test_shards1_bit_equal_single_host_engine` pins it."""
        return self.submit_many((node_id,), tenant=tenant)[0]

    def submit_many(self, node_ids, t=None,
                    tenant=None) -> List[ServeResult]:
        """Vectorized batch submit at the router (round 20, the
        `ServeEngine.submit_many` twin): id-range validation is VECTORIZED
        up front (the whole batch is rejected before any admission — the
        one documented batch/scalar difference), then admission runs per
        request in request order under one striped-lock hold per chunk,
        with one batched journal append and inline flush at every fill —
        so the router's dispatch log is bit-identical to N scalar
        ``submit`` calls."""
        if t is not None:
            raise TypeError(
                "t= is a temporal-serving argument (TemporalDistServeEngine);"
                " this router serves untimed nodes"
            )
        ids = np.asarray(node_ids, dtype=np.int64).reshape(-1)
        n_ids = self.global2host.shape[0]
        bad = (ids < 0) | (ids >= n_ids)
        if bad.any():
            raise ValueError(
                f"node id {int(ids[bad][0])} outside [0, {n_ids})"
            )
        keys = ids.tolist()
        return self._submit_keyed_many(keys, keys, tenant, uniq_arr=ids)

    def _submit_keyed_many(self, keys: List, nodes: List[int],
                           tenant, uniq_arr=None) -> ResultBatch:
        """KEEP IN LOCKSTEP with `ServeEngine._submit_keyed_many` (the
        router has no submit-time prefetch leg; its per-owner prefetch
        runs at seal off the routed split) — including the round-22
        whole-batch vectorized admission gate: `_admit_batch_vector`
        stripes per owner through ``pend.stripe_of`` exactly as the
        scalar inserts would."""
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
                    # round-20 vectorized chunk admission, shared with
                    # the single-host engine (`_admit_chunk_fast`):
                    # the router's per-owner stripes and late-admission
                    # window behave identically under it
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
                self.flush()
        return ResultBatch(items=results)

    # the engine-shape gates are identical on both front ends (the
    # router's extra state — owner split, exchange — only matters after
    # assembly, never at admission)
    _vector_admissible = ServeEngine._vector_admissible

    def _submit_keyed(self, key, node: int,
                      tenant: Optional[str]) -> ServeResult:
        """The router's single-key submit body (`ServeEngine._submit_keyed`'s
        dist twin, one stripe lock = one owner's queue): ``key`` is the
        coalescing/cache identity — the plain node id here, ``(node,
        t_bucket)`` on the round-19 temporal router — and ``node`` what
        telemetry/journal/shed entries carry."""
        tenant = DEFAULT_TENANT if tenant is None else str(tenant)
        now = self._clock()
        events: List[Tuple] = []
        with self._pending.lock_for(key):
            res = self._admit_one_locked(key, node, tenant, now, events)
            need_flush = (res._slot is not None
                          and len(self._pending) >= self.config.max_batch)
        self.journal.record_many(events)
        if need_flush:
            self.flush()
        return res

    def _admit_one_locked(self, key, node: int, tenant: str, now: float,
                          events: List[Tuple]) -> ServeResult:
        """KEEP IN LOCKSTEP with `ServeEngine._admit_one_locked` — same
        cache/coalesce/shed/late-admit decision sequence, router-flavored
        shed message. Caller holds ``key``'s stripe lock (or all of
        them); ``_lock`` is taken only for the rid/late-admission
        window."""
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
            if shed_decision(
                len(self._pending), self._pending.tenant_count(tenant),
                tenant, self.config.max_queue_depth,
                self.config.tenant_weights,
            ):
                self.stats.shed += 1
                self.shed_log.append((self.stats.requests, tenant, node))
                events.append(("shed", -1, -1, node, 0))
                return ServeResult(error=ShedError(
                    f"router queue depth {len(self._pending)} >= "
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
                    # late admission into the routed flush still waiting
                    # for its window slot (owner split happens at seal)
                    fl.keys.append(key)
                    fl.slots.append(slot)
                    self._inflight[key] = slot
                    self.stats.late_admitted += 1
                    events.append(("late_admit", rid, fl.fid, node, 0))
                    admitted_late = True
            if not admitted_late:
                self._pending.insert_unlocked(key, slot, tenant)
                events.append(("submit", rid, -1, node, 0))
        slot.waiters.append((now, tenant))
        return ServeResult(slot=slot)

    def predict(self, node_ids, timeout: Optional[float] = None,
                tenants: Optional[Sequence[str]] = None) -> np.ndarray:
        ids = np.asarray(node_ids).reshape(-1)
        if tenants is not None and len(tenants) != ids.shape[0]:
            raise ValueError(
                f"tenants has {len(tenants)} entries for {ids.shape[0]} ids"
            )
        handles = self.submit_many(ids, tenant=tenants)
        if not handles:
            return np.zeros((0, self.out_dim), np.float32)
        if not self._running:
            while not handles.done() and self._drainable():
                self.flush()
        return self.results_many(handles, timeout)

    # batch consumption surface (round 22), identical on both front ends:
    # a ResultBatch gathers per unique slot + one inverse-map expansion,
    # anything else degrades to the per-handle result() stack
    results_many = ServeEngine.results_many

    # -- flush policy ------------------------------------------------------

    def should_flush(self) -> bool:
        # lock-free probe, mirroring ServeEngine.should_flush (round 20)
        if not self._pending:
            return False
        if len(self._pending) >= self.config.max_batch:
            return True
        oldest = self._pending.oldest_enqueue_t()
        if oldest is None:
            return False
        return (self._clock() - oldest) * 1e3 >= self.config.max_delay_ms

    def pump(self) -> int:
        return self.flush() if self.should_flush() else 0

    # -- the three router stages ------------------------------------------

    def _assemble(self) -> Optional[_RoutedFlush]:
        """Drain + publish (mirrors `ServeEngine._assemble`): the owner
        split waits for `_seal_assembled` so late-admitted seeds route with
        their flush. Lock order (round 20): every stripe lock, THEN
        ``_lock`` — same hierarchy as `ServeEngine._assemble`."""
        with self._pending.all_locks(), self._lock:
            if not self._pending:
                return None
            keys = weighted_drain_keys(
                self._pending.ordered_dict_unlocked(),
                self.config.max_batch, self.config.tenant_weights,
            )
            slots = [self._pending.pop_unlocked(k) for k in keys]
            self._inflight.update(zip(keys, slots))
            fl = _RoutedFlush(keys, slots, [])
            fl.bucket = self.config.max_batch
            self._inflight_flushes += 1
            self.stats.inflight_peak = max(
                self.stats.inflight_peak, self._inflight_flushes
            )
            # caller holds _seq: the index _seal_assembled will draw. The
            # fid is stamped UNCONDITIONALLY since round 15 — the fault
            # injector and the ejection state machine key off it, not
            # just the journal
            fl.fid = self._flush_index + 1
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

    def _seal_assembled(self, fl: _RoutedFlush) -> None:
        with self._lock:
            self._open = None
        self._flush_index += 1
        if self.workload is not None:
            # decay tick on the router's dispatch index (caller holds
            # _seq) — replay-deterministic, never wall time
            self.workload.tick()
        self.journal.emit("seal", -1, fl.fid, len(fl.keys), fl.bucket)
        # epoch pin (round 24): the router version this flush seals
        # against. Zero-stall commits flip graph_version under _seq (the
        # lock the caller holds here), so the stamp and the routing it
        # governs belong to ONE epoch, never a mix.
        fl.graph_version = self.graph_version
        try:
            arr = np.asarray(fl.keys, np.int64)
            fl.tenants = [s.tenant for s in fl.slots]
            fl.ids = arr
            fl.rids = np.fromiter(
                (s.rid for s in fl.slots), np.int64, len(fl.slots)
            )
            tix = self._tenant_index
            fl.tenant_ix = np.fromiter(
                (tix.get(t, -1) for t in fl.tenants), np.int32, len(fl.tenants)
            )
            owners = self.global2host[arr].astype(np.int64)
            rep = self.replica  # swapped only under the fence: stable here
            if rep is not None and rep.ids.size:
                # hot-set replication: replicated seeds re-route to the
                # LOCAL replica pseudo-owner — they never enter the serve
                # exchange (the whole point of the replica)
                owners = np.where(np.isin(arr, rep.ids), REPLICA_HOST,
                                  owners)
            # ONE owner partition via stable argsort (round 20), replacing
            # the per-host nonzero scan: ascending owner groups put the
            # REPLICA_HOST (-2) leg first and hosts in ascending order,
            # positions ascending within each group — exactly the split
            # the old loop built, at O(n log n) instead of O(n·hosts)
            if arr.size:
                order = np.argsort(owners, kind="stable")
                so = owners[order]
                cuts = np.nonzero(np.diff(so))[0] + 1
                for pos in np.split(order, cuts):
                    h = int(owners[pos[0]])
                    if h == REPLICA_HOST or 0 <= h < self.hosts:
                        fl.split.append((h, arr[pos], pos))
            if self.config.record_dispatches:
                self.dispatch_log.append(
                    (arr.copy(), [(h, ids.copy()) for h, ids, _ in fl.split])
                )
                self.dispatch_graph_versions.append(fl.graph_version)
            if self.config.tier_prefetch:
                # round-18: flush-ahead prefetch PER OWNER off the routed
                # sub-batches — one window earlier than each owner's own
                # assemble-time prefetch (their buffers dedup the
                # overlap). Observe-only: a failing issue never fails the
                # routed flush, and no owner key is consumed.
                for h, ids, _ in fl.split:
                    eng = self.engines.get(h)
                    if eng is None:  # replica / retired host
                        continue
                    try:
                        eng.prefetch_seeds(ids, fid=fl.fid)
                    except Exception:
                        pass
        except BaseException as exc:
            fl.error = exc

    def _dispatch(self, fl: _RoutedFlush) -> Optional[np.ndarray]:
        """Forward the per-owner sub-batches and re-interleave the answers
        into flush-key order. Collective mode ships ids/logits over the
        mesh; host mode calls the owner engines directly — per-owner legs
        there carry the round-15 fault-injection hook, the
        ``hedge_deadline_ms`` deadline, and the failover re-route, and an
        owner failure lands in ``fl.slot_errors`` (that sub-batch's slots
        only), never in ``fl.error``. Replica legs (host `REPLICA_HOST`)
        are answered locally in BOTH modes and never touch the
        exchange.

        Round 23: host-mode legs (replica included) FAN OUT onto
        per-flush worker threads and join in split order, so a routed
        flush's wall is max(leg latencies) + merge instead of their sum
        — `sequential_legs=True` keeps the sequential pass as the
        bit-parity twin, and a single-leg flush short-circuits to it
        (one leg has nothing to overlap, so no thread is spawned).
        Collective mode stays one launch either way."""
        # a = bucket per the EVENT_KINDS vocabulary; the router's "bucket"
        # is its admission cap (it pads nothing)
        self.journal.emit("dispatch", -1, fl.fid, fl.bucket)
        wl = self.workload
        out = np.zeros((len(fl.keys), self.out_dim), np.float32)
        owner_split = []
        replica_split = []
        for h, ids, pos in fl.split:
            if h == REPLICA_HOST:
                replica_split.append((h, ids, pos))
            else:
                owner_split.append((h, ids, pos))
        if self.exchange_mode == "collective":
            for _h, ids, pos in replica_split:
                self._replica_leg(fl, ids, pos, out)
            by_host = {h: (ids, pos) for h, ids, pos in owner_split}
            if by_host:  # an all-replica flush skips the collective whole
                host2ids = [
                    by_host[h][0] if h in by_host else np.array([], np.int64)
                    for h in range(self.hosts)
                ]
                host2tenants = None
                if self._tenant_names and fl.tenants:
                    # owner-side QoS: ship each sub-batch's submitting
                    # tenants as int32 registry indices beside the ids
                    # (no QoS config = no second collective — the round-15
                    # wire byte for byte)
                    host2tenants = [
                        (
                            [self._tenant_index.get(fl.tenants[int(p)], -1)
                             for p in by_host[h][1]]
                            if h in by_host else []
                        )
                        for h in range(self.hosts)
                    ]
                t_x0 = self._clock() if wl is not None else 0.0
                try:
                    res = self.comm.exchange_serve(
                        host2ids, out_dim=self.out_dim, budget=self._budget,
                        host2tenants=host2tenants,
                    )
                except comm_mod.OwnerAnswerError as exc:
                    # the collective is one launch: it cannot fail
                    # per-owner, but the failure IS attributable — feed
                    # the health/ejection state before the whole-flush
                    # error propagates
                    self._owner_failed(exc.host, fl.fid)
                    raise
                if wl is not None:
                    # one exchange round-trip covers every owner: its
                    # duration is each participating owner's flush latency
                    # at the router grain (per-owner separation needs host
                    # mode or the owners' own monitors)
                    dt = self._clock() - t_x0
                    for h, ids, _ in owner_split:
                        wl.observe_flush(h, len(ids), dt)
                L = self._budget
                with self._lock:
                    self.stats.exchange_id_bytes += (
                        self.hosts * self.hosts * L * 4
                    )
                    self.stats.exchange_logit_bytes += (
                        self.hosts * self.hosts * L * self.out_dim * 4
                    )
                for h, (ids, pos) in by_host.items():
                    out[pos] = res[h]
                # a successful exchange is a successful leg for every
                # participating owner: reset their failure counts, so
                # `fails` stays CONSECUTIVE (not cumulative over days)
                # and a past ejection never latches in collective mode
                for h, _, _ in owner_split:
                    self._owner_ok(h)
        elif self.config.sequential_legs or len(fl.split) <= 1:
            for _h, ids, pos in replica_split:
                self._replica_leg(fl, ids, pos, out)
            for h, ids, pos in owner_split:
                self._owner_leg(fl, h, ids, pos, out)
        else:
            self._fanout_legs(fl, replica_split + owner_split, out)
        out.setflags(write=False)
        # one routed round-trip = one "execute" at the router grain
        self.journal.emit("execute_done", -1, fl.fid, len(fl.split))
        return out

    # -- round-15 dispatch legs: replica, hedged owner, failover -----------

    def _leg_tenants(self, fl: _RoutedFlush, pos) -> Optional[List[str]]:
        """The submitting tenants of a sub-batch's positions — forwarded
        to the serving engine so owner-side quotas see the real tenants
        (round 16). None when no QoS is configured (tenants then change
        nothing downstream — and the legs keep calling bare
        ``predict(ids)``, byte-compatible with round-15 callables and
        test doubles)."""
        if not self.config.tenant_weights or not fl.tenants:
            return None
        return [fl.tenants[int(p)] for p in pos]

    @staticmethod
    def _predict_leg(engine, ids, tenants: Optional[List[str]]):
        if tenants is None:
            return engine.predict(ids)
        return engine.predict(ids, tenants=tenants)

    def _replica_leg(self, fl: _RoutedFlush, ids, pos, out) -> None:
        """Serve a replicated sub-batch from the LOCAL hot-set replica —
        no routing, no exchange bytes. A (should-be-impossible) local
        failure takes the same failover path as an owner failure."""
        wl = self.workload
        t0 = self._clock()
        try:
            rows = np.asarray(
                self._predict_leg(self.replica.engine, ids,
                                  self._leg_tenants(fl, pos))
            )
        except BaseException as exc:
            self._failover(fl, REPLICA_HOST, ids, pos, out, "error", exc)
            self.journal.emit("leg_done", -1, fl.fid, REPLICA_HOST,
                              len(ids))
            return
        if wl is not None:
            wl.observe_flush(REPLICA_HOST, len(ids), self._clock() - t0)
        out[pos] = rows
        with self._lock:
            self.stats.replica_hits += len(ids)
        self.journal.emit("leg_done", -1, fl.fid, REPLICA_HOST, len(ids))

    def _owner_leg(self, fl: _RoutedFlush, h: int, ids, pos, out) -> None:
        """One host-mode owner sub-batch: fault-injection hook, optional
        per-owner deadline, failover on timeout/error/ejection. Success
        resets the owner's health; failure feeds the ejection state
        machine (flush-indexed backoff — deterministic under replay)."""
        wl = self.workload
        deadline_s = self.config.hedge_deadline_ms / 1e3
        # honoring an ejection only makes sense when someone else can
        # serve the sub-batch: with no failover target, skipping the
        # owner would CONVERT its traffic into guaranteed errors for the
        # whole backoff window — attempt it instead
        ejected = (self._has_failover(h, ids)
                   and self._owner_ejected(h, fl.fid))
        rows, err, timed_out = None, None, False
        if not ejected:
            t0 = self._clock()
            try:
                if deadline_s > 0:
                    # the fault hook runs INSIDE the supervised leg so a
                    # stalled owner is indistinguishable from a slow one
                    # — exactly what the deadline exists to catch
                    rows, timed_out = self._call_with_deadline(
                        h, ids, deadline_s, fl.fid,
                        tenants=self._leg_tenants(fl, pos),
                    )
                    if timed_out:
                        err = OwnerTimeout(
                            f"owner {h} missed the "
                            f"{self.config.hedge_deadline_ms} ms hedge "
                            f"deadline at dispatch index {fl.fid}"
                        )
                else:
                    if self.faults is not None:
                        self.faults.check(h, fl.fid)
                    rows = np.asarray(
                        self._predict_leg(self.engines[h], ids,
                                          self._leg_tenants(fl, pos))
                    )
            except BaseException as exc:
                err = exc
            if wl is not None:
                # each leg individually timed — TRUE per-owner straggler
                # evidence (the fan-out path times INSIDE the leg body
                # for the same reason, so the evidence survives
                # concurrency — round 23). A timed-out leg is CENSORED
                # at the deadline (the owner did NOT answer in the
                # measured wall; the wedged-owner fast path would
                # otherwise record ~0 ms and rank the slowest owner
                # fastest)
                dt = self._clock() - t0
                if timed_out:
                    dt = max(dt, deadline_s)
                wl.observe_flush(h, len(ids), dt)
        if rows is not None and err is None:
            self._owner_ok(h)
            out[pos] = rows
            self.journal.emit("leg_done", -1, fl.fid, h, len(ids))
            return
        if not ejected:
            self._owner_failed(h, fl.fid)
        reason = ("ejected" if ejected
                  else "timeout" if timed_out else "error")
        self._failover(fl, h, ids, pos, out, reason, err)
        self.journal.emit("leg_done", -1, fl.fid, h, len(ids))

    def _call_with_deadline(self, h: int, ids, deadline_s: float,
                            fid: int, tenants: Optional[List[str]] = None):
        """Run an owner leg (fault hook included) on a worker thread
        with a deadline. On timeout the worker is ABANDONED (its eventual
        answer lands in a local box nobody reads — never the flush's
        output) and the caller hedges; an in-leg exception re-raises
        here. While ANY abandoned leg to an owner is still alive, further
        legs to it time out immediately instead of stacking more blocked
        threads — at most ``max_in_flight`` concurrent checks can slip
        through per wedge episode, so thread growth is bounded."""
        with self._lock:
            legs = self._abandoned_legs.get(h, [])
            legs[:] = [t for t in legs if t.is_alive()]
            if legs:
                return None, True  # owner still wedged from earlier legs
        box: Dict[str, object] = {}
        engine = self.engines[h]

        def run():
            try:
                if self.faults is not None:
                    self.faults.check(h, fid)
                box["rows"] = np.asarray(
                    self._predict_leg(engine, ids, tenants)
                )
            except BaseException as exc:  # delivered to the caller below
                box["err"] = exc

        th = threading.Thread(target=run, daemon=True,
                              name="quiver-hedged-owner-leg")
        th.start()
        th.join(deadline_s)
        if th.is_alive():
            with self._lock:
                self._abandoned_legs.setdefault(h, []).append(th)
            return None, True
        if "err" in box:
            raise box["err"]
        return box["rows"], False

    # -- round-23 concurrent fan-out: max(legs) + merge --------------------

    def _fanout_legs(self, fl: _RoutedFlush, split, out) -> None:
        """Run host-mode dispatch legs CONCURRENTLY and join them in
        split order, so a routed flush's wall is max(leg latencies) +
        merge instead of the sequential pass's sum — owner ``predict``
        blocks in XLA with the GIL released (and the fault hook's stall
        sleeps release it too), so the overlap is real even on one
        core.

        Determinism contract (the bit-parity twin is
        ``sequential_legs=True``; docs/api.md "Concurrent owner
        fan-out" tabulates it): leg workers fill ONLY their private
        `_LegRun.box`, and the joiner applies every side effect in
        fl.split order — replica leg first, owners ascending, exactly
        the sequential order: workload `observe_flush` sample (the
        leg's own internal duration, censored at the deadline),
        health/ejection transition, ``out[pos]`` rows, failover
        re-route (failover predicts are thereby serialized in
        deterministic order on the joining thread — one key stream on
        the fallback/replica engines), hedge log + stats, journal tail.
        So logits, dispatch logs, `hedge_events()`, owner health, and
        the journal are bit-identical to the sequential pass.

        A ``hedge_deadline_ms`` deadline becomes a BOUNDED JOIN on the
        leg's thread (`_call_with_deadline` folded into the fan-out):
        timeout abandons the worker into ``_abandoned_legs`` and
        hedges; while any abandoned leg to an owner is alive, further
        legs to it are born timed out instead of spawning — the
        wedged-owner fast path, decided HERE in split order before any
        leg starts. The ejection honor decision is prechecked the same
        way; both are bit-equivalent to the sequential pass deciding at
        leg start because each owner appears at most once per split, so
        no leg's health transition can change another leg's decision
        within one flush."""
        deadline_s = self.config.hedge_deadline_ms / 1e3
        runs = []
        for h, ids, pos in split:
            r = _LegRun(h, ids, pos, self._leg_tenants(fl, pos))
            if h != REPLICA_HOST:
                r.ejected = (self._has_failover(h, ids)
                             and self._owner_ejected(h, fl.fid))
                if not r.ejected and deadline_s > 0:
                    with self._lock:
                        legs = self._abandoned_legs.get(h, [])
                        legs[:] = [t for t in legs if t.is_alive()]
                        if legs:
                            r.box["wedged"] = True
            runs.append(r)
        cap = (self.config.leg_fanout if self.config.leg_fanout > 0
               else len(runs))

        def start_leg(r: _LegRun) -> bool:
            if r.ejected or r.box:  # ejected / wedged: never spawns
                return False
            r.t_start = self._clock()
            r.thread = threading.Thread(
                target=self._leg_body, args=(fl, r), daemon=True,
                name=f"quiver-owner-leg-{r.h}",
            )
            r.thread.start()
            return True

        for r in _bounded_leg_schedule(runs, cap, start_leg):
            self._join_leg(fl, r, deadline_s, out)

    def _leg_body(self, fl: _RoutedFlush, r: _LegRun) -> None:
        """A fan-out leg's WORKER half: fault hook + predict into the
        leg's private box. Deliberately effect-free — no stats, no
        journal, no ``out`` writes — so an abandoned (timed-out) worker
        finishing late touches nothing the joiner already settled (the
        `_call_with_deadline` abandonment contract, kept)."""
        box = r.box
        t0 = self._clock()
        try:
            engine = (self.replica.engine if r.h == REPLICA_HOST
                      else self.engines[r.h])
            if r.h != REPLICA_HOST and self.faults is not None:
                # the fault hook fires INSIDE the leg at the same
                # (owner, dispatch-index) point as the sequential pass
                self.faults.check(r.h, fl.fid)
            box["rows"] = np.asarray(
                self._predict_leg(engine, r.ids, r.tenants)
            )
        except BaseException as exc:
            box["err"] = exc
        finally:
            # leg-INTERNAL duration: true per-owner straggler evidence
            # even though legs overlap (the round-23 fix for the
            # sequential-timing caveat `_owner_leg` documents)
            box["dt"] = self._clock() - t0

    def _join_leg(self, fl: _RoutedFlush, r: _LegRun, deadline_s: float,
                  out) -> None:
        """A fan-out leg's JOINER half, run in split order on the
        flushing thread: bounded join (the hedge deadline), then apply
        the leg's side effects exactly as the sequential pass would."""
        wl = self.workload
        h, ids, pos, box = r.h, r.ids, r.pos, r.box
        if h == REPLICA_HOST:
            r.thread.join()
            err = box.get("err")
            if err is not None:
                self._failover(fl, REPLICA_HOST, ids, pos, out, "error",
                               err)
            else:
                if wl is not None:
                    wl.observe_flush(REPLICA_HOST, len(ids), box["dt"])
                out[pos] = box["rows"]
                with self._lock:
                    self.stats.replica_hits += len(ids)
            self.journal.emit("leg_done", -1, fl.fid, h, len(ids))
            return
        rows, err, timed_out = None, None, False
        if not r.ejected:
            if r.thread is not None:
                if deadline_s > 0:
                    r.thread.join(
                        max(r.t_start + deadline_s - self._clock(), 0.0)
                    )
                    if r.thread.is_alive():
                        with self._lock:
                            self._abandoned_legs.setdefault(
                                h, []).append(r.thread)
                        timed_out = True
                else:
                    r.thread.join()
            if box.get("wedged"):
                timed_out = True
            if not timed_out:
                if "err" in box:
                    err = box["err"]
                else:
                    rows = box.get("rows")
            if timed_out:
                err = OwnerTimeout(
                    f"owner {h} missed the "
                    f"{self.config.hedge_deadline_ms} ms hedge "
                    f"deadline at dispatch index {fl.fid}"
                )
            if wl is not None:
                # the leg's OWN duration (never the join wait), censored
                # at the deadline when it missed it — a wedged leg never
                # ran, so it records the deadline, like the sequential
                # fast path
                if "dt" in box:
                    dt = box["dt"]
                elif r.thread is not None:
                    dt = self._clock() - r.t_start
                else:
                    dt = 0.0
                if timed_out:
                    dt = max(dt, deadline_s)
                wl.observe_flush(h, len(ids), dt)
        if rows is not None and err is None:
            self._owner_ok(h)
            out[pos] = rows
            self.journal.emit("leg_done", -1, fl.fid, h, len(ids))
            return
        if not r.ejected:
            self._owner_failed(h, fl.fid)
        reason = ("ejected" if r.ejected
                  else "timeout" if timed_out else "error")
        self._failover(fl, h, ids, pos, out, reason, err)
        self.journal.emit("leg_done", -1, fl.fid, h, len(ids))

    def _pick_failover(self, h: int, ids
                       ) -> Tuple[Optional[ServeEngine], str]:
        """THE failover target-selection rule, used by both the ejection
        honor decision and the re-route itself (one copy — if they
        disagreed, an ejected owner could be skipped with no target and
        its sub-batch error needlessly): the full-graph fallback serves
        anything; the replica only sub-batches fully inside the hot
        set."""
        if self.fallback is not None:
            return self.fallback, "fallback"
        rep = self.replica
        if (rep is not None and h != REPLICA_HOST
                and all(int(x) in rep.id_set for x in ids)):
            return rep.engine, "replica"
        return None, ""

    def _has_failover(self, h: int, ids) -> bool:
        return self._pick_failover(h, ids)[0] is not None

    def _failover(self, fl: _RoutedFlush, h: int, ids, pos, out,
                  reason: str, err: Optional[BaseException]) -> None:
        """Re-route a failed sub-batch: the full-graph fallback serves
        anything; the replica serves sub-batches fully inside the hot
        set. No (working) target -> the sub-batch's OWN slots resolve
        with the error (per-request isolation — the flush, the engine,
        and every other sub-batch keep serving). Every decision lands in
        the hedge log keyed by the dispatch index."""
        target, tname = self._pick_failover(h, ids)
        if target is not None:
            try:
                rows = np.asarray(
                    self._predict_leg(target, ids,
                                      self._leg_tenants(fl, pos))
                )
                out[pos] = rows
                with self._lock:
                    self.stats.hedges += 1
                    self.stats.hedged_seeds += len(ids)
                    if reason == "timeout":
                        self.stats.hedge_timeouts += 1
                    elif reason == "ejected":
                        self.stats.hedge_ejected += 1
                    else:
                        self.stats.hedge_errors += 1
                self.hedge_log.append((fl.fid, int(h), reason, tname))
                self.journal.emit("hedge", -1, fl.fid, h)
                return
            except BaseException as exc:
                err = exc
        with self._lock:
            self.stats.hedge_failed += 1
        self.hedge_log.append((fl.fid, int(h), reason, "none"))
        final = err if err is not None else RuntimeError(
            f"owner {h} unavailable ({reason}) and no failover target"
        )
        for p in pos:
            fl.slot_errors[int(p)] = final

    # -- owner health / ejection state (flush-indexed, replay-stable) ------

    def _owner_ejected(self, h: int, fid: int) -> bool:
        with self._lock:
            st = self._owner_health.get(h)
            if st is None or st["ejected_at"] < 0:
                return False
            if fid >= st["ejected_at"] + self.config.eject_backoff_flushes:
                st["ejected_at"] = -1  # backoff expired: half-open probe
                return False
            return True

    def _owner_failed(self, h: int, fid: int) -> None:
        with self._lock:
            st = self._owner_health.setdefault(
                h, {"fails": 0, "ejected_at": -1}
            )
            st["fails"] += 1
            if st["fails"] >= self.config.eject_after and st["ejected_at"] < 0:
                st["ejected_at"] = fid
                self.stats.owner_ejections += 1
                self.journal.emit("eject", -1, fid, h)

    def _owner_ok(self, h: int) -> None:
        with self._lock:
            st = self._owner_health.get(h)
            if st is not None:
                st["fails"] = 0
                st["ejected_at"] = -1

    def owner_health(self) -> Dict[int, Dict[str, int]]:
        """Per-owner hedging health snapshot: consecutive ``fails`` and
        ``ejected_at`` (the dispatch index an ejection started at; -1 =
        serving)."""
        with self._lock:
            return {h: dict(st)
                    for h, st in sorted(self._owner_health.items())}

    def hedge_events(self) -> List[Tuple[int, int, str, str]]:
        """The hedge log sorted by (dispatch index, owner, reason,
        target) — the deterministic replay view (append order may
        interleave across concurrent in-flight flushes)."""
        return sorted(self.hedge_log)

    def _resolve(self, fl: _RoutedFlush, rows: Optional[np.ndarray]) -> None:
        """Per-request error isolation (round 15): a slot resolves with
        ITS error — ``fl.error`` (whole-flush: assemble/collective
        failure) or its position's ``fl.slot_errors`` entry (its owner
        sub-batch failed with no failover) — and every other slot
        resolves normally. An errored slot is never cached."""
        with self._lock:
            now = t_res0 = self._clock()
            slots = fl.slots
            if (fl.error is None and not fl.slot_errors and slots
                    and not slots[0].resolved
                    and slots[0].version == self.params_version
                    and not self._scalar_resolve):
                # round-22 block resolution, shared with ServeEngine —
                # the extra dist gate is ``slot_errors``: any per-owner
                # sub-batch failure sends the flush down the per-slot
                # loop that knows how to split error from value rows
                _resolve_block(self, fl, rows, now)
            else:
                for i, (k, slot) in enumerate(zip(fl.keys, fl.slots)):
                    self._inflight.pop(k, None)
                    if slot.resolved:
                        # abandoned by a bounded stop() drain (resolve-
                        # once rule — see ServeEngine._resolve)
                        continue
                    err = fl.error or fl.slot_errors.get(i)
                    if err is None:
                        if slot.version == self.params_version:
                            self.cache.put(k, slot.version, rows[i],
                                           gv=fl.graph_version)
                        slot.resolve(rows[i])
                    else:
                        slot.resolve(None, error=err)
                        self.stats.request_errors += 1
                    for t0, tenant in slot.waiters:
                        ms = (now - t0) * 1e3
                        self.stats.latency.record_ms(ms)
                        self.stats.tenant_hist(tenant).record_ms(ms)
            if fl.error is None:
                self.stats.router_dispatches += 1
                self.stats.routed_seeds += len(fl.keys)
                for h, ids, _ in fl.split:
                    self.stats.sub_batches[h] = self.stats.sub_batches.get(h, 0) + 1
                    self.stats.sub_batch_seeds[h] = (
                        self.stats.sub_batch_seeds.get(h, 0) + len(ids)
                    )
            self._inflight_flushes -= 1
            self._fence.notify_all()
            self.stats.spans.record("resolve", t_res0, self._clock())
            self.journal.record_many((("resolve", -1, fl.fid,
                                       len(fl.keys), 0),))

    def flush(self) -> int:
        """Route up to ``max_batch`` pending unique seeds NOW. Synchronous
        on the calling thread; up to ``max_in_flight`` concurrent callers
        overlap (the router's assemble/split is serialized in dispatch
        order under ``_seq``, so the router log — and through it every
        shard's key stream — stays deterministic). As in
        `ServeEngine.flush`, the window permit is taken under ``_seq``
        AFTER the drain, so seeds arriving while this flush waits for a
        slot join it (late admission) before the owner split is sealed.

        ERROR CONTRACT (round 15): an owner sub-batch failure in host
        mode is PER-REQUEST — it resolves only that sub-batch's slots
        with the exception (after failover was tried) and `flush` returns
        normally; only whole-flush infrastructure failures (assemble/seal
        errors, a collective-exchange abort) re-raise here."""
        fl = None
        have_permit = False
        try:
            with self._seq:
                t0 = self._clock()
                fl = self._assemble()
                if fl is not None:
                    self.stats.spans.record("assemble", t0, self._clock())
                if fl is None:
                    return 0
                try:
                    jr = self.journal
                    t_w0 = self._clock() if jr.enabled else 0.0
                    self._window.acquire()
                    have_permit = True
                    if jr.enabled:
                        jr.emit("window_wait", -1, fl.fid,
                                self._clock() - t_w0)
                    t0 = self._clock()
                    self._seal_assembled(fl)
                    self.stats.spans.record("assemble", t0, self._clock())
                finally:
                    # _seal_assembled's first act already closed admission
                    # (it MUST happen under _lock before the key draw);
                    # this repeat only covers an interrupt landing between
                    # the window acquire and the seal
                    with self._lock:
                        self._open = None
            rows = None
            if fl.error is None:
                t0 = self._clock()
                try:
                    rows = self._dispatch(fl)
                except BaseException as exc:
                    fl.error = exc
                self.stats.spans.record("dispatch", t0, self._clock())
            self._resolve(fl, rows)
            if fl.error is not None:
                raise fl.error
            return len(fl.keys)
        finally:
            if have_permit:
                self._window.release()

    def _drainable(self) -> bool:
        return bool(self._pending)

    # -- weight updates / warmup / lifecycle -------------------------------

    def update_params(self, params) -> None:
        """Fence the ROUTER (no routed flush in the air), then fence every
        shard engine through its own `update_params` — so no served logit
        anywhere crosses the weight update, and every shard's embedding
        cache is invalidated together. Lock order (round 20): stripes
        before ``_lock``, same hierarchy as `ServeEngine.update_params` —
        the fence wait releases only ``_lock`` while the stripe locks
        stay held, so submits park at stripe acquire and resolves (which
        need only ``_lock``) drain freely."""
        with self._seq:
            with self._pending.all_locks():
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    for eng in self.engines.values():
                        eng.update_params(params)
                    # the hot-set replica and the full-graph fallback
                    # serve under the same weights as the owners — same
                    # fence
                    if self.replica is not None:
                        self.replica.engine.update_params(params)
                    if self.fallback is not None:
                        self.fallback.update_params(params)
                    self._params = params
                    self.params_version += 1
                    self.cache.invalidate()
                    for slot in self._pending.values_unlocked():
                        slot.version = self.params_version

    # -- round-17 streaming graphs (ROADMAP item 1) -------------------------

    def stage_edges(self, src, dst) -> int:
        """Accumulate edge arrivals host-side into ``pending_delta`` —
        observe-only until `update_graph` commits (mirrors
        `ServeEngine.stage_edges`, including the stage-time id
        validation: a bad arrival raises here and never poisons the
        pending buffer)."""
        from ..stream import GraphDelta, validate_edge_ids

        src, dst = validate_edge_ids(
            src, dst,
            (self._stream_adj.n if self._stream_adj is not None
             else self.global2host.shape[0]),
            "staged",
        )
        with self._lock:
            if self.pending_delta is None:
                self.pending_delta = GraphDelta()
            self.pending_delta.add_edges(src, dst)
            n = len(self.pending_delta)
        self.journal.emit("graph_delta", -1, -1, n)
        return n

    def stage_removals(self, src, dst) -> int:
        """Accumulate edge DELETIONS into ``pending_delta`` (round 21)
        — mirrors `ServeEngine.stage_removals`: ids validated here,
        existence validated fleet-wide at commit preflight (the edge may
        net out against a same-batch append). Timestamp updates are NOT
        staged here: dist streaming is structural-only, updates ride the
        single-host temporal engine."""
        from ..stream import GraphDelta, validate_edge_ids

        src, dst = validate_edge_ids(
            src, dst,
            (self._stream_adj.n if self._stream_adj is not None
             else self.global2host.shape[0]),
            "removed",
        )
        with self._lock:
            if self.pending_delta is None:
                self.pending_delta = GraphDelta()
            self.pending_delta.remove_edges(src, dst)
            n = len(self.pending_delta)
        self.journal.emit("graph_delta", -1, -1, n)
        return n

    def _current_full_topo(self):
        """The build()-time full topology, RE-MATERIALIZED from the
        stream when graph deltas landed since (lazy: only the auxiliary
        rebuild paths — replica refresh, migration shard builds — pay
        the O(E) materialize; the serving path mutates tiles in place
        and never touches this)."""
        m = self._replica_materials
        with self._mat_lock:
            if self._stream_adj is not None and self._materials_stale:
                m["csr_topo"] = self._stream_adj.to_csr_topo()
                self._src_per_edge = None
                self._materials_stale = False
            return m["csr_topo"]

    def update_graph(self, delta=None) -> Dict[str, object]:
        """Commit a graph delta FLEET-WIDE behind the router's
        `update_params` fence, with the three consumers the round-10
        fence never had (ROADMAP item 1):

        1. **Owner shards extend incrementally** — for each owner, the
           delta's closure growth is BFS'd over the updated graph from
           the arriving endpoints only (k-hop closures are
           union-homomorphic: new mask = old mask OR the arrivals'
           closure — the `closure_masks` argument the r16 migration path
           rides, never a reshard). Rows already in the closure take
           in-place pad-lane appends; rows ENTERING it install their
           full adjacency into the owner stream's reserve, and their
           feature rows land in the `ClosureFeature` reserve — the
           owner's sealed fused executables just rebind arguments.
        2. **Versioned node stamps invalidate caches** — every cached
           seed whose expansion closure touched a changed row is dropped
           at the ROUTER and at every owner (reverse k-hop closure over
           the updated graph; everything else stays warm).
        3. **Stale replicas drop** — a live hot-set replica whose
           replicated seeds lie in the invalidation closure would keep
           serving PRE-delta draws; it is retired under the fence
           (oracle rules: dispatch logs kept) and, with
           ``stream_replica_rebuild``, rebuilt over the updated graph
           right after. (Tier re-placement, consumer (c), rides the
           single-host `ServeEngine.update_graph` — tiered owner
           features gather host-side and require the exchange
           residency, which streaming rebuilds instead.)

        The full-graph fallback commits the same delta so failed-over
        seeds see it too. ``delta=None`` commits ``pending_delta``; an
        empty commit is a strict no-op (frozen == empty-delta replay,
        pinned). An appended edge is visible to the next routed sample
        after this returns.

        Round 21 — staged REMOVALS commit fleet-wide under the same
        fence: existence is validated all-or-none before any mutation,
        each owner holding the row (per its post-install mask) rewrites
        the lanes locally, the fallback and the shared adjacency follow,
        and the removal sources join the invalidation closure — a
        delete-then-replay matches a fleet built without the edge, bit
        for bit (tests/test_lifecycle.py, hosts=2). Timestamp updates
        are rejected here: dist streaming is structural-only."""
        from ..stream import GraphDelta

        if self._stream_adj is None:
            raise ValueError(
                "streaming is off — build with "
                "DistServeConfig(streaming=True)"
            )
        from_pending = delta is None
        with self._lock:
            if delta is None:
                delta, self.pending_delta = self.pending_delta, None
        if delta is None or len(delta) == 0:
            return {"edges": 0, "graph_version": self.graph_version,
                    "cache_invalidated": 0, "closure_installs": 0,
                    "replica_invalidated": False}
        src, dst = delta.edges()
        rsrc, rdst = delta.removals()
        usrc, _, _ = delta.updates()
        if usrc.size:
            raise ValueError(
                "timestamp updates ride the single-host temporal engine "
                "— dist streaming is structural-only (owner streams "
                "carry no ts payload to rewrite)"
            )
        if rsrc.size:
            # all-or-none existence check BEFORE any mutation: count each
            # removal against the shared adjacency plus this batch's own
            # appends, so a bad removal raises with the whole fleet (and
            # the staged buffer, re-staged in the except below) untouched
            avail: Dict[Tuple[int, int], int] = {}
            for u, v in zip(src.tolist(), dst.tolist()):
                avail[(u, v)] = avail.get((u, v), 0) + 1
            adj0 = self._stream_adj
            for u, v in zip(rsrc.tolist(), rdst.tolist()):
                k = (u, v)
                if k not in avail:
                    avail[k] = int(np.sum(
                        np.asarray(adj0.neighbors(u)) == v
                    ))
                if avail[k] <= 0:
                    if from_pending:
                        with self._lock:
                            if self.pending_delta is not None:
                                delta.extend(self.pending_delta)
                            self.pending_delta = delta
                    raise ValueError(
                        f"removal of absent edge ({u}, {v}) — the whole "
                        "batch is rejected (all-or-none), nothing was "
                        "applied"
                    )
                avail[k] -= 1
        m = self._replica_materials
        sizes = list(m["sizes"])
        hops = max(len(sizes) - 1, 0)
        feat_hops = len(sizes)
        inv_hops = self.config.stream_invalidate_hops
        if inv_hops is None:
            inv_hops = hops
        m_feat = np.asarray(m["feat"], np.float32)
        if self.config.fenced_commits:
            return self._update_graph_fenced(
                delta, src, dst, rsrc, rdst, from_pending,
                hops, feat_hops, inv_hops, m_feat)
        return self._update_graph_zerostall(
            delta, src, dst, rsrc, rdst, from_pending,
            hops, feat_hops, inv_hops, m_feat)

    def _plan_commit_window(self, delta, src, dst, rsrc, rdst,
                            from_pending, hops, feat_hops, inv_hops):
        """The tentative-adjacency window (add -> plan/preflight ->
        commit-or-rollback), shared by the fenced and zero-stall commit
        paths. Caller holds ``_mat_lock`` (and either the router fence or
        ``_commit_lock``). On success the shared adjacency carries the
        post-append, post-removal graph, ``_materials_stale`` is set, and
        ``(affected, plans, fb_delta)`` comes back; on ANY failure the
        adjacency is rolled back, a pending-origin delta is re-staged,
        and the error re-raises — the whole fleet untouched."""
        from ..stream import GraphDelta

        adj = self._stream_adj
        adj.add_edges(src, dst)  # validates ids first
        # plan + preflight EVERY consumer over the updated adjacency
        # before mutating ANY owner — a capacity error must leave the
        # whole fleet (and the adjacency, rolled back below) untouched,
        # never one owner committed and the next one not
        try:
            # invalidation seeds: append sources UNION removal
            # sources — a removal changes its src row's draws
            # too. The reverse closure runs over the POST-
            # append, PRE-removal adjacency: reverse reach is
            # a superset there (removals only shrink forward
            # lists), so we over-invalidate, never under
            inv_seeds = (np.unique(np.concatenate([src, rsrc]))
                         if rsrc.size else np.unique(src))
            affected = adj.reverse_closure(inv_seeds, inv_hops)
            plans = []
            for h in sorted(self.engines):
                stream_h = self._owner_streams.get(h)
                if stream_h is None:
                    continue
                topo_mask, feat_mask = self._owner_masks[h]
                # fixpoint over delta chains: an edge whose
                # src entered the mask via an EARLIER delta
                # edge of this batch extends it further.
                # EVERY dst of an in-mask src seeds a BFS —
                # including dsts already in the mask: a node
                # previously at the closure BOUNDARY (row
                # kept, own closure not) can now be reached
                # at a shallower depth and gets EXPANDED, so
                # its k-hop closure must enter the mask too
                # (the >=3-layer under-extension case; a
                # superset costs reserve rows, never
                # correctness)
                new_topo = topo_mask.copy()
                while True:
                    seeds = np.unique(dst[new_topo[src]])
                    if seeds.size == 0:
                        break
                    add = adj.forward_closure(seeds, hops)
                    if not (add & ~new_topo).any():
                        break
                    new_topo |= add
                feat_seeds = np.unique(dst[new_topo[src]])
                new_feat = feat_mask | new_topo
                if feat_seeds.size:
                    # one hop deeper than the adjacency
                    # closure (leaves gathered, never
                    # expanded)
                    new_feat |= adj.forward_closure(
                        feat_seeds, feat_hops
                    )
                topo_new = np.nonzero(new_topo & ~topo_mask)[0]
                installs = [(int(nd), adj.neighbors(int(nd)))
                            for nd in topo_new]
                rel = topo_mask[src]
                owner_delta = GraphDelta(src[rel], dst[rel])
                if rsrc.size:
                    # filter removals by the NEW mask: install
                    # rows are snapshotted from the shared
                    # adjacency BEFORE removals apply (below),
                    # so a freshly-installed row still carries
                    # the doomed edge — every owner holding
                    # the row (old or just-installed) must
                    # delete it locally
                    rel_r = new_topo[rsrc]
                    owner_delta.remove_edges(rsrc[rel_r],
                                             rdst[rel_r])
                feat_new = np.nonzero(new_feat & ~feat_mask)[0]
                stream_h.preflight(owner_delta,
                                   installs=installs)
                if feat_new.size:
                    self._owner_feats[h].preflight_install(
                        feat_new
                    )
                plans.append((h, new_topo, new_feat, installs,
                              owner_delta, feat_new))
            fb_delta = GraphDelta(src, dst)
            if rsrc.size:
                fb_delta.remove_edges(rsrc, rdst)
            fb_stream = (getattr(self.fallback._sampler,
                                 "stream", None)
                         if self.fallback is not None
                         else None)
            if fb_stream is not None:
                fb_stream.preflight(fb_delta)
        except BaseException:
            adj.pop_edges(src, dst)
            if from_pending:
                # a failed commit must not DROP staged
                # arrivals (ServeEngine.update_graph's
                # contract): re-staged ahead of anything
                # staged meanwhile — arrival order is the
                # replay order. _lock guards pending_delta
                # against a concurrent stage_edges (which
                # never takes the fence)
                with self._lock:
                    if self.pending_delta is not None:
                        delta.extend(self.pending_delta)
                    self.pending_delta = delta
            raise
        # every preflight passed: apply removals to the shared
        # adjacency (cannot fail — existence was validated
        # upfront and the batch's appends just landed). Owner
        # install rows above were snapshotted pre-removal; the
        # filtered owner_delta removals bring them in line
        for u, v in zip(rsrc.tolist(), rdst.tolist()):
            adj.remove_one(int(u), int(v))
        self._materials_stale = True
        return affected, plans, fb_delta

    def _sync_fleet_epoch(self) -> None:
        """Align every LIVE engine's ``graph_version`` with the router's
        (round 24). An owner whose slice of a commit was empty (no delta
        edges in its closure, no installs) never sees an `update_graph`
        call and would lag the fleet epoch — but its arrays are
        unchanged across the commit, so its draws are identical at
        either version and the stamp realignment is bit-harmless. Owners
        that DID commit just bumped to exactly this value. Retired
        engines keep their historical stamps (their logs end at the
        epoch they served)."""
        v = self.graph_version
        for h in sorted(self.engines):
            self.engines[h].graph_version = v
        if self.fallback is not None:
            self.fallback.graph_version = v
        rep = self.replica
        if rep is not None:
            rep.engine.graph_version = v

    def _update_graph_fenced(self, delta, src, dst, rsrc, rdst,
                             from_pending, hops, feat_hops, inv_hops,
                             m_feat):
        """The round-23 parity twin (``fenced_commits=True``): drain the
        routed window under the fence, then plan + mutate + invalidate
        synchronously inside the quiet period. Served bits are identical
        to the zero-stall path; what this buys is the simpler ordering
        argument (nothing in flight ever observes a commit) at the cost
        of stalling admission for the whole drain + plan + apply."""
        stale_replica_ids = None
        installs_total = 0
        with self._seq:
            t_stall0 = self._clock()
            with self._fence:
                while self._inflight_flushes:
                    self._fence.wait()
                # _mat_lock covers the whole tentative-adjacency window
                # (add -> plan/preflight -> commit-or-rollback): a
                # background replica refresh / migration build
                # re-materializing via `_current_full_topo` must never
                # iterate the adjacency dicts mid-mutation or capture a
                # graph that is about to roll back (ordering: router
                # fence -> _mat_lock, per the lock's contract)
                with self._mat_lock:
                    affected, plans, fb_delta = self._plan_commit_window(
                        delta, src, dst, rsrc, rdst, from_pending,
                        hops, feat_hops, inv_hops)
                self.graph_version += 1
                for (h, new_topo, new_feat, installs, owner_delta,
                     feat_new) in plans:
                    if feat_new.size:
                        self._owner_feats[h].install_rows(
                            feat_new, m_feat[feat_new]
                        )
                    if len(owner_delta) or installs:
                        self.engines[h].update_graph(
                            owner_delta, installs=installs,
                            invalidate=affected,
                        )
                        installs_total += len(installs)
                    self._owner_masks[h] = (new_topo, new_feat)
                if self.fallback is not None:
                    self.fallback.update_graph(
                        fb_delta, invalidate=affected
                    )
                rep = self.replica
                if (rep is not None and rep.ids.size
                        and np.intersect1d(rep.ids, affected).size):
                    # consumer (b): the replica's closure topology went
                    # stale — retire it under the fence (oracle rules)
                    # so no routed flush ever serves a pre-delta draw
                    stale_replica_ids = rep.ids
                    if rep.engine.config.record_dispatches:
                        self._retired_replicas.append(rep.engine)
                    else:
                        self._retired_stats.merge(rep.engine.stats)
                    self.replica = None
                    self.replica_version += 1
                    self.cache.invalidate_keys(
                        int(x) for x in stale_replica_ids
                    )
                    self.stats.replica_delta_invalidations += 1
                self._sync_fleet_epoch()
                # node-keyed drop (not exact keys): temporal router-cache
                # entries are (node, t)-keyed; identical behavior for the
                # plain int keys of this engine (see
                # EmbeddingCache.invalidate_nodes)
                invalidated = self.cache.invalidate_nodes(
                    int(x) for x in affected
                )
                self.stats.graph_deltas += 1
                self.stats.delta_edges += int(src.size)
                self.stats.edges_deleted += int(rsrc.size)
                self.stats.delta_cache_invalidated += invalidated
                self.stats.delta_closure_installs += installs_total
                # per-commit serving stall = the whole _seq hold: drain
                # wait + plan + owner commits + invalidation (round 24)
                t_now = self._clock()
                stall_us = (t_now - t_stall0) * 1e6
                self.stats.commit_stall.record_ms(stall_us)
                self._commit_samples.append(
                    ("graph_version", t_now, self.graph_version))
                self._commit_samples.append(
                    ("commit_stall_us", t_now, stall_us))
        self.journal.emit("delta_commit", -1, self.graph_version,
                          int(src.size), invalidated)
        if rsrc.size:
            self.journal.emit("edge_delete", -1, self.graph_version,
                              int(rsrc.size))
        out = {"edges": int(src.size),
               "edges_deleted": int(rsrc.size),
               "graph_version": self.graph_version,
               "cache_invalidated": invalidated,
               "affected_seeds": int(affected.size),
               "closure_installs": installs_total,
               "replica_invalidated": stale_replica_ids is not None,
               "commit_stall_us": stall_us}
        if stale_replica_ids is not None and self.config.stream_replica_rebuild:
            # rebuild OUTSIDE the fence (AOT warmup costs seconds;
            # refresh_replicas takes the fence itself for the swap)
            out["replica_refresh"] = self.refresh_replicas(
                ids=stale_replica_ids
            )
        return out

    def _update_graph_zerostall(self, delta, src, dst, rsrc, rdst,
                                from_pending, hops, feat_hops, inv_hops,
                                m_feat):
        """Round-24 tentpole: the fleet commit with NO window drain. The
        plan/preflight window and every owner's array build run entirely
        off-fence under ``_commit_lock`` (owner engines flip under their
        OWN ``_seq`` via their zero-stall `update_graph`); the router's
        flip — version bump + replica retire — holds ``_seq`` only long
        enough for a few reference assignments. Routed flushes sealed
        before the flip complete against the arrays (and owner routing)
        they pinned at seal; flushes sealed after serve the new epoch.
        Invalidation is the post-flip `EmbeddingCache.raise_floor` pass:
        resident pre-commit rows for affected seeds drop eagerly, and
        the per-node floor gates the late writeback of any old-epoch
        flush still in the air — the lazy equivalent of the fenced
        path's synchronous `invalidate_nodes`. The visibility contract
        is unchanged: an appended edge is visible to the next routed
        sample after this returns; a flush RACING the commit may serve
        either epoch (its stamp says which)."""
        stale_replica_ids = None
        installs_total = 0
        with self._commit_lock:
            # same tentative window as the fenced path, minus the fence:
            # _mat_lock alone serializes the shared-adjacency mutation
            # against background replica/migration materializes
            with self._mat_lock:
                affected, plans, fb_delta = self._plan_commit_window(
                    delta, src, dst, rsrc, rdst, from_pending,
                    hops, feat_hops, inv_hops)
            new_version = self.graph_version + 1
            # owner commits BEFORE the router flip: each is itself
            # zero-stall (propagated `fenced_commits`), flipping under
            # its own _seq after building off-fence. Until the router
            # flip lands, routed flushes seal at the OLD router version
            # while an already-flipped owner serves new-epoch draws —
            # exactly the commit race window the epoch stamps resolve
            # (each owner flush replays against its own stamp)
            for (h, new_topo, new_feat, installs, owner_delta,
                 feat_new) in plans:
                if feat_new.size:
                    # reserve rows are fresh (never yet gathered), so
                    # concurrent owner traffic cannot observe the write
                    self._owner_feats[h].install_rows(
                        feat_new, m_feat[feat_new]
                    )
                if len(owner_delta) or installs:
                    self.engines[h].update_graph(
                        owner_delta, installs=installs,
                        invalidate=affected,
                    )
                    installs_total += len(installs)
                self._owner_masks[h] = (new_topo, new_feat)
            if self.fallback is not None:
                self.fallback.update_graph(
                    fb_delta, invalidate=affected
                )
            # THE router flip: O(1) assignments under _seq — no drain,
            # no in-flight wait. _seal_assembled stamps and routes under
            # this same lock, so version, replica routing and the stamp
            # stay one epoch per flush.
            with self._seq:
                t_stall0 = self._clock()
                self.graph_version = new_version
                rep = self.replica
                if (rep is not None and rep.ids.size
                        and np.intersect1d(rep.ids, affected).size):
                    # consumer (b), deferred flavor: the stale replica
                    # unroutes AT the flip; in-flight replica legs
                    # complete against the retired engine's pinned
                    # arrays and replay under their old-epoch stamp
                    stale_replica_ids = rep.ids
                    if rep.engine.config.record_dispatches:
                        self._retired_replicas.append(rep.engine)
                    else:
                        self._retired_stats.merge(rep.engine.stats)
                    self.replica = None
                    self.replica_version += 1
                t_now = self._clock()
                stall_us = (t_now - t_stall0) * 1e6
            self._sync_fleet_epoch()
            # post-flip deferred invalidation (consumer (a)): floors gate
            # stale writebacks from old-epoch in-flight flushes; the
            # replica's exact keys drop conservatively as before
            if stale_replica_ids is not None:
                self.cache.invalidate_keys(
                    int(x) for x in stale_replica_ids
                )
            invalidated = self.cache.raise_floor(
                (int(x) for x in affected), new_version
            )
            with self._lock:
                if stale_replica_ids is not None:
                    self.stats.replica_delta_invalidations += 1
                self.stats.graph_deltas += 1
                self.stats.delta_edges += int(src.size)
                self.stats.edges_deleted += int(rsrc.size)
                self.stats.delta_cache_invalidated += invalidated
                self.stats.delta_closure_installs += installs_total
                self.stats.commit_stall.record_ms(stall_us)
                self._commit_samples.append(
                    ("graph_version", t_now, new_version))
                self._commit_samples.append(
                    ("commit_stall_us", t_now, stall_us))
        self.journal.emit("delta_commit", -1, self.graph_version,
                          int(src.size), invalidated)
        if rsrc.size:
            self.journal.emit("edge_delete", -1, self.graph_version,
                              int(rsrc.size))
        out = {"edges": int(src.size),
               "edges_deleted": int(rsrc.size),
               "graph_version": self.graph_version,
               "cache_invalidated": invalidated,
               "affected_seeds": int(affected.size),
               "closure_installs": installs_total,
               "replica_invalidated": stale_replica_ids is not None,
               "commit_stall_us": stall_us}
        if stale_replica_ids is not None and self.config.stream_replica_rebuild:
            # rebuild outside the commit lock's critical tail (AOT
            # warmup costs seconds; refresh_replicas fences itself for
            # the swap)
            out["replica_refresh"] = self.refresh_replicas(
                ids=stale_replica_ids
            )
        return out

    def compact_graph(self, max_moves: Optional[int] = None
                      ) -> Dict[str, Dict[str, object]]:
        """One fleet-wide compaction pass (round 21): each owner
        engine's `ServeEngine.compact_graph` plus the fallback's, in
        deterministic host order. Each engine plans off-fence and flips
        under its OWN fence (compaction is per-stream row bookkeeping —
        no cross-owner coordination needed, because it is strictly
        observe-only on served bits: no version bump, no invalidation,
        no routing change). Owners without a bound stream are skipped.
        Returns per-owner summaries keyed ``"host<h>"`` plus
        ``"fallback"``, and an aggregate ``"tiles_reclaimed"``."""
        out: Dict[str, Dict[str, object]] = {}
        total = 0
        for h in sorted(self.engines):
            eng = self.engines[h]
            if getattr(eng._sampler, "stream", None) is None:
                continue
            s = eng.compact_graph(max_moves=max_moves)
            out[f"host{h}"] = s
            total += int(s["tiles_reclaimed"])
        if (self.fallback is not None
                and getattr(self.fallback._sampler, "stream", None)
                is not None):
            s = self.fallback.compact_graph(max_moves=max_moves)
            out["fallback"] = s
            total += int(s["tiles_reclaimed"])
        out["tiles_reclaimed"] = total  # type: ignore[assignment]
        return out

    def adapt_tiers(self) -> Dict[int, Dict[str, object]]:
        """One fleet-wide promote/demote pass (round 14): fence the
        ROUTER (no routed flush in the air — the same drain as
        `update_params`), then run each owner engine's `adapt_tiers`
        under it; every owner fences its own in-flight flushes too, so
        no flush anywhere straddles a placement batch. Owners whose
        feature has no adaptive store (or no workload sketch) are
        skipped. Per-owner summaries keyed by host, deterministic order.
        NOTE the owner engines' own background consumers stay OFF in
        dist mode (``tier_adapt_every_s`` is not inherited by the shard
        config) — the router is the single adaptation driver, which is
        what keeps fleet passes fenced against routed flushes."""
        out: Dict[int, Dict[str, object]] = {}
        with self._seq:
            with self._fence:
                while self._inflight_flushes:
                    self._fence.wait()
                for h in sorted(self.engines):
                    eng = self.engines[h]
                    if eng._tier_feature is None or eng.workload is None:
                        continue
                    out[h] = eng.adapt_tiers()
        return out

    @property
    def placement_version(self) -> int:
        """Sum of the owner engines' fenced placement batches (a fleet
        placement-progress gauge, not a coherence version — shards move
        rows independently)."""
        return sum(e.placement_version for e in self.engines.values())

    def refresh_replicas(self, ids=None, k: Optional[int] = None,
                         ) -> Dict[str, object]:
        """(Re)build the hot-set replica (round 15, ROADMAP item 3a):
        pick the head — ``ids`` explicitly, or the ``k`` hottest seeds
        from the ROUTER's workload sketch (``k`` defaults to
        ``config.replicate_top_k``; price it with `scaling.skew_table`
        from the measured head-concentration curve) — and mirror it
        locally as a full `ServeEngine` over the head's halo-closure
        topology (`shard_topology_for_seeds`) + feature rows
        (`ClosureFeature`).

        The swap runs under the SAME fence as `update_params` /
        `apply_placement` (sequencing lock + in-flight drain), so no
        routed flush ever straddles a replica version; the router cache
        entries of every REFRESHED key (old set union new set — the keys
        whose serving path changed) are invalidated, and exactly those
        (pinned in tests/test_serve_dist.py). ``replica_version`` bumps
        per refresh. ``ids=[]`` disables replication.

        Replica-served rows keep the standing parity contract: the
        closure topology makes the replica sampler's draws for
        replicated seeds bit-equal to a full-graph sampler's on the same
        key stream, so `replay_fleet_oracle` replays its dispatch log
        exactly like an owner shard's."""
        if self._replica_materials is None:
            raise ValueError(
                "hot-set replication needs the build()-time materials "
                "(full topology + feature table); a bare-constructed "
                "multi-process engine holds only its own shard"
            )
        m = self._replica_materials
        if ids is None:
            k = int(self.config.replicate_top_k if k is None else k)
            if k <= 0:
                raise ValueError(
                    "pass ids= or set DistServeConfig.replicate_top_k > 0"
                )
            if self.workload is None:
                raise ValueError(
                    "picking the hot set reads the router workload sketch "
                    "— pass DistServeConfig(workload=WorkloadConfig(...)) "
                    "or give ids= explicitly"
                )
            ids = self.workload.hot_set(k)
        ids = np.unique(np.asarray(ids, np.int64))
        new_replica = None
        st: Dict[str, float] = {}
        if ids.size:
            from ..pyg.sage_sampler import GraphSageSampler

            sizes = list(m["sizes"])
            # adjacency closure: len(sizes)-1 expansion hops; feature
            # closure one deeper (leaves gathered, never expanded) — the
            # same construction as the owner shards in `build`. The
            # source topology is the CURRENT one: a streaming fleet
            # re-materializes the full graph from the stream first, so a
            # rebuilt replica serves post-delta draws (round 17).
            full_topo = self._current_full_topo()
            topo_r, st, closure_ids = shard_topology_for_seeds(
                full_topo, ids, hops=len(sizes) - 1,
                closure_hops=len(sizes),
            )
            sampler = GraphSageSampler(
                topo_r, sizes=sizes, mode=m["sampler_mode"],
                seed=m["sampler_seed"], **m["sampler_kw"],
            )
            n = full_topo.indptr.shape[0] - 1
            local_map = np.full(n, -1, np.int32)
            local_map[closure_ids] = np.arange(
                closure_ids.shape[0], dtype=np.int32
            )
            feat_r = ClosureFeature(
                np.asarray(m["feat"], np.float32)[closure_ids], local_map
            )
        # construct + AOT-warmup the replica engine OUTSIDE the fence:
        # the bucket compiles take seconds, and a routine refresh must
        # not stall every submit() (the fence Condition wraps the
        # router's request lock) for that long. Only the pointer swap +
        # cache invalidation need the fence.
        eng = None
        if ids.size:
            with self._lock:
                params_snapshot = self._params
            eng = ServeEngine(
                m["model"], params_snapshot, sampler, feat_r,
                m["shard_config"],
            )
            # a mid-run engine is born AT the current fleet epoch: its
            # dispatch-log stamps must line up with the router's (round
            # 24 epoch-filtered replay)
            eng.graph_version = self.graph_version
            eng.warmup()
        with self._seq:
            with self._fence:
                while self._inflight_flushes:
                    self._fence.wait()
                if eng is not None and self._params is not params_snapshot:
                    # a weight update landed while we compiled: re-stamp
                    # under the fence (cheap — swap + invalidate) so the
                    # replica never serves stale params
                    eng.update_params(self._params)
                old = self.replica
                if old is not None and old.engine.config.record_dispatches:
                    # kept ONLY for the replay oracle (its dispatch log
                    # vouches for pre-refresh rows) — a production engine
                    # without dispatch recording retains nothing, so
                    # periodic refreshes never accumulate dead engines
                    self._retired_replicas.append(old.engine)
                elif old is not None:
                    # dropped engine: counters fold so the merged fleet
                    # view never goes backwards across a refresh
                    self._retired_stats.merge(old.engine.stats)
                self.replica_version += 1
                if eng is not None:
                    new_replica = _HotReplica(
                        eng, ids, self.replica_version, dict(st)
                    )
                self.replica = new_replica
                old_ids = old.ids if old is not None else np.array(
                    [], np.int64
                )
                refreshed = np.union1d(old_ids, ids)
                invalidated = self.cache.invalidate_keys(
                    int(x) for x in refreshed
                )
        return {
            "replicated": int(ids.size),
            "version": self.replica_version,
            "invalidated": invalidated,
            "closure_nodes": int(st.get("closure_nodes", 0)),
            "edge_frac": float(st.get("edge_frac", 0.0)),
        }

    # -- round-16 elastic fleet: live resharding ---------------------------

    def _elastic_gate(self) -> None:
        """Preconditions for `scale`/`rebalance`: build()-time materials
        (the full topology + feature table the extended shards are cut
        from), host-mode per-owner legs (the collective mesh is sized at
        build — growing it means a new mesh, comm, and answerer set, not
        a range flip), and closure feature residency (the exchange
        residency's `DistFeature` partition is registered against a fixed
        ownership map)."""
        if self._replica_materials is None:
            raise ValueError(
                "live resharding needs the build()-time materials (full "
                "topology + feature table); a bare-constructed "
                "multi-process engine holds only its own shard"
            )
        if self.exchange_mode != "host":
            raise ValueError(
                "scale/rebalance ride the host-mode per-owner legs; the "
                "collective mesh is sized at build and cannot gain or "
                "lose hosts mid-run — build with exchange='host'"
            )
        if self.config.feature_residency != "closure":
            raise ValueError(
                "live resharding requires feature_residency='closure' "
                "(the exchange residency's DistFeature partition is "
                "registered against a fixed ownership map)"
            )

    def _build_extended_owner(self, dst: int, ids: np.ndarray):
        """Land ``ids``'s closure on owner ``dst`` OUTSIDE any fence (the
        old owner keeps serving the range): BFS only the migrated range
        (`closure_masks` — k-hop closures are union-homomorphic, so the
        destination's new masks are old-OR-range, no re-BFS of rows it
        already held), materialize the extended shard topology + closure
        feature rows, and AOT-warm a fresh `ServeEngine` over them.

        The new engine's sampler is BORN FRESH (same seed as every shard
        sampler), so its draws for any owned seed are bit-equal to a
        freshly born full-graph sampler's at the same key index — the
        standing parity argument; the replaced engine retires WITH its
        dispatch log so `replay_fleet_oracle` can still vouch for every
        row it served (ownership epochs change WHO computes, never any
        completed bit)."""
        from ..pyg.sage_sampler import GraphSageSampler

        m = self._replica_materials
        # streaming fleets migrate over the UPDATED graph (lazy
        # re-materialize; the masks stay valid — update_graph extends
        # them at every commit)
        topo = self._current_full_topo()
        indptr = np.asarray(topo.indptr, np.int64)
        indices = np.asarray(topo.indices, np.int64)
        n = indptr.shape[0] - 1
        if self._src_per_edge is None:
            self._src_per_edge = np.repeat(
                np.arange(n, dtype=np.int64), (indptr[1:] - indptr[:-1])
            )
        seed_mask = np.zeros(n, bool)
        seed_mask[ids] = True
        sizes = list(m["sizes"])
        add_topo, add_feat = closure_masks(
            indptr, indices, seed_mask,
            hops=len(sizes) - 1, feat_hops=len(sizes),
            src_per_edge=self._src_per_edge,
        )
        base = self._owner_masks.get(dst)
        if base is not None:
            new_topo, new_feat = base[0] | add_topo, base[1] | add_feat
        else:
            new_topo, new_feat = add_topo, add_feat
        shard, _ = shard_from_mask(topo, new_topo,
                                   src_per_edge=self._src_per_edge)
        closure_ids = np.nonzero(new_feat)[0]
        local_map = np.full(n, -1, np.int32)
        local_map[closure_ids] = np.arange(closure_ids.shape[0],
                                           dtype=np.int32)
        feat_r = ClosureFeature(
            np.asarray(m["feat"], np.float32)[closure_ids], local_map,
            reserve_rows=_feat_reserve(self.config, closure_ids.shape[0]),
        )
        sampler = GraphSageSampler(
            shard, sizes=sizes, mode=m["sampler_mode"],
            seed=m["sampler_seed"], **m["sampler_kw"],
        )
        new_stream = None
        if self.config.streaming:
            # a migrated-in owner must keep streaming: bind the extended
            # shard to its own tile stream so later deltas apply in place
            from ..stream import StreamingTiledGraph

            new_stream = StreamingTiledGraph(
                shard, reserve_frac=self.config.stream_reserve_frac
            )
            sampler.bind_stream(new_stream)
        with self._lock:
            params_snapshot = self._params
        eng = ServeEngine(
            m["model"], params_snapshot, sampler, feat_r, m["shard_config"]
        )
        # born at the current fleet epoch (round-24 stamp alignment)
        eng.graph_version = self.graph_version
        eng.warmup()
        return eng, (new_topo, new_feat), params_snapshot, new_stream, feat_r

    def _migrate_batch(self, lo: int, hi: int, src: int, dst: int) -> str:
        """Hand ONE bounded ownership range ``[lo, hi)`` from ``src`` to
        ``dst`` — the migration unit. Build/land outside the fence (old
        owner serves throughout), then a PER-RANGE fence (the
        `update_params`/`apply_placement` drain, held only for the
        pointer flip) swaps the destination engine, flips
        ``global2host[lo:hi]``, bumps the ownership epoch, and
        invalidates exactly the migrated seeds' router-cache and
        old-owner-cache entries. Returns the outcome, one of:

        - ``"commit"``       — the range now routes to ``dst``;
        - ``"rollback"``     — ``dst`` died mid-landing (fault hook at
          this batch's migration index): the built shard is discarded
          and the range STAYS with ``src``, which never stopped serving
          it — no fence was taken, no state moved;
        - ``"rollforward"``  — ``src`` died after the shard landed: the
          flip completes (``dst`` holds everything the range needs) and
          the dead owner's remaining traffic is the hedging machinery's
          problem, exactly like any serve-time kill.

        Deterministic by construction: the outcome reads only (owner,
        migration batch index) — same plan, same batch log."""
        with self._migration_lock:
            mig = self._mig_index
            self._mig_index += 1
            ids = np.arange(lo, hi, dtype=np.int64)
            jr = self.journal
            jr.emit("migrate", -1, mig, lo, hi)
            rollforward = False
            try:
                if self.faults is not None:
                    # destination-side hook: a dst kill/error here is a
                    # death while the shard lands → roll back
                    self.faults.check_migration(dst, mig)
                built = self._build_extended_owner(dst, ids)
                if self.faults is not None:
                    # source-side hook: src died AFTER the shard landed
                    # → roll forward (dst has everything it needs)
                    try:
                        self.faults.check_migration(src, mig)
                    except OwnerFault:
                        rollforward = True
            except OwnerFault:
                self.migration_log.append(
                    (mig, self.ownership_epoch, lo, hi, src, dst, 0,
                     "rollback")
                )
                with self._lock:
                    self.stats.migration_rollbacks += 1
                jr.emit("migrate_rollback", -1, mig, src, dst)
                return "rollback"
            eng, new_masks, params_snapshot, new_stream, new_feat = built
            with self._seq:
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    if self._params is not params_snapshot:
                        # a weight update landed while the shard built:
                        # re-stamp under the fence (cheap), same rule as
                        # a replica refresh
                        eng.update_params(self._params)
                    old = self.engines.get(dst)
                    if old is not None:
                        if old.config.record_dispatches:
                            self._retired_engines.append(old)
                        else:
                            self._retired_stats.merge(old.stats)
                    self.engines[dst] = eng
                    self._owner_masks[dst] = new_masks
                    if new_stream is not None:
                        self._owner_streams[dst] = new_stream
                        self._owner_feats[dst] = new_feat
                    self.global2host[lo:hi] = dst
                    self.ownership_epoch += 1
                    # range-scoped invalidation: exactly the migrated
                    # seeds' entries — their serving path changed (the
                    # replica-refresh rule); everything else stays warm
                    self.cache.invalidate_keys(range(lo, hi))
                    src_eng = self.engines.get(src)
                    if src_eng is not None:
                        src_eng.cache.invalidate_keys(int(i) for i in ids)
                    outcome = "rollforward" if rollforward else "commit"
                    self.migration_log.append(
                        (mig, self.ownership_epoch, lo, hi, src, dst,
                         int(ids.size), outcome)
                    )
                    # the fence Condition wraps _lock — already held here
                    self.stats.migration_batches += 1
                    self.stats.migrated_seeds += int(ids.size)
                    if rollforward:
                        self.stats.migration_rollforwards += 1
            jr.emit("migrate_commit", -1, mig, src, dst)
            return outcome

    def rebalance(self, target_global2host=None,
                  max_seeds: Optional[int] = None) -> Dict[str, object]:
        """Migrate seed ownership toward ``target_global2host`` one
        bounded range at a time (``config.migrate_batch_seeds`` per
        fenced flip; `plan_migration_ranges` cuts the delta into
        per-(src, dst) contiguous runs). With no explicit target, plans
        one load-shedding move off the hottest owner from the router's
        `OwnerLoadStats` + Count-Min estimates (`_plan_load_target`) —
        the telemetry-driven path `maybe_rebalance` and the background
        timer ride. Ranges whose destination dies mid-landing roll back
        (and keep counting); a `stop()` in progress halts BETWEEN
        batches (never mid-range). Returns the pass summary."""
        self._elastic_gate()
        if target_global2host is None:
            target_global2host = self._plan_load_target(max_seeds)
            if target_global2host is None:
                return {"batches": 0, "migrated_seeds": 0, "rollbacks": 0,
                        "rollforwards": 0, "epoch": self.ownership_epoch,
                        "planned": 0, "skipped": "balanced"}
        target = np.asarray(target_global2host, np.int32)
        if target.shape != self.global2host.shape:
            raise ValueError(
                f"target has {target.shape[0]} rows, graph has "
                f"{self.global2host.shape[0]}"
            )
        if target.size and (target.min() < 0 or target.max() >= self.hosts):
            raise ValueError(
                f"target owners outside [0, {self.hosts})"
            )
        ranges = plan_migration_ranges(
            self.global2host, target, self.config.migrate_batch_seeds
        )
        batches = rollbacks = rollforwards = moved = 0
        for lo, hi, src, dst in ranges:
            if self._draining:
                break  # stop() halts between batches, never mid-range
            outcome = self._migrate_batch(lo, hi, src, dst)
            if outcome == "rollback":
                rollbacks += 1
            else:
                batches += 1
                moved += hi - lo
                if outcome == "rollforward":
                    rollforwards += 1
        return {"batches": batches, "migrated_seeds": moved,
                "rollbacks": rollbacks, "rollforwards": rollforwards,
                "epoch": self.ownership_epoch, "planned": len(ranges)}

    def scale(self, hosts: int) -> Dict[str, object]:
        """Grow or shrink the serving fleet to ``hosts`` under live
        traffic (ROADMAP item 2): the target ownership is the canonical
        balanced `contiguous_partition`, and every changed range migrates
        through `rebalance`'s bounded fenced batches — the old owner
        serves each range until the new owner's halo-closure shard and
        feature rows land. Shrinks retire the emptied hosts' engines
        (dispatch logs kept for the replay oracle); if a rollback left
        seeds on a to-be-removed host, that host SURVIVES (reported in
        ``incomplete_hosts``) — a seed is never stranded ownerless."""
        self._elastic_gate()
        new_h = int(hosts)
        if new_h < 1:
            raise ValueError("hosts must be >= 1")
        old_h = self.hosts
        n = self.global2host.shape[0]
        target = contiguous_partition(n, new_h)
        if new_h > old_h:
            # routing to the new owners only begins at their first range
            # flip; until then they own nothing and get no sub-batches
            self.hosts = new_h
        summary = self.rebalance(target)
        summary["hosts_before"], summary["hosts_target"] = old_h, new_h
        if new_h < old_h:
            with self._seq:
                with self._fence:
                    while self._inflight_flushes:
                        self._fence.wait()
                    leftover = np.unique(
                        self.global2host[self.global2host >= new_h]
                    )
                    if leftover.size:
                        summary["incomplete_hosts"] = [
                            int(x) for x in leftover
                        ]
                    else:
                        for h in range(new_h, self.hosts):
                            eng = self.engines.pop(h, None)
                            self._owner_masks.pop(h, None)
                            self._owner_health.pop(h, None)
                            self._owner_streams.pop(h, None)
                            self._owner_feats.pop(h, None)
                            if eng is None:
                                continue
                            if eng.config.record_dispatches:
                                self._retired_engines.append(eng)
                            else:
                                self._retired_stats.merge(eng.stats)
                        self.hosts = new_h
        summary["hosts"] = self.hosts
        return summary

    def maybe_rebalance(self) -> Optional[Dict[str, object]]:
        """The telemetry trigger: migrate ranges off the hottest owner
        iff `OwnerLoadStats` imbalance crossed
        ``config.rebalance_imbalance``. Returns the rebalance summary or
        None when balanced (or no telemetry). `start()` runs this on a
        timer when ``rebalance_every_s`` > 0."""
        self._elastic_gate()
        target = self._plan_load_target()
        if target is None:
            return None
        return self.rebalance(target)

    def _plan_load_target(self, max_seeds: Optional[int] = None
                          ) -> Optional[np.ndarray]:
        """One load-shedding ownership target from the router telemetry:
        when the hottest owner's routed-seed load exceeds
        ``rebalance_imbalance`` x the mean, move its hottest contiguous
        owned runs (scored by the Count-Min per-seed estimate — the
        sketch names WHICH ranges carry the excess) to the least-loaded
        owner, until ~half the excess moved or ``rebalance_max_seeds``
        seeds are in flight. Deterministic: reads only sketch/owner
        state, ties break on ids. None = balanced or not enough
        telemetry."""
        if self.workload is None or self.hosts < 2:
            return None
        loads = {h: 0 for h in range(self.hosts)}
        for h, v in self.workload.owners.seeds_by_owner().items():
            if 0 <= h < self.hosts:
                loads[h] = int(v)
        total = sum(loads.values())
        if total <= 0:
            return None
        mean = total / self.hosts
        hot = max(loads, key=lambda h: (loads[h], -h))
        cold = min(loads, key=lambda h: (loads[h], h))
        if hot == cold or loads[hot] < self.config.rebalance_imbalance * mean:
            return None
        excess = loads[hot] - mean
        owned = np.nonzero(self.global2host == hot)[0]
        if owned.size == 0:
            return None
        cms = self.workload.cms
        est = np.asarray(cms.estimate_many(owned), np.float64)
        # contiguous runs of the hot owner's ids, hottest-first
        cuts = np.nonzero(np.diff(owned) != 1)[0] + 1
        run_bounds = zip(np.concatenate(([0], cuts)),
                         np.concatenate((cuts, [owned.size])))
        runs = sorted(
            ((float(est[a:b].sum()), int(owned[a]), int(owned[b - 1]) + 1)
             for a, b in run_bounds),
            key=lambda r: (-r[0], r[1]),
        )
        budget = int(max_seeds or self.config.rebalance_max_seeds)
        target = self.global2host.copy()
        moved_est, moved_seeds = 0.0, 0
        goal = excess / 2.0
        for score, lo, hi in runs:
            if moved_est >= goal or moved_seeds >= budget:
                break
            take = min(hi - lo, budget - moved_seeds)
            target[lo:lo + take] = cold
            sl = (owned >= lo) & (owned < lo + take)
            moved_est += float(est[sl].sum())
            moved_seeds += take
        if moved_seeds == 0:
            return None
        return target

    def routing_epochs(self) -> List[Tuple[int, int, int, int, int]]:
        """Committed ownership flips as (epoch, lo, hi, src, dst) — the
        deterministic routing-epoch history replay comparisons read
        (rollbacks never bump the epoch and are excluded; read
        ``migration_log`` for the full batch log including them)."""
        return [(e, lo, hi, src, dst)
                for (_mig, e, lo, hi, src, dst, _n, oc) in self.migration_log
                if oc != "rollback"]

    def _replica_refresh_pass(self) -> Optional[Dict[str, object]]:
        """One background-refresh check (the r15 remaining-leverage
        note): re-run `refresh_replicas` iff the router sketch's hot set
        drifted at least ``replica_drift_frac`` away from what the live
        replica holds (`WorkloadMonitor.hot_set_drift`); a first pass
        with no replica builds one. Returns the refresh summary or None
        when skipped — fenced and observe-parity pinned exactly like the
        manual path, because it IS the manual path behind a drift
        check."""
        if self.workload is None or self.config.replicate_top_k <= 0:
            return None
        k = self.config.replicate_top_k
        hot = self.workload.hot_set(k)
        if hot.size == 0:
            return None
        rep = self.replica
        if rep is not None:
            drift = self.workload.hot_set_drift(rep.ids, k)
            if drift < self.config.replica_drift_frac:
                return None
        out = self.refresh_replicas(k=k)
        with self._lock:
            self.stats.replica_refreshes += 1
        return out

    def _policy_loop(self, period: float, fn, err_attr: str) -> None:
        """Shared background-policy driver (replica refresh, rebalance):
        sleep in small slices so stop() never waits a full period; a
        failing pass bumps its error counter instead of killing the
        thread (the tier-daemon contract)."""
        while self._running:
            deadline = time.monotonic() + period
            while self._running and time.monotonic() < deadline:
                time.sleep(min(0.05, period))
            if not self._running:
                return
            try:
                fn()
            except Exception:
                setattr(self, err_attr, getattr(self, err_attr) + 1)

    def warmup(self) -> Dict[object, Dict[int, float]]:
        """Pre-trace every shard engine's bucket programs (twin samplers
        where supported, so no shard's key stream moves) — plus the
        full-graph fallback's and the live replica's, under the
        ``"fallback"`` / ``"replica"`` keys. Returns
        {host: {bucket: seconds}}."""
        out: Dict[object, Dict[int, float]] = {
            h: eng.warmup() for h, eng in self.engines.items()
        }
        if self.fallback is not None:
            out["fallback"] = self.fallback.warmup()
        if self.replica is not None:
            out["replica"] = self.replica.engine.warmup()
        return out

    def aggregate_stats(self) -> Dict[str, object]:
        """Router snapshot + the per-shard `ServeStats` merged into one
        view (`ServeStats.merge` -> the `trace` merge family) + per-shard
        topology shard stats. The merged latency histogram is OWNER-side
        latency; end-to-end latency (queue + route + owner + return) is the
        router's own ``stats.latency``. The replica/fallback engines (when
        built) merge into ``shards_merged`` and appear under their own
        keys — they are serving engines like any owner."""
        merged = ServeStats()
        for h in sorted(self.engines):
            merged.merge(self.engines[h].stats)
        # engines retired by a range handoff or a shrink served real
        # traffic — their counters stay in the merged fleet view
        # (retained engines merge live; dropped ones were folded into
        # _retired_stats at retirement)
        for eng in self._retired_engines:
            merged.merge(eng.stats)
        merged.merge(self._retired_stats)
        out: Dict[str, object] = {
            "router": self.stats.snapshot(),
            "per_shard": {
                h: self.engines[h].stats.snapshot() for h in sorted(self.engines)
            },
            "topology": self.shard_topo_stats,
            "retired_engines": len(self._retired_engines),
        }
        if self.replica is not None:
            merged.merge(self.replica.engine.stats)
            out["replica"] = self.replica.engine.stats.snapshot()
            out["replica"]["replicated_ids"] = int(self.replica.ids.size)
        if self.fallback is not None:
            merged.merge(self.fallback.stats)
            out["fallback"] = self.fallback.stats.snapshot()
        out["shards_merged"] = merged.snapshot()
        return out

    def reset_stats(self) -> None:
        """Zero router counters (re-pointing the router cache's counter at
        the fresh stats, same contract as `ServeEngine.reset_stats`) and
        every shard engine's stats (journals included). Cache CONTENTS are
        untouched."""
        with self._lock:
            self.stats = DistServeStats()
            self.cache.counters = self.stats.router_cache
            if self.journal.enabled:
                self.journal.clear()
            if self.workload is not None:
                self.workload.clear()
        for eng in self.engines.values():
            eng.reset_stats()
        if self.replica is not None:
            self.replica.engine.reset_stats()
        if self.fallback is not None:
            self.fallback.reset_stats()

    # -- fleet observability ----------------------------------------------

    def register_metrics(self, registry: Optional[MetricsRegistry] = None,
                         prefix: str = "quiver_router",
                         labels: Optional[Dict[str, str]] = None,
                         ) -> MetricsRegistry:
        """Adapt the ROUTER's live state into a registry (created when not
        given): `DistServeStats` counters, queue/window gauges, exchange
        wire bytes, per-owner sub-batch counters (``host`` label), the
        router result cache, and the end-to-end latency histogram. All
        callback-backed (read at exposition time, `reset_stats`-safe).
        Owner-engine metrics ride :meth:`fleet_registry`."""
        reg = registry if registry is not None else MetricsRegistry()
        for f in ("requests", "coalesced", "router_dispatches",
                  "routed_seeds", "late_admitted", "replica_hits",
                  "hedges", "hedged_seeds", "hedge_timeouts",
                  "hedge_errors", "hedge_ejected", "hedge_failed",
                  "owner_ejections", "shed", "request_errors",
                  "undrained", "migration_batches", "migration_rollbacks",
                  "migration_rollforwards", "migrated_seeds",
                  "replica_refreshes", "graph_deltas", "delta_edges",
                  "delta_cache_invalidated", "delta_closure_installs",
                  "replica_delta_invalidations", "edges_deleted"):
            reg.counter_fn(f"{prefix}_{f}_total",
                           (lambda f=f: getattr(self.stats, f)),
                           f"DistServeStats.{f}", labels)
        reg.gauge_fn(f"{prefix}_ownership_epoch",
                     lambda: self.ownership_epoch,
                     "committed ownership range flips", labels)
        reg.gauge_fn(f"{prefix}_graph_version",
                     lambda: self.graph_version,
                     "streaming-graph delta commits applied (the fleet "
                     "epoch routed flushes pin against)",
                     labels)
        reg.histogram(f"{prefix}_commit_stall_us",
                      "per-commit routed-serving stall, µs (fenced: the "
                      "whole drain+apply hold; zero-stall: the _seq "
                      "flip)", labels,
                      fn=lambda: self.stats.commit_stall)
        reg.gauge_fn(f"{prefix}_delta_pending_edges",
                     lambda: (len(self.pending_delta)
                              if self.pending_delta is not None else 0),
                     "edge arrivals staged and not yet committed", labels)
        # round-19 satellite: every owner stream's reserve runway as
        # gauges (host label), same family names as the single-host
        # engine's so one alert rule covers both
        from .engine import register_stream_reserve

        for h in sorted(self._owner_streams):
            register_stream_reserve(
                reg, prefix,
                (lambda h=h: self._owner_streams.get(h)),
                dict(labels or {}, host=str(h)),
            )
        reg.gauge_fn(f"{prefix}_hosts",
                     lambda: self.hosts,
                     "current serving fleet host count", labels)
        reg.gauge_fn(f"{prefix}_replica_refresh_errors",
                     lambda: self.replica_refresh_errors,
                     "failed background replica-refresh passes", labels)
        reg.gauge_fn(f"{prefix}_rebalance_errors",
                     lambda: self.rebalance_errors,
                     "failed background rebalance passes", labels)
        reg.gauge_fn(f"{prefix}_replica_version",
                     lambda: self.replica_version,
                     "hot-set replica refreshes applied", labels)
        reg.gauge_fn(f"{prefix}_replica_rows",
                     lambda: (self.replica.ids.size
                              if self.replica is not None else 0),
                     "seeds currently replicated on every host", labels)
        reg.gauge_fn(f"{prefix}_owners_ejected",
                     lambda: sum(
                         1 for st in self.owner_health().values()
                         if st["ejected_at"] >= 0
                     ),
                     "owners currently in ejection backoff", labels)
        register_tenant_latency(
            reg, prefix, "end-to-end routed latency by submitting tenant",
            lambda: self.stats, self.config.tenant_weights, labels,
        )
        reg.counter_fn(f"{prefix}_exchange_id_bytes_total",
                       lambda: self.stats.exchange_id_bytes,
                       "global collective id payload bytes", labels)
        reg.counter_fn(f"{prefix}_exchange_logit_bytes_total",
                       lambda: self.stats.exchange_logit_bytes,
                       "global collective logits payload bytes", labels)
        reg.gauge_fn(f"{prefix}_pending_depth", lambda: len(self._pending),
                     "unique seeds queued at the router", labels)
        reg.gauge_fn(f"{prefix}_inflight_flushes",
                     lambda: self._inflight_flushes,
                     "routed flushes between assemble and resolve", labels)
        reg.gauge_fn(f"{prefix}_inflight_window",
                     lambda: self.config.max_in_flight,
                     "configured router max_in_flight bound", labels)
        reg.gauge_fn(f"{prefix}_inflight_peak",
                     lambda: self.stats.inflight_peak,
                     "largest routed in-flight occupancy observed", labels)
        reg.gauge_fn(f"{prefix}_cache_rows", lambda: len(self.cache),
                     "router result-cache resident rows", labels)
        reg.gauge_fn(f"{prefix}_params_version", lambda: self.params_version,
                     "current weights version", labels)
        reg.gauge_fn(f"{prefix}_placement_version",
                     lambda: self.placement_version,
                     "fenced tier-placement batches across the fleet",
                     labels)
        reg.gauge_fn(f"{prefix}_tier_adapt_errors",
                     lambda: self.tier_adapt_errors,
                     "failed fleet tier-adaptation passes", labels)
        for h in sorted(self.engines):
            reg.counter_fn(
                f"{prefix}_sub_batches_total",
                (lambda h=h: self.stats.sub_batches.get(h, 0)),
                "owner sub-batches routed",
                dict(labels or {}, host=str(h)),
            )
            reg.counter_fn(
                f"{prefix}_sub_batch_seeds_total",
                (lambda h=h: self.stats.sub_batch_seeds.get(h, 0)),
                "seeds routed to owner",
                dict(labels or {}, host=str(h)),
            )
        register_hit_rate(reg, f"{prefix}_cache",
                          lambda: self.stats.router_cache, labels)
        reg.histogram(f"{prefix}_latency_ms",
                      "end-to-end routed request latency", labels,
                      fn=lambda: self.stats.latency)
        if self.workload is not None:
            self.workload.register_metrics(
                reg, prefix=f"{prefix}_workload", labels=labels,
                owners=range(self.hosts),
            )
        return reg

    def fleet_registry(self, registry: Optional[MetricsRegistry] = None,
                       ) -> MetricsRegistry:
        """ONE registry over the whole fleet: the router's metrics plus
        every owner engine's (`ServeEngine.register_metrics`) under a
        ``host`` label, registered in sorted-host order — the same
        deterministic merge discipline as `aggregate_stats`, so two
        expositions of the same state are textually identical. With no
        ``registry`` argument the engine's CACHED fleet registry is
        returned (adapters are callback-backed readers, so one registry
        serves every scrape; re-registration re-points, never
        duplicates)."""
        if registry is None:
            if getattr(self, "_fleet_reg", None) is None:
                self._fleet_reg = MetricsRegistry()
            registry = self._fleet_reg
        reg = self.register_metrics(registry)
        for h in sorted(self.engines):
            self.engines[h].register_metrics(
                reg, prefix="quiver_serve", labels={"host": str(h)}
            )
        # the replica/fallback engines are serving engines like any owner
        # — same families under reserved host labels. A replica refresh
        # swaps the engine; re-calling fleet_registry re-points the
        # adapters (last-writer-wins, the registry's documented rule).
        if self.replica is not None:
            self.replica.engine.register_metrics(
                reg, prefix="quiver_serve", labels={"host": "replica"}
            )
        if self.fallback is not None:
            self.fallback.register_metrics(
                reg, prefix="quiver_serve", labels={"host": "fallback"}
            )
        return reg

    def aggregate_journal(self) -> List[Tuple]:
        """The fleet's lifecycle events as (host, t, kind, rid, fid, a, b)
        tuples — router events first under host=-1, then each owner's in
        sorted-host order. Within one journal the ring is already in
        emit order, and flush events emit in dispatch-index order (seals
        are serialized under each engine's sequencing lock), so the merge
        is deterministic for a deterministic run — the same contract as
        the dispatch-log/stats merges."""
        merged: List[Tuple] = [(-1, *ev) for ev in self.journal.snapshot()]
        for h in sorted(self.engines):
            merged.extend(
                (h, *ev) for ev in self.engines[h].journal.snapshot()
            )
        return merged

    def fleet_snapshot(self) -> Dict[str, object]:
        """Fleet observability in one JSON-able document: the router's
        request breakdown (end-to-end stages), per-owner breakdowns
        (sorted hosts), and the fleet registry snapshot. This is the
        serve-stack answer to "where did this request's time go" at fleet
        grain — queue/route at the router, device/resolve at the owners."""
        return {
            "router": self.journal.request_breakdown(),
            "per_shard": {
                h: self.engines[h].journal.request_breakdown()
                for h in sorted(self.engines)
            },
            "metrics": self.fleet_registry().snapshot(),
        }

    def workload_report(self, capacities: Sequence[int] = (),
                        ) -> Dict[str, object]:
        """The fleet's skew/imbalance planning document (round 13;
        requires ``DistServeConfig.workload``):

        - ``router`` — the ROUTER monitor's `skew_report`: since the
          router observes every submitted seed, this is the fleet's
          access-frequency truth (head-concentration curve, predicted
          hit rate vs capacity) plus per-owner routed load, imbalance
          and straggler stats;
        - ``per_shard`` — each owner engine's own report (owner-side
          cache outcomes, tier attribution);
        - ``shards_merged`` — `WorkloadMonitor.merge_all` over the owner
          monitors in sorted-host order: the multi-process deployment
          shape, where no single router sees every seed and the fleet
          view IS the merge (order-independent by construction — pinned
          in tests/test_skew.py). NOT router + owners: the router
          already counted every seed the owners saw, and summing the two
          would double-count.
        """
        if self.workload is None:
            raise ValueError(
                "workload telemetry is off — pass "
                "DistServeConfig(workload=WorkloadConfig(...))"
            )
        owner_monitors = [
            self.engines[h].workload
            for h in sorted(self.engines)
            if self.engines[h].workload is not None
        ]
        out: Dict[str, object] = {
            "router": self.workload.skew_report(capacities=capacities),
            "per_shard": {
                str(h): self.engines[h].workload.skew_report(
                    capacities=capacities
                )
                for h in sorted(self.engines)
                if self.engines[h].workload is not None
            },
        }
        if owner_monitors:
            out["shards_merged"] = WorkloadMonitor.merge_all(
                owner_monitors
            ).skew_report(capacities=capacities)
        return out

    def export_chrome_trace(self, path: str, extra_sources: Sequence = (),
                            metadata: Optional[Dict[str, object]] = None,
                            ) -> Dict[str, object]:
        """One Perfetto-loadable timeline for the fleet: router spans +
        journal, every owner engine's spans + journal (sorted hosts), and —
        when `comm.record_exchange_spans` installed a recorder — the wire
        legs, all on the shared monotonic clock."""
        sources: List = [("router.spans", self.stats.spans)]
        if self.journal.enabled:
            sources.append(("router.journal", self.journal))
        if self.workload is not None and self.workload.counters is not None:
            sources.append(("router.workload", self.workload.counters))
        for h in sorted(self.engines):
            eng = self.engines[h]
            sources.append((f"owner{h}.spans", eng.stats.spans))
            if eng.journal.enabled:
                sources.append((f"owner{h}.journal", eng.journal))
            if eng.workload is not None and eng.workload.counters is not None:
                sources.append((f"owner{h}.workload", eng.workload.counters))
        rec = comm_mod.EXCHANGE_SPANS
        if rec is not None and len(rec):
            sources.append(("comm.exchange", rec))
        if self._commit_samples:
            # round-24 counter lane: the fleet graph-version staircase +
            # per-commit stall, rendered as ph:"C" tracks
            from .engine import _CommitCounterSource

            sources.append(
                ("router.commits", _CommitCounterSource(self._commit_samples))
            )
        sources.extend(extra_sources)
        return _export_chrome_trace(path, sources, metadata)

    def start(self) -> "DistServeEngine":
        if self._running:
            return self
        self._running = True
        self._draining = False  # re-arm migrations after a stop()
        self._threads = [
            threading.Thread(
                target=self._poll_loop,
                name=f"quiver-dist-serve-flusher-{i}",
                daemon=True,
            )
            for i in range(self.config.max_in_flight)
        ]
        if self.config.tier_adapt_every_s > 0 and any(
            e._tier_feature is not None and e.workload is not None
            for e in self.engines.values()
        ):
            self._threads.append(
                threading.Thread(
                    target=self._tier_loop,
                    name="quiver-dist-serve-tiers",
                    daemon=True,
                )
            )
        # round-16 background policies: the drift-gated replica refresh
        # (the r15 remaining-leverage note) and the imbalance-gated
        # rebalance — both fenced inside their passes, both surviving
        # failures as error counters (the tier-daemon contract)
        if (self.config.replica_refresh_every_s > 0
                and self.config.replicate_top_k > 0
                and self.workload is not None
                and self._replica_materials is not None):
            self._threads.append(
                threading.Thread(
                    target=lambda: self._policy_loop(
                        self.config.replica_refresh_every_s,
                        self._replica_refresh_pass,
                        "replica_refresh_errors",
                    ),
                    name="quiver-dist-serve-replica-refresh",
                    daemon=True,
                )
            )
        if (self.config.rebalance_every_s > 0
                and self.workload is not None
                and self._replica_materials is not None
                and self.exchange_mode == "host"
                and self.config.feature_residency == "closure"):
            self._threads.append(
                threading.Thread(
                    target=lambda: self._policy_loop(
                        self.config.rebalance_every_s,
                        self.maybe_rebalance,
                        "rebalance_errors",
                    ),
                    name="quiver-dist-serve-rebalance",
                    daemon=True,
                )
            )
        for t in self._threads:
            t.start()
        return self

    def _tier_loop(self) -> None:
        from ..tiers import tier_daemon_loop

        tier_daemon_loop(self)

    def stop(self, drain: bool = True) -> None:
        """Stop the pollers and retire queued work, BOUNDED by
        ``config.drain_deadline_s`` (round 15): a poller or owner that
        died mid-flush must not hang the caller. Work not retired by the
        deadline resolves with `serve.engine.DrainTimeout` and is counted
        in ``stats.undrained`` — in the snapshot, never silently
        dropped.

        An OPEN migration range (round 16) is settled FIRST, outside the
        drain budget: ``_draining`` halts rebalance loops between
        batches, and taking the migration lock waits for the in-flight
        batch to commit or roll back — a range handoff is atomic, so
        after the wait every seed has exactly one owner. Only then does
        the drain deadline start counting. A half-landed range abandoned
        to a deadline would strand its seeds ownerless; completing it
        can exceed the deadline, and that is the correct trade."""
        self._running = False
        self._draining = True
        try:
            # settle the open range before any deadline starts: batches
            # are atomic under this lock, and rebalance loops check
            # _draining between batches
            with self._migration_lock:
                pass
            # one deadline covers poller joins too (a poller wedged
            # mid-flush must not defeat the bound — see ServeEngine.stop)
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
            with self._fence:
                while self._inflight_flushes and self._clock() < deadline:
                    self._fence.wait(timeout=0.05)
            abandon_undrained(self, drained=drain)
            # owner engines run un-started in dist mode (the router
            # drives them synchronously), so their staged prefetch rows
            # must be cancelled here — futures observed, no worker leaks
            for eng in self.engines.values():
                eng._cancel_prefetch()
        finally:
            # _draining stays TRUE after stop: a rebalance loop still
            # holding batches must keep halting even though stop already
            # returned (it only checks the flag between batches, so
            # resetting here would let it resume flipping ownership on
            # an engine the caller believes is quiesced). start() is the
            # explicit path back to a migrating engine.
            pass

    def _poll_loop(self) -> None:
        while self._running:
            try:
                self.pump()
            except Exception:
                # whole-flush infrastructure errors only (round-15
                # contract: owner failures are per-request and never
                # raise out of flush); the failing flush already resolved
                # its waiters with the error — keep serving
                pass
            time.sleep(self.config.flush_poll_ms / 1e3)

    def __enter__(self) -> "DistServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def replay_shard_oracle(
    dist: DistServeEngine,
    model,
    params,
    full_sampler_factory: Callable[[], object],
    full_feature,
) -> Dict[int, np.ndarray]:
    """THE parity oracle: replay every shard engine's dispatch log through
    a FRESH sampler over the FULL graph (`full_sampler_factory` must birth
    it exactly like the shard samplers — same seed — so its key stream
    matches) and the offline `inference.batch_logits` path over the full
    feature table. Returns {node_id: logits row} for the first computation
    of each node per shard.

    That this oracle uses the FULL topology + FULL features is the point:
    it proves a shard served from 1/H of each table produced logits
    bit-identical to single-host offline eval. Shard engines must have
    been built with ``record_dispatches=True`` (`DistServeConfig` default
    shard config inherits the router's flag)."""
    from ..inference import _cached_apply, batch_logits

    apply = _cached_apply(model)
    served: Dict[int, np.ndarray] = {}
    for h in sorted(dist.engines):
        sampler = full_sampler_factory()
        for padded, nvalid in dist.engines[h].dispatch_log:
            logits = np.asarray(
                batch_logits(apply, params, sampler, full_feature, padded)
            )
            for i in range(nvalid):
                served.setdefault(int(padded[i]), logits[i])
    return served


def replay_fleet_oracle(
    dist: DistServeEngine,
    model,
    params,
    full_sampler_factory: Callable[[], object],
    full_feature,
    graph_version: Optional[int] = None,
) -> Dict[int, List[np.ndarray]]:
    """`replay_shard_oracle` extended over the WHOLE fleet: owners + the
    hot-set replica + the full-graph fallback + every engine RETIRED by a
    replica refresh, a range handoff, or a shrink (round 16: the oracle
    understands ownership epochs — an epoch changes which engine computes
    a seed, and each epoch's engine vouches for its own dispatch log).
    Each engine's log replays through a fresh FULL-graph sampler and the
    offline `batch_logits` path, collecting EVERY computation of every
    node (not just the first — a cache invalidation, e.g. a replica
    refresh or a migrated range, can legitimately recompute a node under
    a later key draw).

    Returns {node_id: [candidate rows]}. Under hedged/failover dispatch a
    node may be computed by more than one engine over a run (its owner
    before a fault, the fallback after) — a served row is CORRECT iff it
    bit-matches one candidate, which is exactly the fault-parity
    acceptance the probe and tests/test_faults.py assert: faults and
    failovers change WHO computes, never change any completed bit away
    from an offline full-graph replay.

    Round 24 — epoch-aware replay: with ``graph_version=v`` set,
    ``full_sampler_factory`` must birth a sampler over the graph AS OF
    fleet epoch ``v``; every engine's WHOLE log still replays through it
    (the key stream must advance exactly as the live run's did), but
    only rows whose aligned ``dispatch_graph_versions`` stamp equals
    ``v`` are collected. Under zero-stall commits a run's log spans
    epochs — each completed row is bit-equal to the oracle of the epoch
    it SEALED against, which is exactly what the per-epoch sweep
    (one call per version, candidates unioned) asserts."""
    from ..inference import _cached_apply, batch_logits

    apply = _cached_apply(model)
    engines: Dict[object, ServeEngine] = dict(dist.engines)
    if dist.replica is not None:
        engines["replica"] = dist.replica.engine
    for i, retired in enumerate(dist._retired_replicas):
        engines[f"replica_retired_{i}"] = retired
    # round-16 ownership epochs: owner engines replaced by a range
    # handoff (or removed by a shrink) served real traffic under earlier
    # epochs — their dispatch logs are candidates exactly like a live
    # owner's. Every shard sampler (any epoch) is born with the same
    # seed, so one fresh full-graph sampler per engine replays it.
    for i, retired in enumerate(dist._retired_engines):
        engines[f"owner_retired_{i}"] = retired
    if dist.fallback is not None:
        engines["fallback"] = dist.fallback
    served: Dict[int, List[np.ndarray]] = {}
    for h in sorted(engines, key=str):
        sampler = full_sampler_factory()
        eng = engines[h]
        gvs = getattr(eng, "dispatch_graph_versions", None)
        for ix, (padded, nvalid) in enumerate(eng.dispatch_log):
            # the replay ALWAYS computes (each batch advances the
            # sampler's key stream exactly like the live dispatch did);
            # the epoch filter only gates collection
            logits = np.asarray(
                batch_logits(apply, params, sampler, full_feature, padded)
            )
            if graph_version is not None and (
                    gvs is None or ix >= len(gvs)
                    or gvs[ix] != graph_version):
                continue
            for i in range(nvalid):
                served.setdefault(int(padded[i]), []).append(logits[i])
    return served
