"""Streaming graph deltas: serve on a graph that changes under live
traffic (ROADMAP item 1, round 17).

Every layer built through round 16 — tiled sampling, fused one-dispatch
serving, the disk tier, replication, elastic resharding — assumes a frozen
CSR/tile map built once at ingest. The north-star workload (feeds, fraud
graphs) streams edges continuously, and the access-stream papers
(PyTorch-Direct, arxiv 2101.07956; GPU-side sampling invariants, arxiv
2009.06693) both argue the same discipline: mutation must ride the
existing GATHER-ONLY formulations, never reintroduce host-side rebuilds on
the hot path.

The 128-lane tile layout (`ops.sample.build_tiled_host`) makes that
possible almost for free. A node's edges live LANE-aligned in a
``[M, 128]`` tile table, so ceil-padding to 128 leaves ``cap - deg`` slack
pad lanes in every node's last tile row — lanes the degree mask already
gates out of every draw. An edge append is therefore:

- **pad-lane write** (the common case): put the new neighbor in the next
  slack lane and bump the node's degree — one tile-row write + one
  ``(base, deg)`` row write, no relayout, no shape change;
- **tile spill** (a node's allocated rows filled): relocate the node to
  fresh rows from a pre-reserved region at the table's tail (copy its old
  rows, bump ``base``), then write. The old rows become dead padding the
  degree mask never reads. Reserve exhaustion raises
  `StreamCapacityError` — capacity is planned like the sampler's static
  caps, never silently grown (a shape change would invalidate every
  AOT-sealed serve executable).

Deltas accumulate HOST-SIDE in a :class:`GraphDelta` buffer and land on
device as **batched tile swaps**: the touched tile rows (and bd rows) go
through one jitted bucketed row-scatter per commit
(`shard_tensor._scatter_rows` semantics — the same idiom the round-14 tier
promotions ride). Scatter-building big arrays is the compile trap
PERF.md (earlier claims) pins; a bounded ``[K, 128]`` row scatter into an EXISTING
same-shaped array is not. Every sampler path stays gather-only and
bit-replayable: the device arrays keep their shapes for the life of the
stream, so the sealed `inference.BucketPrograms` executables keep running
— `BucketPrograms.rebind` swaps the argument arrays, never recompiles.

Parity discipline (pinned in tests/test_stream.py): a draw from the
streamed ``(bd, tiles)`` is bit-equal to a draw from a tile table freshly
built over the materialized updated CSR (`to_csr_topo`) on the same key —
appends preserve per-row edge order (base edges first, arrivals after),
and `ops.sample._tiled_resolve` reads positions through the ``base``
indirection, so relocation changes no drawn bit. Frozen-graph replay is
bit-identical to delta-replay with an empty delta, and an appended edge is
visible to the NEXT sample after the commit returns (copy-all semantics:
any draw with fanout >= deg must include it).

`StreamingAdjacency` is the host bookkeeping half: the base CSR plus the
appended edges, with forward k-hop closures (the dist router's incremental
owner-shard extension) and reverse k-hop closures (the versioned-node-
stamp invalidation set — every seed whose k-hop expansion could reach a
changed row). The serve engines wire all of this through
``update_graph(delta)`` — see `serve.engine.ServeEngine.update_graph` and
docs/api.md "Streaming graphs".

Round 21 (graph lifecycle, `quiver_tpu.lifecycle`) makes the stream live
forever — the tile map learns to SHRINK, under three distinct bit
disciplines (docs/api.md "Graph lifecycle" has the contract table):

- **edge deletion / timestamp update** (`GraphDelta.remove_edges` /
  `update_edges`): a deletion rewrites the node's lanes in place (the
  surviving edges shift left, preserving base-first-arrivals-after
  order), so the stream stays bit-equal to a graph FRESHLY BUILT without
  the edge — deletion parity is rebuild parity, the same oracle appends
  ride. Draw bits for touched rows change BY DESIGN (the Gumbel uniform
  stream is positional).
- **TTL retention** (`expire_edges`): expiry must NOT shift lanes — the
  per-lane uniform draw makes any shift a bit change, which would break
  the retention<->masking duality — so an expired edge's timestamp is
  overwritten with ``+inf`` (a masked lane write: invisible at every
  finite query t, exactly like a ``cutoff < ts`` band mask on the
  unexpired twin). Dead lanes are RE-USED by later appends to the same
  node (the adjacency replaces the entry in place, so rebuild parity
  still holds), which is what keeps a sliding-window working set's tile
  footprint flat.
- **compaction** (`plan_compaction`/`apply_compaction`): strictly
  observe-only on bits — it reclaims whole tile ROWS (spill-retired
  ranges, over-allocated tails, defrag relocations through the ``base``
  indirection), never lanes, because `ops.sample._tiled_resolve` reads
  positions through ``base`` and the degree mask: row placement is
  invisible to every draw.

Reserve exhaustion stops being terminal: `provision_reserve` grows the
tile tables by a whole bank (one shape change, one sealed-program
rebuild — never a per-commit recompile; see
`inference.BucketPrograms.reprovision`).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .ops.sample import LANE, build_tiled_host
from .shard_tensor import _bucket, _scatter_rows

# The batched tile-swap primitive: one bounded [K, ...] row scatter into
# an existing same-shaped device table, out-of-range positions dropped as
# padding (`shard_tensor._scatter_rows` — the round-14 promotion idiom,
# NOT the PERF.md (earlier claims) scatter-build trap). Named here because every delta
# consumer (tile sync below, `ClosureFeature.install_rows` in serve/dist)
# must commit through this one shape-stable path.
_swap_rows = _scatter_rows

__all__ = [
    "GraphDelta",
    "StreamCapacityError",
    "StreamingAdjacency",
    "StreamingTiledGraph",
    "validate_edge_ids",
]


class StreamCapacityError(RuntimeError):
    """The stream's reserved tile (or feature) rows are exhausted. The
    fix is capacity planning, not silent growth: growing the device
    arrays would change their shapes and invalidate every sealed AOT
    serve executable — rebuild the stream with a larger
    ``reserve_frac``/``reserve_tiles`` (the same contract as the
    sampler's static caps)."""


def validate_edge_ids(src, dst, n: Optional[int] = None,
                      what: str = "delta",
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten an edge batch to matched int64 ``(src, dst)`` arrays and
    (when ``n`` is given) range-check every id against ``[0, n)`` — the
    one validation every staging/commit entry point shares, so a bad
    arrival raises AT ITS CALL SITE and never poisons a pending buffer
    (a commit failure re-stages the delta; an unvalidated bad edge would
    wedge every future ``update_graph``)."""
    src = np.asarray(src, np.int64).reshape(-1)
    dst = np.asarray(dst, np.int64).reshape(-1)
    if src.shape != dst.shape:
        raise ValueError(f"src {src.shape} / dst {dst.shape} mismatch")
    if n is not None:
        bad = (src < 0) | (src >= n) | (dst < 0) | (dst >= n)
        if bad.any():
            raise ValueError(
                f"{what} edge ids outside [0, {n}): "
                f"{np.stack([src[bad], dst[bad]], 1)[:4].tolist()}"
            )
    return src, dst


class GraphDelta:
    """Host-side edge-arrival buffer: ``(src, dst)`` pairs in arrival
    order, held as ndarray CHUNKS (one per staged batch — the ingest
    path is measured by bench's ``stream_append_s``, so no per-edge
    Python boxing). Accumulation is cheap and lock-free per instance
    (the serve engines guard their pending buffer with their own lock);
    nothing touches the device until a fenced ``update_graph``/``apply``
    commits the whole batch. Deterministic: two buffers fed the same
    arrivals apply identically."""

    __slots__ = ("_src", "_dst", "_ts", "_n",
                 "_rsrc", "_rdst", "_usrc", "_udst", "_uts")

    def __init__(self, src=None, dst=None, ts=None):
        self._src: List[np.ndarray] = []
        self._dst: List[np.ndarray] = []
        # per-edge timestamp chunks (round 19, temporal workloads): either
        # EVERY staged chunk carries timestamps or none does — a mixed
        # buffer could not commit into a temporal tile map deterministically
        self._ts: List[np.ndarray] = []
        self._n = 0
        # round-21 lifecycle: staged removals and timestamp updates, in
        # their own arrival order. One commit applies installs, then
        # appends, then removals, then updates — the fixed order every
        # preflight simulates, so "remove an edge this same batch
        # appended" validates exactly once, the same everywhere.
        self._rsrc: List[np.ndarray] = []
        self._rdst: List[np.ndarray] = []
        self._usrc: List[np.ndarray] = []
        self._udst: List[np.ndarray] = []
        self._uts: List[np.ndarray] = []
        if src is not None or dst is not None:
            if (src is None) != (dst is None):
                raise ValueError("src/dst lengths differ")
            self.add_edges(src, dst, ts=ts)

    def add_edge(self, src: int, dst: int, ts: Optional[float] = None) -> None:
        self.add_edges(
            np.asarray([src], np.int64), np.asarray([dst], np.int64),
            ts=None if ts is None else np.asarray([ts], np.float32),
        )

    def add_edges(self, src, dst, ts=None) -> None:
        src, dst = validate_edge_ids(src, dst)
        if src.size:
            if ts is not None:
                ts = np.asarray(ts, np.float32).reshape(-1)
                if ts.shape != src.shape:
                    raise ValueError(
                        f"ts {ts.shape} does not match edges {src.shape}"
                    )
            if self._n and (bool(self._ts) != (ts is not None)):
                raise ValueError(
                    "mixed timestamped and untimestamped edges in one "
                    "GraphDelta — a temporal stream needs a ts per edge"
                )
            # copies: the caller may reuse its arrival buffers after
            # staging, and staged chunks are never mutated in place (so
            # `extend` may share them across buffers)
            self._src.append(src.copy())
            self._dst.append(dst.copy())
            if ts is not None:
                self._ts.append(ts.copy())
            self._n += int(src.size)

    def remove_edge(self, src: int, dst: int) -> None:
        self.remove_edges(np.asarray([src], np.int64),
                          np.asarray([dst], np.int64))

    def remove_edges(self, src, dst) -> None:
        """Stage edge DELETIONS: each ``(src, dst)`` pair removes one
        occurrence of that edge (first in lane order) at commit time.
        All-or-none: the commit preflight validates every removal
        against the post-append adjacency and a single miss fails the
        whole batch before any state moves. A deletion rewrites the
        source row's lanes (survivors shift left), so the stream stays
        bit-equal to a graph freshly built WITHOUT the edge — touched
        rows' draws change by design and are invalidated like appends."""
        src, dst = validate_edge_ids(src, dst)
        if src.size:
            self._rsrc.append(src.copy())
            self._rdst.append(dst.copy())

    def update_edge(self, src: int, dst: int, ts: float) -> None:
        self.update_edges(np.asarray([src], np.int64),
                          np.asarray([dst], np.int64),
                          np.asarray([ts], np.float32))

    def update_edges(self, src, dst, ts) -> None:
        """Stage per-edge TIMESTAMP updates (temporal streams only —
        the timestamp is the one mutable per-edge payload a streamed
        tile map carries; plain streams have no weight tiles to write).
        Each pair retargets the first lane-order occurrence of
        ``(src, dst)``; timestamps must be finite (``+inf`` is the
        retention layer's expiry sentinel — see ``expire_edges``)."""
        src, dst = validate_edge_ids(src, dst)
        if ts is None:
            raise ValueError(
                "update_edges needs a timestamp per edge — the ts lane "
                "is the only mutable per-edge payload"
            )
        ts = np.asarray(ts, np.float32).reshape(-1)
        if ts.shape != src.shape:
            raise ValueError(f"ts {ts.shape} != edges {src.shape}")
        if ts.size and not np.isfinite(ts).all():
            raise ValueError(
                "non-finite edge timestamps staged — +inf is reserved "
                "as the retention expiry sentinel"
            )
        if src.size:
            self._usrc.append(src.copy())
            self._udst.append(dst.copy())
            self._uts.append(ts.copy())

    def extend(self, other: "GraphDelta") -> None:
        if self._n and other._n and bool(self._ts) != bool(other._ts):
            raise ValueError(
                "cannot merge timestamped and untimestamped GraphDeltas"
            )
        self._src.extend(other._src)
        self._dst.extend(other._dst)
        self._ts.extend(other._ts)
        self._n += other._n
        self._rsrc.extend(other._rsrc)
        self._rdst.extend(other._rdst)
        self._usrc.extend(other._usrc)
        self._udst.extend(other._udst)
        self._uts.extend(other._uts)

    @property
    def n_appends(self) -> int:
        return self._n

    def __len__(self) -> int:
        # total staged OPERATIONS: appends + removals + updates (the
        # engines use this for "is there anything to commit" and for
        # their delta_edges op counters)
        return self._n + sum(c.size for c in self._rsrc) + sum(
            c.size for c in self._usrc
        )

    def edges(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(src, dst)`` int64 arrays in arrival order."""
        if not self._src:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        return np.concatenate(self._src), np.concatenate(self._dst)

    def edges_ts(self) -> Optional[np.ndarray]:
        """Per-edge float32 timestamps in arrival order, or None when
        this buffer was staged without them (the pre-round-19 shape)."""
        if not self._ts:
            return None
        return np.concatenate(self._ts)

    def removals(self) -> Tuple[np.ndarray, np.ndarray]:
        """Staged removal pairs ``(src, dst)`` in arrival order."""
        if not self._rsrc:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        return np.concatenate(self._rsrc), np.concatenate(self._rdst)

    def updates(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Staged timestamp updates ``(src, dst, ts)`` in arrival
        order."""
        if not self._usrc:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty(0, np.float32))
        return (np.concatenate(self._usrc), np.concatenate(self._udst),
                np.concatenate(self._uts))

    def max_ts(self):
        """Largest staged timestamp (appends and updates), or None when
        nothing timestamped is staged — the commit clock the retention
        layer advances on (`lifecycle.RetentionPolicy`)."""
        parts = [c for c in self._ts if c.size] + [
            c for c in self._uts if c.size
        ]
        if not parts:
            return None
        return float(max(float(c.max()) for c in parts))

    def sources(self) -> np.ndarray:
        """Sorted unique source ids — the rows whose lanes (and hence
        whose downstream draws) this delta changes: append, removal, and
        update sources alike. Destinations are new LEAVES: they change
        no other row's draw, so invalidation closures seed from sources
        only."""
        parts = self._src + self._rsrc + self._usrc
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))

    def clear(self) -> None:
        self._src.clear()
        self._dst.clear()
        self._ts.clear()
        self._n = 0
        self._rsrc.clear()
        self._rdst.clear()
        self._usrc.clear()
        self._udst.clear()
        self._uts.clear()


class StreamingAdjacency:
    """Host bookkeeping for a streaming graph: an immutable base CSR plus
    per-node appended-edge lists, answering the three questions the delta
    layer asks — current neighbors (in tile-lane order: base first,
    arrivals after), forward k-hop closures over the UPDATED graph (the
    dist router's incremental owner-mask extension), and reverse k-hop
    closures (the invalidation set: every node whose ``hops``-hop
    expansion could reach a changed row). Reverse adjacency of the base
    CSR is built once (O(E) counting sort); appended edges ride small
    per-node dicts, so a bounded delta batch costs O(batch), never
    O(E)."""

    def __init__(self, csr_topo, edge_ts=None):
        self.indptr = np.asarray(csr_topo.indptr, np.int64)
        self.indices = np.asarray(csr_topo.indices, np.int64)
        self.n = self.indptr.shape[0] - 1
        # round-19 temporal workloads: optional per-edge timestamps
        # aligned with the base CSR, plus per-node appended-ts lists kept
        # in lockstep with _extra (same lane order — draw parity and the
        # temporal replay oracle both ride it)
        self.edge_ts = (
            None if edge_ts is None
            else np.asarray(edge_ts, np.float32).reshape(-1)
        )
        if self.edge_ts is not None and (
            self.edge_ts.shape[0] != self.indices.shape[0]
        ):
            raise ValueError(
                f"edge_ts has {self.edge_ts.shape[0]} entries for "
                f"{self.indices.shape[0]} edges"
            )
        self._extra: Dict[int, List[int]] = {}
        self._extra_ts: Dict[int, List[float]] = {}
        self._rev_extra: Dict[int, List[int]] = {}
        self._n_extra = 0
        # round-21 lifecycle: once a row is deleted-from / expired /
        # ts-updated, its FULL lane list moves into an override (base
        # slice copied out + extras folded in — `_materialize`), and the
        # base CSR stops describing it. Keys here are disjoint from
        # `_extra` by construction. The REVERSE adjacency is never
        # shrunk by removals: reverse closures become supersets, which
        # only ever over-invalidates (safe; pinned in tests).
        self._override: Dict[int, List[int]] = {}
        self._override_ts: Dict[int, List[float]] = {}
        # reverse base CSR (counting sort, same construction as CSRTopo)
        order = np.argsort(self.indices, kind="stable")
        counts = np.bincount(self.indices, minlength=self.n)
        self.rev_indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(counts, out=self.rev_indptr[1:])
        src_per_edge = np.repeat(
            np.arange(self.n, dtype=np.int64),
            self.indptr[1:] - self.indptr[:-1],
        )
        self.rev_indices = src_per_edge[order]

    @property
    def extra_edges(self) -> int:
        # net appended-beyond-base count; clamped because a
        # deletion-heavy lifecycle can remove more base edges than were
        # ever appended
        return max(self._n_extra, 0)

    def add_edges(self, src, dst, ts=None) -> None:
        src, dst = validate_edge_ids(src, dst, self.n)
        if self.edge_ts is not None:
            if ts is None:
                raise ValueError(
                    "temporal adjacency (edge_ts set) needs a timestamp "
                    "per appended edge"
                )
            ts = np.asarray(ts, np.float32).reshape(-1)
            if ts.shape != src.shape:
                raise ValueError(f"ts {ts.shape} != edges {src.shape}")
        for i, (u, v) in enumerate(zip(src, dst)):
            self._append_one(
                int(u), int(v),
                ts=None if self.edge_ts is None else float(ts[i]),
            )

    def _append_one(self, u: int, v: int,
                    ts: Optional[float] = None) -> None:
        """Append one edge to ``u``'s lane tail — into the override list
        when the row is materialized, the extra list otherwise."""
        if u in self._override:
            self._override[u].append(v)
            if self.edge_ts is not None:
                self._override_ts[u].append(float(ts))
        else:
            self._extra.setdefault(u, []).append(v)
            if self.edge_ts is not None:
                self._extra_ts.setdefault(u, []).append(float(ts))
        self._rev_extra.setdefault(v, []).append(u)
        self._n_extra += 1

    def pop_edges(self, src, dst) -> None:
        """Reverse a JUST-APPLIED `add_edges(src, dst)` — the caller's
        rollback when a downstream capacity preflight fails after the
        adjacency already advanced (dist `update_graph` computes its
        closure plans over the updated view, then commits or rolls
        back). Only valid as the exact inverse of the last add: entries
        pop from the tails the add appended to."""
        src = np.asarray(src, np.int64).reshape(-1)
        dst = np.asarray(dst, np.int64).reshape(-1)
        for u, v in zip(src[::-1], dst[::-1]):
            u, v = int(u), int(v)
            if u in self._override:
                self._override[u].pop()
                if self.edge_ts is not None:
                    self._override_ts[u].pop()
            else:
                self._extra[u].pop()
                if self.edge_ts is not None:
                    self._extra_ts[u].pop()
            self._rev_extra[v].pop()
        self._n_extra -= src.shape[0]

    # ------------------------------------------------ lifecycle (r21)
    def _materialize(self, u: int) -> List[int]:
        """Fold ``u``'s base CSR slice and extras into a mutable
        override list (idempotent). Lane order is preserved exactly, so
        a materialized-but-untouched row answers every query the same as
        before — materialization itself changes no bit."""
        ov = self._override.get(u)
        if ov is not None:
            return ov
        base = self.indices[self.indptr[u]:self.indptr[u + 1]]
        ov = [int(x) for x in base] + self._extra.pop(u, [])
        self._override[u] = ov
        if self.edge_ts is not None:
            bts = self.edge_ts[self.indptr[u]:self.indptr[u + 1]]
            self._override_ts[u] = (
                [float(x) for x in bts] + self._extra_ts.pop(u, [])
            )
        return ov

    def remove_one(self, u: int, v: int) -> int:
        """Delete the first lane-order occurrence of ``(u, v)``; returns
        the lane position it held. Survivors shift left — the caller
        rewrites the row's tiles from the updated list. Raises KeyError
        semantics as ValueError when the edge is absent (commit-level
        all-or-none is the stream preflight's job)."""
        ov = self._materialize(u)
        try:
            p = ov.index(v)
        except ValueError:
            raise ValueError(f"edge ({u}, {v}) not present") from None
        del ov[p]
        if self.edge_ts is not None:
            del self._override_ts[u][p]
        self._n_extra -= 1
        return p

    def update_one(self, u: int, v: int, ts: float) -> int:
        """Retarget the first lane-order occurrence of ``(u, v)`` to a
        new timestamp; returns its lane position (the tile lane the
        caller rewrites). Temporal adjacencies only."""
        if self.edge_ts is None:
            raise ValueError("adjacency was built without edge_ts")
        ov = self._materialize(u)
        try:
            p = ov.index(v)
        except ValueError:
            raise ValueError(f"edge ({u}, {v}) not present") from None
        self._override_ts[u][p] = float(ts)
        return p

    def replace_at(self, u: int, p: int, v: int,
                   ts: Optional[float] = None) -> None:
        """Overwrite lane position ``p`` of ``u`` with a NEW edge —
        dead-lane reuse: the expired entry it replaces was already
        invisible to every draw, and replacing in place (instead of
        appending) is what keeps the adjacency in lane-lockstep with the
        tiles, so rebuild parity survives. The expired neighbor's
        reverse entry stays (reverse closures are supersets)."""
        ov = self._materialize(u)
        ov[p] = v
        if self.edge_ts is not None:
            self._override_ts[u][p] = float(ts)
        self._rev_extra.setdefault(v, []).append(u)

    def expire_node(self, u: int, cutoff: float) -> List[int]:
        """Mask every edge of ``u`` with ``ts <= cutoff`` by overwriting
        its timestamp with ``+inf`` (already-expired lanes hold +inf and
        never re-match). Returns the masked lane positions, ascending.
        NO lane shifts: expiry must stay the bit-dual of a
        ``cutoff < ts`` band mask, and the Gumbel uniform stream is
        positional."""
        if self.edge_ts is None:
            raise ValueError("adjacency was built without edge_ts")
        self._materialize(u)
        tsl = self._override_ts[u]
        pos = [p for p, t in enumerate(tsl) if t <= cutoff]
        for p in pos:
            tsl[p] = float("inf")
        return pos

    def neighbors(self, node: int) -> np.ndarray:
        """Current adjacency of ``node`` in TILE-LANE order: the base CSR
        row first, appended arrivals after (the order `to_csr_topo`
        materializes and the tile writes preserve — draw parity rides
        it). Materialized (lifecycle-touched) rows answer from their
        override list — same order contract."""
        node = int(node)
        ov = self._override.get(node)
        if ov is not None:
            return np.asarray(ov, np.int64)
        base = self.indices[self.indptr[node]:self.indptr[node + 1]]
        extra = self._extra.get(node)
        if not extra:
            return base.copy()
        return np.concatenate([base, np.asarray(extra, np.int64)])

    def neighbors_ts(self, node: int) -> np.ndarray:
        """Per-edge timestamps of `neighbors(node)`, same lane order
        (base CSR ts first, appended arrival ts after; expired lanes
        read ``+inf``). Temporal adjacencies only."""
        if self.edge_ts is None:
            raise ValueError("adjacency was built without edge_ts")
        node = int(node)
        ov = self._override_ts.get(node)
        if ov is not None:
            return np.asarray(ov, np.float32)
        base = self.edge_ts[self.indptr[node]:self.indptr[node + 1]]
        extra = self._extra_ts.get(node)
        if not extra:
            return base.copy()
        return np.concatenate([base, np.asarray(extra, np.float32)])

    def degree(self, node: int) -> int:
        node = int(node)
        ov = self._override.get(node)
        if ov is not None:
            return len(ov)
        return int(self.indptr[node + 1] - self.indptr[node]) + len(
            self._extra.get(node, ())
        )

    def forward_closure(self, seeds, hops: int) -> np.ndarray:
        """Bool [N] mask of nodes reachable from ``seeds`` within
        ``hops`` hops over the UPDATED graph (seeds included) — the
        incremental owner-shard extension input: k-hop closures are
        union-homomorphic, so a dist owner's new mask is old-mask OR
        this."""
        mask = np.zeros(self.n, bool)
        seeds = np.asarray(seeds, np.int64).reshape(-1)
        if seeds.size == 0:
            return mask
        mask[seeds] = True
        frontier = np.unique(seeds)
        for _ in range(max(int(hops), 0)):
            if frontier.size == 0:
                break
            nxt = self._expand(frontier, self.indptr, self.indices,
                               self._extra, self._override)
            nxt = nxt[~mask[nxt]]
            if nxt.size == 0:
                break
            mask[nxt] = True
            frontier = nxt
        return mask

    def reverse_closure(self, srcs, hops: int) -> np.ndarray:
        """Sorted ids of every node within ``hops`` REVERSE hops of
        ``srcs`` over the updated graph (srcs included) — the
        invalidation set: a seed's k-hop sample can only change if its
        expansion reaches a changed row, i.e. the seed lies in the
        changed rows' ``hops``-reverse closure."""
        srcs = np.unique(np.asarray(srcs, np.int64).reshape(-1))
        if srcs.size == 0:
            return srcs
        mask = np.zeros(self.n, bool)
        mask[srcs] = True
        frontier = srcs
        for _ in range(max(int(hops), 0)):
            if frontier.size == 0:
                break
            nxt = self._expand(frontier, self.rev_indptr, self.rev_indices,
                               self._rev_extra)
            nxt = nxt[~mask[nxt]]
            if nxt.size == 0:
                break
            mask[nxt] = True
            frontier = nxt
        return np.nonzero(mask)[0]

    @staticmethod
    def _expand(frontier, indptr, indices, extra, override=None):
        """One BFS hop: base-CSR rows vectorized, appended edges via the
        per-node dicts (bounded by the delta volume, never O(E)).
        Materialized rows (``override``, forward direction only) answer
        from their override lists instead of base+extra — the reverse
        direction has no overrides and stays a superset after
        removals."""
        if override:
            keep = np.fromiter(
                (int(u) not in override for u in frontier), bool,
                frontier.shape[0],
            )
            ov_nodes = frontier[~keep]
            frontier = frontier[keep]
        else:
            ov_nodes = None
        parts = []
        starts = indptr[frontier]
        ends = indptr[frontier + 1]
        widths = ends - starts
        if frontier.size and widths.sum() > 0:
            flat = np.concatenate([
                indices[s:e] for s, e in zip(starts, ends) if e > s
            ])
            parts.append(flat)
        if extra:
            ext = [extra[int(u)] for u in frontier if int(u) in extra]
            if ext:
                parts.append(np.concatenate(
                    [np.asarray(x, np.int64) for x in ext]
                ))
        if ov_nodes is not None and ov_nodes.size:
            ov = [override[int(u)] for u in ov_nodes if override[int(u)]]
            if ov:
                parts.append(np.concatenate(
                    [np.asarray(x, np.int64) for x in ov]
                ))
        if not parts:
            return np.array([], np.int64)
        return np.unique(np.concatenate(parts))

    def to_csr_topo(self):
        """Materialize the UPDATED graph as a fresh `CSRTopo` (base edges
        first per row, arrivals after — exactly the tile-lane order, so a
        sampler freshly built over the result draws bit-identically to
        the streamed tiles). This is the replay-oracle / rebuild surface,
        NOT the serving path — serving mutates tiles in place."""
        from .utils import CSRTopo

        if not self._extra and not self._override:
            return CSRTopo(indptr=self.indptr.copy(),
                           indices=self.indices.copy())
        base_deg = self.indptr[1:] - self.indptr[:-1]
        new_deg = base_deg.copy()
        for u, vs in self._extra.items():
            new_deg[u] += len(vs)
        for u, vs in self._override.items():
            new_deg[u] = len(vs)
        new_indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(new_deg, out=new_indptr[1:])
        new_indices = np.empty(int(new_indptr[-1]), np.int64)
        # base block copy: each non-overridden row's base edges land at
        # its new offset; materialized rows are written wholesale below
        src_per_edge = np.repeat(np.arange(self.n, dtype=np.int64), base_deg)
        pos_in_row = np.arange(self.indices.shape[0], dtype=np.int64) - (
            np.repeat(self.indptr[:-1], base_deg)
        )
        if self._override:
            keep = np.ones(self.n, bool)
            keep[np.fromiter(self._override.keys(), np.int64,
                             len(self._override))] = False
            sel = keep[src_per_edge]
            new_indices[new_indptr[src_per_edge[sel]] + pos_in_row[sel]] = (
                self.indices[sel]
            )
        else:
            new_indices[new_indptr[src_per_edge] + pos_in_row] = self.indices
        for u, vs in self._extra.items():
            lo = int(new_indptr[u] + base_deg[u])
            new_indices[lo:lo + len(vs)] = vs
        for u, vs in self._override.items():
            lo = int(new_indptr[u])
            new_indices[lo:lo + len(vs)] = vs
        return CSRTopo(indptr=new_indptr, indices=new_indices)

    def to_temporal(self):
        """Materialize the UPDATED graph as ``(CSRTopo, edge_ts)`` with
        the timestamps in exactly `to_csr_topo`'s edge order (base edges
        first per row, arrivals after — the tile-lane order) — the
        temporal replay-oracle / rebuild surface. Temporal adjacencies
        only."""
        if self.edge_ts is None:
            raise ValueError("adjacency was built without edge_ts")
        topo = self.to_csr_topo()
        if not self._extra and not self._override:
            return topo, self.edge_ts.copy()
        new_indptr = np.asarray(topo.indptr, np.int64)
        base_deg = self.indptr[1:] - self.indptr[:-1]
        new_ts = np.zeros(int(new_indptr[-1]), np.float32)
        src_per_edge = np.repeat(np.arange(self.n, dtype=np.int64), base_deg)
        pos_in_row = np.arange(self.indices.shape[0], dtype=np.int64) - (
            np.repeat(self.indptr[:-1], base_deg)
        )
        if self._override:
            keep = np.ones(self.n, bool)
            keep[np.fromiter(self._override.keys(), np.int64,
                             len(self._override))] = False
            sel = keep[src_per_edge]
            new_ts[new_indptr[src_per_edge[sel]] + pos_in_row[sel]] = (
                self.edge_ts[sel]
            )
        else:
            new_ts[new_indptr[src_per_edge] + pos_in_row] = self.edge_ts
        for u, vs in self._extra.items():
            lo = int(new_indptr[u] + base_deg[u])
            new_ts[lo:lo + len(vs)] = np.asarray(
                self._extra_ts.get(u, []), np.float32
            )
        # materialized rows carry their ts wholesale (expired lanes as
        # +inf — a rebuild over this surface reproduces the masked lanes
        # bit for bit, which is what deletion/retention parity pins)
        for u, tsl in self._override_ts.items():
            lo = int(new_indptr[u])
            new_ts[lo:lo + len(tsl)] = np.asarray(tsl, np.float32)
        return topo, new_ts


def _bucketed(idx: np.ndarray, rows: np.ndarray, sentinel: int,
              floor: int = 64) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a row-swap batch to a power-of-two bucket so the jitted
    `shard_tensor._scatter_rows` commit (one bounded [K, ...] row
    scatter into an existing same-shaped device table — the round-14
    promotion idiom, NOT the PERF.md (earlier claims) scatter-build trap) compiles
    once per bucket, not once per delta size."""
    b = _bucket(idx.shape[0], floor=floor)
    pos = np.full(b, sentinel, np.int32)
    pos[: idx.shape[0]] = idx
    padded = np.zeros((b,) + rows.shape[1:], rows.dtype)
    padded[: idx.shape[0]] = rows
    return pos, padded


class StreamingTiledGraph:
    """The delta layer over the 128-lane tile layout: host ``(bd, tiles)``
    mirrors with reserved slack rows, in-place pad-lane appends + staged
    tile spills, and batched device tile swaps (module docstring has the
    design; docs/api.md "Streaming graphs" the contract).

    Parameters
    ----------
    csr_topo : CSRTopo — the ingest-time graph. Kept immutable; appended
        edges live in the stream's own state.
    reserve_tiles : explicit spare tile-row count for spills (default:
        ``ceil(reserve_frac * M)``, min 8). A spill relocates a node to
        ``old_rows + grow_tiles`` fresh rows from this reserve;
        exhaustion raises `StreamCapacityError` (plan capacity like
        sampler caps — shapes are frozen at construction).
    grow_tiles : extra tile rows granted per spill (>=1; each buys 128
        more slack lanes before the node spills again).
    device_arrays : build and maintain the device ``(bd, tiles)`` pair
        (the serving path). False = host bookkeeping only (the dist
        router's full-graph view costs no device HBM).
    id_dtype : tile dtype; defaults to the same `_best_id_dtype` rule as
        `CSRTopo.to_device_tiled`, so a streamed sampler and a frozen one
        run byte-identical programs.

    Thread safety: `apply`/`install_rows` mutate under one lock, but the
    serve engines additionally FENCE every commit (update_params-style
    drain) so no in-flight flush ever reads a half-applied batch — the
    lock only orders bare concurrent callers.
    """

    def __init__(self, csr_topo, reserve_tiles: Optional[int] = None,
                 reserve_frac: float = 0.5, grow_tiles: int = 1,
                 device_arrays: bool = True, id_dtype=None, edge_ts=None):
        from .utils import _best_id_dtype

        self.csr_topo = csr_topo
        self.adj = StreamingAdjacency(csr_topo, edge_ts=edge_ts)
        self.n = self.adj.n
        if id_dtype is None:
            id_dtype = _best_id_dtype(self.n + 1)
        bd, tiles = build_tiled_host(
            self.adj.indptr, self.adj.indices, id_dtype
        )
        m = tiles.shape[0]
        if reserve_tiles is None:
            reserve_tiles = max(8, int(np.ceil(float(reserve_frac) * m)))
        self.m_base = m
        self.m_cap = m + int(reserve_tiles)
        self.grow_tiles = max(int(grow_tiles), 1)
        self.bd = np.ascontiguousarray(bd)  # [N, 2] int32 (base, deg)
        self.tiles = np.zeros((self.m_cap, LANE), tiles.dtype)
        self.tiles[:m] = tiles
        # round-19 temporal payload: per-edge timestamps in a SECOND tile
        # table sharing the tile map byte for byte (the round-5 weights
        # trick) — appends/spills/installs mutate both under one lock and
        # one batched device swap per commit, so a committed edge and its
        # timestamp become drawable in the same `temporal_graph()` read
        self.ttiles: Optional[np.ndarray] = None
        if edge_ts is not None:
            _, tt = build_tiled_host(
                self.adj.indptr, self.adj.edge_ts, np.float32
            )
            self.ttiles = np.zeros((self.m_cap, LANE), np.float32)
            self.ttiles[:m] = tt
        deg = self.bd[:, 1].astype(np.int64)
        self.alloc_rows = (-(-deg // LANE)).astype(np.int32)  # rows held
        # free tile rows as a sorted, coalescing range list — first-fit
        # from the LOWEST start (deterministic). Starts as the whole
        # reserve; compaction releases reclaimed rows back here, and
        # `provision_reserve` appends whole new banks.
        self._free_ranges: List[List[int]] = (
            [[m, self.m_cap - m]] if self.m_cap > m else []
        )
        # rows vacated by spill relocations park here (NOT freed at
        # relocate time — r17 semantics: the reserve report counts them
        # as consumed) until a compaction releases them
        self._retired: List[Tuple[int, int]] = []
        self._retired_rows = 0
        # expired (masked, ts=+inf) lane positions per node, ascending —
        # appends re-use the lowest dead lane before growing the degree
        self._dead: Dict[int, List[int]] = {}
        self._dead_lanes = 0
        # per-node min finite edge ts (+inf when none): makes
        # `expire_edges(cutoff)` an O(expiring) scan, not O(N * deg)
        self._min_ts: Optional[np.ndarray] = None
        if edge_ts is not None:
            self._min_ts = np.full(self.n, np.inf, np.float32)
            base_deg = (self.adj.indptr[1:] - self.adj.indptr[:-1])
            np.minimum.at(
                self._min_ts,
                np.repeat(np.arange(self.n, dtype=np.int64), base_deg),
                self.adj.edge_ts,
            )
        self.version = 0
        # versioned node stamps: the graph version at which a node's row
        # last changed — the invalidation consumers (cache / replicas /
        # tier placement) compare against these instead of guessing
        self.node_version = np.zeros(self.n, np.int64)
        self.stats = {"pad_writes": 0, "tile_spills": 0, "installs": 0,
                      "tile_rows_swapped": 0, "bd_rows_swapped": 0,
                      "edges": 0,
                      # round-21 lifecycle counters
                      "edges_deleted": 0, "edges_expired": 0,
                      "ts_updates": 0, "lanes_reused": 0,
                      "tiles_reclaimed": 0, "compactions": 0,
                      "provisions": 0}
        self._lock = threading.Lock()
        self._bd_dev = None
        self._tiles_dev = None
        self._tt_dev = None
        # zero-stall (round 24) double buffer: commits run with
        # defer_publish=True build the post-commit device arrays HERE
        # (basing on staged-if-present, so apply + expire in one commit
        # accumulate), leaving the live ``_*_dev`` refs — what `graph()`
        # serves and in-flight flushes hold — untouched until `publish()`
        # flips them in O(1)
        self._staged_bd = None
        self._staged_tiles = None
        self._staged_tt = None
        if device_arrays:
            import jax.numpy as jnp

            self._bd_dev = jnp.asarray(self.bd)
            self._tiles_dev = jnp.asarray(self.tiles)
            if self.ttiles is not None:
                self._tt_dev = jnp.asarray(self.ttiles)

    # -------------------------------------------------- row allocator
    @staticmethod
    def _take(ranges: List[List[int]], k: int) -> Optional[int]:
        """First-fit ``k`` contiguous rows from the LOWEST-start free
        range (deterministic); None when no single range fits. The
        preflight simulates allocation on a copy with this same
        function, so "enough total rows but too fragmented" fails there,
        not mid-commit."""
        for r in ranges:
            if r[1] >= k:
                start = r[0]
                r[0] += k
                r[1] -= k
                if r[1] == 0:
                    ranges.remove(r)
                return start
        return None

    @staticmethod
    def _put(ranges: List[List[int]], start: int, k: int) -> None:
        """Return ``k`` rows at ``start`` to a free list, keeping it
        sorted and coalescing with adjacent ranges."""
        if k <= 0:
            return
        i = 0
        while i < len(ranges) and ranges[i][0] < start:
            i += 1
        ranges.insert(i, [start, k])
        if i + 1 < len(ranges) and (
            ranges[i][0] + ranges[i][1] == ranges[i + 1][0]
        ):
            ranges[i][1] += ranges[i + 1][1]
            del ranges[i + 1]
        if i > 0 and ranges[i - 1][0] + ranges[i - 1][1] == ranges[i][0]:
            ranges[i - 1][1] += ranges[i][1]
            del ranges[i]

    def _release_locked(self, start: int, k: int) -> None:
        """Free ``k`` rows at ``start`` AND zero their host mirror, so a
        later reallocation's device sync ships bytes identical to a
        fresh reserve row (released device rows keep stale bytes until
        then — unreachable: the degree mask gates every read)."""
        if k <= 0:
            return
        self.tiles[start:start + k] = 0
        if self.ttiles is not None:
            self.ttiles[start:start + k] = 0
        self._put(self._free_ranges, start, k)

    # ------------------------------------------------------------ reads
    @property
    def free_rows(self) -> int:
        return sum(r[1] for r in self._free_ranges)

    @property
    def _free_row(self) -> int:
        # compatibility view of the pre-r21 bump pointer: rows consumed
        # so far, measured from the table base (== the old next-free-row
        # watermark whenever nothing has been reclaimed)
        return self.m_cap - self.free_rows

    def _reserve_report_locked(self) -> Dict[str, object]:
        free = self.free_rows
        used = max((self.m_cap - self.m_base) - free, 0)
        commits = self.version
        per_commit = used / commits if commits else 0.0
        deg = self.bd[:, 1].astype(np.int64)
        tight = -(-deg // LANE)
        alloc = self.alloc_rows.astype(np.int64)
        deg_sum = int(deg.sum())
        trimmable = int(np.maximum(alloc - tight, 0).sum())
        return {
            "tiles_base": self.m_base,
            "tiles_cap": self.m_cap,
            "reserve_tiles": self.m_cap - self.m_base,
            "reserve_used": used,
            "reserve_free": free,
            "commits": commits,
            "rows_per_commit": per_commit,
            # None = no consumption observed yet (or none at all): there
            # is nothing honest to project from
            "projected_commits_to_exhaustion": (
                free / per_commit if per_commit > 0 else None
            ),
            "tile_spills": self.stats["tile_spills"],
            "installs": self.stats["installs"],
            # round-21 lifecycle fields (exported as gauges by
            # `serve.engine.register_stream_reserve`):
            # slack lanes inside held rows — over-allocation from spill
            # growth and deletions, the compaction trim target
            "fragmented_lanes": int(alloc.sum()) * LANE - deg_sum,
            # rows a compaction pass could hand back to the free list
            # right now: spill-retired ranges + trimmable tails
            "reclaimable_tiles": self._retired_rows + trimmable,
            # expired (masked) lanes as a fraction of live lane content —
            # the append path re-uses these before consuming new rows
            "dead_lane_frac": (
                self._dead_lanes / deg_sum if deg_sum else 0.0
            ),
        }

    def reserve_report(self) -> Dict[str, object]:
        """Live reserve budget (round-18 satellite — the r17 "capacity
        exhaustion is a planned hard error" leftover made diagnosable):
        tiles used / remaining, consumption rate per commit, and the
        projected commits left at that rate (None before any
        consumption). `StreamCapacityError` messages carry the same
        numbers, so the planned hard error names its own runway."""
        with self._lock:
            return self._reserve_report_locked()

    def _capacity_error(self, prefix: str) -> StreamCapacityError:
        """Build the planned hard error WITH the reserve diagnosis
        (caller holds ``_lock``)."""
        r = self._reserve_report_locked()
        proj = r["projected_commits_to_exhaustion"]
        return StreamCapacityError(
            f"{prefix} — reserve {r['reserve_used']}/{r['reserve_tiles']} "
            f"rows used over {r['commits']} commit(s) "
            f"({r['rows_per_commit']:.2f} rows/commit"
            + (f", ~{proj:.0f} commits of runway were left"
               if proj is not None else "")
            + "); reclaim rows with compaction "
            "(plan_compaction/apply_compaction), grow the bank with "
            "provision_reserve (one sealed-program rebuild), or rebuild "
            "the stream with a larger reserve_frac/reserve_tiles"
        )

    @property
    def temporal(self) -> bool:
        """True when this stream carries per-edge timestamps (built with
        ``edge_ts=``) — `temporal_graph()` is then the sampling surface
        and every committed edge must arrive with a timestamp."""
        return self.ttiles is not None

    def graph(self):
        """The CURRENT device ``(bd, tiles)`` pair — what a stream-bound
        `GraphSageSampler` samples from (`bind_stream`). Array objects
        change at every commit; shapes never do."""
        if self._tiles_dev is None:
            raise ValueError(
                "stream was built with device_arrays=False (host "
                "bookkeeping only)"
            )
        return self._bd_dev, self._tiles_dev

    def temporal_graph(self):
        """The CURRENT device ``(bd, tiles, ttiles)`` triple — what a
        temporal-bound sampler (`GraphSageSampler.bind_temporal`) draws
        from. Same commit semantics as `graph()`: array objects change
        per fenced commit, shapes never."""
        if not self.temporal:
            raise ValueError(
                "stream was built without edge_ts (no timestamp payload)"
            )
        if self._tiles_dev is None:
            raise ValueError(
                "stream was built with device_arrays=False (host "
                "bookkeeping only)"
            )
        return self._bd_dev, self._tiles_dev, self._tt_dev

    def neighbors(self, node: int) -> np.ndarray:
        return self.adj.neighbors(node)

    def degree(self, node: int) -> int:
        return self.adj.degree(node)

    def to_csr_topo(self):
        return self.adj.to_csr_topo()

    def affected_seeds(self, srcs, hops: int) -> np.ndarray:
        """The invalidation set of changed rows ``srcs``: every node
        whose ``hops``-hop EXPANSION could reach one (reverse closure
        over the updated graph, srcs included). ``hops`` is the number of
        expansion hops — ``len(sizes) - 1`` for an L-layer sampler, since
        the final hop's frontier is gathered but never expanded."""
        return self.adj.reverse_closure(srcs, hops)

    # ----------------------------------------------------------- writes
    def preflight(self, delta: Optional[GraphDelta] = None,
                  installs: Optional[Sequence[Tuple[int, np.ndarray]]] = None,
                  ) -> int:
        """Validate a WHOLE batch — edge ids, install constraints, and
        reserve capacity (spills simulated in apply order) — without
        mutating anything. Returns the reserve rows the batch would
        consume; raises `StreamCapacityError`/`ValueError` exactly where
        `apply` would, BEFORE any state moves. `apply` runs this first,
        which is what makes a commit atomic: it either lands fully
        (host + device + version stamps) or leaves the stream untouched.
        Multi-stream callers (the dist router) preflight every stream
        before applying to any."""
        src, dst = delta.edges() if delta is not None else (
            np.array([], np.int64), np.array([], np.int64)
        )
        ts = delta.edges_ts() if delta is not None else None
        removals = delta.removals() if delta is not None else None
        updates = delta.updates() if delta is not None else None
        installs = self._normalize_installs(installs)
        with self._lock:
            return self._preflight_locked(src, dst, installs, ts,
                                          removals, updates)

    def _normalize_installs(self, installs):
        """Normalize install entries to ``(node, nbrs, ts_row|None)`` —
        temporal streams accept (and require) a per-neighbor timestamp
        row per install; non-temporal streams reject one."""
        out = []
        for entry in installs or ():
            if len(entry) == 2:
                node, nbrs = entry
                ts_row = None
            else:
                node, nbrs, ts_row = entry
            nbrs = np.asarray(nbrs, np.int64)
            if ts_row is not None:
                ts_row = np.asarray(ts_row, np.float32).reshape(-1)
            out.append((int(node), nbrs, ts_row))
        return out

    def _check_ts(self, src, ts, installs) -> None:
        """The temporal-arity contract, one place: a temporal stream
        takes exactly one timestamp per edge (appends AND installs); a
        non-temporal stream takes none."""
        if self.temporal:
            if src.size and (ts is None or ts.shape != src.shape):
                raise ValueError(
                    "temporal stream (edge_ts set) needs one timestamp "
                    "per appended edge — stage with "
                    "GraphDelta.add_edges(src, dst, ts=...)"
                )
            for node, nbrs, ts_row in installs:
                if nbrs.size and (ts_row is None
                                  or ts_row.shape[0] != nbrs.shape[0]):
                    raise ValueError(
                        f"temporal install for node {node} needs one "
                        f"timestamp per neighbor"
                    )
        else:
            if ts is not None or any(t is not None for _, _, t in installs):
                raise ValueError(
                    "edge timestamps staged into a non-temporal stream — "
                    "build StreamingTiledGraph(edge_ts=...) to carry them"
                )
        if ts is not None and ts.size and not np.isfinite(ts).all():
            raise ValueError(
                "non-finite appended timestamps — +inf is reserved as "
                "the retention expiry sentinel (expire_edges)"
            )
        for node, _nbrs, ts_row in installs:
            if ts_row is not None and ts_row.size and (
                not np.isfinite(ts_row).all()
            ):
                raise ValueError(
                    f"non-finite install timestamps for node {node} — "
                    "+inf is reserved as the retention expiry sentinel"
                )

    def _preflight_locked(self, src, dst, installs, ts=None,
                          removals=None, updates=None) -> int:
        if src.size:
            validate_edge_ids(src, dst, self.n)
        self._check_ts(src, ts, installs)
        rsrc, rdst = removals if removals is not None else (
            np.empty(0, np.int64), np.empty(0, np.int64)
        )
        usrc, udst, uts = updates if updates is not None else (
            np.empty(0, np.int64), np.empty(0, np.int64),
            np.empty(0, np.float32),
        )
        if rsrc.size:
            validate_edge_ids(rsrc, rdst, self.n, what="removal")
        if usrc.size:
            validate_edge_ids(usrc, udst, self.n, what="update")
            if not self.temporal:
                raise ValueError(
                    "timestamp updates staged into a non-temporal "
                    "stream — streamed tiles carry no weight payload; "
                    "the ts lane (edge_ts=...) is the one mutable "
                    "per-edge field"
                )
        # removal/update existence, simulated in APPLY ORDER (installs,
        # appends, removals, updates) over per-(u, v) occurrence counts —
        # all-or-none: one missing edge fails the whole batch here
        if rsrc.size or usrc.size:
            pairs = set(zip(rsrc.tolist(), rdst.tolist())) | set(
                zip(usrc.tolist(), udst.tolist())
            )
            inst_rows = {node: nbrs for node, nbrs, _ in installs}
            avail: Dict[Tuple[int, int], int] = {}
            rows_cache: Dict[int, np.ndarray] = {}
            for (u, v) in pairs:
                if u not in rows_cache:
                    rows_cache[u] = (
                        inst_rows[u] if u in inst_rows
                        else self.adj.neighbors(u)
                    )
                avail[(u, v)] = int((rows_cache[u] == v).sum())
            for u, v in zip(src.tolist(), dst.tolist()):
                if (u, v) in avail:
                    avail[(u, v)] += 1
            for u, v in zip(rsrc.tolist(), rdst.tolist()):
                avail[(u, v)] -= 1
                if avail[(u, v)] < 0:
                    raise ValueError(
                        f"removal of absent edge ({u}, {v}) — the whole "
                        "batch is rejected (all-or-none), nothing was "
                        "applied"
                    )
            for u, v in zip(usrc.tolist(), udst.tolist()):
                if avail[(u, v)] <= 0:
                    raise ValueError(
                        f"timestamp update of absent edge ({u}, {v}) — "
                        "the whole batch is rejected (all-or-none), "
                        "nothing was applied"
                    )
        # reserve capacity: simulate the allocator EXACTLY (same
        # first-fit walk apply will take, on a scratch copy of the free
        # ranges) — with reclamation the free pool fragments, and
        # "enough total rows but no contiguous fit" must fail here, not
        # mid-commit
        need = 0
        sim_ranges = [r[:] for r in self._free_ranges]
        sim_alloc: Dict[int, int] = {}
        sim_deg: Dict[int, int] = {}
        sim_dead: Dict[int, int] = {}
        for node, nbrs, _ts_row in installs:
            if not 0 <= node < self.n:
                raise ValueError(
                    f"install node {node} outside [0, {self.n})"
                )
            if nbrs.size and ((nbrs < 0) | (nbrs >= self.n)).any():
                # same contract as edge appends: a bad id raises here,
                # never lands in the tiles (clipped gathers would
                # silently read the last row otherwise)
                raise ValueError(
                    f"install neighbors of node {node} outside "
                    f"[0, {self.n}): "
                    f"{nbrs[(nbrs < 0) | (nbrs >= self.n)][:4].tolist()}"
                )
            if node in sim_deg:
                raise ValueError(
                    f"duplicate install for node {node} in one batch"
                )
            if int(self.bd[node, 1]) != 0:
                raise ValueError(
                    f"install_rows targets degree-0 rows only (node "
                    f"{node} has degree {int(self.bd[node, 1])}); use "
                    "apply() appends for materialized rows"
                )
            if nbrs.size == 0:
                sim_deg[node] = 0
                sim_alloc[node] = int(self.alloc_rows[node])
                continue
            # a deleted-to-zero row re-installing releases its old rows
            # first, exactly as _install_locked will
            old = int(self.alloc_rows[node])
            if old:
                self._put(sim_ranges, int(self.bd[node, 0]), old)
            rows = -(-int(nbrs.size) // LANE)
            need += rows
            if self._take(sim_ranges, rows) is None:
                raise self._capacity_error(
                    f"tile reserve exhausted: install of node {node} "
                    f"needs {rows} contiguous rows, "
                    f"{sum(r[1] for r in sim_ranges)} free"
                )
            sim_alloc[node] = rows
            sim_deg[node] = int(nbrs.size)
            sim_dead[node] = 0
        for u in src:
            u = int(u)
            dead = sim_dead.get(u, len(self._dead.get(u, ())))
            if dead > 0:
                # the append re-uses an expired lane: no degree growth,
                # no spill risk
                sim_dead[u] = dead - 1
                continue
            sim_dead[u] = 0
            d = sim_deg.get(u, int(self.bd[u, 1]))
            a = sim_alloc.get(u, int(self.alloc_rows[u]))
            if d >= a * LANE:
                a += self.grow_tiles
                need += a
                if self._take(sim_ranges, a) is None:
                    raise self._capacity_error(
                        f"tile reserve exhausted: batch needs {need} "
                        f"rows ({a} contiguous for node {u}), "
                        f"{sum(r[1] for r in sim_ranges)} free"
                    )
                sim_alloc[u] = a
            sim_deg[u] = d + 1
        return need

    def apply(self, delta: GraphDelta,
              installs: Optional[Sequence[Tuple[int, np.ndarray]]] = None,
              defer_publish: bool = False,
              ) -> Dict[str, int]:
        """Commit one delta batch: host pad-lane writes / spills /
        installs, then ONE batched device tile swap + one bd swap.
        ATOMIC: the whole batch is preflighted (ids, install
        constraints, reserve capacity) before any state moves, so a
        raising apply leaves host, device, versions, and the adjacency
        untouched. Returns the commit summary. Callers serving traffic
        go through ``engine.update_graph`` (which fences in-flight
        flushes first, or — zero-stall mode — passes
        ``defer_publish=True`` so the new device arrays stage without
        touching what `graph()` serves until `publish()`); the stream's
        own lock only orders bare concurrent callers."""
        src, dst = delta.edges() if delta is not None else (
            np.array([], np.int64), np.array([], np.int64)
        )
        ts = delta.edges_ts() if delta is not None else None
        removals = delta.removals() if delta is not None else (
            np.empty(0, np.int64), np.empty(0, np.int64)
        )
        updates = delta.updates() if delta is not None else (
            np.empty(0, np.int64), np.empty(0, np.int64),
            np.empty(0, np.float32),
        )
        rsrc, rdst = removals
        usrc, udst, uts = updates
        installs = self._normalize_installs(installs)
        if (src.size == 0 and not installs and rsrc.size == 0
                and usrc.size == 0):
            return {"edges": 0, "pad_writes": 0, "tile_spills": 0,
                    "installs": 0, "tile_rows_swapped": 0,
                    "bd_rows_swapped": 0, "free_rows": self.free_rows,
                    "version": self.version, "edges_deleted": 0,
                    "ts_updates": 0, "lanes_reused": 0}
        with self._lock:
            self._preflight_locked(src, dst, installs, ts,
                                   removals, updates)
            touched_tiles: set = set()
            touched_bd: set = set()
            pad_writes = spills = reused = 0
            for node, nbrs, ts_row in installs:
                self._install_locked(node, nbrs, touched_tiles, touched_bd,
                                     ts_row=ts_row)
            # per-edge: the adjacency and the tiles advance in lockstep
            # (an append that re-uses a dead lane REPLACES the adjacency
            # entry instead of appending — lane order stays shared, which
            # is what keeps rebuild parity through the whole lifecycle).
            # Ids were validated by the preflight above.
            for i, (u, v) in enumerate(zip(src, dst)):
                p, s, r = self._append_locked(
                    int(u), int(v), touched_tiles, touched_bd,
                    ts=None if ts is None else float(ts[i]),
                )
                pad_writes += p
                spills += s
                reused += r
            if rsrc.size:
                for u, v in zip(rsrc, rdst):
                    self.adj.remove_one(int(u), int(v))
                for u in np.unique(rsrc):
                    self._rewrite_node_locked(int(u), touched_tiles,
                                              touched_bd)
            for u, v, t in zip(usrc, udst, uts):
                self._update_one_locked(int(u), int(v), float(t),
                                        touched_tiles, touched_bd)
            self.version += 1
            changed = np.fromiter(touched_bd, np.int64, len(touched_bd))
            self.node_version[changed] = self.version
            n_tiles, n_bd = self._sync_device_locked(
                touched_tiles, touched_bd, defer=defer_publish)
            self.stats["pad_writes"] += pad_writes
            self.stats["tile_spills"] += spills
            self.stats["installs"] += len(installs)
            self.stats["edges"] += int(src.size)
            self.stats["edges_deleted"] += int(rsrc.size)
            self.stats["ts_updates"] += int(usrc.size)
            self.stats["lanes_reused"] += reused
            self.stats["tile_rows_swapped"] += n_tiles
            self.stats["bd_rows_swapped"] += n_bd
            return {"edges": int(src.size), "pad_writes": pad_writes,
                    "tile_spills": spills, "installs": len(installs),
                    "tile_rows_swapped": n_tiles, "bd_rows_swapped": n_bd,
                    "free_rows": self.free_rows, "version": self.version,
                    "edges_deleted": int(rsrc.size),
                    "ts_updates": int(usrc.size), "lanes_reused": reused}

    def install_rows(self, rows: Sequence[Tuple[int, np.ndarray]]
                     ) -> Dict[str, int]:
        """Materialize full adjacency rows for nodes currently reading
        degree 0 — the dist router's incremental halo-closure extension
        (a node newly entering an owner's closure carries its WHOLE
        current edge list, not an append). One batched commit like
        `apply`."""
        return self.apply(None, installs=rows)

    # -------------------------------------------------- lifecycle (r21)
    def expire_edges(self, cutoff, defer_publish: bool = False
                     ) -> Dict[str, object]:
        """TTL retention commit: mask every edge with ``ts <= cutoff``
        by overwriting its timestamp lane with ``+inf`` — NO lane
        shifts, so the expired stream stays the exact bit-dual of the
        unexpired stream queried with a ``cutoff < ts <= t`` band mask
        (the r19 masking's natural dual; pinned in
        tests/test_lifecycle.py). Masked lanes become the dead pool
        later appends re-use. One batched device ttile swap; bumps the
        version and stamps touched nodes (their draws at any t change),
        so the engines' invalidation consumers fire exactly as for
        appends. ``cutoff`` is snapped to the float32 grid — window
        arithmetic must follow the `quantize_t` f32 rule."""
        if not self.temporal:
            raise ValueError(
                "expire_edges needs a temporal stream (edge_ts=...) — "
                "a plain stream has no timestamps to retire"
            )
        cutoff = np.float32(cutoff)
        with self._lock:
            cand = np.nonzero(self._min_ts <= cutoff)[0]
            if cand.size == 0:
                return {"edges_expired": 0, "nodes": 0,
                        "version": self.version, "tile_rows_swapped": 0,
                        "sources": np.empty(0, np.int64)}
            touched_tiles: set = set()
            touched_bd: set = set()
            n_exp = 0
            for u in cand:
                u = int(u)
                pos = self.adj.expire_node(u, float(cutoff))
                if not pos:
                    # stale min (shouldn't persist — reindex below keeps
                    # it exact); recompute defensively
                    self._reindex_node_ts_locked(
                        u, self.adj.neighbors_ts(u))
                    continue
                base = int(self.bd[u, 0])
                for p in pos:
                    self.ttiles[base + p // LANE, p % LANE] = np.inf
                    touched_tiles.add(base + p // LANE)
                touched_bd.add(u)
                n_exp += len(pos)
                self._reindex_node_ts_locked(u, self.adj.neighbors_ts(u))
            self.version += 1
            changed = np.fromiter(touched_bd, np.int64, len(touched_bd))
            self.node_version[changed] = self.version
            n_tiles, n_bd = self._sync_device_locked(
                touched_tiles, touched_bd, defer=defer_publish)
            self.stats["edges_expired"] += n_exp
            self.stats["tile_rows_swapped"] += n_tiles
            self.stats["bd_rows_swapped"] += n_bd
            return {"edges_expired": n_exp, "nodes": len(touched_bd),
                    "version": self.version, "tile_rows_swapped": n_tiles,
                    "sources": np.sort(changed)}

    def plan_compaction(self, max_moves: int = 0) -> Dict[str, object]:
        """Snapshot a reclamation plan — built OFF-FENCE (only the
        stream lock, no traffic drain): spill-retired ranges to release,
        over-allocated rows to trim (``alloc > ceil(deg/128)``), and up
        to ``max_moves`` defrag relocations (highest-based nodes first).
        Every per-node entry carries the node's version stamp;
        `apply_compaction` skips entries whose row committed in between
        (stale) — the LSM discipline: plan cheap, validate at flip."""
        with self._lock:
            plan: Dict[str, object] = {
                "retired": [tuple(r) for r in self._retired],
                "planned_at": self.version,
            }
            deg = self.bd[:, 1].astype(np.int64)
            tight = -(-deg // LANE)
            slack = self.alloc_rows.astype(np.int64) - tight
            plan["trims"] = [
                (int(u), int(self.node_version[u]))
                for u in np.nonzero(slack > 0)[0]
            ]
            moves: List[Tuple[int, int]] = []
            if max_moves:
                order = np.argsort(self.bd[:, 0], kind="stable")[::-1]
                for u in order:
                    if len(moves) >= int(max_moves):
                        break
                    u = int(u)
                    if self.alloc_rows[u] and int(self.bd[u, 0]):
                        moves.append((u, int(self.node_version[u])))
            plan["moves"] = moves
            return plan

    def apply_compaction(self, plan: Dict[str, object],
                         defer_publish: bool = False) -> Dict[str, int]:
        """Apply a `plan_compaction` plan: release retired ranges, trim
        over-allocated tails, relocate planned nodes downward (verbatim
        row copies through the ``base`` indirection). STRICTLY
        observe-only on bits — no version bump, no node-version stamps,
        no draw changes (pinned: logits and dispatch logs identical with
        compaction on/off). Engines fence the flip
        (`engine.compact_graph`); stale per-node entries are skipped."""
        with self._lock:
            freed = trims = 0
            touched_tiles: set = set()
            touched_bd: set = set()
            for rng in plan.get("retired", ()):
                rng = (int(rng[0]), int(rng[1]))
                if rng in self._retired:
                    self._retired.remove(rng)
                    self._retired_rows -= rng[1]
                    self._release_locked(rng[0], rng[1])
                    freed += rng[1]
            for u, ver in plan.get("trims", ()):
                u = int(u)
                if int(self.node_version[u]) != int(ver):
                    continue  # raced a commit — the next plan retries
                deg = int(self.bd[u, 1])
                tight = -(-deg // LANE)
                alloc = int(self.alloc_rows[u])
                if alloc > tight:
                    base = int(self.bd[u, 0])
                    self._release_locked(base + tight, alloc - tight)
                    self.alloc_rows[u] = tight
                    freed += alloc - tight
                    trims += 1
            moved = 0
            for u, ver in plan.get("moves", ()):
                u = int(u)
                if int(self.node_version[u]) != int(ver):
                    continue
                rows = int(self.alloc_rows[u])
                base = int(self.bd[u, 0])
                if rows == 0:
                    continue
                new = self._take(self._free_ranges, rows)
                if new is None or new >= base:
                    if new is not None:
                        # no downward fit — put the trial back
                        self._put(self._free_ranges, new, rows)
                    continue
                self.tiles[new:new + rows] = self.tiles[base:base + rows]
                if self.ttiles is not None:
                    self.ttiles[new:new + rows] = (
                        self.ttiles[base:base + rows]
                    )
                self.bd[u, 0] = new
                self._release_locked(base, rows)
                touched_tiles.update(range(new, new + rows))
                touched_bd.add(u)
                moved += 1
            n_tiles, n_bd = self._sync_device_locked(
                touched_tiles, touched_bd, defer=defer_publish)
            self.stats["tiles_reclaimed"] += freed
            self.stats["compactions"] += 1
            self.stats["tile_rows_swapped"] += n_tiles
            self.stats["bd_rows_swapped"] += n_bd
            return {"tiles_reclaimed": freed, "trims": trims,
                    "moves": moved, "tile_rows_swapped": n_tiles,
                    "free_rows": self.free_rows}

    def compact(self, max_moves: int = 0) -> Dict[str, int]:
        """Plan + apply in one call (bare callers; engines split the
        two around their fence)."""
        return self.apply_compaction(self.plan_compaction(max_moves))

    def provision_reserve(self, tiles: int) -> Dict[str, object]:
        """Grow the tile tables by a whole BANK of ``tiles`` rows — the
        one sanctioned shape change. Host mirrors reallocate, the new
        bank joins the free pool, and (when device arrays exist) fresh
        device tables upload. Sealed AOT executables bound to the old
        shapes must be rebuilt ONCE per provision event
        (`inference.BucketPrograms.reprovision` — never
        recompile-per-commit); `serve.engine.ServeEngine.
        provision_reserve` fences and does both sides."""
        bank = int(tiles)
        if bank <= 0:
            raise ValueError(f"provision_reserve needs tiles > 0, got "
                             f"{tiles}")
        with self._lock:
            old_cap = self.m_cap
            self.m_cap = old_cap + bank
            new_tiles = np.zeros((self.m_cap, LANE), self.tiles.dtype)
            new_tiles[:old_cap] = self.tiles
            self.tiles = new_tiles
            if self.ttiles is not None:
                new_tt = np.zeros((self.m_cap, LANE), np.float32)
                new_tt[:old_cap] = self.ttiles
                self.ttiles = new_tt
            self._put(self._free_ranges, old_cap, bank)
            self.stats["provisions"] += 1
            if self._tiles_dev is not None:
                import jax.numpy as jnp

                # a full re-upload supersedes any staged (defer_publish)
                # arrays — their shapes are the OLD bank size; drop them
                self._staged_bd = None
                self._staged_tiles = None
                self._staged_tt = None
                self._tiles_dev = jnp.asarray(self.tiles)
                if self.ttiles is not None:
                    self._tt_dev = jnp.asarray(self.ttiles)
            return self._reserve_report_locked()

    # ------------------------------------------------------- internals
    def _append_locked(self, u: int, v: int, touched_tiles, touched_bd,
                       ts: Optional[float] = None):
        """One edge append, advancing adjacency and tiles together.
        Returns ``(pad_writes, spills, lanes_reused)``. A node with dead
        (expired) lanes re-uses the LOWEST one first: the new edge takes
        the masked position (adjacency entry replaced in place, degree
        unchanged) — no reserve consumption, which is what keeps a
        sliding-window workload's tile footprint flat."""
        dead = self._dead.get(u)
        if dead:
            p = dead.pop(0)
            if not dead:
                del self._dead[u]
            self._dead_lanes -= 1
            base = int(self.bd[u, 0])
            row = base + p // LANE
            self.tiles[row, p % LANE] = v
            # dead lanes exist only on temporal streams (expiry made them)
            self.ttiles[row, p % LANE] = ts
            self.adj.replace_at(u, p, v, ts=ts)
            self._min_ts[u] = min(float(self._min_ts[u]), float(ts))
            touched_tiles.add(row)
            touched_bd.add(u)
            return 0, 0, 1
        self.adj._append_one(u, v, ts=ts)
        base = int(self.bd[u, 0])
        deg = int(self.bd[u, 1])
        cap = int(self.alloc_rows[u]) * LANE
        spilled = 0
        if deg >= cap:
            base = self._relocate_locked(u, touched_tiles)
            spilled = 1
        row = base + deg // LANE
        self.tiles[row, deg % LANE] = v
        if self.ttiles is not None:
            # the timestamp lands in the SAME (row, lane) as the edge —
            # one commit makes both drawable (arity checked by preflight)
            self.ttiles[row, deg % LANE] = ts
            self._min_ts[u] = min(float(self._min_ts[u]), float(ts))
        self.bd[u, 1] = deg + 1
        touched_tiles.add(row)
        touched_bd.add(u)
        return 1 - spilled, spilled, 0

    def _relocate_locked(self, u: int, touched_tiles) -> int:
        """Move node ``u`` to ``alloc + grow_tiles`` fresh rows from the
        free pool (copy its existing tiles, bump base). The old rows
        become dead padding the degree mask never reads — draws are
        unchanged because `ops.sample._tiled_resolve` only ever
        dereferences ``base + pos // 128`` for valid positions. The
        vacated rows park in ``_retired`` (still counted as consumed —
        r17 semantics) until a compaction releases them."""
        old_base = int(self.bd[u, 0])
        old_rows = int(self.alloc_rows[u])
        need = old_rows + self.grow_tiles
        new_base = self._take(self._free_ranges, need)
        if new_base is None:
            raise self._capacity_error(
                f"tile reserve exhausted: node {u} needs {need} "
                f"contiguous rows, {self.free_rows} free"
            )
        if old_rows:
            self.tiles[new_base:new_base + old_rows] = (
                self.tiles[old_base:old_base + old_rows]
            )
            if self.ttiles is not None:
                self.ttiles[new_base:new_base + old_rows] = (
                    self.ttiles[old_base:old_base + old_rows]
                )
            self._retired.append((old_base, old_rows))
            self._retired_rows += old_rows
        touched_tiles.update(range(new_base, new_base + old_rows + 1))
        self.bd[u, 0] = new_base
        self.alloc_rows[u] = need
        return new_base

    def _rewrite_node_locked(self, u: int, touched_tiles,
                             touched_bd) -> None:
        """Re-emit node ``u``'s lanes from its (just-mutated) adjacency
        — the deletion shift: survivors pack left in lane order,
        trailing lanes zero. Dead-lane positions and the min-ts index
        are recomputed from the shifted timestamp row."""
        base = int(self.bd[u, 0])
        rows = int(self.alloc_rows[u])
        nbrs = self.adj.neighbors(u)
        d = int(nbrs.size)
        tvals = None
        if rows:
            flat = self.tiles[base:base + rows].reshape(-1)
            flat[:d] = nbrs.astype(self.tiles.dtype)
            flat[d:] = 0
            if self.ttiles is not None:
                tvals = self.adj.neighbors_ts(u)
                tflat = self.ttiles[base:base + rows].reshape(-1)
                tflat[:d] = tvals
                tflat[d:] = 0
            touched_tiles.update(range(base, base + rows))
        self.bd[u, 1] = d
        touched_bd.add(u)
        if self.ttiles is not None:
            if tvals is None:
                tvals = np.empty(0, np.float32)
            self._reindex_node_ts_locked(u, tvals)

    def _reindex_node_ts_locked(self, u: int, tvals: np.ndarray) -> None:
        """Rebuild ``u``'s dead-lane list and min-ts entry from its
        current timestamp row."""
        old = self._dead.pop(u, None)
        if old:
            self._dead_lanes -= len(old)
        deadpos = np.nonzero(np.isinf(tvals))[0]
        if deadpos.size:
            self._dead[u] = deadpos.tolist()
            self._dead_lanes += int(deadpos.size)
        finite = tvals[np.isfinite(tvals)]
        self._min_ts[u] = finite.min() if finite.size else np.inf

    def _update_one_locked(self, u: int, v: int, t: float,
                           touched_tiles, touched_bd) -> None:
        """Retarget one edge's timestamp lane (first lane-order
        occurrence of ``(u, v)``). A formerly-dead lane given a finite
        ts comes back to life (leaves the re-use pool)."""
        p = self.adj.update_one(u, v, t)
        base = int(self.bd[u, 0])
        row = base + p // LANE
        self.ttiles[row, p % LANE] = t
        touched_tiles.add(row)
        touched_bd.add(u)
        # recompute (not just min): the update may have MOVED the row's
        # minimum up, and a stale min would re-scan this node at every
        # expiry; this also drops lane p from the dead list if the
        # update revived it
        self._reindex_node_ts_locked(u, self.adj.neighbors_ts(u))

    def _install_locked(self, node: int, nbrs: np.ndarray, touched_tiles,
                        touched_bd, ts_row: Optional[np.ndarray] = None,
                        ) -> None:
        if not 0 <= node < self.n:
            raise ValueError(f"install node {node} outside [0, {self.n})")
        if int(self.bd[node, 1]) != 0:
            raise ValueError(
                f"install_rows targets degree-0 rows only (node {node} "
                f"has degree {int(self.bd[node, 1])}); use apply() "
                "appends for materialized rows"
            )
        if nbrs.size == 0:
            return
        # a deleted-to-zero row re-installing hands its old rows back
        # first (they hold nothing a draw can reach)
        old_rows = int(self.alloc_rows[node])
        if old_rows:
            self._release_locked(int(self.bd[node, 0]), old_rows)
            self.alloc_rows[node] = 0
        need = -(-int(nbrs.size) // LANE)
        base = self._take(self._free_ranges, need)
        if base is None:
            raise self._capacity_error(
                f"tile reserve exhausted installing node {node} "
                f"({need} contiguous rows needed, {self.free_rows} free)"
            )
        flat = self.tiles[base:base + need].reshape(-1)
        flat[: nbrs.size] = nbrs.astype(self.tiles.dtype)
        flat[nbrs.size:] = 0
        if self.ttiles is not None:
            tflat = self.ttiles[base:base + need].reshape(-1)
            tflat[: nbrs.size] = ts_row
            tflat[nbrs.size:] = 0
        self.bd[node, 0] = base
        self.bd[node, 1] = nbrs.size
        self.alloc_rows[node] = need
        touched_tiles.update(range(base, base + need))
        touched_bd.add(node)
        # bookkeeping: an installed row's neighbors enter the adjacency
        # view as "extras" over its empty base row (same lane order) —
        # or replace the override list wholesale when the row was
        # already materialized by a lifecycle op
        if node in self.adj._override:
            self.adj._override[node] = [int(x) for x in nbrs]
            if self.ttiles is not None:
                self.adj._override_ts[node] = [float(x) for x in ts_row]
        else:
            self.adj._extra[node] = [int(x) for x in nbrs]
            if self.ttiles is not None:
                self.adj._extra_ts[node] = [float(x) for x in ts_row]
        for v in nbrs:
            self.adj._rev_extra.setdefault(int(v), []).append(node)
        self.adj._n_extra += int(nbrs.size)
        if self._min_ts is not None:
            finite = ts_row[np.isfinite(ts_row)]
            self._min_ts[node] = finite.min() if finite.size else np.inf

    def _sync_device_locked(self, touched_tiles, touched_bd,
                            defer: bool = False):
        n_tiles, n_bd = len(touched_tiles), len(touched_bd)
        if self._tiles_dev is None or (not n_tiles and not n_bd):
            return n_tiles, n_bd
        import jax.numpy as jnp

        if not defer and self._staged_tiles is not None:
            # a deferred commit was never published (defensive — engine
            # commit locks serialize this away): fold it in first so the
            # scatter below bases on the newest bits
            self._publish_locked()
        if defer:
            # base on staged-if-present: apply + retention-expire inside
            # one zero-stall commit accumulate into ONE flip
            base_tiles = (self._staged_tiles if self._staged_tiles
                          is not None else self._tiles_dev)
            base_tt = (self._staged_tt if self._staged_tt is not None
                       else self._tt_dev)
            base_bd = (self._staged_bd if self._staged_bd is not None
                       else self._bd_dev)
        else:
            base_tiles, base_tt, base_bd = (
                self._tiles_dev, self._tt_dev, self._bd_dev
            )
        if n_tiles:
            idx = np.fromiter(touched_tiles, np.int64, n_tiles)
            idx.sort()
            pos, rows = _bucketed(idx, self.tiles[idx], self.m_cap)
            base_tiles = _scatter_rows(
                base_tiles, jnp.asarray(pos), jnp.asarray(rows)
            )
            if base_tt is not None:
                # the timestamp payload swaps the SAME touched rows in the
                # same commit — a draw can never see an edge without its ts
                tpos, trows = _bucketed(idx, self.ttiles[idx], self.m_cap)
                base_tt = _scatter_rows(
                    base_tt, jnp.asarray(tpos), jnp.asarray(trows)
                )
        if n_bd:
            idx = np.fromiter(touched_bd, np.int64, n_bd)
            idx.sort()
            pos, rows = _bucketed(idx, self.bd[idx], self.n)
            base_bd = _scatter_rows(
                base_bd, jnp.asarray(pos), jnp.asarray(rows)
            )
        if defer:
            self._staged_tiles = base_tiles
            self._staged_tt = base_tt
            self._staged_bd = base_bd
        else:
            self._tiles_dev = base_tiles
            self._tt_dev = base_tt
            self._bd_dev = base_bd
        return n_tiles, n_bd

    def _publish_locked(self) -> bool:
        if self._staged_tiles is None and self._staged_bd is None:
            return False
        if self._staged_tiles is not None:
            self._tiles_dev = self._staged_tiles
            self._tt_dev = self._staged_tt
        if self._staged_bd is not None:
            self._bd_dev = self._staged_bd
        self._staged_bd = None
        self._staged_tiles = None
        self._staged_tt = None
        return True

    def publish(self) -> bool:
        """Flip the staged (defer_publish) device arrays live: O(1) ref
        assignment under the stream lock — the zero-stall commit's only
        serving-visible moment. Flushes sealed before the flip keep the
        old array objects (immutable; `_scatter_rows` copies on write)
        and complete bit-exactly against their epoch. Returns True when
        something was staged."""
        with self._lock:
            return self._publish_locked()
