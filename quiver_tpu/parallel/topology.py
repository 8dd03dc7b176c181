"""Row-sharded graph topology over the device mesh.

The reference scales the *graph* past one device's memory with UVA: the CSR
lives in pinned host DRAM and GPU kernels read it over PCIe
(srcs/cpp/src/quiver/cuda/quiver_sample.cu:361-421 ZERO_COPY register;
benchmarks/ogbn-papers100M/train_quiver_multi_node.py runs 100M+ nodes that
way). The TPU-native equivalent keeps the CSR *in HBM* but row-shards it
across the mesh, so total graph capacity scales with chip count and every
topology read rides ICI/DCN collectives instead of PCIe:

- each shard owns a CONTIGUOUS row range (edge-balanced, so the big
  ``indices`` array splits evenly even on power-law graphs where
  degree-ordered hot rows concentrate at low ids);
- one-hop sampling becomes a collective: every chip draws neighbors for the
  frontier rows it owns (degree-0 elsewhere) and a ``psum`` over the
  topology axes assembles the full ``[W, k]`` neighbor matrix — the same
  owner-exclusive-contribution pattern as
  `quiver_tpu.parallel.collectives.sharded_gather`, riding the same axes.

The alternative formulation — route each frontier id to its owner with a
targeted all_to_all — is NOT better under XLA's static shapes: per-(owner)
request budgets must be provisioned for the worst-case skew, which on
degree-ordered power-law graphs is the full frontier width (the same
analysis as the grouped feature gather, see NEXT.md round-2 note), so the
lane count matches the all_gather/psum formulation while adding sorts.

Two shard LAYOUTS share all of the collective machinery above:

- ``layout="flat"`` (`ShardedTopology`): each shard keeps its contiguous CSR
  block as a local indptr + flat indices array; a drawn position is read
  from the indices seen as 128-lane rows (`ops.sample.flat_resolve`). Bytes
  follow the EDGES: 4 B an edge + 4 B a node;
- ``layout="tiled"`` (`TiledShardedTopology`): each shard's block is rebuilt
  into the 128-lane tile layout of `ops.sample.build_tiled_host` — a local
  ``(base, degree)`` table plus a ``[M, 128]`` tile table in which every
  node's list starts on a row of its own. Bytes follow the NODES: at least
  512 B + 8 B a node, whatever its degree.
  Both resolve positions with the same 2-D ROW gathers + one-hot lane
  selects (`ops.sample._select_lanes`), the fetch shape behind the
  single-chip 2.58x fused-SEPS win (PERF.md (earlier claims)); collective
  payloads and draws are IDENTICAL between layouts (same ``[W, k]``
  neighbor/valid return, same key -> same neighbours). `shard_topology_rows`
  resolves ``layout=None`` from the graph (`resolve_topology_layout`):
  tiled while the tile table costs at most 4 lanes per edge (ogbn-products:
  2.9), flat past that (ogbn-papers100M: 8.8, 28 GB of tiles for 3.2 GB of
  edges). `sampling_comm_bytes(layout=...)` still models the flat fetch as
  the one-element gathers it was before (SCALING.md's comparison).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import axis_size_compat
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.sample import (
    LANE,
    _tiled_bd_lookup,
    _tiled_resolve,
    build_tiled_host,
    fisher_yates_positions,
    flat_resolve,
    pad_widths,
    row_windows,
)


class ShardedTopology(NamedTuple):
    """Device-resident row-sharded CSR (see `shard_topology_rows`).

    ``indptr``  [P, R_max+1] — per-shard LOCAL indptr (offsets into the
                shard's own indices block), edge-padded so padding rows read
                as degree 0;
    ``indices`` [P, E_pad]   — per-shard neighbor block, zero-padded;
    ``row_start`` [P+1]      — global row boundaries (replicated; shard p
                owns rows ``row_start[p]:row_start[p+1]``).
    """

    indptr: jax.Array
    indices: jax.Array
    row_start: jax.Array

    @property
    def n_shards(self) -> int:
        return self.indptr.shape[0]

    def specs(self, feat_axes) -> "ShardedTopology":
        """shard_map in_specs pytree for this topology striped over
        ``feat_axes`` (row_start is replicated)."""
        return topology_specs(feat_axes)


def topology_specs(feat_axes) -> "ShardedTopology":
    """The ONE place the ShardedTopology shard_map spec layout lives: CSR
    blocks striped over ``feat_axes``, row boundaries replicated."""
    return ShardedTopology(
        indptr=P(feat_axes, None), indices=P(feat_axes, None), row_start=P()
    )


class TiledShardedTopology(NamedTuple):
    """Row-sharded CSR in the 128-lane TILE layout (`build_tiled_topology_shards`).

    ``bd``    [P, R_max, 2] int32 — per-shard LOCAL (tile_base, degree)
              table (`ops.sample.tiled_base_host` of the shard's block),
              row-padded so rows past the shard's range read as degree 0;
    ``tiles`` [P, M_max, 128] — per-shard tile tables (`build_tiled_host`
              of the block), tile-count-padded so the blocks stack;
    ``row_start`` [P+1]      — global row boundaries (replicated; shard p
              owns rows ``row_start[p]:row_start[p+1]``), same contract
              as `ShardedTopology`.
    """

    bd: jax.Array
    tiles: jax.Array
    row_start: jax.Array

    @property
    def n_shards(self) -> int:
        return self.bd.shape[0]

    def specs(self, feat_axes) -> "TiledShardedTopology":
        """shard_map in_specs pytree for this topology striped over
        ``feat_axes`` (row_start is replicated)."""
        return tiled_topology_specs(feat_axes)


def tiled_topology_specs(feat_axes) -> "TiledShardedTopology":
    """`topology_specs` for the tiled layout: bd/tile blocks striped over
    ``feat_axes``, row boundaries replicated."""
    return TiledShardedTopology(
        bd=P(feat_axes, None, None),
        tiles=P(feat_axes, None, None),
        row_start=P(),
    )


# A tile table costs 128 lanes per started tile row PER NODE; the flat block
# costs one lane per edge. Up to this ratio of tile slots to edges the tile
# layout is taken (ogbn-products, mean degree 50: 2.9), past it the flat one
# (ogbn-papers100M, mean degree 14.6: 8.8, 28 GB of tiles for 3.2 GB of
# edges). The threshold separates what fits from what does not, not fast
# from slow: at the products shape on four v5e chips the step reads 55.56 ms
# flat (128 MB a chip, placed in 0.6 s) and 55.44 ms tiled (373 MB, 10.1 s),
# one run each, same losses (PERF.md section 6, PR 28). The tile layout of
# `parallel/` buys nothing there; deleting it is queued (ROADMAP S9).
TILE_SLOTS_PER_EDGE_MAX = 4.0


def tile_slots_per_edge(indptr) -> float:
    """Lanes the 128-lane tile layout would hold per edge of this graph
    (`ops.sample.tiled_base_host`'s row count, without building anything)."""
    deg = np.diff(np.asarray(indptr))
    return _tile_rows(deg) * LANE / max(int(deg.sum()), 1)


def _tile_rows(deg: np.ndarray) -> int:
    """Rows of the tile table for these degrees: every list starts a row."""
    return int((-(-deg // LANE)).sum())


def resolve_topology_layout(layout: Optional[str], indptr=None) -> str:
    """The shard layout to build: a given ``layout`` is checked and kept;
    ``None`` is resolved from the GRAPH (``indptr``): "tiled" where the
    tile table stays within `TILE_SLOTS_PER_EDGE_MAX` lanes per edge,
    "flat" where it would not (low mean degree: the tile layout's cost is
    per node, the flat layout's per edge). Both layouts fetch drawn
    positions as 128-lane row gathers and draw the same neighbours from the
    same key, on every backend."""
    if layout is None:
        if indptr is None:
            raise ValueError(
                "layout=None is resolved from the graph: pass its indptr "
                "(shard_topology_rows does), or name a layout"
            )
        layout = (
            "tiled" if tile_slots_per_edge(indptr) <= TILE_SLOTS_PER_EDGE_MAX
            else "flat"
        )
    if layout not in ("flat", "tiled"):
        raise ValueError(f"unsupported topology layout: {layout!r}")
    return layout


def partition_rows_by_edges(indptr: np.ndarray, n_shards: int) -> np.ndarray:
    """Contiguous row boundaries with ~equal edges per shard.

    Returns ``row_start`` [n_shards+1] with ``row_start[0]=0`` and
    ``row_start[-1]=N``. Row ranges may be empty on pathological graphs
    (one row owning nearly all edges); the sampler handles that (degree-0
    ownership elsewhere).
    """
    indptr = np.asarray(indptr)
    n = indptr.shape[0] - 1
    e = int(indptr[-1])
    targets = (np.arange(1, n_shards) * e) // n_shards
    cuts = np.searchsorted(indptr, targets, side="left")
    row_start = np.concatenate([[0], cuts, [n]]).astype(np.int64)
    return np.maximum.accumulate(row_start)  # enforce monotone under ties


def _padded(size: int, multiple: int) -> int:
    """``size`` rounded up to a multiple of ``multiple`` and of a power of
    two between 1/32 and 1/16 of it. Block lengths follow the graph (where
    the edge-balanced cuts fall), and a program is compiled per shape: with
    a coarse granule another graph of the same size and degree profile
    (another seed dealing the same degrees) lands on the SAME shapes and
    compiles nothing (per-seed shapes cost the papers100M cell an 11 s
    compile in seven runs of eight; PERF.md). At most 1/16 is padding."""
    granule = int(np.lcm(multiple, 1 << max(int(size).bit_length() - 5, 0)))
    return max(-(-size // granule) * granule, granule)


def _flat_plan(indptr: np.ndarray, n_shards: int, pad_multiple: int):
    """(row_start, r_max, e_pad) of the flat shard blocks."""
    # whole (8, 128) device tiles: `flat_resolve` reads the edges as lane
    # rows, and a block whose length is no multiple of 1024 is COPIED every
    # step where the program drops its shard axis (3.7 ms for 808 MB)
    pad_multiple = int(np.lcm(pad_multiple, 8 * LANE))
    row_start = partition_rows_by_edges(indptr, n_shards)
    r_max = max(int(np.max(np.diff(row_start))) if n_shards else 0, 1)
    r_max = _padded(r_max + 1, 8 * LANE) - 1  # the indptr block: r_max + 1
    e_pad = max(
        (int(indptr[row_start[p + 1]] - indptr[row_start[p]])
         for p in range(n_shards)),
        default=0,
    )
    return row_start, r_max, _padded(e_pad, pad_multiple)


def _flat_block(indptr, indices, row_start, p: int, r_max: int, e_pad: int,
                id_dtype) -> Tuple[np.ndarray, np.ndarray]:
    """Shard ``p``'s (local indptr [r_max+1], indices [e_pad]) alone."""
    lo, hi = int(row_start[p]), int(row_start[p + 1])
    ptr_dt = np.int32 if e_pad < 2**31 else np.int64
    local = (indptr[lo : hi + 1] - indptr[lo]).astype(ptr_dt)
    ptr = np.empty(r_max + 1, ptr_dt)
    ptr[: hi - lo + 1] = local
    # edge-pad: rows past this shard's range read as degree 0
    ptr[hi - lo + 1 :] = local[-1] if local.size else 0
    idx = np.zeros(e_pad, id_dtype)
    blk = indices[int(indptr[lo]) : int(indptr[hi])]
    idx[: blk.shape[0]] = blk
    return ptr, idx


def _row_start_dtype(row_start: np.ndarray):
    return np.int32 if int(row_start[-1]) < 2**31 else np.int64


def _stacked(plan, block, indptr, indices, n_shards: int):
    """All shards' blocks of a plan, stacked on the host (the public
    ``build_*_shards``; placement goes block by block instead)."""
    row_start, *sizes = plan
    blocks = [block(indptr, indices, row_start, p, *sizes, indices.dtype)
              for p in range(n_shards)]
    return (
        np.stack([b[0] for b in blocks]),
        np.stack([b[1] for b in blocks]),
        row_start.astype(_row_start_dtype(row_start)),
    )


def build_topology_shards(
    indptr: np.ndarray,
    indices: np.ndarray,
    n_shards: int,
    pad_multiple: int = 512,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side shard construction: (indptr_blocks, indices_blocks,
    row_start) as stacked numpy arrays (see `ShardedTopology`). The stack is
    a second copy of the graph: `shard_topology_rows` places block by block
    and never builds it."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    return _stacked(_flat_plan(indptr, n_shards, pad_multiple), _flat_block,
                    indptr, indices, n_shards)


def build_tiled_topology_shards(
    indptr: np.ndarray,
    indices: np.ndarray,
    n_shards: int,
    pad_multiple: int = 8,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side TILED shard construction: (bd_blocks, tiles_blocks,
    row_start) as stacked numpy arrays (see `TiledShardedTopology`).

    Row boundaries come from the same `partition_rows_by_edges` split as
    the flat build, and each shard's contiguous block is rebuilt with
    `build_tiled_host` on its LOCAL indptr — so a shard's tile table holds
    exactly the edges of its flat indices block, in the same per-row
    order (the parity tests lean on this). Per-shard tile counts are
    padded to the max (rounded up to ``pad_multiple`` tile rows) so the
    blocks stack into one ``[P, M_max, 128]`` device array; bd blocks are
    row-padded with degree-0 entries so out-of-range lookups draw nothing.
    `shard_topology_rows` places block by block and never builds the stack.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    return _stacked(_tiled_plan(indptr, n_shards, pad_multiple), _tiled_block,
                    indptr, indices, n_shards)


def _tiled_plan(indptr: np.ndarray, n_shards: int, pad_multiple: int):
    """(row_start, r_max, m_max) of the tiled shard blocks: tile-row counts
    from the degrees alone, nothing is built."""
    row_start = partition_rows_by_edges(indptr, n_shards)
    r_max = max(int(np.max(np.diff(row_start))) if n_shards else 0, 1)
    m_max = 1
    for p in range(n_shards):
        deg = np.diff(indptr[int(row_start[p]) : int(row_start[p + 1]) + 1])
        m_max = max(m_max, _tile_rows(deg))
    return row_start, r_max, -(-m_max // pad_multiple) * pad_multiple


def _tiled_block(indptr, indices, row_start, p: int, r_max: int, m_max: int,
                 id_dtype) -> Tuple[np.ndarray, np.ndarray]:
    """Shard ``p``'s (bd [r_max, 2], tiles [m_max, 128]) alone."""
    lo, hi = int(row_start[p]), int(row_start[p + 1])
    local_ptr = (indptr[lo : hi + 1] - indptr[lo]).astype(np.int64)
    local_idx = indices[int(indptr[lo]) : int(indptr[hi])]
    bd, tiles = build_tiled_host(local_ptr, local_idx, id_dtype)
    bd_blk = np.zeros((r_max, 2), np.int32)
    bd_blk[: bd.shape[0]] = bd
    tiles_blk = np.zeros((m_max, LANE), id_dtype)
    tiles_blk[: tiles.shape[0]] = tiles
    return bd_blk, tiles_blk


def shard_topology_rows(
    mesh: Mesh,
    topo,
    axes: Optional[Tuple[str, ...]] = None,
    layout: Optional[str] = None,
) -> Union["ShardedTopology", "TiledShardedTopology"]:
    """Place a `CSRTopo` row-sharded over the mesh's feature axes.

    Each device ends up holding ONLY its contiguous CSR block (~E/P edges;
    edge-balanced), so total graph capacity scales with chip count — the
    papers100M axis the reference serves with UVA (quiver_sample.cu:361-421).
    Blocks are built and uploaded shard by shard (`collectives.place_shards`):
    no device ever holds more than its block, and the host one block at a
    time, never the stack.

    ``axes`` defaults to the mesh's feature axes ((host, ici) on a 3-axis
    mesh, else (ici,)); the blocks are replicated over the remaining axes.

    ``layout`` picks the per-shard block format: "flat" (`ShardedTopology`)
    or "tiled" (`TiledShardedTopology`, the 128-lane tile layout). ``None``
    resolves from the graph (`resolve_topology_layout`: tiled unless the
    tile table would cost more than 4 lanes per edge). The train step takes
    either (`make_sharded_topo_train_step(layout=None)`).
    """
    from ..trace import trace_scope
    from ..utils import _best_id_dtype
    from .collectives import place_shards
    from .train import mesh_axes

    indptr = np.asarray(topo.indptr)
    indices = np.asarray(topo.indices)
    layout = resolve_topology_layout(layout, indptr)
    if axes is None:
        _, axes, _ = mesh_axes(mesh)
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    id_dtype = _best_id_dtype(indptr.shape[0])  # node ids, not edge ids
    if id_dtype == np.int64 and not jax.config.jax_enable_x64:
        raise ValueError(
            "graph needs int64 node ids on device but jax x64 is disabled — "
            "see CSRTopo.to_device"
        )
    if layout == "tiled":
        row_start, r_max, m_max = _tiled_plan(indptr, n_shards, 8)
        shapes = ((n_shards, r_max, 2), (n_shards, m_max, LANE))
        cls, block = TiledShardedTopology, _tiled_block
        plan = (r_max, m_max)
    else:
        row_start, r_max, e_pad = _flat_plan(indptr, n_shards, 512)
        shapes = ((n_shards, r_max + 1), (n_shards, e_pad))
        cls, block = ShardedTopology, _flat_block
        plan = (r_max, e_pad)
    chip_bytes = sum(int(np.prod(s[1:])) for s in shapes) * 4
    with trace_scope("quiver.shard.topology", tiled=int(layout == "tiled"),
                     chip_bytes=chip_bytes) as span:
        first, second = place_shards(
            mesh, axes, shapes,
            lambda p: tuple(
                b[None] for b in block(indptr, indices, row_start, p, *plan,
                                       id_dtype)
            ),
        )
        rs = jax.device_put(
            row_start.astype(_row_start_dtype(row_start)),
            NamedSharding(mesh, P()),
        )
        span.sync = (first, second, rs)
    return cls(first, second, rs)


def _flat_axis_index(axes: Tuple[str, ...]):
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * axis_size_compat(a) + lax.axis_index(a)
    return idx


def _psum_assemble(nbrs, valid, axes):
    """Owner-exclusive full assembly: shard contributions are zeros off
    the owner, so a psum over the striping axes IS the gather."""
    return lax.psum(nbrs, axes), lax.psum(valid, axes) > 0


def _grouped_collective_sample(partial_fn, cur, cur_valid, k, axes, group_axis, via):
    """The ONE grouped-sample implementation both shard layouts ride:
    all_gather the per-group frontiers over ``group_axis``, draw once via
    ``partial_fn(all_cur, all_valid) -> (nbrs, valid_int32)`` (a layout's
    un-reduced shard contribution at the gathered width), then return each
    group its own ``[W, k]`` slice through one of the two spellings —
    ``via="scatter"`` psum_scatters the ``[G, W, k]`` partials over the
    group axis (ring cost (G-1)/G) and psums the remaining striping axes at
    width W; ``via="psum"`` is the round-3 full-psum+slice spelling (2x the
    group-axis bytes, G x the other axes' width — kept selectable for the
    SCALING.md comparison)."""
    h = axis_size_compat(group_axis)
    w = cur.shape[0]
    all_cur = lax.all_gather(cur, group_axis).reshape(-1)
    all_valid = lax.all_gather(cur_valid, group_axis).reshape(-1)
    if via == "psum" or group_axis not in axes:
        nbrs, valid = _psum_assemble(*partial_fn(all_cur, all_valid), axes)
        me = lax.axis_index(group_axis)
        return nbrs.reshape(h, w, k)[me], valid.reshape(h, w, k)[me]
    if via != "scatter":
        raise ValueError(f"unknown via {via!r}")
    nbrs, valid = partial_fn(all_cur, all_valid)
    nbrs = lax.psum_scatter(
        nbrs.reshape(h, w, k), group_axis, scatter_dimension=0, tiled=False
    )
    valid = lax.psum_scatter(
        valid.reshape(h, w, k), group_axis, scatter_dimension=0, tiled=False
    )
    other = tuple(a for a in axes if a != group_axis)
    if other:
        nbrs = lax.psum(nbrs, other)
        valid = lax.psum(valid, other)
    return nbrs, valid > 0


def sharded_sample_layer(
    indptr_blk: jax.Array,
    indices_blk: jax.Array,
    row_start: jax.Array,
    cur: jax.Array,
    cur_valid: jax.Array,
    k: int,
    key: jax.Array,
    axes,
) -> Tuple[jax.Array, jax.Array]:
    """Collective one-hop sample from a row-sharded CSR (inside shard_map).

    ``cur`` must be identical across every axis in ``axes`` (use
    `sharded_sample_layer_grouped` when a striping axis carries different
    frontiers). Each shard draws neighbors for the frontier rows whose
    global id falls in its ``row_start`` range — everything else reads as
    degree 0 — and the psum over ``axes`` assembles the full result, since
    row ownership is exclusive. Same contract as
    `quiver_tpu.ops.sample.sample_layer`: ``(nbrs [W, k], valid [W, k])``
    with global neighbor ids.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    nbrs, valid = _sample_layer_partial(
        indptr_blk, indices_blk, row_start, cur, cur_valid, k, key, axes
    )
    return _psum_assemble(nbrs, valid, axes)


def _sample_layer_partial(
    indptr_blk, indices_blk, row_start, cur, cur_valid, k, key, axes
):
    """This shard's un-reduced contribution to a one-hop sample: neighbors
    for the frontier rows it owns, zeros elsewhere. Callers choose the
    reduction (full psum, or scatter-over-group then psum)."""
    idx = _flat_axis_index(axes)
    start = jnp.take(row_start, idx)
    end = jnp.take(row_start, idx + 1)
    r_max = indptr_blk.shape[0] - 1
    local = (cur - start).astype(jnp.int32)
    mine = cur_valid & (cur >= start) & (cur < end)
    s = jnp.clip(local, 0, r_max - 1)
    ptr, deg = row_windows(indptr_blk, s)
    deg = jnp.where(mine, deg, 0)
    pos, valid = fisher_yates_positions(key, deg, k)
    nbrs = flat_resolve(indices_blk, ptr, pos, k)
    nbrs = jnp.where(valid, nbrs, 0)
    return nbrs, valid.astype(jnp.int32)


def _tiled_sample_layer_partial(
    bd_blk, tiles_blk, row_start, cur, cur_valid, k, key, axes
):
    """`_sample_layer_partial` over the TILE layout: the owner test and the
    Fisher-Yates draw are identical (same key, same per-row degree — the
    draw is bit-equal to the flat path's), only position resolution differs:
    tile-row gathers + one-hot lane selects through `_tiled_resolve` instead
    of flat element gathers, the same fetch shape as the single-chip
    `tiled_sample_layer`."""
    idx = _flat_axis_index(axes)
    start = jnp.take(row_start, idx)
    end = jnp.take(row_start, idx + 1)
    local = (cur - start).astype(jnp.int32)
    mine = cur_valid & (cur >= start) & (cur < end)
    base, deg = _tiled_bd_lookup(bd_blk, local, mine)
    pos, valid = fisher_yates_positions(key, deg, k)
    nbrs = _tiled_resolve(tiles_blk, base, pos, k)
    nbrs = jnp.where(valid, nbrs, 0)
    return nbrs, valid.astype(jnp.int32)


def tiled_sharded_sample_layer(
    bd_blk: jax.Array,
    tiles_blk: jax.Array,
    row_start: jax.Array,
    cur: jax.Array,
    cur_valid: jax.Array,
    k: int,
    key: jax.Array,
    axes,
) -> Tuple[jax.Array, jax.Array]:
    """`sharded_sample_layer` over the TILE shard layout
    (`TiledShardedTopology`): same contract, same owner-exclusive psum
    assembly, bit-identical draws on the same key — the shard-local fetch
    rides 2-D row gathers instead of element gathers."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    nbrs, valid = _tiled_sample_layer_partial(
        bd_blk, tiles_blk, row_start, cur, cur_valid, k, key, axes
    )
    return _psum_assemble(nbrs, valid, axes)


def sharded_sample_layer_grouped(
    indptr_blk: jax.Array,
    indices_blk: jax.Array,
    row_start: jax.Array,
    cur: jax.Array,
    cur_valid: jax.Array,
    k: int,
    key: jax.Array,
    axes,
    group_axis: str,
    via: str = "scatter",
) -> Tuple[jax.Array, jax.Array]:
    """`sharded_sample_layer` for frontiers that DIFFER across ``group_axis``
    (one of the striping axes, typically "host" — data-parallel groups span
    it, so each host's frontier is distinct). Grouped machinery and both
    ``via`` return-trip spellings live in `_grouped_collective_sample`.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def partial_fn(all_cur, all_valid):
        return _sample_layer_partial(
            indptr_blk, indices_blk, row_start, all_cur, all_valid, k, key, axes
        )

    return _grouped_collective_sample(
        partial_fn, cur, cur_valid, k, axes, group_axis, via
    )


def tiled_sharded_sample_layer_grouped(
    bd_blk: jax.Array,
    tiles_blk: jax.Array,
    row_start: jax.Array,
    cur: jax.Array,
    cur_valid: jax.Array,
    k: int,
    key: jax.Array,
    axes,
    group_axis: str,
    via: str = "scatter",
) -> Tuple[jax.Array, jax.Array]:
    """`sharded_sample_layer_grouped` over the TILE shard layout: identical
    grouped machinery and ``via`` spellings (`_grouped_collective_sample`),
    tiled shard-local fetches."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def partial_fn(all_cur, all_valid):
        return _tiled_sample_layer_partial(
            bd_blk, tiles_blk, row_start, all_cur, all_valid, k, key, axes
        )

    return _grouped_collective_sample(
        partial_fn, cur, cur_valid, k, axes, group_axis, via
    )


def gather_comm_bytes(
    mesh: Mesh,
    width: int,
    dim: int,
    cold_budget: Optional[int] = None,
    feat_bytes: int = 4,
    id_bytes: int = 4,
    via: str = "scatter",
) -> Dict[str, float]:
    """Per-gather collective-byte model (ring costs, same conventions as
    `sampling_comm_bytes`) for ONE feature gather of ``width`` ids on a
    multi-host mesh — the number that makes the replicated-hot win
    quantitative: with ``cold_budget`` set (the `sharded_gather_hot_cold`
    layout) only the cold lanes ride the DCN leg, so DCN bytes scale by
    ``cold_budget / width`` ≈ the hot-tier miss rate.

    ``via`` mirrors `sharded_gather_grouped`: "scatter" (the default
    implementation — psum_scatter the [H, W, D] partials over host, then an
    ici psum at width W) or "psum" (round-3 full psum + slice: 2x the DCN
    row bytes and H x the ici width; see the SCALING.md round-4 table).
    """
    from .train import mesh_axes

    _, feat_axes, _ = mesh_axes(mesh)
    has_host = "host" in mesh.axis_names
    hostsz = mesh.shape["host"] if has_host else 1
    out = {"ici_bytes": 0.0, "dcn_bytes": 0.0}

    def add_psum(n_elems, axes):
        for a in axes:
            sz = mesh.shape[a]
            if sz == 1:
                continue
            b = 2.0 * (sz - 1) / sz * n_elems * feat_bytes
            out["dcn_bytes" if a == "host" else "ici_bytes"] += b

    def add_grouped_rows(w):
        """Return-trip bytes for a grouped gather of w rows per group."""
        if via == "scatter":
            # psum_scatter [H, w, D] over host + psum [w, D] over ici
            out["dcn_bytes"] += (hostsz - 1) / hostsz * hostsz * w * dim * feat_bytes
            add_psum(w * dim, ici_axes)
        else:
            add_psum(w * hostsz * dim, feat_axes)

    ici_axes = tuple(a for a in feat_axes if a != "host")
    if not has_host:
        add_psum(width * dim, feat_axes)
    elif cold_budget is None:
        # grouped: all_gather W ids over host, then the row return trip
        out["dcn_bytes"] += (hostsz - 1) / hostsz * width * hostsz * id_bytes
        add_grouped_rows(width)
    else:
        # hot: ICI-only psum at full width (per host)
        add_psum(width * dim, ici_axes)
        # cold: grouped path at the budgeted width
        out["dcn_bytes"] += (hostsz - 1) / hostsz * cold_budget * hostsz * id_bytes
        add_grouped_rows(cold_budget)
    out["total_bytes"] = out["ici_bytes"] + out["dcn_bytes"]
    return out


def sampling_comm_bytes(
    mesh: Mesh,
    sizes: Sequence[int],
    batch_per_group: int,
    feature_dim: int = 0,
    caps: Optional[Sequence[Optional[int]]] = None,
    id_bytes: int = 4,
    feat_bytes: int = 4,
    via: str = "scatter",
    layout: str = "flat",
) -> Dict[str, float]:
    """Static per-step collective-traffic model for the sharded-topology
    train step — the ICI/DCN byte accounting the multichip artifacts log.

    Counts, per training step and per chip, the bytes each collective moves
    over ICI (within a host) and DCN (the host axis), using the ring model
    (psum ≈ 2(P-1)/P × payload, all_gather ≈ (P-1)/P × gathered payload,
    psum_scatter ≈ (P-1)/P × payload; a multi-axis psum decomposes into a
    per-axis ring each paying its own (A-1)/A factor on the FULL payload,
    ICI legs first). Hop widths follow `pad_widths`; ``feature_dim > 0``
    adds the per-hop sharded feature-gather of the fused pipeline. ``via``
    selects the grouped return-trip spelling the step uses ("scatter" =
    the implementation default; "psum" = the round-3 spelling, kept for the
    SCALING.md comparison). This is a *model* — on real hardware XLA may
    pick other algorithms — but it makes relative layout costs comparable
    without a pod.

    ``layout`` ("flat" | "tiled", the `ShardedTopology` vs
    `TiledShardedTopology` shard formats) does NOT change the collective
    accounting — both layouts move the identical ``[W, k]`` neighbor/valid
    return and frontier all_gather — but it changes the shard-LOCAL HBM
    fetch shape, reported as two extra keys: ``hbm_descriptors`` (gather
    descriptors issued per chip per step: one per frontier row for the
    degree/base lookup plus one per drawn position) and ``hbm_fetch_bytes``
    (bytes those descriptors move: 128-lane tile rows under "tiled",
    single elements under "flat"). Descriptor COUNTS match between layouts;
    what differs is the bytes per descriptor and — the reason tiled wins —
    the issue RATE: TPU row gathers stream ~1.4-2.6x faster than element
    gathers (PERF.md (earlier claims); `scaling.sharded_fetch_table` applies the
    measured rates).
    """
    from .train import mesh_axes

    _, feat_axes, _ = mesh_axes(mesh)
    has_host = "host" in mesh.axis_names
    hostsz = mesh.shape["host"] if has_host else 1
    out: Dict[str, float] = {"ici_bytes": 0.0, "dcn_bytes": 0.0}
    widths = pad_widths(batch_per_group, sizes, caps)
    ici_axes = tuple(a for a in feat_axes if a != "host")

    def add_psum(n_elems: int, elem_bytes: int, axes=None):
        # per-axis rings over the striping axes; payload does not shrink
        for a in (feat_axes if axes is None else axes):
            sz = mesh.shape[a]
            if sz == 1:
                continue
            b = 2.0 * (sz - 1) / sz * n_elems * elem_bytes
            out["dcn_bytes" if a == "host" else "ici_bytes"] += b

    def add_all_gather_host(n_elems: int, elem_bytes: int):
        if hostsz > 1:
            out["dcn_bytes"] += (hostsz - 1) / hostsz * n_elems * hostsz * elem_bytes

    def add_grouped(per_group_elems: int, elem_bytes: int):
        """Return trip of a grouped collective, per_group_elems per group."""
        if not has_host or via == "psum":
            add_psum(per_group_elems * hostsz, elem_bytes)
        else:
            # psum_scatter [H, w] over host + psum [w] over ici
            out["dcn_bytes"] += (
                (hostsz - 1) / hostsz * hostsz * per_group_elems * elem_bytes
            )
            add_psum(per_group_elems, elem_bytes, axes=ici_axes)

    layout = resolve_topology_layout(layout)
    hbm_desc = 0.0
    hbm_fetch = 0.0
    for l, k in enumerate(sizes):
        if has_host:
            add_all_gather_host(widths[l], id_bytes + 1)  # frontier ids + valid
        add_grouped(widths[l] * k, id_bytes + 4)  # nbrs + int32 valid return
        if feature_dim:
            add_grouped(widths[l] * k * feature_dim, feat_bytes)
        # shard-local fetch: every chip resolves the all_gathered frontier
        w = widths[l] * hostsz
        hbm_desc += w + w * k  # degree/base lookup + k-split position fetch
        per_fetch = LANE * id_bytes if layout == "tiled" else id_bytes
        hbm_fetch += w * 8 + w * k * per_fetch
    if feature_dim:
        add_grouped(widths[0] * feature_dim, feat_bytes)  # seed rows
    out["hbm_descriptors"] = hbm_desc
    out["hbm_fetch_bytes"] = hbm_fetch
    out["total_bytes"] = out["ici_bytes"] + out["dcn_bytes"]
    return out
