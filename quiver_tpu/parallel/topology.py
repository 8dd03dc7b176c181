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

Each shard keeps its contiguous CSR block as a local window table + flat
indices array (`ShardedTopology`; bytes follow the EDGES: 4 B an edge + 8 B a
node). A frontier row's (first edge, degree) is one row of the table, built on
the host and placed with the block (`ops.sample.row_windows`), and a drawn
position is read from the indices seen as 128-lane rows
(`ops.sample.flat_resolve`): a 2-D ROW gather + one-hot lane select
(`ops.sample._select_lanes`), the fetch the single-chip tile sampler uses too,
so the same key draws the same neighbours as on one device.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import axis_size_compat
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.sample import (
    LANE,
    fisher_yates_positions,
    flat_resolve,
    flat_windows_host,
    pad_widths,
    row_windows,
)


class ShardedTopology(NamedTuple):
    """Device-resident row-sharded CSR (see `shard_topology_rows`).

    ``windows`` [P, R_max, 2] — per-shard (first edge, degree) of every
                local row, offsets into the shard's own indices block
                (`ops.sample.flat_windows_host`); padding rows have degree 0;
    ``indices`` [P, E_pad]   — per-shard neighbor block, zero-padded;
    ``row_start`` [P+1]      — global row boundaries (replicated; shard p
                owns rows ``row_start[p]:row_start[p+1]``).
    """

    windows: jax.Array
    indices: jax.Array
    row_start: jax.Array

    @property
    def n_shards(self) -> int:
        return self.windows.shape[0]

    def specs(self, feat_axes) -> "ShardedTopology":
        """shard_map in_specs pytree for this topology striped over
        ``feat_axes`` (row_start is replicated)."""
        return topology_specs(feat_axes)


def topology_specs(feat_axes) -> "ShardedTopology":
    """The ONE place the ShardedTopology shard_map spec layout lives: CSR
    blocks striped over ``feat_axes``, row boundaries replicated."""
    return ShardedTopology(
        windows=P(feat_axes, None, None), indices=P(feat_axes, None), row_start=P()
    )


def _check_layout(layout: Optional[str]) -> None:
    """The ``layout`` keyword of `shard_topology_rows` and the two step
    factories: there is one layout, so ``None`` and "flat" pass."""
    if layout not in (None, "flat"):
        raise ValueError(f"unsupported topology layout: {layout!r}")


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
    r_max = _padded(r_max, 8 * LANE)
    e_pad = max(
        (int(indptr[row_start[p + 1]] - indptr[row_start[p]])
         for p in range(n_shards)),
        default=0,
    )
    return row_start, r_max, _padded(e_pad, pad_multiple)


def _flat_block(indptr, indices, row_start, p: int, r_max: int, e_pad: int,
                id_dtype) -> Tuple[np.ndarray, np.ndarray]:
    """Shard ``p``'s (local windows [r_max, 2], indices [e_pad]) alone."""
    lo, hi = int(row_start[p]), int(row_start[p + 1])
    ptr_dt = np.int32 if e_pad < 2**31 else np.int64
    # rows past this shard's range read as degree 0
    windows = flat_windows_host(indptr[lo : hi + 1] - indptr[lo], ptr_dt, rows=r_max)
    idx = np.zeros(e_pad, id_dtype)
    blk = indices[int(indptr[lo]) : int(indptr[hi])]
    idx[: blk.shape[0]] = blk
    return windows, idx


def _row_start_dtype(row_start: np.ndarray):
    return np.int32 if int(row_start[-1]) < 2**31 else np.int64


def build_topology_shards(
    indptr: np.ndarray,
    indices: np.ndarray,
    n_shards: int,
    pad_multiple: int = 512,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side shard construction: (window_blocks, indices_blocks,
    row_start) as stacked numpy arrays (see `ShardedTopology`). The stack is
    a second copy of the graph: `shard_topology_rows` places block by block
    and never builds it."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    row_start, r_max, e_pad = _flat_plan(indptr, n_shards, pad_multiple)
    blocks = [_flat_block(indptr, indices, row_start, p, r_max, e_pad, indices.dtype)
              for p in range(n_shards)]
    return (
        np.stack([b[0] for b in blocks]),
        np.stack([b[1] for b in blocks]),
        row_start.astype(_row_start_dtype(row_start)),
    )


def shard_topology_rows(
    mesh: Mesh,
    topo,
    axes: Optional[Tuple[str, ...]] = None,
    layout: Optional[str] = None,
) -> ShardedTopology:
    """Place a `CSRTopo` row-sharded over the mesh's feature axes.

    Each device ends up holding ONLY its contiguous CSR block (~E/P edges;
    edge-balanced), so total graph capacity scales with chip count — the
    papers100M axis the reference serves with UVA (quiver_sample.cu:361-421).
    Blocks are built and uploaded shard by shard (`collectives.place_shards`):
    no device ever holds more than its block, and the host one block at a
    time, never the stack.

    ``axes`` defaults to the mesh's feature axes ((host, ici) on a 3-axis
    mesh, else (ici,)); the blocks are replicated over the remaining axes.

    ``layout`` chooses nothing (`_check_layout`): the benchmark's four-chip
    cell still passes it by keyword (ROADMAP D13 deletes it after that).
    """
    from ..trace import trace_scope
    from ..utils import _best_id_dtype
    from .collectives import place_shards
    from .train import mesh_axes

    _check_layout(layout)
    indptr = np.asarray(topo.indptr)
    indices = np.asarray(topo.indices)
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
    row_start, r_max, e_pad = _flat_plan(indptr, n_shards, 512)
    shapes = ((n_shards, r_max, 2), (n_shards, e_pad))
    chip_bytes = (2 * r_max + e_pad) * 4
    with trace_scope("quiver.shard.topology", chip_bytes=chip_bytes) as span:
        win, idx = place_shards(
            mesh, axes, shapes,
            lambda p: tuple(
                b[None] for b in _flat_block(indptr, indices, row_start, p,
                                             r_max, e_pad, id_dtype)
            ),
        )
        rs = jax.device_put(
            row_start.astype(_row_start_dtype(row_start)),
            NamedSharding(mesh, P()),
        )
        span.sync = (win, idx, rs)
    return ShardedTopology(win, idx, rs)


def _flat_axis_index(axes: Tuple[str, ...]):
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * axis_size_compat(a) + lax.axis_index(a)
    return idx


def _psum_assemble(nbrs, valid, axes):
    """Owner-exclusive full assembly: shard contributions are zeros off
    the owner, so a psum over the striping axes IS the gather."""
    return lax.psum(nbrs, axes), lax.psum(valid, axes) > 0


def _grouped_collective_sample(partial_fn, cur, cur_valid, k, axes, group_axis):
    """The grouped sample: all_gather the per-group frontiers over
    ``group_axis``, draw once through ``partial_fn(all_cur, all_valid) -> (nbrs,
    valid_int32)`` (the un-reduced shard contribution at the gathered width),
    then return each group its own ``[W, k]`` slice: psum_scatter the
    ``[G, W, k]`` partials over the group axis (ring cost (G-1)/G) and psum
    the remaining striping axes at width W."""
    h = axis_size_compat(group_axis)
    w = cur.shape[0]
    all_cur = lax.all_gather(cur, group_axis).reshape(-1)
    all_valid = lax.all_gather(cur_valid, group_axis).reshape(-1)
    if group_axis not in axes:
        # blocks not striped over the group axis: its participants hold
        # identical partials, which a scatter-reduce would count G-fold
        nbrs, valid = _psum_assemble(*partial_fn(all_cur, all_valid), axes)
        me = lax.axis_index(group_axis)
        return nbrs.reshape(h, w, k)[me], valid.reshape(h, w, k)[me]
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
    windows_blk: jax.Array,
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
        windows_blk, indices_blk, row_start, cur, cur_valid, k, key, axes
    )
    return _psum_assemble(nbrs, valid, axes)


def _sample_layer_partial(
    windows_blk, indices_blk, row_start, cur, cur_valid, k, key, axes
):
    """This shard's un-reduced contribution to a one-hop sample: neighbors
    for the frontier rows it owns, zeros elsewhere. Callers choose the
    reduction (full psum, or scatter-over-group then psum)."""
    idx = _flat_axis_index(axes)
    start = jnp.take(row_start, idx)
    end = jnp.take(row_start, idx + 1)
    local = (cur - start).astype(jnp.int32)
    mine = cur_valid & (cur >= start) & (cur < end)
    ptr, deg = row_windows(windows_blk, local, mine)
    pos, valid = fisher_yates_positions(key, deg, k)
    nbrs = flat_resolve(indices_blk, ptr, pos, k)
    nbrs = jnp.where(valid, nbrs, 0)
    return nbrs, valid.astype(jnp.int32)


def sharded_sample_layer_grouped(
    windows_blk: jax.Array,
    indices_blk: jax.Array,
    row_start: jax.Array,
    cur: jax.Array,
    cur_valid: jax.Array,
    k: int,
    key: jax.Array,
    axes,
    group_axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """`sharded_sample_layer` for frontiers that DIFFER across ``group_axis``
    (one of the striping axes, typically "host" — data-parallel groups span
    it, so each host's frontier is distinct): `_grouped_collective_sample`.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def partial_fn(all_cur, all_valid):
        return _sample_layer_partial(
            windows_blk, indices_blk, row_start, all_cur, all_valid, k, key, axes
        )

    return _grouped_collective_sample(
        partial_fn, cur, cur_valid, k, axes, group_axis
    )


def gather_comm_bytes(
    mesh: Mesh,
    width: int,
    dim: int,
    cold_budget: Optional[int] = None,
    feat_bytes: int = 4,
    id_bytes: int = 4,
) -> Dict[str, float]:
    """Per-gather collective-byte model (ring costs, same conventions as
    `sampling_comm_bytes`) for ONE feature gather of ``width`` ids on a
    multi-host mesh — the number that makes the replicated-hot win
    quantitative: with ``cold_budget`` set (the `sharded_gather_hot_cold`
    layout) only the cold lanes ride the DCN leg, so DCN bytes scale by
    ``cold_budget / width`` ≈ the hot-tier miss rate. The grouped return
    trip is `sharded_gather_grouped`'s: psum_scatter the [H, W, D] partials
    over host, then an ici psum at width W.
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
        """Return-trip bytes for a grouped gather of w rows per group:
        psum_scatter [H, w, D] over host + psum [w, D] over ici."""
        out["dcn_bytes"] += (hostsz - 1) / hostsz * hostsz * w * dim * feat_bytes
        add_psum(w * dim, ici_axes)

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
) -> Dict[str, float]:
    """Static per-step collective-traffic model for the sharded-topology
    train step — the ICI/DCN byte accounting the multichip artifacts log
    (keys ``ici_bytes``, ``dcn_bytes``, ``total_bytes``).

    Counts, per training step and per chip, the bytes each collective moves
    over ICI (within a host) and DCN (the host axis), using the ring model
    (psum ≈ 2(P-1)/P × payload, all_gather ≈ (P-1)/P × gathered payload,
    psum_scatter ≈ (P-1)/P × payload; a multi-axis psum decomposes into a
    per-axis ring each paying its own (A-1)/A factor on the FULL payload,
    ICI legs first). Hop widths follow `pad_widths`; ``feature_dim > 0``
    adds the per-hop sharded feature-gather of the fused pipeline. This is
    a *model* — on real hardware XLA may pick other algorithms — but it
    makes relative mesh costs comparable without a pod.
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
        if not has_host:
            add_psum(per_group_elems, elem_bytes)
        else:
            # psum_scatter [H, w] over host + psum [w] over ici
            out["dcn_bytes"] += (
                (hostsz - 1) / hostsz * hostsz * per_group_elems * elem_bytes
            )
            add_psum(per_group_elems, elem_bytes, axes=ici_axes)

    for l, k in enumerate(sizes):
        if has_host:
            add_all_gather_host(widths[l], id_bytes + 1)  # frontier ids + valid
        add_grouped(widths[l] * k, id_bytes + 4)  # nbrs + int32 valid return
        if feature_dim:
            add_grouped(widths[l] * k * feature_dim, feat_bytes)
    if feature_dim:
        add_grouped(widths[0] * feature_dim, feat_bytes)  # seed rows
    out["total_bytes"] = out["ici_bytes"] + out["dcn_bytes"]
    return out
