"""Mesh collectives for sharded feature access.

TPU-native replacement for the reference's three transports (SURVEY.md 5):

- NVLink peer-pointer reads inside one kernel (shard_tensor.cu.hpp:44-55)
  -> `sharded_gather`: the hot feature table is row-sharded across an ICI
  mesh axis; every chip gathers its in-range rows and a `psum` over the axis
  assembles full rows. One collective rides ICI instead of per-row peer loads.
- NCCL send/recv pairwise exchange (quiver_comm.cu:38-64, comm.py:42-75)
  -> `all_to_all` based exchange in `quiver_tpu.comm` over a DCN axis.
- CUDA IPC handles -> nothing: one process drives all local chips.

Everything here runs *inside* ``shard_map`` — callers wrap with
`jax.experimental.shard_map.shard_map` (see `quiver_tpu.parallel.train`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import axis_size_compat


def sharded_gather(table_block: jax.Array, ids: jax.Array, axis_name) -> jax.Array:
    """Gather rows by *global* id from a row-sharded table.

    table_block: this chip's ``[rows_per_shard, D]`` contiguous block.
    ids: global row ids, any shape; identical across the axis (replicated).
    axis_name: one mesh axis name, or a TUPLE of names when the table is
    striped over several axes (e.g. ``("host", "ici")`` for a multi-host
    shard — matching a ``P(("host", "ici"), None)`` sharding, whose dim-0
    blocks are ordered major-to-minor across the named axes). The psum then
    rides ICI within a host and DCN across hosts.

    Returns full rows, replicated across the axis/axes. Out-of-range ids
    (e.g. padding sentinels) return zero rows.
    """
    if isinstance(axis_name, str):
        axes = (axis_name,)
    else:
        axes = tuple(axis_name)
    return lax.psum(_partial_rows(table_block, ids, axes), axes)


def _partial_rows(table_block: jax.Array, ids: jax.Array, axes) -> jax.Array:
    """This shard's un-reduced contribution to a row gather: its in-range
    rows, zeros elsewhere. Callers choose the reduction (psum, psum_scatter,
    or a scatter/psum mix). Shard index is flat major-to-minor over ``axes``
    — the block order of ``P((a, b), ...)``. int64 ids stay wide (>2^31-row
    global tables, x64 mode); everything else runs int32 (cheaper TPU
    gathers)."""
    rows_per_shard = table_block.shape[0]
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * axis_size_compat(a) + lax.axis_index(a)
    id_dt = ids.dtype if ids.dtype == jnp.int64 else jnp.int32
    local = ids.astype(id_dt) - idx.astype(id_dt) * rows_per_shard
    in_range = (local >= 0) & (local < rows_per_shard)
    rows = jnp.take(table_block, jnp.clip(local, 0, rows_per_shard - 1), axis=0)
    return jnp.where(in_range[..., None], rows, jnp.zeros_like(rows))


def sharded_gather_grouped(
    table_block: jax.Array, ids: jax.Array, feat_axes, group_axis: str,
) -> jax.Array:
    """`sharded_gather` for id lists that DIFFER across ``group_axis`` (one
    of the table's striping axes, typically "host").

    `sharded_gather` requires ids identical across every psum axis; when
    data-parallel groups span the host axis, each host samples different
    seeds, so the lists are first all_gathered over ``group_axis`` and
    gathered once for all groups. On the return trip the ``[G, W, D]``
    partial rows are `psum_scatter`ed over ``group_axis`` (each group
    receives only ITS slice, reduced on the way — ring cost (G-1)/G of the
    payload), then the ``[W, D]`` remainder is psummed over the other
    striping axes. DCN row-bytes: (G-1)*W*D.
    """
    if isinstance(feat_axes, str):
        axes = (feat_axes,)
    else:
        axes = tuple(feat_axes)
    if group_axis not in axes:
        # table not striped over the group axis: every group participant
        # holds identical partials, so a scatter-reduce would G-fold-count
        # them; a full psum + slice is the correct (and equally cheap, no
        # reduction rides group_axis at all) form there
        all_ids = lax.all_gather(ids, group_axis)
        rows = sharded_gather(table_block, all_ids, axes)
        return rows[lax.axis_index(group_axis)]
    all_ids = lax.all_gather(ids, group_axis)  # [G, ...]
    rows = _partial_rows(table_block, all_ids, axes)  # [G, W, D]
    own = lax.psum_scatter(rows, group_axis, scatter_dimension=0, tiled=False)
    other = tuple(a for a in axes if a != group_axis)
    if other:
        own = lax.psum(own, other)
    return own


def sharded_gather_a2a(
    table_block: jax.Array, ids: jax.Array, axis_name: str, axis_size: int
) -> jax.Array:
    """Per-chip-request gather: each chip requests only its own ``ids``
    (sharded over the axis) and receives only its own rows.

    ids: [B_local] this chip's request list (global ids).
    Returns [B_local, D]: rows for this chip's ids.

    This is exactly `sharded_gather_grouped` specialized to one axis that
    is both the striping and the group axis, so it DELEGATES there (one
    return-trip implementation; the reference's id/feature exchange
    pattern, comm.py:127-182, collapsed into two XLA collectives).

    When to use which (measured compiled-HLO payloads at W=512, D=32,
    P=8 — SCALING.md round-5 table): with a SHARDED consumer, a2a moves 10240 B/chip
    (2048 request all-gather + 8192 reduce-scatter) vs the
    replicated-request `sharded_gather`'s 65536 B all-reduce — 6.4x
    cheaper. But if the consumer needs the FULL row set (every train step
    in this library does: the model eats all of x), the re-assembly
    all_gather brings it to 75776 B — WORSE than the all-reduce — so the
    train steps stay on `sharded_gather`/`sharded_gather_grouped`. a2a is
    the right spelling only when downstream consumption is sharded over
    the same axis (e.g. an embedding-table exchange feeding per-chip
    partitions).
    """
    return sharded_gather_grouped(
        table_block, ids, feat_axes=axis_name, group_axis=axis_name
    )


def sharded_gather_hot_cold(
    hot_block: jax.Array,
    cold_block: jax.Array,
    ids: jax.Array,
    feat_axes,
    group_axis: str,
    hot_rows: int,
    cold_budget: int,
):
    """Grouped gather with a per-host REPLICATED hot prefix — the in-jit
    analog of the reference's `PartitionInfo.replicate` hot set
    (feature.py:461-526; mag240m preprocess.py:117-179 replicates the hot
    rows on every host for exactly this reason).

    The plain `sharded_gather_grouped` pays ``axis_size(group_axis)`` x the
    full gather width over the DCN axis for EVERY row. Here the table is
    heat-ordered (reindex_by_config / Feature degree order) and split:

    - rows ``< hot_rows``: replicated per host, striped over the non-group
      axes — served by an ICI-only psum at full width;
    - rows ``>= hot_rows``: striped over ALL ``feat_axes`` — the cold ids
      are compacted (one cheap sort) into a static ``cold_budget``-lane
      buffer and only THAT rides the grouped DCN path.

    DCN row-volume drops from W to ``cold_budget`` — i.e. by the hot-tier
    hit rate; calibrate the budget like the sampler caps (observed max cold
    count x margin, `pyg.sage_sampler.caps_from_counts` policy). Returns
    ``(rows [W, D], overflow)`` where ``overflow`` counts cold ids beyond
    the budget this call (their rows come back ZERO — monitor it; a
    persistent nonzero overflow means the budget needs recalibrating).

    Inside shard_map only. ``ids`` identical across every non-group feat
    axis; may differ across ``group_axis``.
    """
    ici_axes = tuple(a for a in feat_axes if a != group_axis)
    if not ici_axes:
        raise ValueError("hot/cold gather needs a non-group striping axis")
    # same int64 treatment as sharded_gather/_a2a: this is the layout built
    # for the LARGEST tables, so >2^31-row global id spaces must not wrap
    ids = ids.astype(ids.dtype if ids.dtype == jnp.int64 else jnp.int32)
    w = ids.shape[0]
    if isinstance(cold_budget, float):
        # fraction of the gather width (handy when one policy must serve
        # calls of several static widths, e.g. the fused per-hop gathers);
        # 256-lane granule, never above the width itself
        cold_budget = min(w, -(-int(w * cold_budget) // 256) * 256)
    if cold_budget > w:
        raise ValueError(f"cold_budget {cold_budget} exceeds gather width {w}")
    # hot side: ids >= hot_rows fall out of the hot shards' range -> zeros
    # (hot padding rows are zero, so cold ids landing in [hot_rows, padded)
    # contribute nothing either)
    hot_part = sharded_gather(hot_block, ids, ici_axes)
    # cold side: compact the cold ids to the front (argsort of the hot flag
    # is stable and costs ~0.5 ms/M lanes — sorts are the cheap primitive,
    # PERF.md (earlier claims)), slice the static budget, gather grouped, scatter back.
    # Out-of-range ids (padding sentinels: reindex pads with intmax) are
    # NEITHER hot nor cold — they must not consume budget lanes
    n_cold_global = cold_block.shape[0]
    for a in feat_axes:
        n_cold_global = n_cold_global * axis_size_compat(a)
    is_cold = (ids >= hot_rows) & (ids < hot_rows + n_cold_global)
    n_cold = is_cold.sum().astype(jnp.int32)
    order = jnp.argsort(jnp.where(is_cold, 0, 1), stable=True)
    sel = order[:cold_budget]
    lane_ok = jnp.arange(cold_budget, dtype=jnp.int32) < n_cold
    cold_local = jnp.where(lane_ok, jnp.take(ids, sel) - hot_rows, -1)
    cold_rows = sharded_gather_grouped(cold_block, cold_local, feat_axes, group_axis)
    cold_rows = jnp.where(lane_ok[:, None], cold_rows, jnp.zeros_like(cold_rows))
    out = hot_part.at[sel].add(cold_rows, mode="drop")
    overflow = jnp.maximum(n_cold - cold_budget, 0)
    return out, overflow


def replicated_psum(x, axis_name: str):
    return lax.psum(x, axis_name)


def pad_to_multiple(arr, multiple: int, axis: int = 0):
    """Pad rows so a table splits evenly across shards (host-side helper)."""
    import numpy as np

    n = arr.shape[axis]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return np.asarray(arr)
    pad_width = [(0, 0)] * arr.ndim
    pad_width[axis] = (0, target - n)
    return np.pad(np.asarray(arr), pad_width)


def place_shards(mesh, axes, shapes, make):
    """Arrays of ``shapes``, each split in dim 0 over the mesh axes
    ``axes`` (replicated over the others), built and uploaded SHARD BY
    SHARD: ``make(p)`` returns the host blocks of shard ``p`` (one per
    array, dim 0 a ``1/P`` share of the array's) and they go straight to
    the devices that hold shard ``p``. No device ever holds more than its
    own blocks and the host never holds a copy of the whole: one shard's
    blocks at a time (on four v5e chips a 28 GB table went up in 30-33 s
    from four threads and 30-32 s from this loop; four flat topology blocks
    took 5-6 s from four threads and 8 s from this loop: 3 s of a 70 s
    set-up did not pay for a pool,
    PERF.md). Returns one `jax.Array` per shape.
    """
    import math

    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    shapes = [tuple(int(d) for d in s) for s in shapes]
    shardings = [
        NamedSharding(mesh, P(axes, *([None] * (len(s) - 1)))) for s in shapes
    ]
    parts = math.prod(mesh.shape[a] for a in axes)
    if any(s[0] % parts for s in shapes):
        raise ValueError(f"dim 0 of {shapes} does not split {parts} ways")
    share = shapes[0][0] // parts
    holders = {}
    index_map = shardings[0].addressable_devices_indices_map(shapes[0])
    for dev, index in index_map.items():
        holders.setdefault((index[0].start or 0) // max(share, 1), []).append(dev)
    placed = [[] for _ in shapes]
    for p in sorted(holders):
        for i, blk in enumerate(make(p)):
            placed[i] += [jax.device_put(blk, dev) for dev in holders[p]]
    return tuple(
        jax.make_array_from_single_device_arrays(s, sh, arrs)
        for s, sh, arrs in zip(shapes, shardings, placed)
    )
