"""Device-side k-hop neighbor sampling, XLA/TPU-native.

Re-design of the reference's CUDA sampling pipeline
(``srcs/cpp/src/quiver/cuda/quiver_sample.cu:134-200`` sample_kernel and the
warp-per-row reservoir kernel ``include/quiver/cuda_random.cu.hpp:7-69``).

The reference pipeline is ragged: per-seed degree pass -> cap -> exclusive scan
-> ragged output buffer. XLA demands static shapes, so the TPU design returns a
dense padded ``[B, k]`` neighbor matrix plus a validity mask:

- ``deg <= k``  -> copy-all (positions ``0..deg-1`` valid), matching the
  copy-all branch of the reference kernel (cuda_random.cu.hpp:33-38);
- ``deg > k``   -> an exact uniform k-subset without replacement, matching the
  reservoir-sampling branch (cuda_random.cu.hpp:40-60) in distribution.

The without-replacement draw uses a vectorised *partial Fisher-Yates* over a
virtual ``arange(deg)`` permutation: slot values below ``k`` live in a dense
``head`` array, swaps landing at ``j >= k`` are recorded in a k-entry override
table (at most one new override per step). This is O(k^2) vector work per row
(k <= 32 in practice) with fully static shapes — no per-row data-dependent
control flow, so the whole thing fuses into a handful of XLA ops.

All functions are jittable; the padded output feeds the dense reindex pass
(`quiver_tpu.ops.reindex`) and the padded-[B,k] GraphSAGE aggregation
(`quiver_tpu.models.sage`), which turns sparse segment ops into dense
reshape+mean — the TPU-friendly formulation (SURVEY.md section 7.1).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

LANE = 128  # native int32 lane width — the tile-layout row size


def pad_widths(batch: int, sizes, caps=None):
    """Static padded n_id widths per hop: ``W_{l+1} = min(cap_l, W_l*(1+k_l))``.

    Single source of truth for the shape contract shared by the device
    pipeline (`quiver_tpu.pyg.sage_sampler.sample_dense_pure`) and the host
    engine (`quiver_tpu.ops.cpu_kernels.HostSampler.sample_multilayer`) —
    their outputs must be bit-identical in shape/masking.
    """
    widths = [int(batch)]
    for l, k in enumerate(sizes):
        w = widths[-1] * (1 + int(k))
        if caps is not None and caps[l] is not None:
            w = min(w, int(caps[l]))
        widths.append(w)
    return widths


def row_windows(
    table: jax.Array, seeds: jax.Array, seed_valid: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """``(base, degree)`` of every seed, the degree 0 where ``~seed_valid``:
    ONE ``[W, 2]`` row gather from the ``[N, 2]`` table that is PLACED with
    the graph. The tile layout's ``bd`` holds (first tile row, degree)
    (`CSRTopo.to_device_tiled`), the flat layout's windows (first edge,
    degree) (`CSRTopo.to_device_lane_rows`; a shard's block of it,
    `parallel.topology.shard_topology_rows`): both are built once, on the
    host, and reach every program as an argument. TPU gathers are
    descriptor-rate bound and width-invariant up to ~128 lanes (PERF.md
    section 6), so the pair costs one descriptor a seed where two element
    gathers from ``indptr`` cost two. A 1-D ``indptr`` is stacked into that
    table INSIDE the program, in every launch (6.2 ms of ``[N]``-sized
    operations at 55.5M nodes: PERF.md section 6, PR 33): fine at a test's
    size and for the weighted flat sampler, and the reason the library
    places a big graph's table. The ONE lookup of every sampler; seeds are
    clipped to the table (garbage is allowed where ``~seed_valid``)."""
    if table.ndim == 1:
        table = jnp.stack([table[:-1], table[1:] - table[:-1]], axis=1)
    s = jnp.clip(seeds, 0, table.shape[0] - 1).astype(table.dtype)
    both = jnp.take(table, s, axis=0)
    return both[:, 0], jnp.where(seed_valid, both[:, 1], 0).astype(jnp.int32)


def fisher_yates_positions(key: jax.Array, deg: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Draw, for each row ``b``, ``min(deg[b], k)`` distinct positions in
    ``[0, deg[b])``.

    Returns ``(pos, valid)`` with ``pos`` int32 ``[B, k]`` and ``valid`` bool
    ``[B, k]``. For rows with ``deg <= k`` positions are ``0..deg-1`` in order
    (copy-all semantics). For ``deg > k`` positions are an exact uniform
    k-subset, in random order.
    """
    deg = deg.astype(jnp.int32)
    B = deg.shape[0]
    ar_k = jnp.arange(k, dtype=jnp.int32)

    if k == 0:
        return (jnp.zeros((B, 0), jnp.int32), jnp.zeros((B, 0), bool))

    us = jax.random.uniform(key, (k, B))

    def step(state, inp):
        head, tail_j, tail_v, cnt = state
        i, u = inp
        span = jnp.maximum(deg - i, 1)
        j = i + (u * span.astype(u.dtype)).astype(jnp.int32)
        j = jnp.minimum(j, jnp.maximum(deg - 1, 0))
        in_head = j < k
        # one-hot select, NOT take_along_axis: a per-row dynamic lane read
        # lowers to a B-descriptor gather per scan step (~5 ms/hop at
        # products hop-3 shape — measured);
        # the one-hot compare+sum is pure VPU work
        head_val = jnp.where(ar_k[None, :] == j[:, None], head, 0).sum(axis=1)
        match = tail_j == j[:, None]  # [B, k]
        has_match = match.any(axis=1)
        tail_val = jnp.where(has_match, jnp.where(match, tail_v, 0).sum(axis=1), j)
        val_j = jnp.where(in_head, head_val, tail_val)
        val_i = head[:, i]
        # a[j] = a[i]
        onehot_j = (ar_k[None, :] == j[:, None]) & in_head[:, None]
        head = jnp.where(onehot_j, val_i[:, None], head)
        # a[i] = a[j] (slot i is never drawn again but keep the permutation honest)
        head = head.at[:, i].set(val_j)
        slot = jnp.where(has_match, jnp.argmax(match, axis=1).astype(jnp.int32), cnt)
        write_tail = ~in_head
        onehot_s = (ar_k[None, :] == slot[:, None]) & write_tail[:, None]
        tail_j = jnp.where(onehot_s, j[:, None], tail_j)
        tail_v = jnp.where(onehot_s, val_i[:, None], tail_v)
        cnt = cnt + (write_tail & ~has_match).astype(jnp.int32)
        return (head, tail_j, tail_v, cnt), val_j

    init = (
        jnp.broadcast_to(ar_k, (B, k)),
        jnp.full((B, k), -1, jnp.int32),
        jnp.zeros((B, k), jnp.int32),
        jnp.zeros((B,), jnp.int32),
    )
    _, outs = lax.scan(step, init, (ar_k, us))
    pos = outs.T  # [B, k]
    # copy-all override for low-degree rows (reference cuda_random.cu.hpp:33-38)
    pos = jnp.where(deg[:, None] <= k, ar_k[None, :], pos)
    valid = ar_k[None, :] < jnp.minimum(deg, k)[:, None]
    return pos, valid


def gumbel_topk_positions(
    key: jax.Array, deg: jax.Array, k: int, weight_rows: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Weighted without-replacement k-subset per row via Gumbel top-k.

    The XLA formulation of the reference's ``weight_sample`` kernel
    (cuda_random.cu.hpp:177-221): drawing k items without replacement with
    probability proportional to weights (successive/Plackett-Luce sampling)
    is exactly taking the top-k of ``log w_i + Gumbel(0,1)`` — no sequential
    draw loop, one sort-free `lax.top_k`.

    weight_rows: ``[B, W]`` per-row candidate weights (garbage beyond
    ``deg[b]`` is masked). Rows with ``deg <= k`` return all their
    candidates (copy-all, like the uniform sampler). Returns ``(pos, valid)``
    with positions into ``[0, W)``.
    """
    B, W = weight_rows.shape
    if k == 0:
        return (jnp.zeros((B, 0), jnp.int32), jnp.zeros((B, 0), bool))
    u = jax.random.uniform(key, (B, W), minval=1e-20, maxval=1.0)
    g = -jnp.log(-jnp.log(u))
    w = jnp.maximum(weight_rows.astype(jnp.float32), 0.0)
    scores = jnp.where(
        (jnp.arange(W, dtype=jnp.int32)[None, :] < deg[:, None]) & (w > 0),
        jnp.log(jnp.maximum(w, 1e-30)) + g,
        -jnp.inf,
    )
    vals, pos = lax.top_k(scores, k)
    n_valid = jnp.minimum(deg, k)
    # zero-weight candidates are never valid draws; count only finite
    # scores — read off top_k's OWN values (a take_along_axis here would
    # lower to a B*k-descriptor gather; the values are already in hand)
    finite = vals > -jnp.inf
    valid = (jnp.arange(k, dtype=jnp.int32)[None, :] < n_valid[:, None]) & finite
    return pos.astype(jnp.int32), valid


@functools.partial(jax.jit, static_argnames=("k", "max_deg"))
def weighted_sample_layer(
    indptr: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
    max_deg: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """One-hop WEIGHTED neighbor sample (reference quiver.cu.hpp:61-82
    bucketed weights + cuda_random.cu.hpp:177-221 weight_sample).

    ``weights`` [E] edge weights aligned with ``indices``. The weighted
    flat sampler keeps the 1-D ``indptr`` (`CSRTopo.to_device`), so this
    program stacks its window table in every launch (`row_windows`); no
    cell runs it. Static-shape
    tradeoff: each row considers its first ``min(deg, max_deg)`` neighbors
    (one ``[B, max_deg]`` lane window instead of the reference's dynamic
    bucket machinery) — set ``max_deg`` >= the graph's max degree for exact
    semantics; heavier-degree tails are truncated and a row's sample then
    comes from its first ``max_deg`` edges.
    """
    ptr, deg = row_windows(indptr, seeds, seed_valid)
    deg = jnp.minimum(deg, max_deg)
    lanes = ptr[:, None] + jnp.arange(max_deg, dtype=ptr.dtype)[None, :]
    lanes = jnp.clip(lanes, 0, indices.shape[0] - 1)
    w_rows = jnp.take(weights, lanes)
    pos, valid = gumbel_topk_positions(key, deg, k, w_rows)
    # NOT take_along_axis (a [B, k] per-row dynamic lane read lowers to a
    # B*k-descriptor gather — the round-5 trap, PERF.md (earlier claims) grep rule) and
    # not even the one-hot compare+sum: the lane window is AFFINE in the
    # drawn position (lanes[b, p] == clip(ptr[b] + p)), so the select is
    # plain address arithmetic — zero descriptors, bit-identical flat ids
    flat = jnp.clip(
        ptr[:, None] + pos.astype(ptr.dtype),
        0,
        jnp.asarray(indices.shape[0] - 1, ptr.dtype),
    )
    nbrs = jnp.take(indices, flat)
    return nbrs, valid


def _select_lanes(tiles, rows, lane, k):
    """``tiles[rows[b, j], lane[b, j]]`` (rows in range) as k-split row gathers + one-hot
    lane selects (k separate [B]-row gathers measured faster than one
    [B*k]: 6.2 vs 7.1 ms; one-hot instead of take_along_axis — the
    descriptor trap). One ``[B, 128]`` row fetched per DRAWN POSITION: k
    descriptors a seed, each moving 512 B to deliver one int32 (9.3 ns a
    row; PERF.md section 6). The flat layer (`flat_resolve`) and the tile
    layout's k-fetch (`_tiled_k_fetch`: its far seeds, its small hops and
    its fallback) ride it, so the k-split pattern is tuned in one place."""
    ar = jnp.arange(LANE, dtype=jnp.int32)
    cols = []
    for j in range(k):
        win = jnp.take(tiles, rows[:, j], axis=0)
        oh = lane[:, j][:, None] == ar[None, :]
        cols.append(jnp.where(oh, win, 0).sum(axis=1))
    return jnp.stack(cols, axis=1).astype(tiles.dtype)


FAR_SHARE = 8     # the far list holds B // 8 seeds
FAR_MIN = 1024    # hops whose far list would be shorter keep the k-fetch


def far_width(batch: int, k: int) -> int:
    """Static width ``H`` of `_tiled_resolve`'s compacted list of far seeds
    at a hop of ``batch`` seeds and fan-out ``k``; 0 where the hop keeps
    the k-fetch. A function of the hop's shape alone: ``H = B // 8`` where
    ``k > 2`` (one fetch a seed costs ``2B + (k + 1)H`` descriptors against
    ``kB``: nothing to win at ``k <= 2``) and ``H >= 1024``, i.e.
    ``B >= 8192``. Engaged: igb's hops 1 and 2 (10240 x 10, 73728 x 15),
    products' hops 2 and 3 (16384 x 10, 180224 x 5); never a serve bucket
    (``B <= 704``), whose programs stay the k-fetch's text for text.
    Readings that put the line there (ms a hop on a v5e over igb's
    frontier, k-fetch -> one fetch; PERF.md section 6, PR 35): 73728 seeds
    10.79 -> 3.68 at k=15, 3.62 -> 1.94 at k=5, 2.19 -> 1.58 at k=3;
    8192 seeds 1.24 -> 0.48 at k=15, 0.44 -> 0.26 at k=5; 4096 seeds
    0.64 -> 0.24 at k=15; at 2048 and below both read the 0.2 ms of a
    launch. The line could sit at 4096: no cell has a hop between 1024
    and 8192 seeds to show it end to end."""
    far = batch // FAR_SHARE
    return far if k > 2 and far >= FAR_MIN else 0


def _tiled_k_fetch(tiles, base, pos, k):
    """Position ``p`` of a node sits at tile row ``base + p // 128``, lane
    ``p % 128``: every drawn position through a row fetch of its own
    (`_select_lanes`)."""
    rows = base[:, None] + lax.shift_right_logical(pos, LANE.bit_length() - 1)
    rows = jnp.clip(rows, 0, tiles.shape[0] - 1)
    return _select_lanes(tiles, rows, jnp.bitwise_and(pos, LANE - 1), k)


def _tiled_one_fetch(tiles, base, pos, k, far, width):
    """The same ids as `_tiled_k_fetch` where at most ``width`` seeds are
    ``far`` (some drawn position past their first tile row): ONE
    ``[B, 128]`` fetch of every seed's first row, from which all its draws
    below 128 are one-hot lane selects, and the far seeds, compacted by a
    sort of ``[B]`` keys into a ``[width]`` list, through the k-fetch; a
    far seed reads its ``k`` ids back by rank (one ``[B]``-row gather of
    k-int rows)."""
    B = base.shape[0]
    row0 = jnp.take(tiles, jnp.clip(base, 0, tiles.shape[0] - 1), axis=0)
    lane = jnp.bitwise_and(pos, LANE - 1)
    ar = jnp.arange(LANE, dtype=jnp.int32)
    near = jnp.stack(
        [jnp.where(lane[:, j][:, None] == ar[None, :], row0, 0).sum(axis=1)
         for j in range(k)], axis=1).astype(tiles.dtype)
    idx = jnp.arange(B, dtype=jnp.int32)
    # a sort, not a scatter: this chip sorts 1.18M keys in 2.2 ms and
    # scatters at 45 ns a row (PERF.md section 6); slots past the far
    # seeds hold B, clipped to a seed whose ids nobody reads back
    slots = jnp.minimum(jnp.sort(jnp.where(far, idx, B))[:width], B - 1)
    packed = jnp.concatenate([base[:, None], pos], axis=1)  # int32, as `bd` is
    picked = jnp.take(packed, slots, axis=0)  # [width, 1 + k]: one descriptor a slot
    far_ids = _tiled_k_fetch(tiles, picked[:, 0], picked[:, 1:], k)
    rank = jnp.clip(jnp.cumsum(far.astype(jnp.int32)) - 1, 0, width - 1)
    return jnp.where(far[:, None], jnp.take(far_ids, rank, axis=0), near)


def _tiled_resolve(tiles, base, pos, k):
    """Resolve drawn positions ``pos [B, k]`` to neighbour ids through the
    tile table: ``tiles[base + pos // 128, pos % 128]``, bit for bit,
    whatever the graph. Returns ``(ids [B, k], one_fetch)``, the second an
    int32 scalar: 1 where the hop went through one fetch a seed. Shared by
    the uniform, weighted and temporal tiled layers (and `stream.py`'s
    relocated rows, through ``base``): the ONE position fetch of the tile
    layout.

    A node's list starts at a tile-row boundary (`build_tiled_host`), so
    every draw of a node of degree <= 128 reads the SAME row: the k-fetch
    moves that row k times. Where `far_width` engages the hop, seeds are
    split by what was drawn, not by a degree table: a seed is *far* when
    some position of its row of ``pos`` is >= 128 (only a seed of degree
    > 128 can be; a masked draw of the weighted layers may be, and is then
    fetched like any other, so masked lanes keep the k-fetch's bits too).
    If the hop holds at most ``H = far_width(B, k)`` far seeds it takes
    `_tiled_one_fetch`, else `_tiled_k_fetch`: both under one `lax.cond`
    on the device-side count, no host read, no cap that can overflow.
    Readings (v5e; PERF.md section 6, PR 35): igb's second hop (73,728
    seeds x 15; 5,326 nodes of 1M have a degree over 128, ~3,100 seeds of
    a frontier are far) 15 gathers of ``[73728, 128]`` at 0.686 ms each ->
    one, plus a ``[9216]``-wide far list: 10.79 -> 3.86 ms; products'
    frontiers are ~44% far and take the k-fetch branch on every hop, at
    the k-fetch's time + 0.02-0.05 ms for the count and the branch."""
    width = far_width(base.shape[0], k)
    if not width:
        return _tiled_k_fetch(tiles, base, pos, k), jnp.zeros((), jnp.int32)
    far = (pos >= LANE).any(axis=1)
    fits = far.sum() <= width
    ids = lax.cond(
        fits,
        lambda: _tiled_one_fetch(tiles, base, pos, k, far, width),
        lambda: _tiled_k_fetch(tiles, base, pos, k),
    )
    return ids, fits.astype(jnp.int32)


def lane_rows(indices):
    """A flat edge array as 128-lane rows. ``[R, 128]`` (what
    `CSRTopo.to_device_lane_rows` places) is taken as it is and ``[E]`` with
    E a multiple of 128 reshapes for free; any other ``[E]`` is padded, a
    COPY of the array inside the program: fine at a test's size, and the
    reason the library places a big graph as rows."""
    if indices.ndim == 2:
        return indices
    if indices.shape[0] % LANE:
        indices = jnp.pad(indices, (0, -indices.shape[0] % LANE))
    return indices.reshape(-1, LANE)


def flat_resolve(indices, ptr, pos, k):
    """Resolve drawn positions through a FLAT edge array seen as 128-lane
    rows (`lane_rows`): position ``p`` of a node whose list starts at
    ``ptr`` sits at row ``(ptr + p) // 128``, lane ``(ptr + p) % 128``. The
    same row-gather fetch as the tile layout with no per-node padding — a
    list may straddle two rows, which costs nothing: every position is its
    own descriptor either way. (One-element gathers from a 1-D array of 1e8
    entries take the TPU compiler minutes and run at half the row rate.)"""
    tiles = lane_rows(indices)
    off = jnp.clip(ptr[:, None] + pos.astype(ptr.dtype), 0, tiles.size - 1)
    shift = LANE.bit_length() - 1
    rows = lax.shift_right_logical(off, jnp.asarray(shift, off.dtype))
    lane = jnp.bitwise_and(off, LANE - 1).astype(jnp.int32)
    return _select_lanes(tiles, rows.astype(jnp.int32), lane, k)


@functools.partial(jax.jit, static_argnames=("k", "max_deg"))
def tiled_weighted_sample_hop(
    bd: jax.Array,
    tiles: jax.Array,
    wtiles: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
    max_deg: int = 512,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`tiled_weighted_sample_layer`'s ``(nbrs, valid)`` and, third,
    `_tiled_resolve`'s ``one_fetch`` scalar, for the caller that counts
    (`pyg.sage_sampler.sample_dense_program`)."""
    base, deg = row_windows(bd, seeds, seed_valid)
    deg = jnp.minimum(deg, max_deg)
    w_rows = _tiled_payload_window(base, wtiles, max_deg)
    pos, valid = gumbel_topk_positions(key, deg, k, w_rows)
    nbrs, one_fetch = _tiled_resolve(tiles, base, pos, k)
    return nbrs, valid, one_fetch


@functools.partial(jax.jit, static_argnames=("k", "max_deg"))
def tiled_weighted_sample_layer(
    bd: jax.Array,
    tiles: jax.Array,
    wtiles: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
    max_deg: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Weighted one-hop sample over the tile layout.

    ``wtiles`` is the weights array laid out with the SAME tile map as
    ``tiles`` (`build_tiled_host(indptr, weights, np.float32)`), so each
    row's first ``ceil(max_deg/128)`` weight tiles arrive as row gathers
    — ~128x fewer descriptors than the flat path's [B, max_deg] lane
    window — and chosen positions resolve like `tiled_sample_layer`.
    Draw-identical to :func:`weighted_sample_layer` on the same key when
    ``max_deg`` is a multiple of 128 (same [B, max_deg] Gumbel shape,
    same scores, same top-k). Same truncation semantics: each row
    considers its first ``min(deg, max_deg)`` edges.
    """
    return tiled_weighted_sample_hop(
        bd, tiles, wtiles, seeds, seed_valid, k, key, max_deg)[:2]


def _tiled_payload_window(base, ptiles, max_deg: int):
    """Each row's first ``ceil(max_deg/128)`` PAYLOAD tiles as one
    ``[B, T*128]`` window: T per-row tile fetches, k-split style — a
    [B, T] 3-D gather compiles pathologically, see `_select_lanes`.
    The ONE payload-window fetch (weights and timestamps both ride it;
    the temporal-vs-weighted bit-parity pin depends on the two never
    diverging)."""
    T = -(-max_deg // LANE)
    m_rows = ptiles.shape[0]
    parts = []
    for t in range(T):
        tr = jnp.clip(base + t, 0, m_rows - 1)
        parts.append(jnp.take(ptiles, tr, axis=0))
    return jnp.concatenate(parts, axis=1)  # [B, T*128] >= max_deg


def flat_windows_host(indptr, dtype, rows: Optional[int] = None) -> "np.ndarray":
    """Host build of the flat layout's ``[N, 2]`` (first edge, degree) table
    that `row_windows` reads, of ``dtype``, with no ``[N]`` temporary of
    ``indptr``'s own width. ``rows`` > N appends rows of degree 0 (a
    shard's block, padded to the shards' common length)."""
    import numpy as np

    n = indptr.shape[0] - 1
    out = np.empty((n if rows is None else rows, 2), dtype)
    out[:n, 0] = indptr[:-1]
    np.subtract(indptr[1:], indptr[:-1], out=out[:n, 1], casting="unsafe")
    out[n:] = (indptr[-1], 0)
    return out


@functools.partial(jax.jit, static_argnames=("k",))
def sample_layer(
    indptr: jax.Array,
    indices: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One-hop sample for every valid seed.

    Equivalent of ``TorchQuiver::sample_neighbor`` (quiver_sample.cu:113-132):
    degree lookup, position draw, neighbor gather — all dense.

    Parameters
    ----------
    indptr : the placed ``[N, 2]`` (first edge, degree) windows
        (`CSRTopo.to_device_lane_rows`), taken as they are, or an ``[N+1]``
        indptr, stacked into them inside the program (`row_windows`)
    indices : [E] int array in HBM, or the same edges as ``[R, 128]``
        lane rows (`lane_rows`): positions are fetched as row gathers and
        one-hot lane selects (`flat_resolve`), as the tiled layout fetches
        them, so both layouts draw the same neighbours from the same key
    seeds : [B] int array (garbage allowed where ``~seed_valid``)
    seed_valid : [B] bool
    k : static fanout

    Returns
    -------
    nbrs : [B, k] same dtype as ``indices``; garbage where invalid
    valid : [B, k] bool
    """
    ptr, deg = row_windows(indptr, seeds, seed_valid)
    pos, valid = fisher_yates_positions(key, deg, k)
    return flat_resolve(indices, ptr, pos, k), valid


def build_tiled_host(
    indptr: "np.ndarray", indices: "np.ndarray", id_dtype=None
) -> Tuple["np.ndarray", "np.ndarray"]:
    """Host-side build of the LANE-aligned edge-tile layout.

    Each node's edge list is copied to start at a 128-lane row boundary
    of a ``[M, 128]`` tile table; a ``[N, 2]`` (tile_base, degree) int32
    table replaces indptr for sampling. Sampled position ``p`` of node
    ``i`` then lives at tile row ``base[i] + p // 128``, lane ``p % 128``
    — so the neighbor fetch becomes 2-D ROW gathers (measured ~115-145M
    rows/s on v5e) + an in-register one-hot lane select, instead of
    one-element gathers (~45-90M/s). A node of degree <= 128 has ONE tile
    row, so at a large hop `_tiled_resolve` fetches every seed's first row
    once and reads all its draws from it; the seeds with a draw past that
    row go through a compacted list. Exact for every degree: the RESULT
    has no copy-all/hub split and no cap, whichever way a hop fetched.
    Memory: ceil-padding to 128 costs ~(E + 64*N)/E x the flat CSR
    (products: 1.45 GB vs 0.49 GB).

    Replaces the flat-CSR read path of the reference's sample_kernel
    (srcs/cpp/src/quiver/cuda/quiver_sample.cu:134-200) — GPU warps read
    ragged rows through UVA/HBM fine, TPU DMA wants tiled rows.

    Returns ``(bd, tiles)``: bd ``[N, 2]`` int32, tiles ``[M, 128]`` of
    ``id_dtype`` (int32 when node ids fit).
    """
    import numpy as np

    if id_dtype is None:
        from ..utils import _best_id_dtype

        id_dtype = _best_id_dtype(indptr.shape[0])  # node ids, not edge ids
    bd, M = tiled_base_host(indptr)
    base = bd[:, 0].astype(np.int64)
    deg = bd[:, 1].astype(np.int64)
    tiles = np.zeros((M, LANE), np.dtype(id_dtype))
    out_pos = (
        np.repeat(base * LANE, deg)
        + np.arange(len(indices), dtype=np.int64)
        - np.repeat(indptr[:-1].astype(np.int64), deg)
    )
    tiles.reshape(-1)[out_pos] = indices.astype(id_dtype, copy=False)
    return bd, tiles


@jax.jit
def build_tiled_device(
    indices: jax.Array, row_start: jax.Array, row_width: jax.Array
) -> jax.Array:
    """Build the ``[M, 128]`` tile table ON DEVICE from a flat indices
    array already in HBM (the host build + H2D of `build_tiled_host`
    costs ~25-45 s of tile-table transfer through a thin link; this is
    one [M, 128] gather on-chip, ~seconds).

    ``row_start``/``row_width``: per-TILE-ROW flat edge offset and valid
    lane count, host-computed by `tiled_rowmap_host` (cheap [M] numpy
    work, ~20 MB upload). Deliberately gather-only: the scatter/scan
    formulation of this build compiled pathologically on TPU (>25 min —
    big 1-D scatters, the same wall ops/reindex.py documents for 1-D
    million-element ops).
    """
    e = indices.shape[0]
    lanes = jnp.arange(LANE, dtype=row_start.dtype)
    g = row_start[:, None] + lanes[None, :]
    vals = jnp.take(indices, jnp.clip(g, 0, e - 1))
    return jnp.where(lanes[None, :] < row_width[:, None], vals, 0)


def tiled_base_host(indptr) -> Tuple["np.ndarray", int]:
    """Host half of the tile build: ``(bd [N,2] int32, m_rows)``."""
    import numpy as np

    deg = np.diff(indptr).astype(np.int64)
    rows_per = -(-deg // LANE)
    base = np.zeros(len(deg) + 1, np.int64)
    np.cumsum(rows_per, out=base[1:])
    if base[-1] > np.iinfo(np.int32).max:
        raise ValueError(f"tile row count {base[-1]} exceeds int32")
    bd = np.stack([base[:-1].astype(np.int32), deg.astype(np.int32)], axis=1)
    return bd, max(int(base[-1]), 1)


def tiled_rowmap_host(indptr):
    """Per-tile-row (flat_edge_start, valid_lane_count) for
    `build_tiled_device`: row r of the tile table holds edges
    ``[start[r], start[r] + width[r])`` of its owner node. Row
    accounting comes from `tiled_base_host` — one definition of the
    base/degree math."""
    import numpy as np

    bd, M = tiled_base_host(indptr)
    base = bd[:, 0].astype(np.int64)
    deg = bd[:, 1].astype(np.int64)
    rows_per = -(-deg // LANE)
    owner = np.repeat(np.arange(len(deg), dtype=np.int64), rows_per)
    if owner.shape[0] == 0:  # empty graph: one all-padding row
        return np.zeros(1, np.int64), np.zeros(1, np.int32)
    t = np.arange(M, dtype=np.int64) - base[owner]
    start = indptr[:-1][owner] + t * LANE
    width = np.minimum(indptr[1:][owner] - start, LANE).astype(np.int32)
    return start, width


def temporal_edge_weights(ts: jax.Array, recency: float) -> jax.Array:
    """Recency weight per edge from its timestamp: ``exp(recency * ts)``
    — the Plackett-Luce weight the temporal sampler hands the SAME
    Gumbel top-k the weighted sampler rides (a draw then prefers recent
    edges with half-life ``ln(2)/recency`` in timestamp units;
    ``recency=0`` is uniform over the valid set, exactly 1.0 per edge).
    The query time ``t`` never enters the weight — ``exp(recency*(ts-t))``
    differs from this by a per-row constant factor, which top-k ignores —
    so at ``t=inf`` a temporal draw IS a weighted draw over these
    weights, bit for bit (the frozen==temporal-at-t=inf parity pin in
    tests/test_temporal.py). One definition shared by the device layer,
    the host-masked oracle, and `recency weight-tile` builds, so the
    float32 exp is always the same elementwise op on the same inputs.
    Timestamps must keep ``recency * ts`` within float32 exp range
    (|x| < ~87); scale epochs accordingly."""
    if recency == 0.0:
        return jnp.ones_like(ts, jnp.float32)
    return jnp.exp(jnp.float32(recency) * ts.astype(jnp.float32))


def temporal_weight_rows(
    ts_rows: jax.Array, t: jax.Array, recency: float, cutoff=None
) -> jax.Array:
    """The masked weight window of a temporal draw: recency weights where
    ``ts <= t`` (per-row query times ``t`` [B] broadcast over lanes),
    0 elsewhere — zero weight is exactly how `gumbel_topk_positions`
    already excludes a candidate, so "sample edges with ts <= t" costs
    ONE where. Shared by `tiled_temporal_sample_layer` and the host-
    masked oracle (`workloads.temporal.host_masked_oracle`): both build
    their ``[B, W]`` timestamp windows differently (tile fetch vs host
    CSR slices) but weight them through this one function, which is what
    makes the oracle a bit-parity pin on the tile path.

    ``cutoff`` (scalar, optional) additionally excludes ``ts <= cutoff``
    — the sliding-window band mask ``cutoff < ts <= t``. This is the
    bit-dual of round-21 retention: `stream.expire_edges(cutoff)`
    rewrites expired lanes' ts to ``+inf`` (masked here by ``ts <= t``
    at any finite t), and because the Gumbel uniform stream is
    positional and weights agree lane-for-lane on the survivors, an
    expired stream draws bit-identically to its unexpired twin queried
    through this band (pinned in tests/test_lifecycle.py)."""
    w = temporal_edge_weights(ts_rows, recency)
    keep = ts_rows.astype(jnp.float32) <= t[:, None]
    if cutoff is not None:
        keep = keep & (
            ts_rows.astype(jnp.float32) > jnp.float32(cutoff)
        )
    return jnp.where(keep, w, 0.0)


@functools.partial(jax.jit, static_argnames=("k", "max_deg", "recency"))
def tiled_temporal_sample_layer(
    bd: jax.Array,
    tiles: jax.Array,
    ttiles: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
    t: jax.Array,
    max_deg: int = 512,
    recency: float = 0.0,
    cutoff=None,
) -> Tuple[jax.Array, jax.Array]:
    """TEMPORAL one-hop sample over the tile layout (ROADMAP item 4):
    draw k neighbors per seed among edges with ``ts <= t``, recency-
    biased via the existing Gumbel machinery. ``cutoff`` (optional
    traced scalar) narrows the draw to the ``cutoff < ts <= t`` band —
    the retention duality surface (`temporal_weight_rows`).

    ``ttiles`` is the per-edge timestamp payload laid out with the SAME
    tile map as ``tiles`` (`build_tiled_host(indptr, edge_ts,
    np.float32)`) — timestamps ride the payload lanes exactly like the
    round-5 edge weights, so the fetch is the weighted layer's fetch
    verbatim and positions resolve through the same `_tiled_resolve`.
    ``t`` is a ``[B]`` float32 of per-SEED query times — a traced jit
    ARGUMENT, never a static constant (the NEXT.md rule: one compiled
    program serves every query time), so multi-hop pipelines thread each
    request's own t down its frontier lineage
    (`workloads.temporal.temporal_sample_dense`).

    Draw semantics: among a row's first ``min(deg, max_deg)`` edges,
    every edge with ``ts <= t[row]`` scores ``log w + Gumbel`` with
    ``w = temporal_edge_weights(ts, recency)``; edges beyond t (or
    recency-underflowed to weight 0) are excluded exactly like
    zero-weight edges in the weighted sampler. At ``t = +inf`` the mask
    passes everything and the draw is BIT-EQUAL to
    `tiled_weighted_sample_layer` over weight tiles
    ``temporal_edge_weights(ttiles, recency)`` on the same key — the
    frozen-graph parity pin. Rows whose valid-edge count is below k
    return all their valid edges (copy-all, like every sampler here)."""
    base, deg = row_windows(bd, seeds, seed_valid)
    deg = jnp.minimum(deg, max_deg)
    ts_rows = _tiled_payload_window(base, ttiles, max_deg)
    w_rows = temporal_weight_rows(ts_rows, t.astype(jnp.float32), recency,
                                  cutoff=cutoff)
    pos, valid = gumbel_topk_positions(key, deg, k, w_rows)
    return _tiled_resolve(tiles, base, pos, k)[0], valid


@functools.partial(jax.jit, static_argnames=("k",))
def tiled_sample_hop(
    bd: jax.Array,
    tiles: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`tiled_sample_layer`'s ``(nbrs, valid)`` and, third,
    `_tiled_resolve`'s ``one_fetch`` scalar, for the caller that counts
    (`pyg.sage_sampler.sample_dense_program`)."""
    base, deg = row_windows(bd, seeds, seed_valid)
    pos, valid = fisher_yates_positions(key, deg, k)
    nbrs, one_fetch = _tiled_resolve(tiles, base, pos, k)
    return nbrs, valid, one_fetch


@functools.partial(jax.jit, static_argnames=("k",))
def tiled_sample_layer(
    bd: jax.Array,
    tiles: jax.Array,
    seeds: jax.Array,
    seed_valid: jax.Array,
    k: int,
    key: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One-hop sample over the LANE-aligned tile layout (`build_tiled_host`).

    Draw-identical to :func:`sample_layer` on the same key (same
    Fisher-Yates positions; only the fetch path differs): positions are
    resolved by `_tiled_resolve`, one 2-D row gather a seed plus a
    compacted list of far seeds at a large hop, k row gathers a seed
    otherwise, and one-hot lane selects. Measured at products hop-3 shape,
    k-fetch against element gathers: 6.5 vs 9.0 ms.
    """
    return tiled_sample_hop(bd, tiles, seeds, seed_valid, k, key)[:2]


def neighbor_prob(
    indptr: jax.Array,
    indices: jax.Array,
    prob: jax.Array,
    k: int,
    *,
    edge_chunk: int = 1 << 22,
) -> jax.Array:
    """One step of sampling-probability propagation.

    Equivalent of ``cal_neighbor_prob``/``cal_next``
    (quiver_sample.cu:100-111, cuda_random.cu.hpp:71-104): given P(node is in
    the sampled frontier) per node, propagate to neighbors — each sampled node
    u touches neighbor v with probability ``min(k/deg(u), 1)``, accumulated as
    ``next[v] += prob[u] * min(k/deg(u), 1)``.

    In XLA this is a flat edge-parallel segment-sum over the CSR (the TPU-native
    replacement for the atomicAdd kernel). Chunked over edges with a
    ``lax.fori_loop`` so the traced program holds ONE chunk body regardless of
    graph size (an unrolled Python loop would bake 15+ scatter-adds into the
    graph at products scale, worse at papers100M scale).
    """
    n = indptr.shape[0] - 1
    e = indices.shape[0]
    if e == 0:
        return jnp.zeros((n,), jnp.float32)
    deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)
    w = prob * jnp.minimum(k / jnp.maximum(deg, 1.0), 1.0)  # weight per src node
    chunk = min(edge_chunk, e)
    nchunks = -(-e // chunk)

    def body(c, out):
        # chunks cover [c*chunk, (c+1)*chunk); the final chunk's start is
        # clamped so the static-size slice stays in bounds, and lanes the
        # previous chunk already covered are masked out
        start_u = c * chunk
        start = jnp.minimum(start_u, e - chunk)
        eidx = start + jnp.arange(chunk, dtype=indptr.dtype)
        fresh = eidx >= start_u
        # edge i belongs to row searchsorted(indptr, i, 'right')-1
        src = jnp.searchsorted(indptr, eidx, side="right") - 1
        dst = lax.dynamic_slice(indices, (start,), (chunk,))
        dst = jnp.where(fresh, dst, n)  # n is out of range -> dropped
        return out.at[dst].add(jnp.where(fresh, jnp.take(w, src), 0.0), mode="drop")

    return lax.fori_loop(0, nchunks, body, jnp.zeros((n,), jnp.float32))


def sample_prob(
    indptr: jax.Array,
    indices: jax.Array,
    sizes,
    train_idx: jax.Array,
    num_nodes: Optional[int] = None,
) -> jax.Array:
    """Multi-layer hot-probability estimate (reference sage_sampler.py:149-157).

    Seeds get probability 1; each hop propagates with `neighbor_prob`. The
    result drives degree-free hot/cold placement and the offline partitioner.
    """
    n = num_nodes if num_nodes is not None else indptr.shape[0] - 1
    prob = jnp.zeros((n,), jnp.float32).at[train_idx].set(1.0)
    last = prob
    for k in sizes:
        nxt = neighbor_prob(indptr, indices, last, k)
        prob = prob + nxt
        last = nxt
    return prob
