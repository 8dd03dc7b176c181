"""GraphSAGE k-hop sampler — TPU-native re-design of the reference
``srcs/python/quiver/pyg/sage_sampler.py`` (GraphSageSampler at
sage_sampler.py:36-178).

Reference modes (sage_sampler.py:55-81) and their TPU mapping:

- ``GPU``  (graph resident in device memory)     -> ``"TPU"``: CSR in HBM,
  sampling + reindex run as fused XLA ops on-chip.
- ``UVA``  (graph in pinned host mem, GPU kernels read over PCIe) -> ``"HOST"``:
  no UVA exists on TPU; the graph stays in host DRAM and sampling runs in the
  native host engine (C++/numpy), feeding padded batches to the device. This
  preserves the capability (graph larger than HBM) the UVA mode existed for
  (SURVEY.md section 7.3 item 2).
- ``CPU``  -> ``"CPU"``: host sampling, results stay host-side.

Two output surfaces:

- :meth:`GraphSageSampler.sample_dense` — fully static-shape pytree
  (padded ``[S, k]`` adjacency + masks + counts), jittable end to end; this is
  what the TPU training loop consumes.
- :meth:`GraphSageSampler.sample` — reference/PyG-compatible
  ``(n_id, batch_size, [Adj])`` with ragged ``edge_index`` (host sync), so
  reference training scripts port line for line
  (sage_sampler.py:118-147).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..shard_tensor import _device_of
from ..trace import trace_scope
from ..utils import CSRTopo
from ..ops.sample import (
    pad_widths,
    sample_layer as _sample_layer_op,
    sample_prob as _sample_prob,
    tiled_sample_hop as _tiled_sample_hop_op,
    tiled_weighted_sample_hop as _tiled_weighted_sample_hop_op,
    weighted_sample_layer as _weighted_sample_layer_op,
)
from ..ops.reindex import local_reindex


class Adj(NamedTuple):
    """PyG-compatible adjacency (reference sage_sampler.py:21-28)."""

    edge_index: np.ndarray  # [2, nnz] (col=source, row=target local ids)
    e_id: np.ndarray        # empty — reference keeps it empty too (sage_sampler.py:143)
    size: Tuple[int, int]   # (n_src, n_dst)

    def to(self, *args, **kwargs):  # torch-API compat shim
        return self


class DenseAdj(NamedTuple):
    """Static-shape adjacency for one hop.

    ``cols[i, j]`` is the local id (into the *source* n_id of this hop) of the
    j-th sampled neighbor of target node i; ``mask`` marks real samples. The
    target nodes are always the prefix ``[:mask.shape[0]]`` of the source
    n_id, so dense GraphSAGE aggregation is a gather + masked mean.

    ``cols is None`` marks the STRUCTURAL layout of the fused (no-dedup)
    pipeline: neighbor (i, j) sits at source position ``W + j*W + i`` with
    ``W = mask.shape[0]``, so aggregation needs no gather at all — a slice +
    reshape replaces it (measured 2.3x faster than the equivalent iota-cols
    take on TPU: XLA does not recognize the pattern). ``None`` is a pytree
    aux value, so jitted code can branch on it in Python.
    """

    cols: Optional[jax.Array]  # [S, k] int32, or None (structural layout)
    mask: jax.Array   # [S, k] bool
    n_src: jax.Array  # scalar int32 — valid source-node count
    n_dst: jax.Array  # scalar int32 — valid target-node count

    @property
    def w_dst(self) -> int:
        """Static target-node width of this hop."""
        return self.mask.shape[0]

    def gather_src(self, x_src: jax.Array) -> jax.Array:
        """Neighbor features ``[W_dst, k, ...]`` from the hop-source array,
        honoring the layout: a slice+reshape for the structural (fused)
        layout, a gather for explicit cols. For consumers that need every
        neighbor row (GCN's weights, the DGL shim); a sum over the k slots
        should not lay them out: see `quiver_tpu.ops.gather_sum`
        (`gather_masked_sum` behind `models.masked_mean_aggregate`,
        `gather_attention_sum` behind `models.GATConv`, for explicit cols)."""
        w, k = self.mask.shape
        if self.cols is None:
            s = x_src[w : w * (1 + k)]
            return s.reshape((k, w) + x_src.shape[1:]).swapaxes(0, 1)
        return jnp.take(x_src, jnp.clip(self.cols, 0, x_src.shape[0] - 1), axis=0)


class DenseSample(NamedTuple):
    n_id: jax.Array          # [cap] padded unique node ids (global)
    count: jax.Array         # scalar int32 valid length of n_id
    batch_size: int
    adjs: Tuple[DenseAdj, ...]  # outermost hop first (reference reverses too)
    # dedup pipelines only (None elsewhere): the machinery that lets static
    # caps run TIGHT margins without silently changing sampling semantics.
    # cap_overflow: scalar int32, unique frontier nodes dropped by the caps
    # this batch (0 == bit-exact reference semantics); raw_counts: [L] int32
    # PRE-cap unique counts per hop (innermost-sampled last) — feed them to
    # `caps_from_counts` to recalibrate instead of re-probing.
    cap_overflow: Optional[jax.Array] = None
    raw_counts: Optional[jax.Array] = None
    # one_fetch_hops: scalar int32, how many of the call's hops resolved
    # their positions through one tile-row fetch a seed
    # (`ops.sample._tiled_resolve`); set by `sample_dense_program` over the
    # tile layout, None elsewhere (flat layout, host engine, a caller that
    # binds the hop inside its own program). Nobody reads it inside a step.
    one_fetch_hops: Optional[jax.Array] = None


def sample_dense_fused(
    indptr: jax.Array,
    indices: jax.Array,
    key: jax.Array,
    seeds: jax.Array,
    sizes: Tuple[int, ...],
    sample_fn=None,
) -> DenseSample:
    """Fused multi-hop sample with NO per-layer dedup/reindex — the
    TPU-idiomatic hot path.

    The reference dedups every hop with a GPU hash table because UVA/PCIe
    bandwidth made repeated feature/topology reads expensive. On TPU the
    dedup itself is the expensive part (sort-based `unique` costs two
    O(W log W) sorts per hop on the MXU-starved sort unit), while the padded
    frontier is exactly the same width with or without dedup
    (W_{l+1} = W_l * (1+k)). Skipping dedup makes the local adjacency a
    STATIC index pattern — ``cols[i, j] = W_l + i*k + j`` — so the whole
    multihop pipeline is just degree lookups, Fisher-Yates draws and index
    gathers: zero sorts, zero scatters.

    Semantics: identical sampled-edge distribution; ``n_id`` may contain
    duplicate nodes (each occurrence carries the same feature row, so model
    outputs are bit-identical to the deduped pipeline up to float order).
    Use :func:`sample_dense_pure` when the unique-n_id contract matters
    (PyG-compat surface, cross-host dispatch).
    """
    if sample_fn is None:
        def sample_fn(cur, cur_valid, k, key):
            return _sample_layer_op(indptr, indices, cur, cur_valid, k, key)
    B = seeds.shape[0]
    cur = seeds
    cur_valid = jnp.ones((B,), bool)
    adjs: List[DenseAdj] = []
    prev_count = jnp.asarray(B, jnp.int32)
    for k in sizes:
        key, sub = jax.random.split(key)
        w = cur.shape[0]
        nbrs, valid = sample_fn(cur, cur_valid, k, sub)
        # transposed flatten: a [big, tiny] row-major flatten costs ~40 s of
        # TPU compile (lane-tile relayout); [k, w] -> flat is free. Neighbor
        # (i, j) lands at n_id position w + j*w + i — the structural layout
        # (cols=None) that lets aggregation run gather-free.
        n_id = jnp.concatenate([cur, nbrs.T.reshape(-1)])
        n_valid = jnp.concatenate([cur_valid, valid.T.reshape(-1)])
        count = n_valid.sum().astype(jnp.int32)
        adjs.append(DenseAdj(cols=None, mask=valid, n_src=count, n_dst=prev_count))
        cur, cur_valid, prev_count = n_id, n_valid, count
    return DenseSample(n_id=cur, count=prev_count, batch_size=B, adjs=tuple(adjs[::-1]))


def sample_and_gather_fused(
    indptr: jax.Array,
    indices: jax.Array,
    table: jax.Array,
    key: jax.Array,
    seeds: jax.Array,
    sizes: Tuple[int, ...],
    gather_fn=None,
    sample_fn=None,
) -> Tuple[DenseSample, jax.Array]:
    """Fused multi-hop sample with the FEATURE GATHER interleaved per hop.

    ``n_id`` is a concatenation of per-hop neighbor blocks, so the feature
    rows can be fetched hop by hop as each frontier materializes instead of
    in one big take at the end — XLA then overlaps hop l's (row-rate-bound)
    gather with hop l+1's sampling compute. Returns ``(ds, x)`` with
    ``x == table[clip(ds.n_id)]`` row for row (invalid lanes carry garbage
    rows that ``adj.mask`` gates out of every aggregation, exactly like the
    single-take formulation).

    ``gather_fn(table, ids) -> rows`` overrides the local HBM take — e.g.
    `quiver_tpu.parallel.collectives.sharded_gather` inside shard_map, so
    the ICI collective per hop overlaps with sampling the same way.
    """
    B = seeds.shape[0]
    if gather_fn is None:
        n_rows = table.shape[0]

        def gather_fn(tab, ids):
            return jnp.take(tab, jnp.clip(ids, 0, n_rows - 1), axis=0)
    if sample_fn is None:
        def sample_fn(cur, cur_valid, k, key):
            return _sample_layer_op(indptr, indices, cur, cur_valid, k, key)
    cur = seeds
    cur_valid = jnp.ones((B,), bool)
    adjs: List[DenseAdj] = []
    xs = [gather_fn(table, seeds)]
    prev_count = jnp.asarray(B, jnp.int32)
    for k in sizes:
        key, sub = jax.random.split(key)
        w = cur.shape[0]
        nbrs, valid = sample_fn(cur, cur_valid, k, sub)
        flat = nbrs.T.reshape(-1)
        xs.append(gather_fn(table, flat))
        n_id = jnp.concatenate([cur, flat])
        n_valid = jnp.concatenate([cur_valid, valid.T.reshape(-1)])
        count = n_valid.sum().astype(jnp.int32)
        adjs.append(DenseAdj(cols=None, mask=valid, n_src=count, n_dst=prev_count))
        cur, cur_valid, prev_count = n_id, n_valid, count
    ds = DenseSample(n_id=cur, count=prev_count, batch_size=B, adjs=tuple(adjs[::-1]))
    return ds, jnp.concatenate(xs, axis=0)


def sample_and_gather_dedup(
    indptr: jax.Array,
    indices: jax.Array,
    table: jax.Array,
    key: jax.Array,
    seeds: jax.Array,
    sizes: Tuple[int, ...],
    caps: Optional[Tuple[Optional[int], ...]] = None,
    gather_fn=None,
    sample_fn=None,
) -> Tuple[DenseSample, jax.Array]:
    """Reference-parity dedup sampling with a STRUCTURAL last hop — the fast
    formulation of the deduped e2e train step.

    The sampling DAG is identical to `sample_dense_pure` (each hop draws k
    neighbors of each node of the UNIQUE previous frontier — the reference's
    hash-table reindex contract, sage_sampler.py:133-145): hops 1..L-1 run
    dedup + sort-reindex exactly as `sample_dense_pure`. The LAST hop skips
    the reindex: its leaves stay in the sampled ``[W_{L-1}, k]`` layout and
    their feature rows are gathered straight from ``table`` into the
    structural (cols=None) block. Per (target, slot) the sampled edge and
    its feature row are exactly what the full-dedup pipeline feeds the
    model, so model outputs match up to float association; what changes is
    the data flow:

    - the leaf aggregation becomes a slice+reshape (2.3x faster than the
      equivalent take, PERF.md (earlier claims)) instead of a W_{L-1}*k_L-row gather
      from computed activations;
    - that gather's backward scatter disappears entirely — the structural
      leaf rows read the CONSTANT feature table, so no gradient flows;
    - the last (largest) reindex's sorts and the unique-leaf feature gather
      are replaced by one structural gather.

    Net on products shapes: ~1.0M gathered rows/step vs ~1.6M for gathering
    unique n_id + cols-aggregation. Returns ``(ds, x)``; ``ds.n_id`` is the
    hop-(L-1) unique frontier followed by the structural leaf block (NOT
    globally unique — this is the e2e-internal surface; the public sampler
    contract lives in `sample_dense_pure`/`GraphSageSampler.sample`).
    """
    if len(sizes) == 0:
        raise ValueError("sizes must name at least one hop")
    if gather_fn is None:
        n_rows = table.shape[0]

        def gather_fn(tab, ids):
            return jnp.take(tab, jnp.clip(ids, 0, n_rows - 1), axis=0)

    if sample_fn is None:
        def sample_fn(cur, cur_valid, k, key):
            return _sample_layer_op(indptr, indices, cur, cur_valid, k, key)

    B = seeds.shape[0]
    inner_caps = None if caps is None else tuple(caps[: len(sizes) - 1])
    widths = pad_widths(B, sizes[:-1], inner_caps)
    cur = seeds
    cur_valid = jnp.ones((B,), bool)
    adjs: List[DenseAdj] = []
    raws: List[jax.Array] = []
    overflow = jnp.asarray(0, jnp.int32)
    prev_count = jnp.asarray(B, jnp.int32)
    for l, k in enumerate(sizes[:-1]):
        key, sub = jax.random.split(key)
        nbrs, valid = sample_fn(cur, cur_valid, k, sub)
        res = local_reindex(cur, cur_valid, nbrs, valid)
        n_id, count = res.n_id, res.count
        raws.append(count)
        local_nbrs, nbr_valid = res.local_nbrs, res.nbr_valid
        if widths[l + 1] < n_id.shape[0]:
            cap = widths[l + 1]
            n_id = n_id[:cap]
            overflow = overflow + jnp.maximum(count - cap, 0)
            count = jnp.minimum(count, cap)
            nbr_valid = nbr_valid & (local_nbrs < cap)
        adjs.append(
            DenseAdj(cols=local_nbrs, mask=nbr_valid, n_src=count, n_dst=prev_count)
        )
        cur = n_id
        cur_valid = jnp.arange(n_id.shape[0], dtype=jnp.int32) < count
        prev_count = count
    # last hop: structural leaves, features straight off the table
    k = sizes[-1]
    key, sub = jax.random.split(key)
    nbrs, valid = sample_fn(cur, cur_valid, k, sub)
    flat = nbrs.T.reshape(-1)  # leaf (i, j) -> position W + j*W + i
    x = jnp.concatenate([gather_fn(table, cur), gather_fn(table, flat)], axis=0)
    n_src = prev_count + valid.sum().astype(jnp.int32)
    adjs.append(DenseAdj(cols=None, mask=valid, n_src=n_src, n_dst=prev_count))
    raws.append(n_src)  # structural leaves are never capped
    ds = DenseSample(
        n_id=jnp.concatenate([cur, flat]),
        count=n_src,
        batch_size=B,
        adjs=tuple(adjs[::-1]),
        cap_overflow=overflow,
        raw_counts=jnp.stack(raws),
    )
    return ds, x


def sample_dense_pure(
    indptr: jax.Array,
    indices: jax.Array,
    key: jax.Array,
    seeds: jax.Array,
    sizes: Tuple[int, ...],
    caps: Optional[Tuple[Optional[int], ...]] = None,
    sample_fn=None,
) -> DenseSample:
    """Pure, jittable multi-hop sample (static ``sizes``/``caps``).

    The reference's per-layer loop (sage_sampler.py:133-145) with the ragged
    hash-table reindex replaced by the static-shape sort reindex.

    ``sample_fn(cur, cur_valid, k, key) -> (nbrs, valid)`` overrides the
    local one-hop op — e.g. the collective
    `quiver_tpu.parallel.topology.sharded_sample_layer` when the CSR is
    row-sharded across the mesh (``indptr``/``indices`` may then be None).
    """
    if sample_fn is None:
        def sample_fn(cur, cur_valid, k, key):
            return _sample_layer_op(indptr, indices, cur, cur_valid, k, key)
    B = seeds.shape[0]
    widths = pad_widths(B, sizes, caps)
    cur = seeds
    cur_valid = jnp.ones((B,), bool)
    adjs: List[DenseAdj] = []
    raws: List[jax.Array] = []
    overflow = jnp.asarray(0, jnp.int32)
    prev_count = jnp.asarray(B, jnp.int32)
    for l, k in enumerate(sizes):
        key, sub = jax.random.split(key)
        nbrs, valid = sample_fn(cur, cur_valid, k, sub)
        res = local_reindex(cur, cur_valid, nbrs, valid)
        n_id, count = res.n_id, res.count
        raws.append(count)
        local_nbrs, nbr_valid = res.local_nbrs, res.nbr_valid
        if widths[l + 1] < n_id.shape[0]:
            cap = widths[l + 1]
            n_id = n_id[:cap]
            overflow = overflow + jnp.maximum(count - cap, 0)
            count = jnp.minimum(count, cap)
            nbr_valid = nbr_valid & (local_nbrs < cap)
        adjs.append(
            DenseAdj(cols=local_nbrs, mask=nbr_valid, n_src=count, n_dst=prev_count)
        )
        cur = n_id
        cur_valid = jnp.arange(n_id.shape[0], dtype=jnp.int32) < count
        prev_count = count
    return DenseSample(
        n_id=cur,
        count=prev_count,
        batch_size=B,
        adjs=tuple(adjs[::-1]),
        cap_overflow=overflow,
        raw_counts=jnp.stack(raws),
    )


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("sizes",))
def _probe_hop_counts_scan(ip, ix, key0, batches, sizes):
    def body(_, i):
        ds = sample_dense_pure(
            ip, ix, jax.random.fold_in(key0, i), batches[i], sizes
        )
        return None, jnp.stack([a.n_src for a in ds.adjs[::-1]])

    _, counts = jax.lax.scan(
        body, None, jnp.arange(batches.shape[0], dtype=jnp.int32)
    )
    return counts


def probe_hop_counts(
    indptr: jax.Array,
    indices: jax.Array,
    key: jax.Array,
    seeds_all: jax.Array,
    sizes: Tuple[int, ...],
    graph=None,
    bind=None,
    cache: dict = None,
) -> np.ndarray:
    """Per-hop unique-frontier counts over ``m`` probe batches: ``[m, L]``.

    One jitted scan over the UNCAPPED dedup pipeline — one dispatch total,
    so probing costs one host round trip, however many probe batches. The
    default flat-CSR path reuses one module-level compiled program across
    calls. Other layouts and weighted samplers (caps MUST be calibrated
    under the distribution they will serve) pass ``graph`` — the sampler's
    device-array pytree — and ``bind(graph) -> sample_fn``
    (`GraphSageSampler._graph_and_bind`): the arrays are ARGUMENTS of the
    jitted scan, never closure constants (at products scale a closed-over
    1.45 GB tile table is baked into the executable: minutes of compile and
    a program too large for the persistent cache). Pass ``cache`` (any dict
    owned by the caller, keyed here by ``sizes``) to reuse the traced scan
    across calls — `GraphSageSampler.calibrate_caps` passes a per-sampler
    dict, sound because everything ``bind`` closes over (layout, weighting,
    ``max_deg``) is fixed at construction. Without ``cache``, each call
    retraces.
    """
    seeds_all = jnp.asarray(seeds_all)
    if bind is None:
        return np.asarray(
            _probe_hop_counts_scan(indptr, indices, key, seeds_all, tuple(sizes))
        )

    sizes_t = tuple(sizes)
    run = cache.get(sizes_t) if cache is not None else None
    if run is None:

        @jax.jit
        def run(g, key0, batches):
            sample_fn = bind(g)

            def body(_, i):
                ds = sample_dense_pure(
                    None, None, jax.random.fold_in(key0, i), batches[i],
                    sizes_t, sample_fn=sample_fn,
                )
                return None, jnp.stack([a.n_src for a in ds.adjs[::-1]])

            _, counts = jax.lax.scan(
                body, None, jnp.arange(batches.shape[0], dtype=jnp.int32)
            )
            return counts

        if cache is not None:
            cache[sizes_t] = run

    return np.asarray(run(graph, key, seeds_all))


def caps_from_counts(
    counts: np.ndarray,
    batch: int,
    sizes: Tuple[int, ...],
    margin: float = 1.2,
    granule: int = 4096,
) -> Tuple[int, ...]:
    """Static per-hop n_id caps from probed unique counts.

    ``max`` over the probe batches x ``margin`` safety factor, rounded up to
    ``granule`` (shape granularity keeps recompiles away when recalibrating),
    clipped to the uncapped worst case ``B*prod(1+k)``. This is the policy
    the round-2 bench hand-rolled, promoted into the
    library — the reference needs no caps (ragged CUDA shapes); static-shape
    TPU pipelines do, so choosing them is the framework's job.
    """
    counts = np.asarray(counts).reshape(-1, len(sizes))
    worst = pad_widths(batch, sizes)[1:]
    caps = []
    for l in range(len(sizes)):
        need = int(np.max(counts[:, l])) * margin
        caps.append(int(min(-(-need // granule) * granule, worst[l])))
    return tuple(caps)


def one_hop_binder(layout: str, weighted: bool, max_deg: int, one_fetch=None):
    """``bind(graph) -> sample_fn``: the one-hop op of a TPU-mode sampler
    over the device-array pytree `GraphSageSampler.fused_sample_spec` hands
    out beside it: ``(bd, tiles[, wtiles])`` tiled, ``(windows, rows)``
    flat (the placed ``[N, 2]`` (first edge, degree) table and the
    ``[R, 128]`` lane rows of `CSRTopo.to_device_lane_rows`), ``(indptr,
    indices, weights)`` weighted flat (1-D arrays: that one stacks its
    table in the program, `ops.sample.row_windows`). The one source of
    that closure: every program that samples in-jit calls ``bind`` on its
    TRACED graph argument, and hands the pair through as it is.
    ``one_fetch``: a list to which every hop over the tile layout appends
    its scalar of `ops.sample._tiled_resolve`, for a caller that is one
    program and adds them up (`sample_dense_program`); ``sample_fn`` keeps
    its ``(nbrs, valid)`` either way."""
    tally = (lambda flag: None) if one_fetch is None else one_fetch.append

    def bind(g):
        if layout == "tiled" and weighted:
            bd, tiles, wtiles = g

            def sample_fn(cur, cur_valid, k, key):
                nbrs, valid, flag = _tiled_weighted_sample_hop_op(
                    bd, tiles, wtiles, cur, cur_valid, k, key, max_deg
                )
                tally(flag)
                return nbrs, valid
        elif layout == "tiled":
            bd, tiles = g

            def sample_fn(cur, cur_valid, k, key):
                nbrs, valid, flag = _tiled_sample_hop_op(bd, tiles, cur, cur_valid, k, key)
                tally(flag)
                return nbrs, valid
        elif weighted:
            indptr, indices, w = g

            def sample_fn(cur, cur_valid, k, key):
                return _weighted_sample_layer_op(
                    indptr, indices, w, cur, cur_valid, k, key, max_deg
                )
        else:
            windows, rows = g

            def sample_fn(cur, cur_valid, k, key):
                return _sample_layer_op(windows, rows, cur, cur_valid, k, key)

        return sample_fn

    return bind


@_functools.partial(jax.jit, static_argnames=("sizes", "caps", "dedup", "hop"))
def sample_dense_program(key0, call, seeds, graph, *, sizes, caps, dedup, hop):
    """`GraphSageSampler.sample_dense`'s TPU path as ONE program (XLA module
    ``jit_sample_dense_program``, `trace.SAMPLE_PROGRAM_NAMES`): the key of
    call ``call`` folded from the sampler's base key (the bits `next_key`
    hands out), then the same `sample_dense_pure` / `sample_dense_fused`
    composition a caller can run op by op. ``graph`` and ``key0`` are
    ARGUMENTS (`fused_sample_spec` says why; a seed baked in would also
    compile anew for every seed); ``hop`` is `one_hop_binder`'s argument
    triple. Traced once per batch shape and static set, for every sampler
    of the process: a twin built for warm-up warms the one it stands for.
    ``one_fetch_hops`` of the result adds up the hops' flags over the tile
    layout (`DenseSample`); the flat layout has none to add."""
    key = jax.random.fold_in(key0, call)
    fetches: List[jax.Array] = []
    sample_fn = one_hop_binder(*hop, one_fetch=fetches)(graph)
    if dedup:
        ds = sample_dense_pure(None, None, key, seeds, sizes, caps, sample_fn=sample_fn)
    else:
        ds = sample_dense_fused(None, None, key, seeds, sizes, sample_fn=sample_fn)
    # a Python int leaf would come back as a device array
    return ds._replace(batch_size=None, one_fetch_hops=sum(fetches) if fetches else None)


class GraphSageSampler:
    """K-hop sampler over a :class:`CSRTopo` (reference sage_sampler.py:36).

    Parameters
    ----------
    csr_topo : CSRTopo
    sizes : fanouts, outermost-first like PyG (e.g. ``[15, 10, 5]``)
    device : int, local device index for TPU mode (reference's GPU ordinal)
    mode : "TPU" | "HOST" | "CPU" (aliases: "GPU" -> TPU, "UVA" -> HOST,
        "ZERO_COPY"/"DMA" -> HOST/TPU)
    caps : optional per-layer static n_id budget (TPU-only knob; bounds padded
        growth for deep fanouts)
    seed : RNG seed; sampling is deterministic given (seed, call index)
    layout : "tiled" (default) | "flat" — TPU-mode graph layout. "tiled"
        stores edges 128-lane-aligned (`CSRTopo.to_device_tiled`) so the
        neighbor fetch rides 2-D row gathers (~1.4x the element-gather
        rate, measured) at ~2-3x flat-CSR HBM bytes; "flat" keeps the
        plain CSR's bytes (use when HBM is tight) and fetches positions
        through its edges seen as 128-lane rows, the same row gathers
        (`ops.sample.flat_resolve`). Draw-identical on the same
        seed (weighted: when max_deg is a multiple of 128). Weighted
        tiled additionally tiles the edge weights
        (`to_device_tiled_weights`) so the [B, max_deg] weight window
        rides ceil(max_deg/128) row gathers per row instead of max_deg
        element gathers.
    dedup : True (default) dedups every hop like the reference's hash-table
        reindex; False uses the fused no-reindex hot path
        (`sample_dense_fused`) — fastest on TPU, n_id may repeat nodes
    auto_grow_caps : opt-in overflow ladder for TIGHT caps. When a dedup
        batch overflows its caps (``DenseSample.cap_overflow > 0`` — unique
        nodes would have been dropped), recalibrate the caps from that
        batch's pre-cap ``raw_counts`` (margin/granule from the last
        `calibrate_caps` call) and resample. Costs one host sync per
        ``sample_dense`` call and a recompile per cap change, so use with
        granule-rounded caps where regrowth is rare; the payoff is running
        margins like 1.1 instead of 1.2 — less padded gather width — while
        keeping exact reference sampling semantics.
    """

    MODE_ALIASES = {"GPU": "TPU", "UVA": "HOST", "ZERO_COPY": "HOST", "DMA": "TPU"}

    def __init__(
        self,
        csr_topo: CSRTopo,
        sizes: Sequence[int],
        device=0,
        mode: str = "TPU",
        caps: Optional[Sequence[Optional[int]]] = None,
        seed: int = 0,
        dedup: bool = True,
        weighted: bool = False,
        max_deg: int = 512,
        auto_grow_caps: bool = False,
        layout: str = "tiled",
    ):
        mode = self.MODE_ALIASES.get(mode, mode)
        if mode not in ("TPU", "HOST", "CPU"):
            raise ValueError(f"unsupported mode: {mode}")
        if layout not in ("tiled", "flat"):
            raise ValueError(f"unsupported layout: {layout}")
        self.csr_topo = csr_topo
        self.sizes = tuple(int(s) for s in sizes)
        self.caps = None if caps is None else tuple(caps)
        self.mode = mode
        self.device = device
        self.dedup = dedup
        self.weighted = weighted
        self.max_deg = int(max_deg)
        self.auto_grow_caps = bool(auto_grow_caps)
        # recalibration policy for the overflow ladder; updated by
        # calibrate_caps so regrowth uses the margin the caps were born with
        self.cap_margin = 1.2
        self.cap_granule = 4096
        if weighted:
            if csr_topo.edge_weights is None:
                raise ValueError(
                    "weighted=True needs CSRTopo(edge_weights=...) "
                    "(per-edge weights aligned with the COO input)"
                )
            # TPU mode: Gumbel-top-k device op. HOST/CPU: the native
            # engine's Efraimidis-Spirakis weighted k-subset (same
            # distribution; qt_sample_layer_weighted) — the reference has
            # no CPU weighted path at all (weight_sample is CUDA-only,
            # cuda_random.cu.hpp:177-221).
        self.layout = layout
        self._seed = seed
        self._call = 0
        self._dev_arrays = None
        self._dev_tiled = None
        self._w_dev = None
        # round-17 streaming binding (`bind_stream`): when set, the tiled
        # device graph is READ FROM THE STREAM at every sample/spec call
        # instead of the frozen CSRTopo cache — fenced graph deltas become
        # visible to the next draw without touching the key stream
        self._stream = None
        # round-19 temporal binding (`bind_temporal`): (source, recency)
        # — the source carries per-edge timestamps in the tile payload
        # lanes and every draw takes a per-seed query time t
        self._temporal = None
        # per-sampler probe-scan cache: under the default layout='tiled'
        # (and for weighted samplers) probe_hop_counts builds its jitted
        # scan around this sampler's bind(), so without this it would
        # retrace on EVERY calibrate_caps call
        self._probe_scan_cache: dict = {}
        if mode == "TPU":
            self.lazy_init_quiver()
            # the base key `sample_dense_program` folds the call index into
            self._key0 = jax.random.key(seed)
        self._host_engine = None

    def _device_obj(self):
        if isinstance(self.device, int):
            return _device_of(self.device)
        return None

    # -- streaming graph binding (round 17; quiver_tpu.stream) -----------
    @property
    def stream(self):
        """The bound `stream.StreamingTiledGraph`, or None (frozen
        graph). Serve engines read this to decide whether
        ``update_graph`` is supported."""
        return self._stream

    def bind_stream(self, stream) -> "GraphSageSampler":
        """Attach a `quiver_tpu.stream.StreamingTiledGraph`: every
        sample (split path), fused-spec build, and `lazy_init_quiver`
        then reads the stream's CURRENT device ``(bd, tiles)`` pair —
        array objects change at each fenced delta commit, shapes never
        do, so sealed AOT serve programs keep running (the engine
        rebinds their argument arrays via `BucketPrograms.rebind`).
        TPU-mode tiled uniform samplers only: HOST/CPU engines sample a
        host CSR the stream does not maintain, the flat layout has no
        pad lanes to append into, and weighted samplers would need the
        weight tiles streamed in lockstep (not built — stage weights
        with a rebuild instead)."""
        if self.mode != "TPU":
            raise TypeError("bind_stream needs mode='TPU' (device graph)")
        if self.layout != "tiled":
            raise TypeError(
                "bind_stream needs layout='tiled' — the flat CSR has no "
                "pad lanes to append into"
            )
        if self.weighted:
            raise TypeError(
                "streaming deltas keep the uniform tile map only; "
                "weighted samplers would need wtiles streamed in lockstep"
            )
        self._stream = stream
        self._dev_tiled = None
        return self

    # -- temporal binding (round 19; quiver_tpu.workloads) ----------------
    @property
    def temporal(self):
        """``(source, recency)`` when this sampler draws temporally
        (`bind_temporal`), else None. The serve engines read this to pick
        the temporal serve-step shape (an extra per-seed query-time
        argument on every dispatch)."""
        return self._temporal

    def bind_temporal(self, source, recency: float = 0.0) -> "GraphSageSampler":
        """Attach a temporal graph: every draw then samples only edges
        with ``ts <= t`` (per-seed query times, a jit ARGUMENT of every
        dispatch — never a closure constant), recency-biased via the
        weighted sampler's Gumbel machinery
        (`ops.sample.tiled_temporal_sample_layer`;
        ``recency`` is the exponent of `ops.sample.temporal_edge_weights`,
        0 = uniform over the valid set).

        ``source`` is a `workloads.temporal.TemporalTiledGraph` (frozen
        graph + timestamps) or a `stream.StreamingTiledGraph` built with
        ``edge_ts=`` — the streaming case ALSO binds the stream
        (`bind_stream` semantics), so fenced ``update_graph`` commits
        make an arriving edge visible to the next ``t >= ts`` query and
        invisible below it. TPU-mode tiled uniform samplers with
        ``dedup=False`` only: the temporal pipeline threads each seed's
        own t down its frontier lineage, which needs the structural
        no-dedup layout (a dedup reindex would merge frontiers across
        requests with different query times)."""
        if self.mode != "TPU":
            raise TypeError("bind_temporal needs mode='TPU' (device graph)")
        if self.layout != "tiled":
            raise TypeError(
                "bind_temporal needs layout='tiled' — timestamps ride the "
                "tile payload lanes"
            )
        if self.weighted:
            raise TypeError(
                "temporal recency bias replaces static edge weights; "
                "bind_temporal needs weighted=False"
            )
        if self.dedup:
            raise TypeError(
                "temporal sampling threads per-seed query times down the "
                "frontier lineage — construct with dedup=False (the "
                "structural no-dedup pipeline)"
            )
        if not getattr(source, "temporal", False):
            raise TypeError(
                "bind_temporal wants a TemporalTiledGraph or a "
                "StreamingTiledGraph built with edge_ts= (got "
                f"{type(source).__name__})"
            )
        from ..stream import StreamingTiledGraph

        if isinstance(source, StreamingTiledGraph):
            # streaming temporal: the stream binding rides along so the
            # serve engines' update_graph/stage_edges find it
            self._stream = source
            self._dev_tiled = None
        self._temporal = (source, float(recency))
        return self

    def temporal_graph_arrays(self):
        """The CURRENT device ``(bd, tiles, ttiles)`` triple a temporal
        draw reads — re-read per call so fenced stream commits become
        visible to the next draw."""
        if self._temporal is None:
            raise TypeError("sampler has no temporal binding")
        return self._temporal[0].temporal_graph()

    def fused_graph_arrays(self):
        """The CURRENT device-graph pytree the fused serve programs take
        as their ``graph`` argument — temporal triple, streamed pair, or
        the frozen binding (`lazy_init_quiver`), in that precedence. The
        serve engines rebind sealed executables to this after a fenced
        graph commit."""
        if self._temporal is not None:
            return self.temporal_graph_arrays()
        if self._stream is not None:
            return self._stream.graph()
        return self.lazy_init_quiver()

    # -- device-graph binding (reference lazy_init_quiver, sage_sampler.py:98-113)
    def lazy_init_quiver(self):
        """Bind the graph to the device and return the binding: the
        ``(bd, tiles)`` pair under the default tiled layout (weighted
        samplers included — their weight tiles bind separately via
        ``to_device_tiled_weights``); under ``layout='flat'`` the
        ``(windows, rows)`` pair of `CSRTopo.to_device_lane_rows`, the
        ``[N, 2]`` (first edge, degree) table and the edges as ``[R, 128]``
        lane rows (a weighted sampler: `CSRTopo.to_device`'s 1-D
        ``(indptr [N+1], indices [E])``). Either way the first array is
        what `ops.sample.row_windows` looks a seed up in and the second
        holds the edges. Callers needing the ``[E]`` pair regardless of
        layout should use ``self.csr_topo.to_device()``."""
        if self.layout == "tiled":
            if self._stream is not None:
                return self._stream.graph()
            if self._dev_tiled is None:
                self._dev_tiled = self.csr_topo.to_device_tiled(self._device_obj())
            return self._dev_tiled
        if self._dev_arrays is None:
            # weighted draws read the weights by edge position beside the
            # [E] array; uniform ones fetch through 128-lane rows and the
            # placed window table
            place = (self.csr_topo.to_device if self.weighted
                     else self.csr_topo.to_device_lane_rows)
            self._dev_arrays = place(self._device_obj())
        return self._dev_arrays

    def _host(self):
        if self._host_engine is None:
            from ..ops import cpu_kernels

            self._host_engine = cpu_kernels.HostSampler(
                self.csr_topo.indptr,
                self.csr_topo.indices,
                weights=self.csr_topo.edge_weights if self.weighted else None,
            )
        return self._host_engine

    def next_call(self) -> int:
        """Consume and return the next call index of this sampler's
        deterministic stream WITHOUT deriving a key: call i draws
        ``fold_in(key(seed), i)``, and the programs that take
        ``(key0, call)`` (`sample_dense_program`, the fused serve step of
        `inference.make_serve_step`) fold it on the device. The serve
        engine draws one index a dispatch under its sequencing lock, a
        host integer and nothing else, so the index stream (and any
        replay of the dispatch log through a twin sampler) stays
        identical to the split sample/forward path."""
        if self.mode != "TPU":
            raise TypeError(
                "next_call() draws the TPU-mode jax key stream; HOST/CPU "
                "samplers derive their RNG seed inside sample_dense"
            )
        call, self._call = self._call, self._call + 1
        return call

    def next_key(self) -> jax.Array:
        """Consume the next call index (`next_call`) and return its key,
        ``fold_in(key(seed), i)``, derived eagerly on the host: key i is
        exactly the key `sample_dense`'s i-th call draws. For callers
        that sample op by op or skip an index (a replay twin); the hot
        paths pass the index into their one program instead."""
        call = self.next_call()  # first: the TypeError of a HOST/CPU sampler
        return jax.random.fold_in(self._key0, call)

    def fused_sample_spec(self):
        """``(graph, bind, id_dtype)`` for building FUSED in-jit
        sample+gather+forward programs (`inference.make_serve_step`).

        ``graph`` is the device-array pytree the fused program must take as
        jit ARGUMENTS — never closure constants: big closure constants are
        the slow-compile trap (NEXT.md; bit round 5's probe script).
        ``bind(graph)`` rebuilds the one-hop ``sample_fn`` over the TRACED
        graph arrays inside the jit (`one_hop_binder`: the one source of
        that closure, which `sample_dense`'s own program, the cap probe and
        `sample_layer` bind too). Raises TypeError when this sampler
        cannot be fused
        (HOST/CPU modes sample host-side; ``auto_grow_caps`` resizes caps
        mid-stream, which a pre-bound static-shape executable cannot
        follow)."""
        if self.mode != "TPU":
            raise TypeError("fused sampling needs mode='TPU' (device-resident graph)")
        if self.auto_grow_caps:
            raise TypeError(
                "auto_grow_caps resizes caps mid-stream; the fused serve "
                "program needs static caps (calibrate_caps first, or "
                "construct with auto_grow_caps=False)"
            )
        return self._graph_and_bind()

    def _graph_and_bind(self):
        """``(graph, bind, id_dtype)`` of `fused_sample_spec`, without its
        serving-only checks (cap calibration probes with it too)."""
        bind = one_hop_binder(*self._hop())
        if self.layout == "tiled":
            bd, tiles = self.lazy_init_quiver()
            if self.weighted:
                wtiles = self.csr_topo.to_device_tiled_weights(self._device_obj())
                return (bd, tiles, wtiles), bind, tiles.dtype
            return (bd, tiles), bind, tiles.dtype
        windows, edges = self.lazy_init_quiver()
        if self.weighted:
            if self._w_dev is None:
                self._w_dev = jnp.asarray(
                    np.asarray(self.csr_topo.edge_weights, np.float32)
                )
            return (windows, edges, self._w_dev), bind, edges.dtype
        return (windows, edges), bind, edges.dtype

    def _hop(self):
        return self.layout, self.weighted, self.max_deg

    # -- dense static-shape surface --------------------------------------
    def sample_dense(self, seeds, t=None) -> DenseSample:
        """Sample a padded, jittable mini-batch. TPU mode runs fully on
        device, ONE program a call (`sample_dense_program`: call i draws
        ``fold_in(key(seed), i)``, the key `next_key` would hand out);
        HOST/CPU modes run the native host engine and pad. The
        ``quiver.sample`` span carries ``call`` and ``compiled`` (1 when
        the call traced a program: a batch shape, sizes, caps or dedup
        the process had not sampled with).

        ``t`` (temporal samplers only — `bind_temporal`): per-seed query
        times, scalar or ``[B]``; every hop of a seed's expansion then
        draws only edges with ``ts <= t[seed]``. Consumes one key of the
        same deterministic stream as every other sample call."""
        if self._temporal is not None:
            if t is None:
                raise TypeError(
                    "temporal sampler needs a query time: "
                    "sample_dense(seeds, t=...)"
                )
            from ..workloads.temporal import temporal_sample_dense

            source, recency = self._temporal
            graph = self.temporal_graph_arrays()
            seeds = jnp.asarray(np.asarray(seeds), graph[1].dtype)
            tv = np.asarray(t, np.float32).reshape(-1)
            if tv.shape[0] == 1 and seeds.shape[0] != 1:
                tv = np.broadcast_to(tv, (seeds.shape[0],)).copy()
            if tv.shape[0] != seeds.shape[0]:
                raise ValueError(
                    f"t has {tv.shape[0]} entries for {seeds.shape[0]} seeds"
                )
            return temporal_sample_dense(
                graph, self.next_key(), seeds, jnp.asarray(tv),
                self.sizes, recency=recency, max_deg=self.max_deg,
            )
        if t is not None:
            raise TypeError(
                "t= is only meaningful on a temporal sampler "
                "(bind_temporal first)"
            )
        if self.mode == "TPU":
            with trace_scope("quiver.sample", call=self._call) as span:
                built = sample_dense_program._cache_size()
                ds = self._device_sample_dense(seeds)
                span.set(compiled=int(sample_dense_program._cache_size() > built))
                return ds
        return self._host_sample_dense(np.asarray(seeds))

    def _run_sample_program(self, seeds, dedup: bool) -> DenseSample:
        """One launch of `sample_dense_program`: the next key of the
        stream, the graph as it stands (a stream-bound sampler hands over
        the committed arrays: same shapes, so the same program)."""
        graph, _, id_dtype = self._graph_and_bind()
        seeds = np.asarray(seeds).astype(np.dtype(id_dtype), copy=False)
        call, self._call = self._call, self._call + 1
        ds = sample_dense_program(
            self._key0, np.uint32(call), seeds, graph, sizes=self.sizes,
            caps=self.caps if dedup else None, dedup=dedup, hop=self._hop(),
        )
        return ds._replace(batch_size=seeds.shape[0])

    def _device_sample_dense(self, seeds) -> DenseSample:
        """The TPU path of `sample_dense`: one `sample_dense_program` a
        call and around it, on the host, the overflow ladder of
        ``auto_grow_caps``: a regrow changes ``caps``, a static argument,
        so it builds one more program inside the call that overflowed."""
        ds = self._run_sample_program(seeds, self.dedup)
        if self.dedup and self.auto_grow_caps and self.caps is not None:
            # overflow ladder: regrow caps from the observed pre-cap
            # counts and resample until nothing is dropped. raw_counts of
            # hop l+1 are measured under hop l's (possibly capped)
            # frontier, so one regrow can reveal more demand — iterate,
            # bounded (caps_from_counts clips at the uncapped worst case,
            # where overflow is impossible by construction).
            for _ in range(len(self.sizes) + 1):
                if int(ds.cap_overflow) == 0:
                    break
                grown = caps_from_counts(
                    np.asarray(ds.raw_counts)[None, :], ds.batch_size,
                    self.sizes, margin=self.cap_margin,
                    granule=self.cap_granule,
                )
                # monotone merge: one batch's raw_counts must only ever
                # RAISE caps — taking them wholesale would shrink hops
                # that didn't overflow this batch (raw_counts are a
                # single sample, not the calibrated max), ping-ponging
                # caps and recompiling every few batches. None stays
                # None: an uncapped hop cannot overflow, so capping it
                # would force a shape change no overflow ever demanded.
                self.caps = tuple(
                    None if o is None else max(o, n)
                    for o, n in zip(self.caps, grown)
                )
                ds = self._run_sample_program(seeds, True)
            if int(ds.cap_overflow) > 0:
                # ladder bound exhausted (per-key count fluctuation can
                # outrun a small margin): surface it — the caller still
                # sees cap_overflow, but silence here would contradict
                # the "resample until nothing is dropped" contract
                import warnings

                warnings.warn(
                    f"auto_grow_caps: still dropping "
                    f"{int(ds.cap_overflow)} nodes after regrowth to "
                    f"caps={self.caps}; raise cap_margin/cap_granule",
                    RuntimeWarning,
                    stacklevel=3,
                )
        return ds

    def _host_sample_dense(self, seeds: np.ndarray) -> DenseSample:
        eng = self._host()
        rng_seed = (self._seed * 0x9E3779B1 + self._call) & 0x7FFFFFFF
        self._call += 1
        n_id, count, adjs = eng.sample_multilayer(
            seeds.astype(np.int64), self.sizes, rng_seed, self.caps
        )
        dense_adjs = tuple(
            DenseAdj(
                cols=jnp.asarray(a["cols"]),
                mask=jnp.asarray(a["mask"]),
                n_src=jnp.asarray(a["n_src"], jnp.int32),
                n_dst=jnp.asarray(a["n_dst"], jnp.int32),
            )
            for a in adjs[::-1]
        )
        return DenseSample(
            n_id=jnp.asarray(n_id),
            count=jnp.asarray(count, jnp.int32),
            batch_size=int(seeds.shape[0]),
            adjs=dense_adjs,
        )

    # -- reference/PyG-compatible surface ---------------------------------
    def sample(self, input_nodes):
        """Reference-compatible ``(n_id, batch_size, [Adj])``
        (sage_sampler.py:118-147). Ragged — forces a host sync; prefer
        :meth:`sample_dense` inside TPU training loops.

        Always uses the deduped pipeline: the ragged contract requires
        unique, prefix-valid n_id, which the fused path does not provide.
        """
        if self.mode == "TPU" and not self.dedup:
            ds = self._run_sample_program(input_nodes, True)
        else:
            ds = self.sample_dense(input_nodes)
        return dense_to_pyg(ds)

    def sample_layer(self, seeds, size: int):
        """One-hop sample (reference sage_sampler.py:83-96): returns ragged
        (neighbors, counts) on host."""
        if self.mode == "TPU":
            graph, bind, id_dtype = self._graph_and_bind()
            seeds_d = jnp.asarray(np.asarray(seeds), id_dtype)
            nbrs, valid = bind(graph)(
                seeds_d, jnp.ones(seeds_d.shape, bool), size, self.next_key()
            )
            nbrs, valid = np.asarray(nbrs), np.asarray(valid)
        else:
            eng = self._host()
            rng_seed = (self._seed * 0x9E3779B1 + self._call) & 0x7FFFFFFF
            self._call += 1
            nbrs, valid = eng.sample_layer(np.asarray(seeds, np.int64), size, rng_seed)
        counts = valid.sum(axis=1)
        return nbrs[valid], counts

    def reindex(self, inputs, outputs, counts):
        """Reference-compatible reindex of a ragged one-hop result
        (sage_sampler.py:115-116): returns (n_id, row, col).

        The ragged->padded conversion is vectorized (row-major mask
        assignment matches the ragged concatenation order) — a per-row
        Python loop here was the compat surface's bottleneck at products
        batch sizes."""
        inputs = np.asarray(inputs)
        counts = np.asarray(counts, np.int64)
        S = inputs.shape[0]
        k = int(counts.max()) if S else 0
        padded = np.zeros((S, max(k, 1)), np.int64)
        mask = np.arange(max(k, 1))[None, :] < counts[:, None]
        padded[mask] = np.asarray(outputs)
        res = local_reindex(
            jnp.asarray(inputs), jnp.ones((S,), bool), jnp.asarray(padded), jnp.asarray(mask)
        )
        n_id = np.asarray(res.n_id)[: int(res.count)]
        rows = np.repeat(np.arange(S), counts)
        cols = np.asarray(res.local_nbrs)[np.asarray(res.nbr_valid)]
        return n_id, rows, cols

    # -- static-cap calibration (TPU-only concern; see caps_from_counts) --
    def calibrate_caps(
        self,
        probe_seeds,
        margin: float = 1.2,
        granule: int = 4096,
        set_caps: bool = True,
    ) -> Tuple[int, ...]:
        """Probe-batch calibration of the per-hop static n_id caps.

        ``probe_seeds``: [m, B] array (or list of m same-length batches) of
        representative seed batches — use >= 8 so the max is stable. Returns
        the caps and (by default) installs them on this sampler. Persist
        alongside other offline artifacts via
        ``checkpoint.save_partition_artifacts(path, caps=np.asarray(caps))``.
        """
        batches = np.stack([np.asarray(b) for b in probe_seeds])
        if batches.ndim != 2:
            raise ValueError(f"probe_seeds must be [m, B]; got {batches.shape}")
        if self.mode == "TPU":
            # the graph arrays are ARGUMENTS of the cached probe scan, read
            # here per call: a stream-bound sampler probes the graph as of
            # its latest commit with no retrace (shapes never change)
            graph, bind, id_dtype = self._graph_and_bind()
            counts = probe_hop_counts(
                None, None, self.next_key(),
                jnp.asarray(batches.astype(np.dtype(id_dtype))), self.sizes,
                graph=graph, bind=bind, cache=self._probe_scan_cache,
            )
        else:
            rows = []
            for b in batches:  # host engine: uncapped dense sample per batch
                saved = self.caps
                self.caps = None
                try:
                    ds = self._host_sample_dense(b)
                finally:
                    self.caps = saved
                rows.append([int(a.n_src) for a in ds.adjs[::-1]])
            counts = np.asarray(rows)
        caps = caps_from_counts(
            counts, batches.shape[1], self.sizes, margin=margin, granule=granule
        )
        self.cap_margin, self.cap_granule = float(margin), int(granule)
        if set_caps:
            self.caps = caps
        return caps

    # -- hot-probability propagation (reference sage_sampler.py:149-157) --
    def sample_prob(self, train_idx, total_node_count: int):
        # flat CSR regardless of sampling layout: neighbor_prob's
        # edge-parallel segment sum wants the plain (indptr, indices)
        indptr, indices = self.csr_topo.to_device(
            self._device_obj() if self.mode == "TPU" else None
        )
        return _sample_prob(
            indptr, indices, self.sizes, jnp.asarray(np.asarray(train_idx)), total_node_count
        )

    # -- multiprocess hand-off shims (reference sage_sampler.py:159-178) --
    def share_ipc(self):
        return (
            self.csr_topo, self.sizes, self.device, self.mode, self.caps,
            self._seed, self.dedup, self.weighted, self.max_deg,
            self.auto_grow_caps, self.layout,
        )

    @classmethod
    def lazy_from_ipc_handle(cls, ipc_handle):
        (csr_topo, sizes, device, mode, caps, seed, dedup, weighted, max_deg,
         auto_grow_caps, layout) = ipc_handle
        return cls(
            csr_topo, sizes, device=device, mode=mode, caps=caps, seed=seed,
            dedup=dedup, weighted=weighted, max_deg=max_deg,
            auto_grow_caps=auto_grow_caps, layout=layout,
        )


def dense_to_pyg(ds: DenseSample):
    """Convert a padded DenseSample to the reference's ragged
    ``(n_id, batch_size, [Adj])`` (host-side)."""
    count = int(ds.count)
    n_id = np.asarray(ds.n_id)[:count]
    adjs = []
    for adj in ds.adjs:
        mask = np.asarray(adj.mask)
        if adj.cols is None:  # structural layout: cols[i, j] = W + j*W + i
            w, k = mask.shape
            cols = w * (1 + np.arange(k))[None, :] + np.arange(w)[:, None]
        else:
            cols = np.asarray(adj.cols)
        rows = np.broadcast_to(np.arange(cols.shape[0])[:, None], cols.shape)
        edge_index = np.stack([cols[mask], rows[mask]]).astype(np.int64)
        adjs.append(
            Adj(
                edge_index=edge_index,
                e_id=np.empty((0,), np.int64),
                size=(int(adj.n_src), int(adj.n_dst)),
            )
        )
    return n_id, ds.batch_size, adjs
