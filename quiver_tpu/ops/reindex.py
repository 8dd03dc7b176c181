"""Dedup + local-id rewrite ("reindex") with static shapes.

Re-design of the reference's GPU ordered hash table
(``include/quiver/reindex.cu.hpp``: DeviceOrderedHashTable atomicCAS insert
keeping the *minimum input index* per key, reindex.cu.hpp:120-139) and the
``reindex_kernel``/``FillWithDuplicates`` pipeline (quiver_sample.cu:202-255,
18-63).

The contract the reference establishes (and PyG relies on):

- ``n_id[:num_seeds] == seeds`` — seeds keep their slots VERBATIM, in order,
  duplicates included (reference reindex.cu.hpp writes seeds straight into
  the output; a duplicate seed still owns its slot while lookups resolve to
  the first slot holding the value);
- the remaining unique nodes follow, each exactly once;
- every sampled neighbor is rewritten to the canonical local id of its value.

The reference orders the non-seed tail by first occurrence (hash insert
order); here the tail is ordered by ascending node id instead — an
implementation detail no consumer depends on (features/labels are always
gathered *through* ``n_id``), chosen because it keeps the whole pass in
sorted space.

TPU cost/compile model (measured on v5e):

- a 1M-element sort RUNS in ~0.3-0.7 ms while a 1M scatter/gather runs in
  ~5-8 ms — so sorts are the only data-movement primitive here, including a
  key-sort standing in for the inverse permutation (never scatter);
- XLA's TPU compile time for million-element 1-D sort/cumsum/scan ops is
  pathological (~12-60 s EACH), while 2-D row ops compile in ~1 s and
  identical sort signatures compile once per shape. The first sort uses
  a (key + 2 payloads) signature (it needs both origin position and seed
  slot); the second and third use a slimmer (key + 1 payload) signature —
  1/3 less data movement per pass, one extra cached compile. All running
  sums/scans are blocked into [rows, 1024] two-level form.

Per hop: three sorts (two signatures) + blocked cumsums + elementwise work,
O(W log W) with tiny constants, fully static shapes, jittable.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

_BLOCK = 1024


def _sort3(key: jax.Array, a: jax.Array, b: jax.Array):
    """Stable sort by ``key`` carrying two payloads (the first pass, which
    genuinely needs both origin position and seed slot)."""
    return lax.sort((key, a, b), num_keys=1, is_stable=True)


def _sort2(key: jax.Array, a: jax.Array):
    """Stable sort by ``key`` carrying ONE payload — the second and third
    reindex passes need only one, and the slimmer tuple moves 1/3 less
    data per pass (measured 6.7 -> 5.8 ms on the 811k hop-3 reindex;
    the extra compiled sort signature is a one-time cache entry)."""
    return lax.sort((key, a), num_keys=1, is_stable=True)


def _blocked(x: jax.Array, fill) -> Tuple[jax.Array, int]:
    """Reshape [W] -> [R, 1024], padding the tail with ``fill``."""
    W = x.shape[0]
    R = -(-W // _BLOCK)
    pad = R * _BLOCK - W
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(R, _BLOCK), W


def blocked_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive 1-D cumsum as row-cumsum + row-carry (compiles ~17x faster
    than the 1-D op at W=1M on TPU)."""
    x2, W = _blocked(x, 0)
    row = jnp.cumsum(x2, axis=1)
    carry = jnp.cumsum(row[:, -1])
    carry = jnp.concatenate([jnp.zeros((1,), carry.dtype), carry[:-1]])
    return (row + carry[:, None]).reshape(-1)[:W]


def propagate_group_start(is_start: jax.Array, val: jax.Array) -> jax.Array:
    """For each position t, the ``val`` of the latest position <= t with
    ``is_start`` set — broadcasts a group start's value down its group
    without a gather. Blockwise "latest start wins" associative scan:
    within-row pair scan, tiny cross-row carry scan, elementwise merge."""
    n = val.shape[0]
    pos = jnp.where(is_start, jnp.arange(n, dtype=jnp.int32), -1)
    pos2, _ = _blocked(pos, -1)
    val2, _ = _blocked(val, 0)

    def combine(x, y):
        px, vx = x
        py, vy = y
        take_y = py >= px
        return jnp.where(take_y, py, px), jnp.where(take_y, vy, vx)

    p_row, v_row = lax.associative_scan(combine, (pos2, val2), axis=1)
    pc, vc = lax.associative_scan(combine, (p_row[:, -1], v_row[:, -1]))
    p_prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), pc[:-1]])
    v_prev = jnp.concatenate([jnp.zeros((1,), val.dtype), vc[:-1]])
    keep = p_row >= p_prev[:, None]
    out = jnp.where(keep, v_row, v_prev[:, None])
    return out.reshape(-1)[:n]


class ReindexResult(NamedTuple):
    n_id: jax.Array        # [cap] node ids: valid seeds verbatim, then unique
                           # new neighbors ascending; sentinel padding
    count: jax.Array       # scalar int32: number of valid entries in n_id
    local_seeds: jax.Array  # [S] output slot of each seed (-1 where invalid)
    local_nbrs: jax.Array  # [S, k] canonical local id of each sampled neighbor
    nbr_valid: jax.Array   # [S, k] validity mask (propagated from sampling)


def _reindex(seeds, seed_valid, nbrs, nbr_valid):
    """The body of `local_reindex`, over any ``[R, C]`` block of neighbours
    (flattened transposed: entry (i, j) at position ``S + j*R + i``).
    Returns (n_id, count, seed_valid as bool, output slot of each seed,
    canonical local id of every position, seeds first)."""
    S = seeds.shape[0]
    W = S + nbrs.size
    idt = jnp.promote_types(seeds.dtype, nbrs.dtype)
    sentinel = jnp.asarray(jnp.iinfo(idt).max, idt)

    seed_valid = seed_valid.astype(bool)
    # output slot of each valid seed (compacted; identity for prefix-valid)
    seed_slot = blocked_cumsum(seed_valid.astype(jnp.int32)) - 1
    n_seed = seed_valid.sum().astype(jnp.int32)

    # Flatten [S, k] TRANSPOSED: XLA's TPU compile time for a [big, tiny]
    # row-major flatten is pathological (~40 s at S=180k, k=5 — a lane-tile
    # relayout), while [k, S] -> flat is layout-preserving (<1 s). Neighbor
    # (i, j) lands at position S + j*S + i; order within the flat array is
    # irrelevant to the contract (ties resolve by slot payload, not
    # position).
    all_nodes = jnp.concatenate([
        jnp.where(seed_valid, seeds.astype(idt), sentinel),
        jnp.where(nbr_valid, nbrs.astype(idt), sentinel).T.reshape(-1),
    ])
    pos = jnp.arange(W, dtype=jnp.int32)
    # payload 2: a seed's output slot, or S for neighbors/invalid
    slotpay = jnp.concatenate([
        jnp.where(seed_valid, seed_slot, S),
        jnp.full((nbrs.size,), S, jnp.int32),
    ])
    sv, order, spay = _sort3(all_nodes, pos, slotpay)

    is_start = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
    valid_sorted = sv != sentinel
    from_seed = order < S

    # new unique = group start that is a (valid) neighbor (stable sort puts
    # any seed with the value first); slots follow the seed block in sorted
    # (ascending id) order — rank is a cumsum, not a second sort
    new_unique = is_start & valid_sorted & ~from_seed
    rank = blocked_cumsum(new_unique.astype(jnp.int32)) - 1
    id_if_start = jnp.where(from_seed, spay, n_seed + rank)
    canonical = propagate_group_start(is_start, id_if_start)

    # back to input order: sort by original position (the inverse
    # permutation as a key-sort — scatters are ~15x a sort on TPU)
    _, local_all = _sort2(order, canonical)
    # n_id: sort values by output slot (valid seeds -> their slot, new
    # uniques -> their rank slot, everything else -> past the end)
    outkey = jnp.where(
        valid_sorted & from_seed,
        spay,
        jnp.where(new_unique, n_seed + rank, W),
    )
    outval = jnp.where(outkey < W, sv, sentinel)
    _, n_id = _sort2(outkey, outval)

    count = n_seed + new_unique.sum().astype(jnp.int32)
    return n_id, count, seed_valid, seed_slot, local_all


@functools.partial(jax.jit, static_argnames=())
def local_reindex(
    seeds: jax.Array,
    seed_valid: jax.Array,
    nbrs: jax.Array,
    nbr_valid: jax.Array,
) -> ReindexResult:
    """Build ``n_id`` (valid seeds verbatim, then unique new neighbors
    ascending) and rewrite neighbors to canonical local ids.

    Matches ``TorchQuiver::reindex_single`` semantics
    (quiver_sample.cu:305-357) including duplicate seeds: each valid seed
    keeps its own slot, lookups resolve to the first slot with the value.

    ``seeds`` is [S]; ``nbrs`` is [S, k]. cap = S + S*k.
    """
    S = seeds.shape[0]
    k = nbrs.shape[1]
    n_id, count, seed_valid, seed_slot, local_all = _reindex(seeds, seed_valid, nbrs, nbr_valid)
    return ReindexResult(
        n_id=n_id,
        count=count,
        local_seeds=jnp.where(seed_valid, seed_slot, -1),
        local_nbrs=local_all[S:].reshape(k, S).T,
        nbr_valid=nbr_valid,
    )


def segment_reindex(seeds: jax.Array, seed_valid: jax.Array, flat: jax.Array,
                    flat_valid: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`local_reindex` over a flat ``[M]`` list of neighbours that need not
    come ``k`` to a seed: the draws of several relations into one node type
    (`pyg.hetero_sampler`). ``seeds`` ([S], S may be 0) keep their slots
    verbatim as the prefix of ``n_id``. Returns (n_id ``[S + M]``, count,
    the canonical local id of each entry of ``flat``)."""
    n_id, count, _, _, local_all = _reindex(seeds, seed_valid, flat[:, None], flat_valid[:, None])
    return n_id, count, local_all[seeds.shape[0]:]


def reindex_single(
    seeds: jax.Array, inputs: jax.Array, counts=None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Free-function analog of the reference's standalone ``reindex_single``
    (quiver_sample.cu:305-357): given seeds and their sampled neighbors,
    return (n_id, count, local_ids_of_inputs).

    ``inputs`` is either a padded ``[S, k]`` matrix, or the reference's
    FLAT ragged concatenation — in which case ``counts`` (neighbors per
    seed, the shape the reference call sites actually pass) is REQUIRED
    unless the flat length happens to be uniform: a flat ragged list whose
    length is coincidentally divisible by S must not be silently gridded.
    Returned local ids for a ragged input are the positions of the real
    (unpadded) entries, in input order.
    """
    S = seeds.shape[0]
    if inputs.ndim == 2:
        res = local_reindex(
            seeds, jnp.ones((S,), bool), inputs, jnp.ones(inputs.shape, bool)
        )
        return res.n_id, res.count, res.local_nbrs.reshape(-1)
    if counts is None:
        if inputs.shape[0] % S != 0:
            raise ValueError(
                f"flat ragged neighbor list (len {inputs.shape[0]}, {S} "
                f"seeds): pass counts= (neighbors per seed) — guessing a "
                f"uniform [S, k] grid would mis-assign neighbors"
            )
        flat = inputs.reshape(S, -1)
        res = local_reindex(
            seeds, jnp.ones((S,), bool), flat, jnp.ones(flat.shape, bool)
        )
        return res.n_id, res.count, res.local_nbrs.reshape(-1)
    counts = np.asarray(counts)
    if counts.shape[0] != S or int(counts.sum()) != inputs.shape[0]:
        raise ValueError(
            f"counts {counts.shape}/{int(counts.sum())} inconsistent with "
            f"{S} seeds and {inputs.shape[0]} flat neighbors"
        )
    k = max(int(counts.max()), 1) if S else 1
    flat_np = np.asarray(inputs)
    padded = np.zeros((S, k), flat_np.dtype)
    mask = np.zeros((S, k), bool)
    off = 0
    for i, c in enumerate(counts):
        padded[i, : int(c)] = flat_np[off : off + int(c)]
        mask[i, : int(c)] = True
        off += int(c)
    res = local_reindex(seeds, jnp.ones((S,), bool), jnp.asarray(padded), jnp.asarray(mask))
    return res.n_id, res.count, np.asarray(res.local_nbrs)[np.asarray(mask)]
