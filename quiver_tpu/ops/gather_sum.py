"""Masked sum of gathered rows: ``out[i] = sum_j mask[i, j] * x[cols[i, j]]``.

The aggregation of a `DenseAdj` with explicit ``cols`` (models/sage.py).
Written as ``take -> mask -> sum(axis=1)`` it makes XLA hold the k-fold
``[W * k, D]`` gather in HBM: written, copied to ``[W, k, D]`` (k is not a
multiple of the 8-row tile) and read again, to produce ``[W, D]``.

Two forms of the one sum, both adding the k masked rows of a target in the
order of `_fold_sum`:

- `_slot_major_sum`: plain `jax.numpy`, the gather laid out ``[k, W, D]`` so
  that the reshape is free and the sum an elementwise add of k slabs. Runs
  off the TPU, for rows the kernel does not take, and gives the backward.
- `_fused_sum`: a Pallas TPU kernel that never holds the gather. Per block of
  destination rows the ids and the 0/1 weights sit in SMEM, ``x`` stays in
  HBM, each slot's row is one DMA into a VMEM ring, and one ``[block, D]``
  sum is stored. Mosaic cannot slice one row out of an ``(8, 128)``-tiled
  HBM array, so rows travel as ``[N, 1, D]`` (each row contiguous): XLA
  relays ``x`` out once per call, which is the price. The kernel is bound
  by the issue of its row DMAs (~18 ns a row whatever its width, TPU v5e),
  XLA's own gather by bytes: the kernel wins from ~2 KB a row and is used
  from `MIN_ROW_BYTES`. A program that holds it re-compiles the kernel each
  time it is LOADED, from the persistent cache too (~0.5 s, which is why
  the loops are rolled: unrolled they ran 5 ms a step faster and loaded in
  25-40 s), so a gather under `MIN_GATHER_BYTES` is left to XLA, which it
  costs under 2 ms (PERF.md section 6, PR 29).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

BLOCK = 512       # destination rows a grid step
SUB = 32          # destination rows a ring slot: SUB * k row DMAs a slot
RING = 3          # slots: RING - 1 being filled while one is summed (a power of two is slower)
SMEM_TILE = 1024  # a 1-D SMEM block is a whole number of these
MIN_ROW_BYTES = 4096        # narrower rows: XLA's gather is as fast or faster
MIN_GATHER_BYTES = 1 << 28  # a smaller k-fold gather costs XLA under 2 ms: no kernel load


def _import_pallas():
    global pl, pltpu
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


_pallas_import = None


def _pallas_thread() -> threading.Thread:
    global _pallas_import
    if _pallas_import is None:
        _pallas_import = threading.Thread(target=_import_pallas, name="quiver-pallas-import")
        _pallas_import.start()
    return _pallas_import


def kernel_rows(dtype, dim: int) -> bool:
    """float32 rows of whole 128-lane tiles (what a row DMA into a
    ``(1, 128)``-tiled ring can carry), wide enough for the kernel to win."""
    return dtype == jnp.float32 and dim % 128 == 0 and dim * 4 >= MIN_ROW_BYTES


def prefetch_pallas(dtype, dim: int) -> None:
    """Start importing `jax.experimental.pallas` on a thread, if rows of this
    kind are the kernel's. The import takes about a second (it brings the GPU
    dialects along) and nothing but this kernel needs it: `Feature` calls
    this as it uploads a table of such rows, so that the second is paid beside
    the upload and the program loads (the interpreter lock is free there) and
    not on top of the first step; `_fused_sum` joins before its first trace."""
    if kernel_rows(dtype, dim):
        _pallas_thread()


def _fold_sum(rows):
    """Sum of a list of arrays as a balanced tree: the upper half folded onto
    the lower (slot j with j + P/2, P the next power of two) until one is
    left. For k = 15 rows of 1024 lanes this is, add for add, the order of
    XLA's TPU reduction of ``[W, 15, 1024]`` over its middle axis."""
    rows = list(rows)
    half = (1 << (len(rows) - 1).bit_length()) // 2
    while half:
        rows = [rows[j] + rows[j + half] if j + half < len(rows) else rows[j]
                for j in range(min(half, len(rows)))]
        half //= 2
    return rows[0]


def _slot_major_sum(x, cols, mask):
    gathered = jnp.take(x, cols.T, axis=0, mode="clip")          # [k, W, ...]
    m = mask.T.reshape(mask.T.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
    return _fold_sum([gathered[j] * m[j] for j in range(cols.shape[1])])


def _kernel(ids_ref, weight_ref, x_hbm, out_ref, ring, sem, *, k, block):
    n_sub = block // SUB

    def start(s, carry=0):
        """Row DMAs of sub-block ``s`` into its ring slot, all on one semaphore."""
        def target(r, carry):
            for j in range(k):
                pltpu.make_async_copy(x_hbm.at[pl.ds(ids_ref[(s * SUB + r) * k + j], 1)],
                                      ring.at[s % RING, pl.ds(r * k + j, 1)],
                                      sem.at[s % RING]).start()
            return carry

        return jax.lax.fori_loop(0, SUB, target, carry)

    def sub_block(s, carry):
        slot = s % RING

        @pl.when(s + RING - 1 < n_sub)
        def _():
            start(s + RING - 1)

        # one wait for the slot's SUB * k copies: a DMA semaphore counts bytes
        pltpu.make_async_copy(x_hbm.at[pl.ds(0, SUB * k)], ring.at[slot], sem.at[slot]).wait()

        def target(r, carry):
            out_ref[pl.ds(s * SUB + r, 1)] = _fold_sum(
                [ring[slot, pl.ds(r * k + j, 1)] * weight_ref[(s * SUB + r) * k + j]
                 for j in range(k)])
            return carry

        return jax.lax.fori_loop(0, SUB, target, carry)

    jax.lax.fori_loop(0, min(RING - 1, n_sub), start, 0)
    jax.lax.fori_loop(0, n_sub, sub_block, 0)


def _fused_sum(x, cols, mask, *, interpret=False):
    _pallas_thread().join()
    (n, d), (w, k) = x.shape, cols.shape
    block = min(BLOCK, -(-w // SUB) * SUB)
    n_blocks = -(-w // block)
    per_block = -(-block * k // SMEM_TILE) * SMEM_TILE

    def per_block_flat(a):  # [w, k] -> 1-D, each block's block * k entries on whole SMEM tiles
        a = jnp.pad(a, ((0, n_blocks * block - w), (0, 0))).reshape(n_blocks, block * k)
        return jnp.pad(a, ((0, 0), (0, per_block - block * k))).reshape(-1)

    smem = pl.BlockSpec((per_block,), lambda b: (b,), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        out_shape=jax.ShapeDtypeStruct((n_blocks * block, 1, d), x.dtype),
        grid=(n_blocks,),
        in_specs=[smem, smem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, 1, d), lambda b: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((RING, SUB * k, 1, d), x.dtype),
                        pltpu.SemaphoreType.DMA((RING,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="gather_masked_sum",
        interpret=interpret,
    )(per_block_flat(jnp.clip(cols, 0, n - 1).astype(jnp.int32)),
      per_block_flat(mask.astype(x.dtype)), x.reshape(n, 1, d))
    return out[:w].reshape(w, d)


def _kernel_takes(x, cols) -> bool:
    """`kernel_rows`, and enough of them for the kernel to win back its load."""
    return (x.ndim == 2 and kernel_rows(x.dtype, x.shape[1])
            and cols.size * x.shape[1] * 4 >= MIN_GATHER_BYTES)


@jax.custom_vjp
def gather_masked_sum(x: jax.Array, cols: jax.Array, mask: jax.Array) -> jax.Array:
    """``sum_j mask[i, j] * x[clip(cols[i, j])]``: ``[W, k]`` ids into
    ``x[N, ...]`` give ``[W, ...]``. An invalid slot's row is multiplied by
    zero whatever its id; the k products are added in ``x``'s dtype in the
    order of `_fold_sum`. On a TPU, rows `_kernel_takes` go through the
    fused kernel; the backward (with respect to ``x``) is the plain form's
    scatter-add."""
    if not _kernel_takes(x, cols):
        return _slot_major_sum(x, cols, mask)
    return jax.lax.platform_dependent(x, cols, mask, tpu=_fused_sum, default=_slot_major_sum)


def _fwd(x, cols, mask):
    return gather_masked_sum(x, cols, mask), (x, cols, mask)


def _bwd(saved, g):
    x, cols, mask = saved
    (dx,) = jax.linear_transpose(lambda v: _slot_major_sum(v, cols, mask), x)(g)
    return dx, None, None


gather_masked_sum.defvjp(_fwd, _bwd)


# --- attention (models/gat.py) ---------------------------------------------
# `gather_attention_sum` is the sum of attention: a weight per slot AND head that
# is a softmax of scores of the gathered rows themselves. Plain `jax.numpy`,
# `ATTENTION_BLOCK` targets at a time, so that one block's ``[k, block, H * D]``
# gather is the largest temporary; its backward gathers each block again
# instead of keeping the k-fold rows, and adds every slot's gradient (the
# weighted sum's and the score's) to its source row in ONE scatter-add: a TPU
# scatter costs 30-70 ns a row whatever the row's width, so a second one for the
# scores' ``[W * k, H]`` cost more than half as much again (PERF.md section 6,
# PR 34).

ATTENTION_BLOCK = 8192  # targets a block: 252 MB of 2 KB rows at k = 15


def _target_blocks(w: int, *arrays):
    """``[W, ...]`` arrays cut into equal blocks of at most `ATTENTION_BLOCK`
    targets, a whole number of 8-row tiles each (zero-padded: a padded
    target's slots are all masked), stacked for a scan."""
    n = -(-w // ATTENTION_BLOCK)
    block = -(-w // (8 * n)) * 8
    return [jnp.pad(a, ((0, n * block - w),) + ((0, 0),) * (a.ndim - 1))
            .reshape((n, block) + a.shape[1:]) for a in arrays]


def attention_block(rows, x_dst, valid, att, t, slope):
    """Attention of a set of targets over their k slots and themselves,
    slot-major: ``rows [k, *T, D]`` the slots' source rows, ``x_dst [*T, D]``
    the targets' own, ``t [*T]`` the targets' half of the score; ``valid``
    (which slots are real) broadcasts against ``[k, *T]`` and ``att`` (the
    source half's vector) against ``[*T, D]``, heads being some of the axes
    ``T``. A pair scores ``leaky_relu(row . att + t)``; the softmax runs in
    float32 over the valid slots and the target itself (a masked slot's share
    underflows to exactly 0); returns ``sum_j alpha_j row_j + alpha_self
    x_dst`` as ``[*T, D]``, in the rows' dtype."""
    att = att.astype(rows.dtype)
    pre = (rows * att).sum(axis=-1) + t
    pre_self = (x_dst * att).sum(axis=-1) + t
    e = jnp.where(valid, jax.nn.leaky_relu(pre, slope), jnp.asarray(-1e9, pre.dtype))
    e = jnp.concatenate([e, jax.nn.leaky_relu(pre_self, slope)[None]], axis=0)
    alpha = jax.nn.softmax(e.astype(jnp.float32), axis=0).astype(rows.dtype)
    return (alpha[:-1, ..., None] * rows).sum(axis=0) + alpha[-1, ..., None] * x_dst


def _tiles(a, h):
    """``[..., B, H * D]`` rows as ``[..., B / 8, H, 8, D]``: on a TPU the
    ``(8, 128)`` tiles of 2-D rows in the order memory holds them, so that for
    D = 128 the view is a bitcast and a head is an axis all the same (a
    reshape to ``[..., B, H, D]`` relays every byte: 7 ms a pass of the IGB
    cell's 2.26 GB)."""
    return a.reshape(a.shape[:-2] + (a.shape[-2] // 8, 8, h, -1)).swapaxes(-3, -2)


def _rows(a):
    """`_tiles` undone: ``[..., B / 8, H, 8, D]`` as ``[..., B, H * D]``."""
    a = a.swapaxes(-3, -2)
    return a.reshape(a.shape[:-4] + (a.shape[-4] * 8, -1))


def _tiled_block(x, c, m, x_dst, t_b, att):
    """`attention_block`'s arguments for one block of targets, in `_tiles`'
    arrangement: ids ``c [B, k]`` into ``x``, mask ``m [B, k]``, the targets'
    rows and score halves. Returns (clipped ids ``[k, B]``, arguments)."""
    h = att.shape[0]
    ids = jnp.clip(c.T, 0, x.shape[0] - 1)
    return ids, (_tiles(jnp.take(x, ids, axis=0), h), _tiles(x_dst, h),
                 m.T.reshape(m.shape[1], -1, 1, 8), att[:, None, :],
                 t_b.reshape(-1, 8, h).swapaxes(-2, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gather_attention_sum(x, cols, mask, att, t, slope):
    """`attention_block` of every target over ``x[clip(cols)]``: ``[W, k]`` ids
    into ``x[N, H * D]`` (heads side by side in a row, the targets its first W
    rows; ``att [H, D]`` says how a row splits) and the targets' score halves
    ``t [W, H]`` give ``[W, H * D]``. The ``[W, k, H, D]`` rows are never laid
    out whole, forward or backward. Rows are gathered and their gradients
    scattered as 2-D rows: a TPU scatter-add into ``[N, H * D]`` costs 45 ns a
    row, into ``[N, H, D]`` 73."""
    w = cols.shape[0]

    def block(args):
        _, tiled = _tiled_block(x, *args, att)
        return _rows(attention_block(*tiled, slope))

    out = jax.lax.map(block, tuple(_target_blocks(w, cols, mask, x[:w], t)))
    return out.reshape(-1, x.shape[1])[:w]


def _attention_fwd(x, cols, mask, att, t, slope):
    return gather_attention_sum(x, cols, mask, att, t, slope), (x, cols, mask, att, t)


def _attention_bwd(slope, saved, g):
    x, cols, mask, att, t = saved
    w = cols.shape[0]

    def block(carry, args):
        dx, datt = carry
        ids, (rows, x_dst, valid, att_t, t_b) = _tiled_block(x, *args[:-1], att)
        _, vjp = jax.vjp(lambda rows, xd, a, tb: attention_block(rows, xd, valid, a, tb, slope),
                         rows, x_dst, att_t, t_b)
        drows, dx_dst, datt_b, dt_b = vjp(_tiles(args[-1], att.shape[0]))
        return ((dx.at[ids].add(_rows(drows)), datt + datt_b[:, 0]),
                (_rows(dx_dst), dt_b.swapaxes(-2, -1).reshape(-1, att.shape[0])))

    (dx, datt), (dx_dst, dt) = jax.lax.scan(
        block, (jnp.zeros_like(x), jnp.zeros_like(att)),
        tuple(_target_blocks(w, cols, mask, x[:w], t, g)))
    dx = dx.at[:w].add(dx_dst.reshape(-1, x.shape[1])[:w])
    return dx, None, None, datt, dt.reshape((-1,) + t.shape[1:])[:w]


gather_attention_sum.defvjp(_attention_fwd, _attention_bwd)
