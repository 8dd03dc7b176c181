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
