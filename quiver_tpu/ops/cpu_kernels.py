"""Host-side (CPU) sampling engine.

TPU-native replacement for the reference's two host/graph-too-big paths:

- ``quiver<T, CPU>`` OpenMP-style sampler (include/quiver/quiver.cpu.hpp:57-102:
  parallel degree pass + per-seed ``std::sample``) -> the native C++ engine in
  ``quiver_tpu/csrc/quiver_cpu.cpp`` (std::thread parallel, per-thread
  mt19937, partial Fisher-Yates), loaded via ctypes;
- the UVA mode (GPU kernels reading pinned host memory,
  quiver.cu.hpp:16-26) -> "HOST" mode: the graph stays in host DRAM, this
  engine samples it, and padded batches stream to the TPU. TPUs cannot map
  host memory into kernels, so host-side sampling + async H2D is the
  replacement (SURVEY.md section 7.3 item 2).

The library is built from ``csrc/quiver_cpu.cpp`` whenever it is missing or
older than that source, so what is loaded always matches the committed code.
HOST-mode sampling needs it and fails loudly without it (`require_native`);
the row gather and the reindex keep numpy mirrors that tests compare against.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SENTINEL = np.iinfo(np.int64).max

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_SO = os.path.join(_CSRC, "libquiver_cpu.so")
_SRC = os.path.join(_CSRC, "quiver_cpu.cpp")

_LIB = None
_LIB_TRIED = False
# what the loader did in this process: seconds spent building (None = the
# library on disk was already newer than the source) and, when the build or
# the load failed, why
_BUILD: Dict[str, object] = {"build_s": None, "error": None}

_VP, _I64 = ctypes.c_void_p, ctypes.c_int64
_SAMPLE_ARGS = [_VP, _VP, _I64, _VP, _I64, _I64, ctypes.c_uint64, _VP, _VP]
# name -> argtypes (every entry point returns void). indptr/indices/seeds/
# ids are int64*, masks uint8*, feature rows raw bytes
_ABI = {
    # indptr, indices, num_nodes, seeds, batch, k, rng seed, out nbrs [B*k],
    # out valid [B*k]
    "qt_sample_layer": _SAMPLE_ARGS,
    # same, with float32 weights (CSR edge order) inserted third
    "qt_sample_layer_weighted": _SAMPLE_ARGS[:2] + [_VP] + _SAMPLE_ARGS[2:],
    # src [N, D] f32, N, D, ids [B], B, out [B, D]
    "qt_gather_rows": [_VP, _I64, _I64, _VP, _I64, _VP],
    # src bytes, N rows, row bytes, ids [B], B, out bytes
    "qt_gather_rows_bytes": [_VP, _I64, _I64, _VP, _I64, _VP],
    # head [seed_count], seed_count, nbrs [total], mask [total], total,
    # out n_id [seed_count+total], out count, out local int32 [total]
    "qt_reindex": [_VP, _I64, _VP, _VP, _I64, _VP, _VP, _VP],
}


def _build_native() -> None:
    """Build libquiver_cpu.so unless it is already newer than its source.
    The compiler writes a per-process temp name that is renamed into place,
    so concurrent builders (spawned sampler workers) never load half a
    file."""
    if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        return
    tmp = f"libquiver_cpu.so.{os.getpid()}.tmp"
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            ["make", "-C", _CSRC, f"TARGET={tmp}"],
            capture_output=True, text=True, timeout=300,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"make -C {_CSRC} failed ({proc.returncode}):\n"
                + (proc.stderr or proc.stdout)[-2000:]
            )
        os.replace(os.path.join(_CSRC, tmp), _SO)
    finally:
        if os.path.exists(os.path.join(_CSRC, tmp)):
            os.remove(os.path.join(_CSRC, tmp))
    _BUILD["build_s"] = time.perf_counter() - t0


def _load_native():
    """The loaded native library, or None when it cannot be built or loaded
    here — said once, as a warning with the compiler's own words, never
    silently."""
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    try:
        _build_native()
        lib = ctypes.CDLL(_SO)
        for name, argtypes in _ABI.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = None
        _LIB = lib
    except (OSError, RuntimeError, AttributeError, subprocess.SubprocessError) as exc:
        _BUILD["error"] = f"{type(exc).__name__}: {exc}"
        warnings.warn(
            f"native host engine unavailable ({_BUILD['error']}); host "
            "gathers fall back to numpy and HOST-mode sampling will refuse "
            "to run",
            RuntimeWarning,
            stacklevel=2,
        )
    return _LIB


def native_available() -> bool:
    return _load_native() is not None


def native_engine_info() -> Dict[str, object]:
    """Which host engine this process runs: ``native`` (bool), ``build_s``
    (seconds spent building it here, None when the library on disk was
    already newer than the source) and ``error`` (why it is unavailable)."""
    return {"native": native_available(), **_BUILD}


def require_native(what: str):
    """The native library, for a path that must not run without it: the
    numpy stand-in for HOST-mode sampling is a per-row Python loop that
    takes minutes at products scale, so quietly carrying on would hide the
    engine the caller asked for."""
    lib = _load_native()
    if lib is None:
        raise RuntimeError(
            f"{what} needs the native host engine (make -C quiver_tpu/csrc): "
            f"{_BUILD['error']}"
        )
    return lib


def host_reindex(
    seeds: np.ndarray,
    seed_count: int,
    nbrs: np.ndarray,
    mask: np.ndarray,
) -> Tuple[np.ndarray, int, np.ndarray, np.ndarray]:
    """Host mirror of :func:`quiver_tpu.ops.reindex.local_reindex`: returns
    (n_id_unpadded, count, local_nbrs [S,k], nbr_valid). Valid seeds keep
    slots 0..seed_count-1 VERBATIM (duplicates included, reference
    reindex.cu.hpp min-index contract: lookups resolve to the first slot
    holding a value); unique new neighbors follow in ascending-id order —
    the same contract as the device op, so outputs are bit-identical."""
    S, k = nbrs.shape
    seeds = np.asarray(seeds, np.int64)
    head = seeds[:seed_count]
    lib = _load_native()
    if lib is not None:
        total = S * k
        head_c = np.ascontiguousarray(head, np.int64)
        nbrs_c = np.ascontiguousarray(nbrs, np.int64)
        mask_c = np.ascontiguousarray(mask, np.uint8)
        n_id_buf = np.empty(seed_count + total, np.int64)
        count_buf = np.zeros(1, np.int64)
        local = np.empty(total, np.int32)
        lib.qt_reindex(
            head_c.ctypes.data, seed_count, nbrs_c.ctypes.data,
            mask_c.ctypes.data, total, n_id_buf.ctypes.data,
            count_buf.ctypes.data, local.ctypes.data,
        )
        count = int(count_buf[0])
        return n_id_buf[:count], count, local.reshape(S, k), mask
    nbr_vals = nbrs[mask]
    new = np.setdiff1d(nbr_vals, head)  # sorted unique, seed values excluded
    count = seed_count + new.shape[0]
    n_id = np.concatenate([head, new])

    # canonical id: first seed slot holding the value, else the rank slot
    local_new = seed_count + np.clip(
        np.searchsorted(new, nbrs), 0, max(new.shape[0] - 1, 0)
    )
    if seed_count > 0:
        uq_s, first_slot = np.unique(head, return_index=True)
        pc = np.clip(np.searchsorted(uq_s, nbrs), 0, uq_s.shape[0] - 1)
        in_seeds = uq_s[pc] == nbrs
        local = np.where(in_seeds, first_slot[pc], local_new)
    else:
        local = local_new
    local_nbrs = np.where(mask, local, 0).astype(np.int32)
    return n_id, count, local_nbrs, mask


class HostSampler:
    """Stateful host engine bound to one CSR graph (reference
    ``CPUQuiver``, srcs/cpp/src/quiver/quiver.cpp:11-38).

    ``weights`` (optional, float32, CSR edge order — e.g.
    ``CSRTopo.edge_weights``) switches every draw to the weighted k-subset
    engine (`qt_sample_layer_weighted`, same Efraimidis-Spirakis/Gumbel
    distribution as the device op). Construction fails without the native
    library (`require_native`)."""

    def __init__(
        self,
        indptr: np.ndarray,
        indices: np.ndarray,
        weights: Optional[np.ndarray] = None,
    ):
        self.indptr = np.ascontiguousarray(indptr, np.int64)
        self.indices = np.ascontiguousarray(indices, np.int64)
        self._lib = require_native("HOST-mode sampling (HostSampler)")
        self.weights = None
        if weights is not None:
            self.weights = np.ascontiguousarray(weights, np.float32)
            if self.weights.shape[0] != self.indices.shape[0]:
                raise ValueError(
                    f"weights has {self.weights.shape[0]} entries for "
                    f"{self.indices.shape[0]} edges"
                )

    @property
    def node_count(self) -> int:
        return self.indptr.shape[0] - 1

    def sample_layer(self, seeds: np.ndarray, k: int, seed: int):
        seeds = np.ascontiguousarray(seeds, np.int64)
        B = seeds.shape[0]
        nbrs = np.empty((B, k), np.int64)
        valid_u8 = np.empty((B, k), np.uint8)
        # one arg list for both ABIs: the weighted entry point takes the
        # identical signature with the weights pointer inserted third
        args = [
            self.indptr.ctypes.data,
            self.indices.ctypes.data,
            self.node_count,
            seeds.ctypes.data,
            B,
            k,
            ctypes.c_uint64(seed),
            nbrs.ctypes.data,
            valid_u8.ctypes.data,
        ]
        if self.weights is not None:
            args.insert(2, self.weights.ctypes.data)
            self._lib.qt_sample_layer_weighted(*args)
        else:
            self._lib.qt_sample_layer(*args)
        return nbrs, valid_u8.astype(bool)

    def sample_multilayer(
        self,
        seeds: np.ndarray,
        sizes: Sequence[int],
        seed: int,
        caps: Optional[Sequence[Optional[int]]] = None,
    ) -> Tuple[np.ndarray, int, List[Dict]]:
        """Multi-hop sample with the same static padding as the device path
        (single width source: `quiver_tpu.ops.sample.pad_widths`)."""
        from .sample import pad_widths

        B = seeds.shape[0]
        widths = pad_widths(B, sizes, caps)
        width = B
        cur = np.ascontiguousarray(seeds, np.int64)
        cur_count = B
        adjs: List[Dict] = []
        for l, k in enumerate(sizes):
            # sample only the valid prefix; pad the rest
            nbrs_v, valid_v = self.sample_layer(cur[:cur_count], k, seed + l * 1000003)
            nbrs = np.zeros((width, k), np.int64)
            mask = np.zeros((width, k), bool)
            nbrs[:cur_count] = nbrs_v
            mask[:cur_count] = valid_v
            n_id, count, local_nbrs, mask = host_reindex(cur, cur_count, nbrs, mask)
            new_width = widths[l + 1]
            if count > new_width:
                n_id = n_id[:new_width]
                count = new_width
                mask = mask & (local_nbrs < new_width)
            adjs.append(
                dict(cols=local_nbrs, mask=mask, n_src=count, n_dst=cur_count)
            )
            cur = np.full(new_width, SENTINEL, np.int64)
            cur[:count] = n_id
            cur_count = count
            width = new_width
        return cur, cur_count, adjs

    def gather_rows(self, table: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Parallel host feature gather (cold-tier analog of
        quiver_tensor_gather's host-pointer branch, shard_tensor.cu.hpp:44-55);
        dtype-agnostic via the byte-row engine — see module-level
        :func:`gather_rows`."""
        return gather_rows(table, ids)


def gather_rows(table: np.ndarray, ids: np.ndarray, out: np.ndarray = None) -> np.ndarray:
    """Module-level host gather using the native lib when possible.
    ``out`` (C-contiguous, the table's dtype and width, at least
    ``len(ids)`` rows) takes the rows in its first ``len(ids)`` rows and is
    returned: a staging block a caller keeps is then not allocated anew,
    and faulted in anew, on every batch.

    Dtype-agnostic: any C-contiguous 2-D table goes through the native
    byte-row engine (`qt_gather_rows_bytes`) — bf16 cold tiers included
    (the reference's gather kernel is float32-only,
    quiver_feature.cu:65-69). Out-of-range ids (negative or >= N) return
    zero rows — one contract on EVERY path: the native byte/f32 engines
    zero-fill in C, and the numpy fallback (non-contiguous or object
    tables, or no native library) masks invalid ids and zeroes their rows."""
    lib = _load_native()
    ids = np.ascontiguousarray(ids, np.int64)
    plain = (
        table.ndim == 2
        and table.flags.c_contiguous
        and not table.dtype.hasobject  # object rows are PyObject* — memcpy
        #                                would skip refcounting (crash at GC)
    )
    if out is not None:
        if not (out.flags.c_contiguous and out.dtype == table.dtype
                and out.shape[1:] == table.shape[1:] and out.shape[0] >= ids.shape[0]):
            raise ValueError("out must be C-contiguous [>= len(ids), D] of the table's dtype")
        if lib is None or not plain:
            out[: ids.shape[0]] = gather_rows(table, ids)
            return out
    if lib is not None and plain:
        if out is None:
            out = np.empty((ids.shape[0], table.shape[1]), table.dtype)
        lib.qt_gather_rows_bytes(
            table.ctypes.data,
            table.shape[0],
            table.shape[1] * table.itemsize,
            ids.ctypes.data,
            ids.shape[0],
            out.ctypes.data,
        )
        return out
    # numpy fallback: enforce the same zero-row contract as the native
    # paths (fancy indexing would instead raise on ids >= N and silently
    # wrap negative ids to end-relative rows)
    if table.shape[0] == 0:
        # degenerate zero-row table: every id is out of range, and the
        # np.where(ok, ids, 0) trick below would still index row 0 of an
        # empty table (IndexError) where the native engines zero-fill
        return np.zeros((ids.shape[0], table.shape[1]), table.dtype)
    ok = (ids >= 0) & (ids < table.shape[0])
    if ok.all():
        return np.ascontiguousarray(table[ids])
    out = table[np.where(ok, ids, 0)]
    out[~ok] = 0
    return out
