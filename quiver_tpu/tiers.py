"""Disk-backed cold tier + sketch-driven adaptive placement (round 14).

The tier stack so far stopped at host DRAM — the capacity wall "millions
of users" hits first. The reference spanned its hierarchy to mmap'd disk
(PAPER.md L2/L4: ``quiver<T,CPU>`` + the ShardTensor CPU slice); the
PAPERS.md entries "GPU Initiated Direct Storage Accesses" (2306.16384)
and PyTorch-Direct (2101.07956) are the same lever. This module is the
TPU-native version, in two halves:

1. **A fourth storage tier**: :class:`DiskShard` — a flat-file ``.npy``
   row shard read through ``np.memmap`` (page-cache-friendly) and an
   optional :class:`quiver_tpu.pipeline.AsyncReadPool` (the same
   one-worker-per-stage thread machinery the train pipeline runs on,
   widened to a bounded pool: disk reads are the one stage that scales
   with parallel outstanding requests). `ShardTensor.append_disk` hangs
   it under the existing shard book as a static tail; rows are stored at
   the STORE's dtype, so a `QuantizedFeature`'s disk tier holds int8 —
   cold rows are encoded on disk AND on the wire.

2. **Adaptive placement**: :class:`TierStore` — HBM cache table + host
   DRAM cache + full disk backing, with a host-side
   :class:`TierPlacement` map (stored row -> tier, slot). Gathers stay
   GATHER-ONLY (the placement map is computed on host; per-tier gathers
   scatter-merge into the output exactly like `ShardTensor.__getitem__`
   — no scatter builds of big arrays per gather, PERF.md (earlier claims)).
   :func:`plan_adaptive` turns the round-13 frequency sketch
   (`WorkloadMonitor.promotion_candidates`) into a bounded
   :class:`PlacementPlan`; `TierStore.apply` executes it in batches
   (demotions free slots, promotions batch-read the backing file and
   land as ONE device row-scatter per batch — the "stage host-side, swap
   device tiles in batches" discipline). The serve engines fence the
   apply exactly like ``update_params`` (drain in-flight flushes, bump a
   placement version, invalidate moved rows' embedding-cache entries).

Bit-parity contract: every row's bytes live on disk permanently (the
backing file is the full table), so placement NEVER changes a gathered
byte — promotion copies, demotion just edits the map. A frozen placement
replays bit-identically, and a run straddling a promotion batch still
serves bit-identical logits (pinned in tests/test_tiers.py).

Module imports: `shard_tensor` only (leaf-ward); the read pool and the
serve engines import lazily, so `feature`/`pipeline`/`serve` can all
reach this module without a cycle.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .shard_tensor import _bucket, _device_of, _gather_local, _scatter_rows

TIER_HBM = 0
TIER_HOST = 1
TIER_DISK = 2
TIER_NAMES = ("hbm", "host", "disk")

# O_DIRECT reads must be aligned to the device's logical block size in
# offset, length AND buffer address; 4096 covers every common device
# (512e drives accept it too). Anonymous mmap buffers are page-aligned,
# which is what makes the direct path possible from Python at all.
DIRECT_ALIGN = 4096


def drop_page_cache(path: str) -> bool:
    """Ask the kernel to evict ``path``'s pages from the page cache
    (``posix_fadvise(DONTNEED)`` over the whole file) — the portable
    page-cache defeat for real-disk measurement when the filesystem
    refuses O_DIRECT. Best-effort: returns False (instead of raising)
    on platforms without the syscall, so probes can record WHICH method
    actually ran."""
    if not hasattr(os, "posix_fadvise"):
        return False
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return False
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        return True
    except OSError:
        return False
    finally:
        os.close(fd)


def o_direct_supported(path: str) -> bool:
    """Whether ``path``'s filesystem accepts an O_DIRECT aligned read —
    probed by actually doing one (overlayfs/tmpfs commonly refuse with
    EINVAL; the only honest answer is empirical). The probe reads the
    first aligned block into a page-aligned anonymous mmap buffer."""
    if not hasattr(os, "O_DIRECT"):
        return False
    import mmap as _mmap

    try:
        fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
    except OSError:
        return False
    try:
        buf = _mmap.mmap(-1, DIRECT_ALIGN)
        try:
            return os.preadv(fd, [buf], 0) >= 0
        finally:
            buf.close()
    except OSError:
        return False
    finally:
        os.close(fd)


class DiskShard:
    """Flat-file ``[R, D]`` row shard on disk (``.npy`` format, read
    through ``np.memmap``).

    ``read_rows`` is the only read surface: local row ids in, a fresh
    C-contiguous array out. With a pool the read is split into chunks
    that run on the pool's workers concurrently — each chunk is an
    independent page-cache/disk read, which is where parallelism
    actually pays (a single thread serializes the page faults).
    Out-of-range ids raise loudly: unlike lookup padding (which the
    callers mask BEFORE reaching the disk tier), a bad local id here
    means a corrupt placement map, not padding.

    ``direct=True`` (round 18, real-disk measurement) reads through an
    ``O_DIRECT`` descriptor instead of the memmap: every ``read_block``
    is an aligned pread into a page-aligned buffer, bypassing the page
    cache entirely — the honest cold-read path a 10x-DRAM claim must be
    measured on. Bytes are identical to the memmap path by construction
    (same file, same offsets); only the cache behavior differs. Raises
    at open when the filesystem refuses O_DIRECT (probe with
    :func:`o_direct_supported` first; fall back to
    :func:`drop_page_cache` between measurement legs).
    """

    def __init__(self, path: str, direct: bool = False):
        self.path = path
        # mmap_mode='r': reads hit the page cache; nothing is resident
        # until touched, which is the whole point of the tier
        self._mm = np.load(path, mmap_mode="r")
        if self._mm.ndim != 2:
            raise ValueError(f"disk shard {path} must be [R, D]")
        self.direct = bool(direct)
        self._fd = None
        if self.direct:
            if not hasattr(os, "O_DIRECT"):
                raise OSError("platform has no O_DIRECT")
            # raises OSError where the filesystem refuses — callers that
            # want a fallback probe o_direct_supported() first
            self._fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
            if not o_direct_supported(path):
                os.close(self._fd)
                self._fd = None
                raise OSError(f"filesystem refuses O_DIRECT reads: {path}")
            # the npy payload offset: np.load's memmap records where the
            # header ends — direct preads address rows relative to it
            self._data_off = int(self._mm.offset)
            # PER-THREAD descriptors for pooled reads: concurrent preads
            # on one shared fd serialize in the kernel (measured SLOWER
            # than single-threaded on this box's filesystem), so each
            # pool worker reads through its own fd. _fd above stays the
            # probe/owner descriptor; _all_fds tracks every lazy open
            # for close.
            self._tls = threading.local()
            self._all_fds: List[int] = [self._fd]
            self._fd_lock = threading.Lock()

    def _direct_fd(self) -> int:
        fd = getattr(self._tls, "fd", None)
        if fd is None:
            fd = os.open(self.path, os.O_RDONLY | os.O_DIRECT)
            self._tls.fd = fd
            with self._fd_lock:
                self._all_fds.append(fd)
        return fd

    def _direct_buf(self, nbytes: int) -> np.ndarray:
        """This thread's persistent block-address-aligned read buffer,
        grown (never shrunk) to ``nbytes``."""
        buf = getattr(self._tls, "buf", None)
        if buf is None or buf.shape[0] < nbytes:
            base = np.empty(nbytes + DIRECT_ALIGN, np.uint8)
            shift = (-base.ctypes.data) % DIRECT_ALIGN
            buf = base[shift: shift + nbytes]
            self._tls.buf_base = base  # keeps the allocation alive
            self._tls.buf = buf
        return buf

    def __del__(self):
        fds = getattr(self, "_all_fds", None)
        if fds is None:
            fds = [f for f in (getattr(self, "_fd", None),)
                   if f is not None]
        for fd in fds:
            try:
                os.close(fd)
            except OSError:
                pass

    # contiguous aligned spans merge into one pread up to this many
    # bytes: amortizes the per-syscall cost (and the Python dispatch
    # around it, which holds the GIL) without unbounded buffer growth
    DIRECT_RUN_BYTES = 1 << 20

    def _read_block_direct(self, ids: np.ndarray) -> np.ndarray:
        """Aligned O_DIRECT gather, span-grouped: rows are bucketed by
        the aligned block span enclosing them, spans dedup (rows smaller
        than a block share one read), and CONTIGUOUS spans merge into a
        single pread up to ``DIRECT_RUN_BYTES``. A naive per-row pread
        loop is GIL-bound from Python — per-row slicing serializes pool
        workers and 128-byte rows re-read the same 4 KiB block 32 times
        — so grouping is what makes the direct path pool-parallel at
        all. Reads land in a PERSISTENT per-thread block-aligned buffer
        (O_DIRECT requires the buffer ADDRESS aligned too): a fresh
        anonymous mmap per call would serialize pool workers on the
        process mmap lock and pay a TLB shootdown at every munmap —
        measured 4x slower across 4 workers than one thread. Never
        touches the page cache; bytes equal the memmap path (same file
        region)."""
        rb = self.row_bytes
        out = np.empty((ids.shape[0], self._mm.shape[1]), self._mm.dtype)
        row_u8 = out.view(np.uint8).reshape(ids.shape[0], rb)
        offs = self._data_off + ids.astype(np.int64) * rb
        a0 = (offs // DIRECT_ALIGN) * DIRECT_ALIGN           # span start
        a1 = (-(-(offs + rb) // DIRECT_ALIGN)) * DIRECT_ALIGN  # span end
        # merge the sorted spans into contiguous runs, recording which
        # run each row landed in (a span near the cap boundary may start
        # inside run i yet belong to run i+1 — membership must be
        # tracked, not re-derived from positions)
        order = np.argsort(a0, kind="stable")
        runs: List[Tuple[int, int]] = []        # (run_start, run_end)
        rows_of: List[List[int]] = []           # run -> row indices
        for j in order.tolist():
            s, e = int(a0[j]), int(a1[j])
            if (runs and s <= runs[-1][1]
                    and e - runs[-1][0] <= self.DIRECT_RUN_BYTES):
                if e > runs[-1][1]:
                    runs[-1] = (runs[-1][0], e)
            else:
                # new run; when the cap split a contiguous stretch the
                # boundary block re-reads, which is correct just not free
                runs.append((s, e))
                rows_of.append([])
            rows_of[-1].append(j)
        buf_bytes = max((e - s for s, e in runs), default=DIRECT_ALIGN)
        buf_np = self._direct_buf(buf_bytes)
        mv = memoryview(buf_np)
        fd = self._direct_fd()  # this thread's own descriptor
        for (s, e), members in zip(runs, rows_of):
            got = os.preadv(fd, [mv[: e - s]], s)
            for j in members:
                # the DATA extent is what must be covered: the last
                # row's aligned span may exceed EOF, where pread
                # honestly returns only what exists
                lo = int(offs[j]) - s
                if lo + rb > got:
                    raise OSError(
                        f"short O_DIRECT read at row {int(ids[j])}: "
                        f"run [{s}, {e}) got {got}"
                    )
                row_u8[j] = buf_np[lo: lo + rb]
        return out

    @classmethod
    def create(cls, path: str, rows: np.ndarray) -> "DiskShard":
        """Write ``rows`` as a ``.npy`` flat file and open it mmap'd.
        The array is written at ITS dtype — an int8 store spills int8."""
        rows = np.ascontiguousarray(rows)
        if rows.ndim != 2:
            raise ValueError("disk shard rows must be [R, D]")
        if not path.endswith(".npy"):
            path = path + ".npy"
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        np.save(path, rows)
        return cls(path)

    @property
    def shape(self) -> Tuple[int, int]:
        return self._mm.shape

    @property
    def dtype(self) -> np.dtype:
        return self._mm.dtype

    @property
    def nbytes(self) -> int:
        """Payload bytes (rows * row_bytes; the npy header is noise)."""
        return int(self._mm.shape[0]) * self.row_bytes

    @property
    def row_bytes(self) -> int:
        return int(self._mm.shape[1]) * self._mm.dtype.itemsize

    def read_block(self, local_ids: np.ndarray) -> np.ndarray:
        """One synchronous gather (the unit of work a read pool chunks)."""
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= self._mm.shape[0]):
            raise ValueError(
                f"disk read ids outside [0, {self._mm.shape[0]}): "
                "corrupt placement map (callers mask padding before the "
                "disk tier)"
            )
        if self._fd is not None:
            return self._read_block_direct(ids)
        return np.ascontiguousarray(self._mm[ids])

    def drop_cache(self) -> bool:
        """Evict this shard's pages from the page cache (see
        :func:`drop_page_cache`); the measurement-leg reset for real-disk
        probes on filesystems without O_DIRECT."""
        return drop_page_cache(self.path)

    def read_rows(self, local_ids: np.ndarray, pool=None) -> np.ndarray:
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        if pool is None or ids.size == 0:
            return self.read_block(ids)
        return pool.gather(self.read_block, ids)


@jax.jit
def _set_rows(table: jax.Array, slots: jax.Array, rows: jax.Array) -> jax.Array:
    # padded slots point past the table; 'drop' discards them — one
    # bounded batched row-scatter per PROMOTION batch (a placement
    # update, not a per-gather build)
    return table.at[slots].set(rows, mode="drop")


class PrefetchBuffer:
    """Flush-ahead staging for disk-tier reads (round 18, ROADMAP item
    3a): the serve/train engines know a gather's row set one stage
    before the gather runs, so they ``issue()`` `AsyncReadPool` reads
    then and the gather ``take()``s the landed rows out of DRAM instead
    of waiting on the device path's critical section.

    STRICTLY OBSERVE-ONLY ON BITS: staged rows are read by the SAME
    ``read_fn`` the direct path uses (resolved at call time, so probe
    wrappers and simulated latencies apply identically), so a taken row
    is byte-identical to an unstaged read — prefetch can change WHEN a
    byte is read, never WHICH byte. A staged read that failed is simply
    not a hit: the gather falls back to the direct read and surfaces the
    same error the prefetch-off run would (error parity).

    Accounting: ``issued`` counts rows submitted to the pool (after
    dedup against in-flight stages and the ``max_rows`` bound),
    ``hits`` rows a gather consumed from staging, ``wasted`` rows
    staged but never consumed (cleared by ``cancel()`` — the fence
    hook). An optional ``listener(kind, n)`` mirrors hit/wasted counts
    into engine stats without a second source of truth.

    Thread safety: the map mutates under one small lock; futures are
    observed on cancel so a fenced-away prefetch never logs "exception
    was never retrieved" at GC (the r7/r14 error-contract discipline).
    """

    def __init__(self, read_fn: Callable[[np.ndarray], np.ndarray],
                 pool, max_rows: int = 8192):
        if pool is None:
            raise ValueError("PrefetchBuffer needs an AsyncReadPool")
        self._read_fn = read_fn
        self._pool = pool
        self.max_rows = int(max_rows)
        # local row id -> (chunk future, lane within the chunk's rows)
        self._staged: Dict[int, Tuple[object, int]] = {}
        self._lock = threading.Lock()
        self.issued = 0
        self.hits = 0
        self.wasted = 0
        self.errors = 0
        self.listener: Optional[Callable[[str, int], None]] = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._staged)

    def _emit(self, kind: str, n: int) -> None:
        if n and self.listener is not None:
            try:
                self.listener(kind, n)
            except Exception:
                pass  # observe-only: a broken tap never breaks reads

    def issue(self, local_ids: np.ndarray) -> int:
        """Submit pool reads for the not-yet-staged subset of
        ``local_ids`` (bounded by ``max_rows`` total staged); returns
        rows actually issued. Duplicate/in-flight ids are free — the
        router and its owner engines may both prefetch the same rows."""
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        if ids.size == 0:
            return 0
        # dedup WITHOUT sorting: callers pass BFS-ordered closures, and
        # when max_rows bites the truncation below must keep the nearest
        # (most-certainly-gathered) rows, not the lowest ids
        _, first = np.unique(ids, return_index=True)
        ids = ids[np.sort(first)]
        chunk = max(int(getattr(self._pool, "chunk_rows", 1024)), 1)
        read = self._read_fn
        with self._lock:
            fresh = [int(i) for i in ids if int(i) not in self._staged]
            room = self.max_rows - len(self._staged)
            if room <= 0 or not fresh:
                return 0
            fresh = fresh[:room]
            arr = np.asarray(fresh, np.int64)
            for lo in range(0, arr.shape[0], chunk):
                part = arr[lo : lo + chunk]
                fut = self._pool.submit(read, part)
                for lane, sid in enumerate(part.tolist()):
                    self._staged[sid] = (fut, lane)
            self.issued += len(fresh)
        return len(fresh)

    def staged_mask(self, local_ids: np.ndarray) -> np.ndarray:
        """Bool mask of ``local_ids`` currently staged (peek, no
        consume) — the `disk_prefetched` attribution input."""
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        with self._lock:
            staged = self._staged
            return np.fromiter(
                (int(i) in staged for i in ids), bool, ids.shape[0]
            )

    def take(self, local_ids: np.ndarray
             ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Consume the staged subset of ``local_ids``: returns
        ``(positions, rows)`` where ``positions`` indexes into
        ``local_ids`` and ``rows`` are the staged bytes (None when no
        position hit). A staged read still in flight is waited on (the
        bytes must be right; most of its latency is already hidden); a
        staged read that FAILED is dropped from the result so the caller
        re-reads directly and surfaces the prefetch-off error."""
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        with self._lock:
            if not self._staged:
                return np.empty(0, np.int64), None
            entries = []
            for j, i in enumerate(ids.tolist()):
                e = self._staged.pop(int(i), None)
                if e is not None:
                    entries.append((j, e))
        # group by chunk future: one wait + one fancy-index per CHUNK
        # (a per-row python loop here costs more than the rows at batch
        # scale — this runs inside the gather's critical section)
        by_fut: Dict[int, Tuple[object, List[int], List[int]]] = {}
        for j, (fut, lane) in entries:
            g = by_fut.get(id(fut))
            if g is None:
                g = by_fut[id(fut)] = (fut, [], [])
            g[1].append(j)
            g[2].append(lane)
        pos_parts, row_parts = [], []
        failed = 0
        for fut, js, lanes in by_fut.values():
            try:
                chunk_rows = fut.result()
            except BaseException:
                failed += len(js)
                continue
            pos_parts.append(np.asarray(js, np.int64))
            row_parts.append(chunk_rows[np.asarray(lanes)])
        hits = sum(p.shape[0] for p in pos_parts)
        self.hits += hits
        # a failed staged read is BOTH an error (diagnostic) and waste
        # (the issue bought nothing) — keeping the two ledgers in step
        # with the listener mirror, which reports it as wasted
        self.errors += failed
        self.wasted += failed
        self._emit("hit", hits)
        self._emit("wasted", failed)
        if not pos_parts:
            return np.empty(0, np.int64), None
        return np.concatenate(pos_parts), np.concatenate(row_parts)

    def take_or_read(self, local_ids: np.ndarray,
                     read_fn: Callable[[np.ndarray], np.ndarray]
                     ) -> np.ndarray:
        """Assemble ``[n, D]`` rows for ``local_ids``: staged bytes for
        the rows a prefetch landed, ``read_fn(rest)`` for the remainder
        — byte-identical either way (staged rows came through the same
        read path, earlier). THE single consume-side helper: every
        gather that can hit staging routes here, so the hit/fallback
        semantics live in one place."""
        ids = np.asarray(local_ids, np.int64).reshape(-1)
        if not len(self):
            return read_fn(ids)
        hit_pos, hit_rows = self.take(ids)
        if hit_pos.size == 0:
            return read_fn(ids)
        out = np.empty((ids.shape[0], hit_rows.shape[1]), hit_rows.dtype)
        out[hit_pos] = hit_rows
        rest = np.ones(ids.shape[0], bool)
        rest[hit_pos] = False
        if rest.any():
            out[rest] = read_fn(ids[rest])
        return out

    def cancel(self) -> int:
        """Drop every staged row (the FENCE hook — update_params /
        apply_placement / update_graph / stop all route here): cancel
        what the pool has not started, observe every future so nothing
        logs at GC, count the unconsumed rows as wasted. Returns the
        rows dropped. Never blocks on an in-flight read."""
        with self._lock:
            staged, self._staged = self._staged, {}
        if not staged:
            return 0
        seen = set()
        for fut, _ in staged.values():
            if id(fut) in seen:
                continue
            seen.add(id(fut))
            fut.cancel()
            fut.add_done_callback(
                lambda f: f.cancelled() or f.exception()
            )
        n = len(staged)
        self.wasted += n
        self._emit("wasted", n)
        return n

    def stats(self) -> Dict[str, int]:
        with self._lock:
            staged = len(self._staged)
        return {"issued": self.issued, "hits": self.hits,
                "wasted": self.wasted, "errors": self.errors,
                "staged": staged, "max_rows": self.max_rows}


def expected_closure(sampler, seeds, hops: int,
                     max_nodes: Optional[int] = None) -> np.ndarray:
    """The rows a ``hops``-layer sample of ``seeds`` can GATHER: the
    forward k-hop closure over the sampler's CURRENT graph (the
    streamed adjacency when the sampler is stream-bound, the frozen CSR
    otherwise), in BFS order so a ``max_nodes`` truncation keeps the
    nearest — most-certainly-gathered — rows. A sampled draw touches a
    SUBSET of this closure (fanouts cap each hop), which is exactly why
    prefetching it is observe-only: a superset staged early costs wasted
    reads, never wrong bytes.

    ``hops`` for an L-layer sampler is ``len(sizes)`` — one MORE than
    the cache-invalidation depth, because the final hop's frontier is
    gathered even though it is never expanded (the round-11
    closure-hops rule)."""
    seeds = np.unique(np.asarray(seeds, np.int64).reshape(-1))
    stream = getattr(sampler, "stream", None)
    if stream is not None:
        adj = stream.adj
        n = adj.n

        def expand(frontier):
            # forward expansion must honor round-21 lifecycle rewrites:
            # a node with deletions/updates answers from its override
            # list, not the base CSR slice
            return adj._expand(frontier, adj.indptr, adj.indices,
                               adj._extra, adj._override)
    else:
        topo = getattr(sampler, "csr_topo", None)
        if topo is None:
            return seeds
        indptr = np.asarray(topo.indptr)
        indices = np.asarray(topo.indices)
        n = indptr.shape[0] - 1

        def expand(frontier):
            parts = [indices[s:e] for s, e in
                     zip(indptr[frontier], indptr[frontier + 1]) if e > s]
            if not parts:
                return np.array([], np.int64)
            return np.unique(np.concatenate(parts))

    seeds = seeds[(seeds >= 0) & (seeds < n)]
    if seeds.size == 0:
        return seeds
    mask = np.zeros(n, bool)
    mask[seeds] = True
    order = [seeds]
    frontier = seeds
    for _ in range(max(int(hops), 0)):
        if frontier.size == 0:
            break
        if max_nodes is not None and sum(p.size for p in order) >= max_nodes:
            break
        nxt = expand(frontier)
        nxt = nxt[~mask[nxt]]
        if nxt.size == 0:
            break
        mask[nxt] = True
        order.append(nxt)
        frontier = nxt
    out = np.concatenate(order)
    if max_nodes is not None and out.shape[0] > max_nodes:
        out = out[:max_nodes]
    return out


class TierPlacement:
    """Host-side placement book for a 3-tier adaptive store.

    ``tier_of[stored_row]`` in {TIER_HBM, TIER_HOST, TIER_DISK};
    ``slot_of[stored_row]`` is the row's slot within its tier's cache
    table (-1 on disk — disk rows are addressed by stored id against the
    full backing file). ``hbm_slots``/``host_slots`` are the inverse
    (slot -> stored id, -1 free). Pure numpy, mutated only under the
    owner's placement fence; ``version`` bumps once per applied batch.
    """

    def __init__(self, n: int, hbm_rows: int, host_rows: int):
        if hbm_rows < 0 or host_rows < 0:
            raise ValueError("tier capacities must be >= 0")
        hbm_rows = min(hbm_rows, n)
        host_rows = min(host_rows, n - hbm_rows)
        self.n = int(n)
        self.hbm_rows = int(hbm_rows)
        self.host_rows = int(host_rows)
        self.tier_of = np.full(n, TIER_DISK, np.int8)
        self.slot_of = np.full(n, -1, np.int64)
        # prefix init: the degree/id-ordered head fills the fast tiers —
        # exactly the static placement, so a frozen adaptive store and a
        # static store start bit-and-placement identical
        self.tier_of[:hbm_rows] = TIER_HBM
        self.slot_of[:hbm_rows] = np.arange(hbm_rows)
        self.tier_of[hbm_rows : hbm_rows + host_rows] = TIER_HOST
        self.slot_of[hbm_rows : hbm_rows + host_rows] = np.arange(host_rows)
        self.hbm_slots = np.full(hbm_rows, -1, np.int64)
        self.hbm_slots[:hbm_rows] = np.arange(hbm_rows)
        self.host_slots = np.full(host_rows, -1, np.int64)
        self.host_slots[:host_rows] = np.arange(
            hbm_rows, hbm_rows + host_rows
        )
        self.version = 0

    def counts(self) -> Dict[str, int]:
        return {
            "hbm": int((self.tier_of == TIER_HBM).sum()),
            "host": int((self.tier_of == TIER_HOST).sum()),
            "disk": int((self.tier_of == TIER_DISK).sum()),
        }

    def residents(self, tier: int) -> np.ndarray:
        """Stored ids currently resident in ``tier`` (disk = everything
        not in a faster tier)."""
        return np.nonzero(self.tier_of == tier)[0]

    def _slot_table(self, tier: int) -> np.ndarray:
        return self.hbm_slots if tier == TIER_HBM else self.host_slots

    def free_slots(self, tier: int) -> np.ndarray:
        return np.nonzero(self._slot_table(tier) < 0)[0]

    def release(self, stored: int) -> None:
        """Free ``stored``'s slot (no-op on disk)."""
        t = int(self.tier_of[stored])
        if t == TIER_DISK:
            return
        self._slot_table(t)[self.slot_of[stored]] = -1
        self.tier_of[stored] = TIER_DISK
        self.slot_of[stored] = -1

    def occupy(self, stored: int, tier: int, slot: int) -> None:
        self._slot_table(tier)[slot] = stored
        self.tier_of[stored] = tier
        self.slot_of[stored] = slot

    def check(self) -> None:
        """Invariant sweep (tests; O(N))."""
        for tier in (TIER_HBM, TIER_HOST):
            tab = self._slot_table(tier)
            res = self.residents(tier)
            assert res.size == int((tab >= 0).sum()), "slot table drift"
            assert np.array_equal(
                np.sort(tab[tab >= 0]), np.sort(res)
            ), "slot table contents drift"
            slots = self.slot_of[res]
            assert np.array_equal(tab[slots], res), "inverse map drift"
        assert np.all(self.slot_of[self.tier_of == TIER_DISK] == -1)


@dataclass
class PlacementPlan:
    """An ordered batch of tier moves: ``(stored_row, dst_tier)``.
    Demotions are listed before the promotions whose slots they free;
    `TierStore.apply` executes in order and batches the data movement."""

    moves: List[Tuple[int, int]] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.moves)

    def demote(self, stored: int, dst: int = TIER_DISK) -> None:
        self.moves.append((int(stored), int(dst)))

    def promote(self, stored: int, dst: int) -> None:
        self.moves.append((int(stored), int(dst)))


def plan_adaptive(
    placement: TierPlacement,
    hot_stored: np.ndarray,
    hot_weight: np.ndarray,
    resident_weight: Callable[[np.ndarray], np.ndarray],
    max_moves: int = 64,
    min_weight: float = 2.0,
    hysteresis: float = 1.25,
) -> PlacementPlan:
    """Greedy bounded promote/demote plan from a measured hot set.

    ``hot_stored``/``hot_weight`` are the sketch's err-corrected heavy
    hitters mapped into stored-row space (unmapped entries already
    dropped); ``resident_weight(stored_ids)`` prices CURRENT residents
    (the engine answers it from the Count-Min sketch). Two passes:

    - HBM pass: hottest non-HBM candidates displace the coldest HBM
      residents, but only when ``cand_w >= max(victim_w * hysteresis,
      min_weight)`` — the hysteresis band is what keeps near-tied rows
      from ping-ponging between windows. A displaced HBM victim cascades
      to host DRAM when host has a free slot or a colder resident
      (which then drops to disk); otherwise it drops to disk.
    - Host pass: remaining disk candidates displace the coldest host
      residents under the same band.

    Each promotion costs at most 2 moves (victim out, candidate in) plus
    at most 1 cascade move; ``max_moves`` bounds the TOTAL move count,
    so an apply batch's device scatter and disk read are bounded too.
    """
    plan = PlacementPlan()
    hot_stored = np.asarray(hot_stored, np.int64).reshape(-1)
    hot_weight = np.asarray(hot_weight, np.float64).reshape(-1)
    keep = hot_weight >= min_weight
    hot_stored, hot_weight = hot_stored[keep], hot_weight[keep]
    if hot_stored.size == 0:
        return plan
    order = np.argsort(-hot_weight, kind="stable")
    hot_stored, hot_weight = hot_stored[order], hot_weight[order]
    hot_w_of = dict(zip(hot_stored.tolist(), hot_weight.tolist()))

    # victim books: (weight asc) heaps per fast tier, weights from the
    # sketch for every CURRENT resident — bounded by the tier capacities
    def victim_list(tier: int) -> List[Tuple[float, int]]:
        res = placement.residents(tier)
        if res.size == 0:
            return []
        w = np.asarray(resident_weight(res), np.float64)
        # a resident that is itself a tracked hot row keeps its (larger)
        # head estimate — never victimize a row hotter than the candidate
        for i, sid in enumerate(res.tolist()):
            if sid in hot_w_of:
                w[i] = max(w[i], hot_w_of[sid])
        order = np.argsort(w, kind="stable")
        return [(float(w[i]), int(res[i])) for i in order]

    moved: set = set()
    free_host = placement.free_slots(TIER_HOST).size
    host_victims = victim_list(TIER_HOST)
    hv_i = 0  # next coldest host victim

    def spill_to_host(victim_sid: int, victim_w: float) -> None:
        """Cascade an HBM victim: host free slot, else displace a colder
        host resident to disk, else straight to disk."""
        nonlocal free_host, hv_i
        if placement.host_rows == 0:
            plan.demote(victim_sid, TIER_DISK)
            return
        if free_host > 0:
            free_host -= 1
            plan.demote(victim_sid, TIER_HOST)
            return
        while hv_i < len(host_victims) and host_victims[hv_i][1] in moved:
            hv_i += 1
        if hv_i < len(host_victims) and host_victims[hv_i][0] < victim_w:
            w, sid = host_victims[hv_i]
            hv_i += 1
            moved.add(sid)
            plan.demote(sid, TIER_DISK)
            plan.demote(victim_sid, TIER_HOST)
        else:
            plan.demote(victim_sid, TIER_DISK)

    # -- HBM pass ---------------------------------------------------------
    if placement.hbm_rows > 0:
        hbm_victims = victim_list(TIER_HBM)
        free_hbm = placement.free_slots(TIER_HBM).size
        vi = 0
        for sid, w in zip(hot_stored.tolist(), hot_weight.tolist()):
            if len(plan) + 3 > max_moves:
                break
            if placement.tier_of[sid] == TIER_HBM or sid in moved:
                continue
            if free_hbm > 0:
                free_hbm -= 1
            else:
                while vi < len(hbm_victims) and hbm_victims[vi][1] in moved:
                    vi += 1
                if vi >= len(hbm_victims):
                    break
                vw, vsid = hbm_victims[vi]
                if w < max(vw * hysteresis, min_weight):
                    break  # victims only get hotter from here
                vi += 1
                moved.add(vsid)
                spill_to_host(vsid, vw)
            moved.add(sid)
            plan.promote(sid, TIER_HBM)

    # -- host pass --------------------------------------------------------
    if placement.host_rows > 0:
        host_victims2 = [
            (w, sid) for w, sid in victim_list(TIER_HOST) if sid not in moved
        ]
        vi = 0
        for sid, w in zip(hot_stored.tolist(), hot_weight.tolist()):
            if len(plan) + 2 > max_moves:
                break
            if sid in moved or placement.tier_of[sid] != TIER_DISK:
                continue
            if free_host > 0:
                free_host -= 1
            else:
                while vi < len(host_victims2) and host_victims2[vi][1] in moved:
                    vi += 1
                if vi >= len(host_victims2):
                    break
                vw, vsid = host_victims2[vi]
                if w < max(vw * hysteresis, min_weight):
                    break
                vi += 1
                moved.add(vsid)
                plan.demote(vsid, TIER_DISK)
            moved.add(sid)
            plan.promote(sid, TIER_HOST)
    return plan


class TierStore:
    """Adaptive 3-tier row store: HBM cache table + host DRAM cache +
    full flat-file disk backing, placed by a :class:`TierPlacement`.

    The backing file holds EVERY stored row (at the store dtype), so a
    placement move never moves truth — promotion copies disk bytes into
    a cache slot, demotion frees the slot. That is what makes placement
    bit-neutral: ``gather(ids)`` returns identical bytes under any
    placement (the parity pin in tests/test_tiers.py), and a promotion
    batch can never corrupt an in-flight gather that the engine fence
    already excluded.

    Gathers are gather-only: the per-tier split is host-computed from
    the placement map; HBM rows ride one jitted take + scatter-merge
    (the `ShardTensor.__getitem__` pattern), host+disk rows assemble
    host-side and ship as ONE padded H2D copy.
    """

    def __init__(
        self,
        backing: DiskShard,
        placement: TierPlacement,
        hbm_table: Optional[jax.Array],
        host_cache: Optional[np.ndarray],
        rank: int = 0,
        read_pool=None,
    ):
        self.backing = backing
        self.placement = placement
        self.hbm_table = hbm_table  # [hbm_rows, D] device, or None
        self.host_cache = host_cache  # [host_rows, D] numpy, or None
        self.rank = rank
        self.read_pool = read_pool
        self.dtype = np.dtype(backing.dtype)
        self.dim = int(backing.shape[1])
        # orders concurrent apply() calls ONLY. Gathers are deliberately
        # lock-free (serializing them would kill the engines' in-flight
        # overlap), so a gather racing a bare apply() can see new maps
        # over old cache bytes — callers must fence gathers against
        # placement moves, which is exactly what the serve engines'
        # `apply_placement` does (drain in-flight flushes under _seq).
        # Bare stores: treat apply() like the engines treat it — no
        # concurrent gathers.
        self._lock = threading.Lock()
        self.rows_promoted = 0
        self.rows_demoted = 0
        # round-18 flush-ahead prefetch staging (enable_prefetch);
        # strictly observe-only on bits — see PrefetchBuffer
        self.prefetch: Optional[PrefetchBuffer] = None

    @classmethod
    def build(
        cls,
        arr: np.ndarray,
        path: str,
        hbm_rows: int,
        host_rows: int,
        rank: int = 0,
        read_pool=None,
    ) -> "TierStore":
        """Spill the FULL stored table to ``path`` and seed the fast
        tiers with the prefix placement (rows [0, hbm) in HBM,
        [hbm, hbm+host) in DRAM — identical to the static split)."""
        arr = np.ascontiguousarray(arr)
        n, d = arr.shape
        backing = DiskShard.create(path, arr)
        placement = TierPlacement(n, hbm_rows, host_rows)
        hbm_rows, host_rows = placement.hbm_rows, placement.host_rows
        hbm_table = None
        if hbm_rows > 0:
            hbm_table = jax.device_put(
                jnp.asarray(arr[:hbm_rows]), _device_of(rank)
            )
        host_cache = None
        if host_rows > 0:
            # an owned COPY, never a view: promotions write into these
            # slots, and a view would silently mutate the caller's table
            host_cache = np.array(
                arr[hbm_rows : hbm_rows + host_rows], copy=True, order="C"
            )
        return cls(backing, placement, hbm_table, host_cache,
                   rank=rank, read_pool=read_pool)

    # ------------------------------------------------------------------ reads
    @property
    def n_rows(self) -> int:
        return self.placement.n

    @property
    def placement_version(self) -> int:
        return self.placement.version

    def tier_bytes(self) -> Dict[str, int]:
        """LIVE byte footprint per tier at the stored dtype — reflects
        the current placement, so a demotion batch shrinks the device
        row immediately (the honest-accounting satellite: ``device`` is
        occupied rows, never the cache capacity)."""
        row = self.dim * self.dtype.itemsize
        c = self.placement.counts()
        return {
            "device": c["hbm"] * row,
            "host": c["host"] * row,
            "disk": self.backing.nbytes,
            "device_capacity": self.placement.hbm_rows * row,
            "host_capacity": self.placement.host_rows * row,
            "row": row,
        }

    def tier_split(self, stored_ids: np.ndarray) -> Dict[str, int]:
        """Host-side per-tier row counts for a gather batch (the
        attribution the workload monitor records). Disk rows a prefetch
        already STAGED in DRAM report as ``disk_prefetched`` — the tier
        labels tell the truth about where the bytes actually come from
        (round-18 satellite), while the placement itself is unchanged."""
        ids = np.asarray(stored_ids, np.int64)
        t = self.placement.tier_of[ids]
        disk = int((t == TIER_DISK).sum())
        staged = 0
        pf = self.prefetch
        if pf is not None and disk and len(pf):
            staged = int(pf.staged_mask(ids[t == TIER_DISK]).sum())
        out = {
            "hbm": int((t == TIER_HBM).sum()),
            "host": int((t == TIER_HOST).sum()),
            "disk": disk - staged,
        }
        if staged:
            out["disk_prefetched"] = staged
        return out

    # ----------------------------------------------------------- prefetch
    def enable_prefetch(self, max_rows: int = 8192,
                        listener: Optional[Callable[[str, int], None]] = None,
                        ) -> PrefetchBuffer:
        """Attach (or retune) the flush-ahead staging buffer. Requires a
        read pool (the reads must land off the caller's thread to hide
        anything). Idempotent: a second call updates the bound/listener
        on the existing buffer so router + owner engines can share."""
        if self.read_pool is None:
            raise ValueError(
                "prefetch needs an AsyncReadPool (build the Feature with "
                "read_pool=/disk_read_workers=)"
            )
        if self.prefetch is None:
            self.prefetch = PrefetchBuffer(
                lambda ids: self.backing.read_block(ids),
                self.read_pool, max_rows=max_rows,
            )
        else:
            self.prefetch.max_rows = int(max_rows)
        if listener is not None:
            self.prefetch.listener = listener
        return self.prefetch

    def prefetch_rows(self, stored_ids) -> int:
        """Issue flush-ahead reads for the DISK-resident subset of
        ``stored_ids`` (no-op rows already in a fast tier or already
        staged). Returns rows issued. Call `enable_prefetch` first."""
        if self.prefetch is None:
            return 0
        ids = np.asarray(stored_ids, np.int64).reshape(-1)
        ids = ids[(ids >= 0) & (ids < self.placement.n)]
        if ids.size == 0:
            return 0
        disk = ids[self.placement.tier_of[ids] == TIER_DISK]
        if disk.size == 0:
            return 0
        return self.prefetch.issue(disk)

    def cancel_prefetch(self) -> int:
        """Drop staged prefetch rows (fence hook); see
        `PrefetchBuffer.cancel`."""
        return self.prefetch.cancel() if self.prefetch is not None else 0

    def gather_np(self, stored_ids: np.ndarray) -> np.ndarray:
        """Host-side oracle gather straight from the backing file — the
        bit-parity reference every placement-routed gather is tested
        against (placement cannot change these bytes)."""
        return self.backing.read_rows(
            np.asarray(stored_ids, np.int64), pool=self.read_pool
        )

    def gather(self, stored_ids) -> jax.Array:
        """Tiered gather by STORED row id onto this rank's device.

        Placement-routed: HBM slots via one jitted take (+ scatter-merge
        into the output), host-cache and disk rows assembled host-side
        (disk through the read pool) and shipped as ONE padded H2D copy.
        Caller passes pre-sanitized ids (the Feature masks invalid lanes
        before and after)."""
        ids = np.asarray(stored_ids, np.int64).reshape(-1)
        n = ids.shape[0]
        target = _device_of(self.rank)
        out = jnp.zeros((n, self.dim), self.dtype, device=target)
        if n == 0:
            return out
        pl = self.placement
        tiers = pl.tier_of[ids]
        hbm_sel = np.nonzero(tiers == TIER_HBM)[0]
        if hbm_sel.size and self.hbm_table is not None:
            b = _bucket(hbm_sel.shape[0])
            pos = np.full(b, n, np.int32)
            pos[: hbm_sel.shape[0]] = hbm_sel
            slots = np.zeros(b, np.int64)
            slots[: hbm_sel.shape[0]] = pl.slot_of[ids[hbm_sel]]
            rows = _gather_local(self.hbm_table, jnp.asarray(slots))
            out = _scatter_rows(out, jnp.asarray(pos), rows)
        cold_sel = np.nonzero(tiers != TIER_HBM)[0]
        if cold_sel.size:
            from .ops import cpu_kernels

            b = _bucket(cold_sel.shape[0])
            pos = np.full(b, n, np.int32)
            pos[: cold_sel.shape[0]] = cold_sel
            rows_np = np.zeros((b, self.dim), self.dtype)
            host_sel = np.nonzero(tiers == TIER_HOST)[0]
            if host_sel.size and self.host_cache is not None:
                # cold_sel is sorted and host/disk partition it, so the
                # searchsorted below recovers each row's lane in rows_np
                lanes = np.searchsorted(cold_sel, host_sel)
                rows_np[lanes] = cpu_kernels.gather_rows(
                    self.host_cache, pl.slot_of[ids[host_sel]]
                )
            disk_sel = np.nonzero(tiers == TIER_DISK)[0]
            if disk_sel.size:
                lanes = np.searchsorted(cold_sel, disk_sel)
                disk_ids = ids[disk_sel]
                pf = self.prefetch

                def read(i):
                    return self.backing.read_rows(i, pool=self.read_pool)

                # flush-ahead staging: rows a prefetch landed in DRAM
                # skip the backing read — SAME bytes (the buffer read
                # them through the same read path), earlier
                rows_np[lanes] = (read(disk_ids) if pf is None
                                  else pf.take_or_read(disk_ids, read))
            rows = jax.device_put(jnp.asarray(rows_np), target)
            out = _scatter_rows(out, jnp.asarray(pos), rows)
        return out

    # ------------------------------------------------------------ placement
    def apply(self, plan: PlacementPlan) -> Dict[str, object]:
        """Execute a :class:`PlacementPlan` as one batch: map updates in
        plan order (demotions free the slots promotions take), then the
        data movement batched per destination — one pooled backing read
        + numpy write for host promotions, one pooled backing read + ONE
        jitted row-scatter for HBM promotions. Callers running a serve
        engine go through ``engine.apply_placement`` (which fences
        in-flight flushes first); the store's own lock only orders bare
        concurrent callers."""
        with self._lock:
            # staged prefetch rows predate this placement: a promoted row
            # would stop being consumed (wasted forever) and attribution
            # would lie — drop the staging at every placement batch (the
            # engine fence calls apply under its drain, so nothing is
            # mid-gather here)
            self.cancel_prefetch()
            pl = self.placement
            promote_hbm: List[Tuple[int, int]] = []   # (stored, slot)
            promote_host: List[Tuple[int, int]] = []
            promoted = demoted = 0
            for sid, dst in plan.moves:
                cur = int(pl.tier_of[sid])
                if dst == cur:
                    continue
                pl.release(sid)
                if dst == TIER_DISK:
                    demoted += 1
                    continue
                free = pl.free_slots(dst)
                if free.size == 0:
                    # over-full plan (stale weights): leave the row on
                    # disk rather than evict outside the plan
                    if cur != TIER_DISK:
                        demoted += 1
                    continue
                slot = int(free[0])
                pl.occupy(sid, dst, slot)
                (promote_hbm if dst == TIER_HBM else promote_host).append(
                    (sid, slot)
                )
                if dst < cur:
                    promoted += 1
                else:
                    demoted += 1  # an hbm->host demotion lands in DRAM
            moved_stored = np.asarray(
                sorted({sid for sid, _ in plan.moves}), np.int64
            )
            if promote_host and self.host_cache is not None:
                sids = np.asarray([s for s, _ in promote_host], np.int64)
                slots = np.asarray([sl for _, sl in promote_host], np.int64)
                self.host_cache[slots] = self.backing.read_rows(
                    sids, pool=self.read_pool
                )
            if promote_hbm and self.hbm_table is not None:
                sids = np.asarray([s for s, _ in promote_hbm], np.int64)
                slots_np = np.asarray([sl for _, sl in promote_hbm], np.int64)
                rows_np = self.backing.read_rows(sids, pool=self.read_pool)
                b = _bucket(slots_np.shape[0])
                slots = np.full(b, self.placement.hbm_rows, np.int64)
                slots[: slots_np.shape[0]] = slots_np
                rows = np.zeros((b, self.dim), self.dtype)
                rows[: rows_np.shape[0]] = rows_np
                self.hbm_table = _set_rows(
                    self.hbm_table, jnp.asarray(slots), jnp.asarray(rows)
                )
            pl.version += 1
            self.rows_promoted += promoted
            self.rows_demoted += demoted
            return {
                "moves": len(plan.moves),
                "promoted_rows": promoted,
                "demoted_rows": demoted,
                "promoted_hbm": len(promote_hbm),
                "promoted_host": len(promote_host),
                "moved_stored": moved_stored,
                "version": pl.version,
                "counts": pl.counts(),
            }


def tier_daemon_loop(engine) -> None:
    """Body of the background promote/demote consumer, shared by
    `ServeEngine` and `DistServeEngine` (both expose ``_running``,
    ``config.tier_adapt_every_s``, ``adapt_tiers`` and a
    ``tier_adapt_errors`` counter). Sleeps in small slices so ``stop()``
    never waits a full period; a failing pass increments the error
    counter (exposed as a gauge) instead of killing serving — a counter
    stuck rising is how operators tell "adaptation crashing every
    period" from "nothing hot to move"."""
    period = engine.config.tier_adapt_every_s
    while engine._running:
        deadline = time.monotonic() + period
        while engine._running and time.monotonic() < deadline:
            time.sleep(min(0.05, period))
        if not engine._running:
            return
        try:
            engine.adapt_tiers()
        except Exception:
            engine.tier_adapt_errors += 1


def find_tiered_feature(feature):
    """The feature object owning an adaptive :class:`TierStore` under
    the serve-feature wrappers (`QuantizedFeature.inner`, the dist
    engine's ``_ShardFeature`` -> `DistFeature` chain). Returns the
    feature that can map stored rows <-> node ids (``tier_store`` +
    ``node_ids_of_stored``), or None when the engine's feature has no
    adaptive store — static placements have nothing to adapt."""
    seen = set()
    obj = feature
    while obj is not None and id(obj) not in seen:
        seen.add(id(obj))
        if (
            getattr(obj, "tier_store", None) is not None
            and hasattr(obj, "node_ids_of_stored")
        ):
            return obj
        nxt = None
        for attr in ("inner", "_dist", "feature"):
            n = getattr(obj, attr, None)
            if n is not None:
                nxt = n
                break
        obj = nxt
    return None
