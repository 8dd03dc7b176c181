"""ShardTensor — one logical ``[N, D]`` tensor spanning memory tiers.

TPU-native re-design of the reference's ShardTensor
(srcs/python/quiver/shard_tensor.py: Offset at :7, ShardTensorConfig at :35,
append at :75-95, from_cpu_tensor at :108-136, __getitem__ at :154-180) and its
CUDA twin (srcs/cpp/src/quiver/cuda/quiver_feature.cu:56-361 with the
multi-pointer gather kernel shard_tensor.cu.hpp:16-58).

Tier mapping (reference -> TPU):

- local GPU HBM shard            -> local TPU chip HBM (jax.Array on device)
- peer GPU HBM over NVLink (P2P) -> peer chip HBM over ICI: the eager path
  gathers on the owning chip and ships rows over ICI via ``jax.device_put``;
  the jit path (`quiver_tpu.parallel.collectives.sharded_gather`) does it
  inside ``shard_map`` with collectives;
- pinned host DRAM via UVA       -> host numpy (optionally mmap-backed); TPUs
  cannot read host memory from a kernel, so the host tier is gathered by the
  native C++ engine (`qt_gather_rows`) and shipped with one H2D copy.

Row ownership is a static offset book exactly like the reference's
``offset_list_`` (quiver_feature.cu:300-320); ``access_book`` degenerates on
TPU because every chip in a slice reaches every other over ICI.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .utils import parse_size
from .ops import cpu_kernels

CPU_DEVICE = -1  # reference uses device == -1 for the pinned-CPU shard


def normalize_dtype(dtype) -> np.dtype:
    """One dtype-spelling normalizer for every tiered store ("bfloat16"
    strings resolve through jnp since numpy may not register the name)."""
    return np.dtype(jnp.bfloat16) if str(dtype) == "bfloat16" else np.dtype(dtype)


@dataclass
class Offset:
    """Row range [start, end) owned by one shard (reference shard_tensor.py:7)."""

    start: int
    end: int


@dataclass
class ShardTensorConfig:
    """Per-device HBM budget (reference shard_tensor.py:35-72).

    ``device_memory_budget`` maps local device rank -> bytes (int or "200M"
    style strings).
    """

    device_memory_budget: Dict[int, Union[int, str]] = field(default_factory=dict)

    def __post_init__(self):
        self.device_memory_budget = {
            int(d): parse_size(v) for d, v in self.device_memory_budget.items()
        }

    @property
    def device_list(self) -> List[int]:
        return sorted(self.device_memory_budget.keys())


def _device_of(rank: int):
    """Local device ``rank``. A rank this process does not have is an error:
    wrapping it (``rank % n``) would stack every shard of a
    ``device_list=[0, 1, 2, 3]`` on the one chip of a one-chip machine
    without a word."""
    local = jax.local_devices()
    if not 0 <= rank < len(local):
        raise ValueError(
            f"device rank {rank} out of range: this process has "
            f"{len(local)} local device(s) ({local[0].platform})"
        )
    return local[rank]


@jax.jit
def _gather_local(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


@jax.jit
def _scatter_rows(out: jax.Array, pos: jax.Array, rows: jax.Array) -> jax.Array:
    # positions == out.shape[0] are padding; 'drop' discards them
    return out.at[pos].set(rows, mode="drop")


PIECE_BYTES = 256 << 20  # of one host-to-device copy of `place_rows`


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(table: jax.Array, piece: jax.Array, at) -> jax.Array:
    return jax.lax.dynamic_update_slice(table, piece, (at, jnp.zeros((), at.dtype)))


class HostRows:
    """A host shard held as ROWS OF AN ARRAY THE CALLER KEEPS: local row
    ``i`` is ``base[rows[i]]``, never materialised. A degree-ordered table's
    cold tail is most of the table; permuting it would be a second table on
    the host (28 GB at half of ogbn-papers100M), and a lookup that knows the
    caller's ids reads ``base`` without the detour (`Feature.stage_tiered`)."""

    def __init__(self, base: np.ndarray, rows: np.ndarray):
        self.base, self.rows = base, rows
        self.shape = (int(rows.shape[0]), int(base.shape[1]))
        self.dtype = base.dtype

    def gather(self, local_ids, out=None) -> np.ndarray:
        return cpu_kernels.gather_rows(self.base, self.rows[np.asarray(local_ids)], out=out)


def host_gather(shard, local_ids, out=None) -> np.ndarray:
    """Rows ``local_ids`` of a host shard: an array or `HostRows`."""
    if isinstance(shard, HostRows):
        return shard.gather(local_ids, out=out)
    return cpu_kernels.gather_rows(shard, local_ids, out=out)


def place_pieces(n: int, dim: int, dtype, device, piece_of) -> jax.Array:
    """An ``[n, dim]`` array on ``device`` from host pieces of `PIECE_BYTES`:
    a zeroed array on the device, each ``piece_of(lo, hi)`` (``[hi - lo,
    dim]``, made on the host as it is asked for) copied and written in
    place. No second copy of the whole on the host, no one copy of gigabytes
    through the default device. The last piece starts early enough to be a
    whole one, so one write program serves all."""
    dtype = np.dtype(dtype)
    table = jnp.zeros((n, dim), dtype, device=device)
    per = max(min(PIECE_BYTES // max(dim * dtype.itemsize, 1), n), 1)
    for lo in range(0, n, per):
        lo = min(lo, n - per)
        piece = np.ascontiguousarray(piece_of(lo, lo + per).astype(dtype, copy=False))
        table = _write_rows(table, jax.device_put(piece, device), np.int32(lo))
    return table


def place_rows(base: np.ndarray, rows, device, dtype) -> jax.Array:
    """``base[rows]`` on ``device`` (``rows``: an index array, or a slice of
    leading rows) through `place_pieces`: each piece gathered on the host
    (native, threaded) as it goes up."""
    if isinstance(rows, slice):
        base, rows = base[rows], None
    n = int(base.shape[0] if rows is None else rows.shape[0])
    return place_pieces(
        n, int(base.shape[1]), dtype, device,
        (lambda lo, hi: base[lo:hi]) if rows is None
        else (lambda lo, hi: cpu_kernels.gather_rows(base, rows[lo:hi])))


def _bucket(n: int, floor: int = 256) -> int:
    """Pad id-batch lengths to power-of-two buckets so the jitted gather and
    scatter programs are reused across calls (XLA recompiles per shape; an
    eager per-batch shape would recompile every step)."""
    b = floor
    while b < n:
        b <<= 1
    return b


class ShardTensor:
    """Logical row-sharded tensor with gather across tiers.

    ``append`` order defines the row ranges, like the reference (device shards
    first, then at most one host shard — shard_tensor.py:75-95 enforces the
    same layout).
    """

    def __init__(
        self,
        current_device: int = 0,
        shard_tensor_config: Optional[ShardTensorConfig] = None,
        dtype=np.float32,
    ):
        self.current_device = current_device
        self.config = shard_tensor_config or ShardTensorConfig({})
        # bfloat16 halves every tier (2x the hot rows per HBM byte); the
        # reference is float32-only (quiver_feature.cu:65-69)
        self.dtype = normalize_dtype(dtype)
        self.device_shards: List[tuple] = []  # (device_rank, jax.Array, Offset)
        self.cpu_tensor: Optional[np.ndarray] = None
        self.cpu_offset: Optional[Offset] = None
        # 4th tier (round 14): flat-file row shard below host DRAM,
        # read through an optional AsyncReadPool (pipeline.py)
        self.disk_shard = None  # tiers.DiskShard
        self.disk_offset: Optional[Offset] = None
        self.read_pool = None
        self._n_rows = 0
        self._dim: Optional[int] = None

    # ------------------------------------------------------------------ build
    def append(self, tensor, device: int) -> None:
        """Place ``tensor`` as the next row range on ``device``
        (-1 = host DRAM). Mirrors reference shard_tensor.py:75-95."""
        if isinstance(tensor, HostRows):
            return self.append_rows(tensor.base, tensor.rows, device)
        arr = np.asarray(tensor)
        if arr.ndim != 2:
            raise ValueError("ShardTensor shards must be 2-D")
        if self.disk_shard is not None:
            raise ValueError("the disk shard must be the final tier")
        if self._dim is None:
            self._dim = arr.shape[1]
        elif arr.shape[1] != self._dim:
            raise ValueError("shard dim mismatch")
        off = Offset(self._n_rows, self._n_rows + arr.shape[0])
        if device == CPU_DEVICE:
            if self.cpu_tensor is not None:
                raise ValueError("host shard already set")
            self.cpu_tensor = np.ascontiguousarray(arr.astype(self.dtype, copy=False))
            self.cpu_offset = off
        else:
            if self.cpu_tensor is not None:
                raise ValueError("device shards must precede the host shard")
            dev_arr = jax.device_put(
                jnp.asarray(arr).astype(self.dtype), _device_of(device)
            )
            self.device_shards.append((device, dev_arr, off))
        self._n_rows = off.end

    def append_rows(self, base, rows, device: int) -> None:
        """`append` of ``base[rows]`` without making it on the host: a
        device shard goes up in pieces (`place_rows`), the host shard stays
        `HostRows` (an index into ``base``, which the caller keeps).
        ``rows`` is an index array or a slice of leading rows."""
        base = np.asarray(base)
        if base.ndim != 2 or base.dtype != self.dtype:
            raise ValueError(f"append_rows takes a 2-D {self.dtype} array")
        if self.disk_shard is not None or self.cpu_tensor is not None:
            raise ValueError("shards must precede the host shard and the disk shard")
        if self._dim is None:
            self._dim = base.shape[1]
        elif base.shape[1] != self._dim:
            raise ValueError("shard dim mismatch")
        n = (len(range(*rows.indices(base.shape[0]))) if isinstance(rows, slice)
             else int(rows.shape[0]))
        off = Offset(self._n_rows, self._n_rows + n)
        if device == CPU_DEVICE:
            self.cpu_tensor = base[rows] if isinstance(rows, slice) else HostRows(base, rows)
            self.cpu_offset = off
        else:
            self.device_shards.append(
                (device, place_rows(base, rows, _device_of(device), self.dtype), off))
        self._n_rows = off.end

    def append_disk(self, tensor, path: str, read_pool=None) -> None:
        """Spill ``tensor`` as the FINAL tier — a flat-file ``.npy`` row
        shard at ``path`` (round 14; the reference's mmap'd disk slice,
        feature.py:84-93, as a first-class shard-book tier). Rows are
        written at the STORE dtype, so a quantized store spills int8.
        Reads go through ``read_pool`` (`pipeline.AsyncReadPool`) when
        attached, else one synchronous page-cache gather."""
        from .tiers import DiskShard  # lazy: tiers imports this module

        arr = np.ascontiguousarray(
            np.asarray(tensor).astype(self.dtype, copy=False)
        )
        if arr.ndim != 2:
            raise ValueError("ShardTensor shards must be 2-D")
        if self.disk_shard is not None:
            raise ValueError("disk shard already set")
        if self._dim is None:
            self._dim = arr.shape[1]
        elif arr.shape[1] != self._dim:
            raise ValueError("shard dim mismatch")
        self.disk_shard = DiskShard.create(path, arr)
        self.disk_offset = Offset(self._n_rows, self._n_rows + arr.shape[0])
        self._n_rows = self.disk_offset.end
        if read_pool is not None:
            self.read_pool = read_pool

    @classmethod
    def new_from_cpu_tensor(
        cls,
        tensor,
        shard_tensor_config: ShardTensorConfig,
        current_device: int = 0,
        dtype=np.float32,
    ) -> "ShardTensor":
        """Budget-based split across device HBM shards + host tail
        (reference from_cpu_tensor, shard_tensor.py:108-136)."""
        self = cls(current_device, shard_tensor_config, dtype=dtype)
        arr = np.asarray(tensor)
        row_bytes = arr.shape[1] * self.dtype.itemsize
        cursor = 0
        for dev in self.config.device_list:
            budget = self.config.device_memory_budget[dev]
            rows = min(budget // row_bytes, arr.shape[0] - cursor)
            if rows <= 0:
                continue
            self.append(arr[cursor : cursor + rows], dev)
            cursor += rows
        if cursor < arr.shape[0]:
            self.append(arr[cursor:], CPU_DEVICE)
        return self

    from_cpu_tensor = new_from_cpu_tensor

    # ------------------------------------------------------------------ props
    @property
    def shape(self):
        return (self._n_rows, self._dim or 0)

    @property
    def size(self):
        return self._n_rows * (self._dim or 0)

    def device_ratio(self) -> float:
        dev_rows = sum(o.end - o.start for _, _, o in self.device_shards)
        return dev_rows / max(self._n_rows, 1)

    def tier_bytes(self) -> Dict[str, int]:
        """Actual byte footprint per tier at the STORED dtype — what the
        quantized capacity tables (`scaling.quant_fetch_table`) predict and
        tests verify: an int8 store's hot shard holds 4x the rows of an
        fp32 store in the same device bytes."""
        row = (self._dim or 0) * self.dtype.itemsize
        dev = sum((o.end - o.start) * row for _, _, o in self.device_shards)
        host = 0 if self.cpu_tensor is None else (
            (self.cpu_offset.end - self.cpu_offset.start) * row
        )
        disk = 0 if self.disk_shard is None else (
            (self.disk_offset.end - self.disk_offset.start) * row
        )
        return {"device": dev, "host": host, "disk": disk, "row": row}

    # ----------------------------------------------------------------- gather
    def __getitem__(self, ids) -> jax.Array:
        """Gather rows by global id onto ``current_device``.

        Eager multi-tier gather: per-shard local gather on the owning device
        (ICI transfer for peers, native host gather + one H2D for the host
        tier), then scatter-merge on the target. This is the TPU analog of the
        reference's single multi-pointer kernel (shard_tensor.cu.hpp:16-58) —
        the device<->device / device<->host boundary crossings that the CUDA
        kernel hid inside loads become explicit transfers here.
        """
        ids_np = np.asarray(ids).astype(np.int64).reshape(-1)
        n = ids_np.shape[0]
        target = _device_of(self.current_device)
        out = jnp.zeros((n, self._dim), self.dtype, device=target)

        def pad_sel(sel: np.ndarray, local: np.ndarray, pad_id: int):
            # pow2-bucketed padding; padded scatter positions point past the
            # output (mode='drop'), padded gather ids clamp to a valid row
            b = _bucket(sel.shape[0])
            pos = np.full(b, n, np.int32)
            pos[: sel.shape[0]] = sel
            loc = np.full(b, pad_id, np.int64)
            loc[: local.shape[0]] = local
            return pos, loc

        for dev_rank, table, off in self.device_shards:
            sel = np.nonzero((ids_np >= off.start) & (ids_np < off.end))[0]
            if sel.size == 0:
                continue
            pos, loc = pad_sel(sel, ids_np[sel] - off.start, 0)
            local_ids = jax.device_put(jnp.asarray(loc), _device_of(dev_rank))
            rows = _gather_local(table, local_ids)
            rows = jax.device_put(rows, target)  # rides ICI for peer chips
            out = _scatter_rows(out, jnp.asarray(pos), rows)
        if self.cpu_tensor is not None:
            off = self.cpu_offset
            sel = np.nonzero((ids_np >= off.start) & (ids_np < off.end))[0]
            if sel.size:
                # host tier: native parallel gather, then ONE padded H2D copy
                b = _bucket(sel.shape[0])
                pos = np.full(b, n, np.int32)
                pos[: sel.shape[0]] = sel
                rows_np = np.zeros((b, self._dim), self.dtype)
                host_gather(self.cpu_tensor, ids_np[sel] - off.start, out=rows_np)
                rows = jax.device_put(jnp.asarray(rows_np), target)
                out = _scatter_rows(out, jnp.asarray(pos), rows)
        if self.disk_shard is not None:
            off = self.disk_offset
            sel = np.nonzero((ids_np >= off.start) & (ids_np < off.end))[0]
            if sel.size:
                # disk tier: pooled flat-file gather, then ONE padded H2D
                b = _bucket(sel.shape[0])
                pos = np.full(b, n, np.int32)
                pos[: sel.shape[0]] = sel
                rows_np = np.zeros((b, self._dim), self.dtype)
                rows_np[: sel.size] = self.disk_shard.read_rows(
                    ids_np[sel] - off.start, pool=self.read_pool
                )
                rows = jax.device_put(jnp.asarray(rows_np), target)
                out = _scatter_rows(out, jnp.asarray(pos), rows)
        return out

    # ------------------------------------------------------- ipc-compat shims
    def share_ipc(self):
        """Reference shard_tensor.py:190-210. One JAX process drives all local
        chips, so "IPC" is just handing over the pieces."""
        items = [
            dict(device=d, array=np.asarray(t), offset=(o.start, o.end))
            for d, t, o in self.device_shards
        ]
        disk_path = None if self.disk_shard is None else self.disk_shard.path
        return items, self.cpu_tensor, self.config, str(self.dtype), disk_path

    @classmethod
    def new_from_share_ipc(cls, ipc_handle, current_device: int = 0) -> "ShardTensor":
        items, cpu_tensor, config, *rest = ipc_handle
        self = cls(current_device, config, dtype=rest[0] if rest else np.float32)
        for item in items:
            self.append(item["array"], item["device"])
        if cpu_tensor is not None:
            self.append(cpu_tensor, CPU_DEVICE)
        if len(rest) > 1 and rest[1] is not None:
            # the disk tier re-opens by PATH (the flat file is the shared
            # medium — no bytes ride the handle)
            from .tiers import DiskShard

            self.disk_shard = DiskShard(rest[1])
            self.disk_offset = Offset(
                self._n_rows, self._n_rows + self.disk_shard.shape[0]
            )
            self._n_rows = self.disk_offset.end
        return self
