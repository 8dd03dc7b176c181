"""Core graph-topology containers and helpers.

TPU-native re-design of the reference's ``srcs/python/quiver/utils.py``
(CSRTopo at utils.py:120, Topo/p2pCliqueTopo at utils.py:54-107,
reindex_by_config at utils.py:230-248, parse_size at utils.py:260-281,
init_p2p at utils.py:251-257).

Key departures from the reference:

- Topology lives in host numpy arrays (the TPU analog of pageable/pinned host
  memory) and is materialised into device HBM on demand (`to_device`), instead
  of the reference's UVA ``cudaHostRegister`` mapping — TPUs cannot read host
  memory from inside a kernel, so the "UVA" tier becomes host-side sampling and
  the "GPU" tier becomes HBM-resident CSR (see SURVEY.md section 7.3).
- ids default to int32 on device when the graph fits (faster gathers on TPU);
  int64 is kept for >2B-edge graphs (ogbn-papers100M scale).
- The NVLink-clique `Topo` becomes `IciTopo`: introspection of the JAX device
  mesh, where every chip in a TPU slice is one "clique" (all-to-all ICI),
  replacing cudaDeviceCanAccessPeer probing.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import List, Optional, Sequence, Union

import numpy as np


def parse_size(sz: Union[int, str, float]) -> int:
    """Parse a human byte size like ``"200M"``, ``"4GB"``, ``"1.5g"`` to bytes.

    Mirrors reference ``utils.py:260-281`` (parse_size) but accepts fractional
    values and an optional trailing "B".
    """
    if isinstance(sz, (int, np.integer)):
        return int(sz)
    if isinstance(sz, float):
        return int(sz)
    s = str(sz).strip().upper()
    m = re.fullmatch(r"([0-9]*\.?[0-9]+)\s*([KMGT]?)B?", s)
    if not m:
        raise ValueError(f"Cannot parse size: {sz!r}")
    value = float(m.group(1))
    unit = m.group(2)
    mult = {"": 1, "K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40}[unit]
    return int(value * mult)


def _best_id_dtype(max_value: int) -> np.dtype:
    """int32 when every index fits, else int64 (papers100M-scale edges)."""
    return np.dtype(np.int32) if max_value < 2**31 - 1 else np.dtype(np.int64)


class CSRTopo:
    """CSR graph topology container (reference ``utils.py:120-248``).

    Construct from an edge_index COO pair (2 x E) or from (indptr, indices).
    Arrays are held as host numpy; `to_device()` returns jnp copies placed in
    TPU HBM for device-mode sampling.

    Attributes
    ----------
    indptr : np.ndarray [N+1]
    indices : np.ndarray [E]
    eid : optional np.ndarray [E] original edge ids (reference keeps these for
        edge-feature lookup; ``Adj.e_id`` is empty in the reference snapshot,
        sage_sampler.py:143, but we keep the slot)
    feature_order : optional np.ndarray [N] new_order permutation produced by
        `reindex_by_config` / `Feature.from_cpu_tensor` (reference
        utils.py:171-186); stays None under a `Feature` whose hot tier holds
        the whole table (no reorder happens there)
    """

    def __init__(
        self,
        edge_index=None,
        indptr=None,
        indices=None,
        eid=None,
        num_nodes: Optional[int] = None,
        edge_weights=None,
    ):
        if edge_index is not None:
            edge_index = np.asarray(edge_index)
            if edge_index.shape[0] != 2:
                raise ValueError("edge_index must be [2, E]")
            src = np.asarray(edge_index[0], dtype=np.int64)
            dst = np.asarray(edge_index[1], dtype=np.int64)
            n = int(num_nodes) if num_nodes is not None else int(
                max(src.max(initial=-1), dst.max(initial=-1)) + 1
            )
            # COO -> CSR via counting sort on rows (reference uses scipy
            # csr_matrix, utils.py:110-117; counting sort avoids the scipy dep
            # and preserves a stable order of neighbors within a row).
            order = np.argsort(src, kind="stable")
            src_sorted = src[order]
            self.indptr = np.zeros(n + 1, dtype=np.int64)
            counts = np.bincount(src_sorted, minlength=n)
            np.cumsum(counts, out=self.indptr[1:])
            self.indices = dst[order]
            self.eid = order.astype(np.int64)  # original edge id per CSR slot
            # optional per-edge weights for the weighted sampler
            # (reference quiver.cu.hpp:61-82); stored CSR-aligned
            if edge_weights is None:
                self.edge_weights = None
            else:
                ew = np.asarray(edge_weights, np.float32)
                if ew.shape != src.shape:
                    raise ValueError(
                        f"edge_weights shape {ew.shape} != edge count "
                        f"{src.shape} of edge_index"
                    )
                self.edge_weights = ew[order]
        elif indptr is not None and indices is not None:
            self.indptr = np.ascontiguousarray(np.asarray(indptr, dtype=np.int64))
            self.indices = np.ascontiguousarray(np.asarray(indices, dtype=np.int64))
            self.eid = None if eid is None else np.asarray(eid, dtype=np.int64)
            self.edge_weights = (
                None
                if edge_weights is None
                else np.asarray(edge_weights, np.float32)
            )
            if num_nodes is not None and num_nodes + 1 > self.indptr.shape[0]:
                pad = np.full(num_nodes + 1 - self.indptr.shape[0], self.indptr[-1])
                self.indptr = np.concatenate([self.indptr, pad])
        else:
            raise ValueError("need edge_index or (indptr, indices)")
        if self.edge_weights is not None and self.edge_weights.shape != self.indices.shape:
            raise ValueError(
                f"edge_weights shape {self.edge_weights.shape} != indices "
                f"shape {self.indices.shape}"
            )
        self._feature_order: Optional[np.ndarray] = None
        self._device_cache = None
        self._tiled_cache = None
        self._lanes_cache = None

    @property
    def feature_order(self) -> Optional[np.ndarray]:
        return self._feature_order

    @feature_order.setter
    def feature_order(self, order) -> None:
        self._feature_order = np.asarray(order, dtype=np.int64)

    @property
    def degree(self) -> np.ndarray:
        """Out-degree per node (reference utils.py:189-195)."""
        return self.indptr[1:] - self.indptr[:-1]

    @property
    def node_count(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def edge_count(self) -> int:
        return int(self.indptr[-1]) if self.indices is None else self.indices.shape[0]

    def drop_host_edges(self) -> None:
        """Free the host's edge array (6.5 GB at half of ogbn-papers100M)
        once a device layout holds the edges: a TPU-mode sampler reads its
        cached device arrays and `Feature` reads the degrees, which `indptr`
        gives. ``indices`` is None from here on: whatever still wants the
        host's edges (a HOST-mode sampler, a layout not yet built) fails
        loudly instead of reading something else."""
        if not any((self._device_cache, self._tiled_cache, self._lanes_cache)):
            raise ValueError("no device layout holds the edges yet: place one first")
        self.indices = None

    def __getstate__(self):
        # device arrays don't cross process boundaries; children re-bind
        # lazily (the reference reshares topology via torch shm and re-runs
        # lazy_init_quiver in the child, sage_sampler.py:98-113)
        state = self.__dict__.copy()
        state["_device_cache"] = None
        state["_tiled_cache"] = None
        state["_lanes_cache"] = None
        state["_wtiled_cache"] = None
        return state

    def share_memory_(self):
        """No-op compat shim (reference utils.py:216-226).

        JAX drives every local chip from one process; numpy arrays passed to
        worker processes for CPU sampling go through OS fork/pickle instead of
        torch shared memory.
        """
        return self

    def to_device(self, device=None, id_dtype=None):
        """Materialise (indptr, indices) as jnp arrays in HBM.

        Returns a cached (indptr_dev, indices_dev) pair. ``id_dtype`` defaults
        to int32 when indices fit (TPU gathers are cheaper on int32).
        """
        import jax
        import jax.numpy as jnp

        if id_dtype is None:
            id_dtype = _best_id_dtype(max(self.edge_count, self.node_count + 1))
        if np.dtype(id_dtype) == np.int64 and not jax.config.jax_enable_x64:
            # jnp.asarray would SILENTLY wrap int64 -> int32 here (jax
            # default); >2^31 ids would corrupt instead of erroring
            raise ValueError(
                "graph needs int64 ids on device but jax x64 is disabled "
                "(ids would silently wrap to int32): enable it via "
                'jax.config.update("jax_enable_x64", True) before first jax '
                "use, or keep the graph host-side with mode='HOST' (the "
                "native engine is int64 end to end)"
            )
        key = (str(device), np.dtype(id_dtype).name)
        if self._device_cache is not None and self._device_cache[0] == key:
            return self._device_cache[1]
        indptr = jnp.asarray(self.indptr.astype(id_dtype))
        indices = jnp.asarray(self.indices.astype(id_dtype))
        if device is not None:
            indptr = jax.device_put(indptr, device)
            indices = jax.device_put(indices, device)
        self._device_cache = (key, (indptr, indices))
        return self._device_cache[1]

    def to_device_lane_rows(self, device=None):
        """``(windows, rows)`` in HBM: the flat layout as
        ``GraphSageSampler(layout="flat")`` binds it. ``rows`` is the edge
        array as ``[R, 128]`` rows, the last one zero-padded, as
        `ops.sample.flat_resolve` fetches from it (row gathers at the tile
        layout's rate, for the flat CSR's bytes); ``windows`` is the
        ``[N, 2]`` (first edge, degree) table `ops.sample.row_windows`
        reads, built here ONCE on the host (`ops.sample.flat_windows_host`)
        and placed beside the rows as ``bd`` is beside the tiles: no 1-D
        ``indptr`` goes to the chip and no program stacks one. `to_device`
        keeps ``(indptr [N+1], indices [E])`` for everything that walks
        the edges."""
        import jax

        from .ops.sample import LANE, flat_windows_host

        key = ("lanes", str(device))
        if getattr(self, "_lanes_cache", None) is not None and self._lanes_cache[0] == key:
            return self._lanes_cache[1]
        id_dtype = _best_id_dtype(max(self.edge_count, self.node_count + 1))
        if np.dtype(id_dtype) == np.int64 and not jax.config.jax_enable_x64:
            raise ValueError(
                "graph needs int64 ids on device but jax x64 is disabled — "
                "see CSRTopo.to_device"
            )
        from .shard_tensor import place_pieces

        # in pieces, each cast as it is cut: no second copy of the edges on
        # the host and no copy of gigabytes in one piece
        e = self.edge_count

        def piece_of(lo: int, hi: int) -> np.ndarray:
            piece = np.zeros((hi - lo) * LANE, id_dtype)
            cut = self.indices[lo * LANE: hi * LANE]
            piece[: cut.shape[0]] = cut
            return piece.reshape(hi - lo, LANE)

        rows = place_pieces(max(-(-e // LANE), 1), LANE, id_dtype, device, piece_of)
        windows = jax.device_put(flat_windows_host(self.indptr, id_dtype), device)
        placed = (windows, rows)
        self._lanes_cache = (key, placed)
        return placed

    def to_device_tiled(self, device=None, id_dtype=None):
        """Materialise the 128-lane-aligned tile layout in HBM:
        ``(bd [N, 2] int32, tiles [M, 128])`` — see
        `quiver_tpu.ops.sample.build_tiled_host`. The TPU-mode sampler's
        default graph layout: neighbor fetches ride 2-D row gathers
        (~1.4-2x the one-element gather rate) at the cost of ceil-padding
        each node's edge list to 128 lanes (~2-3x flat-CSR bytes on
        power-law graphs; pass ``layout='flat'`` to the sampler when HBM
        is tight)."""
        import jax

        import jax.numpy as jnp

        from .ops.sample import build_tiled_host

        if id_dtype is None:
            id_dtype = _best_id_dtype(self.node_count + 1)
        if np.dtype(id_dtype) == np.int64 and not jax.config.jax_enable_x64:
            raise ValueError(
                "graph needs int64 node ids on device but jax x64 is "
                "disabled — see CSRTopo.to_device"
            )
        key = ("tiled", str(device), np.dtype(id_dtype).name)
        if getattr(self, "_tiled_cache", None) is not None and self._tiled_cache[0] == key:
            return self._tiled_cache[1]
        bd_np, tiles_np = build_tiled_host(self.indptr, self.indices, id_dtype)
        bd = jnp.asarray(bd_np)
        tiles = jnp.asarray(tiles_np)
        if device is not None:
            bd = jax.device_put(bd, device)
            tiles = jax.device_put(tiles, device)
        self._tiled_cache = (key, (bd, tiles))
        return self._tiled_cache[1]

    def to_device_tiled_weights(self, device=None):
        """Edge weights in the SAME tile map as `to_device_tiled`'s edge
        tiles (``[M, 128]`` f32) — the weighted sampler's lane windows
        then ride row gathers too (`ops.sample.tiled_weighted_sample_layer`)."""
        import jax

        import jax.numpy as jnp

        from .ops.sample import build_tiled_host

        if self.edge_weights is None:
            raise ValueError("no edge_weights on this CSRTopo")
        key = ("wtiled", str(device))
        if getattr(self, "_wtiled_cache", None) is not None and self._wtiled_cache[0] == key:
            return self._wtiled_cache[1]
        _, wtiles_np = build_tiled_host(
            self.indptr, self.edge_weights, np.float32
        )
        wtiles = jnp.asarray(wtiles_np)
        if device is not None:
            wtiles = jax.device_put(wtiles, device)
        self._wtiled_cache = (key, wtiles)
        return wtiles


def heat_reorder(
    edge_index,
    num_nodes: Optional[int] = None,
    features=None,
    labels=None,
    index_sets=(),
    heat=None,
):
    """Renumber the WHOLE id space heat-descending, so the hot prefix
    convention of `shard_feature_hot_cold` / `sharded_gather_hot_cold`
    ("rows < hot_rows are the replicated tier") holds for graph, features,
    labels and index sets alike — the ONE implementation of that convention.

    ``heat``: per-node hotness scores; default is in+out degree. Pass
    measured access probabilities (`GraphSageSampler.sample_prob`) for the
    reference's prob-driven placement (mag240m preprocess.py:117-179).

    Returns ``(edge_index_r, features_r, labels_r, sets_r, order, inv)``
    with ``order[new_id] = old_id`` and ``inv[old_id] = new_id``; pass-
    through ``None`` for absent features/labels. (`reindex_by_config` /
    `Feature.from_cpu_tensor` reorder only the TABLE and translate ids at
    lookup; this reorders the id space itself, which collective gathers
    need — they test hotness by raw id.)"""
    edge_index = np.asarray(edge_index)
    n = int(num_nodes) if num_nodes is not None else int(edge_index.max()) + 1
    if heat is None:
        heat = np.bincount(edge_index[0], minlength=n) + np.bincount(
            edge_index[1], minlength=n
        )
    else:
        heat = np.asarray(heat)
        if heat.shape[0] != n:
            raise ValueError(f"heat has {heat.shape[0]} entries for {n} nodes")
    order = np.argsort(-heat, kind="stable").astype(np.int64)
    inv = np.empty(n, np.int64)
    inv[order] = np.arange(n)
    edge_r = inv[edge_index]
    feats_r = None if features is None else np.asarray(features)[order]
    labels_r = None if labels is None else np.asarray(labels)[order]
    sets_r = tuple(inv[np.asarray(s)] for s in index_sets)
    return edge_r, feats_r, labels_r, sets_r, order, inv


def show_tensor_info(x, name: str = "", file=None) -> str:
    """Debug dump of an array's identity — the TPU analog of the
    reference's ``show_tensor_info`` (srcs/cpp/src/quiver/cpu/tensor.cpp:
    74-95: dtype/shape/device/data pointer). Handles jax arrays (device +
    sharding), numpy arrays (memmap path included), and anything exposing
    shape/dtype. Returns the line (also printed)."""
    parts = [name or type(x).__name__]
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    parts.append(f"shape={tuple(shape) if shape is not None else '?'}")
    parts.append(f"dtype={dtype}")
    nbytes = getattr(x, "nbytes", None)
    if nbytes is not None:
        parts.append(f"nbytes={nbytes:,}")
    if isinstance(x, np.memmap):
        parts.append(f"memmap={getattr(x, 'filename', '?')}")
    elif isinstance(x, np.ndarray):
        parts.append("host=numpy")
    else:
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            devs = getattr(x, "devices", None)
            parts.append(f"sharding={sharding}")
            if callable(devs):
                parts.append(f"devices={sorted(str(d) for d in devs())}")
        committed = getattr(x, "committed", None)
        if committed is not None:
            parts.append(f"committed={committed}")
    line = " ".join(str(p) for p in parts)
    print(line, file=file)
    return line


def reindex_by_config(adj_csr: CSRTopo, graph_feature, gpu_portion: float, seed: int = 0):
    """Degree-descending hot/cold reorder (reference ``utils.py:230-248``).

    Sort nodes by out-degree descending, randomly shuffle the hot prefix
    (top ``gpu_portion`` fraction) to load-balance striped placement, and
    return ``(permuted_feature, prev_order)`` where ``prev_order`` maps
    old node id -> position in the permuted feature ("feature_order").

    The hot-prefix shuffle is seeded (default 0) so cache placement — and
    any performance comparison across runs — is reproducible; pass a
    different ``seed`` to resample the striping.
    """
    prev_order, new_order = degree_order(adj_csr, gpu_portion, seed)
    if graph_feature is not None:
        graph_feature = np.asarray(graph_feature)[prev_order]
    return graph_feature, new_order


def degree_order(adj_csr: CSRTopo, gpu_portion: float, seed: int = 0):
    """The permutation of `reindex_by_config` without a table to permute:
    ``(prev_order, new_order)``, stored row -> old node id and its inverse
    ("feature_order"). `Feature.from_cpu_tensor` places a tiered table from
    ``prev_order`` without making the permuted copy."""
    if not 0.0 <= gpu_portion <= 1.0:
        raise ValueError("gpu_portion must be in [0, 1]")
    node_count = adj_csr.node_count
    split = int(node_count * gpu_portion)
    perm_range = np.random.default_rng(seed).permutation(split)
    # descending degree order; stable for determinism on ties
    prev_order = np.argsort(-adj_csr.degree, kind="stable")
    prev_order[:split] = prev_order[perm_range]
    new_order = np.empty(node_count, dtype=np.int64)
    new_order[prev_order] = np.arange(node_count, dtype=np.int64)
    return prev_order, new_order


def reindex_feature(graph: CSRTopo, feature, ratio: float, seed: int = 0):
    """Reference ``utils.py:230`` companion used by Feature; returns
    (reordered_feature, feature_order)."""
    feature, new_order = reindex_by_config(graph, feature, ratio, seed=seed)
    return feature, new_order


@dataclass
class IciTopo:
    """TPU replacement for the NVLink p2p-clique `Topo` (reference
    ``utils.py:54-107`` + Bron-Kerbosch find_cliques utils.py:8-33).

    On a TPU slice every local chip is connected over ICI, so clique discovery
    degenerates to "all local devices form one clique per slice". We keep the
    same info surface: `get_clique(rank)`, `info()`.
    """

    cliques: List[List[int]]

    @staticmethod
    def detect(devices: Optional[Sequence] = None) -> "IciTopo":
        import jax

        devs = list(devices) if devices is not None else jax.local_devices()
        by_slice = {}
        for i, d in enumerate(devs):
            slice_idx = getattr(d, "slice_index", 0) or 0
            by_slice.setdefault(slice_idx, []).append(i)
        return IciTopo(cliques=[sorted(v) for _, v in sorted(by_slice.items())])

    def get_clique_id(self, device_rank: int) -> int:
        for cid, clique in enumerate(self.cliques):
            if device_rank in clique:
                return cid
        raise KeyError(device_rank)

    def get_clique(self, device_rank: int) -> List[int]:
        return self.cliques[self.get_clique_id(device_rank)]

    @property
    def p2p_clique(self):  # reference-compatible spelling
        return {i: c for i, c in enumerate(self.cliques)}

    def info(self) -> str:
        lines = ["Device ICI Topology:"]
        for cid, clique in enumerate(self.cliques):
            lines.append(f"  clique {cid}: devices {clique} (all-to-all ICI)")
        return "\n".join(lines)


# Reference-compatible alias (`p2pCliqueTopo`, __init__.py:6).
p2pCliqueTopo = IciTopo
Topo = IciTopo


def force_virtual_cpu_devices(n_devices: int) -> None:
    """Give this process an ``n_devices`` virtual CPU mesh — a CPU-ONLY
    tool for the hermetic tests, the multichip dry run and the examples'
    ``QUIVER_VIRTUAL_DEVICES`` knob.

    Call it before the first JAX operation. A process whose live backend
    is an accelerator is refused: dropping a chip it already holds to
    carry on on virtual CPU devices would report CPU work under the
    accelerator's name. A live CPU backend with another device count is
    rebuilt (no device is hidden by that).
    """
    import os

    xla_flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    )
    os.environ["XLA_FLAGS"] = (
        xla_flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.extend.backend

    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        # a backend is already up with another CPU device count (the
        # config refuses changes once one is); what is live decides
        platform = jax.devices()[0].platform
        if platform != "cpu":
            raise RuntimeError(
                f"force_virtual_cpu_devices({n_devices}): this process "
                f"already holds the {platform!r} backend; the virtual mesh "
                "is a CPU-only tool — run it in a fresh process with "
                "JAX_PLATFORMS=cpu"
            ) from None
        jax.extend.backend.clear_backends()
        jax.clear_caches()
        jax.config.update("jax_num_cpu_devices", n_devices)
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if len(devs) != n_devices or devs[0].platform != "cpu":
        raise RuntimeError(
            f"could not force {n_devices} virtual CPU devices: this process "
            f"already initialised {devs}; run in a fresh process with "
            "JAX_PLATFORMS=cpu"
        )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory
    — the ONE place the repo decides where compiled programs are kept
    (chip_smoke.py, tests/conftest).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it, and no
    directory is set in code (a machine that provides the variable keeps
    what is cached there for the next call). Unset: the fixed
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it is
    never a temp name, a pid or a time.
    """
    import os

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def axis_size_compat(axis_name):
    """``lax.axis_size`` (inside shard_map/pmap only); a static Python int,
    usable in shapes. The name predates the installed jax and is kept for
    its callers."""
    from jax import lax

    return lax.axis_size(axis_name)


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with this repo's default ``check_vma=False`` — the
    one spelling every caller (library, tests, scripts) goes through."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def init_p2p(device_list: Optional[List[int]] = None) -> None:
    """Compat no-op (reference utils.py:251-257 / quiver_feature.cu:363-406).

    TPU chips in a slice are always mutually reachable over ICI; there is no
    peer-access switch to flip. Kept so reference scripts port unchanged.
    """
    return None


def can_device_access_peer(a: int, b: int) -> bool:
    """ICI reachability probe (reference quiver_feature.cu:407-413): true when
    both ranks sit on the same TPU slice."""
    topo = IciTopo.detect()
    try:
        return topo.get_clique_id(a) == topo.get_clique_id(b)
    except KeyError:
        return False
