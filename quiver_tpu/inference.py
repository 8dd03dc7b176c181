"""Evaluation / inference paths.

The reference evaluates two ways: layer-wise FULL-neighbor inference (the
`model.inference` loop of examples/multi_gpu/pyg/ogb-products/
dist_sampling_ogb_products_quiver.py:118-139, subgraph loader over all
nodes) and sampled eval with the training sampler. TPU equivalents:

- `sage_full_inference`: exact layered embeddings for ALL nodes. The
  full-neighbor mean aggregation is ONE edge-parallel pass over the CSR per
  layer (chunked `lax.fori_loop`, same trick as `ops.sample.neighbor_prob`)
  — no subgraph loader needed; XLA streams the gather/scatter chunks.
- `sampled_eval`: high-fanout sampled accuracy for any model (GraphSAGE or
  GAT — full-neighbor attention would need per-edge softmax passes; the
  reference evaluates GAT by sampling too, dist_sampling_reddit_gat.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


@functools.partial(jax.jit, static_argnames=("edge_chunk",))
def full_mean_aggregate(
    indptr: jax.Array,
    indices: jax.Array,
    h: jax.Array,
    edge_chunk: int = 1 << 20,
) -> jax.Array:
    """Exact mean over ALL neighbors for every node: ``out[u] =
    mean_{v in N(u)} h[v]`` (zero where deg 0).

    Edge-parallel chunked segment-sum over the CSR — the dense-batch analog
    of `ops.sample.neighbor_prob`'s scalar pass; one traced chunk body
    regardless of graph size.
    """
    n = indptr.shape[0] - 1
    e = indices.shape[0]
    d = h.shape[1]
    out = jnp.zeros((n + 1, d), h.dtype)  # +1: out-of-range dump row
    if e == 0:
        return out[:n]
    chunk = min(edge_chunk, e)
    nchunks = -(-e // chunk)

    def body(c, out):
        start_u = c * chunk
        start = jnp.minimum(start_u, e - chunk)
        eidx = start + jnp.arange(chunk, dtype=indptr.dtype)
        fresh = eidx >= start_u
        src = jnp.searchsorted(indptr, eidx, side="right") - 1
        dst = lax.dynamic_slice(indices, (start,), (chunk,))
        rows = jnp.take(h, jnp.clip(dst, 0, h.shape[0] - 1), axis=0)
        rows = jnp.where(fresh[:, None], rows, 0)
        src = jnp.where(fresh, src, n)  # dump lane
        return out.at[src].add(rows, mode="drop")

    out = lax.fori_loop(0, nchunks, body, out)[:n]
    deg = (indptr[1:] - indptr[:-1]).astype(h.dtype)
    return out / jnp.maximum(deg, 1)[:, None]


def sage_full_inference(
    model,
    params,
    indptr: jax.Array,
    indices: jax.Array,
    x_all: jax.Array,
) -> jax.Array:
    """Layer-wise full-neighbor GraphSAGE inference over ALL nodes —
    the reference `SAGE.inference` semantics
    (dist_sampling_ogb_products_quiver.py:118-139) without a subgraph
    loader: per layer, one full-graph mean aggregation + the layer's dense
    projections, relu between layers (no dropout at eval).

    Works for the `models.GraphSAGE` flax module (reads its
    ``conv{i}/lin_l|lin_r`` params directly; GAT needs per-edge softmax —
    use `sampled_eval` there)."""
    p = params["params"] if "params" in params else params
    num_layers = model.num_layers
    h = jnp.asarray(x_all)
    for i in range(num_layers):
        layer = p[f"conv{i}"]
        agg = full_mean_aggregate(indptr, indices, h)
        out = agg @ layer["lin_l"]["kernel"]
        if "bias" in layer["lin_l"]:
            out = out + layer["lin_l"]["bias"]
        out = out + h @ layer["lin_r"]["kernel"]
        h = jax.nn.relu(out) if i != num_layers - 1 else out
    return h


@functools.lru_cache(maxsize=32)
def _cached_apply_hashable(model):
    return jax.jit(lambda p, x, adjs: model.apply(p, x, adjs))


def _cached_apply(model):
    """One jitted apply per model VALUE — a fresh jit per sampled_eval call
    would recompile an identical program every invocation.

    Value-keyed (flax modules are frozen dataclasses, hashable by field
    values: equal configs share one entry) and BOUNDED: the lru_cache holds
    at most 32 models + executables, so repeated model construction (e.g. a
    hyperparameter sweep) evicts old entries instead of growing without
    bound. Weak-keyed variants were rejected — a closure capturing the key
    pins it (no eviction), and a weakref proxy raises ReferenceError when a
    retrace outlives the first-seen equal model."""
    try:
        return _cached_apply_hashable(model)
    except TypeError:  # unhashable custom module: skip caching
        return jax.jit(lambda p, x, adjs: model.apply(p, x, adjs))


def pad_seed_batch(
    batch: np.ndarray, batch_size: int, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """Pad a 1-D seed batch up to ``batch_size`` by repeating ``batch[-1]``
    (the convention every fixed-shape eval/serve path here uses — the
    duplicate rows are sliced off after the forward). Pass ``out`` to reuse
    one buffer across a loop instead of allocating per batch."""
    batch = np.asarray(batch)
    if batch.shape[0] == 0:
        raise ValueError("cannot pad an empty seed batch")
    if batch.shape[0] > batch_size:
        raise ValueError(f"batch of {batch.shape[0]} exceeds batch_size={batch_size}")
    if out is None or out.shape[0] != batch_size or out.dtype != batch.dtype:
        out = np.empty(batch_size, batch.dtype)
    out[: batch.shape[0]] = batch
    out[batch.shape[0] :] = batch[-1]
    return out


def lookup_features(feature, n_id, ids_out: Optional[np.ndarray] = None):
    """Feature rows for a sampled ``n_id`` — one helper for every consumer
    (``sampled_eval``, the serve engine): raw ``[N, D]`` numpy tables get the
    clip-and-take path (``ids_out`` reuses the clipped-id buffer across
    calls), quiver ``Feature``/``QuantizedFeature`` objects their tiered
    ``__getitem__``."""
    if isinstance(feature, np.ndarray):
        ids = np.asarray(n_id)
        if ids_out is not None and ids_out.shape == ids.shape:
            np.clip(ids, 0, feature.shape[0] - 1, out=ids_out)
            ids = ids_out
        else:
            ids = np.clip(ids, 0, feature.shape[0] - 1)
        return jnp.asarray(feature[ids])
    return feature[n_id]


def sample_batch(sampler, padded_batch):
    """Stage 1 of the fixed-shape eval step: draw the sampler's next key
    and dispatch the k-hop sample for ``padded_batch``. Split out of
    :func:`batch_logits` so the pipelined serve engine can consume the
    sampler's key stream in dispatch-index order (under its sequencing
    lock) while the forward of the PREVIOUS flush still runs."""
    return sampler.sample_dense(padded_batch)


def forward_logits(apply, params, feature, ds, ids_out=None) -> jax.Array:
    """Stage 2 of the fixed-shape eval step: gather features for an
    already-sampled ``ds`` and run the jitted ``apply``. Composes with
    :func:`sample_batch`; `batch_logits` is exactly the two in sequence."""
    x = lookup_features(feature, ds.n_id, ids_out=ids_out)
    return apply(params, x, ds.adjs)


def batch_logits(
    apply, params, sampler, feature, padded_batch, ids_out=None
) -> jax.Array:
    """One fixed-shape eval step: sample ``padded_batch`` with ``sampler``,
    gather its features, run the jitted ``apply``. This IS the unbatched
    `sampled_eval` inner loop — the serve engine dispatches through the same
    two stages (`sample_batch` + `forward_logits`), which is what makes
    served logits bit-identical to offline eval on the same (sampler state,
    batch) pair."""
    ds = sample_batch(sampler, padded_batch)
    return forward_logits(apply, params, feature, ds, ids_out=ids_out)


# -- fused one-dispatch serving (ROADMAP item 4a/4b) --------------------------

_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def fold_in_call(key0, call):
    """``jax.random.fold_in(key0, call)`` bit for bit (tests/test_serve.py
    holds it to that), for the head of a serve program: the key of
    dispatch ``call`` (a uint32 scalar) from the sampler's base key.

    For the default threefry keys this is Threefry-2x32 of the counter
    ``(0, call)`` under ``key0``, as `jax.random.fold_in` defines it,
    written out in `lax` primitives on scalars. `jax.random.fold_in`
    itself costs each program that holds it one more lowering of JAX's
    unrolled threefry rule, which traces its ~130 `jnp` operations anew in
    every module: 0.65 s a bucket program on the serving host, 4.4 s of
    `ServeEngine.warmup()` over the seven buckets (chip runs, PR 37). The
    same operations bound directly lower in milliseconds. Any other key
    implementation goes through `jax.random.fold_in`."""
    impl = jax.random.key_impl(key0)
    if impl != "threefry2x32":
        return jax.random.fold_in(key0, call)
    u32 = np.uint32
    data = jax.random.key_data(key0)
    ks = [lax.index_in_dim(data, 0, keepdims=False),
          lax.index_in_dim(data, 1, keepdims=False)]
    ks.append(lax.bitwise_xor(lax.bitwise_xor(ks[0], ks[1]), u32(0x1BD11BDA)))
    x0 = ks[0]                       # the counter's high word is 0
    x1 = lax.add(lax.convert_element_type(call, u32), ks[1])
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = lax.add(x0, x1)
            x1 = lax.bitwise_or(lax.shift_left(x1, u32(r)),
                                lax.shift_right_logical(x1, u32(32 - r)))
            x1 = lax.bitwise_xor(x0, x1)
        x0 = lax.add(x0, ks[(i + 1) % 3])
        x1 = lax.add(lax.add(x1, ks[(i + 2) % 3]), u32(i + 1))
    return jax.random.wrap_key_data(
        lax.concatenate([lax.expand_dims(x0, [0]), lax.expand_dims(x1, [0])], 0),
        impl=impl,
    )


def feature_gather_spec(feature):
    """``(table, index_map)`` device arrays for an IN-JIT serve gather.

    ``table`` is a dense ``[R, D]`` row table; ``index_map`` is either None
    (ids index ``table`` directly, clipped) or an ``[N]`` int32 global→row
    map (clipped after mapping) — the indirection `serve.ClosureFeature`
    shards ride. Raises TypeError for features whose lookup is host-side by
    design (tiered `Feature`, `DistFeature`): materializing them onto the
    device would silently void the capacity contract the tiers exist for,
    so those engines stay on the split sample/forward path instead."""
    if isinstance(feature, np.ndarray):
        if feature.ndim != 2:
            raise TypeError(f"feature table must be [N, D]; got {feature.shape}")
        return jnp.asarray(feature), None
    if isinstance(feature, jax.Array):
        if feature.ndim != 2:
            raise TypeError(f"feature table must be [N, D]; got {feature.shape}")
        return feature, None
    spec = getattr(feature, "jit_gather_spec", None)
    if spec is not None:
        return spec()
    raise TypeError(
        f"{type(feature).__name__} has no in-jit gather (host-side lookup "
        "by design) — the serve engine falls back to the split path"
    )


def make_serve_step(model, sampler):
    """Build the fused serve step: ONE jittable function running
    sample + feature gather + forward for a padded seed batch.

    Returns ``(serve_step, graph, id_dtype)`` where ``serve_step(params,
    key0, call, seeds, table, index_map, graph)`` reproduces
    `sample_batch` + `forward_logits` bit-for-bit in one program (the
    bit-parity tests in tests/test_serve.py pin it), ``graph`` is the
    sampler's device-array pytree (a jit ARGUMENT of every call — big
    closure constants are the slow-compile trap, NEXT.md), and
    ``id_dtype`` the seed dtype the program was built for. The sampler's
    key is derived INSIDE the program, the bits of ``fold_in(key0, call)``
    that `sample_dense_program` derives (`fold_in_call`): ``key0`` is the
    sampler's base key (an argument: a seed baked in would compile anew
    for every seed) and ``call`` a uint32 scalar, the call index the
    ENGINE draws in dispatch order (`GraphSageSampler.next_call`), so
    fused and split engines consume identical key indices and the host
    derives no key."""
    from .pyg.sage_sampler import sample_dense_fused, sample_dense_pure

    graph, bind, id_dtype = sampler.fused_sample_spec()
    sizes, caps, dedup = sampler.sizes, sampler.caps, sampler.dedup

    def serve_step(params, key0, call, seeds, table, index_map, graph):
        key = fold_in_call(key0, call)
        sample_fn = bind(graph)
        if dedup:
            ds = sample_dense_pure(
                None, None, key, seeds, sizes, caps, sample_fn=sample_fn
            )
        else:
            ds = sample_dense_fused(
                None, None, key, seeds, sizes, sample_fn=sample_fn
            )
        n = index_map.shape[0] if index_map is not None else table.shape[0]
        ids = jnp.clip(ds.n_id, 0, n - 1)
        if index_map is not None:
            ids = jnp.clip(jnp.take(index_map, ids), 0, table.shape[0] - 1)
        x = jnp.take(table, ids, axis=0)
        return model.apply(params, x, ds.adjs)

    return serve_step, graph, id_dtype


def make_temporal_serve_step(model, sampler):
    """The TEMPORAL analog of :func:`make_serve_step` (round 19,
    `quiver_tpu.workloads`): ``serve_step(params, key0, call, seeds, table,
    index_map, graph, t)`` runs the masked temporal sample
    (`workloads.temporal.temporal_sample_dense`) + gather + forward as ONE
    program. ``t`` is the padded per-seed query-time vector — a jit
    ARGUMENT exactly like the graph arrays (the NEXT.md rule: a
    closure-constant t would recompile per query time; an argument serves
    every t through one sealed executable). The sampler must be
    temporal-bound (`GraphSageSampler.bind_temporal`); its recency/fanout
    config is baked statically, its graph arrays stay swappable via
    `BucketPrograms.rebind` (streaming commits)."""
    from .workloads.temporal import temporal_sample_dense

    if getattr(sampler, "temporal", None) is None:
        raise TypeError("make_temporal_serve_step needs a temporal-bound sampler")
    _, recency = sampler.temporal
    graph = sampler.temporal_graph_arrays()
    sizes, max_deg = sampler.sizes, sampler.max_deg
    id_dtype = graph[1].dtype

    def serve_step(params, key0, call, seeds, table, index_map, graph, t):
        key = fold_in_call(key0, call)
        ds = temporal_sample_dense(
            graph, key, seeds, t, sizes, recency=recency, max_deg=max_deg
        )
        n = index_map.shape[0] if index_map is not None else table.shape[0]
        ids = jnp.clip(ds.n_id, 0, n - 1)
        if index_map is not None:
            ids = jnp.clip(jnp.take(index_map, ids), 0, table.shape[0] - 1)
        x = jnp.take(table, ids, axis=0)
        return model.apply(params, x, ds.adjs)

    return serve_step, graph, id_dtype


# Process-wide cache of compiled serve executables, keyed by everything the
# lowering depends on (model value, sampler config, graph/table/params
# AVALS, bucket). Two engines over same-shaped state share one executable —
# the sharing the jit cache used to provide, kept so per-engine AOT
# pre-binding doesn't multiply compile time across a test suite or a shard
# fleet — while each engine still holds its OWN pre-bound table with
# hard-miss semantics. LRU-bounded: live engines keep direct references to
# their executables, so eviction only reduces cross-engine sharing, never
# invalidates a sealed program table.
import collections as _collections
import threading as _threading

_SERVE_EXE_CACHE: "_collections.OrderedDict" = _collections.OrderedDict()
_SERVE_EXE_CACHE_MAX = 256
_SERVE_EXE_LOCK = _threading.Lock()


def _aval_spec(tree) -> tuple:
    return tuple(
        (tuple(leaf.shape), np.dtype(leaf.dtype).str)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


class BucketPrograms:
    """AOT pre-bound per-bucket fused serve executables (ROADMAP item 4a —
    the CUDA-Graphs analog's capture step).

    `compile_bucket` turns the fused `make_serve_step` function into one
    LOADED executable per bucket via ``jax.jit(...).lower(...).compile()``
    — held here, not as a jit-cache entry, so a flush is a direct
    table-lookup + execute with zero trace-cache machinery on the hot path.
    The per-flush seed buffer is DONATED (``donate_argnums``) so XLA may
    reuse its device allocation for outputs/scratch; the feature table and
    graph arrays are NOT donated — they are persistent state every flush
    re-reads, and donating them would invalidate them after one call.

    `seal()` (called by `ServeEngine.warmup`) flips misses from
    compile-on-first-use to a HARD RuntimeError: after warmup a retrace or
    recompile is structurally impossible — a shape the fleet didn't warm is
    a bug surfaced in milliseconds, not a silent 12–60 s compile eaten by a
    live request."""

    def __init__(self, model, sampler, feature):
        # temporal samplers (round 19, quiver_tpu.workloads) compile the
        # temporal serve step, which takes ONE extra per-flush argument:
        # the padded per-seed query-time vector
        temporal = getattr(sampler, "temporal", None)
        if temporal is not None:
            self._fn, self._graph, self._id_dtype = make_temporal_serve_step(
                model, sampler
            )
            self._n_extra = 1
        else:
            self._fn, self._graph, self._id_dtype = make_serve_step(
                model, sampler
            )
            self._n_extra = 0
        self._sampler = sampler
        self._key0 = sampler._key0  # the base key every dispatch folds from
        self._caps = sampler.caps  # snapshot the program was built for
        self._table, self._map = feature_gather_spec(feature)
        self._jit = jax.jit(self._fn, donate_argnums=(3,))
        self._exes: dict = {}
        self._sealed = False
        try:
            spec = (
                model, sampler.sizes, sampler.caps, sampler.dedup,
                getattr(sampler, "layout", None),
                getattr(sampler, "weighted", False),
                self._n_extra,
                None if temporal is None else (
                    float(temporal[1]), int(getattr(sampler, "max_deg", 0))
                ),
                np.dtype(self._id_dtype).str,
                _aval_spec(self._graph),
                _aval_spec(self._table),
                None if self._map is None else _aval_spec(self._map),
            )
            hash(spec)
            self._spec = spec
        except TypeError:  # unhashable custom model: per-engine compiles only
            self._spec = None

    def rebind(self, graph=None, table=None, index_map=None) -> None:
        """Swap the persistent graph / feature-table arguments for
        SAME-SHAPED updated arrays (round-17 streaming graph deltas: a
        fenced ``update_graph`` commit produces new device arrays; the
        executables take them as ARGUMENTS, so the swap is free — no
        recompile, the sealed table stays sealed). A shape/dtype mismatch
        raises instead of silently feeding the compiled avals garbage."""
        if graph is not None:
            if _aval_spec(graph) != _aval_spec(self._graph):
                raise ValueError(
                    "rebind graph avals differ from the compiled ones "
                    f"({_aval_spec(graph)} vs {_aval_spec(self._graph)}) — "
                    "streaming swaps contents, never shapes"
                )
            self._graph = graph
        if table is not None:
            if _aval_spec(table) != _aval_spec(self._table):
                raise ValueError("rebind table avals differ from compiled")
            self._table = table
        if index_map is not None:
            if self._map is None or _aval_spec(index_map) != _aval_spec(
                self._map
            ):
                raise ValueError("rebind index_map avals differ from compiled")
            self._map = index_map

    def reprovision(self, graph, params=None) -> int:
        """Rebind the graph arguments across a SHAPE change — the
        round-21 reserve re-provisioning event (`StreamingTiledGraph.
        provision_reserve` grew the tile tables by a whole bank). This
        is the one sanctioned exception to `rebind`'s shapes-never-
        change contract, and it is paid for honestly: the program spec
        is updated to the new graph avals, every previously-warmed
        bucket executable is dropped and recompiled against them (via
        the process-wide executable cache, so a second engine over the
        same shapes compiles nothing), and the sealed/unsealed state is
        preserved — after the rebuild the table is complete again, so
        sealed hard-miss semantics still hold. One rebuild per provision
        event; the per-commit path still never recompiles. Returns the
        number of buckets rebuilt."""
        new_avals = _aval_spec(graph)
        if new_avals == _aval_spec(self._graph):
            # same shapes (e.g. a retried provision already absorbed):
            # a plain content rebind
            self._graph = graph
            return 0
        self._graph = graph
        if self._spec is not None:
            # graph avals live at one spec slot — keep everything else
            # (model, sampler config, table/map avals) identical so the
            # executable cache shares across engines as before
            self._spec = self._spec[:9] + (new_avals,) + self._spec[10:]
        warmed = tuple(sorted(self._exes))
        self._exes = {}
        if params is not None:
            for b in warmed:
                self.compile_bucket(b, params)
        return len(warmed)

    @property
    def buckets(self) -> Tuple[int, ...]:
        return tuple(sorted(self._exes))

    @property
    def sealed(self) -> bool:
        return self._sealed

    def seal(self) -> None:
        self._sealed = True

    def compile_bucket(self, bucket: int, params) -> None:
        """Bind (compiling if no same-shaped executable exists anywhere in
        the process) the executable for ``bucket``."""
        bucket = int(bucket)
        if bucket in self._exes:
            return
        cache_key = None
        if self._spec is not None:
            cache_key = (self._spec, _aval_spec(params), bucket)
            with _SERVE_EXE_LOCK:
                exe = _SERVE_EXE_CACHE.get(cache_key)
                if exe is not None:
                    _SERVE_EXE_CACHE.move_to_end(cache_key)
            if exe is not None:
                self._exes[bucket] = exe
                return
        seeds = jnp.zeros((bucket,), self._id_dtype)
        extras = (
            (jnp.zeros((bucket,), jnp.float32),) if self._n_extra else ()
        )
        import warnings

        with warnings.catch_warnings():
            # the donated seed buffer has no same-shaped output to alias on
            # every backend; the donation is still declared so backends
            # that CAN reuse it (and future outputs) do
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            exe = self._jit.lower(
                params, self._key0, np.uint32(0), seeds, self._table,
                self._map, self._graph, *extras,
            ).compile()
        if cache_key is not None:
            with _SERVE_EXE_LOCK:
                exe = _SERVE_EXE_CACHE.setdefault(cache_key, exe)
                _SERVE_EXE_CACHE.move_to_end(cache_key)
                while len(_SERVE_EXE_CACHE) > _SERVE_EXE_CACHE_MAX:
                    _SERVE_EXE_CACHE.popitem(last=False)
        self._exes[bucket] = exe

    def binding(self):
        """The persistent-argument triple ``(table, index_map, graph)``
        CURRENTLY bound — an epoch snapshot. Zero-stall engines capture
        this at seal time and pass it back as ``binding=`` so a flush
        dispatches against the graph arrays of ITS dispatch index even
        when a commit rebinds mid-flight (the arrays are immutable; a
        rebind swaps references, never bits)."""
        return (self._table, self._map, self._graph)

    def __call__(self, bucket: int, params, call: int, seeds, *extra,
                 binding=None) -> jax.Array:
        """ONE execute call: the whole sample+gather+forward for a padded
        seed batch at ``bucket``, drawn with the key of call index
        ``call`` (a Python int, `GraphSageSampler.next_call`: the program
        folds it into the sampler's base key itself). Misses compile
        lazily before `seal()`, raise RuntimeError after. Temporal
        programs take one ``extra`` argument — the padded per-seed
        query-time vector, float32 ``[bucket]`` (the engine pads it
        exactly like the seeds).
        ``binding=`` (a `binding()` snapshot) overrides the live
        table/map/graph arguments — the epoch-pinning hook."""
        if len(extra) != self._n_extra:
            raise TypeError(
                f"this serve program takes {self._n_extra} extra "
                f"argument(s) (got {len(extra)}) — temporal engines pass "
                "the padded query-time vector, plain engines none"
            )
        if self._sampler.caps != self._caps:
            # the fused program bakes the caps' static shapes in; sampling
            # with mutated caps would silently diverge from the split path
            # and the replay oracle (calibrate_caps after engine build)
            raise RuntimeError(
                f"sampler caps changed from {self._caps} to "
                f"{self._sampler.caps} after the serve programs were built "
                "— calibrate caps BEFORE constructing the engine"
            )
        exe = self._exes.get(int(bucket))
        if exe is None:
            if self._sealed:
                raise RuntimeError(
                    f"serve bucket {bucket} has no pre-bound executable "
                    f"(warmed: {self.buckets}) — warmup() seals the program "
                    "table; a post-warmup miss means the bucket ladder and "
                    "the warmed shapes disagree"
                )
            self.compile_bucket(int(bucket), params)
            exe = self._exes[int(bucket)]
        # cast on the HOST and hand the executable host arrays: its one C++
        # call transfers them with the call index. A device-side convert
        # would be an eager program compiled per bucket shape after warmup()
        # sealed the table, and an eager transfer before the call is one
        # more point where the dispatching thread gives up the interpreter
        seeds = np.asarray(seeds, np.dtype(self._id_dtype))
        extra = tuple(np.asarray(e, np.float32) for e in extra)
        table, imap, graph = (
            binding if binding is not None
            else (self._table, self._map, self._graph)
        )
        return exe(
            params, self._key0, np.uint32(call), seeds, table, imap, graph,
            *extra,
        )


def time_eval_split(
    apply, params, sampler, feature, padded_batch, iters: int = 10
) -> Tuple[float, float]:
    """Measured per-call seconds of the two `batch_logits` stages —
    ``(t_sample_s, t_forward_s)`` at this batch shape — the EVAL-shaped
    dispatch costs `parallel.scaling.serve_table` wants instead of a
    train-step proxy. Warms one full untimed pass first; each timed leg
    syncs once at the end (raw averages). One shared implementation so
    every caller (`scripts/serve_probe.py`) reports the same methodology."""
    import time

    ds = sample_batch(sampler, padded_batch)
    jax.block_until_ready(ds.n_id)
    jax.block_until_ready(forward_logits(apply, params, feature, ds))
    t0 = time.perf_counter()
    for _ in range(iters):
        ds = sample_batch(sampler, padded_batch)
    jax.block_until_ready(ds.n_id)
    t_sample = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        out = forward_logits(apply, params, feature, ds)
    jax.block_until_ready(out)
    t_forward = (time.perf_counter() - t0) / iters
    return t_sample, t_forward


def sampled_eval(
    model,
    params,
    sampler,
    feature,
    labels: np.ndarray,
    nodes: np.ndarray,
    batch_size: int = 1024,
) -> float:
    """Sampled accuracy over ``nodes`` (any model; use an eval sampler with
    higher fanouts than training for a tighter estimate — the reference's
    eval runs the same loop with test seeds). Returns fraction correct."""
    nodes = np.asarray(nodes)
    labels = np.asarray(labels)
    correct = 0
    apply = _cached_apply(model)
    # hoisted per-batch work: one padded seed buffer reused across the loop
    # (pad_seed_batch writes in place) and one clipped-id buffer for the
    # raw-table path, allocated lazily at the first batch's n_id shape
    seed_buf = np.empty(batch_size, nodes.dtype)
    ids_buf: Optional[np.ndarray] = None
    for lo in range(0, nodes.shape[0], batch_size):
        batch = pad_seed_batch(nodes[lo : lo + batch_size], batch_size, out=seed_buf)
        ds = sampler.sample_dense(batch)
        if isinstance(feature, np.ndarray) and ids_buf is None:
            ids_buf = np.empty(np.asarray(ds.n_id).shape, np.asarray(ds.n_id).dtype)
        x = lookup_features(feature, ds.n_id, ids_out=ids_buf)
        logits = apply(params, x, ds.adjs)
        pred = np.asarray(jnp.argmax(logits, axis=-1))[: min(batch_size, nodes.shape[0] - lo)]
        correct += int((pred == labels[nodes[lo : lo + batch_size]]).sum())
    return correct / nodes.shape[0]


def full_inference_accuracy(
    model, params, topo, x_all, labels, nodes
) -> float:
    """Accuracy of `sage_full_inference` on a node subset."""
    indptr, indices = topo.to_device()
    h = sage_full_inference(model, params, indptr, indices, jnp.asarray(x_all))
    pred = np.asarray(jnp.argmax(h, axis=-1))
    nodes = np.asarray(nodes)
    return float((pred[nodes] == np.asarray(labels)[nodes]).mean())
