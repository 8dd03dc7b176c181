"""Sharded end-to-end training step over a device mesh.

The reference's scaling story is torch DDP (gradient allreduce over NCCL,
examples/multi_gpu/pyg/ogb-products/dist_sampling_ogb_products_quiver.py:85-117)
around per-GPU sampling + the tiered feature cache. The TPU-native story is a
single jitted step over a 2-D mesh:

- ``dp`` axis: data parallelism — per-shard seed batches, gradient ``psum``
  (replacing DDP/NCCL allreduce);
- ``ici`` axis: the hot feature table is row-sharded across chips
  (``p2p_clique_replicate`` analog, reference feature.py:225-265), assembled
  per batch with one collective gather (`sharded_gather`).

Sampling, reindex, gather, forward, backward, and the optimizer update all
trace into ONE XLA program — the compiler overlaps the collectives with
compute, which is the ICI analog of the reference overlapping NVLink peer
reads inside its gather kernel.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import axis_size_compat, shard_map_compat as _shard_map_fn

from ..pyg.sage_sampler import (
    sample_and_gather_dedup,
    sample_and_gather_fused,
)
from .collectives import (
    sharded_gather,
    sharded_gather_grouped,
    sharded_gather_hot_cold,
)
from .topology import (
    _check_layout,
    sampling_comm_bytes,
    sharded_sample_layer,
    sharded_sample_layer_grouped,
)


def make_mesh(
    n_devices: Optional[int] = None,
    dp: Optional[int] = None,
    hosts: Optional[int] = None,
) -> Mesh:
    """Build a (dp, ici) mesh over the first n local devices; ici gets the
    largest power-of-two factor so the feature shard spans chips.

    ``hosts`` adds a leading DCN axis: a (host, dp, ici) mesh where the
    feature table stripes over (host, ici) and gradients psum over
    (host, dp) — the papers100M-scale multi-host layout in one program
    (on a real pod ``host`` maps to the inter-host dimension of
    ``jax.devices()``; hermetically it is just more virtual devices).
    """
    import numpy as np

    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(
            f"make_mesh: requested {n} devices but only {len(devs)} are "
            f"visible ({devs}); for a virtual mesh set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} and "
            f'jax.config.update("jax_platforms", "cpu") before first jax use'
        )
    devs = np.array(devs[:n])
    if hosts is not None:
        if hosts <= 0 or n % hosts != 0:
            raise ValueError(f"make_mesh: hosts={hosts} does not divide {n}")
        per_host = n // hosts
        inner = make_mesh_shape(per_host, dp)
        return Mesh(devs.reshape(hosts, *inner), ("host", "dp", "ici"))
    return Mesh(devs.reshape(make_mesh_shape(n, dp)), ("dp", "ici"))


def make_mesh_shape(n: int, dp: Optional[int] = None) -> Tuple[int, int]:
    """(dp, ici) factorization: ici takes the largest power-of-two factor."""
    if dp is None:
        dp = 1
        m = n
        while m % 2 == 0 and dp < m // 2:
            dp *= 2
            m //= 2
    if dp <= 0 or n % dp != 0:
        raise ValueError(f"make_mesh: dp={dp} does not divide device count {n}")
    return dp, n // dp


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], Tuple[str, ...], int]:
    """(data_axes, feature_axes, n_data_groups) for a quiver mesh — the ONE
    place the (host?, dp, ici) layout conventions live: seeds/gradients span
    ``data_axes``, the feature table stripes over ``feature_axes``."""
    has_host = "host" in mesh.axis_names
    data_axes = ("host", "dp") if has_host else ("dp",)
    feat_axes = ("host", "ici") if has_host else ("ici",)
    n_groups = 1
    for a in data_axes:
        n_groups *= mesh.shape[a]
    return data_axes, feat_axes, n_groups


def _validate_step_config(mesh, pipeline, caps, hot_rows, cold_budget):
    """Shared precondition checks + layout facts for both step factories.
    Returns (has_host, data_axes, feat_axes, hot_cold)."""
    if pipeline not in ("dedup", "fused"):
        raise ValueError(f"unknown pipeline: {pipeline!r}")
    if pipeline == "fused" and caps is not None:
        raise ValueError(
            "caps only apply to the dedup pipeline: the fused layout is "
            "structural (width is exactly B*prod(1+k), not cappable)"
        )
    has_host = "host" in mesh.axis_names
    data_axes, feat_axes, _ = mesh_axes(mesh)
    hot_cold = hot_rows is not None
    if hot_cold and not has_host:
        raise ValueError(
            "hot_rows/cold_budget need a multi-host mesh: on a single host "
            "the plain ici-sharded gather already pays no DCN cost"
        )
    if hot_cold and cold_budget is None:
        raise ValueError("hot_rows set but cold_budget missing")
    return has_host, data_axes, feat_axes, hot_cold


def _make_gather_rows(has_host, hot_cold, feat_axes, hot_rows, cold_budget,
                      overflow_acc):
    """The per-step feature gather closure both factories share: plain
    ici-sharded, host-grouped, or replicated-hot/cold (appending each
    call's overflow to ``overflow_acc``)."""
    def gather_rows(tab, ids):
        # hosts sample DIFFERENT seeds, so the host axis needs the grouped
        # gather (see sharded_gather_grouped: all_gather ids over host,
        # gather once, slice own answer)
        if hot_cold:
            hot_block, cold_block = tab
            rows, overflow = sharded_gather_hot_cold(
                hot_block, cold_block, ids, feat_axes, "host",
                hot_rows, cold_budget,
            )
            overflow_acc.append(overflow)
            return rows
        if not has_host:
            return sharded_gather(tab, ids, feat_axes)
        return sharded_gather_grouped(tab, ids, feat_axes, "host")

    return gather_rows


def _fold_group_key(key, has_host):
    """Distinct sample stream per data-parallel group, identical within an
    ici group."""
    dp_idx = lax.axis_index("dp")
    if has_host:
        dp_idx = lax.axis_index("host") * axis_size_compat("dp") + dp_idx
    return jax.random.fold_in(key, dp_idx)


def _loss_and_update(model, tx, train, data_axes, hot_cold, overflow_acc,
                     params, opt_state, dropout_key, ds, x, labels, seeds):
    """Shared tail of both step functions: objective, grad pmean over the
    data axes (the DDP-analog allreduce), optimizer update — plus, on
    hot/cold layouts, the worst cold-budget overflow across groups as a
    FOURTH output (persistently nonzero means the budget needs raising,
    see `sharded_gather_hot_cold`)."""
    y = jnp.take(labels, jnp.clip(ds.n_id[: seeds.shape[0]], 0, labels.shape[0] - 1))

    def objective(p):
        logits = model.apply(
            p, x, ds.adjs, train=train,
            rngs={"dropout": dropout_key} if train else None,
        )
        ll = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(ll, y[:, None].astype(jnp.int32), axis=1)[:, 0]
        return nll.mean()

    loss, grads = jax.value_and_grad(objective)(params)
    grads = lax.pmean(grads, data_axes)
    loss = lax.pmean(loss, data_axes)
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    if hot_cold:
        overflow = lax.pmax(sum(overflow_acc), data_axes)
        return params, opt_state, loss, overflow
    return params, opt_state, loss


def _step_specs(hot_cold, feat_axes):
    """(feat_spec, out_specs) for shard_map: hot block replicated over host
    (striped over ici) + cold block striped over every feature axis on
    hot/cold layouts; a single striped table otherwise."""
    if hot_cold:
        ici_axes = tuple(a for a in feat_axes if a != "host")
        feat_spec = (P(ici_axes, None), P(feat_axes, None))
        return feat_spec, (P(), P(), P(), P())
    return P(feat_axes, None), (P(), P(), P())


def make_sharded_train_step(
    mesh: Mesh,
    model,
    tx,
    sizes: Sequence[int],
    caps: Optional[Sequence[Optional[int]]] = None,
    train: bool = True,
    pipeline: str = "dedup",
    hot_rows: Optional[int] = None,
    cold_budget=None,
):
    """Build ``step(params, opt_state, key, indptr, indices, feat_block,
    labels, seeds) -> (params, opt_state, loss)``.

    Sharding contract (the full tp/dp layout of this framework):
      - indptr/indices/labels: replicated (graph topology in every HBM; use
        `make_sharded_topo_train_step` to row-shard the CSR instead);
      - feat_block: hot rows striped over the ici axis, replicated over dp
        (the p2p_clique_replicate layout, reference feature.py:225-265);
      - seeds: sharded over dp, replicated over ici;
      - params/opt_state: replicated; grads psum over dp.

    ``pipeline``: "dedup" (reference-parity per-hop reindex) or "fused"
    (no-dedup structural layout; per-hop ICI gathers interleave with
    sampling — the fastest path, same tradeoff as the single-chip
    pipelines, PERF.md (earlier claims)).

    ``hot_rows``/``cold_budget`` (multi-host meshes only) switch the feature
    gather to the replicated-hot layout (`sharded_gather_hot_cold`): the
    heat-ordered table's first ``hot_rows`` rows are replicated per host
    (striped over ici) and only up to ``cold_budget`` cold lanes per gather
    ride the DCN grouped path. ``feat_block`` must then be the
    ``(hot_block, cold_block)`` pair from `shard_feature_hot_cold`;
    ``cold_budget`` may be a float fraction of each gather's width.
    Overflowing cold ids come back as zero rows, and the step returns a
    FOURTH output — the worst summed overflow across data groups this
    step; persistently nonzero means the budget needs raising
    (`calibrate_cold_budget` produces a float budget with margin).
    """
    # with a "host" DCN axis (make_mesh(hosts=...)), the feature table
    # stripes over (host, ici) and gradients sync over (host, dp)
    has_host, data_axes, feat_axes, hot_cold = _validate_step_config(
        mesh, pipeline, caps, hot_rows, cold_budget
    )

    def step_local(params, opt_state, key, indptr, indices, feat_block, labels, seeds):
        overflow_acc = []
        gather_rows = _make_gather_rows(
            has_host, hot_cold, feat_axes, hot_rows, cold_budget, overflow_acc
        )
        key, dropout_key = jax.random.split(_fold_group_key(key, has_host))
        if pipeline == "fused":
            ds, x = sample_and_gather_fused(
                indptr, indices, feat_block, key, seeds, tuple(sizes),
                gather_fn=gather_rows,
            )
        else:
            # struct-leaf dedup (same formulation as the single-chip e2e):
            # reference-parity sampling DAG, last hop's features gathered
            # straight through the sharded gather in structural layout
            ds, x = sample_and_gather_dedup(
                indptr, indices, feat_block, key, seeds, tuple(sizes), caps,
                gather_fn=gather_rows,
            )
        return _loss_and_update(
            model, tx, train, data_axes, hot_cold, overflow_acc,
            params, opt_state, dropout_key, ds, x, labels, seeds,
        )

    feat_spec, out_specs = _step_specs(hot_cold, feat_axes)
    sharded = _shard_map_fn(
        step_local,
        mesh=mesh,
        in_specs=(
            P(),            # params (replicated)
            P(),            # opt_state
            P(),            # rng key
            P(),            # indptr
            P(),            # indices
            feat_spec,      # feature rows (see docstring)
            P(),            # labels
            P(data_axes),   # seeds sharded over (host?,) dp
        ),
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded)


def _topo_sample_local(pipeline, sizes, caps, has_host, hot_cold, feat_axes,
                       hot_rows, cold_budget, key, stopo, feat_block, seeds):
    """What a sharded-topology step samples and gathers, inside shard_map:
    ``(ds, x, dropout_key, overflow_acc)``. The train step and
    `make_sharded_topo_sample` both run exactly this, so the same key and
    seeds give the same draws and rows in either program."""
    overflow_acc = []
    gather_rows = _make_gather_rows(
        has_host, hot_cold, feat_axes, hot_rows, cold_budget, overflow_acc
    )
    row_start = stopo.row_start     # [P+1] replicated boundaries
    # this shard's blocks, the [R_max, 2] local windows and the [E_pad] edge
    # block, the leading shard axis of length 1 dropped by a reshape:
    # ``block[0]`` compiles to a COPY of the block every step (3.7 ms for the
    # 808 MB edge block of the papers100M cell; PERF.md)
    blocks = tuple(b.reshape(b.shape[1:]) for b in (stopo.windows, stopo.indices))

    def sample_fn(cur, cur_valid, k, sub):
        if not has_host:
            return sharded_sample_layer(
                *blocks, row_start, cur, cur_valid, k, sub, feat_axes)
        return sharded_sample_layer_grouped(
            *blocks, row_start, cur, cur_valid, k, sub, feat_axes, "host")

    key, dropout_key = jax.random.split(_fold_group_key(key, has_host))
    if pipeline == "fused":
        ds, x = sample_and_gather_fused(
            None, None, feat_block, key, seeds, tuple(sizes),
            gather_fn=gather_rows, sample_fn=sample_fn,
        )
    else:
        ds, x = sample_and_gather_dedup(
            None, None, feat_block, key, seeds, tuple(sizes), caps,
            gather_fn=gather_rows, sample_fn=sample_fn,
        )
    return ds, x, dropout_key, overflow_acc


def make_sharded_topo_train_step(
    mesh: Mesh,
    model,
    tx,
    sizes: Sequence[int],
    caps: Optional[Sequence[Optional[int]]] = None,
    train: bool = True,
    pipeline: str = "dedup",
    hot_rows: Optional[int] = None,
    cold_budget=None,
    layout: Optional[str] = None,
):
    """`make_sharded_train_step` with the GRAPH row-sharded across the mesh.

    Build ``step(params, opt_state, key, stopo, feat_block,
    labels, seeds) -> (params, opt_state, loss)``. Unlike
    `make_sharded_train_step` — which replicates indptr/indices in every
    HBM — each device holds only its contiguous CSR block
    (`topology.shard_topology_rows`), so total graph capacity scales with
    chip count: the papers100M axis the reference reaches with UVA
    (quiver_sample.cu:361-421, train_quiver_multi_node.py). Each hop's
    neighbor draw becomes a psum collective over the topology axes
    (`topology.sharded_sample_layer`); with a "host" axis the frontier is
    first all_gathered over it (hosts sample different seeds), mirroring the
    grouped feature gather.

    ``stopo`` is the `ShardedTopology` that `shard_topology_rows` placed.
    ``layout`` chooses nothing (`topology._check_layout`, ROADMAP D13).

    ``hot_rows``/``cold_budget`` compose the replicated-hot feature tier
    with the sharded topology (multi-host meshes; same contract as
    `make_sharded_train_step`): pass ``(hot_block, cold_block)`` from
    `shard_feature_hot_cold` as ``feat_block``.

    The compiled program is named ``sharded_topo_train_step``
    (`trace.STEP_PROGRAM_NAMES`). Per-step collective traffic is statically
    modeled by `topology.sampling_comm_bytes` (`step_comm_bytes`: one number
    a step). `make_sharded_topo_sample` returns what a step sampled and
    gathered.
    """
    _check_layout(layout)
    has_host, data_axes, feat_axes, hot_cold = _validate_step_config(
        mesh, pipeline, caps, hot_rows, cold_budget
    )
    feat_spec, out_specs = _step_specs(hot_cold, feat_axes)

    def step_local(params, opt_state, key, stopo, feat_block, labels, seeds):
        ds, x, dropout_key, overflow_acc = _topo_sample_local(
            pipeline, sizes, caps, has_host, hot_cold, feat_axes,
            hot_rows, cold_budget, key, stopo, feat_block, seeds,
        )
        return _loss_and_update(
            model, tx, train, data_axes, hot_cold, overflow_acc,
            params, opt_state, dropout_key, ds, x, labels, seeds,
        )

    def sharded_topo_train_step(params, opt_state, key, stopo, feat_block,
                                labels, seeds):
        return _shard_map_fn(
            step_local,
            mesh=mesh,
            in_specs=(
                P(),            # params (replicated)
                P(),            # opt_state
                P(),            # rng key
                stopo.specs(feat_axes),  # CSR blocks + boundaries
                feat_spec,      # feature rows (see docstring)
                P(),            # labels
                P(data_axes),   # seeds sharded over (host?,) dp
            ),
            out_specs=out_specs,
            check_vma=False,
        )(params, opt_state, key, stopo, feat_block, labels, seeds)

    return jax.jit(sharded_topo_train_step)


def make_sharded_topo_sample(
    mesh: Mesh,
    sizes: Sequence[int],
    caps: Optional[Sequence[Optional[int]]] = None,
    pipeline: str = "dedup",
    hot_rows: Optional[int] = None,
    cold_budget=None,
    layout: Optional[str] = None,
):
    """What `make_sharded_topo_train_step` samples, as a program of its own:
    ``sample(key, stopo, feat_block, seeds) -> (ds, x)``, the step's own
    code up to its loss (`_topo_sample_local`), so the same ``key`` and
    ``seeds`` give the `DenseSample` and the gathered rows ``x`` that the
    step trained on — for evaluation, for debugging a loss, for holding a
    step against the host graph. Every array of ``ds`` and ``x`` gains a
    leading axis of one entry per data-parallel group (groups fold their
    index into the key and sample their own share of ``seeds``). ``layout``
    as on the step."""
    _check_layout(layout)
    has_host, data_axes, feat_axes, hot_cold = _validate_step_config(
        mesh, pipeline, caps, hot_rows, cold_budget
    )
    n_groups = mesh_axes(mesh)[2]
    feat_spec, _ = _step_specs(hot_cold, feat_axes)

    def sample_local(key, stopo, feat_block, seeds):
        ds, x, _, _ = _topo_sample_local(
            pipeline, sizes, caps, has_host, hot_cold, feat_axes,
            hot_rows, cold_budget, key, stopo, feat_block, seeds,
        )
        # the static batch size is no array: put back outside the program
        return jax.tree_util.tree_map(
            lambda a: a[None], (ds._replace(batch_size=None), x))

    @jax.jit
    def sharded_topo_sample(key, stopo, feat_block, seeds):
        return _shard_map_fn(
            sample_local,
            mesh=mesh,
            in_specs=(P(), stopo.specs(feat_axes), feat_spec, P(data_axes)),
            out_specs=P(data_axes),
            check_vma=False,
        )(key, stopo, feat_block, seeds)

    def sample(key, stopo, feat_block, seeds):
        ds, x = sharded_topo_sample(key, stopo, feat_block, seeds)
        return ds._replace(batch_size=seeds.shape[0] // n_groups), x

    return sample


def step_comm_bytes(mesh: Mesh, sizes: Sequence[int], batch_per_group: int,
                    feature_dim: int, caps=None, **model) -> float:
    """The modelled collective bytes a chip moves in ONE sharded-topology
    step (`topology.sampling_comm_bytes` with the per-hop feature gathers:
    neighbour and mask all-reduces plus the row all-reduces, ring costs,
    float32 rows). A static number of the step's shapes: compute it once,
    where the step is built, and count it per dispatched step with
    ``trace.observe("quiver.step.comm_bytes", total)`` (recorded only while
    tracing is on)."""
    return sampling_comm_bytes(
        mesh, sizes, batch_per_group, feature_dim=feature_dim, caps=caps, **model
    )["total_bytes"]


def _place_rows(mesh: Mesh, axes, table) -> jax.Array:
    """``table`` [N, D] row-striped over ``axes`` (replicated over the other
    mesh axes), zero-padded to a multiple of the shard count, uploaded
    shard by shard from slices of the host table (`place_shards`): no device
    holds more than its rows and the host makes no padded copy of the
    whole. ``table`` needs only ``shape``, ``dtype`` and row slicing (a
    numpy array, a memmap)."""
    import math

    import numpy as np

    from ..trace import trace_scope
    from .collectives import place_shards

    shards = math.prod(mesh.shape[a] for a in axes)
    n, dim = table.shape
    rows = -(-n // shards)

    def block(p):
        part = np.asarray(table[p * rows : min((p + 1) * rows, n)])
        if part.shape[0] < rows:  # the tail shard alone is padded
            part = np.concatenate(
                [part, np.zeros((rows - part.shape[0], dim), part.dtype)])
        return (part,)

    chip_bytes = rows * dim * np.dtype(table.dtype).itemsize
    with trace_scope("quiver.shard.features", chip_bytes=chip_bytes, shards=shards) as span:
        (placed,) = place_shards(mesh, axes, [(rows * shards, dim)], block)
        span.sync = placed
    return placed


def shard_feature_rows(mesh: Mesh, table) -> jax.Array:
    """Place a [N, D] host table row-striped over the feature axes — ici,
    plus host when the mesh has the DCN axis (replicated over dp); pads N
    to a multiple of the shard count. Uploaded shard by shard
    (`_place_rows`): a table larger than one chip is placed as long as a
    shard fits."""
    _, feat_axes, _ = mesh_axes(mesh)
    return _place_rows(mesh, feat_axes, table)


def shard_feature_hot_cold(
    mesh: Mesh, table, hot_rows: int
) -> Tuple[jax.Array, jax.Array]:
    """Split a heat-ordered [N, D] table for `sharded_gather_hot_cold`:
    rows ``< hot_rows`` replicated per host (striped over ici), the cold
    remainder striped over every feature axis. Zero-pads both blocks to
    their shard multiples (hot padding rows MUST be zero — cold ids landing
    in the padded hot range rely on it). Order the table by heat first
    (``Feature`` degree order / `utils.reindex_by_config`) — the analog of
    the reference's replicate-hottest preprocessing
    (mag240m preprocess.py:117-179)."""
    _, feat_axes, _ = mesh_axes(mesh)
    ici_axes = tuple(a for a in feat_axes if a != "host")
    if ici_axes == feat_axes:
        raise ValueError("hot/cold placement needs a multi-host mesh")
    if not 0 < hot_rows < table.shape[0]:
        raise ValueError(f"hot_rows {hot_rows} out of range for {table.shape}")
    return (_place_rows(mesh, ici_axes, table[:hot_rows]),
            _place_rows(mesh, feat_axes, table[hot_rows:]))


def calibrate_cold_budget(
    sampler,
    probe_seeds,
    hot_rows: int,
    margin: float = 1.3,
) -> float:
    """Cold-lane budget FRACTION for `sharded_gather_hot_cold`, calibrated
    like the sampler caps: max observed cold share of the sampled id space
    over probe batches x ``margin`` (capped at 1.0).

    A fraction — not a lane count — because the train steps gather at
    several static widths per step (frontier block, structural leaf block);
    `sharded_gather_hot_cold` scales a float budget to each call's width
    with a 256-lane granule. The id space must be heat-ordered (rows <
    ``hot_rows`` are the replicated tier) — the convention the gather
    itself assumes."""
    import numpy as np

    shares = []
    for seeds in probe_seeds:
        ds = sampler.sample_dense(np.asarray(seeds))
        n_id = np.asarray(ds.n_id)
        # prefix-valid (dedup) samples: count real lanes only; structural
        # samples interleave invalid lanes that carry real sampled ids, so
        # counting every lane is the conservative choice there
        if all(a.cols is not None for a in ds.adjs):
            n_id = n_id[: int(ds.count)]
        if n_id.shape[0]:
            shares.append(float((n_id >= hot_rows).mean()))
    if not shares:
        raise ValueError("calibrate_cold_budget needs at least one probe batch")
    return float(min(max(shares) * margin, 1.0))


def replicate(mesh: Mesh, x):
    """Place an array or pytree fully replicated on the mesh (a host array
    goes to each device directly, not through the default device)."""
    return jax.device_put(x, NamedSharding(mesh, P()))
