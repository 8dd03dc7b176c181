"""papers100M-axis workflow: train GraphSAGE on a graph that does NOT fit
one device's memory — the reference's ogbn-papers100M story
(benchmarks/ogbn-papers100M/train_quiver_multi_node.py: UVA-resident 111M-node
CSR + partitioned Feature + NCCL DistFeature) re-designed for TPU.

Two layouts, both turnkey at any scale (defaults are hermetic-small; pass
--nodes 111000000 --avg-deg 29 on a pod for the real shape, or --dataset
papers100M.npz from scripts/export_ogb.py):

- ``--layout sharded`` (multi-chip): the CSR is row-sharded over the mesh
  (`shard_topology_rows` — no chip holds the full graph), features ride the
  replicated-hot/cold tier on multi-host meshes, sampling hops are psum
  collectives. Graph capacity scales with chip count; per-step ICI/DCN
  bytes are logged from the same static model `SCALING.md` uses.
- ``--layout host`` (single chip): the CSR stays in host DRAM and the
  native engine samples (HOST mode = the UVA analog, SURVEY.md section
  7.3); features run the tiered hot-HBM/cold-host(/mmap-disk) prefetch
  pipeline (`TrainPipeline`), so neither graph nor features need to fit
  HBM. The benchmark's cell ``papers100M-sage-tiered.train-hot6g`` (PR 32,
  ``qbench/TIERED.md``) measures the same tiered `Feature` +
  `TrainPipeline` + `make_tiered_train_step` with the sampler on the
  DEVICE (``GraphSageSampler(mode="TPU", layout="flat")``: the flat graph
  of half of ogbn-papers100M is 3.45 GB of HBM); ``mode="HOST"`` sampling,
  which this script's host layout runs, is the other, unmeasured way.

Run hermetically: QUIVER_VIRTUAL_DEVICES=8 python benchmarks/papers100M_workflow.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _maybe_force_virtual_devices():
    n = os.environ.get("QUIVER_VIRTUAL_DEVICES")
    if n:
        from quiver_tpu.utils import force_virtual_cpu_devices

        force_virtual_cpu_devices(int(n))


def build_graph(args):
    from quiver_tpu.datasets import load_npz, synthetic_powerlaw

    if args.dataset:
        d = load_npz(args.dataset)
        return d["edge_index"], d["features"], d["labels"], d["train_idx"]
    n, e = args.nodes, args.nodes * args.avg_deg
    return synthetic_powerlaw(
        n, e, dim=args.dim, classes=args.classes, train_frac=0.2, seed=0
    )


def run_sharded(args, edge_index, feat, labels, train_idx, val_idx):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quiver_tpu import CSRTopo
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import (
        calibrate_cold_budget,
        make_mesh,
        make_sharded_topo_train_step,
        mesh_axes,
        replicate,
        shard_feature_hot_cold,
        shard_feature_rows,
        shard_topology_rows,
    )
    from quiver_tpu.parallel.topology import sampling_comm_bytes
    from quiver_tpu.pyg import GraphSageSampler

    n = feat.shape[0]
    sizes = tuple(int(s) for s in args.sizes.split(","))
    mesh = make_mesh(hosts=args.hosts or None)
    data_axes, _, dp = mesh_axes(mesh)
    print(f"mesh {dict(mesh.shape)}: {dp} data groups")

    topo = CSRTopo(edge_index=edge_index)
    stopo = shard_topology_rows(mesh, topo)
    per_shard = stopo.indices.shape[1]
    total = topo.indices.shape[0]
    print(
        f"sharded CSR: {total} edges -> {per_shard} per shard "
        f"({per_shard / total:.1%} of the graph per device)"
    )

    rng = np.random.default_rng(0)
    sampler = GraphSageSampler(topo, sizes=sizes, mode="TPU", seed=7)
    # probe at the TRAINING batch size (caps scale with B) over >= 8
    # batches (calibrate_caps docstring: fewer gives an unstable max)
    probe_b = min(args.batch_per_dp, len(train_idx))
    probes = [rng.choice(train_idx, probe_b) for _ in range(8)]
    caps = sampler.calibrate_caps(np.stack(probes), margin=1.2)
    hot_rows = int(n * args.hot_frac) if args.hot_frac and args.hosts else None
    cold_budget = (
        calibrate_cold_budget(sampler, probes, hot_rows) if hot_rows else None
    )
    comm = sampling_comm_bytes(
        mesh, sizes, args.batch_per_dp, feature_dim=feat.shape[1], caps=caps
    )
    print(
        f"caps {caps}; per-step comm model: ici {comm['ici_bytes']/1e6:.1f} MB, "
        f"dcn {comm['dcn_bytes']/1e6:.1f} MB"
        + (f"; hot tier {hot_rows} rows, cold budget {cold_budget:.2f}" if hot_rows else "")
    )

    model = GraphSAGE(
        hidden_dim=args.hidden, out_dim=args.classes, num_layers=len(sizes),
        dropout=0.5,
    )
    tx = optax.adam(1e-3)
    step = make_sharded_topo_train_step(
        mesh, model, tx, sizes=sizes, caps=caps,
        hot_rows=hot_rows, cold_budget=cold_budget,
    )
    feat_d = (
        shard_feature_hot_cold(mesh, feat, hot_rows)
        if hot_rows else shard_feature_rows(mesh, feat)
    )
    labels_d = replicate(mesh, labels)

    from quiver_tpu.pyg.sage_sampler import sample_dense_pure

    # init-shape probe through the sampler's own device arrays: CSRTopo
    # picks the id dtype (and refuses int64 when x64 is off) instead of a
    # hand-rolled int32 cast that would wrap >2^31-edge graphs
    # flat device pair for the init-shape probe (lazy_init_quiver
    # returns the TILED binding under the default layout)
    ip0, ix0 = sampler.csr_topo.to_device()
    ds0 = sample_dense_pure(
        ip0, ix0, jax.random.key(0),
        jnp.arange(args.batch_per_dp, dtype=ix0.dtype), sizes, caps,
    )
    x0 = jnp.zeros((ds0.n_id.shape[0], feat.shape[1]), jnp.float32)
    params = replicate(
        mesh,
        model.init(
            {"params": jax.random.key(1), "dropout": jax.random.key(2)},
            x0, ds0.adjs, train=True,
        ),
    )
    opt_state = jax.device_put(tx.init(params), NamedSharding(mesh, P()))

    batch_global = args.batch_per_dp * dp
    steps = args.steps_per_epoch or max(len(train_idx) // batch_global, 1)
    for epoch in range(args.epochs):
        t0 = time.time()
        for i in range(steps):
            seeds = jax.device_put(
                jnp.asarray(rng.choice(train_idx, batch_global).astype(np.int32)),
                NamedSharding(mesh, P(data_axes)),
            )
            out = step(params, opt_state, jax.random.key(epoch * 10000 + i),
                       stopo, feat_d, labels_d, seeds)
            if hot_rows:
                params, opt_state, loss, overflow = out
            else:
                (params, opt_state, loss), overflow = out, None
        jax.block_until_ready(loss)
        dt = time.time() - t0
        # persistent nonzero overflow = cold rows silently zeroed: raise
        # the budget (same monitoring as examples/products_multichip.py)
        ov = f"  cold_overflow={int(overflow)}" if overflow is not None else ""
        print(f"epoch {epoch}: {dt:.2f}s  loss={float(loss):.4f}  "
              f"{steps * batch_global / dt:.0f} seeds/s{ov}")
    # fresh UNCAPPED sampler for eval: the training caps were calibrated
    # for batch_per_dp-seed batches and would truncate bigger eval batches
    eval_sampler = GraphSageSampler(topo, sizes=sizes, mode="TPU", seed=123)
    return model, params, eval_sampler


def run_host(args, edge_index, feat, labels, train_idx, val_idx, mmap_dir):
    import jax
    import optax

    from quiver_tpu import CSRTopo, Feature
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.pipeline import TrainPipeline, make_tiered_train_step
    from quiver_tpu.pyg import GraphSageSampler

    n, dim = feat.shape
    sizes = tuple(int(s) for s in args.sizes.split(","))
    topo = CSRTopo(edge_index=edge_index)
    # graph stays host-side; native engine samples (the UVA analog)
    sampler = GraphSageSampler(topo, sizes=sizes, mode="HOST", seed=7)
    hot_rows = max(int(n * (args.hot_frac or 0.2)), 1)
    from quiver_tpu.feature import DeviceConfig

    if mmap_dir:  # disk tier: cold rows never touch RAM either
        path = os.path.join(mmap_dir, "feat.npy")
        np.save(path, feat)
        mm = np.load(path, mmap_mode="r")
        feature = Feature.from_mmap(mm, DeviceConfig([0], hot_rows * dim * 4))
    else:
        feature = Feature(
            rank=0, device_list=[0],
            device_cache_size=hot_rows * dim * 4, csr_topo=topo,
        )
        feature.from_cpu_tensor(feat)
    print(f"HOST layout: graph in DRAM, hot {hot_rows}/{n} rows in HBM"
          + (", cold tier on disk (mmap)" if mmap_dir else ""))

    import jax.numpy as jnp

    labels_d = jax.device_put(jnp.asarray(labels))
    model = GraphSAGE(
        hidden_dim=args.hidden, out_dim=args.classes, num_layers=len(sizes),
        dropout=0.5,
    )
    tx = optax.adam(1e-3)
    from quiver_tpu.pipeline import TieredFeaturePipeline

    pipe = TieredFeaturePipeline(feature)
    step_fn = make_tiered_train_step(model, tx, labels_d, pipe.hot_table)
    # share the ONE tiered pipeline (step_fn closes over its hot_table)
    tp = TrainPipeline(sampler, feature, step_fn, depth=2, tiered=pipe)

    rng = np.random.default_rng(0)
    b0 = tp._stage(rng.choice(train_idx, args.batch_per_dp))
    from quiver_tpu.pipeline import tiered_lookup

    x0 = tiered_lookup(pipe.hot_table, b0.mapped, b0.cold_rows, b0.cold_pos)
    params = model.init(
        {"params": jax.random.key(1), "dropout": jax.random.key(2)},
        x0, b0.ds.adjs, train=True,
    )
    opt_state = tx.init(params)
    steps = args.steps_per_epoch or max(len(train_idx) // args.batch_per_dp, 1)
    for epoch in range(args.epochs):
        batches = [rng.choice(train_idx, args.batch_per_dp) for _ in range(steps)]
        t0 = time.time()
        params, opt_state, losses = tp.run_epoch(
            batches, params, opt_state, jax.random.key(epoch)
        )
        dt = time.time() - t0
        print(f"epoch {epoch}: {dt:.2f}s  loss={float(losses[-1]):.4f}  "
              f"{steps * args.batch_per_dp / dt:.0f} seeds/s  "
              f"(cold rows seen: {tp.tiered.cold_rows_seen})")
    eval_sampler = GraphSageSampler(topo, sizes=sizes, mode="HOST", seed=123)
    return model, params, eval_sampler


def main():
    _maybe_force_virtual_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", default="sharded", choices=["sharded", "host"])
    ap.add_argument("--nodes", type=int, default=60_000)
    ap.add_argument("--avg-deg", type=int, default=12)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--sizes", default="10,5")
    ap.add_argument("--batch-per-dp", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--steps-per-epoch", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=0)
    ap.add_argument("--hot-frac", type=float, default=0.0)
    ap.add_argument("--mmap-dir", default="", help="host layout: put the cold "
                    "feature tier in a memory-mapped file here (disk tier)")
    ap.add_argument("--dataset", default="", help=".npz from scripts/export_ogb.py")
    args = ap.parse_args()

    edge_index, feat, labels, train_idx = build_graph(args)
    n = feat.shape[0]
    rest = np.setdiff1d(np.arange(n), train_idx)
    val_idx = rest[: max(n // 20, 1)]
    if args.layout == "sharded" and args.hot_frac and args.hosts:
        # heat-order the id space so the replicated tier is the hot prefix
        # (reference mag240m preprocess.py:117-179 does this offline); must
        # happen before ANY id-space consumer — topology, splits, eval
        from quiver_tpu.utils import heat_reorder

        edge_index, feat, labels, (train_idx, val_idx), _, _ = heat_reorder(
            edge_index, n, feat, labels, (train_idx, val_idx)
        )

    if args.layout == "sharded":
        model, params, sampler = run_sharded(
            args, edge_index, feat, labels, train_idx, val_idx
        )
    else:
        model, params, sampler = run_host(
            args, edge_index, feat, labels, train_idx, val_idx,
            args.mmap_dir or None,
        )

    import jax

    from quiver_tpu.inference import sampled_eval

    host_params = jax.tree_util.tree_map(np.asarray, params)
    acc = sampled_eval(
        model, host_params, sampler, feat, labels, val_idx,
        batch_size=min(512, len(val_idx)),
    )
    print(f"val acc: {acc:.4f} ({len(val_idx)} nodes)")


if __name__ == "__main__":
    main()
