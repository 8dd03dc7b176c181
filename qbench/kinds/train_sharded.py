"""Sharded train cells: the library's multi-chip path, driven as
`examples/products_multichip.py` and `chip_smoke.four_chip_phase` drive it:

    make_mesh(chips, dp) -> shard_topology_rows + shard_feature_rows
      -> make_sharded_topo_train_step(pipeline=...)  (ONE program a step)
      -> block_until_ready(loss)

The graph and the feature table exist only across the chips: no chip holds
either. One `ShardedCell` is built from the seed (the table goes up while
the graph is still being made), driven through its first three steps by the window's own call and then handed to the window. The
step program returns a loss and nothing it sampled, so afterwards the
library's `make_sharded_topo_sample` (the step's own code up to the loss)
gives the samples and gathered rows of those three steps from the same keys
and seeds; they are held against the host CSR rows of their targets and the
host table, and the plain reference follows the three steps on them.
`qbench.limits_sharded` drives the same object over many seeds.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

from .. import check, graphgen, harness, manifest, reference, work
from . import train

CHECK_STEPS = train.CHECK_STEPS
GATHER_SAMPLE = train.GATHER_SAMPLE
FAULTS = train.FAULTS
SHARE_OF_TOTAL = (0.20, 0.35)  # a chip's bytes in use over all sharded bytes


class HostData:
    """The run's data on the host, from the seed: the graph on one thread
    and the table on another (both fan out over `graphgen.GEN_THREADS`).
    ``place_table(features)`` runs on the table's thread as soon as the
    table is made, so that its upload goes beside the rest of the graph."""

    def __init__(self, config: Dict[str, Any], seed: int, place_table=None):
        g = config["graph"]

        def graph():
            t0 = time.perf_counter()
            out = graphgen.powerlaw_graph(
                config["n_nodes"], config["n_edges"], seed, alpha=g["alpha"],
                shift=g["shift"], max_degree=g["max_degree"])
            return out, time.perf_counter() - t0

        def table():
            t0 = time.perf_counter()
            features, labels = graphgen.features_and_labels(
                config["n_nodes"], config["feat_dim"], config["classes"], seed,
                label_signal=config["label_signal"])
            split = graphgen.train_split(config["n_nodes"], config["train_nodes"], seed)
            made_s = time.perf_counter() - t0
            return features, labels, split, made_s, place_table and place_table(features)

        with ThreadPoolExecutor(2) as pool:
            made_graph, made_table = pool.submit(graph), pool.submit(table)
            self.graph, self.graph_s = made_graph.result()
            (self.features, self.labels, self.train_idx, self.features_s,
             self.placed_table) = made_table.result()


class HostRows:
    """The host table behind ``table[ids]``, for `train.follow_with_reference`:
    the reference's rows come from the host, a step's worth at a time (the
    whole table is larger than the chip the reference runs on)."""

    def __init__(self, features: np.ndarray):
        self.features = features

    def __getitem__(self, ids):
        import jax.numpy as jnp

        return jnp.asarray(self.features[np.asarray(ids)])


class RowOracle(check.EdgeOracle):
    """`check.EdgeOracle`'s answers from the CSR rows of ``nodes`` alone:
    sorting one key per edge of the whole graph would take minutes and
    6.5 GB at 808M edges, and only the sampled targets' rows are asked."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, nodes: np.ndarray):
        self.n = int(indptr.shape[0] - 1)
        self.degree = np.diff(indptr)
        nodes = np.unique(np.clip(nodes.astype(np.int64), 0, self.n - 1))
        lens = self.degree[nodes]
        ends = np.cumsum(lens)
        # slot j of the concatenated rows reads indices[start(row) + j - first(row)]
        at = np.repeat(indptr[nodes] - (ends - lens), lens)
        at += np.arange(int(ends[-1]) if ends.size else 0, dtype=np.int64)
        keys = np.repeat(nodes * self.n, lens)
        keys += indices[at]
        keys.sort()
        self.keys = keys if keys.size else np.full(1, -1, np.int64)


class HalfBatch:
    """The planted fault ``half_batch``: the second half of the batch
    teaches nothing (its logits are constants), as a wrapper of the model
    the library's step is built on."""

    def __init__(self, model):
        self.model = model

    def apply(self, *args, **kwargs):
        import jax.numpy as jnp

        logits = self.model.apply(*args, **kwargs)
        keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.where(keep[:, None], logits, 0.0)


def frozen(tx):
    """The planted fault ``state_unchanged``: an optimizer that keeps its
    state and proposes no update."""
    import jax
    import jax.numpy as jnp
    import optax

    return optax.GradientTransformation(
        tx.init, lambda grads, state, params=None: (jax.tree.map(jnp.zeros_like, grads), state))


class ShardedCell:
    """The placed graph and table, the compiled step and its state: what
    set-up builds, what the first steps drive and what the window is handed."""

    def __init__(self, cell: manifest.Cell, seed: int, *,
                 compute_dtype: Optional[str] = None, fault: Optional[str] = None):
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from quiver_tpu import CSRTopo
        from quiver_tpu.parallel import (make_mesh, make_sharded_topo_sample, mesh_axes,
                                         replicate, shard_feature_rows, shard_topology_rows,
                                         step_comm_bytes)

        cfg, traffic = cell.config, cell.traffic
        if cfg["dropout"] != 0.0:
            raise ValueError("the reference follows no dropout mask: dropout must be 0")
        self.cfg, self.traffic, self.chips = cfg, traffic, cell.chips
        self.batch, self.sizes = int(cfg["batch"]), tuple(cfg["fanout"])
        self.pipeline = traffic["pipeline"]
        self.timing: Dict[str, Any] = {}

        self.mesh = make_mesh(cell.chips, dp=int(traffic["dp"]))
        data_axes, _, groups = mesh_axes(self.mesh)
        if groups != 1:
            raise ValueError("data-parallel groups draw from their own keys; the check "
                             "follows one group: dp must be 1")
        self.seed_sharding = NamedSharding(self.mesh, P(data_axes))
        # the model of the step's collective bytes: a number of its shapes
        self.comm_bytes = step_comm_bytes(self.mesh, self.sizes, self.batch, cfg["feat_dim"])
        self.replicated = NamedSharding(self.mesh, P())

        def place_table(features):
            t0 = time.perf_counter()
            placed = jax.block_until_ready(shard_feature_rows(self.mesh, features))
            self.timing["feature_upload_s"] = time.perf_counter() - t0
            return placed

        t0 = time.perf_counter()
        self.data = data = HostData(cfg, seed, place_table)
        self.feat = data.placed_table
        self.timing.update(graph_s=data.graph_s, features_s=data.features_s,
                           data_and_table_s=time.perf_counter() - t0)
        t0 = time.perf_counter()
        topo = CSRTopo(indptr=data.graph.indptr, indices=data.graph.indices)
        self.stopo = shard_topology_rows(self.mesh, topo, layout=None)
        self.labels = replicate(self.mesh, data.labels)
        jax.block_until_ready((self.stopo, self.labels))
        self.timing["topology_upload_s"] = time.perf_counter() - t0
        self.timing["topology_layout"] = type(self.stopo).__name__
        self.placement = self.placement_faults()

        self.tx = optax.adam(cfg["lr"])
        self.sample = make_sharded_topo_sample(self.mesh, self.sizes,
                                               pipeline=self.pipeline, layout=None)
        self.take_rows = jax.jit(lambda x, sel: x[sel])
        self.key_shape = jax.random.key_data(jax.random.key(0)).shape
        self.rebuild_step(compute_dtype, fault)
        self.reseed(seed)

    def placement_faults(self) -> Dict[str, int]:
        """Sharded arrays that are not split one share a chip, and chips
        whose bytes in use are not about one share of all sharded bytes (the
        whole table on the first chip would be four shares there). The CPU
        backend keeps no memory statistics: the rehearsal holds the shapes."""
        import jax

        sharded = {"features": self.feat}
        sharded.update({f"topology.{k}": v for k, v in self.stopo._asdict().items()
                        if k != "row_start"})
        unsplit = 0
        for arr in sharded.values():
            shards = arr.addressable_shards
            unsplit += not (len({s.device for s in shards}) == self.chips and all(
                s.data.shape[0] * self.chips == arr.shape[0] for s in shards))
        total = sum(int(a.nbytes) for a in sharded.values())
        stats = [d.memory_stats() for d in jax.devices()[: self.chips]]
        in_use = [int(s["bytes_in_use"]) for s in stats if s]
        lo, hi = SHARE_OF_TOTAL
        self.timing.update(sharded_bytes=total, bytes_in_use_per_chip=in_use,
                           shapes={k: list(v.shape) for k, v in sharded.items()})
        return {"unsplit_arrays": int(unsplit),
                "chips_off_their_share": sum(not lo * total < b < hi * total for b in in_use)}

    def rebuild_step(self, compute_dtype: Optional[str], fault: Optional[str]) -> None:
        """The model and the library's step over the same placed graph and
        table: as the configuration states, or in the control's precision
        (the library's own ``dtype=bfloat16`` path), or with a fault planted."""
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.parallel import make_sharded_topo_train_step

        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg = self.cfg
        model = GraphSAGE(hidden_dim=cfg["hidden_dim"], out_dim=cfg["classes"],
                          num_layers=cfg["num_layers"], dropout=cfg["dropout"],
                          dtype=train.compute_dtype_of(compute_dtype))
        self.train_step = make_sharded_topo_train_step(
            self.mesh, HalfBatch(model) if fault == "half_batch" else model,
            frozen(self.tx) if fault == "state_unchanged" else self.tx,
            self.sizes, pipeline=self.pipeline, layout=None)

    def reseed(self, seed: int) -> None:
        """Fresh weights, optimizer state, batches and sampling keys from
        ``seed`` over the same placed graph and compiled programs."""
        import jax

        self.seed = seed
        self.params = jax.device_put(reference.params_of(self.cfg, seed), self.replicated)
        self.opt_state = jax.device_put(self.tx.init(self.params), self.replicated)
        self.batches = train.seed_batches(self.data.train_idx, self.batch, seed)
        self.steps_done = 0
        self.first: List[Dict[str, Any]] = []
        self.snap: Dict[str, Any] = {}

    def key_of(self, step: int):
        """Step ``step``'s sampling key, from the seed: raw key words made
        on the host and wrapped, so that no program runs beside the step."""
        import jax

        words = graphgen.stream(self.seed, 9, step).integers(
            0, 2**32, self.key_shape, dtype=np.uint32)
        return jax.random.wrap_key_data(jax.device_put(words, self.replicated))

    def step(self, seeds: np.ndarray):
        """The window's own call: one training step, one program, ended with
        block_until_ready. Returns the loss, still on the device."""
        import jax
        from jax.profiler import TraceAnnotation

        from quiver_tpu.trace import observe

        with TraceAnnotation("qbench.train_step"):
            placed = jax.device_put(seeds.astype(np.int32), self.seed_sharding)
            self.params, self.opt_state, loss = self.train_step(
                self.params, self.opt_state, self.key_of(self.steps_done), self.stopo,
                self.feat, self.labels, placed)
            observe("quiver.step.comm_bytes", self.comm_bytes)
        self.steps_done += 1
        with TraceAnnotation("qbench.wait"):
            jax.block_until_ready(loss)
        return loss

    def first_steps(self) -> None:
        """Steps 1..3 through `step`, keeping what the check needs: each
        step's seeds and loss (its key follows from its number), the
        optimizer state after step 1 (Adam's first moment gives the first
        gradient as the optimizer got it) and the parameters before step 1
        and after step 3."""
        self.snap["params0"] = self.params
        for i in range(CHECK_STEPS):
            seeds = next(self.batches)
            self.first.append({"seeds": seeds, "step": self.steps_done,
                               "loss": self.step(seeds)})
            if i == 0:
                self.snap["opt_state1"] = self.opt_state
        self.snap["params3"] = self.params

    def window(self, seconds: float) -> Dict[str, Any]:
        """Steps until ``seconds`` have passed. The rate is over all seeds
        and the whole window, the last step's overshoot included."""
        losses = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            losses.append(self.step(next(self.batches)))
            now = time.perf_counter()
            if now >= deadline:
                break
        elapsed = now - t0
        losses = np.asarray([float(l) for l in losses])
        return {"steps": len(losses), "elapsed_s": elapsed,
                "seeds_per_s": len(losses) * self.batch / elapsed,
                "nonfinite_losses": int((~np.isfinite(losses)).sum()),
                "loss_first": float(losses[0]), "loss_last": float(losses[-1])}

    # -- what the check reads, pulled to the host --------------------------

    def collect(self) -> Dict[str, Any]:
        """Everything the check compares, as numpy, in `train.TrainCell
        .collect`'s form: the first steps' samples and a seed-drawn sample of
        their gathered rows come from the library's sample program on those
        steps' keys and seeds."""
        import jax

        steps = []
        for i, f in enumerate(self.first):
            placed = jax.device_put(f["seeds"].astype(np.int32), self.seed_sharding)
            ds, x = self.sample(self.key_of(f["step"]), self.stopo, self.feat, placed)
            n_id = np.asarray(ds.n_id)[0]
            sel = graphgen.stream(self.seed, 8, i).integers(
                0, n_id.shape[0], min(GATHER_SAMPLE, n_id.shape[0])).astype(np.int32)
            masks = [np.asarray(adj.mask)[0] for adj in ds.adjs]
            steps.append({
                "seeds": f["seeds"], "labels": self.data.labels[f["seeds"]], "n_id": n_id,
                "count": int(np.asarray(ds.count)[0]), "structural": ds.adjs[0].cols is None,
                "blocks": [check.Block(
                    check.structural_cols(*m.shape) if adj.cols is None
                    else np.asarray(adj.cols)[0], m, int(np.asarray(adj.n_src)[0]))
                    for adj, m in zip(ds.adjs, masks)],
                "cap_overflow": 0 if ds.cap_overflow is None else int(np.asarray(ds.cap_overflow)[0]),
                "loss": float(f["loss"]), "sel": sel,
                "rows": np.asarray(self.take_rows(x[0], sel))})
            del ds, x
        mu1 = self.snap["opt_state1"][0].mu
        b1 = reference.ADAM_B1
        to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        return {"steps": steps,
                "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - b1), mu1),
                "params0": to_np(self.snap["params0"]),
                "params3": to_np(self.snap["params3"])}

    def oracle_of(self, got: Dict[str, Any]) -> RowOracle:
        """The host CSR rows of every target the collected steps sampled
        from (the targets of the widest block: all rows but the leaves)."""
        targets = [s["n_id"][: s["blocks"][0].mask.shape[0]] for s in got["steps"]]
        return RowOracle(self.data.graph.indptr, self.data.graph.indices,
                         np.concatenate(targets))

    def release(self) -> None:
        """Free the program's device state (the reference runs afterwards,
        on the first chip alone)."""
        for name in ("feat", "stopo", "labels", "params", "opt_state", "train_step",
                     "sample", "take_rows", "first", "snap"):
            setattr(self, name, None)
        import jax

        jax.clear_caches()  # loaded programs keep their temporaries reserved


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None) -> str:
    # a library without the sharded sample program (a parent commit) cannot
    # run this kind of cell: it fails here, at once, before any data is made
    from quiver_tpu.parallel import make_sharded_topo_sample, step_comm_bytes  # noqa: F401

    cfg, limits = cell.config, cell.traffic["limits"]
    watch = harness.CompileWatch()
    try:
        sc = ShardedCell(cell, seed, compute_dtype=compute_dtype, fault=fault)
        data = sc.data
        t_first = time.perf_counter()
        sc.first_steps()
        warm_programs = watch.mark()
        setup_s = time.perf_counter() - t_start
        with harness.TraceWindow(trace) as tw:
            win = sc.window(seconds)
        compiled_in_window = watch.mark()
    finally:
        watch.close()
    peak = harness.memory_peak_bytes(cell.chips)
    t0 = time.perf_counter()
    got = sc.collect()
    timing = dict(sc.timing, chip_init_s=chip_init_s, warm_programs=warm_programs,
                  first_steps_s=setup_s - (t_first - t_start),
                  sample_programs_s=time.perf_counter() - t0)
    placement = sc.placement
    oracle = sc.oracle_of(got)
    sc.release()

    exact = train.exact_faults(data, got, oracle, sc.batch)
    del oracle
    ref = train.follow_with_reference(cfg, data, seed, got, table=HostRows(data.features))
    read = train.readings(got, ref)
    timing["check_s"] = time.perf_counter() - t0
    compared = [check.Compared(k, float(read[k]), float(limits[k]))
                for k in ("loss1_gap", "loss2_gap", "loss3_gap",
                          "grad1_norm_gap", "dparam3_norm_gap") if k in limits]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("weights_differ", read["weights_differ"]),
        ("not_edges", exact["not_edges"]),
        ("wrong_fanout", exact["wrong_fanout"]),
        ("gather_rows_differ", exact["gather_rows_differ"]),
        ("unsplit_arrays", placement["unsplit_arrays"]),
        ("chips_off_their_share", placement["chips_off_their_share"]),
        ("nonfinite_losses", win["nonfinite_losses"]),
        ("compiled_in_window", compiled_in_window))]
    compared.append(check.Compared("no_pairs_sampled",
                                   float(exact["sampled_pairs"] == 0), 0.0))

    sizes = train.block_sizes(got)
    values = {"train_seeds_per_s": win["seeds_per_s"], "setup_s": setup_s}
    breakdown = None
    if trace:
        summary = tw.reduce(keep=keep_trace)
        row_bytes = cfg["feat_dim"] * 4
        ctx = {"trace": summary, "units": {"steps": win["steps"]},
               "work": {
                   # every chip of a dp group computes the whole model: the
                   # algorithm's operations once, over the peak of all chips
                   "step_flops": work.sage_flops(sizes["targets"], sizes["pairs"],
                                                 reference.dims_of(cfg),
                                                 backward=True) / cell.chips,
                   "gather_bytes": work.gather_bytes(sizes["rows_valid"], row_bytes),
                   # the rows a chip does not own have to reach it
                   "exchange_bytes": sizes["rows_valid"] * row_bytes
                   * (cell.chips - 1) / cell.chips},
               "counters": {}}
        metrics = harness.per_layer_metrics(cell, device, ctx)
        device = dict(device, busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        metrics = harness.end_to_end_metrics(cell, values)
    device = dict(device, memory_peak_bytes=peak)
    correct = check.verdict(compared)
    return harness.result_line(
        correct=correct, attempted=win["steps"],
        failed=win["nonfinite_losses"], metrics=metrics, device=device,
        compared=check.as_record(compared), breakdown=breakdown,
        extra={"window": win, "sizes": sizes, "timing": timing, "readings": read})
