"""Train cells: the loop a user of the library writes (the loop of
`examples/reddit_sage.py`), driven for ``--seconds``:

    GraphSageSampler.sample_dense(seeds) -> Feature.lookup_padded(n_id)
      -> jitted optax step on models.GraphSAGE -> block_until_ready

One `TrainCell` is built from the seed, driven through its first three steps
(which compile, and which the reference follows afterwards), and the same
object is then handed to the window. `qbench.limits` drives the same object
over many seeds to read the numbers the limits were set from.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import check, graphgen, harness, manifest, reference, work

CHECK_STEPS = 3          # the steps the reference follows
GATHER_SAMPLE = 16384    # gathered rows per check step compared bit for bit
FAULTS = (None, "state_unchanged", "half_batch")


class HostData:
    """The run's data on the host, from the seed."""

    def __init__(self, config: Dict[str, Any], seed: int):
        g = config["graph"]
        t0 = time.perf_counter()
        self.graph = graphgen.powerlaw_graph(
            config["n_nodes"], config["n_edges"], seed, alpha=g["alpha"],
            shift=g["shift"], max_degree=g["max_degree"])
        self.graph_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.features, self.labels = graphgen.features_and_labels(
            config["n_nodes"], config["feat_dim"], config["classes"], seed,
            label_signal=config["label_signal"])
        self.train_idx = graphgen.train_split(
            config["n_nodes"], config["train_nodes"], seed)
        self.features_s = time.perf_counter() - t0


def compute_dtype_of(name: Optional[str]):
    """The model's ``dtype``: None is the configurations' float32; bfloat16
    is the control (the library's own lower-precision path)."""
    import jax.numpy as jnp

    return {None: None, "float32": None, "bfloat16": jnp.bfloat16}[name]


def blocks_of(ds) -> List[check.Block]:
    """A `DenseSample`'s hops as plain arrays, outermost first, the fused
    pipeline's structural layout made explicit."""
    out = []
    for adj in ds.adjs:
        mask = np.asarray(adj.mask)
        cols = (check.structural_cols(*mask.shape) if adj.cols is None
                else np.asarray(adj.cols))
        out.append(check.Block(cols, mask, int(adj.n_src)))
    return out


def seed_batches(train_idx: np.ndarray, batch: int, seed: int):
    """Endless seed batches: each epoch a fresh permutation of the train
    split from the seed, cut into whole batches (the ragged tail of an epoch
    is dropped, so every step has the one compiled shape)."""
    epoch = 0
    while True:
        order = graphgen.stream(seed, 6, epoch).permutation(train_idx)
        for lo in range(0, order.shape[0] - batch + 1, batch):
            yield order[lo: lo + batch]
        epoch += 1


def make_train_step(model, tx, fault: Optional[str]):
    """The jitted optax step of examples/reddit_sage.py. ``fault`` plants one
    of the faults that tests/qbench and `qbench.limits` must see fail."""
    import jax
    import optax

    @jax.jit
    def train_step(params, opt_state, key, x, adjs, y):
        def loss_fn(p):
            logits = model.apply(p, x, adjs, train=True, rngs={"dropout": key})
            if fault == "half_batch":
                half = y.shape[0] // 2
                logits, labels = logits[:half], y[:half]
            else:
                labels = y
            return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_state = tx.update(grads, opt_state, params)
        if fault == "state_unchanged":
            return params, opt_state, loss
        return optax.apply_updates(params, updates), new_state, loss

    return train_step


class TrainCell:
    """The compiled step with its state: what set-up builds, what the first
    steps drive and what the window is handed."""

    def __init__(self, cell: manifest.Cell, data: HostData, seed: int, *,
                 compute_dtype: Optional[str] = None, fault: Optional[str] = None):
        import jax
        import optax

        from quiver_tpu import CSRTopo, Feature
        from quiver_tpu.pyg import GraphSageSampler

        cfg, traffic = cell.config, cell.traffic
        if cfg["dropout"] != 0.0:
            raise ValueError("the reference follows no dropout mask: dropout must be 0")
        self.cfg, self.traffic, self.data, self.seed = cfg, traffic, data, seed
        self.batch = int(cfg["batch"])
        self.sizes = tuple(cfg["fanout"])
        self.dedup = bool(traffic["dedup"])
        self.timing: Dict[str, float] = {}

        t0 = time.perf_counter()
        self.topo = CSRTopo(indptr=data.graph.indptr, indices=data.graph.indices)
        sampler_seed = int(graphgen.stream(seed, 7).integers(0, 2**31 - 1))
        self.sampler = GraphSageSampler(
            self.topo, self.sizes, device=0, mode="TPU", dedup=self.dedup,
            seed=sampler_seed, caps=tuple(traffic["caps"]) if self.dedup else None)
        jax.block_until_ready(self.topo.to_device_tiled(jax.local_devices()[0]))
        self.timing["tiles_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.feature = Feature(rank=0, device_list=[0],
                               device_cache_size=data.features.nbytes,
                               csr_topo=self.topo)
        self.feature.from_cpu_tensor(data.features)
        jax.block_until_ready(self.feature.shard_tensor.device_shards[0][1])
        if self.feature.shard_tensor.cpu_tensor is not None:
            raise RuntimeError("the whole feature table was meant to sit in HBM")
        self.timing["feature_upload_s"] = time.perf_counter() - t0
        stats = jax.local_devices()[0].memory_stats()
        self.timing["resident_bytes"] = int(stats["bytes_in_use"]) if stats else 0

        self.tx = optax.adam(cfg["lr"])
        self.take_rows = jax.jit(lambda x, sel: x[sel])
        self.key = jax.random.key(0)  # dropout is 0: the key is never read
        self.rebuild_step(compute_dtype, fault)
        self.reseed(seed)

    def rebuild_step(self, compute_dtype: Optional[str], fault: Optional[str]) -> None:
        """The model and its jitted step over the same sampler and feature
        store: as the configuration states, or in the control's precision
        (the library's own ``dtype=bfloat16`` path), or with a fault planted."""
        from quiver_tpu.models import GraphSAGE

        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg = self.cfg
        self.model = GraphSAGE(hidden_dim=cfg["hidden_dim"], out_dim=cfg["classes"],
                               num_layers=cfg["num_layers"], dropout=cfg["dropout"],
                               dtype=compute_dtype_of(compute_dtype))
        self.train_step = make_train_step(self.model, self.tx, fault)
        self.step_loaded = False

    def reseed(self, seed: int) -> None:
        """Fresh weights, optimizer state and batches from ``seed`` over the
        same graph and compiled programs (`qbench.limits` reads many seeds in
        one process)."""
        self.seed = seed
        self.params = reference.params_of(self.cfg, seed)
        self.opt_state = self.tx.init(self.params)
        self.batches = seed_batches(self.data.train_idx, self.batch, seed)
        self.first: List[Dict[str, Any]] = []
        self.snap: Dict[str, Any] = {}

    def step(self, seeds: np.ndarray, rows_at: Optional[np.ndarray] = None):
        """The window's own call: one training step, ended with
        block_until_ready. Returns the loss and the sample, still on the
        device (and, for the check's first steps, the gathered rows at the
        positions ``rows_at``). The very first call also waits after each
        stage: it LOADS the step program, whose temporaries (8.7 GiB at
        igb-small) need one contiguous block, and a gather output placed
        while the sampler's temporaries were still alive would split it."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        first = not self.step_loaded
        with TraceAnnotation("qbench.sample_dense"):
            ds = self.sampler.sample_dense(seeds)
            if first:
                jax.block_until_ready(ds)
        with TraceAnnotation("qbench.lookup_padded"):
            x = self.feature.lookup_padded(ds.n_id)
            if first:
                jax.block_until_ready(x)
        with TraceAnnotation("qbench.train_step"):
            y = jnp.asarray(self.data.labels[seeds])
            self.params, self.opt_state, loss = self.train_step(
                self.params, self.opt_state, self.key, x, ds.adjs, y)
        rows = None if rows_at is None else self.take_rows(x, rows_at)
        del x  # before the next step's gather allocates its own
        with TraceAnnotation("qbench.wait"):
            jax.block_until_ready(loss)
        self.step_loaded = True
        return loss, ds, rows

    def first_steps(self) -> None:
        """Steps 1..3 through `step`, keeping what the check needs: each
        step's seeds, sample and loss, a seed-drawn sample of its gathered
        rows, the optimizer state after step 1 (Adam's first moment gives the
        first gradient as the optimizer got it) and the parameters before
        step 1 and after step 3."""
        self.snap["params0"] = self.params
        # rows the gather returns: every sampled position, or the hop's cap
        width = self.batch
        for hop, k in enumerate(self.sizes):
            width *= 1 + k
            if self.dedup:
                width = min(width, self.traffic["caps"][hop])
        for i in range(CHECK_STEPS):
            seeds = next(self.batches)
            sel = graphgen.stream(self.seed, 8, i).integers(
                0, width, min(GATHER_SAMPLE, width)).astype(np.int32)
            loss, ds, rows = self.step(seeds, rows_at=sel)
            self.first.append({"seeds": seeds, "ds": ds, "loss": loss, "sel": sel,
                               "rows": rows})
            if i == 0:
                self.snap["opt_state1"] = self.opt_state
        self.snap["params3"] = self.params

    def window(self, seconds: float) -> Dict[str, Any]:
        """Steps until ``seconds`` have passed. The rate is over all seeds
        and the whole window, the last step's overshoot included."""
        losses, overflows = [], []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            loss, ds, _ = self.step(next(self.batches))
            losses.append(loss)
            if ds.cap_overflow is not None:
                overflows.append(ds.cap_overflow)
            now = time.perf_counter()
            if now >= deadline:
                break
        elapsed = now - t0
        losses = np.asarray([float(l) for l in losses])
        return {"steps": len(losses), "elapsed_s": elapsed,
                "seeds_per_s": len(losses) * self.batch / elapsed,
                "nonfinite_losses": int((~np.isfinite(losses)).sum()),
                "cap_overflow": int(sum(int(o) for o in overflows)),
                "loss_first": float(losses[0]), "loss_last": float(losses[-1])}

    # -- what the check reads, pulled to the host --------------------------

    def collect(self) -> Dict[str, Any]:
        """Everything the check compares, as numpy; after this the device
        state can be freed."""
        import jax

        steps = []
        for f in self.first:
            ds = f["ds"]
            steps.append({
                "seeds": f["seeds"], "labels": self.data.labels[f["seeds"]],
                "n_id": np.asarray(ds.n_id), "count": int(ds.count), "blocks": blocks_of(ds),
                "structural": ds.adjs[0].cols is None,
                "cap_overflow": 0 if ds.cap_overflow is None else int(ds.cap_overflow),
                "loss": float(f["loss"]), "sel": f["sel"], "rows": np.asarray(f["rows"])})
        mu1 = self.snap["opt_state1"][0].mu
        b1 = reference.ADAM_B1
        to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        return {"steps": steps,
                "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - b1), mu1),
                "params0": to_np(self.snap["params0"]),
                "params3": to_np(self.snap["params3"])}

    def release(self) -> None:
        """Free the program's device state (the reference runs afterwards,
        and must not be what sets the memory peak)."""
        for name in ("sampler", "feature", "topo", "params", "opt_state",
                     "train_step", "take_rows", "first", "snap", "model", "tx"):
            setattr(self, name, None)
        import jax

        jax.clear_caches()  # loaded programs keep their temporaries reserved


def follow_with_reference(cfg: Dict[str, Any], data: HostData, seed: int,
                          got: Dict[str, Any], table=None, operands: Optional[str] = None):
    """The reference over the same seeds, samples and labels, its own rows
    from the host table and its own weights from the seed."""
    import jax
    import jax.numpy as jnp

    if table is None:
        table = jax.device_put(data.features)
    n = data.features.shape[0]

    def batches():
        for s in got["steps"]:
            ids = jnp.asarray(np.clip(s["n_id"].astype(np.int64), 0, n - 1).astype(np.int32))
            blocks = [(jnp.asarray(b.cols), jnp.asarray(b.mask)) for b in s["blocks"]]
            yield table[ids], blocks, jnp.asarray(s["labels"])

    params = reference.params_of(cfg, seed)
    losses, grad1, params3 = reference.follow_steps(
        params, batches(), cfg["lr"], operands or cfg["matmul_operands"])
    return {"losses": losses, "grad1": grad1, "params3": params3,
            "params0": jax.tree.map(np.asarray, params)}


def readings(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared with the reference (PERF.md section 2)."""
    out = {}
    for i, (s, want) in enumerate(zip(got["steps"], ref["losses"]), start=1):
        # against the FIRST step's loss: by step 3 the loss itself may have
        # fallen a thousandfold, and a gap relative to it reads its noise
        out[f"loss{i}_gap"] = abs(s["loss"] - want) / abs(ref["losses"][0])
    ref_grad = check.leaf_norms(ref["grad1"])
    out["grad1_norm_gap"] = check.worst_norm_gap(check.leaf_norms(got["grad1"]), ref_grad)
    out["dparam3_norm_gap"] = check.worst_norm_gap(
        check.leaf_norms(check.tree_diff(got["params3"], got["params0"])),
        check.leaf_norms(check.tree_diff(ref["params3"], ref["params0"])),
        skip=check.quiet_leaves(ref_grad))
    out["weights_differ"] = float(any(
        np.abs(d).max() > 0 for d in check.tree_diff(got["params0"], ref["params0"]).values()))
    return out


def exact_faults(data: HostData, got: Dict[str, Any], oracle: check.EdgeOracle,
                 batch: int) -> Dict[str, int]:
    """The comparisons whose limit is 0: the samples against the host CSR and
    the gathered rows against the host table, bit for bit."""
    n = data.features.shape[0]
    out = {"not_edges": 0, "wrong_fanout": 0, "sampled_pairs": 0,
           "gather_rows_differ": 0, "cap_overflow_first": 0}
    for s in got["steps"]:
        for k, v in check.sample_faults(oracle, s["n_id"], s["blocks"],
                                        s["structural"], batch).items():
            out[k] += v
        ids = np.clip(s["n_id"][s["sel"]].astype(np.int64), 0, n - 1)
        want = data.features[ids]
        out["gather_rows_differ"] += int(
            (s["rows"].view(np.uint32) != want.view(np.uint32)).any(axis=1).sum())
        out["cap_overflow_first"] += s["cap_overflow"]
    return out


def block_sizes(got: Dict[str, Any]) -> Dict[str, Any]:
    """Valid targets, sampled pairs and gathered rows per step, as the mean
    over the check steps: the sizes `qbench.work` counts from."""
    steps = got["steps"]
    layers = len(steps[0]["blocks"])
    targets = [float(np.mean([b.mask.any(axis=1).sum() for b in
                              (s["blocks"][i] for s in steps)])) for i in range(layers)]
    pairs = [float(np.mean([s["blocks"][i].mask.sum() for s in steps]))
             for i in range(layers)]
    return {"targets": targets, "pairs": pairs,
            "rows_valid": float(np.mean([s["count"] for s in steps])),
            "rows_padded": int(steps[0]["n_id"].shape[0])}


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None) -> str:
    cfg, limits = cell.config, cell.traffic["limits"]
    watch = harness.CompileWatch()
    try:
        data = HostData(cfg, seed)
        tc = TrainCell(cell, data, seed, compute_dtype=compute_dtype, fault=fault)
        t_first = time.perf_counter()
        tc.first_steps()
        warm_programs = watch.mark()
        setup_s = time.perf_counter() - t_start
        with harness.TraceWindow(trace) as tw:
            win = tc.window(seconds)
        compiled_in_window = watch.mark()
    finally:
        watch.close()
    peak = harness.memory_peak_bytes(cell.chips)
    got = tc.collect()
    timing = dict(tc.timing, graph_s=data.graph_s, features_s=data.features_s,
                  chip_init_s=chip_init_s, warm_programs=warm_programs,
                  first_steps_s=setup_s - (t_first - t_start))
    tc.release()

    t0 = time.perf_counter()
    oracle = check.EdgeOracle(data.graph.indptr, data.graph.indices)
    exact = exact_faults(data, got, oracle, tc.batch)
    del oracle
    ref = follow_with_reference(cfg, data, seed, got)
    read = readings(got, ref)
    timing["check_s"] = time.perf_counter() - t0
    compared = [check.Compared(k, float(read[k]), float(limits[k]))
                for k in ("loss1_gap", "loss2_gap", "loss3_gap",
                          "grad1_norm_gap", "dparam3_norm_gap") if k in limits]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("weights_differ", read["weights_differ"]),
        ("not_edges", exact["not_edges"]),
        ("wrong_fanout", exact["wrong_fanout"]),
        ("gather_rows_differ", exact["gather_rows_differ"]),
        ("cap_overflow", exact["cap_overflow_first"] + win["cap_overflow"]),
        ("nonfinite_losses", win["nonfinite_losses"]),
        ("compiled_in_window", compiled_in_window))]
    compared.append(check.Compared("no_pairs_sampled",
                                   float(exact["sampled_pairs"] == 0), 0.0))

    sizes = block_sizes(got)
    values = {"train_seeds_per_s": win["seeds_per_s"], "setup_s": setup_s}
    breakdown = None
    if trace:
        summary = tw.reduce(keep=keep_trace)
        ctx = {"trace": summary, "units": {"steps": win["steps"]},
               "work": {"step_flops": work.sage_flops(sizes["targets"], sizes["pairs"],
                                                      reference.dims_of(cfg), backward=True),
                        "gather_bytes": work.gather_bytes(sizes["rows_valid"],
                                                          cfg["feat_dim"] * 4)},
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

