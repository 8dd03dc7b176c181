"""Tiered train cells: the library's documented path for a feature table that
does not fit the chip (docs/api.md "Prefetch pipeline"), driven for
``--seconds``:

    CSRTopo -> GraphSageSampler(mode="TPU", layout=..., dedup=True, caps=...)
      -> Feature(device_cache_size=hot_bytes, cache_policy="device_replicate",
                 csr_topo=topo).from_cpu_tensor(table)      (cold_cap fixed)
      -> TrainPipeline(sampler, feature, step_fn, depth) with
         make_tiered_train_step: sample / host gather / upload threads ahead
         of ONE step program (hot gather + merge of the cold block + model +
         optimizer)

The hottest rows by degree sit in HBM, the rest of the table stays in the
host's DRAM in the caller's order, and every step brings its cold rows over
the host link. One `TieredCell` is built from the seed; the SAME
`TrainPipeline` object is driven through steps 1-3 (which compile, and which
the reference follows afterwards) and then through the window. What a step
looked up is read from the batch the step consumed, through the feature's own
device half, in the first three steps only. `qbench.limits_tiered` drives the
same object over many seeds.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import check, graphgen, harness, manifest, reference, work, work_tiered
from . import train, train_sharded

CHECK_STEPS = train.CHECK_STEPS
GATHER_SAMPLE = train.GATHER_SAMPLE
FAULTS = train.FAULTS


ADD_ROWS = 65536  # rows a thread nudges at a time while the table is made


def features_and_labels(n_nodes: int, dim: int, classes: int, seed: int,
                        label_signal: float = 1.5):
    """`graphgen.features_and_labels`, byte for byte, without its
    temporaries: there each of eight threads adds the class directions to a
    thirty-second of the table in one expression, 0.9 GB of gathered
    directions a thread at this size, 7 GB in all beside a 28.4 GB table on a
    host of 40 GiB. Here the same chunks take the same normals from the same
    streams and the directions `ADD_ROWS` rows at a time."""
    from concurrent.futures import ThreadPoolExecutor

    rng = graphgen.stream(seed, 3)
    labels = rng.integers(0, classes, n_nodes).astype(np.int32)
    basis = rng.standard_normal((classes, dim), dtype=np.float32)
    basis *= np.float32(label_signal)
    table = np.empty((n_nodes, dim), np.float32)
    bounds = np.linspace(0, n_nodes, 4 * graphgen.GEN_THREADS + 1).astype(np.int64)

    def fill(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        graphgen.stream(seed, 4, i).standard_normal(out=table[lo:hi], dtype=np.float32)
        for at in range(lo, hi, ADD_ROWS):
            end = min(at + ADD_ROWS, hi)
            table[at:end] += basis[labels[at:end]]

    with ThreadPoolExecutor(graphgen.GEN_THREADS) as pool:
        list(pool.map(fill, range(len(bounds) - 1)))
    return table, labels


class HostData:
    """The run's data on the host, from the seed, made in two parts with the
    graph's placement between them (`TieredCell`): a one-chip host has 40
    GiB, the table takes 28.4 GB of them, and the graph's making peaks at
    16-21 GB. The graph is `train.HostData`'s, seed for seed; once its edges
    are on the chip, and held against the host's there, the host frees them."""

    def __init__(self, config: Dict[str, Any], seed: int):
        g = config["graph"]
        t0 = time.perf_counter()
        self.graph = graphgen.powerlaw_graph(
            config["n_nodes"], config["n_edges"], seed, alpha=g["alpha"],
            shift=g["shift"], max_degree=g["max_degree"])
        self.graph_s = time.perf_counter() - t0
        self.features = self.labels = self.train_idx = None
        self.features_s = 0.0

    def drop_edges(self, topo, rows_dev) -> Dict[str, int]:
        """Hold the chip's copy of the edges against the host's, piece by
        piece and bit for bit, then free the host's (in ``topo`` too:
        `CSRTopo.drop_host_edges`). From here on the chip's lane rows ARE
        the run's edge list: the check reads its targets' CSR rows from them
        (`DeviceRowOracle`). The 6.5 GB (3.2 as int32) do not fit beside the
        table on a host of 40 GiB."""
        edges = self.graph.indices
        lane = rows_dev.shape[1]
        per = max(min((256 << 20) // (4 * lane), rows_dev.shape[0]), 1)
        differ = 0
        for lo in range(0, rows_dev.shape[0], per):
            got = np.asarray(rows_dev[lo: lo + per]).reshape(-1)
            want = edges[lo * lane: lo * lane + got.shape[0]]
            differ += int((got[: want.shape[0]] != want).sum()) + int(got[want.shape[0]:].any())
        topo.drop_host_edges()
        self.graph = graphgen.Graph(self.graph.indptr, None)
        return {"placed_edges_differ": differ}

    def make_table(self, config: Dict[str, Any], seed: int) -> None:
        t0 = time.perf_counter()
        self.features, self.labels = features_and_labels(
            config["n_nodes"], config["feat_dim"], config["classes"], seed,
            label_signal=config["label_signal"])
        self.train_idx = graphgen.train_split(
            config["n_nodes"], config["train_nodes"], seed)
        self.features_s = time.perf_counter() - t0


class DeviceRowOracle(check.EdgeOracle):
    """`train_sharded.RowOracle` for a run whose host holds no edges: the CSR
    rows of ``nodes`` read from the chip's ``[R, 128]`` lane rows (held
    against the host's array, bit for bit, before that was freed:
    `HostData.drop_edges`). Made in two parts, because the host has little
    room: one row gather of the lane rows the targets' lists touch while the
    chip still holds the graph, and the sorted keys on the first question,
    `NODES_A_PASS` nodes at a time, once the run's state is freed."""

    NODES_A_PASS = 32768

    def __init__(self, indptr: np.ndarray, rows_dev, nodes: np.ndarray):
        import jax
        import jax.numpy as jnp

        self.n = int(indptr.shape[0] - 1)
        self.degree = np.diff(indptr)
        self.lanes = int(rows_dev.shape[1])
        self.nodes = np.unique(np.clip(nodes.astype(np.int64), 0, self.n - 1))
        self.starts = indptr[self.nodes]
        lens = self.degree[self.nodes]
        shift = self.lanes.bit_length() - 1
        first, last = self.starts >> shift, (self.starts + np.maximum(lens, 1) - 1) >> shift
        spans = last - first + 1
        ends = np.cumsum(spans)
        touched = np.repeat(first - (ends - spans), spans) + np.arange(int(ends[-1]))
        self.touched = np.unique(touched)
        padded = np.zeros(-(-self.touched.shape[0] // 65536) * 65536, np.int32)
        padded[: self.touched.shape[0]] = self.touched
        self.fetched = np.asarray(jax.jit(
            lambda r, i: jnp.take(r, i, axis=0, mode="clip"))(rows_dev, padded))
        self._keys = None

    @property
    def keys(self) -> np.ndarray:
        if self._keys is None:
            lens = self.degree[self.nodes]
            keys = np.empty(int(lens.sum()), np.int64)
            shift, done = self.lanes.bit_length() - 1, 0
            for lo in range(0, self.nodes.shape[0], self.NODES_A_PASS):
                part = slice(lo, lo + self.NODES_A_PASS)
                n_part = lens[part]
                ends = np.cumsum(n_part)
                total = int(ends[-1]) if ends.size else 0
                # slot j of the concatenated rows is edge start(row) + j - first(row)
                at = np.repeat(self.starts[part] - (ends - n_part), n_part)
                at += np.arange(total, dtype=np.int64)
                out = keys[done: done + total]
                out[:] = np.repeat(self.nodes[part] * self.n, n_part)
                out += self.fetched[np.searchsorted(self.touched, at >> shift),
                                    at & (self.lanes - 1)]
                done += total
            keys.sort()
            self._keys = keys if keys.size else np.full(1, -1, np.int64)
            self.fetched = self.touched = None
        return self._keys


def host_used_bytes() -> int:
    """What the host has in use, by the kernel's count (MemTotal less
    MemAvailable): one process runs here, and its resident size counts
    device mappings that are no memory of the host's."""
    with open("/proc/meminfo") as f:
        kb = {line.split(":")[0]: int(line.split()[1]) for line in f if ":" in line}
    return (kb["MemTotal"] - kb["MemAvailable"]) * 1024


class TieredCell:
    """The placed graph, the tiered table, the pipeline and its state: what
    set-up builds, what the first steps drive and what the window is handed."""

    def __init__(self, cell: manifest.Cell, seed: int, *,
                 compute_dtype: Optional[str] = None, fault: Optional[str] = None):
        import jax
        import optax

        from quiver_tpu import CSRTopo, Feature
        from quiver_tpu.pipeline import TrainPipeline
        from quiver_tpu.pyg import GraphSageSampler

        cfg, traffic = cell.config, cell.traffic
        if cfg["dropout"] != 0.0:
            raise ValueError("the reference follows no dropout mask: dropout must be 0")
        self.cfg, self.traffic = cfg, traffic
        self.batch, self.sizes = int(cfg["batch"]), tuple(cfg["fanout"])
        self.timing: Dict[str, Any] = {}
        device = jax.local_devices()[0]

        self.data = data = HostData(cfg, seed)
        t0 = time.perf_counter()
        self.topo = CSRTopo(indptr=data.graph.indptr, indices=data.graph.indices)
        sampler_seed = int(graphgen.stream(seed, 7).integers(0, 2**31 - 1))
        self.sampler = GraphSageSampler(
            self.topo, self.sizes, device=0, mode="TPU", dedup=bool(traffic["dedup"]),
            seed=sampler_seed, caps=tuple(traffic["caps"]), layout=traffic["layout"])
        self.graph_dev = jax.block_until_ready(self.sampler.lazy_init_quiver())
        self.placement = data.drop_edges(self.topo, self.graph_dev[1])
        self.timing["topology_upload_s"] = time.perf_counter() - t0
        data.make_table(cfg, seed)
        self.timing.update(graph_s=data.graph_s, features_s=data.features_s)
        t0 = time.perf_counter()
        self.feature = Feature(rank=0, device_list=[0],
                               device_cache_size=int(traffic["hot_bytes"]),
                               cache_policy="device_replicate", csr_topo=self.topo)
        self.feature.cold_cap = int(traffic["cold_cap"])
        self.feature.from_cpu_tensor(data.features)
        self.hot_table, self.hot_rows, host = self.feature.tiered_tables()
        jax.block_until_ready(self.hot_table)
        self.timing["feature_upload_s"] = time.perf_counter() - t0
        if getattr(host, "base", None) is not data.features:
            raise RuntimeError("the cold tier was meant to be the caller's table, not a copy")
        self.labels = jax.device_put(data.labels, device)
        stats = device.memory_stats()
        self.timing.update(hot_rows=self.hot_rows,
                           resident_bytes=int(stats["bytes_in_use"]) if stats else 0)

        self.tx = optax.adam(cfg["lr"])
        self.take_rows = jax.jit(lambda x, sel: x[sel])
        self.key = jax.random.key(0)  # dropout is 0: the keys are never read
        self.keep: Optional[List[Dict[str, Any]]] = None
        self.overflows: List[Any] = []
        self.pipe = TrainPipeline(self.sampler, self.feature, self.step_fn,
                                  depth=int(traffic["depth"]))
        self.rebuild_step(compute_dtype, fault)
        self.reseed(seed)

    def rebuild_step(self, compute_dtype: Optional[str], fault: Optional[str]) -> None:
        """The model and the library's tiered step over the same sampler,
        table and pipeline: as the configuration states, or in the control's
        precision (the library's own ``dtype=bfloat16`` path), or with a
        fault planted (as `train_sharded` plants them, around the model and
        the optimizer the library's step is built on)."""
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.pipeline import make_tiered_train_step

        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg = self.cfg
        model = GraphSAGE(hidden_dim=cfg["hidden_dim"], out_dim=cfg["classes"],
                          num_layers=cfg["num_layers"], dropout=cfg["dropout"],
                          dtype=train.compute_dtype_of(compute_dtype))
        self.lib_step = make_tiered_train_step(
            train_sharded.HalfBatch(model) if fault == "half_batch" else model,
            train_sharded.frozen(self.tx) if fault == "state_unchanged" else self.tx,
            self.labels, self.hot_table)

    def reseed(self, seed: int) -> None:
        """Fresh weights, optimizer state and batches from ``seed`` over the
        same placed graph, table and compiled programs."""
        self.seed = seed
        self.params = reference.params_of(self.cfg, seed)
        self.opt_state = self.tx.init(self.params)
        self.batches = train.seed_batches(self.data.train_idx, self.batch, seed)
        self.first: List[Dict[str, Any]] = []
        self.snap: Dict[str, Any] = {}

    # -- what the pipeline is handed -----------------------------------------

    def step_fn(self, params, opt_state, key, batch):
        """`TrainPipeline`'s ``step_fn``: the library's tiered step. In the
        check's first steps it also keeps the batch the step consumed, a
        seed-drawn sample of what the feature's device half makes of it, and
        the optimizer's state after step 1."""
        from jax.profiler import TraceAnnotation

        from quiver_tpu.feature import _padded_gather_tiered

        with TraceAnnotation("qbench.train_step"):
            out = self.lib_step(params, opt_state, key, batch)
        self.overflows.append(batch.ds.cap_overflow)
        if self.keep is not None:
            i = len(self.keep)
            width = batch.mapped.shape[0]
            sel = graphgen.stream(self.seed, 8, i).integers(
                0, width, min(GATHER_SAMPLE, width)).astype(np.int32)
            x = _padded_gather_tiered(self.hot_table, batch.mapped, batch.cold_rows)
            self.keep.append({"ds": batch.ds, "loss": out[2], "sel": sel,
                              "rows": self.take_rows(x, sel),
                              "mapped": self.take_rows(batch.mapped, sel),
                              "seeds": batch.seeds})
            if i == 0:
                self.snap["opt_state1"] = out[1]
        return out

    def samples(self, seed_batches):
        from jax.profiler import TraceAnnotation

        for seeds in seed_batches:
            with TraceAnnotation("qbench.sample_dense"):
                ds = self.sampler.sample_dense(seeds)
            yield ds

    def first_steps(self) -> None:
        """Steps 1..3 through the pipeline, keeping what the check needs."""
        self.snap["params0"] = self.params
        self.keep = []
        seeds = [next(self.batches) for _ in range(CHECK_STEPS)]
        try:
            self.params, self.opt_state, _ = self.pipe.run_epoch_iter(
                self.samples(seeds), self.params, self.opt_state, self.key)
        finally:
            self.first, self.keep = self.keep, None
        for f, s in zip(self.first, seeds):
            if not (np.asarray(f["seeds"]) == s).all():
                raise RuntimeError("the pipeline handed the step another batch's seeds")
            f["seeds"] = s
        self.snap["params3"] = self.params

    def window(self, seconds: float) -> Dict[str, Any]:
        """Batches enter the pipeline until ``seconds`` have passed; the
        rate is over all seeds and the whole window, the drain of the
        batches in flight at the deadline included."""
        self.overflows = []
        stats = self.pipe.stats
        before = (self.feature.cold_overflow, stats.cold_rows, stats.batches)
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def until_deadline():
            while time.perf_counter() < deadline:
                yield next(self.batches)

        self.params, self.opt_state, losses = self.pipe.run_epoch_iter(
            self.samples(until_deadline()), self.params, self.opt_state, self.key)
        elapsed = time.perf_counter() - t0
        losses = np.asarray(losses)
        return {"steps": len(losses), "elapsed_s": elapsed,
                "seeds_per_s": len(losses) * self.batch / elapsed,
                "nonfinite_losses": int((~np.isfinite(losses)).sum()),
                "cap_overflow": int(sum(int(o) for o in self.overflows)),
                "cold_overflow": self.feature.cold_overflow - before[0],
                "cold_rows_per_step": (stats.cold_rows - before[1])
                / max(stats.batches - before[2], 1),
                "loss_first": float(losses[0]), "loss_last": float(losses[-1])}

    # -- what the check reads, pulled to the host --------------------------

    def collect(self) -> Dict[str, Any]:
        """Everything the check compares, as numpy, in `train.TrainCell
        .collect`'s form (plus each compared row's place in the tiers)."""
        import jax

        steps = []
        for f in self.first:
            ds = f["ds"]
            steps.append({
                "seeds": f["seeds"], "labels": self.data.labels[f["seeds"]],
                "n_id": np.asarray(ds.n_id), "count": int(ds.count),
                "blocks": train.blocks_of(ds), "structural": ds.adjs[0].cols is None,
                "cap_overflow": int(ds.cap_overflow), "loss": float(f["loss"]),
                "sel": f["sel"], "rows": np.asarray(f["rows"]),
                "mapped": np.asarray(f["mapped"])})
        mu1 = self.snap["opt_state1"][0].mu
        b1 = reference.ADAM_B1
        to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        return {"steps": steps,
                "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - b1), mu1),
                "params0": to_np(self.snap["params0"]),
                "params3": to_np(self.snap["params3"])}

    def oracle_of(self, got: Dict[str, Any]) -> DeviceRowOracle:
        targets = [s["n_id"][: s["blocks"][0].mask.shape[0]] for s in got["steps"]]
        return DeviceRowOracle(self.data.graph.indptr, self.graph_dev[1],
                               np.concatenate(targets))

    def exact_faults(self, got: Dict[str, Any], oracle: DeviceRowOracle) -> Dict[str, int]:
        """The comparisons whose limit is 0: the samples against the host CSR
        rows of their targets, and what the steps looked up against the host
        table, bit for bit: the table's row where the lane is valid, a zero
        row past the sample's count. Hot and cold lanes are counted apart:
        a check that met none of either has compared nothing of that tier."""
        n = self.data.features.shape[0]
        out = {"not_edges": 0, "wrong_fanout": 0, "sampled_pairs": 0, "gather_rows_differ": 0,
               "cap_overflow_first": 0, "hot_rows_compared": 0, "cold_rows_compared": 0}
        for s in got["steps"]:
            for k, v in check.sample_faults(oracle, s["n_id"], s["blocks"],
                                            s["structural"], self.batch).items():
                out[k] += v
            live = s["sel"] < s["count"]
            ids = np.clip(s["n_id"][s["sel"]].astype(np.int64), 0, n - 1)
            want = np.where(live[:, None], self.data.features[ids], np.float32(0))
            out["gather_rows_differ"] += int(
                (s["rows"].view(np.uint32) != want.view(np.uint32)).any(axis=1).sum())
            out["hot_rows_compared"] += int((live & (s["mapped"] < self.hot_rows)).sum())
            out["cold_rows_compared"] += int((live & (s["mapped"] >= self.hot_rows)).sum())
            out["cap_overflow_first"] += s["cap_overflow"]
        return out

    def release(self) -> None:
        """Free the program's device state (the reference runs afterwards)."""
        for name in ("sampler", "feature", "topo", "graph_dev", "labels", "hot_table", "params", "opt_state",
                     "lib_step", "pipe", "take_rows", "first", "snap", "tx"):
            setattr(self, name, None)
        import jax

        jax.clear_caches()  # loaded programs keep their temporaries reserved


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None) -> str:
    # a library without the tiered padded lookup (a parent commit) cannot run
    # this kind of cell: it fails here, at once, before any data is made
    from quiver_tpu.feature import _padded_gather_tiered, tiered_gather  # noqa: F401

    cfg, limits = cell.config, cell.traffic["limits"]
    watch = harness.CompileWatch()
    try:
        tc = TieredCell(cell, seed, compute_dtype=compute_dtype, fault=fault)
        data = tc.data
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
    t0 = time.perf_counter()
    used = [host_used_bytes()]
    got = tc.collect()
    oracle = tc.oracle_of(got)  # its rows fetched while the chip holds the graph
    timing = dict(tc.timing, chip_init_s=chip_init_s, warm_programs=warm_programs,
                  first_steps_s=setup_s - (t_first - t_start))
    tc.release()
    exact = tc.exact_faults(got, oracle)  # once the run's state is freed: the oracle's keys
    used.append(host_used_bytes())
    del oracle

    ref = train.follow_with_reference(cfg, data, seed, got,
                                      table=train_sharded.HostRows(data.features))
    read = train.readings(got, ref)
    timing["check_s"] = time.perf_counter() - t0
    timing["host_used_bytes"] = max(used + [host_used_bytes()])
    compared = [check.Compared(k, float(read[k]), float(limits[k]))
                for k in ("loss1_gap", "loss2_gap", "loss3_gap",
                          "grad1_norm_gap", "dparam3_norm_gap") if k in limits]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("weights_differ", read["weights_differ"]),
        ("not_edges", exact["not_edges"]),
        ("wrong_fanout", exact["wrong_fanout"]),
        ("gather_rows_differ", exact["gather_rows_differ"]),
        ("placed_edges_differ", tc.placement["placed_edges_differ"]),
        ("no_hot_rows_compared", exact["hot_rows_compared"] == 0),
        ("no_cold_rows_compared", exact["cold_rows_compared"] == 0),
        ("cap_overflow", exact["cap_overflow_first"] + win["cap_overflow"]),
        ("cold_overflow", win["cold_overflow"]),
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
               "work": {"step_flops": work.sage_flops(sizes["targets"], sizes["pairs"],
                                                      reference.dims_of(cfg), backward=True),
                        "gather_bytes": work.gather_bytes(sizes["rows_valid"], row_bytes),
                        "h2d_bytes": work_tiered.h2d_bytes(win["cold_rows_per_step"], row_bytes)},
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
        extra={"window": win, "sizes": sizes, "timing": timing, "readings": read,
               "tiers": {"hot_rows": timing["hot_rows"],
                         "hot_rows_compared": exact["hot_rows_compared"],
                         "cold_rows_compared": exact["cold_rows_compared"]}})
