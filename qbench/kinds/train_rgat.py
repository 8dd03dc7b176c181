"""Relational attention train cells: `qbench.kinds.train`'s loop, window and
check over a typed graph:

    HeteroSampler.sample_dense(seeds) -> Feature.lookup_padded(n_id)
      -> jitted optax step on models.RGAT -> block_until_ready

`RgatCell` is `train.TrainCell` with the graph, the sampler and the feature
store of a typed configuration: one `HeteroCSRTopo` of the configuration's
relations (`qbench.graphgen_hetero`), a `HeteroSampler` at the cell's
per-(hop, type) caps, and one `Feature` over every type's rows stacked in
the configuration's ``feature_dtype``. Its step is `train.make_train_step`'s
over `quiver_tpu.models.RGAT` (the program keeps the name ``jit_train_step``,
so the sampler's and the gather's metrics read the cell as they read the
others); its weights and followed steps come from `qbench.reference_rgat`,
its sample and gather checks from `qbench.check_rgat`. `qbench.limits_rgat`
drives the same object over many seeds.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import check, check_rgat, graphgen, graphgen_hetero, harness, manifest, reference_rgat
from .. import work, work_rgat
from . import train

NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap")


class HostData:
    """The run's typed data on the host, from the seed."""

    def __init__(self, config: Dict[str, Any], seed: int):
        t0 = time.perf_counter()
        self.graph = graphgen_hetero.hetero_graph(config, seed)
        self.graph_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.features, self.labels = graphgen_hetero.features_and_labels(config, seed)
        self.train_idx = graphgen.train_split(config["node_types"][config["seed_type"]],
                                              config["train_nodes"], seed)
        self.features_s = time.perf_counter() - t0
        starts = np.cumsum([0] + list(config["node_types"].values()))
        self.offsets = dict(zip(config["node_types"], (int(s) for s in starts)))

    def oracles(self) -> Dict[str, check_rgat.RelationOracle]:
        nodes = self.graph.num_nodes
        return {reference_rgat.relation_key(rel): check_rgat.RelationOracle(
            r.indptr, r.indices, nodes[rel[0]]) for rel, r in self.graph.relations.items()}


class RgatCell(train.TrainCell):
    """`train.TrainCell` over a `HeteroSampler` and `models.RGAT`."""

    def __init__(self, cell: manifest.Cell, data: HostData, seed: int, *,
                 compute_dtype: Optional[str] = None, fault: Optional[str] = None):
        import jax
        import optax

        from quiver_tpu import CSRTopo, Feature
        from quiver_tpu.pyg import HeteroCSRTopo, HeteroSampler

        cfg, traffic = cell.config, cell.traffic
        if cfg["dropout"] != 0.0:
            raise ValueError("the reference follows no dropout mask: dropout must be 0")
        self.cfg, self.traffic, self.data, self.seed = cfg, traffic, data, seed
        self.batch = int(cfg["batch"])
        self.sizes = tuple(cfg["fanout"])
        self.timing: Dict[str, float] = {}

        t0 = time.perf_counter()
        topo = HeteroCSRTopo(data.graph.num_nodes, {
            rel: CSRTopo(indptr=r.indptr, indices=r.indices)
            for rel, r in data.graph.relations.items()})
        sampler_seed = int(graphgen.stream(seed, 7).integers(0, 2**31 - 1))
        self.sampler = HeteroSampler(topo, self.sizes, caps=traffic["caps"], seed=sampler_seed,
                                     seed_type=cfg["seed_type"], layout=traffic["layouts"])
        jax.block_until_ready(self.sampler.graph_arrays())
        self.timing["tiles_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.feature = Feature(rank=0, device_list=[0], device_cache_size=data.features.nbytes,
                               dtype=cfg["feature_dtype"])
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
        from quiver_tpu.models import RGAT

        if fault not in train.FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg = self.cfg
        self.model = RGAT(out_dim=cfg["classes"], hidden_dim=cfg["hidden_dim"],
                          heads=cfg["heads"], num_layers=cfg["num_layers"],
                          relation_aggr=cfg["relation_aggr"],
                          negative_slope=cfg["negative_slope"], dropout=cfg["dropout"],
                          dtype=train.compute_dtype_of(compute_dtype))
        self.train_step = train.make_train_step(self.model, self.tx, fault)
        self.step_loaded = False

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self.params = reference_rgat.params_of(self.cfg, seed)
        self.opt_state = self.tx.init(self.params)
        self.batches = train.seed_batches(self.data.train_idx, self.batch, seed)
        self.first, self.snap = [], {}

    def first_steps(self) -> None:
        """`train.TrainCell.first_steps` over the typed sample's stacked rows."""
        self.snap["params0"] = self.params
        width = sum(self.sampler.plan().widths(self.batch)[-1].values())
        for i in range(train.CHECK_STEPS):
            seeds = next(self.batches)
            sel = graphgen.stream(self.seed, 8, i).integers(
                0, width, min(train.GATHER_SAMPLE, width)).astype(np.int32)
            loss, ds, rows = self.step(seeds, rows_at=sel)
            self.first.append({"seeds": seeds, "ds": ds, "loss": loss, "sel": sel, "rows": rows})
            if i == 0:
                self.snap["opt_state1"] = self.opt_state
        self.snap["params3"] = self.params

    def collect(self) -> Dict[str, Any]:
        import jax

        steps = []
        for f in self.first:
            ds = f["ds"]
            typed = check_rgat.TypedStep(
                type_ids={t: np.asarray(v) for t, v in ds.type_ids.items()},
                type_counts={t: np.asarray(v) for t, v in ds.type_counts.items()},
                layers=[{k: (np.asarray(a.cols), np.asarray(a.mask)) for k, a in layer.items()}
                        for layer in ds.adjs.layers])
            steps.append({"seeds": f["seeds"], "labels": self.data.labels[f["seeds"]],
                          "n_id": np.asarray(ds.n_id), "typed": typed,
                          "cap_overflow": int(ds.cap_overflow), "loss": float(f["loss"]),
                          "sel": f["sel"], "rows": np.asarray(f["rows"])})
        mu1 = self.snap["opt_state1"][0].mu
        b1 = reference_rgat.reference.ADAM_B1
        to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        return {"steps": steps,
                "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - b1), mu1),
                "params0": to_np(self.snap["params0"]),
                "params3": to_np(self.snap["params3"])}


def follow_with_reference(cfg: Dict[str, Any], data: HostData, seed: int,
                          got: Dict[str, Any], operands: Optional[str] = None):
    """The reference over the same seeds, samples and labels, its own rows
    gathered from the host table (only a step's rows go to the chip) and
    its own weights from the seed."""
    import jax
    import jax.numpy as jnp

    nodes = cfg["node_types"]

    def batches():
        for s in got["steps"]:
            x = {t: jax.device_put(data.features[data.offsets[t] + np.clip(
                ids.astype(np.int64), 0, nodes[t] - 1)]) for t, ids in s["typed"].type_ids.items()}
            blocks = [{k: (jnp.asarray(c), jnp.asarray(m)) for k, (c, m) in layer.items()}
                      for layer in s["typed"].layers]
            yield x, blocks, jnp.asarray(s["labels"])

    params = reference_rgat.params_of(cfg, seed)
    losses, grad1, params3 = reference_rgat.follow_steps(
        params, batches(), cfg["lr"], operands or cfg["matmul_operands"], cfg["relation_aggr"],
        cfg["negative_slope"])
    return {"losses": losses, "grad1": grad1, "params3": params3,
            "params0": jax.tree.map(np.asarray, params)}


def exact_faults(data: HostData, got: Dict[str, Any],
                 oracles: Dict[str, check_rgat.RelationOracle]) -> Dict[str, int]:
    """The comparisons whose limit is 0: the typed samples against the host
    CSRs and the gathered rows against the host table, bit for bit."""
    out = {"not_edges": 0, "wrong_fanout": 0, "not_unique": 0, "sampled_pairs": 0,
           "gather_rows_differ": 0, "cap_overflow_first": 0}
    for s in got["steps"]:
        for k, v in check_rgat.sample_faults(oracles, s["typed"]).items():
            out[k] += v
        out["gather_rows_differ"] += check_rgat.gather_faults(data.features, s["n_id"], s["sel"],
                                                              s["rows"])
        out["cap_overflow_first"] += s["cap_overflow"]
    return out


def block_sizes(got: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Per layer and relation ``(d_in, sources, targets, pairs)`` as the mean
    over the check steps (what `qbench.work_rgat` counts from), whether the
    relation's attention runs as a loop (``looped``: its static target width
    spans more than one `ATTENTION_BLOCK`; a one-block loop is unrolled by
    the compiler and leaves no ``while`` for `rgat_edge_ms.train` to time),
    and the gathered rows."""
    from quiver_tpu.ops.gather_sum import ATTENTION_BLOCK

    steps = got["steps"]
    hops = len(steps[0]["typed"].layers)
    layers: List[List[List[float]]] = []
    looped: List[List[bool]] = []
    for l, layer in enumerate(steps[0]["typed"].layers):
        d_in = cfg["feat_dim"] if l == 0 else cfg["hidden_dim"]
        rels = []
        looped.append([layer[key][0].shape[0] > ATTENTION_BLOCK for key in sorted(layer)])
        for key in sorted(layer):
            src, _, dst = key.split("__")
            mean = lambda f: float(np.mean([f(s["typed"]) for s in steps]))  # noqa: E731
            rels.append([d_in, mean(lambda t: t.type_counts[src][hops - l]),
                         mean(lambda t: t.type_counts[dst][hops - 1 - l]),
                         mean(lambda t: t.layers[l][key][1].sum())])
        layers.append(rels)
    return {"layers": layers, "looped": looped,
            "rows_valid": float(np.mean([sum(int(c[-1]) for c in s["typed"].type_counts.values())
                                         for s in steps])),
            "rows_padded": int(steps[0]["n_id"].shape[0])}


def work_of(cfg: Dict[str, Any], sizes: Dict[str, Any], batch: int) -> Dict[str, float]:
    """The step's operations and bytes (`ctx["work"]` of the per-layer
    readers): the whole step, the first layer's projections (forward and
    weight gradient), the per-edge part of the relations whose loops
    `rgat_edge_roofline` times (``looped``), and the gather of
    ``feature_dtype`` rows."""
    width, row_bytes = cfg["hidden_dim"], cfg["feat_dim"] * graphgen_hetero.table_dtype(
        cfg["feature_dtype"]).itemsize
    layers = [[tuple(r) for r in rels] for rels in sizes["layers"]]
    timed = [[r for r, on in zip(rels, loop) if on] for rels, loop in zip(layers, sizes["looped"])]
    return {"step_flops": work_rgat.rgat_flops(layers, width, cfg["heads"],
                                               (batch, width, cfg["classes"]), backward=True),
            "project_flops": 2 * work_rgat.project_flops(layers[0], width),
            "edge_bytes": work_rgat.edge_bytes(timed, width, backward=True),
            "gather_bytes": work.gather_bytes(sizes["rows_valid"], row_bytes)}


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None) -> str:
    # a library without typed sampling (a parent commit) cannot run this kind
    # of cell: it fails here, at once, before any data is made
    from quiver_tpu.pyg.hetero_sampler import HeteroSampler  # noqa: F401

    cfg, limits = cell.config, cell.traffic["limits"]
    watch = harness.CompileWatch()
    try:
        data = HostData(cfg, seed)
        tc = RgatCell(cell, data, seed, compute_dtype=compute_dtype, fault=fault)
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
    exact = exact_faults(data, got, data.oracles())
    ref = follow_with_reference(cfg, data, seed, got)
    read, worst = train.readings(got, ref), check_rgat.worst_leaves(got, ref)
    timing["check_s"] = time.perf_counter() - t0
    compared = [check.Compared(k, float(read[k]), float(limits[k])) for k in NUMBERS]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("weights_differ", read["weights_differ"]),
        ("not_edges", exact["not_edges"]),
        ("wrong_fanout", exact["wrong_fanout"]),
        ("not_unique", exact["not_unique"]),
        ("gather_rows_differ", exact["gather_rows_differ"]),
        ("cap_overflow", exact["cap_overflow_first"] + win["cap_overflow"]),
        ("nonfinite_losses", win["nonfinite_losses"]),
        ("compiled_in_window", compiled_in_window),
        ("no_pairs_sampled", exact["sampled_pairs"] == 0))]

    sizes = block_sizes(got, cfg)
    values = {"train_seeds_per_s": win["seeds_per_s"], "setup_s": setup_s}
    breakdown = None
    if trace:
        summary = tw.reduce(keep=keep_trace)
        ctx = {"trace": summary, "units": {"steps": win["steps"]},
               "work": work_of(cfg, sizes, tc.batch), "counters": {}}
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
               "worst_leaf": worst})
