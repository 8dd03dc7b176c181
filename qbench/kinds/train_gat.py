"""Attention train cells: `qbench.kinds.train`'s loop, object and check with
the model changed and nothing else:

    GraphSageSampler.sample_dense(seeds) -> Feature.lookup_padded(n_id)
      -> jitted optax step on models.GAT -> block_until_ready

`GatCell` is `train.TrainCell` (the same graph, sampler, feature store, first
steps and window) whose step is built over `quiver_tpu.models.GAT` from the
configuration (heads, output heads, activation, slope) and whose weights and
followed steps come from `qbench.reference_gat`. The step program is
`train.make_train_step`'s, so it keeps the name ``jit_train_step`` and the
sampler's and the gather's metrics read the cell as they read its sibling.
`qbench.limits_gat` drives the same object over many seeds.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from .. import check, harness, manifest, reference_gat, work, work_gat
from . import train

NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap")


class GatCell(train.TrainCell):
    """`train.TrainCell` over `models.GAT`."""

    def rebuild_step(self, compute_dtype: Optional[str], fault: Optional[str]) -> None:
        from quiver_tpu.models import GAT

        if fault not in train.FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        cfg = self.cfg
        self.model = GAT(hidden_dim=cfg["hidden_dim"], out_dim=cfg["classes"],
                         heads=cfg["heads"], out_heads=cfg["out_heads"],
                         num_layers=cfg["num_layers"], dropout=cfg["dropout"],
                         activation=reference_gat.ACTIVATIONS[cfg["activation"]],  # jax.nn's own
                         negative_slope=cfg["negative_slope"],
                         dtype=train.compute_dtype_of(compute_dtype))
        self.train_step = train.make_train_step(self.model, self.tx, fault)
        self.step_loaded = False

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self.params = reference_gat.params_of(self.cfg, seed)
        self.opt_state = self.tx.init(self.params)
        self.batches = train.seed_batches(self.data.train_idx, self.batch, seed)
        self.first, self.snap = [], {}


def follow_with_reference(cfg: Dict[str, Any], data: train.HostData, seed: int,
                          got: Dict[str, Any], table=None, operands: Optional[str] = None):
    """`train.follow_with_reference` with `reference_gat`'s weights and steps."""
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

    params = reference_gat.params_of(cfg, seed)
    losses, grad1, params3 = reference_gat.follow_steps(
        params, batches(), cfg["lr"], operands or cfg["matmul_operands"],
        cfg["activation"], cfg["negative_slope"])
    return {"losses": losses, "grad1": grad1, "params3": params3,
            "params0": jax.tree.map(np.asarray, params)}


def block_sizes(got: Dict[str, Any], batch: int) -> Dict[str, Any]:
    """`train.block_sizes`, and what attention counts from besides: each
    layer's valid SOURCE rows, and its valid targets whether or not they drew
    a neighbour (a target without one still attends itself): the next layer's
    sources, the batch's seeds in the last."""
    sizes = train.block_sizes(got)
    steps = got["steps"]
    sources = [float(np.mean([s["blocks"][i].n_src for s in steps]))
               for i in range(len(sizes["pairs"]))]
    return dict(sizes, sources=sources, targets=sources[1:] + [float(batch)])


def work_of(cfg: Dict[str, Any], sizes: Dict[str, Any]) -> Dict[str, float]:
    """The step's operations and bytes (`ctx["work"]` of the per-layer
    readers): the whole step, and the first layer's projection (forward and
    weight gradient) and per-edge part, which the layer's own metrics read."""
    dims = reference_gat.dims_of(cfg)
    d_in, heads, dim = dims[0]
    return {"step_flops": work_gat.gat_flops(sizes["sources"], sizes["targets"], sizes["pairs"],
                                             dims, backward=True),
            "project_flops": 2 * work_gat.project_flops(sizes["sources"][0], d_in, heads, dim),
            "edge_bytes": work_gat.edge_bytes(sizes["pairs"][0], sizes["targets"][0], heads, dim,
                                              backward=True),
            "gather_bytes": work.gather_bytes(sizes["rows_valid"], cfg["feat_dim"] * 4)}


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None) -> str:
    # a library whose attention lays the k-fold rows out (a parent commit)
    # cannot run this kind of cell: it fails here, at once, before any data is made
    from quiver_tpu.ops.gather_sum import gather_attention_sum  # noqa: F401

    cfg, limits = cell.config, cell.traffic["limits"]
    watch = harness.CompileWatch()
    try:
        data = train.HostData(cfg, seed)
        tc = GatCell(cell, data, seed, compute_dtype=compute_dtype, fault=fault)
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
    exact = train.exact_faults(data, got, oracle, tc.batch)
    del oracle
    read = train.readings(got, follow_with_reference(cfg, data, seed, got))
    timing["check_s"] = time.perf_counter() - t0
    compared = [check.Compared(k, float(read[k]), float(limits[k])) for k in NUMBERS]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("weights_differ", read["weights_differ"]),
        ("not_edges", exact["not_edges"]),
        ("wrong_fanout", exact["wrong_fanout"]),
        ("gather_rows_differ", exact["gather_rows_differ"]),
        ("cap_overflow", exact["cap_overflow_first"] + win["cap_overflow"]),
        ("nonfinite_losses", win["nonfinite_losses"]),
        ("compiled_in_window", compiled_in_window),
        ("no_pairs_sampled", exact["sampled_pairs"] == 0))]

    sizes = block_sizes(got, tc.batch)
    values = {"train_seeds_per_s": win["seeds_per_s"], "setup_s": setup_s}
    breakdown = None
    if trace:
        summary = tw.reduce(keep=keep_trace)
        ctx = {"trace": summary, "units": {"steps": win["steps"]},
               "work": work_of(cfg, sizes), "counters": {}}
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
