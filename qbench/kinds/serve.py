"""Serve cells: `ServeEngine` (fused sealed bucket programs) driven open loop
through `submit` / `result`, one node id per request, at a rate fixed in the
cell's file. Each latency is timed from the instant the request was DUE, so a
stall is charged to every request that waited behind it; how late the
generator itself ran is reported beside it (``gen_late_ms``).

A collector thread takes the answers in the order of submission (what a
client with one ordered connection sees) and stamps each as it returns.
Once the window has closed, a seed-drawn sample of the answers (the slowest
request in it) is held against the plain reference: each candidate dispatch
of the log is sampled again by an identically seeded twin sampler, the
sample held against the host CSR, the reference's logits computed from the
host table and the seed's weights, and the served row compared with them.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import check, graphgen, harness, manifest, reference, traffic, work
from .train import HostData, blocks_of, compute_dtype_of

ANSWER_WAIT_S = 60.0     # past the window's close, for an answer still due
WARM_REQUESTS = 256      # driven before the window through submit/result
FAULTS = (None, "answer_altered")


class ServeCell:
    def __init__(self, cell: manifest.Cell, data: HostData, seed: int, *,
                 compute_dtype: Optional[str] = None):
        from quiver_tpu import CSRTopo
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.serve import ServeConfig, ServeEngine

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.data = cfg, data
        self.sizes = tuple(cfg["fanout"])
        self.topo = CSRTopo(indptr=data.graph.indptr, indices=data.graph.indices)
        self.sampler_seed = int(graphgen.stream(seed, 7).integers(0, 2**31 - 1))
        self.model = GraphSAGE(hidden_dim=cfg["hidden_dim"], out_dim=cfg["classes"],
                               num_layers=cfg["num_layers"], dropout=0.0,
                               dtype=compute_dtype_of(compute_dtype))
        self.params = reference.params_of(cfg, seed)
        self.engine = ServeEngine(
            self.model, self.params, self.make_sampler(), data.features,
            ServeConfig(max_in_flight=tr["max_in_flight"],
                        cache_entries=tr["cache_entries"],
                        record_dispatches=True, dispatch_mode="fused"))
        self.warm = self.engine.warmup()

    def make_sampler(self):
        from quiver_tpu.pyg import GraphSageSampler

        return GraphSageSampler(self.topo, self.sizes, device=0, mode="TPU",
                                seed=self.sampler_seed)


def drive(engine, reqs: traffic.Requests, fault: Optional[str] = None) -> Dict[str, Any]:
    """Open loop: submit each request when it is due (never earlier), take
    the answers in order on a second thread. Returns per-request send and
    done times relative to due, the rows, and the log positions that bound
    which dispatch served each request."""
    from jax.profiler import TraceAnnotation

    n = reqs.due_s.shape[0]
    send = np.zeros(n)
    done = np.full(n, np.inf)
    log_lo = np.zeros(n, np.int64)
    log_hi = np.zeros(n, np.int64)
    rows: List[Optional[np.ndarray]] = [None] * n
    errors: List[str] = []
    handles: List[Any] = []
    log = engine.dispatch_log
    submitted = threading.Semaphore(0)
    closing = {"t": None}

    def collect() -> None:
        for i in range(n):
            submitted.acquire()
            h = handles[i]
            try:
                left = ANSWER_WAIT_S if closing["t"] is None else max(
                    0.0, closing["t"] + ANSWER_WAIT_S - time.perf_counter())
                row = h.result(timeout=max(left, 0.001))
                done[i] = time.perf_counter()
                log_hi[i] = len(log)
                rows[i] = np.array(row)
            except Exception as exc:  # a failed or late answer is counted, not raised
                errors.append(f"request {i}: {exc!r}")

    collector = threading.Thread(target=collect, name="qbench-collector", daemon=True)
    collector.start()
    t0 = time.perf_counter() + 0.05
    with TraceAnnotation("qbench.window"):
        for i in range(n):
            due = t0 + reqs.due_s[i]
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                if due - now > 3e-4:
                    time.sleep(due - now - 2e-4)
            log_lo[i] = len(log)
            with TraceAnnotation("qbench.submit"):
                handles.append(engine.submit(int(reqs.nodes[i])))
            send[i] = now
            submitted.release()
        closing["t"] = time.perf_counter()
        with TraceAnnotation("qbench.result"):
            collector.join(timeout=ANSWER_WAIT_S + 30.0)
        t_end = time.perf_counter()
    if collector.is_alive():
        errors.append("the collector did not end")
    if fault == "answer_altered":  # every answer, where the client takes it
        rows = [None if r is None else r + np.float32(0.05) for r in rows]
    due_abs = t0 + reqs.due_s
    return {"latency_s": done - due_abs, "gen_late_s": send - due_abs, "rows": rows,
            "log_lo": log_lo, "log_hi": log_hi, "errors": errors,
            "elapsed_s": closing["t"] - t0, "drain_s": t_end - closing["t"]}


def compare_answers(sc: ServeCell, reqs: traffic.Requests, res: Dict[str, Any],
                    log, seed: int, sample: int) -> Dict[str, float]:
    """Hold a seed-drawn sample of the answers (the slowest among them)
    against the reference and against the engine's own replay guarantee."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu.inference import forward_logits, sample_batch

    data, cfg = sc.data, sc.cfg
    n_nodes = data.features.shape[0]
    answered = np.flatnonzero([r is not None for r in res["rows"]])
    out = {"answers_compared": 0, "logit_gap": 0.0, "replay_rows_differ": 0,
           "not_edges": 0, "wrong_fanout": 0, "no_dispatch_found": 0}
    if answered.size == 0:
        return out
    pick = graphgen.stream(seed, 10).choice(answered, min(sample, answered.size),
                                            replace=False)
    slowest = answered[np.argmax(res["latency_s"][answered])]
    pick = np.unique(np.append(pick, slowest))
    # node -> log positions that computed it
    where: Dict[int, List[int]] = {}
    wanted = {int(reqs.nodes[i]) for i in pick}
    for pos, (padded, n_valid) in enumerate(log):
        for v in padded[:n_valid]:
            if int(v) in wanted:
                where.setdefault(int(v), []).append(pos)
    slack = 4
    need: Dict[int, List[int]] = {}
    for i in pick:
        v = int(reqs.nodes[i])
        lo, hi = res["log_lo"][i] - slack, res["log_hi"][i]
        cands = [p for p in where.get(v, ()) if lo <= p < hi]
        if not cands:
            out["no_dispatch_found"] += 1
        for p in cands:
            need.setdefault(p, []).append(int(i))

    oracle = check.EdgeOracle(data.graph.indptr, data.graph.indices)
    apply = jax.jit(lambda p, x, adjs: sc.model.apply(p, x, adjs))
    ref_params = reference.params_of(cfg, seed)
    ref_forward = jax.jit(lambda p, x, blocks: reference.forward(
        p, x, blocks, cfg["matmul_operands"]))
    twin, at = sc.make_sampler(), 0
    best = {int(i): np.inf for i in pick}
    replay_ok = {int(i): False for i in pick}
    for pos in sorted(need):
        while at < pos:  # keys of the dispatches in between, unsampled
            twin.next_key()
            at += 1
        padded, n_valid = log[pos]
        ds = sample_batch(twin, padded)
        at += 1
        replay = np.asarray(forward_logits(apply, sc.params, data.features, ds))
        blocks = blocks_of(ds)
        n_id = np.asarray(ds.n_id)
        for k, v in check.sample_faults(oracle, n_id, blocks, False, len(padded)).items():
            if k in out:
                out[k] += v
        ids = np.clip(n_id.astype(np.int64), 0, n_nodes - 1)
        want = np.asarray(ref_forward(
            ref_params, jnp.asarray(data.features[ids]),
            [(jnp.asarray(b.cols), jnp.asarray(b.mask)) for b in blocks]))
        lanes = {int(v): lane for lane, v in enumerate(padded[:n_valid])}
        for i in need[pos]:
            lane = lanes[int(reqs.nodes[i])]
            row = res["rows"][i]
            best[i] = min(best[i], float(np.abs(row - want[lane]).max()))
            replay_ok[i] = replay_ok[i] or np.array_equal(
                row.view(np.uint32), replay[lane].view(np.uint32))
    found = [i for i in best if np.isfinite(best[i])]
    out["answers_compared"] = len(found)
    out["logit_gap"] = max((best[i] for i in found), default=0.0)
    out["replay_rows_differ"] = sum(1 for i in found if not replay_ok[i])
    return out


def percentile(lat: np.ndarray, q: float) -> float:
    """Percentile over ALL requests; unanswered ones sit at +inf."""
    s = np.sort(lat)
    return float(s[min(len(s) - 1, int(np.ceil(q / 100.0 * len(s))) - 1)])


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict[str, Any], t_start: float, chip_init_s: float = 0.0,
        keep_trace: Optional[str] = None, fault: Optional[str] = None,
        compute_dtype: Optional[str] = None, rate: Optional[float] = None) -> str:
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    cfg, tr = cell.config, cell.traffic
    rate = float(tr["rate"] if rate is None else rate)
    watch = harness.CompileWatch()
    try:
        data = HostData(cfg, seed)
        sc = ServeCell(cell, data, seed, compute_dtype=compute_dtype)
        eng = sc.engine
        mix = dict(rate=rate, alpha=tr["alpha"], arrivals=tr.get("arrivals", "poisson"),
                   burst=tr.get("burst", 1))
        warm = traffic.requests(cfg["n_nodes"], seed + 1, seconds=WARM_REQUESTS / rate, **mix)
        reqs = traffic.requests(cfg["n_nodes"], seed, seconds=seconds, **mix)
        eng.start()
        drive(eng, warm)
        # what set-up allocated leaves the collector's view: a full
        # collection over it stalled every thread for 97 ms mid-window
        # (my chip run, PR 25); the window's own garbage is still collected
        gc.collect()
        gc.freeze()
        warm_dispatches = len(eng.dispatch_log)
        stats0 = (eng.stats.dispatches, eng.stats.dispatched_seeds)
        watch.mark()
        setup_s = time.perf_counter() - t_start
        with harness.TraceWindow(trace) as tw:
            res = drive(eng, reqs, fault)
        compiled_in_window = watch.mark()
        eng.stop()
    finally:
        gc.unfreeze()
        watch.close()
    peak = harness.memory_peak_bytes(cell.chips)
    dispatches = eng.stats.dispatches - stats0[0]
    flushed_seeds = eng.stats.dispatched_seeds - stats0[1]
    buckets = {str(b): c for b, c in sorted(eng.stats.dispatch_buckets.items())}
    inflight_peak = eng.stats.inflight_peak
    log = list(eng.dispatch_log)
    sc.engine = eng = None

    lat = res["latency_s"]
    n = lat.shape[0]
    limit_s = tr["latency_limit_ms"] * 1e-3
    failed = int((~np.isfinite(lat)).sum())
    p99_ms = percentile(lat, 99) * 1e3
    values = {"serve_p50_ms": percentile(lat, 50) * 1e3,
              "serve_good_rps": float((lat <= limit_s).sum()) / seconds,
              "setup_s": setup_s}
    t0 = time.perf_counter()
    cmp = compare_answers(sc, reqs, res, log, seed, int(tr["answers_compared"]))
    check_s = time.perf_counter() - t0
    limits = tr["limits"]
    compared = [check.Compared("logit_gap", float(cmp["logit_gap"]), float(limits["logit_gap"]))]
    compared += [check.Compared(k, float(v), 0.0) for k, v in (
        ("replay_rows_differ", cmp["replay_rows_differ"]),
        ("not_edges", cmp["not_edges"]), ("wrong_fanout", cmp["wrong_fanout"]),
        ("no_dispatch_found", cmp["no_dispatch_found"]),
        ("unanswered", failed),
        ("compiled_in_window", compiled_in_window),
        ("none_compared", float(cmp["answers_compared"] == 0)))]

    breakdown = None
    if trace:
        summary = tw.reduce(keep=keep_trace)
        # forward FLOPs of one dispatch, from the sizes of the mean dispatch:
        # `flushed_seeds / dispatches` seeds, full fan-out
        width = flushed_seeds / max(dispatches, 1)
        targets, pairs, w = [], [], width
        for k in cfg["fanout"]:
            targets.append(w)
            pairs.append(w * k)
            w = w * (1 + k)
        ctx = {"trace": summary, "units": {"dispatches": dispatches, "requests": n},
               "work": {"dispatch_flops": work.sage_flops(
                   targets[::-1], pairs[::-1], reference.dims_of(cfg), backward=False)},
               "counters": {"flush_width": width, "p99_ms": p99_ms,
                            "gen_late_p99_ms": percentile(res["gen_late_s"], 99) * 1e3}}
        metrics = harness.per_layer_metrics(cell, device, ctx)
        device = dict(device, busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        metrics = harness.end_to_end_metrics(cell, values)
    device = dict(device, memory_peak_bytes=peak)
    correct = check.verdict(compared)
    return harness.result_line(
        correct=correct, attempted=n, failed=failed, metrics=metrics, device=device,
        compared=check.as_record(compared), breakdown=breakdown,
        extra={"window": {"requests": n, "rate": rate, "dispatches": dispatches,
                          "flush_width": flushed_seeds / max(dispatches, 1),
                          "buckets": buckets, "inflight_peak": inflight_peak,
                          "elapsed_s": res["elapsed_s"], "warm_dispatches": warm_dispatches,
                          "gen_late_p99_ms": percentile(res["gen_late_s"], 99) * 1e3,
                          "p50_ms": values["serve_p50_ms"], "p99_ms": p99_ms,
                          "good_rps": values["serve_good_rps"],
                          "errors": res["errors"][:3], "answers_compared": cmp["answers_compared"]},
               "timing": {"chip_init_s": chip_init_s, "graph_s": data.graph_s, "features_s": data.features_s,
                          "warmup_s": sum(sc.warm.values()), "check_s": check_s}})
