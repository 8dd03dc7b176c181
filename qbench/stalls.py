"""For a serve cell, on the chip: window after window of the cell's own
traffic in ONE process with the library's stall watch on and no profiler
(``QUIVER_ENABLE_TRACE=1`` for the windows alone), and after each window what
the watch caught: every tick late by more than ``--threshold-ms`` with its
deltas (wall, process CPU, the watch's run-queue wait, faults, involuntary
switches) and the library spans, collections and compiles open across it, by
thread; the tick's count, mean and longest; the length of every full
(generation 2) collection; the window's latencies.

    python3 qbench/stalls.py --workload <cell> --seed <n> --seconds 20 --windows 30 --out <file.json>

A threshold far under the library's 30 ms (``--threshold-ms 2``) gives the
distribution of the ticks' overshoots. The benchmark's own runs never call
this; `PERF.md` section 7 row 2 has what it showed."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe(stall: dict, threads: dict, t_first: float) -> dict:
    """A `trace.stall_report` entry for the report: its start as seconds into
    the window, and per thread NAME what was open across it as ``[span,
    milliseconds of it inside the stall, ids]`` (the per-request submit spans
    left out: the flush spans say what the engine was doing)."""
    t0, t1 = stall.pop("t0"), stall.pop("t1")
    held = {}
    for tid, spans in stall["open"].items():
        held[threads.get(tid, str(tid))] = [
            [name, round((min(b, t1) - max(a, t0)) * 1e3, 3), ids]
            for name, a, b, ids in spans if name != "quiver.serve.submit"][:12]
    return dict(stall, at_s=t0 - t_first, open=held)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--threshold-ms", type=float, default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    ap.add_argument("--root", default=None, help="tests only: another manifest's checkout")
    args = ap.parse_args(argv)

    from qbench import harness, manifest, traffic
    from qbench.kinds import serve
    from qbench.kinds.train import HostData
    from quiver_tpu import trace

    cell = manifest.load_cell(args.workload, args.root or manifest.ROOT)
    cfg, tr = cell.config, cell.traffic
    if not args.any_device:
        harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    mix = dict(rate=float(tr["rate"]), alpha=tr["alpha"], arrivals=tr.get("arrivals", "poisson"),
               burst=tr.get("burst", 1))
    sc = serve.ServeCell(cell, HostData(cfg, args.seed), args.seed)
    eng = sc.engine
    eng.start()
    serve.drive(eng, traffic.requests(cfg["n_nodes"], args.seed + 1,
                                      seconds=serve.WARM_REQUESTS / mix["rate"], **mix))
    gc.collect()
    gc.freeze()  # as the serve kind does before its window
    library_threshold = trace.STALL_S
    if args.threshold_ms is not None:
        trace.STALL_S = args.threshold_ms * 1e-3
    report = {"workload": args.workload, "threshold_ms": trace.STALL_S * 1e3, "windows": []}
    try:
        for w in range(args.windows):
            reqs = traffic.requests(cfg["n_nodes"], args.seed + 2 + w, seconds=args.seconds, **mix)
            os.environ[trace.TRACE_ENV] = "1"
            try:
                res = serve.drive(eng, reqs)
            finally:
                del os.environ[trace.TRACE_ENV]
            threads = {t.ident: t.name for t in threading.enumerate()}
            found = trace.stall_report()
            ticks = trace.trace_report(reset=True, with_max=True).get("quiver.host.tick", (0, 0.0, 0.0))
            timeline = trace.trace_timeline(reset=True)
            t_first = min((e[1] for e in timeline), default=0.0)
            full_gc_ms = [round((e[2] - e[1]) * 1e3, 3) for e in timeline
                          if e[0] == "quiver.host.gc" and e[4]["generation"] == 2]
            stalls = [describe(s, threads, t_first) for s in found]
            lat = res["latency_s"]
            row = {"window": w, "requests": int(lat.shape[0]),
                   "p50_ms": serve.percentile(lat, 50) * 1e3, "p99_ms": serve.percentile(lat, 99) * 1e3,
                   "max_ms": float(lat.max()) * 1e3,
                   "gen_late_p99_ms": serve.percentile(res["gen_late_s"], 99) * 1e3,
                   "gen_late_max_ms": float(res["gen_late_s"].max()) * 1e3,
                   "ticks": ticks[0], "tick_mean_ms": 1e3 * ticks[1] / max(ticks[0], 1),
                   "tick_max_ms": 1e3 * ticks[2], "full_gc_ms": full_gc_ms, "stalls": stalls}
            report["windows"].append(row)
            print(json.dumps({k: v for k, v in row.items() if k != "stalls"} | {"stalls": len(stalls)}),
                  flush=True)
    finally:
        eng.stop()
        gc.unfreeze()
        trace.STALL_S = library_threshold
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
