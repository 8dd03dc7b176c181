"""For a serve cell, on the chip, once: the sweep that finds the highest rate
the engine sustains (the cell's fixed rate is four fifths of it), and the
readings its ``logit_gap`` limit is set from (the program over many seeds,
the bfloat16 control and the altered-answer fault over a few).

    python3 qbench/sweep.py --workload <cell> --rates 500,1000,2000 --seconds 5 --out <file.json>
    python3 qbench/sweep.py --workload <cell> --seeds 12 --others 3 --seconds 3 --out <file.json>

One process; the graph and features are made once. The benchmark's own runs
never call this."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=3_000_000_019)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--others", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    args = ap.parse_args(argv)

    import numpy as np

    from qbench import harness, manifest, traffic
    from qbench.kinds import serve
    from qbench.kinds.train import HostData

    cell = manifest.load_cell(args.workload)
    cfg, tr = cell.config, cell.traffic
    harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    data = HostData(cfg, args.base_seed)
    report = {"workload": args.workload, "sweep": [], "gaps": []}

    def mix(rate):
        return dict(rate=rate, alpha=tr["alpha"], arrivals=tr.get("arrivals", "poisson"),
                    burst=tr.get("burst", 1))

    if args.rates:
        sc = serve.ServeCell(cell, data, args.base_seed)
        sc.engine.start()
        serve.drive(sc.engine, traffic.requests(cfg["n_nodes"], 1, seconds=0.5, **mix(500.0)))
        for rate in (float(r) for r in args.rates.split(",")):
            reqs = traffic.requests(cfg["n_nodes"], args.base_seed, seconds=args.seconds, **mix(rate))
            d0 = (sc.engine.stats.dispatches, sc.engine.stats.dispatched_seeds)
            res = serve.drive(sc.engine, reqs)
            lat = res["latency_s"] * 1e3
            half = lat.shape[0] // 2
            row = {"rate": rate, "requests": int(lat.shape[0]),
                   "p50_ms": serve.percentile(lat, 50), "p99_ms": serve.percentile(lat, 99),
                   "p50_first_half_ms": serve.percentile(lat[:half], 50),
                   "p50_second_half_ms": serve.percentile(lat[half:], 50),
                   "max_ms": float(lat.max()), "unanswered": int((~np.isfinite(lat)).sum()),
                   "drain_s": res["drain_s"],
                   "gen_late_p99_ms": serve.percentile(res["gen_late_s"], 99) * 1e3,
                   "flush_width": (sc.engine.stats.dispatched_seeds - d0[1])
                   / max(sc.engine.stats.dispatches - d0[0], 1)}
            report["sweep"].append(row)
            print(json.dumps(row), flush=True)
        sc.engine.stop()
        report["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
        sc = None
        gc.collect()

    plans = [("program", None, None, args.seeds), ("control_bfloat16", "bfloat16", None, args.others),
             ("fault_answer_altered", None, "answer_altered", args.others)]
    for label, dtype, fault, count in plans:
        for i in range(count):
            seed = args.base_seed + 1 + i
            sc = serve.ServeCell(cell, data, seed, compute_dtype=dtype)
            sc.engine.start()
            reqs = traffic.requests(cfg["n_nodes"], seed, seconds=args.seconds, **mix(float(tr["rate"])))
            res = serve.drive(sc.engine, reqs, fault)
            sc.engine.stop()
            log = list(sc.engine.dispatch_log)
            sc.engine = None
            gc.collect()
            cmp = serve.compare_answers(sc, reqs, res, log, seed, int(tr["answers_compared"]))
            row = {"run": label, "seed": seed, **{k: float(v) for k, v in cmp.items()},
                   "p50_ms": serve.percentile(res["latency_s"], 50) * 1e3}
            report["gaps"].append(row)
            print(json.dumps(row), flush=True)
            sc = None
            gc.collect()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
