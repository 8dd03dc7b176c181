"""`qbench.limits` for a relational attention train cell
(`qbench.kinds.train_rgat`): reads, on the chip and at the cell's own size,
the numbers its limits are set from (PERF.md section 2): the program as the
configuration states it over many seeds (the lower readings), the control
(`RGAT(dtype=bfloat16)`, the library's own lower-precision path) and each
planted fault over a few (the upper readings).

    python3 qbench/limits_rgat.py --workload <cell> --seeds 31 --others 3 --out <file.json>

One process: the typed graph, the feature store and the sampler are built
once and each seed brings fresh weights, batches and samples. The reference
runs at the end, once the program's state is freed. The benchmark's own runs
never call this."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, root=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seeds", type=int, default=31)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    args = ap.parse_args(argv)

    from qbench import check_rgat, harness, manifest
    from qbench.kinds import train, train_rgat

    cell = manifest.load_cell(args.workload, root or manifest.ROOT)
    if not args.any_device:
        harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    t0 = time.perf_counter()
    data = train_rgat.HostData(cell.config, args.base_seed)
    tc = train_rgat.RgatCell(cell, data, args.base_seed)
    report = {"workload": args.workload, "base_seed": args.base_seed,
              "setup_s": time.perf_counter() - t0}

    plans = [("program", None, None, args.seeds),
             ("control_bfloat16", "bfloat16", None, args.others),
             ("fault_half_batch", None, "half_batch", args.others),
             ("fault_state_unchanged", None, "state_unchanged", args.others)]
    collected = []
    for label, dtype, fault, count in plans:
        tc.rebuild_step(dtype, fault)
        for i in range(count):
            seed = args.base_seed + 1 + i
            tc.reseed(seed)
            tc.first_steps()
            got = tc.collect()
            for s in got["steps"]:
                s["rows"] = s["sel"] = None
            collected.append((label, seed, got))
            print(label, seed, [s["loss"] for s in got["steps"]], flush=True)
    peak = harness.memory_peak_bytes(cell.chips)
    tc.release()

    oracles = data.oracles()
    rows = []
    for label, seed, got in collected:
        exact = {"not_edges": 0, "wrong_fanout": 0, "not_unique": 0, "sampled_pairs": 0}
        for s in got["steps"]:
            for k, v in check_rgat.sample_faults(oracles, s["typed"]).items():
                exact[k] += v
        ref = train_rgat.follow_with_reference(cell.config, data, seed, got)
        read = train.readings(got, ref)
        worst = {f"{k}_worst_leaf": v for k, v in check_rgat.worst_leaves(got, ref).items()}
        rows.append({"run": label, "seed": seed, **read, **worst, **exact})
        print(json.dumps(rows[-1]), flush=True)
    numbers = train_rgat.NUMBERS
    summary = {}
    for label, *_ in plans:
        mine = [r for r in rows if r["run"] == label]
        if mine:
            summary[label] = {k: {"min": min(r[k] for r in mine), "max": max(r[k] for r in mine)}
                              for k in numbers}
    report.update(rows=rows, summary=summary, memory_peak_bytes=peak)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(summary, indent=1))
    return report


if __name__ == "__main__":
    main()
