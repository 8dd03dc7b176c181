"""`qbench.limits` for a sharded train cell: reads, on the chips and at the
cell's own size, the numbers its limits are set from (PERF.md section 2): the
program as the configuration states it over many seeds (the lower readings),
the control (the library's own bfloat16 compute path) and each planted fault
over a few (the upper readings).

    python3 qbench/limits_sharded.py --workload <cell> --seeds 21 --others 3 --out <file.json>

One process: the graph and the table are made and placed once and each seed
brings fresh weights, batches and sampling keys. The reference runs at the
end, on the first chip, once the program's state is freed. The benchmark's
own runs never call this."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap")


def main(argv=None, root=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seeds", type=int, default=21)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    args = ap.parse_args(argv)

    from qbench import harness, manifest
    from qbench.kinds import train, train_sharded

    cell = manifest.load_cell(args.workload, root or manifest.ROOT)
    if not args.any_device:
        harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    sc = train_sharded.ShardedCell(cell, args.base_seed)
    data = sc.data
    plans = [("program", None, None, args.seeds),
             ("control_bfloat16", "bfloat16", None, args.others),
             ("fault_half_batch", None, "half_batch", args.others),
             ("fault_state_unchanged", None, "state_unchanged", args.others)]
    collected = []
    for label, dtype, fault, count in plans:
        sc.rebuild_step(dtype, fault)
        for i in range(count):
            seed = args.base_seed + 1 + i
            sc.reseed(seed)
            sc.first_steps()
            got = sc.collect()
            exact = train.exact_faults(data, got, sc.oracle_of(got), sc.batch)
            for s in got["steps"]:
                s["rows"] = s["sel"] = None
            collected.append((label, seed, got, exact))
            print(label, seed, [s["loss"] for s in got["steps"]], exact, flush=True)
    peak = harness.memory_peak_bytes(cell.chips)
    report = {"workload": args.workload, "base_seed": args.base_seed,
              "placement": sc.placement, "timing": sc.timing, "memory_peak_bytes": peak}
    sc.release()

    table = train_sharded.HostRows(data.features)
    rows = []
    for label, seed, got, exact in collected:
        ref = train.follow_with_reference(cell.config, data, seed, got, table=table)
        rows.append({"run": label, "seed": seed, **train.readings(got, ref), **exact,
                     "ref_losses": ref["losses"]})
        print(json.dumps(rows[-1]), flush=True)
    summary = {}
    for label, *_ in plans:
        mine = [r for r in rows if r["run"] == label]
        if mine:
            summary[label] = {k: {"min": min(r[k] for r in mine), "max": max(r[k] for r in mine)}
                              for k in NUMBERS}
    report.update(rows=rows, summary=summary)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(summary, indent=1))
    return report


if __name__ == "__main__":
    main()
