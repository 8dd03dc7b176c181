"""`qbench.limits` for a tiered train cell: reads, on the chip and at the
cell's own size, the numbers its limits are set from (PERF.md section 2): the
program as the configuration states it over many seeds (the lower readings),
the control (the library's own bfloat16 compute path) and each planted fault
over a few (the upper readings); with ``--calibrate`` also what the caps'
and the cold block's calibrations give on that graph.

    python3 qbench/limits_tiered.py --workload <cell> --seeds 21 --others 3 \
        --calibrate 8 --out <file.json>

One process: the graph and the table are made and placed once and each seed
brings fresh weights, batches and samples through the same `TrainPipeline`.
The reference runs at the end, once the program's state is freed. The
benchmark's own runs never call this."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap")


def calibrations(tc, batches: int) -> dict:
    """`calibrate_caps` and `calibrate_cold_cap` (margin 1.1, granule 4096)
    over probe batches of the cell's own train split; nothing is installed."""
    import numpy as np

    probe = np.stack([next(tc.batches) for _ in range(batches)])
    saved = tc.sampler.caps
    caps = tc.sampler.calibrate_caps(probe, margin=1.1, granule=4096, set_caps=False)
    tc.sampler.caps = caps  # the cold rows of samples drawn under the caps just read
    samples = [tc.sampler.sample_dense(b) for b in probe]
    ids, counts = [np.asarray(ds.n_id) for ds in samples], [int(ds.count) for ds in samples]
    tc.sampler.caps = saved
    cold_cap = tc.feature.calibrate_cold_cap(ids, counts, margin=1.1, granule=4096,
                                             set_cap=False)
    return {"caps": list(caps), "cold_cap": cold_cap, "unique_rows": counts}


def main(argv=None, root=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seeds", type=int, default=21)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--calibrate", type=int, default=0, help="probe batches (printed only)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    args = ap.parse_args(argv)

    from qbench import harness, manifest
    from qbench.kinds import train, train_sharded, train_tiered

    cell = manifest.load_cell(args.workload, root or manifest.ROOT)
    if not args.any_device:
        harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    tc = train_tiered.TieredCell(cell, args.base_seed)
    data = tc.data
    report = {"workload": args.workload, "base_seed": args.base_seed, "timing": tc.timing}
    if args.calibrate:
        report["calibrations"] = calibrations(tc, args.calibrate)
        print(json.dumps(report["calibrations"]), flush=True)
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
            exact = tc.exact_faults(got, tc.oracle_of(got))
            for s in got["steps"]:
                s["rows"] = s["sel"] = s["mapped"] = None
            collected.append((label, seed, got, exact))
            print(label, seed, [s["loss"] for s in got["steps"]], exact, flush=True)
    report["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    report["cold_overflow"] = tc.feature.cold_overflow
    tc.release()

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
