"""`qbench.limits` for an attention train cell (`qbench.kinds.train_gat`):
reads, on the chip and at the cell's own size, the numbers its limits are set
from (PERF.md section 2): the program as the configuration states it over many
seeds (the lower readings), the control (`GAT(dtype=bfloat16)`, the library's
own lower-precision path) and each planted fault over a few (the upper
readings).

    python3 qbench/limits_gat.py --workload <cell> --seeds 21 --others 3 --out <file.json>

One process: the graph, the feature store and the sampler are built once and
each seed brings fresh weights, batches and samples. The reference runs at
the end, once the program's state is freed. The benchmark's own runs never
call this."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, root=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seeds", type=int, default=21)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--any-device", action="store_true", help="tests only")
    args = ap.parse_args(argv)

    import jax

    from qbench import check, harness, manifest
    from qbench.kinds import train, train_gat

    cell = manifest.load_cell(args.workload, root or manifest.ROOT)
    if not args.any_device:
        harness.enable_compile_cache()
    harness.find_chips(cell.chips, any_device=args.any_device)
    data = train.HostData(cell.config, args.base_seed)
    oracle = check.EdgeOracle(data.graph.indptr, data.graph.indices)
    tc = train_gat.GatCell(cell, data, args.base_seed)
    report = {"workload": args.workload, "base_seed": args.base_seed}

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
            exact = train.exact_faults(data, got, oracle, tc.batch)
            for s in got["steps"]:
                s["rows"] = s["sel"] = None
            collected.append((label, seed, got, exact))
            print(label, seed, [s["loss"] for s in got["steps"]], exact, flush=True)
    peak = harness.memory_peak_bytes(cell.chips)
    tc.release()
    del oracle

    table = jax.device_put(data.features)
    refs = {}
    rows = []
    for label, seed, got, exact in collected:
        if seed not in refs:
            refs[seed] = train_gat.follow_with_reference(cell.config, data, seed, got, table=table)
        # the reference of a seed follows the program's samples of that seed;
        # a control or fault run drew other samples, so it gets its own
        ref = refs[seed] if label == "program" else train_gat.follow_with_reference(
            cell.config, data, seed, got, table=table)
        read = train.readings(got, ref)
        exact_ref = train_gat.follow_with_reference(cell.config, data, seed, got, table=table,
                                                    operands="float32")
        read.update({f"{k}_vs_highest": v for k, v in train.readings(got, exact_ref).items()})
        rows.append({"run": label, "seed": seed, **read, **exact,
                     "ref_losses": ref["losses"]})
        print(json.dumps(rows[-1]), flush=True)
    numbers = train_gat.NUMBERS + tuple(f"{k}_vs_highest" for k in train_gat.NUMBERS)
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
