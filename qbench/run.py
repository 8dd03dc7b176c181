"""One run of one cell:

    python3 qbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process sets up the cell from the seed, warms up the cell's own shapes,
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints the result as the last line of standard output.
It measures on a TPU only, and takes no notice of ``BENCH_RUN``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb here (for looking at one by hand)")
    return ap.parse_args(argv)


def run(argv=None, any_device: bool = False, root: str = None, **overrides) -> str:
    """The whole run; returns the result line. ``any_device`` skips the look
    for a chip and ``overrides`` reach the kind's runner: both are for the
    tests under tests/qbench, which rehearse on the CPU and plant faults."""
    args = parse_args(argv)
    from qbench import harness, manifest

    cell = manifest.load_cell(args.workload, root or manifest.ROOT)
    if not any_device:  # the tests keep the cache their conftest chose
        harness.enable_compile_cache()
    device = harness.find_chips(cell.chips, any_device=any_device)
    runner = manifest.load_kind(cell.traffic["kind"])
    # set-up counts from here: JAX has the chip. What came before (imports
    # and the TPU runtime's own start-up, 8-14 s) is no work of this repo and
    # drifts by seconds from one machine to the next; it is reported apart
    t_start = time.perf_counter()
    return runner.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=device, t_start=t_start,
                      chip_init_s=t_start - T_PROCESS,
                      keep_trace=args.keep_trace, **overrides)


def main() -> None:
    line = run()
    sys.stderr.flush()
    print(line, flush=True)


if __name__ == "__main__":
    main()
