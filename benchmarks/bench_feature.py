"""Feature-collection throughput (GB/s) — the reference's
benchmarks/feature/bench_feature.py (GB/s at lines 44-46), TPU edition.

Measures the tiered Feature gather at several hot-cache ratios, plus the
fully-HBM jit path, on a products-like table (N x 100 float32, batch =
typical 3-hop subgraph size).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2_000_000)
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--batch", type=int, default=300_000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--ratios", default="1.0,0.5,0.2,0.0")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from quiver_tpu import Feature
    from quiver_tpu.trace import gbps

    rng = np.random.default_rng(0)
    table = rng.standard_normal((args.nodes, args.dim)).astype(np.float32)
    row_bytes = args.dim * 4

    # skewed access pattern: 80% of reads hit the first 20% of rows (the
    # power-law justification, docs/Introduction_en.md:77-80)
    hot_n = args.nodes // 5
    hot = rng.integers(0, hot_n, int(args.batch * 0.8))
    cold = rng.integers(hot_n, args.nodes, args.batch - hot.shape[0])
    ids = np.concatenate([hot, cold])
    rng.shuffle(ids)

    for ratio in [float(r) for r in args.ratios.split(",")]:
        cache = int(args.nodes * ratio) * row_bytes
        feat = Feature(rank=0, device_list=[0], device_cache_size=cache)
        feat.from_cpu_tensor(table)
        out = feat[ids]  # warm
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(args.iters):
            out = feat[ids]
        jax.block_until_ready(out)
        dt = time.time() - t0
        print(f"cache={ratio:4.0%}: {gbps(args.iters * args.batch, args.dim, dt):7.2f} GB/s")

    # fully-resident jit path (lookup_padded is jitted internally; do NOT
    # jax.jit the bound method — that bakes the table in as a constant)
    feat = Feature(rank=0, device_list=[0], device_cache_size=args.nodes * row_bytes)
    feat.from_cpu_tensor(table)
    ids_d = jnp.asarray(ids)
    jax.block_until_ready(feat.lookup_padded(ids_d))
    t0 = time.time()
    for _ in range(args.iters):
        out = feat.lookup_padded(ids_d)
    jax.block_until_ready(out)
    dt = time.time() - t0
    print(f"jit HBM : {gbps(args.iters * args.batch, args.dim, dt):7.2f} GB/s")
    print("note: cold-tier numbers include host->device copies")


if __name__ == "__main__":
    main()
