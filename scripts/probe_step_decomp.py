"""Decompose the e2e train step's 44 ms: sampling / reindex / gather /
model+grad, each as its own scanned program.

At the gather rate earlier rounds recorded (~94M rows/s, see PERF.md
"Earlier claims") the gather should be ~9 ms of the 44 ms dedup step — this
probe finds where the rest goes. Same measurement discipline as bench.py.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # bench.py: graph cache + compile cache helpers

bench.enable_compile_cache()

import jax
import jax.numpy as jnp
from jax import lax

from quiver_tpu.pyg.sage_sampler import (
    sample_and_gather_dedup,
    sample_and_gather_fused,
    sample_dense_fused,
    sample_dense_pure,
)

ITERS = 100
SIZES = (15, 10, 5)
CAPS = (16384, 135168, 499712)  # the bench's calibrated caps


def timed(fn, *args):
    jax.block_until_ready(fn(*args))  # compile + warm
    best = None
    for _ in range(2):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return best


def main():
    indptr_np, indices_np = bench.build_graph()
    indptr = jax.device_put(jnp.asarray(indptr_np.astype(np.int32)))
    indices = jax.device_put(jnp.asarray(indices_np.astype(np.int32)))
    int(indptr[-1]), int(indices[-1])
    n = indptr.shape[0] - 1
    table = jax.jit(lambda k: jax.random.normal(k, (n, 100), jnp.float32))(
        jax.random.key(7)
    )
    rng = np.random.default_rng(1)
    seeds = jax.device_put(
        jnp.asarray(rng.integers(0, n, (24, 1024)).astype(np.int32))
    )

    def scan_over(body):
        @jax.jit
        def run(ip, ix, tab, key0, seeds_all):
            m = seeds_all.shape[0]

            def step(acc, i):
                key = jax.random.fold_in(key0, i)
                return acc + body(ip, ix, tab, key, seeds_all[i % m]), None

            acc, _ = lax.scan(step, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
            return acc

        return run

    def report(name, run):
        dt = timed(run, indptr, indices, table, jax.random.key(0), seeds)
        ms = dt / ITERS * 1e3
        print(f"  {name:26s}: {ms:6.2f} ms/iter")
        return ms

    # a. fused sampling only
    def fused_sample(ip, ix, tab, key, s):
        ds = sample_dense_fused(ip, ix, key, s, SIZES)
        return ds.n_id.sum(dtype=jnp.float32)

    # b. dedup sampling only (sorts + reindex included)
    def dedup_sample(ip, ix, tab, key, s):
        ds = sample_dense_pure(ip, ix, key, s, SIZES, CAPS)
        return ds.n_id.sum(dtype=jnp.float32)

    # c. dedup sample + leaf gather (no model)
    def dedup_gather(ip, ix, tab, key, s):
        ds, x = sample_and_gather_dedup(ip, ix, tab, key, s, SIZES, CAPS)
        return x.sum(dtype=jnp.float32)

    # d. fused sample + interleaved gather (no model)
    def fused_gather(ip, ix, tab, key, s):
        ds, x = sample_and_gather_fused(ip, ix, tab, key, s, SIZES)
        return x.sum(dtype=jnp.float32)

    # e. gather only, dedup-width take from the table
    W = 811_008
    ids = jax.device_put(
        jnp.asarray(rng.integers(0, n, W).astype(np.int32))
    )

    @jax.jit
    def pure_gather(tab, ids):
        def stepf(acc, i):
            sh = (ids + i * 977) % n
            return acc + jnp.take(tab, sh, axis=0).sum(dtype=jnp.float32), None

        acc, _ = lax.scan(stepf, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    for name, body in (
        ("a fused sample only", fused_sample),
        ("b dedup sample only", dedup_sample),
        ("c dedup sample+gather", dedup_gather),
        ("d fused sample+gather", fused_gather),
    ):
        report(name, scan_over(body))
    dt = timed(pure_gather, table, ids)
    print(f"  e pure take {W} rows       : {dt/ITERS*1e3:6.2f} ms/iter")


if __name__ == "__main__":
    main()
