"""Probe: does position LOCALITY change the element-gather rate? (honest
windows — the round-3 'sort order is irrelevant' conclusion was measured
in windows too short to trust). If sorted positions gather meaningfully faster, a
cheap sort (~0.5 ms/M) in front of the 1.07M-element neighbor fetch
(~11 ms) would pay."""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

bench.enable_compile_cache()

import jax
import jax.numpy as jnp
from jax import lax

ITERS = 200
W = 1_048_576


def main():
    _, indices_np = bench.build_graph()
    tab = jax.device_put(jnp.asarray(indices_np.astype(np.int32)))
    int(tab[-1])
    E = tab.shape[0]
    rng = np.random.default_rng(0)
    raw = rng.integers(0, E, W)
    variants = {
        "random": raw,
        "sorted": np.sort(raw),
        # blockwise-sorted: sort within 8k-position chunks — what an in-jit
        # pre-sort of each hop's row-major frontier would roughly give
        "block-sorted": np.sort(raw.reshape(-1, 8192), axis=1).reshape(-1),
    }

    @jax.jit
    def run(tab, idx):
        def body(acc, i):
            sh = (idx + i) % E  # +i keeps iterations distinct, order intact
            return acc + jnp.take(tab, sh).sum(dtype=jnp.int32), None

        acc, _ = lax.scan(body, jnp.int32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    for name, ids in variants.items():
        idx = jax.device_put(jnp.asarray(ids.astype(np.int32)))
        int(run(tab, idx))
        t0 = time.time()
        int(run(tab, idx))
        dt = time.time() - t0
        print(f"  {name:12s}: {ITERS*W/dt/1e6:7.1f}M elems/s ({dt/ITERS*1e3:.2f} ms/iter)")


if __name__ == "__main__":
    main()
