"""Confirm the SEPS-bench DCE hazard: consuming only adj.mask lets XLA
delete the neighbor-id gathers (masks depend only on degrees), so the
benched program is not doing the sampling it claims. Compare mask-only vs
mask+n_id consumption on the same scanned fused sampler."""
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

from quiver_tpu.pyg.sage_sampler import sample_dense_fused

ITERS = 100
SIZES = (15, 10, 5)


def main():
    indptr_np, indices_np = bench.build_graph()
    indptr = jax.device_put(jnp.asarray(indptr_np.astype(np.int32)))
    indices = jax.device_put(jnp.asarray(indices_np.astype(np.int32)))
    int(indptr[-1]), int(indices[-1])
    rng = np.random.default_rng(1)
    seeds = jax.device_put(
        jnp.asarray(rng.integers(0, indptr.shape[0] - 1, (24, 1024)).astype(np.int32))
    )

    def make(consume):
        @jax.jit
        def run(ip, ix, key0, seeds_all):
            m = seeds_all.shape[0]

            def body(acc, i):
                key = jax.random.fold_in(key0, i)
                ds = sample_dense_fused(ip, ix, key, seeds_all[i % m], SIZES)
                v = sum(a.mask.sum(dtype=jnp.int32) for a in ds.adjs)
                if consume == "mask+nid":
                    v = v + (ds.n_id.sum(dtype=jnp.int32) & 1)
                return acc + v, None

            acc, _ = lax.scan(body, jnp.int32(0), jnp.arange(ITERS, dtype=jnp.int32))
            return acc

        return run

    for consume in ("mask_only", "mask+nid"):
        run = make(consume)
        int(run(indptr, indices, jax.random.key(0), seeds))
        t0 = time.time()
        int(run(indptr, indices, jax.random.key(1), seeds))
        dt = time.time() - t0
        print(f"  {consume:10s}: {dt/ITERS*1e3:6.2f} ms/iter")


if __name__ == "__main__":
    main()
