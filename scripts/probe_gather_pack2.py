"""Probe 2: de-noised gather-rate comparison + parallel-take concurrency.

Probe 1 (probe_gather_pack.py) had ~0.1s measured windows -> per-dispatch
jitter (~0.05-0.3s) dominated. Here ITERS=100 so compute is ~1-2s, and
each config is timed 3x to show spread.

Configs:
  a. plain take, [2.45M, 100] f32       (the hot-gather op as benched)
  b. pack=2 one-hot select, [1.22M,200] (the packing candidate)
  c. G=4 independent takes of W/4 each, concatenated (DMA concurrency?)
  d. plain take, dim=200 f32            (row-rate at 2x width)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

W = 262_144
ITERS = 100
N0, D = 2_449_029, 100


def timed3(fn, *args):
    float(fn(*args))  # compile + warm
    out = []
    for _ in range(3):
        t0 = time.time()
        float(fn(*args))
        out.append(time.time() - t0)
    return out


def report(name, dts, rows_per_iter=W):
    rates = [ITERS * rows_per_iter / dt / 1e6 for dt in dts]
    print(
        f"  {name:28s}: " + " ".join(f"{r:6.1f}" for r in rates) + " M rows/s"
        f"   (dt {min(dts):.2f}-{max(dts):.2f}s)"
    )
    return max(rates)


def main():
    print("devices:", jax.devices())
    idx = jax.random.randint(jax.random.key(9), (W,), 0, N0, dtype=jnp.int32)

    # a. plain dim-100
    tab = jax.random.normal(jax.random.key(1), (N0, D), jnp.float32)

    @jax.jit
    def plain(tab, idx):
        def body(acc, i):
            ids = (idx + i * 977) % N0
            return acc + jnp.take(tab, ids, axis=0).sum(dtype=jnp.float32), None

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    jax.block_until_ready((tab, idx))
    report("a plain take dim100", timed3(plain, tab, idx))

    # c. 4 independent takes of W/4, same table (tests DMA queue concurrency)
    @jax.jit
    def par4(tab, idx):
        parts = jnp.split(idx, 4)

        def body(acc, i):
            s = jnp.float32(0)
            for part in parts:
                ids = (part + i * 977) % N0
                s = s + jnp.take(tab, ids, axis=0).sum(dtype=jnp.float32)
            return acc + s, None

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    report("c 4 parallel takes", timed3(par4, tab, idx))
    del tab

    # b. pack=2 one-hot select
    npk = (N0 + 1) // 2
    tab2 = jax.random.normal(jax.random.key(2), (npk, 2 * D), jnp.float32)

    @jax.jit
    def pack2(tab2, idx):
        def body(acc, i):
            ids = (idx + i * 977) % N0
            packed = jnp.take(tab2, ids // 2, axis=0).reshape(W, 2, D)
            sel = jax.nn.one_hot(ids % 2, 2, dtype=packed.dtype)
            rows = jnp.einsum("wp,wpd->wd", sel, packed)
            return acc + rows.sum(dtype=jnp.float32), None

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    jax.block_until_ready(tab2)
    report("b pack2 one-hot", timed3(pack2, tab2, idx))

    # d. plain take at dim 200 (raw row rate at 2x width)
    @jax.jit
    def plain200(tab2, idx):
        def body(acc, i):
            ids = (idx + i * 977) % npk
            return acc + jnp.take(tab2, ids, axis=0).sum(dtype=jnp.float32), None

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
        return acc

    report("d plain take dim200", timed3(plain200, tab2, idx))
    del tab2


if __name__ == "__main__":
    main()
