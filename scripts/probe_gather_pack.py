"""Probe: XLA row-gather rate vs row width, and packed-row gather schemes.

PERF.md (earlier claims) records the hot-gather descriptor wall: ~20M rows/s for
dim<=128, but ~26M rows/s at dim 256. If rate keeps rising with row width,
storing the feature table packed ([N/p, p*D]) and selecting the needed
D-slice on-chip beats the plain gather even with p-1 wasted lanes.

Two sections:
  1. rate-vs-dim curve: f32 dims 100..1024 (+bf16), constant ~1 GB table.
  2. end-to-end packed-select: deliver [W, 100] useful f32 rows from a
     pack-p table via take(ids >> log2 p) + per-row half select.

Measurement discipline (PERF.md (earlier claims)): tables generated ON DEVICE, passed
as jit ARGUMENTS, iterations scanned in-jit, timing ended with a dependent
float() fetch. Run with `python -u`, nothing else on the machine.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

W = 262_144
ITERS = 10
TARGET_BYTES = 980_000_000  # ~ the products table, constant across dims


def make_gather(iters):
    @jax.jit
    def gather_many(tab, idx):
        def body(acc, i):
            shifted = (idx + i * 977) % tab.shape[0]
            return acc + jnp.take(tab, shifted, axis=0).sum(dtype=jnp.float32), None

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(iters, dtype=jnp.int32))
        return acc

    return gather_many


def timed(fn, *args):
    float(fn(*args))  # compile + warm
    t0 = time.time()
    float(fn(*args))
    return time.time() - t0


def section_rate_vs_dim():
    print("== rate vs dim (gather W=%d rows, %d iters in-jit) ==" % (W, ITERS))
    gather_many = make_gather(ITERS)
    for dtype, dsize in ((jnp.float32, 4), (jnp.bfloat16, 2)):
        for dim in (100, 128, 200, 256, 400, 512, 800, 1024):
            n = TARGET_BYTES // (dim * dsize)
            key = jax.random.key(dim)
            tab = jax.random.normal(key, (n, dim), dtype=dtype)
            idx = jax.random.randint(jax.random.key(7), (W,), 0, n, dtype=jnp.int32)
            tab, idx = jax.block_until_ready((tab, idx))
            dt = timed(gather_many, tab, idx)
            rows_s = ITERS * W / dt
            gbps = rows_s * dim * dsize / 1e9
            print(
                f"  {jnp.dtype(dtype).name:8s} dim={dim:5d} N={n:8d}: "
                f"{rows_s/1e6:6.1f}M rows/s  {gbps:7.2f} GB/s raw"
            )
            del tab


def section_packed_select():
    """Deliver [W, 100] useful f32 rows from a pack-p table.

    Base table conceptually [N0, 100] f32, N0 = 2.45M (products). Packed
    table [N0/p, p*100]; requested ids uniform in [0, N0). Scheme: take the
    packed row id>>log2(p), then select the 100-wide slice (id % p) with a
    one-hot contraction-free where-chain (p is tiny and static).
    """
    print("== packed-select end-to-end (useful D=100 f32, W=%d) ==" % W)
    n0, d = 2_449_029, 100

    for p in (1, 2, 4, 8):
        npk = (n0 + p - 1) // p
        key = jax.random.key(p)
        tab = jax.random.normal(key, (npk, p * d), dtype=jnp.float32)
        idx = jax.random.randint(jax.random.key(9), (W,), 0, n0, dtype=jnp.int32)
        tab, idx = jax.block_until_ready((tab, idx))

        @jax.jit
        def run(tab, idx, p=p):
            def body(acc, i):
                ids = (idx + i * 977) % n0
                packed = jnp.take(tab, ids // p, axis=0)  # [W, p*d]
                if p == 1:
                    rows = packed
                else:
                    parts = packed.reshape(W, p, d)
                    sel = jax.nn.one_hot(ids % p, p, dtype=packed.dtype)
                    rows = jnp.einsum("wp,wpd->wd", sel, parts)
                return acc + rows.sum(dtype=jnp.float32), None

            acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
            return acc

        dt = timed(run, tab, idx)
        rows_s = ITERS * W / dt
        useful_gbps = rows_s * d * 4 / 1e9
        print(
            f"  pack={p}: {rows_s/1e6:6.1f}M useful rows/s  "
            f"{useful_gbps:6.2f} GB/s useful ({useful_gbps*p:7.2f} GB/s raw)"
        )
        del tab


def section_packed_select_dynslice():
    """pack-p with per-row dynamic-slice select instead of one-hot einsum."""
    print("== packed-select via vmap dynamic_slice ==")
    n0, d = 2_449_029, 100
    for p in (2, 4):
        npk = (n0 + p - 1) // p
        tab = jax.random.normal(jax.random.key(p + 100), (npk, p * d), jnp.float32)
        idx = jax.random.randint(jax.random.key(9), (W,), 0, n0, dtype=jnp.int32)
        tab, idx = jax.block_until_ready((tab, idx))

        @jax.jit
        def run(tab, idx, p=p):
            def body(acc, i):
                ids = (idx + i * 977) % n0
                packed = jnp.take(tab, ids // p, axis=0)  # [W, p*d]
                off = (ids % p) * d
                rows = jax.vmap(
                    lambda row, o: lax.dynamic_slice(row, (o,), (d,))
                )(packed, off)
                return acc + rows.sum(dtype=jnp.float32), None

            acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(ITERS, dtype=jnp.int32))
            return acc

        dt = timed(run, tab, idx)
        rows_s = ITERS * W / dt
        print(
            f"  pack={p}: {rows_s/1e6:6.1f}M useful rows/s  "
            f"{rows_s*d*4/1e9:6.2f} GB/s useful"
        )
        del tab


if __name__ == "__main__":
    print("devices:", jax.devices())
    section_rate_vs_dim()
    section_packed_select()
    section_packed_select_dynslice()
