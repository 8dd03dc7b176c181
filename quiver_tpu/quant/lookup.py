"""Fused dequant-on-gather lookups for quantized feature tables.

Every function here traces into the CALLER's jitted program (none is
jitted itself): the gather touches encoded rows + per-row side entries
and decodes in-register, so the f32 table never exists anywhere — not in
HBM, not on the H2D wire, not as an XLA temp bigger than the gathered
batch. This is the quantized twin of ``pipeline.tiered_lookup`` /
``collectives.sharded_gather``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .codecs import get_codec


def _side_lookup(mapped, scale, zero):
    """Per-lane scale/zero from the full [N_stored] side tables (clip keeps
    invalid lanes in range; their rows are masked by the caller)."""
    n = scale.shape[0]
    safe = jnp.clip(mapped, 0, n - 1)
    return jnp.take(scale, safe), jnp.take(zero, safe)


def gather_dequant(codec, payload, ids, scale=None, zero=None):
    """Fused gather + decode from a fully device-resident encoded table.

    payload: ``[N, D]`` encoded rows; scale/zero: ``[N]`` f32 side tables
    (codecs without side tables pass None). ids: any int shape — clipped
    into range exactly like ``Feature.lookup_padded`` (the jit contract;
    use :meth:`Feature.validate_ids` when silent clipping is not wanted).
    Returns f32 rows ``[..., D]``.
    """
    codec = get_codec(codec)
    n = payload.shape[0]
    q = jnp.take(payload, jnp.clip(ids, 0, n - 1), axis=0)
    if scale is not None:
        s, z = _side_lookup(ids, scale, zero)
        return codec.dequant(q, s, z)
    return codec.dequant(q)


def quantized_tiered_lookup(
    codec,
    hot_payload: jax.Array,
    mapped: jax.Array,
    cold_payload: jax.Array,
    cold_pos: jax.Array,
    scale: Optional[jax.Array] = None,
    zero: Optional[jax.Array] = None,
) -> jax.Array:
    """Quantized twin of :func:`quiver_tpu.pipeline.tiered_lookup`.

    The assembly stays ENCODED end to end: gather encoded hot rows from
    HBM, scatter the prefetched encoded cold rows (which crossed the H2D
    wire at codec width) into their lanes, THEN decode the merged [W, D]
    block once — dequant-after-scatter, so hot and cold lanes share one
    decode and the program holds no f32 temp wider than the batch. Side
    entries come from the device-resident [N_stored] tables indexed by
    ``mapped`` (cold rows never ship scale/zero over the wire).

    mapped: [W] stored-row ids, -1 invalid (the pipeline's contract);
    cold_payload/cold_pos: the staged cold rows in storage dtype. Lanes
    whose ``mapped`` points past the hot prefix MUST be covered by
    ``cold_pos`` (the pipeline guarantees it); uncovered cold lanes decode
    to the row's zero-point, not to 0.
    """
    codec = get_codec(codec)
    hot_n = hot_payload.shape[0]
    valid = mapped >= 0
    is_hot = valid & (mapped < hot_n)
    q = jnp.take(hot_payload, jnp.clip(mapped, 0, hot_n - 1), axis=0)
    q = q * is_hot[:, None].astype(q.dtype)
    if cold_payload.shape[0]:
        q = q.at[cold_pos].set(cold_payload, mode="drop")
    if scale is not None:
        s, z = _side_lookup(mapped, scale, zero)
        x = codec.dequant(q, s, z)
    else:
        x = codec.dequant(q)
    return x * valid[:, None].astype(x.dtype)


def sharded_dequant_gather(
    codec, payload_block, ids, axis_name, scale=None, zero=None
):
    """Global-id gather from an ICI-row-striped ENCODED table, inside
    shard_map — the quantized twin of ``collectives.sharded_gather``.

    The psum rides the encoded payload (int8 moves 4x fewer ICI bytes than
    f32 per gathered row); scale/zero are replicated per chip ([N_global]
    f32, ~2% of an fp32 table at D=100) and applied AFTER the collective.
    Summing encoded partials is exact: every non-owner contributes zeros.
    Out-of-range ids return zero rows (matching sharded_gather).
    """
    # lazy: pulling quiver_tpu.parallel at import time would drag the whole
    # train-step machinery into `import quiver_tpu`
    from ..parallel.collectives import sharded_gather

    codec = get_codec(codec)
    q = sharded_gather(payload_block, ids, axis_name)
    if scale is None:
        return codec.dequant(q)
    n = scale.shape[0]
    ok = (ids >= 0) & (ids < n)
    s, z = _side_lookup(ids, scale, zero)
    x = codec.dequant(q, s, z)
    return x * ok[..., None].astype(x.dtype)


def make_quantized_train_step(
    model, tx, labels: jax.Array, hot_payload: jax.Array,
    scale: Optional[jax.Array] = None, zero: Optional[jax.Array] = None,
    codec="int8",
):
    """Jitted ``step(params, opt_state, key, batch)`` with the fused
    dequant-gather inside fwd/bwd — the quantized twin of
    :func:`quiver_tpu.pipeline.make_tiered_train_step` (consumes the same
    :class:`TieredBatch`; the batch's ``cold_rows`` arrive in storage
    dtype from a ``TieredFeaturePipeline`` built over a
    :class:`QuantizedFeature`). Tables/labels enter as jit ARGUMENTS —
    closure capture would bake them in as XLA constants.
    """
    import optax

    codec = get_codec(codec)
    hot_payload = jnp.asarray(hot_payload)
    labels = jnp.asarray(labels)

    @jax.jit
    def step(params, opt_state, key, hot, s, z, lab, batch):
        x = quantized_tiered_lookup(
            codec, hot, batch.mapped, batch.cold_rows, batch.cold_pos, s, z
        )
        y = jnp.take(lab, jnp.clip(batch.seeds, 0, lab.shape[0] - 1))

        def objective(p):
            logits = model.apply(
                p, x, batch.ds.adjs, train=True, rngs={"dropout": key}
            )
            ll = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(ll, y[:, None].astype(jnp.int32), axis=1)[:, 0]
            return nll.mean()

        loss, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def bound(params, opt_state, key, batch):
        return step(params, opt_state, key, hot_payload, scale, zero, labels, batch)

    return bound
