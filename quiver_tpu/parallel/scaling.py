"""Analytic multi-chip scaling model for the sharded train steps.

The reference publishes MEASURED 1-to-4-GPU scaling tables for its sampling
and e2e benchmarks (docs/Introduction_en.md:123-126 sampling, :144-158 e2e
epochs). Multi-chip hardware is the exception in this repo's runs
(`chip_smoke.py --chips 4` is the one path that takes four), so the
framework's multichip evidence is split: hermetic correctness on the virtual
CPU mesh (tests/test_parallel.py, `__graft_entry__.dryrun_multichip`) plus
THIS static cost model, which predicts step/epoch time on N chips from

- the single-chip measured step time, and
- per-step collective bytes counted statically from the same layout the
  jitted programs use (`topology.sampling_comm_bytes` ring model), divided
  by explicit, overridable link-bandwidth assumptions.

Every number the model emits is tagged with the assumptions; on real
multi-chip hardware `scripts/scaling_model.py --measured ...` rows can be
replaced by measurements one at a time without touching the model.

Model shape
-----------
A data-parallel epoch at ``N`` chips runs ``ceil(steps_1 / N)`` steps whose
duration is bounded below by ``max(t_compute, t_comm)`` (perfect overlap)
and above by ``t_compute + t_comm`` (no overlap). XLA overlaps collectives
with compute inside one program, so reality sits between; the table reports
the pessimistic (additive) bound plus the optimistic bound, and scaling
efficiency against ideal linear speedup. ``t_compute`` is the measured
single-chip step time: per-chip batch work is constant under dp scaling
(each dp group samples its own seed batch).
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple


class ShapeMesh(NamedTuple):
    """Duck-typed stand-in for `jax.sharding.Mesh` carrying only what the
    byte model reads (`mesh_axes` / `sampling_comm_bytes` touch
    ``axis_names`` and ``shape[axis]`` exclusively), so layouts larger than
    the visible device count can be modeled without devices."""

    axis_names: Tuple[str, ...]
    shape: Dict[str, int]


# Link-rate assumptions (bytes/s, per chip or per host). Deliberately
# conservative public-ballpark figures — the point is relative layout cost,
# and each is a named knob the caller can override.
DEFAULT_BANDWIDTHS = {
    # v5e inter-chip interconnect, usable per-chip ring bandwidth
    "ici_bytes_per_s": 9.0e10,
    # data-center network per host (200 Gbps NIC class)
    "dcn_bytes_per_s": 2.5e10,
}


class LayoutPrediction(NamedTuple):
    layout: str
    n_devices: int
    mesh_shape: Dict[str, int]
    step_comm_s: float
    step_s_optimistic: float   # max(compute, comm): perfect overlap
    step_s_pessimistic: float  # compute + comm: zero overlap
    epoch_s_optimistic: float
    epoch_s_pessimistic: float
    efficiency_pessimistic: float  # vs ideal linear scaling of the epoch
    ici_bytes: float
    dcn_bytes: float


import re as _re

# sync collectives are counted by their RESULT shape; async pairs by the
# `-done` op's result only (a `-start` result tuple carries BOTH operand
# and result buffers, which would double-count the payload)
_HLO_COLLECTIVE_LINE_RE = _re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(-start|-done)?\("
)
_HLO_SHAPE_RE = _re.compile(
    r"(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]"
)
_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
    "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}


def collective_payload_bytes(
    hlo_text: str, expected: Optional[Sequence[str]] = None
) -> Dict[str, int]:
    """Measured counterpart of the ring model: parse a COMPILED program's
    HLO and sum the payload bytes of every collective, per op kind.

    Returns e.g. ``{"all-reduce": 123456, "all-gather": 789}`` — payloads
    are the per-device program's result shapes (tuples summed), i.e. the
    quantity the ring model multiplies by ``2(P-1)/P`` per axis. Feed it
    ``jax.jit(step).lower(*args).compile().as_text()``; pairing these
    measured bytes with `sampling_comm_bytes`' predictions turns the
    scaling table's traffic column from arithmetic into evidence (see
    tests/test_scaling_model.py::test_model_matches_compiled_step).

    Matched spellings: sync (``all-gather(...)``) and async pairs
    (``all-gather-start``/``-done`` — counted once, on the ``-done``).
    Generic ``async-start``/``async-done`` wrappers print the wrapped
    collective inside their called computation, whose body line matches the
    sync form, so those are counted too. Because a future XLA spelling
    could still slip through silently, pass ``expected`` (op-kind names)
    and the parser raises if any expected kind shows ZERO bytes — callers
    validating a program they *know* contains a psum should always use it
    (round-3 ADVICE.md item 3).
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _HLO_COLLECTIVE_LINE_RE.search(line)
        if not m or m.group(3) == "-start":
            continue
        total = 0
        for dt, dims in _HLO_SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _HLO_DTYPE_BYTES[dt]
        out[m.group(2)] = out.get(m.group(2), 0) + total
    if expected:
        missing = [k for k in expected if not out.get(k)]
        if missing:
            raise ValueError(
                f"expected collective kinds {missing} not found in HLO — "
                "either the program lost its collectives or XLA emits a "
                "spelling this parser does not match"
            )
    return out


def comm_seconds(
    ici_bytes: float,
    dcn_bytes: float,
    bandwidths: Optional[Dict[str, float]] = None,
) -> float:
    bw = dict(DEFAULT_BANDWIDTHS)
    if bandwidths:
        bw.update(bandwidths)
    return ici_bytes / bw["ici_bytes_per_s"] + dcn_bytes / bw["dcn_bytes_per_s"]


def grad_psum_bytes(param_bytes: int, mesh: ShapeMesh) -> Dict[str, float]:
    """Gradient allreduce cost over the data axes (ring model, per chip):
    the DDP-analog `lax.pmean` in the train steps (train.py:218)."""
    out = {"ici_bytes": 0.0, "dcn_bytes": 0.0}
    for axis in ("dp", "host"):
        if axis in mesh.axis_names and mesh.shape[axis] > 1:
            a = mesh.shape[axis]
            key = "dcn_bytes" if axis == "host" else "ici_bytes"
            out[key] += 2.0 * (a - 1) / a * param_bytes
    return out


def predict_layout(
    layout: str,
    mesh: ShapeMesh,
    step_s_1chip: float,
    steps_per_epoch_1chip: int,
    sizes: Sequence[int],
    batch_per_group: int,
    feature_dim: int,
    param_bytes: int,
    caps: Optional[Sequence[Optional[int]]] = None,
    bandwidths: Optional[Dict[str, float]] = None,
) -> LayoutPrediction:
    """One row of the scaling table.

    ``layout``:
      - "dp_replicated": graph + features replicated per chip; the only
        collective is the gradient psum (the reference's DDP layout,
        dist_sampling_ogb_products_quiver.py:85-117).
      - "dp_ici_features": features row-striped over ici
        (p2p_clique_replicate analog); adds the per-hop sharded-gather
        psums of the fused pipeline.
      - "sharded_topology": CSR row-sharded too (papers100M layout); adds
        the per-hop neighbor psums of `sharded_sample_layer`.
      - "sharded_topology_hot_cold": same, with the replicated-hot feature
        tier (`sharded_gather_hot_cold`): only ``cold_frac`` of the feature
        payload rides the host (DCN) axis — the model face of
        tests/test_hot_cold.py's measured lane reduction.

    Note on ``efficiency_pessimistic``: it divides by IDEAL linear speedup
    over ALL chips. Layouts that spend the ici axis on *capacity* (feature
    or graph rows beyond one HBM) parallelize batches only over the data
    groups, so their efficiency is bounded by dp_groups/n by construction —
    read their rows as "what capacity costs", not as a defect.
    """
    from .topology import sampling_comm_bytes

    cold_frac = 1.0
    kind = layout
    if layout == "sharded_topology_hot_cold":
        kind, cold_frac = "sharded_topology", 0.2  # calibrated-budget scale

    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    comm = grad_psum_bytes(param_bytes, mesh)
    if kind == "dp_replicated":
        pass  # feature + topology local: gradient psum only
    elif kind == "dp_ici_features":
        # sampling is LOCAL in this layout; the only sharded traffic is the
        # per-hop feature gathers, modeled directly by gather_comm_bytes
        # (grouped id all-gather + row return — including the DCN legs on
        # (host, ...) meshes, which round 3 modeled as free: ADVICE item 2)
        from ..ops.sample import pad_widths
        from .topology import gather_comm_bytes

        widths = pad_widths(batch_per_group, sizes, caps)
        gather_widths = [widths[0]] + [w * k for w, k in zip(widths, sizes)]
        for gw in gather_widths:
            g = gather_comm_bytes(mesh, gw, feature_dim)
            comm["ici_bytes"] += g["ici_bytes"]
            comm["dcn_bytes"] += g["dcn_bytes"]
    elif kind == "sharded_topology":
        c = sampling_comm_bytes(
            mesh, sizes, batch_per_group, feature_dim=feature_dim, caps=caps
        )
        c_ids = sampling_comm_bytes(mesh, sizes, batch_per_group, caps=caps)
        comm["ici_bytes"] += c["ici_bytes"]
        # id exchange always pays DCN in full; the feature payload's DCN leg
        # shrinks to the cold fraction under the replicated-hot tier
        comm["dcn_bytes"] += (
            c_ids["dcn_bytes"]
            + (c["dcn_bytes"] - c_ids["dcn_bytes"]) * cold_frac
        )
    else:
        raise ValueError(f"unknown layout {kind!r}")

    t_comm = comm_seconds(comm["ici_bytes"], comm["dcn_bytes"], bandwidths)
    opt = max(step_s_1chip, t_comm)
    pess = step_s_1chip + t_comm
    dp_groups = 1
    for a in ("host", "dp"):
        if a in mesh.axis_names:
            dp_groups *= mesh.shape[a]
    steps = math.ceil(steps_per_epoch_1chip / dp_groups)
    ideal = step_s_1chip * steps_per_epoch_1chip / n
    return LayoutPrediction(
        layout=layout,
        n_devices=n,
        mesh_shape=dict(mesh.shape),
        step_comm_s=t_comm,
        step_s_optimistic=opt,
        step_s_pessimistic=pess,
        epoch_s_optimistic=opt * steps,
        epoch_s_pessimistic=pess * steps,
        efficiency_pessimistic=ideal / (pess * steps) if steps else 0.0,
        ici_bytes=comm["ici_bytes"],
        dcn_bytes=comm["dcn_bytes"],
    )


def products_scaling_table(
    step_s_1chip: float,
    steps_per_epoch_1chip: int = 193,
    sizes: Sequence[int] = (15, 10, 5),
    batch_per_group: int = 1024,
    feature_dim: int = 100,
    param_bytes: int = 1_650_000,
    caps: Optional[Sequence[Optional[int]]] = None,
    bandwidths: Optional[Dict[str, float]] = None,
) -> List[LayoutPrediction]:
    """The products-config scaling table the reference publishes measured
    (Introduction_en.md:144-158: 11.1s/6.0s/4.0s/3.2s at 1/2/3/4 GPUs),
    predicted for this framework's three layouts at 1..8 chips plus one
    2-host DCN row."""
    rows: List[LayoutPrediction] = []
    for n in (1, 2, 4, 8):
        dp = n  # all-dp: the DDP-analog scaling axis
        rows.append(
            predict_layout(
                "dp_replicated",
                ShapeMesh(("dp", "ici"), {"dp": dp, "ici": 1}),
                step_s_1chip, steps_per_epoch_1chip, sizes, batch_per_group,
                feature_dim, param_bytes, caps, bandwidths,
            )
        )
    for n in (4, 8):
        rows.append(
            predict_layout(
                "dp_ici_features",
                ShapeMesh(("dp", "ici"), {"dp": n // 2, "ici": 2}),
                step_s_1chip, steps_per_epoch_1chip, sizes, batch_per_group,
                feature_dim, param_bytes, caps, bandwidths,
            )
        )
        rows.append(
            predict_layout(
                "sharded_topology",
                ShapeMesh(("dp", "ici"), {"dp": n // 2, "ici": 2}),
                step_s_1chip, steps_per_epoch_1chip, sizes, batch_per_group,
                feature_dim, param_bytes, caps, bandwidths,
            )
        )
    for layout in ("sharded_topology", "sharded_topology_hot_cold"):
        rows.append(
            predict_layout(
                layout,
                ShapeMesh(("host", "dp", "ici"), {"host": 2, "dp": 2, "ici": 2}),
                step_s_1chip, steps_per_epoch_1chip, sizes, batch_per_group,
                feature_dim, param_bytes, caps, bandwidths,
            )
        )
    return rows


class QuantPrediction(NamedTuple):
    codec: str
    bytes_per_elem: float
    row_bytes: float           # payload + per-row side-table bytes
    hot_capacity_multiplier: float  # rows hot per HBM byte, vs fp32
    gather_gb_per_step: float  # HBM bytes the step's row gathers touch
    h2d_gb_per_step: float     # cold wire bytes (side tables stay on device)
    gather_reduction: float    # fraction of the fp32 gather bytes
    h2d_reduction: float       # fraction of the fp32 H2D bytes


def quant_fetch_table(
    sizes: Sequence[int],
    batch_per_group: int,
    feature_dim: int,
    caps: Optional[Sequence[Optional[int]]] = None,
    cold_frac: float = 0.2,
    codecs: Sequence[str] = ("fp32", "bf16", "int8"),
) -> List[QuantPrediction]:
    """Per-codec fetch/byte rows for the quantized feature store
    (`quiver_tpu.quant`): what each codec does to the three byte walls the
    tiered step pays —

    - hot capacity: ``4*D / row_bytes`` more rows fit the same HBM budget
      (int8 at D=100: 3.70x — the 20% fp32 hot tier becomes ~74%, i.e.
      most cold host-gathers become hot HBM hits before any wire speedup).
      This is the amortized full-residency figure: ``QuantizedFeature``
      charges the full-N side tables at ingest, so realized hot rows are
      ``(budget - side_bytes_per_row*N) / payload_row_bytes``;
    - gather bytes: the step's final padded n_id width (`pad_widths`, the
      dedup/tiered pipelines' single full-row gather) times row bytes;
    - H2D bytes: ``cold_frac`` of that width crosses the host link at
      PAYLOAD width (per-row side tables are device-replicated,
      quant/feature.py) — the wire leg `trace.gbps(bytes_per_elem=...)`
      measures.

    Codec byte shapes come from the live `quant.codecs` registry, so a
    registered custom codec shows up by adding its name to ``codecs``.
    """
    from ..ops.sample import pad_widths
    from ..quant.codecs import get_codec

    widths = pad_widths(batch_per_group, sizes, caps)
    w = widths[-1]
    base_row = 4.0 * feature_dim
    base_gather = w * base_row
    base_h2d = cold_frac * w * base_row
    rows: List[QuantPrediction] = []
    for name in codecs:
        c = get_codec(name)
        row_b = c.row_bytes(feature_dim)
        gather = w * row_b
        h2d = cold_frac * w * c.bytes_per_elem * feature_dim
        rows.append(
            QuantPrediction(
                codec=c.name,
                bytes_per_elem=c.bytes_per_elem,
                row_bytes=row_b,
                hot_capacity_multiplier=base_row / row_b,
                gather_gb_per_step=gather / 1e9,
                h2d_gb_per_step=h2d / 1e9,
                gather_reduction=gather / base_gather,
                # cold_frac=0 (fully HBM-resident): no H2D leg, reduction
                # is vacuously 1.0 rather than 0/0
                h2d_reduction=h2d / base_h2d if base_h2d else 1.0,
            )
        )
    return rows


def format_quant_markdown(rows: Sequence[QuantPrediction]) -> str:
    lines = [
        "| codec | B/elem | row B | hot capacity x | gather GB/step | H2D GB/step | gather vs f32 | H2D vs f32 |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r.codec} | {r.bytes_per_elem:g} | {r.row_bytes:g} "
            f"| {r.hot_capacity_multiplier:.2f} | {r.gather_gb_per_step:.4f} "
            f"| {r.h2d_gb_per_step:.4f} | {r.gather_reduction:.0%} "
            f"| {r.h2d_reduction:.0%} |"
        )
    lines.append("")
    lines.append(
        "Rows gathered/step = final padded n_id width (pad_widths); side "
        "tables (int8 fp32 scale+zero, 8 B/row) are device-replicated so "
        "they count against hot capacity but never the H2D wire "
        "(quiver_tpu/quant). The capacity multiplier compounds with the "
        "byte shrink: more rows hot means FEWER cold H2D rows on top of "
        "each row being cheaper."
    )
    return "\n".join(lines)


class ServePrediction(NamedTuple):
    bucket: int            # dispatched batch shape (GLOBAL, pre-split)
    hit_rate: float        # embedding-cache hit rate
    unique_frac: float     # unique seeds / requests among cache misses
    dispatch_s: float      # per-shard sample + gather + forward (shard_bucket wide)
    requests_per_dispatch: float
    qps: float             # sustainable device-bound AGGREGATE throughput
    device_us_per_request: float
    floor_p50_ms: float    # latency floor: half the flush window + dispatch (+ exchange)
    # -- H-host fields (defaults keep the hosts=1 rows and older callers
    # byte-identical to the round-9 model) --
    hosts: int = 1
    shard_bucket: int = 0          # per-shard batch width, ceil(bucket/H)
    exchange_bytes: float = 0.0    # router exchange bytes per routed dispatch
    exchange_s: float = 0.0        # that payload over the DCN link
    # -- one-vs-two-dispatch fields (round 11; defaults keep older rows
    # value-identical: zero overhead makes the call count irrelevant) --
    dispatches_per_flush: int = 1  # 1 = fused serve_step, 2 = split path
    overhead_s: float = 0.0        # fixed per-execute overhead paid each call
    # -- host-path fields (round 20; default 0 = no host term, rows
    # byte-identical to the round-11 model) --
    host_submit_us: float = 0.0    # measured submit->seal host cost/request
    host_qps_cap: float = math.inf # serial host ceiling, 1e6/(submit+resolve)
    # -- drain-side host field (round 22; default 0 keeps round-20 rows
    # byte-identical: the cap reduces to 1e6/host_submit_us) --
    host_resolve_us: float = 0.0   # measured drain (assemble→resolve)/request
    # -- routed fan-out fields (round 23; default 0 = collective pricing,
    # rows byte-identical to the round-22 model) --
    owner_fanout: int = 0          # host-mode legs running concurrently (F)
    leg_merge_us: float = 0.0      # per-flush join/merge host cost (us)


def serve_table(
    t_sample_s: float,
    t_gather_s: float,
    t_forward_s: float,
    ref_batch: int,
    buckets: Sequence[int] = (8, 32, 64),
    hit_rates: Sequence[float] = (0.0, 0.5, 0.9),
    unique_frac: float = 0.8,
    max_delay_ms: float = 2.0,
    hosts: int = 1,
    out_dim: int = 47,
    bandwidths: Optional[Dict[str, float]] = None,
    dispatches_per_flush: int = 1,
    dispatch_overhead_s: float = 0.0,
    host_submit_us: float = 0.0,
    host_resolve_us: float = 0.0,
    owner_fanout: Optional[int] = None,
    leg_merge_us: float = 0.0,
) -> List[ServePrediction]:
    """Analytic QPS model for the online serving engine
    (`quiver_tpu.serve.ServeEngine`) from MEASURED per-batch costs.

    The engine's device work per dispatch is exactly one offline eval step
    (`inference.batch_logits`): sample + gather + forward at the bucket
    shape. Feed the three measured costs at a reference batch ``ref_batch``
    (bench.py's sampling/feature/e2e sections, or scripts/serve_probe.py on
    CPU); they are scaled to each bucket linearly in batch rows — honest at
    large shapes because all three paths are descriptor/row-count bound,
    not occupancy bound (PERF.md (earlier claims)), but OPTIMISTIC for tiny buckets:
    the linear model omits the fixed per-dispatch overhead (kernel launch,
    host sync), which does not shrink with batch and dominates small
    dispatches. Read small-bucket rows as ceilings on
    dispatch speed, large-bucket rows as floors when the cost input is a
    train step (which additionally pays backward + update).

    Request algebra: of R incoming requests/s, ``(1-hit_rate)`` miss the
    embedding cache and ``unique_frac`` of those survive coalescing, so one
    bucket-B dispatch retires ``B / ((1-hit_rate) * unique_frac)`` requests.
    Sustainable QPS is that over the dispatch time; the p50 latency floor
    is half the flush window plus one dispatch (a request arrives mid-
    window on average, then rides the next flush).

    ``hosts > 1`` prices the distributed engine
    (`quiver_tpu.serve.DistServeEngine`): the router splits each bucket-B
    flush by seed ownership, so every shard samples/forwards a
    ``ceil(B/hosts)``-wide sub-batch (the 1/H width shrink the serve probe
    measures) and the shards run CONCURRENTLY — one routed dispatch takes
    one shard-width dispatch plus the exchange hop. Exchange bytes per
    routed flush are the serve-shaped collective's actual payloads
    (`comm.exchange_serve_all`): ``H*H*L`` int32 seed ids out plus
    ``H*H*L*out_dim`` float32 logits back, with ``L`` the STATIC per-owner
    lane budget ``round_up_pow2(bucket)`` — the engine's default, sized
    for worst-case skew (a whole flush owned by one host), so these rows
    match the engine's measured ``exchange_id_bytes``/
    ``exchange_logit_bytes`` counters byte for byte — priced against
    ``dcn_bytes_per_s`` exactly like `sampling_comm_bytes` prices the
    training-side exchange. Aggregate QPS then scales ~H-fold until the
    exchange term catches the shrinking dispatch — the crossover this
    table exists to locate before hardware does.

    ``dispatches_per_flush`` x ``dispatch_overhead_s`` is the
    ONE-vs-TWO-dispatch cost model (round 11): every device execute call
    pays a fixed overhead that does not shrink with batch (kernel launch,
    host sync).
    The round-9 split path pays it twice per flush (sample + forward,
    ``dispatches_per_flush=2``); the fused `inference.serve_step` path
    pays it once (``=1``, the engine default). With the default zero
    overhead the rows reduce to the round-10 model exactly; feed the
    measured floor (or the probe's measured split-minus-fused delta) to
    price what the 2→1 cut buys at each bucket — the smaller the bucket,
    the more of its flush time was overhead, so the win concentrates
    exactly where latency-bound serving lives.

    ``host_submit_us`` is the HOST-side submit→seal cost per request
    (round 20): admission — cache/coalesce probe, shed decision, queue
    insert, journal append — runs serially on the submit path, so it
    caps sustainable throughput at ``1e6 / host_submit_us`` requests/s
    no matter how fast the device retires dispatches. Feed the measured
    batch-path number from ``scripts/bench_frontend.py``
    (FRONTEND_r01.json ``host_submit_us``, or via ``scripts/
    scaling_model.py --frontend``); the default 0 keeps every row
    byte-identical to the round-11 model. Rows where the cap binds
    (``qps == host_qps_cap`` below the device-bound ceiling) are
    exactly the regimes the vectorized `submit_many` path exists for —
    the scalar-path cost typically binds at high cache-hit rates, where
    one dispatch retires many requests.

    ``host_resolve_us`` (round 22) is the drain-side twin: the
    assemble→seal→resolve host work per request (block resolution,
    `put_many` cache fill, batched delivery), measured as
    FRONTEND_r02.json's ``host_resolve_us``. The two host phases run on
    the same serial admission/drain path, so the cap becomes
    ``1e6 / (host_submit_us + host_resolve_us)``; the default 0 keeps
    every row byte-identical to the round-20 model.

    ``owner_fanout`` (round 23) prices the HOST-mode router instead of
    the collective: direct owner legs over loopback (no DCN collective
    payload — exchange bytes drop to zero) with ``F = owner_fanout``
    legs running concurrently, so the routed dispatch term is
    ``ceil(H / F) * t_dispatch + leg_merge_us`` — ``F=1`` is the
    pre-round-23 SEQUENTIAL router (the implicit Σ(legs) =
    ``H * t_dispatch`` this model silently assumed away), ``F >= H``
    the concurrent fan-out's max(legs) + merge. ``leg_merge_us`` is the
    measured per-FLUSH join/merge host cost (FRONTEND_r03.json's
    ``leg_merge_us``; via ``scripts/scaling_model.py --frontend``). The
    default ``owner_fanout=None`` keeps every row byte-identical to
    the round-22 collective pricing.
    """
    bw = dict(DEFAULT_BANDWIDTHS)
    if bandwidths:
        bw.update(bandwidths)
    if hosts < 1:
        raise ValueError("hosts must be >= 1")
    if dispatches_per_flush < 1:
        raise ValueError("dispatches_per_flush must be >= 1")
    rows: List[ServePrediction] = []
    per_seed = (t_sample_s + t_gather_s + t_forward_s) / max(ref_batch, 1)
    for b in buckets:
        shard_b = -(-b // hosts)
        t_dispatch = (
            per_seed * shard_b + dispatches_per_flush * dispatch_overhead_s
        )
        if owner_fanout is not None and hosts > 1:
            # host-mode routed dispatch (round 23): F legs at a time,
            # direct owner calls — no collective payload to price
            fan = max(1, int(owner_fanout))
            xbytes = 0.0
            x_s = 0.0
            t_routed = (
                -(-hosts // fan) * t_dispatch + leg_merge_us * 1e-6
            )
        elif hosts > 1:
            from ..comm import round_up_pow2

            lanes = round_up_pow2(b)  # the engine's default static budget
            xbytes = hosts * hosts * lanes * (4 + 4 * out_dim)
            x_s = xbytes / bw["dcn_bytes_per_s"]
            t_routed = t_dispatch + x_s
        else:
            xbytes = 0.0
            x_s = 0.0
            t_routed = t_dispatch + x_s
        host_us = host_submit_us + host_resolve_us
        host_cap = 1e6 / host_us if host_us > 0 else math.inf
        for h in hit_rates:
            miss = (1.0 - h) * unique_frac
            rpd = b / miss if miss > 0 else math.inf
            qps = min(rpd / t_routed, host_cap)
            rows.append(
                ServePrediction(
                    bucket=b,
                    hit_rate=h,
                    unique_frac=unique_frac,
                    dispatch_s=t_dispatch,
                    requests_per_dispatch=rpd,
                    qps=qps,
                    device_us_per_request=(
                        0.0 if math.isinf(rpd) else t_dispatch / rpd * 1e6
                    ),
                    floor_p50_ms=max_delay_ms / 2 + t_routed * 1e3,
                    hosts=hosts,
                    shard_bucket=shard_b,
                    exchange_bytes=xbytes,
                    exchange_s=x_s,
                    dispatches_per_flush=dispatches_per_flush,
                    overhead_s=dispatch_overhead_s,
                    host_submit_us=host_submit_us,
                    host_qps_cap=host_cap,
                    host_resolve_us=host_resolve_us,
                    owner_fanout=(
                        0 if owner_fanout is None or hosts <= 1
                        else max(1, int(owner_fanout))
                    ),
                    leg_merge_us=(
                        leg_merge_us
                        if owner_fanout is not None and hosts > 1
                        else 0.0
                    ),
                )
            )
    return rows


def format_serve_markdown(rows: Sequence[ServePrediction]) -> str:
    multi = any(getattr(r, "hosts", 1) > 1 for r in rows)
    if multi:
        lines = [
            "| bucket | hosts | shard bucket | cache hit | req/dispatch | shard dispatch ms | exchange KB | exchange ms | agg QPS | p50 floor ms |",
            "|---|---|---|---|---|---|---|---|---|---|",
        ]
    else:
        lines = [
            "| bucket | cache hit | req/dispatch | dispatch ms | QPS | device us/req | p50 floor ms |",
            "|---|---|---|---|---|---|---|",
        ]
    for r in rows:
        rpd = "inf" if math.isinf(r.requests_per_dispatch) else f"{r.requests_per_dispatch:.0f}"
        qps = "inf" if math.isinf(r.qps) else f"{r.qps:.0f}"
        if multi:
            lines.append(
                f"| {r.bucket} | {r.hosts} | {r.shard_bucket} | {r.hit_rate:.0%} "
                f"| {rpd} | {r.dispatch_s*1e3:.2f} | {r.exchange_bytes/1e3:.1f} "
                f"| {r.exchange_s*1e3:.3f} | {qps} | {r.floor_p50_ms:.2f} |"
            )
        else:
            lines.append(
                f"| {r.bucket} | {r.hit_rate:.0%} | {rpd} "
                f"| {r.dispatch_s*1e3:.2f} | {qps} "
                f"| {r.device_us_per_request:.1f} | {r.floor_p50_ms:.2f} |"
            )
    lines.append("")
    if multi:
        lines.append(
            "Aggregate QPS = bucket / ((1-hit)*unique_frac) / (shard "
            "dispatch + exchange): the router splits each flush by seed "
            "owner, shards run ~bucket/H-wide dispatches concurrently, and "
            "the exchange ships H*H*L ids out + H*H*L*out_dim f32 logits "
            "back over DCN (comm.exchange_serve payloads). Measured "
            "counterpart: scripts/serve_probe.py --hosts."
        )
        fanned = [r for r in rows if getattr(r, "owner_fanout", 0) > 0]
        if fanned:
            f0 = fanned[0]
            lines.append(
                f"Host-mode routed dispatch (round 23): legs priced at "
                f"ceil(H/{f0.owner_fanout}) shard dispatches + "
                f"{f0.leg_merge_us:.2f} us join/merge per flush, no "
                "collective payload — owner_fanout=1 is the sequential "
                "router's Σ(legs); fan-out >= H is max(legs) + merge "
                "(scripts/bench_frontend.py --r03, FRONTEND_r03.json)."
            )
    else:
        lines.append(
            "QPS = bucket / ((1-hit)*unique_frac) / dispatch_s — device-bound "
            "ceiling, ignores host queueing; p50 floor = max_delay_ms/2 + one "
            "dispatch. Costs scale linearly from the measured reference batch "
            "(row-count-bound regime, PERF.md (earlier claims)); the serving engine's "
            "measured counterpart is scripts/serve_probe.py / bench.py serve."
        )
    hosted = [
        r for r in rows
        if getattr(r, "host_submit_us", 0.0) > 0
        or getattr(r, "host_resolve_us", 0.0) > 0
    ]
    if hosted:
        hs = hosted[0].host_submit_us
        hr = getattr(hosted[0], "host_resolve_us", 0.0)
        if hr > 0:
            lines.append(
                f"Host path (round 22): {hs:.2f} us/request submit + "
                f"{hr:.2f} us/request drain (assemble→resolve, scripts/"
                f"bench_frontend.py) cap QPS at {1e6 / (hs + hr):.0f}/s "
                "per admission path; rows at that value are host-bound, "
                "not device-bound."
            )
        else:
            lines.append(
                f"Host submit path (round 20): {hs:.2f} us/request "
                f"(submit→seal, scripts/bench_frontend.py) caps QPS at "
                f"{1e6 / hs:.0f}/s per admission path; rows at that value "
                "are host-bound, not device-bound."
            )
    return "\n".join(lines)


class SkewPrediction(NamedTuple):
    top_k: int                 # rows replicated on every host
    coverage: float            # measured request share of those rows
    replica_bytes_per_host: float  # feature bytes the replica set costs
    exchange_seed_frac: float  # seeds still crossing the exchange
    exchange_bytes_frac: float # collective payload vs no replication
    exchange_s: float          # exchange time per routed flush, replicated
    routed_flush_s: float      # shard dispatch + exchange, replicated
    qps_uplift: float          # aggregate QPS multiplier vs no replication


def skew_table(
    coverage: Sequence[Tuple[int, float]],
    hosts: int,
    bucket: int,
    out_dim: int,
    dispatch_s: float,
    feature_dim: int = 100,
    feature_bytes_per_elem: float = 4.0,
    bandwidths: Optional[Dict[str, float]] = None,
) -> List[SkewPrediction]:
    """Predicted hot-shard REPLICATION benefit from a MEASURED
    head-concentration curve — the `scaling` face of the round-13
    frequency sketch, feeding ROADMAP item 3a before it is built.

    ``coverage`` is [(k, frac)]: the request share of the hottest ``k``
    rows, straight from ``WorkloadMonitor.skew_report()['top_coverage']``
    (or an analytic Zipf curve for what-if rows). Replicating those ``k``
    rows' results on every host means that share of seeds is served
    locally and never crosses the serve exchange; a routed bucket-B flush
    then ships only ``(1-frac)*B`` seeds, so the static per-owner lane
    budget shrinks from ``pow2(B)`` to ``pow2(ceil((1-frac)*B))`` and the
    exchange term of `serve_table`'s routed-flush model shrinks with it
    (ids out + logits back, priced against ``dcn_bytes_per_s``; the
    model matches the engine's measured ``exchange_id_bytes`` /
    ``exchange_logit_bytes`` counters shape for shape). Aggregate device
    work is unchanged — hot seeds still compute somewhere — so
    ``qps_uplift`` isolates what replication buys on the WIRE and at the
    straggler boundary: (dispatch + exchange_full) / (dispatch +
    exchange_replicated). ``replica_bytes_per_host`` prices what it
    costs: k feature rows per host at the stated width.

    ``dispatch_s`` is the per-shard dispatch time at ``bucket/hosts``
    width (measure it: bench.py ``serve_fused_step_s`` scaled, or the
    probe's measured costs); ``hosts=1`` rows are legal and show uplift
    1.0 — replication buys nothing without an exchange to avoid.
    """
    if hosts < 1:
        raise ValueError("hosts must be >= 1")
    bw = dict(DEFAULT_BANDWIDTHS)
    if bandwidths:
        bw.update(bandwidths)

    def pow2(n: int) -> int:
        p = 1
        while p < n:
            p *= 2
        return p

    def exchange_s_for(lanes: int) -> float:
        if hosts == 1:
            return 0.0
        xbytes = hosts * hosts * lanes * (4 + 4 * out_dim)
        return xbytes / bw["dcn_bytes_per_s"]

    base_lanes = pow2(bucket)
    base_x = exchange_s_for(base_lanes)
    base_t = dispatch_s + base_x
    rows: List[SkewPrediction] = []
    for k, frac in coverage:
        frac = min(max(float(frac), 0.0), 1.0)
        routed = max(int(math.ceil((1.0 - frac) * bucket)), 0)
        lanes = pow2(routed) if routed else 0
        x_s = exchange_s_for(lanes) if routed else 0.0
        t = dispatch_s + x_s
        rows.append(
            SkewPrediction(
                top_k=int(k),
                coverage=frac,
                replica_bytes_per_host=(
                    float(k) * feature_dim * feature_bytes_per_elem
                ),
                exchange_seed_frac=routed / bucket if bucket else 0.0,
                # zero baseline (hosts=1: no exchange exists) -> nothing
                # is paid, so the honest fraction is 0, not 100%
                exchange_bytes_frac=(
                    exchange_s_for(lanes) / base_x if base_x else 0.0
                ),
                exchange_s=x_s,
                routed_flush_s=t,
                qps_uplift=base_t / t if t > 0 else 1.0,
            )
        )
    return rows


def pick_replication_k(
    rows: Sequence[SkewPrediction],
    min_uplift: float = 1.0,
    replica_budget_bytes: Optional[float] = None,
) -> Optional[SkewPrediction]:
    """The CHEAPEST `skew_table` row worth replicating: the smallest
    top-k whose predicted ``qps_uplift`` strictly beats ``min_uplift``
    within the per-host replica byte budget (None = unbounded). Returns
    None when no row qualifies — replication buys nothing at this skew /
    budget, don't pay for it. This is how the round-15 serve stack sizes
    ``DistServeConfig.replicate_top_k`` from a MEASURED head-concentration
    curve instead of a guess (serve_probe --faults closes the loop:
    measured uplift vs this row's prediction)."""
    best: Optional[SkewPrediction] = None
    for r in sorted(rows, key=lambda r: r.top_k):
        if r.qps_uplift <= min_uplift:
            continue
        if (replica_budget_bytes is not None
                and r.replica_bytes_per_host > replica_budget_bytes):
            continue
        best = r
        break
    return best


class FleetPrediction(NamedTuple):
    action: str                # "baseline" | "replicate top-k" | "add host"
    hosts: int                 # fleet size under this action
    top_k: int                 # replicated head size (0 for host actions)
    dispatch_s: float          # per-owner shard dispatch at this size
    exchange_s: float          # serve-exchange wire time per routed flush
    routed_flush_s: float      # dispatch + exchange
    agg_qps: float             # bucket / routed_flush_s
    qps_uplift: float          # vs the baseline row
    added_bytes_per_host: float  # replica rows, or the new host's shard


def fleet_table(
    coverage: Sequence[Tuple[int, float]],
    hosts: int,
    bucket: int,
    out_dim: int,
    dispatch_s: float,
    table_rows: int,
    feature_dim: int = 100,
    add_hosts: Sequence[int] = (1, 2),
    feature_bytes_per_elem: float = 4.0,
    bandwidths: Optional[Dict[str, float]] = None,
) -> List[FleetPrediction]:
    """Price ADD-A-HOST against REPLICATE-THE-HEAD on one table — the
    round-16 elastic-fleet planning face (`DistServeEngine.scale` vs
    `refresh_replicas`), from the same measured inputs the round-13/15
    models ride: the sketch's head-concentration ``coverage`` [(k, frac)]
    and the measured per-owner ``dispatch_s`` at the CURRENT ``hosts``
    (bench.py ``serve_fused_step_s`` scaled, or the probe's in-run
    timing).

    Replication rows reuse `skew_table`'s wire model exactly (device
    work unchanged, exchange term shrinks with the head share; cost = k
    feature rows ON EVERY host). Add-host rows scale the per-owner
    dispatch with the sub-batch width (``ceil(bucket/H')`` vs
    ``ceil(bucket/H)`` — row-count-bound regime, PERF.md (earlier claims)) and
    re-price the exchange at the larger ``H'^2 * L`` payload (the
    all_to_all grows quadratically in hosts — adding hosts buys device
    width but PAYS wire); cost = the new host's resident shard,
    ``table_rows/H'`` feature rows (closure halo excluded — label it
    when the partition isn't k-hop closed). The two costs land in one
    ``added_bytes_per_host`` column so `pick_fleet_action` can choose
    the cheapest uplift within a byte budget. Replication attacks the
    wire and the head; a host attacks device width and capacity — at
    high skew the table shows replication winning long before a host
    pays for itself, which is the round-15 measured story."""
    if hosts < 1:
        raise ValueError("hosts must be >= 1")
    bw = dict(DEFAULT_BANDWIDTHS)
    if bandwidths:
        bw.update(bandwidths)

    def pow2(n: int) -> int:
        p = 1
        while p < n:
            p *= 2
        return p

    def exchange_s_at(h: int, lanes: int) -> float:
        if h == 1 or lanes == 0:
            return 0.0
        return h * h * lanes * (4 + 4 * out_dim) / bw["dcn_bytes_per_s"]

    base_width = max(-(-bucket // hosts), 1)
    base_x = exchange_s_at(hosts, pow2(bucket))
    base_t = dispatch_s + base_x
    rows = [FleetPrediction(
        action="baseline", hosts=hosts, top_k=0, dispatch_s=dispatch_s,
        exchange_s=base_x, routed_flush_s=base_t,
        agg_qps=bucket / base_t if base_t > 0 else 0.0,
        qps_uplift=1.0, added_bytes_per_host=0.0,
    )]
    for k, frac in coverage:
        frac = min(max(float(frac), 0.0), 1.0)
        routed = max(int(math.ceil((1.0 - frac) * bucket)), 0)
        x_s = exchange_s_at(hosts, pow2(routed) if routed else 0)
        t = dispatch_s + x_s
        rows.append(FleetPrediction(
            action="replicate top-k", hosts=hosts, top_k=int(k),
            dispatch_s=dispatch_s, exchange_s=x_s, routed_flush_s=t,
            agg_qps=bucket / t if t > 0 else 0.0,
            qps_uplift=base_t / t if t > 0 else 1.0,
            added_bytes_per_host=(
                float(k) * feature_dim * feature_bytes_per_elem
            ),
        ))
    for dh in add_hosts:
        h2 = hosts + int(dh)
        if h2 <= hosts:
            continue
        width2 = max(-(-bucket // h2), 1)
        d_s = dispatch_s * width2 / base_width
        x_s = exchange_s_at(h2, pow2(bucket))
        t = d_s + x_s
        rows.append(FleetPrediction(
            action="add host", hosts=h2, top_k=0, dispatch_s=d_s,
            exchange_s=x_s, routed_flush_s=t,
            agg_qps=bucket / t if t > 0 else 0.0,
            qps_uplift=base_t / t if t > 0 else 1.0,
            added_bytes_per_host=(
                float(table_rows) / h2 * feature_dim
                * feature_bytes_per_elem
            ),
        ))
    return rows


def pick_fleet_action(
    rows: Sequence[FleetPrediction],
    min_uplift: float = 1.0,
    budget_bytes_per_host: Optional[float] = None,
) -> Optional[FleetPrediction]:
    """The cheapest `fleet_table` row whose predicted uplift strictly
    beats ``min_uplift`` within the per-host byte budget (None =
    unbounded): rows sort by added bytes, first qualifying wins — the
    same shape as `pick_replication_k`, now choosing BETWEEN replication
    and a new host. None = nothing qualifies; keep the fleet as is."""
    best: Optional[FleetPrediction] = None
    for r in sorted(rows, key=lambda r: (r.added_bytes_per_host, r.hosts)):
        if r.action == "baseline" or r.qps_uplift <= min_uplift:
            continue
        if (budget_bytes_per_host is not None
                and r.added_bytes_per_host > budget_bytes_per_host):
            continue
        best = r
        break
    return best


def format_fleet_markdown(rows: Sequence[FleetPrediction]) -> str:
    lines = [
        "| action | hosts | top-k | dispatch ms | exchange ms | flush ms | agg QPS | uplift | added KB/host |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r.action} | {r.hosts} | {r.top_k} "
            f"| {r.dispatch_s*1e3:.3f} | {r.exchange_s*1e3:.3f} "
            f"| {r.routed_flush_s*1e3:.3f} | {r.agg_qps:.0f} "
            f"| {r.qps_uplift:.2f}x | {r.added_bytes_per_host/1e3:.1f} |"
        )
    lines.append("")
    lines.append(
        "Add-a-host vs replicate-the-head priced from the same measured "
        "coverage curve + per-owner dispatch cost: replication shrinks "
        "the exchange term (device work unchanged), a new host shrinks "
        "per-owner width but grows the H^2 all_to_all payload. "
        "added_bytes = k replica rows per host, or the new host's 1/H' "
        "shard (closure halo excluded). Measured counterpart: "
        "scripts/serve_probe.py --scale."
    )
    return "\n".join(lines)


class TierPrediction(NamedTuple):
    mix: str
    hbm_frac: float
    host_frac: float
    disk_frac: float
    gather_s: float        # host-side tiered gather per flush
    h2d_bytes: float       # cold rows shipped per flush (host + disk)
    flush_s: float         # gather + device dispatch (split path: serial)
    qps: float             # bucket / flush_s
    slowdown_vs_hbm: float # flush_s over the all-HBM flush_s
    prefetch_hit_rate: float = 0.0  # disk rows already staged at gather


def tier_table(
    mixes: Sequence[Tuple[str, float, float, float]],
    bucket: int,
    dispatch_s: float,
    hbm_row_s: float,
    host_row_s: float,
    disk_row_s: float,
    feature_dim: int = 100,
    bytes_per_elem: float = 4.0,
    read_workers: int = 4,
    prefetch_hit_rate: float = 0.0,
) -> List[TierPrediction]:
    """Price disk/DRAM/HBM HIT MIXES for the round-14 tiered serve path
    — the `scaling` face of the disk tier, answering "what does a
    placement (or a predicted hit-rate curve) cost per flush" BEFORE a
    run commits to it.

    ``mixes`` is ``[(name, f_hbm, f_host, f_disk)]`` — fractions of a
    bucket-``B`` flush's feature rows resolving in each tier. Feed it
    MEASURED attribution (``WorkloadMonitor.skew_report()['tiers']``
    normalized, or `Feature.tier_bytes` ratios) for placement-vs-
    placement comparisons, or the Che-predicted hit rate at a candidate
    DRAM capacity (``predicted_hit_rate``) for what-if rows.

    Per-row tier costs are MEASURED inputs (bench.py legs or the
    probe's in-run timings — this model invents no constants):
    ``hbm_row_s`` the amortized jitted-take cost, ``host_row_s`` the
    native DRAM gather + H2D share, and ``disk_row_s`` the
    SINGLE-THREAD flat-file read per row (bench.py
    ``tier_disk_row_single_s``; NOT the pooled ``tier_disk_row_s``,
    which already amortizes the workers — feeding it here would
    double-discount the disk term). Disk reads fan out over the
    `AsyncReadPool`'s ``read_workers``, so the model divides the
    single-thread cost by the pool width. The tiered
    gather is host-mediated (split dispatch path), so a flush costs
    ``gather + dispatch`` serially — the honest upper bound the probe's
    measured p99 is compared against.

    ``prefetch_hit_rate`` (round 18): the measured fraction of disk rows
    a flush-ahead prefetch already staged in DRAM when the gather ran
    (``tier_prefetch_hit / tier_prefetch_issued``-weighted attribution,
    or the probe's `disk_prefetched` gather share over the disk total).
    A staged row costs the DRAM-staging consume (priced at
    ``host_row_s``) instead of the pooled backing read — the column this
    knob adds is how the table prices "hide the read" against "shorten
    the read".
    """
    if bucket < 1:
        raise ValueError("bucket must be >= 1")
    if read_workers < 1:
        raise ValueError("read_workers must be >= 1")
    p = float(prefetch_hit_rate)
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"prefetch_hit_rate must be in [0, 1]: {p}")
    base = dispatch_s + bucket * hbm_row_s
    # a staged disk row is consumed from DRAM at gather time; the
    # remainder pays the pooled backing read
    disk_eff_s = (1.0 - p) * disk_row_s / read_workers + p * host_row_s
    rows: List[TierPrediction] = []
    for name, f_hbm, f_host, f_disk in mixes:
        fracs = (float(f_hbm), float(f_host), float(f_disk))
        if any(f < 0 for f in fracs) or abs(sum(fracs) - 1.0) > 1e-6:
            raise ValueError(
                f"mix {name!r} fractions must be >= 0 and sum to 1: {fracs}"
            )
        f_hbm, f_host, f_disk = fracs
        gather_s = bucket * (
            f_hbm * hbm_row_s
            + f_host * host_row_s
            + f_disk * disk_eff_s
        )
        h2d = bucket * (f_host + f_disk) * feature_dim * bytes_per_elem
        flush_s = dispatch_s + gather_s
        rows.append(
            TierPrediction(
                mix=str(name),
                hbm_frac=f_hbm,
                host_frac=f_host,
                disk_frac=f_disk,
                gather_s=gather_s,
                h2d_bytes=h2d,
                flush_s=flush_s,
                qps=bucket / flush_s if flush_s > 0 else 0.0,
                slowdown_vs_hbm=flush_s / base if base > 0 else 0.0,
                prefetch_hit_rate=p,
            )
        )
    return rows


def format_tier_markdown(rows: Sequence[TierPrediction]) -> str:
    lines = [
        "| mix | hbm | dram | disk | pf hit | gather ms | H2D KB | flush ms | QPS bound | vs all-HBM |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r.mix} | {r.hbm_frac:.0%} | {r.host_frac:.0%} "
            f"| {r.disk_frac:.0%} | {r.prefetch_hit_rate:.0%} "
            f"| {r.gather_s*1e3:.3f} "
            f"| {r.h2d_bytes/1e3:.1f} | {r.flush_s*1e3:.2f} "
            f"| {r.qps:.0f} | {r.slowdown_vs_hbm:.2f}x |"
        )
    lines.append("")
    lines.append(
        "Hit mixes priced with MEASURED per-row tier costs (bench/probe "
        "inputs; disk term divided by the read pool width). Feed measured "
        "attribution (skew_report tiers) or Che-predicted hit rates at a "
        "candidate capacity — the round-14 placement planning table. "
        "`pf hit` (round 18) is the measured flush-ahead prefetch hit "
        "rate: that fraction of disk rows is priced at the DRAM-staging "
        "consume instead of the pooled backing read."
    )
    return "\n".join(lines)


class DeltaPrediction(NamedTuple):
    name: str
    edges_per_s: float       # offered edge-arrival rate
    edges_per_commit: float  # arrivals accumulated per fenced commit
    commit_s: float          # host appends + batched device tile swap
    duty_frac: float         # commit wall over the commit period
    fence_stall_s: float     # serving stall per commit (the fenced part)
    sustainable: bool        # duty < 1 (the stream keeps up)
    # round-21 lifecycle terms (default 0: the round-17 table unchanged)
    churn_s: float = 0.0         # per-commit delete/expiry lane rewrites
    compact_amort_s: float = 0.0  # compaction wall amortized per commit
    # round-24: which commit discipline priced the stall column
    fence_mode: str = "fenced"   # "fenced" (drain) | "zerostall" (flip)


def delta_table(
    cases: Sequence[Tuple[str, float]],
    append_s_per_edge: float,
    swap_s_per_commit: float,
    commit_period_s: float = 1.0,
    delete_frac: float = 0.0,
    delete_s_per_edge: float = 0.0,
    compact_s_per_pass: float = 0.0,
    compact_every_commits: float = 0.0,
    commit_stall_us: Optional[float] = None,
    fence_mode: str = "fenced",
) -> List[DeltaPrediction]:
    """Price streaming-graph ingest (round 17) from MEASURED per-edge
    costs: "at edge rate R with a commit every ``commit_period_s``, what
    does `update_graph` cost and does the stream keep up?"

    ``cases`` is ``[(name, edges_per_s)]``. ``append_s_per_edge`` is the
    host pad-lane apply cost per edge and ``swap_s_per_commit`` the
    batched device tile-swap cost per commit — both measured by bench.py
    (``stream_append_s`` / ``stream_swap_s``); this model invents no
    constants. The whole commit runs under the update_params-style fence,
    so ``fence_stall_s`` IS the per-commit serving stall — ``duty_frac``
    (commit wall over period) is the fraction of wall the engine spends
    fenced, and a case is ``sustainable`` only while that stays below 1.
    Batching is the lever the table makes visible: the swap cost
    amortizes over ``edges_per_commit``, so longer periods trade delta
    visibility lag for lower duty.

    Round-21 lifecycle terms (all default 0 — the round-17 table is
    unchanged without them): a ``delete_frac`` of arrivals also pay
    ``delete_s_per_edge`` (the measured lane-rewrite cost of a removal
    or TTL expiry, bench ``stream_delete_s``) per commit, and a
    background compaction pass costing ``compact_s_per_pass`` (bench
    ``stream_compact_s``) every ``compact_every_commits`` commits is
    amortized into the duty — the steady-state price of a stream that
    lives forever instead of only growing.

    Round-24 zero-stall pricing: ``fence_mode="zerostall"`` decouples
    the DUTY (the commit work still costs the same host/device wall,
    it just runs off-fence) from the SERVING STALL, which collapses to
    the measured flip hold — pass it as ``commit_stall_us`` (the
    engine's ``commit_stall`` histogram mean, serve_probe
    ``--stream-stall``). With ``fence_mode="fenced"`` (default) the
    stall stays equal to the whole commit wall and ``commit_stall_us``
    is ignored — the drain-vs-flip comparison the Round-24 SCALING.md
    section tabulates.
    """
    if append_s_per_edge < 0 or swap_s_per_commit < 0:
        raise ValueError("per-edge/per-commit costs must be >= 0")
    if commit_period_s <= 0:
        raise ValueError("commit_period_s must be > 0")
    if delete_frac < 0 or delete_s_per_edge < 0 or compact_s_per_pass < 0:
        raise ValueError("lifecycle costs must be >= 0")
    if fence_mode not in ("fenced", "zerostall"):
        raise ValueError(
            f"fence_mode must be 'fenced' or 'zerostall', got {fence_mode!r}"
        )
    if fence_mode == "zerostall" and commit_stall_us is None:
        raise ValueError(
            "zerostall pricing needs the measured flip hold: pass "
            "commit_stall_us (serve_probe --stream-stall measures it)"
        )
    if commit_stall_us is not None and commit_stall_us < 0:
        raise ValueError("commit_stall_us must be >= 0")
    compact_amort = (compact_s_per_pass / compact_every_commits
                     if compact_every_commits > 0 else 0.0)
    rows: List[DeltaPrediction] = []
    for name, rate in cases:
        rate = float(rate)
        if rate < 0:
            raise ValueError(f"edge rate must be >= 0 for case {name!r}")
        per_commit = rate * commit_period_s
        churn = per_commit * delete_frac * delete_s_per_edge
        commit_s = per_commit * append_s_per_edge + swap_s_per_commit + churn
        duty = (commit_s + compact_amort) / commit_period_s
        # zero-stall: the commit WORK is unchanged (duty identical) but
        # the serving stall is the measured flip hold, not the wall
        stall_s = (commit_stall_us * 1e-6 if fence_mode == "zerostall"
                   else commit_s)
        rows.append(
            DeltaPrediction(
                name=str(name),
                edges_per_s=rate,
                edges_per_commit=per_commit,
                commit_s=commit_s,
                duty_frac=duty,
                fence_stall_s=stall_s,
                sustainable=duty < 1.0,
                churn_s=churn,
                compact_amort_s=compact_amort,
                fence_mode=fence_mode,
            )
        )
    return rows


def format_delta_markdown(rows: Sequence[DeltaPrediction]) -> str:
    lifecycle = any(r.churn_s or r.compact_amort_s for r in rows)
    zerostall = any(r.fence_mode == "zerostall" for r in rows)
    stall_col = "commit stall ms" if zerostall else "fence stall ms"
    if lifecycle:
        lines = [
            "| case | edges/s | edges/commit | commit ms | churn ms "
            f"| compact ms | {stall_col} | duty | sustainable |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
    else:
        lines = [
            f"| case | edges/s | edges/commit | commit ms | {stall_col} "
            "| duty | sustainable |",
            "|---|---|---|---|---|---|---|",
        ]
    for r in rows:
        mid = (f"| {r.churn_s*1e3:.2f} | {r.compact_amort_s*1e3:.2f} "
               if lifecycle else "")
        stall = (f"{r.fence_stall_s*1e3:.4f}" if r.fence_mode == "zerostall"
                 else f"{r.fence_stall_s*1e3:.2f}")
        lines.append(
            f"| {r.name} | {r.edges_per_s:.0f} | {r.edges_per_commit:.0f} "
            f"| {r.commit_s*1e3:.2f} {mid}"
            f"| {stall} "
            f"| {r.duty_frac:.1%} | {'yes' if r.sustainable else 'NO'} |"
        )
    lines.append("")
    lines.append(
        "Streaming-graph ingest priced from MEASURED bench legs "
        "(stream_append_s per edge, stream_swap_s per batched commit"
        + (", stream_delete_s per lane rewrite, stream_compact_s per "
           "background pass" if lifecycle else "")
        + "). "
        + ("Zero-stall commits: the commit WORK still costs the same "
           "wall (duty unchanged) but builds off-fence, so the serving "
           "stall collapses to the measured flip hold "
           "(serve_probe --stream-stall commit_stall_us). "
           if zerostall else
           "The commit runs fenced, so its wall is the per-commit "
           "serving stall; ")
        + "longer commit periods amortize the swap at the cost of "
        "delta visibility lag — the round-17 ingest planning table"
        + (" with the round-21 lifecycle churn/compaction terms."
           if lifecycle else ".")
    )
    return "\n".join(lines)


class LPPrediction(NamedTuple):
    bucket: int
    hit_rate: float            # endpoint embedding-cache hit rate
    unique_frac: float         # endpoint seeds surviving coalescing
    dispatch_s: float          # one bucket-B endpoint dispatch
    node_qps: float            # node-classification requests/s ceiling
    pairs_per_dispatch: float  # pairs retired per endpoint dispatch
    head_s: float              # pair-head cost per retired dispatch
    pair_qps: float            # LP pairs/s ceiling
    qps_ratio: float           # pair_qps / node_qps


def lp_table(
    t_node_step_s: float,
    ref_batch: int,
    head_s_per_pair: float = 0.0,
    buckets: Sequence[int] = (8, 32, 64),
    hit_rates: Sequence[float] = (0.0, 0.5, 0.9),
    unique_frac: float = 0.8,
) -> List[LPPrediction]:
    """Price PAIR-QPS against node-QPS from measured step costs (round
    19): a link-prediction request is TWO endpoint computations through
    the same serve path plus a head.

    ``t_node_step_s`` is the measured fused serve-step cost at
    ``ref_batch`` (bench.py ``serve_fused_step_s``, or the temporal leg's
    ``temporal_step_s``), scaled linearly per seed like `serve_table`;
    ``head_s_per_pair`` the measured scoring-head cost per pair (bench
    ``lp_head_s`` — one jitted dispatch per scored batch, so per pair
    it is tiny and amortized). Request algebra: of P pairs/s, each
    submits 2 endpoint requests; ``(1-hit)*unique_frac`` of those reach
    the device (endpoints of a hot candidate set hit the embedding cache
    and coalesce EXACTLY like node requests — the sharing is the whole
    design, see workloads/linkpred.py), so one bucket-B dispatch retires
    ``B / (2*(1-hit)*unique_frac)`` pairs. Temporal serving shrinks the
    effective hit rate (cache keys gain the t_bucket dimension: only
    same-window repeats hit) — feed the MEASURED temporal hit rate in,
    the table stays honest.

    The ratio column is the planning number: pair traffic costs ~2x node
    traffic at equal cache behavior, less when candidate endpoints are
    hotter than classification seeds (their hit rate is what you buy
    with a bigger cache)."""
    if t_node_step_s < 0 or head_s_per_pair < 0:
        raise ValueError("step/head costs must be >= 0")
    rows: List[LPPrediction] = []
    per_seed = t_node_step_s / max(ref_batch, 1)
    for b in buckets:
        t_dispatch = per_seed * b
        for h in hit_rates:
            miss = (1.0 - h) * unique_frac
            node_rpd = b / miss if miss > 0 else math.inf
            node_qps = node_rpd / t_dispatch if t_dispatch > 0 else math.inf
            pairs_pd = node_rpd / 2.0
            head_s = (
                0.0 if math.isinf(pairs_pd) else pairs_pd * head_s_per_pair
            )
            t_pair = t_dispatch + head_s
            pair_qps = pairs_pd / t_pair if t_pair > 0 else math.inf
            ratio = (
                0.5 if math.isinf(node_qps) and math.isinf(pair_qps)
                else pair_qps / node_qps
            )
            rows.append(
                LPPrediction(
                    bucket=b, hit_rate=h, unique_frac=unique_frac,
                    dispatch_s=t_dispatch, node_qps=node_qps,
                    pairs_per_dispatch=pairs_pd, head_s=head_s,
                    pair_qps=pair_qps, qps_ratio=ratio,
                )
            )
    return rows


def format_lp_markdown(rows: Sequence[LPPrediction]) -> str:
    lines = [
        "| bucket | cache hit | dispatch ms | node QPS | pairs/dispatch "
        "| head ms | pair QPS | pair/node |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        nq = "inf" if math.isinf(r.node_qps) else f"{r.node_qps:.0f}"
        pq = "inf" if math.isinf(r.pair_qps) else f"{r.pair_qps:.0f}"
        ppd = ("inf" if math.isinf(r.pairs_per_dispatch)
               else f"{r.pairs_per_dispatch:.0f}")
        lines.append(
            f"| {r.bucket} | {r.hit_rate:.0%} | {r.dispatch_s*1e3:.2f} "
            f"| {nq} | {ppd} | {r.head_s*1e3:.3f} | {pq} "
            f"| {r.qps_ratio:.2f}x |"
        )
    lines.append("")
    lines.append(
        "Link-prediction pricing from measured step costs (round 19): a "
        "pair = 2 endpoint lookups through the shared serve path + a "
        "batched scoring head. The pair/node ratio sits near 0.5x at "
        "equal cache behavior; hotter candidate endpoints (higher hit "
        "rate) close the gap. Measured counterpart: bench.py workloads "
        "leg + scripts/serve_probe.py --temporal."
    )
    return "\n".join(lines)


def format_skew_markdown(rows: Sequence[SkewPrediction]) -> str:
    lines = [
        "| replicated top-k | coverage | replica KB/host | exchange seeds | exchange bytes | exchange ms | routed flush ms | QPS uplift |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r.top_k} | {r.coverage:.0%} "
            f"| {r.replica_bytes_per_host/1e3:.1f} "
            f"| {r.exchange_seed_frac:.0%} | {r.exchange_bytes_frac:.0%} "
            f"| {r.exchange_s*1e3:.3f} | {r.routed_flush_s*1e3:.2f} "
            f"| {r.qps_uplift:.2f}x |"
        )
    lines.append("")
    lines.append(
        "Coverage from a measured head-concentration curve "
        "(WorkloadMonitor.skew_report — the round-13 frequency sketch); "
        "replicating the top-k keeps that request share off the serve "
        "exchange, shrinking the static lane budget pow2(bucket) -> "
        "pow2((1-coverage)*bucket). Device work is unchanged — the uplift "
        "is the wire term only (ROADMAP item 3a's predicted benefit)."
    )
    return "\n".join(lines)


def format_markdown(rows: Sequence[LayoutPrediction], step_s_1chip: float,
                    bandwidths: Optional[Dict[str, float]] = None) -> str:
    bw = dict(DEFAULT_BANDWIDTHS)
    if bandwidths:
        bw.update(bandwidths)
    lines = [
        "| layout | mesh | chips | comm ms/step | epoch s (overlap..none) | eff |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        mesh = ",".join(f"{k}={v}" for k, v in r.mesh_shape.items() if v > 1) or "1"
        lines.append(
            f"| {r.layout} | {mesh} | {r.n_devices} | {r.step_comm_s*1e3:.2f} "
            f"| {r.epoch_s_optimistic:.2f}..{r.epoch_s_pessimistic:.2f} "
            f"| {r.efficiency_pessimistic:.0%} |"
        )
    lines.append("")
    lines.append(
        f"Assumptions: single-chip step {step_s_1chip*1e3:.1f} ms (measured); "
        f"ICI {bw['ici_bytes_per_s']/1e9:.0f} GB/s/chip, "
        f"DCN {bw['dcn_bytes_per_s']/1e9:.0f} GB/s/host (ring model, "
        "see quiver_tpu/parallel/scaling.py docstring)."
    )
    return "\n".join(lines)
