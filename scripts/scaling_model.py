"""Emit the predicted multi-chip scaling table (SCALING.md + one JSON line).

Usage:
    python scripts/scaling_model.py [--step-ms 55] [--bench BENCH.json]
        [--ici-gbps 90] [--dcn-gbps 25] [--out SCALING.md]

Single-chip step time comes from --step-ms, or is pulled from a bench
artifact's e2e context (fused epoch / 193 steps) with --bench. See
quiver_tpu/parallel/scaling.py for the model and its assumptions; the
reference's measured counterpart is docs/Introduction_en.md:144-158."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--step-ms", type=float, default=None)
    ap.add_argument("--bench", default=None, help="BENCH_r*.json to read e2e from")
    ap.add_argument("--ici-gbps", type=float, default=90.0)
    ap.add_argument("--dcn-gbps", type=float, default=25.0)
    ap.add_argument("--steps-per-epoch", type=int, default=193)
    # eval-shaped serve dispatch cost (NEXT.md follow-up b): sample +
    # forward at --serve-ref-batch, measured by bench.py's serve section
    # (context serve_sample_s/serve_forward_s) or passed directly. When
    # present, serve_table prices QPS from THESE instead of the
    # pessimistic train-step bound.
    ap.add_argument("--serve-sample-ms", type=float, default=None)
    ap.add_argument("--serve-forward-ms", type=float, default=None)
    ap.add_argument("--serve-ref-batch", type=int, default=64)
    # one-vs-two-dispatch model (round 11): fixed per-execute overhead —
    # the RPC/launch floor paid once per flush on the fused serve path,
    # twice on the split path. Measured by bench.py's serve section as
    # serve_split_minus_fused_s (picked up via --bench) or passed here.
    ap.add_argument("--serve-overhead-ms", type=float, default=None)
    # distributed serving (round 10): H-host rows for the seed-ownership
    # routed engine — per-shard dispatch + DCN exchange term
    ap.add_argument("--serve-hosts", default="1,2,4,8")
    ap.add_argument("--serve-out-dim", type=int, default=47)
    # hot-shard replication what-if (round 13): head-concentration curve
    # source — a SERVE_r06 skew artifact's measured top_coverage, or an
    # analytic Zipf(alpha) curve when no artifact is given
    ap.add_argument("--tier", default=None,
                    help="TIER_r01.json tiers artifact to read measured "
                         "row costs + hit mixes from (default: analytic "
                         "placeholder costs, labeled)")
    # round-18 flush-ahead prefetch pricing: the measured fraction of
    # disk rows already staged in DRAM when the gather runs
    ap.add_argument("--tier-prefetch", default=None,
                    help="flush-ahead prefetch hit rate for the tier "
                         "table: a fraction in [0,1], or a TIER_r02.json "
                         "real-disk artifact to read the measured "
                         "median hit rate from (default: 0, labeled)")
    ap.add_argument("--skew", default=None,
                    help="SERVE_r06.json skew artifact to read the "
                         "measured head-concentration curve from")
    ap.add_argument("--skew-alpha", type=float, default=1.3,
                    help="analytic Zipf alpha for the replication table "
                         "when no --skew artifact is given")
    ap.add_argument("--skew-nodes", type=int, default=100_000)
    # round-17 streaming-graph ingest pricing (delta_table): measured
    # per-edge append + per-commit swap costs from bench.py's stream leg
    # (context stream_append_s / stream_swap_s, picked up via --bench)
    # or passed directly
    ap.add_argument("--stream-append-us", type=float, default=None,
                    help="host pad-lane apply cost per edge (us; bench "
                         "stream_append_s)")
    ap.add_argument("--stream-swap-ms", type=float, default=None,
                    help="batched device tile-swap cost per commit (ms; "
                         "bench stream_swap_s)")
    ap.add_argument("--stream-commit-s", type=float, default=1.0,
                    help="commit period for the ingest table")
    # round-21 graph lifecycle pricing: steady-state churn (deletes/TTL
    # expiry lane rewrites) + amortized background compaction on top of
    # the round-17 ingest table (delta_table's lifecycle kwargs)
    ap.add_argument("--lifecycle", action="store_true",
                    help="emit the round-21 lifecycle section (ingest "
                         "table re-priced with churn + compaction terms)")
    ap.add_argument("--stream-delete-us", type=float, default=None,
                    help="lane-rewrite cost per deleted/expired edge "
                         "(us; bench stream_delete_s)")
    ap.add_argument("--stream-compact-ms", type=float, default=None,
                    help="one background compaction pass (ms; bench "
                         "stream_compact_s)")
    ap.add_argument("--delete-frac", type=float, default=1.0,
                    help="deletions+expiries per appended edge at steady "
                         "state (1.0 = flat footprint: every append "
                         "eventually expires)")
    ap.add_argument("--compact-every-commits", type=float, default=10.0,
                    help="commits between background compaction passes")
    # round-24 zero-stall commit pricing: the drain-vs-flip comparison.
    # The stall input is MEASURED by serve_probe --stream-stall
    # (STREAM_r02.json commit_stall_us, the _seq flip hold)
    ap.add_argument("--stream-commit-stall-us", type=float, default=None,
                    help="measured zero-stall per-commit flip hold (us; "
                         "serve_probe --stream-stall commit_stall_us)")
    ap.add_argument("--fence-mode", choices=("fenced", "zerostall"),
                    default="zerostall",
                    help="commit discipline for the round-24 stall "
                         "re-pricing under --lifecycle")
    # round-19 link-prediction pricing (lp_table): measured fused
    # temporal step + per-pair head costs from bench.py's workloads leg
    # (context temporal_step_s / lp_head_s, picked up via --bench)
    ap.add_argument("--lp-step-ms", type=float, default=None,
                    help="fused temporal serve-step cost at --lp-ref-batch "
                         "(ms; bench temporal_step_s)")
    ap.add_argument("--lp-ref-batch", type=int, default=64)
    ap.add_argument("--lp-head-us", type=float, default=None,
                    help="pair scoring-head cost per pair (us; bench "
                         "lp_head_s)")
    # round 20: host-side admission cost. serve_table caps every QPS row
    # at the serial submit-path rate 1e6/host_submit_us when > 0.
    # round 22: FRONTEND_r02.json also carries host_resolve_us (the drain
    # half); the cap becomes 1e6/(host_submit_us + host_resolve_us).
    # round 23: FRONTEND_r03.json carries owner_fanout / leg_merge_us —
    # the host-mode routed-dispatch pricing inputs (concurrent owner
    # fan-out: max(legs) + merge instead of sum(legs)). --frontend takes
    # a comma-separated list so r02 (admission/drain) and r03 (fan-out)
    # artifacts can both feed one table.
    ap.add_argument("--frontend", default=None,
                    help="host submit cost: a float (us/request) or "
                         "comma-separated FRONTEND_r0*.json paths — "
                         "FRONTEND_r02.json contributes host_submit_us/"
                         "host_resolve_us, FRONTEND_r03.json contributes "
                         "owner_fanout/leg_merge_us (all measured by "
                         "scripts/bench_frontend.py)")
    ap.add_argument("--out", default=None, help="write a markdown table here")
    args = ap.parse_args()

    host_submit_us = 0.0
    host_resolve_us = 0.0
    owner_fanout = None
    leg_merge_us = 0.0
    fanout_source = None
    host_submit_source = (
        "none (analytic: no host admission cap — pass --frontend)"
    )
    if args.frontend:
        for token in args.frontend.split(","):
            token = token.strip()
            if not token:
                continue
            try:
                host_submit_us = float(token)
                host_submit_source = f"--frontend {host_submit_us}"
                continue
            except ValueError:
                pass
            with open(token) as fh:
                fr = json.load(fh)
            if "host_submit_us" in fr:
                host_submit_us = float(fr["host_submit_us"])
                host_resolve_us = float(fr.get("host_resolve_us", 0.0))
                host_submit_source = (
                    f"{token} host_submit_us (measured, "
                    "scripts/bench_frontend.py)"
                )
                if host_resolve_us:
                    host_submit_source = (
                        f"{token} host_submit_us+host_resolve_us "
                        "(measured, scripts/bench_frontend.py)"
                    )
            # round-23 r03 keys: routed-dispatch fan-out pricing
            if "owner_fanout" in fr:
                owner_fanout = int(fr["owner_fanout"])
                leg_merge_us = float(fr.get("leg_merge_us", 0.0))
                fanout_source = (
                    f"{token} owner_fanout/leg_merge_us (measured, "
                    "scripts/bench_frontend.py --r03)"
                )

    step_s = (args.step_ms or 0) / 1e3
    source = f"--step-ms {args.step_ms}"
    serve_sample_s = (args.serve_sample_ms or 0) / 1e3
    serve_forward_s = (args.serve_forward_ms or 0) / 1e3
    serve_overhead_s = (args.serve_overhead_ms or 0) / 1e3
    serve_ref_batch = args.serve_ref_batch
    serve_source = "--serve-sample-ms/--serve-forward-ms"
    if args.bench:
        with open(args.bench) as fh:
            data = json.load(fh)
        ctx = (data.get("parsed") or data).get("context", {})
        if not step_s:
            epoch = ctx.get("e2e_fused_epoch_s")
            if epoch:
                step_s = epoch / args.steps_per_epoch
                source = f"{args.bench} e2e_fused_epoch_s={epoch}"
        if not (serve_sample_s or serve_forward_s):
            if ctx.get("serve_sample_s") or ctx.get("serve_forward_s"):
                serve_sample_s = ctx.get("serve_sample_s", 0.0)
                serve_forward_s = ctx.get("serve_forward_s", 0.0)
                serve_ref_batch = ctx.get("serve_eval_ref_batch", serve_ref_batch)
                serve_source = f"{args.bench} serve_sample_s/serve_forward_s"
        if args.serve_overhead_ms is None and ctx.get("serve_split_minus_fused_s"):
            serve_overhead_s = ctx["serve_split_minus_fused_s"]
        if (args.stream_append_us is None
                and ctx.get("stream_append_s") is not None):
            args.stream_append_us = ctx["stream_append_s"] * 1e6
        if (args.stream_swap_ms is None
                and ctx.get("stream_swap_s") is not None):
            args.stream_swap_ms = ctx["stream_swap_s"] * 1e3
        if (args.stream_delete_us is None
                and ctx.get("stream_delete_s") is not None):
            args.stream_delete_us = ctx["stream_delete_s"] * 1e6
        if (args.stream_compact_ms is None
                and ctx.get("stream_compact_s") is not None):
            args.stream_compact_ms = ctx["stream_compact_s"] * 1e3
        if args.lp_step_ms is None and ctx.get("temporal_step_s") is not None:
            args.lp_step_ms = ctx["temporal_step_s"] * 1e3
        if args.lp_head_us is None and ctx.get("lp_head_s") is not None:
            args.lp_head_us = ctx["lp_head_s"] * 1e6
        if not host_submit_us and ctx.get("host_submit_us"):
            host_submit_us = float(ctx["host_submit_us"])
            host_resolve_us = float(ctx.get("host_resolve_us", 0.0))
            host_submit_source = (
                f"{args.bench} context host_submit_us (measured, "
                "bench.py serve)"
            )
    if not step_s:
        step_s = 0.0415  # PERF.md (earlier claims) round-4 measured products step (fused)
        source = "PERF.md (earlier claims) round-4 default 41.5 ms"

    from quiver_tpu.parallel.scaling import (
        delta_table,
        format_delta_markdown,
        format_lp_markdown,
        format_markdown,
        format_quant_markdown,
        format_serve_markdown,
        format_skew_markdown,
        format_tier_markdown,
        lp_table,
        products_scaling_table,
        quant_fetch_table,
        serve_table,
        skew_table,
        tier_table,
    )

    bw = {"ici_bytes_per_s": args.ici_gbps * 1e9, "dcn_bytes_per_s": args.dcn_gbps * 1e9}
    rows = products_scaling_table(
        step_s, steps_per_epoch_1chip=args.steps_per_epoch, bandwidths=bw
    )
    md = format_markdown(rows, step_s, bw)
    # per-codec quantized feature-store rows (quiver_tpu.quant): hot-cache
    # capacity multiplier + gather/H2D byte reduction at the products config
    quant_rows = quant_fetch_table((15, 10, 5), 1024, 100)
    quant_md = (
        "## Quantized feature store: per-codec capacity / byte table "
        "(products config, D=100)\n\n" + format_quant_markdown(quant_rows)
    )
    # online-serving QPS model. Preferred cost input: the EVAL-SHAPED
    # dispatch split (sample_batch + forward_logits, measured by bench.py's
    # serve section / serve_probe.py) — a serve dispatch IS that step.
    # Fallback when no split is available: the train step, pessimistic at
    # the reference batch (it additionally pays backward + update). Either
    # way the linear down-scaling to small buckets omits fixed per-dispatch
    # overhead and is optimistic there (serve_table docstring).
    if serve_sample_s or serve_forward_s:
        serve_rows = serve_table(
            serve_sample_s, 0.0, serve_forward_s, ref_batch=serve_ref_batch,
            buckets=(64, 256, 1024), hit_rates=(0.0, 0.5, 0.9),
            unique_frac=0.8, max_delay_ms=2.0,
            host_submit_us=host_submit_us,
            host_resolve_us=host_resolve_us,
        )
        serve_cost_note = (
            "Device cost per dispatch is the MEASURED eval-shaped split "
            f"(sample {serve_sample_s*1e3:.2f} ms +\nforward "
            f"{serve_forward_s*1e3:.2f} ms at batch {serve_ref_batch}; "
            f"source: {serve_source}) — the exact\nsample_batch + "
            "forward_logits stages a serve dispatch runs, no train-step "
            "proxy."
        )
    else:
        serve_rows = serve_table(
            step_s, 0.0, 0.0, ref_batch=1024, buckets=(64, 256, 1024),
            hit_rates=(0.0, 0.5, 0.9), unique_frac=0.8, max_delay_ms=2.0,
            host_submit_us=host_submit_us,
            host_resolve_us=host_resolve_us,
        )
        serve_cost_note = (
            "Device cost per dispatch is the measured TRAIN step at batch "
            "1024 (pessimistic: a serve\ndispatch runs the same sample + "
            "gather + forward but no backward/update — pass the\nmeasured "
            "split via --serve-sample-ms/--serve-forward-ms or a bench "
            "artifact with\nserve_sample_s to drop the proxy)."
        )
    serve_md = (
        "## Online serving: predicted QPS vs bucket / cache hit "
        "rate (quiver_tpu.serve)\n\n"
        + serve_cost_note
        + " Scaled linearly to each bucket (OPTIMISTIC at small\nbuckets: "
        "fixed per-dispatch overhead is omitted — see the serve_table "
        "docstring).\nThe measured counterpart with the real engine is "
        "scripts/serve_probe.py ->\nSERVE_r04.json (fused vs split, "
        "median-of-N), SERVE_r02.json (window sweep),\nSERVE_r01.json "
        "(cache/skew sweep).\n\n"
        + format_serve_markdown(serve_rows)
    )
    # one-vs-two-dispatch rows (round 11): the fixed per-execute overhead
    # paid once on the fused serve path, twice on the round-9 split path
    serve_dispatch_rows = []
    if serve_overhead_s:
        sc = (
            (serve_sample_s, serve_forward_s, serve_ref_batch)
            if (serve_sample_s or serve_forward_s)
            else (step_s, 0.0, 1024)
        )
        for dpf in (1, 2):
            serve_dispatch_rows += serve_table(
                sc[0], 0.0, sc[1], ref_batch=sc[2], buckets=(64, 256),
                hit_rates=(0.0, 0.5), unique_frac=0.8, max_delay_ms=2.0,
                dispatches_per_flush=dpf, dispatch_overhead_s=serve_overhead_s,
            )
        serve_md += (
            "\n\n### One-vs-two-dispatch (fused serve_step vs split "
            "sample+forward)\n\n"
            f"Fixed per-execute overhead {serve_overhead_s*1e3:.2f} ms "
            "(measured split-minus-fused delta\nor --serve-overhead-ms) "
            "paid once per flush fused, twice split; the win\nconcentrates "
            "at small (latency-bound) buckets.\n\n"
            + format_serve_markdown(serve_dispatch_rows)
        )
    # H-host distributed serving rows (quiver_tpu.serve.DistServeEngine):
    # same cost inputs, bucket split by seed ownership — per-shard width
    # bucket/H, the serve-shaped exchange priced at the DCN rate like the
    # training-side sampling exchange
    serve_cost = (
        (serve_sample_s, serve_forward_s, serve_ref_batch)
        if (serve_sample_s or serve_forward_s)
        else (step_s, 0.0, 1024)
    )
    dist_rows = []
    for hosts in (int(h) for h in args.serve_hosts.split(",")):
        dist_rows += serve_table(
            serve_cost[0], 0.0, serve_cost[1], ref_batch=serve_cost[2],
            buckets=(256,), hit_rates=(0.0, 0.5), unique_frac=0.8,
            max_delay_ms=2.0, hosts=hosts, out_dim=args.serve_out_dim,
            bandwidths={"dcn_bytes_per_s": args.dcn_gbps * 1e9},
            host_submit_us=host_submit_us,
            host_resolve_us=host_resolve_us,
        )
    serve_dist_md = (
        "## Distributed serving: predicted aggregate QPS vs host count "
        "(quiver_tpu.serve.dist)\n\n"
        "Seed-ownership routed engine at global bucket 256: each of H "
        "shards dispatches a\nbucket/H-wide sub-batch concurrently; one "
        "routed flush pays one shard dispatch plus\nthe serve-shaped "
        "exchange (H*H*L int32 ids out + H*H*L*C f32 logits back over "
        "DCN).\nAggregate QPS scales ~H-fold until the exchange term "
        "catches the shrinking dispatch.\nMeasured CPU-tier counterpart: "
        "scripts/serve_probe.py --hosts -> SERVE_r03.json\n(width shrink + "
        "wire bytes + in-run bit-parity; absolute QPS there shares one "
        "core).\n\n"
        + format_serve_markdown(dist_rows)
    )
    # round-23 host-mode fan-out rows: same cost inputs, routed dispatch
    # priced at ceil(H/F) * leg + merge instead of the collective
    # exchange — measured counterpart is bench_frontend.py --r03
    dist_fanout_rows = []
    if owner_fanout is not None:
        for hosts in (int(h) for h in args.serve_hosts.split(",")):
            dist_fanout_rows += serve_table(
                serve_cost[0], 0.0, serve_cost[1], ref_batch=serve_cost[2],
                buckets=(256,), hit_rates=(0.0, 0.5), unique_frac=0.8,
                max_delay_ms=2.0, hosts=hosts, out_dim=args.serve_out_dim,
                bandwidths={"dcn_bytes_per_s": args.dcn_gbps * 1e9},
                host_submit_us=host_submit_us,
                host_resolve_us=host_resolve_us,
                owner_fanout=owner_fanout, leg_merge_us=leg_merge_us,
            )
        serve_dist_md += (
            "\n\n### Host-mode concurrent owner fan-out (round 23)\n\n"
            f"Fan-out inputs: {fanout_source} — routed dispatch priced "
            f"at ceil(H/{owner_fanout}) legs\nplus a "
            f"{leg_merge_us:.0f} us join/apply merge, zero exchange "
            "bytes (direct owner legs on\nworker threads; "
            "`DistServeEngine` exchange='host'). Measured counterpart:\n"
            "scripts/bench_frontend.py --r03 -> FRONTEND_r03.json "
            "(sequential-vs-fan-out wall\nwith stall-shaped owners, "
            "bit-parity asserted in-run).\n\n"
            + format_serve_markdown(dist_fanout_rows)
        )
    # hot-shard replication table (round 13, ROADMAP item 3a): predicted
    # wire-side benefit of replicating the measured hot head on every
    # host, from the frequency sketch's head-concentration curve
    per_seed = (serve_cost[0] + serve_cost[1]) / max(serve_cost[2], 1)
    skew_bucket = 256
    skew_hosts = max(
        [int(h) for h in args.serve_hosts.split(",")] or [2]
    )
    if args.skew:
        with open(args.skew) as fh:
            skew_doc = json.load(fh)
        pts = [p for p in skew_doc.get("points", [])
               if p.get("skew_report")]
        pt = max(pts, key=lambda p: p.get("alpha", 0)) if pts else None
        cov_map = (pt or {}).get("skew_report", {}).get("top_coverage", {})
        cov = sorted((int(k), float(v)) for k, v in cov_map.items())
        skew_source = (
            f"{args.skew} measured top_coverage (alpha="
            f"{(pt or {}).get('alpha')})"
        )
    else:
        import math as _math

        harm = sum(r ** -args.skew_alpha
                   for r in range(1, args.skew_nodes + 1))
        cov, acc = [], 0.0
        ks = (64, 256, 1024, 4096)
        it = iter(ks)
        nxt = next(it)
        for r in range(1, max(ks) + 1):
            acc += r ** -args.skew_alpha / harm
            if r == nxt:
                cov.append((r, acc))
                nxt = next(it, None)
                if nxt is None:
                    break
        skew_source = (
            f"analytic Zipf(alpha={args.skew_alpha}) over "
            f"{args.skew_nodes} nodes"
        )
    skew_rows = skew_table(
        cov, hosts=skew_hosts, bucket=skew_bucket,
        out_dim=args.serve_out_dim,
        dispatch_s=per_seed * -(-skew_bucket // skew_hosts),
        bandwidths={"dcn_bytes_per_s": args.dcn_gbps * 1e9},
    )
    skew_md = (
        "## Hot-shard replication: predicted benefit from the measured "
        "access skew (round 13)\n\n"
        f"Coverage source: {skew_source}; hosts={skew_hosts}, global "
        f"bucket {skew_bucket}.\nMeasured counterpart: "
        "scripts/serve_probe.py --skew -> SERVE_r06.json "
        "(sketch-vs-exact overlap,\npredicted-vs-measured hit rate, "
        "owner imbalance).\n\n"
        + format_skew_markdown(skew_rows)
    )
    # -- round-14: disk/DRAM/HBM hit-mix pricing (tier_table) ------------
    # round-18: flush-ahead prefetch hit rate — a measured fraction (or
    # a TIER_r02 artifact carrying one) prices staged disk rows at the
    # DRAM-staging consume instead of the pooled backing read
    if args.tier_prefetch is None:
        pf_rate, pf_source = 0.0, "no prefetch (pass --tier-prefetch)"
    else:
        try:
            pf_rate = float(args.tier_prefetch)
            pf_source = f"--tier-prefetch {pf_rate}"
        except ValueError:
            with open(args.tier_prefetch) as fh:
                pf_rate = float(
                    json.load(fh)["prefetch_hit_rate_measured"]["median"]
                )
            pf_source = (f"{args.tier_prefetch} measured median "
                         "tier_prefetch hit rate")
    if args.tier:
        with open(args.tier) as fh:
            tier_doc = json.load(fh)
        cost = tier_doc["measured_row_costs_s"]
        t_cfg = tier_doc["config"]
        mixes = [("all_hbm", 1.0, 0.0, 0.0)]
        for label in ("static", "adaptive"):
            m = tier_doc[label]["runs"][-1]["gather_mix"]
            hbm, host = m.get("hbm", 0.0), m.get("host", 0.0)
            mixes.append((f"{label}_measured", hbm, host,
                          max(1.0 - hbm - host, 0.0)))
        workers = t_cfg.get("read_workers", 4)
        tier_rows = tier_table(
            mixes, bucket=t_cfg.get("max_batch", 32),
            dispatch_s=cost["dispatch_s"], hbm_row_s=cost["hbm"],
            host_row_s=cost["host"],
            disk_row_s=cost["disk_pooled"] * workers,
            feature_dim=t_cfg.get("dim", 100), read_workers=workers,
            prefetch_hit_rate=pf_rate,
        )
        tier_source = f"{args.tier} measured row costs + hit mixes"
    else:
        # labeled placeholders: page-cache-class host/disk split with a
        # 100 us cold-read per row — swap for bench.py tier_*_row_s /
        # TIER_r01.json measurements via --tier
        tier_rows = tier_table(
            [("all_hbm", 1.0, 0.0, 0.0),
             ("static_cold", 0.06, 0.14, 0.80),
             ("adapted", 0.26, 0.19, 0.55)],
            bucket=32, dispatch_s=3.5e-3, hbm_row_s=4e-6,
            host_row_s=6e-6, disk_row_s=1e-4, feature_dim=100,
            read_workers=4, prefetch_hit_rate=pf_rate,
        )
        tier_source = "analytic placeholder costs (pass --tier TIER_r01.json)"
    tier_md = (
        "## Tiered storage: disk/DRAM/HBM hit-mix pricing (round 14)\n\n"
        f"Cost source: {tier_source}.\n"
        f"Prefetch hit-rate source (round 18): {pf_source}.\n"
        "Measured counterpart: "
        "scripts/serve_probe.py --tiers -> TIER_r01.json (static vs\n"
        "sketch-driven adaptive placement, median-of-3, simulated cold-"
        "read latency\nlabeled in config) and --tiers --real-disk -> "
        "TIER_r02.json (page-cache-\ndefeated reads, mid-run hot-set "
        "shift, prefetch on/off/all-DRAM\ninterleaved median-of-3).\n\n"
        + format_tier_markdown(tier_rows)
    )
    # -- round-17: streaming-graph ingest pricing (delta_table) ----------
    # each cost labels its own provenance: one measured + one
    # placeholder input must never read as "measured" wholesale (the
    # tier/skew sections' labeling discipline), and an explicit 0 is a
    # measurement, not "unset"
    append_s = (10e-6 if args.stream_append_us is None
                else args.stream_append_us / 1e6)
    swap_s = (2e-3 if args.stream_swap_ms is None
              else args.stream_swap_ms / 1e3)
    if args.stream_append_us is not None and args.stream_swap_ms is not None:
        delta_source = "measured bench stream_append_s/stream_swap_s"
    elif args.stream_append_us is None and args.stream_swap_ms is None:
        # labeled placeholders — swap for bench.py's stream leg via
        # --bench BENCH_r*.json or the explicit flags
        delta_source = (
            "analytic placeholder costs (pass --bench or "
            "--stream-append-us/--stream-swap-ms)"
        )
    else:
        measured, missing = (
            ("stream_append_s", "stream_swap_s (placeholder 2 ms)")
            if args.stream_swap_ms is None
            else ("stream_swap_s", "stream_append_s (placeholder 10 us)")
        )
        delta_source = (
            f"measured bench {measured}; {missing} — pass both flags "
            "or --bench for a fully measured table"
        )
    delta_rows = delta_table(
        [("feed_trickle", 100), ("feed_busy", 2_000),
         ("fraud_burst", 20_000), ("ingest_storm", 200_000)],
        append_s_per_edge=append_s, swap_s_per_commit=swap_s,
        commit_period_s=args.stream_commit_s,
    )
    delta_md = (
        "## Streaming-graph ingest: delta-apply cost vs edge rate "
        "(round 17)\n\n"
        f"Cost source: {delta_source}; commit period "
        f"{args.stream_commit_s} s.\nMeasured counterpart: "
        "scripts/serve_probe.py --stream -> STREAM_r01.json (served "
        "Zipf\ntrace under live edge appends, empty-delta bit-parity, "
        "invalidation counts).\n\n"
        + format_delta_markdown(delta_rows)
    )
    # -- round-21: graph-lifecycle pricing (delta_table churn terms) -----
    lifecycle_md = None
    lifecycle_rows = []
    lifecycle_source = None
    if args.lifecycle:
        delete_s = (5e-6 if args.stream_delete_us is None
                    else args.stream_delete_us / 1e6)
        compact_s = (5e-3 if args.stream_compact_ms is None
                     else args.stream_compact_ms / 1e3)
        if (args.stream_delete_us is not None
                and args.stream_compact_ms is not None):
            lifecycle_source = (
                "measured bench stream_delete_s/stream_compact_s"
            )
        elif args.stream_delete_us is None and args.stream_compact_ms is None:
            lifecycle_source = (
                "analytic placeholder costs (pass --bench or "
                "--stream-delete-us/--stream-compact-ms)"
            )
        else:
            lifecycle_source = (
                "partially measured — pass both --stream-delete-us and "
                "--stream-compact-ms (or --bench) for a fully measured "
                "table"
            )
        lifecycle_rows = delta_table(
            [("feed_trickle", 100), ("feed_busy", 2_000),
             ("fraud_burst", 20_000), ("ingest_storm", 200_000)],
            append_s_per_edge=append_s, swap_s_per_commit=swap_s,
            commit_period_s=args.stream_commit_s,
            delete_frac=args.delete_frac,
            delete_s_per_edge=delete_s,
            compact_s_per_pass=compact_s,
            compact_every_commits=args.compact_every_commits,
        )
        lifecycle_md = (
            "## Graph lifecycle: steady-state churn + compaction "
            "(round 21)\n\n"
            f"Cost source: {lifecycle_source}; append/swap as the ingest "
            f"table above;\ndelete_frac {args.delete_frac} (deletes+TTL "
            "expiries per append — 1.0 is the\nflat-footprint regime), "
            f"compaction every {args.compact_every_commits:.0f} commits "
            "amortized into duty.\nMeasured counterpart: "
            "scripts/serve_probe.py --lifecycle -> LIFECYCLE_r01.json\n"
            "(appends+expiries at steady state under live Zipf traffic, "
            "flat reserve\noccupancy, in-run oracle parity).\n\n"
            + format_delta_markdown(lifecycle_rows)
        )
        # -- round-24: drain-vs-flip commit-stall re-pricing -------------
        if args.fence_mode == "zerostall":
            stall_us = (100.0 if args.stream_commit_stall_us is None
                        else args.stream_commit_stall_us)
            stall_source = (
                "measured serve_probe --stream-stall commit_stall_us"
                if args.stream_commit_stall_us is not None else
                "analytic placeholder flip hold (pass "
                "--stream-commit-stall-us from STREAM_r02.json)"
            )
            zerostall_rows = delta_table(
                [("feed_trickle", 100), ("feed_busy", 2_000),
                 ("fraud_burst", 20_000), ("ingest_storm", 200_000)],
                append_s_per_edge=append_s, swap_s_per_commit=swap_s,
                commit_period_s=args.stream_commit_s,
                delete_frac=args.delete_frac,
                delete_s_per_edge=delete_s,
                compact_s_per_pass=compact_s,
                compact_every_commits=args.compact_every_commits,
                commit_stall_us=stall_us,
                fence_mode="zerostall",
            )
            lifecycle_md += (
                "\n\n## Zero-stall commits: drain vs flip pricing "
                "(round 24)\n\n"
                f"Stall source: {stall_source}; churn terms as the "
                "lifecycle table above.\nThe fenced twin's per-commit "
                "stall is the whole drain+apply hold (the\nfence stall "
                "column above); zero-stall commits build off-fence and "
                "only\nhold the dispatch lock for the pointer flip, so "
                "duty is unchanged and\nthe stall column collapses to "
                "the flip hold.\nMeasured counterpart: "
                "scripts/serve_probe.py --stream-stall -> "
                "STREAM_r02.json\n(commit storm under saturated Zipf "
                "traffic, fenced-vs-zero-stall stall\nratio, on-commit "
                "p99, epoch-aware oracle parity).\n\n"
                + format_delta_markdown(zerostall_rows)
            )
    # -- round-19: link-prediction pricing (lp_table) --------------------
    lp_step_s = (2e-3 if args.lp_step_ms is None else args.lp_step_ms / 1e3)
    lp_head_s = (1e-6 if args.lp_head_us is None else args.lp_head_us / 1e6)
    if args.lp_step_ms is not None and args.lp_head_us is not None:
        lp_source = "measured bench temporal_step_s/lp_head_s"
    elif args.lp_step_ms is None and args.lp_head_us is None:
        lp_source = (
            "analytic placeholder costs (pass --bench or "
            "--lp-step-ms/--lp-head-us)"
        )
    else:
        lp_source = (
            "partially measured — pass both --lp-step-ms and "
            "--lp-head-us (or --bench) for a fully measured table"
        )
    lp_rows = lp_table(
        lp_step_s, args.lp_ref_batch, head_s_per_pair=lp_head_s,
    )
    lp_md = (
        "## Link-prediction serving: pair-QPS vs node-QPS (round 19)\n\n"
        f"Cost source: {lp_source} (ref batch {args.lp_ref_batch}).\n"
        "Measured counterpart: scripts/serve_probe.py --temporal -> "
        "WORKLOAD_r01.json\n(split-owner pairs through the exchange, "
        "temporal oracle parity in-run).\n\n"
        + format_lp_markdown(lp_rows)
    )
    print(md, file=sys.stderr)
    print("\n" + quant_md, file=sys.stderr)
    print("\n" + serve_md, file=sys.stderr)
    print("\n" + serve_dist_md, file=sys.stderr)
    print("\n" + skew_md, file=sys.stderr)
    print("\n" + tier_md, file=sys.stderr)
    print("\n" + delta_md, file=sys.stderr)
    if lifecycle_md is not None:
        print("\n" + lifecycle_md, file=sys.stderr)
    print("\n" + lp_md, file=sys.stderr)
    if args.out:
        header = (
            "# Predicted multi-chip scaling (static model)\n\n"
            "Reference publishes measured 1-4 GPU scaling "
            "(docs/Introduction_en.md:144-158: epochs 11.1 / 6.0 / 4.0 / 3.2 s);\n"
            "this table is the analytic counterpart for the TPU layouts — see\n"
            "`quiver_tpu/parallel/scaling.py` for the model, assumptions, and\n"
            "how to swap predictions for measurements on real hardware.\n"
            f"Single-chip step source: {source}.\n\n"
        )
        with open(args.out, "w") as fh:
            fh.write(
                header + md + "\n\n" + quant_md
                + "\n\n" + serve_md + "\n\n" + serve_dist_md
                + "\n\n" + skew_md + "\n\n" + tier_md + "\n\n"
                + delta_md + "\n\n"
                + ((lifecycle_md + "\n\n") if lifecycle_md else "")
                + lp_md + "\n"
            )
    print(json.dumps({
        "step_s_1chip": step_s,
        "source": source,
        "serve_cost_source": (
            serve_source if (serve_sample_s or serve_forward_s)
            else "train-step proxy"
        ),
        "serve_sample_s": serve_sample_s,
        "serve_forward_s": serve_forward_s,
        "serve_overhead_s": serve_overhead_s,
        "host_submit_us": host_submit_us,
        "host_resolve_us": host_resolve_us,
        "host_submit_source": host_submit_source,
        "rows": [r._asdict() for r in rows],
        "quant_fetch": [r._asdict() for r in quant_rows],
        "serve": [r._asdict() for r in serve_rows],
        "serve_one_vs_two_dispatch": [r._asdict() for r in serve_dispatch_rows],
        "serve_dist": [r._asdict() for r in dist_rows],
        "owner_fanout": owner_fanout,
        "leg_merge_us": leg_merge_us,
        "fanout_source": fanout_source,
        "serve_dist_fanout": [r._asdict() for r in dist_fanout_rows],
        "skew_source": skew_source,
        "skew_replication": [r._asdict() for r in skew_rows],
        "delta_source": delta_source,
        "delta_table": [r._asdict() for r in delta_rows],
        "lifecycle_source": lifecycle_source,
        "lifecycle_table": [r._asdict() for r in lifecycle_rows],
        "lp_source": lp_source,
        "lp_table": [r._asdict() for r in lp_rows],
    }))


if __name__ == "__main__":
    main()
