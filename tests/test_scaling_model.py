"""Scaling-model sanity: the static predictor must behave like the physics
it models (reference anchor: the measured 1-4 GPU tables in
docs/Introduction_en.md:123-158, which this environment cannot measure)."""

import numpy as np
import pytest

from quiver_tpu.parallel.scaling import (
    collective_payload_bytes,
    ShapeMesh,
    comm_seconds,
    grad_psum_bytes,
    predict_layout,
    products_scaling_table,
)


STEP = 0.055  # measured single-chip products step (PERF.md (earlier claims))


def test_dp_replicated_near_linear():
    """Gradient-psum-only layout: tiny comm, so dp scaling must stay near
    linear (the reference's DDP epochs scale 11.1 -> 3.2 s at 4 GPUs =
    87% efficiency; the model should predict at least that well for the
    collective the TPU step actually runs)."""
    rows = products_scaling_table(STEP)
    dp = [r for r in rows if r.layout == "dp_replicated"]
    assert [r.n_devices for r in dp] == [1, 2, 4, 8]
    assert dp[0].epoch_s_pessimistic >= STEP * 193 * 0.99
    for r in dp[1:]:
        assert r.efficiency_pessimistic > 0.9, r
    # epochs shrink monotonically with chips
    es = [r.epoch_s_pessimistic for r in dp]
    assert es == sorted(es, reverse=True)


def test_comm_grows_with_layout_richness():
    """At the same chip count, each richer layout pays at least as much
    comm: replicated <= ici-sharded features <= sharded topology."""
    mesh = ShapeMesh(("dp", "ici"), {"dp": 2, "ici": 2})
    kw = dict(
        step_s_1chip=STEP, steps_per_epoch_1chip=193, sizes=(15, 10, 5),
        batch_per_group=1024, feature_dim=100, param_bytes=1_650_000,
    )
    a = predict_layout("dp_replicated", mesh, **kw)
    b = predict_layout("dp_ici_features", mesh, **kw)
    c = predict_layout("sharded_topology", mesh, **kw)
    assert a.step_comm_s < b.step_comm_s < c.step_comm_s
    assert b.ici_bytes > a.ici_bytes
    assert c.ici_bytes > b.ici_bytes


def test_host_axis_bytes_ride_dcn():
    """Adding a host axis must move bytes onto the DCN account, and DCN
    bytes must cost more seconds than the same bytes on ICI."""
    kw = dict(
        step_s_1chip=STEP, steps_per_epoch_1chip=193, sizes=(15, 10, 5),
        batch_per_group=1024, feature_dim=100, param_bytes=1_650_000,
    )
    single = predict_layout(
        "sharded_topology", ShapeMesh(("dp", "ici"), {"dp": 2, "ici": 2}), **kw
    )
    multi = predict_layout(
        "sharded_topology",
        ShapeMesh(("host", "dp", "ici"), {"host": 2, "dp": 2, "ici": 2}), **kw
    )
    assert single.dcn_bytes == 0.0
    assert multi.dcn_bytes > 0.0
    assert comm_seconds(0.0, 1e9) > comm_seconds(1e9, 0.0)


def test_grad_psum_ring_model():
    pb = 4_000_000
    m = ShapeMesh(("dp", "ici"), {"dp": 4, "ici": 1})
    out = grad_psum_bytes(pb, m)
    np.testing.assert_allclose(out["ici_bytes"], 2 * 3 / 4 * pb)
    assert out["dcn_bytes"] == 0.0
    m2 = ShapeMesh(("host", "dp", "ici"), {"host": 2, "dp": 2, "ici": 1})
    out2 = grad_psum_bytes(pb, m2)
    np.testing.assert_allclose(out2["dcn_bytes"], 2 * 1 / 2 * pb)


def test_caps_shrink_comm():
    """Tighter sampler caps must shrink the modeled collective payloads —
    the multichip face of the bench's tight-margin work."""
    mesh = ShapeMesh(("dp", "ici"), {"dp": 2, "ici": 2})
    kw = dict(
        step_s_1chip=STEP, steps_per_epoch_1chip=193, sizes=(15, 10, 5),
        batch_per_group=1024, feature_dim=100, param_bytes=1_650_000,
    )
    loose = predict_layout("sharded_topology", mesh, **kw)
    tight = predict_layout(
        "sharded_topology", mesh, caps=(8192, 65536, 262144), **kw
    )
    assert tight.ici_bytes < loose.ici_bytes


def test_hot_cold_tier_cuts_dcn():
    """The replicated-hot tier must cut the modeled DCN feature payload to
    the cold fraction while leaving ICI untouched — the static face of
    tests/test_hot_cold.py::test_hot_cold_dcn_reduction_at_measured_hit_rate."""
    mesh = ShapeMesh(("host", "dp", "ici"), {"host": 2, "dp": 2, "ici": 2})
    kw = dict(
        step_s_1chip=STEP, steps_per_epoch_1chip=193, sizes=(15, 10, 5),
        batch_per_group=1024, feature_dim=100, param_bytes=1_650_000,
    )
    full = predict_layout("sharded_topology", mesh, **kw)
    hc = predict_layout("sharded_topology_hot_cold", mesh, **kw)
    assert hc.ici_bytes == full.ici_bytes
    assert hc.dcn_bytes < full.dcn_bytes
    assert hc.layout == "sharded_topology_hot_cold"


def test_collective_payload_bytes_parses_tuples():
    txt = """
  %ar = (f32[16,8]{1,0}, f32[64,8]{1,0}) all-reduce(%a, %b), replica_groups={}
  %ag = bf16[128]{0} all-gather(%c), dimensions={0}
  %x = f32[4,4]{1,0} add(%y, %z)
"""
    got = collective_payload_bytes(txt)
    assert got == {
        "all-reduce": (16 * 8 + 64 * 8) * 4,
        "all-gather": 128 * 2,
    }


def test_collective_payload_bytes_async_pairs():
    """Async pairs must count the -done result only: a -start result tuple
    carries operand AND result buffers (double the payload)."""
    txt = """
  %s = (f32[64]{0}, f32[64]{0}) all-reduce-start(%a), replica_groups={}
  %d = f32[64]{0} all-reduce-done(%s)
  %gs = (f32[8,16]{1,0}, f32[64,16]{1,0}) all-gather-start(%b), dimensions={0}
  %gd = f32[64,16]{1,0} all-gather-done(%gs)
"""
    got = collective_payload_bytes(txt)
    assert got == {
        "all-reduce": 64 * 4,
        "all-gather": 64 * 16 * 4,
    }


def test_collective_payload_bytes_expected_guard():
    import pytest

    txt = "  %ag = bf16[128]{0} all-gather(%c), dimensions={0}\n"
    assert collective_payload_bytes(txt, expected=["all-gather"])
    with pytest.raises(ValueError, match="all-to-all"):
        collective_payload_bytes(txt, expected=["all-to-all"])


def test_model_matches_compiled_step():
    """Validation of the byte model against the COMPILED sharded train
    step: the all-reduce payloads XLA actually emits must equal the
    model's accounting (per-hop feature psums + gradient psum), within a
    small slack for scalars (loss pmean) and compiler strategy drift."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quiver_tpu import CSRTopo
    from quiver_tpu.datasets import synthetic_powerlaw
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.ops.sample import pad_widths
    from quiver_tpu.parallel import (
        make_mesh,
        make_sharded_train_step,
        mesh_axes,
        replicate,
        shard_feature_rows,
    )
    from quiver_tpu.pyg.sage_sampler import sample_dense_fused

    ei, feat, labels, _ = synthetic_powerlaw(2000, 16000, dim=8, classes=4, seed=0)
    topo = CSRTopo(edge_index=ei)
    mesh = make_mesh(8)
    sizes, B, D = (4, 3), 16, 8
    model = GraphSAGE(hidden_dim=16, out_dim=4, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-3)
    step = make_sharded_train_step(mesh, model, tx, sizes=sizes, pipeline="fused")

    import numpy as np

    ip = replicate(mesh, topo.indptr.astype(np.int32))
    ix = replicate(mesh, topo.indices.astype(np.int32))
    fd = shard_feature_rows(mesh, feat)
    ld = replicate(mesh, labels)
    da, _, dp = mesh_axes(mesh)
    seeds = jax.device_put(
        jnp.arange(dp * B, dtype=jnp.int32), NamedSharding(mesh, P(da))
    )
    ds0 = sample_dense_fused(
        jnp.asarray(topo.indptr.astype(np.int32)),
        jnp.asarray(topo.indices.astype(np.int32)),
        jax.random.key(0), jnp.arange(B, dtype=jnp.int32), sizes,
    )
    x0 = jnp.zeros((ds0.n_id.shape[0], D), jnp.float32)
    params = replicate(mesh, model.init(jax.random.key(1), x0, ds0.adjs))
    opt = jax.device_put(tx.init(params), NamedSharding(mesh, P()))

    # `expected` makes a silent parser miss (e.g. a new XLA async spelling)
    # raise instead of passing vacuously (round-3 ADVICE.md item 3)
    txt = step.lower(params, opt, jax.random.key(2), ip, ix, fd, ld, seeds).compile().as_text()
    measured = collective_payload_bytes(txt, expected=["all-reduce"])["all-reduce"]

    widths = pad_widths(B, sizes)
    feature_payload = (widths[0] + sum(w * k for w, k in zip(widths, sizes))) * D * 4
    param_payload = sum(
        int(np.prod(l.shape)) * 4 for l in jax.tree_util.tree_leaves(params)
    )
    predicted = feature_payload + param_payload
    # slack: loss pmean scalar + whatever small extras a compiler version
    # adds; the point is the BIG payloads match the model exactly
    assert predicted <= measured <= predicted * 1.1 + 256, (measured, predicted)


def test_serve_table_request_algebra():
    from quiver_tpu.parallel.scaling import format_serve_markdown, serve_table

    rows = serve_table(
        t_sample_s=0.01, t_gather_s=0.005, t_forward_s=0.005, ref_batch=100,
        buckets=(10, 100), hit_rates=(0.0, 0.5, 0.9), unique_frac=0.8,
        max_delay_ms=2.0,
    )
    assert len(rows) == 6
    by = {(r.bucket, r.hit_rate): r for r in rows}
    # per-seed cost 0.02/100 = 0.2ms -> bucket 10 dispatch 2ms, bucket 100 20ms
    assert by[(10, 0.0)].dispatch_s == pytest.approx(2e-3)
    assert by[(100, 0.0)].dispatch_s == pytest.approx(2e-2)
    # no cache, unique_frac 0.8: one bucket-10 dispatch retires 12.5 requests
    assert by[(10, 0.0)].requests_per_dispatch == pytest.approx(12.5)
    assert by[(10, 0.0)].qps == pytest.approx(12.5 / 2e-3)
    # hit rate 0.9 multiplies requests/dispatch (and QPS) by 10x vs 0.0
    assert by[(10, 0.9)].qps == pytest.approx(by[(10, 0.0)].qps * 10)
    # linear per-seed model: QPS ceiling is bucket-invariant...
    assert by[(100, 0.5)].qps == pytest.approx(by[(10, 0.5)].qps)
    # ...but the latency floor is not — that's the bucket trade-off
    assert by[(100, 0.5)].floor_p50_ms > by[(10, 0.5)].floor_p50_ms
    assert by[(10, 0.5)].floor_p50_ms == pytest.approx(1.0 + 2.0)
    # device time per request = dispatch_s / requests_per_dispatch
    r = by[(100, 0.5)]
    assert r.device_us_per_request == pytest.approx(
        r.dispatch_s / r.requests_per_dispatch * 1e6
    )
    md = format_serve_markdown(rows)
    assert "| bucket |" in md and md.count("\n|") >= 6


def test_serve_table_one_vs_two_dispatch_overhead():
    """The round-11 cost model: a fixed per-execute overhead is paid once
    on the fused path, twice on the split path; zero overhead reduces to
    the round-10 rows exactly."""
    from quiver_tpu.parallel.scaling import serve_table

    kw = dict(t_sample_s=0.01, t_gather_s=0.0, t_forward_s=0.01,
              ref_batch=100, buckets=(10, 100), hit_rates=(0.0,),
              unique_frac=1.0, max_delay_ms=2.0)
    base = serve_table(**kw)
    legacy = serve_table(**kw, dispatches_per_flush=2)  # zero overhead
    assert [r.dispatch_s for r in base] == [r.dispatch_s for r in legacy]
    fused = serve_table(**kw, dispatches_per_flush=1, dispatch_overhead_s=0.1)
    split = serve_table(**kw, dispatches_per_flush=2, dispatch_overhead_s=0.1)
    by_f = {r.bucket: r for r in fused}
    by_s = {r.bucket: r for r in split}
    for b in (10, 100):
        # exactly one extra overhead per flush on the split path
        assert by_s[b].dispatch_s == pytest.approx(by_f[b].dispatch_s + 0.1)
        assert by_f[b].qps > by_s[b].qps
    # the win concentrates at small buckets: relative QPS gain shrinks as
    # the per-seed term amortizes the fixed overhead away
    gain = {b: by_f[b].qps / by_s[b].qps for b in (10, 100)}
    assert gain[10] > gain[100] > 1.0
    assert by_f[10].dispatches_per_flush == 1 and by_s[10].overhead_s == 0.1
    with pytest.raises(ValueError):
        serve_table(**kw, dispatches_per_flush=0)


def test_serve_table_owner_fanout_pricing():
    """The round-23 host-mode routed term: ``owner_fanout=None`` keeps
    every row byte-identical to the collective pricing; with a fan-out
    the routed dispatch costs ceil(H/F) legs + merge and carries zero
    exchange bytes — F=1 is the sequential router's Σ(legs), F>=H is
    max(legs)."""
    from quiver_tpu.parallel.scaling import (
        format_serve_markdown,
        serve_table,
    )

    kw = dict(t_sample_s=0.01, t_gather_s=0.0, t_forward_s=0.01,
              ref_batch=100, buckets=(100,), hit_rates=(0.0,),
              unique_frac=1.0, max_delay_ms=2.0, hosts=4, out_dim=8,
              bandwidths={"dcn_bytes_per_s": 25e9})
    base = serve_table(**kw)
    default = serve_table(**kw, owner_fanout=None)
    assert [r._asdict() for r in base] == [r._asdict() for r in default]
    assert base[0].owner_fanout == 0 and base[0].leg_merge_us == 0.0

    seq = serve_table(**kw, owner_fanout=1)[0]
    fan = serve_table(**kw, owner_fanout=4)[0]
    over = serve_table(**kw, owner_fanout=8)[0]  # capped at ceil(H/F)=1
    # dispatch_s stays the per-shard leg cost; the leg count rides the
    # flush wall (qps + latency floor). F=1 pays all H legs serially,
    # F>=H pays exactly one.
    assert seq.dispatch_s == pytest.approx(fan.dispatch_s)
    assert fan.qps == pytest.approx(seq.qps * 4)
    assert over.qps == pytest.approx(fan.qps)
    assert (seq.floor_p50_ms - fan.floor_p50_ms
            == pytest.approx(3 * fan.dispatch_s * 1e3))
    # routed legs ship no collective payload
    assert fan.exchange_bytes == 0.0 and fan.exchange_s == 0.0
    assert base[0].exchange_bytes > 0.0
    # the merge term is additive on the flush wall
    merged = serve_table(**kw, owner_fanout=4, leg_merge_us=500.0)[0]
    assert (merged.floor_p50_ms - fan.floor_p50_ms
            == pytest.approx(0.5))
    assert merged.qps < fan.qps
    assert merged.leg_merge_us == 500.0 and merged.owner_fanout == 4
    # hosts=1 never prices a fan-out (there is one leg, no merge)
    one = serve_table(**{**kw, "hosts": 1}, owner_fanout=4,
                      leg_merge_us=500.0)[0]
    assert one.owner_fanout == 0 and one.leg_merge_us == 0.0
    md = format_serve_markdown([seq, fan, merged])
    assert "round 23" in md and "owner_fanout=1" in md


def test_median_min_max():
    from quiver_tpu.trace import median_min_max

    s = median_min_max([3.0, 1.0, 2.0])
    assert s == {"median": 2.0, "min": 1.0, "max": 3.0, "n": 3}
    assert median_min_max([4, 1, 3, 2])["median"] == pytest.approx(2.5)
    assert median_min_max([7])["median"] == 7.0
    with pytest.raises(ValueError):
        median_min_max([])


def test_pick_replication_k_smallest_qualifying_row():
    from quiver_tpu.parallel.scaling import pick_replication_k, skew_table

    rows = skew_table(
        [(1, 0.2), (8, 0.5), (64, 0.9)], hosts=2, bucket=64, out_dim=8,
        dispatch_s=1e-3, feature_dim=100,
        bandwidths={"dcn_bytes_per_s": 1e8},  # slow wire: uplift is real
    )
    pick = pick_replication_k(rows, min_uplift=1.0)
    assert pick is not None
    # smallest k whose uplift clears the bar, not the biggest uplift
    qualifying = [r for r in rows if r.qps_uplift > 1.0]
    assert pick.top_k == min(r.top_k for r in qualifying)
    # a byte budget below every row's replica cost finds nothing
    assert pick_replication_k(rows, replica_budget_bytes=1.0) is None
    # hosts=1 rows (no exchange to avoid) never qualify
    rows1 = skew_table([(8, 0.5)], hosts=1, bucket=64, out_dim=8,
                       dispatch_s=1e-3)
    assert pick_replication_k(rows1) is None


def test_fleet_table_prices_add_host_vs_replicate():
    from quiver_tpu.parallel.scaling import (
        fleet_table, format_fleet_markdown, pick_fleet_action,
    )

    rows = fleet_table(
        [(8, 0.5), (64, 0.9)], hosts=2, bucket=64, out_dim=8,
        dispatch_s=1e-3, table_rows=2000, feature_dim=100,
        add_hosts=(1, 2),
        bandwidths={"dcn_bytes_per_s": 1e8},  # slow wire: terms are real
    )
    by_action = {}
    for r in rows:
        by_action.setdefault(r.action, []).append(r)
    base = by_action["baseline"][0]
    assert base.qps_uplift == 1.0 and base.added_bytes_per_host == 0.0
    # replication: device work unchanged, exchange shrinks with coverage
    for r in by_action["replicate top-k"]:
        assert r.dispatch_s == base.dispatch_s
        assert r.exchange_s <= base.exchange_s
        assert r.added_bytes_per_host == r.top_k * 100 * 4.0
    # add-host: per-owner dispatch shrinks, H^2 wire term grows
    add = {r.hosts: r for r in by_action["add host"]}
    assert add[3].dispatch_s < base.dispatch_s
    assert add[4].dispatch_s < add[3].dispatch_s
    assert add[4].exchange_s > base.exchange_s  # the quadratic payload
    assert add[3].added_bytes_per_host == pytest.approx(
        2000 / 3 * 100 * 4.0
    )
    # the picker returns the cheapest qualifying uplift within budget
    pick = pick_fleet_action(rows, min_uplift=1.0)
    assert pick is not None and pick.action != "baseline"
    qualifying = [r for r in rows
                  if r.action != "baseline" and r.qps_uplift > 1.0]
    assert pick.added_bytes_per_host == min(
        r.added_bytes_per_host for r in qualifying
    )
    # a per-host byte budget below every option finds nothing
    assert pick_fleet_action(rows, budget_bytes_per_host=1.0) is None
    md = format_fleet_markdown(rows)
    assert "add host" in md and "replicate top-k" in md


def test_delta_table_prices_streaming_ingest():
    """Round-17 ingest pricing: duty scales linearly in the edge rate on
    top of the fixed per-commit swap floor, longer commit periods
    amortize the swap, and `sustainable` flips exactly at duty 1."""
    from quiver_tpu.parallel.scaling import delta_table, format_delta_markdown

    append_s, swap_s = 2e-6, 5e-3
    rows = delta_table(
        [("idle", 0.0), ("feed", 1e3), ("storm", 1e5)],
        append_s_per_edge=append_s, swap_s_per_commit=swap_s,
        commit_period_s=1.0,
    )
    idle, feed, storm = rows
    # rate 0 still pays the swap floor — the fence stall is never free
    assert idle.commit_s == pytest.approx(swap_s)
    assert idle.fence_stall_s == idle.commit_s
    # linear in rate above the floor
    assert feed.commit_s == pytest.approx(swap_s + 1e3 * append_s)
    assert storm.edges_per_commit == pytest.approx(1e5)
    assert all(r.sustainable for r in rows)
    # a longer period amortizes the swap: duty strictly drops
    amortized = delta_table([("storm", 1e5)], append_s, swap_s,
                            commit_period_s=10.0)[0]
    assert amortized.duty_frac < storm.duty_frac
    assert amortized.fence_stall_s > storm.fence_stall_s  # the trade
    # sustainability flips exactly where append work alone fills the wall
    over = delta_table([("melt", 1.1 / append_s)], append_s, swap_s)[0]
    assert not over.sustainable and over.duty_frac > 1.0
    with pytest.raises(ValueError):
        delta_table([("x", -1.0)], append_s, swap_s)
    with pytest.raises(ValueError):
        delta_table([("x", 1.0)], append_s, swap_s, commit_period_s=0.0)
    md = format_delta_markdown(rows)
    assert "storm" in md and "sustainable" in md


def test_delta_table_commit_stall_pricing():
    """Round-24 drain-vs-flip pricing: fence_mode="zerostall" keeps the
    commit WORK (duty) identical — the build just runs off-fence — and
    collapses the serving stall to the measured flip hold."""
    from quiver_tpu.parallel.scaling import delta_table, format_delta_markdown

    append_s, swap_s = 2e-6, 5e-3
    cases = [("idle", 0.0), ("feed", 1e3), ("storm", 1e5)]
    fenced = delta_table(cases, append_s, swap_s, commit_period_s=1.0)
    zs = delta_table(cases, append_s, swap_s, commit_period_s=1.0,
                     commit_stall_us=1.2, fence_mode="zerostall")
    for f, z in zip(fenced, zs):
        # same work, same sustainability frontier...
        assert z.commit_s == pytest.approx(f.commit_s)
        assert z.duty_frac == pytest.approx(f.duty_frac)
        assert z.sustainable == f.sustainable
        # ...but the stall is the flip hold, decoupled from edge rate
        assert z.fence_stall_s == pytest.approx(1.2e-6)
        assert f.fence_stall_s == pytest.approx(f.commit_s)
        assert z.fence_mode == "zerostall" and f.fence_mode == "fenced"
    # the fenced stall grows with rate; the zero-stall one does not
    assert fenced[2].fence_stall_s > fenced[1].fence_stall_s
    assert zs[2].fence_stall_s == zs[1].fence_stall_s
    # zerostall pricing demands a measurement — no invented constants
    with pytest.raises(ValueError):
        delta_table(cases, append_s, swap_s, fence_mode="zerostall")
    with pytest.raises(ValueError):
        delta_table(cases, append_s, swap_s, fence_mode="zerostall",
                    commit_stall_us=-1.0)
    with pytest.raises(ValueError):
        delta_table(cases, append_s, swap_s, fence_mode="drain")
    # fenced mode ignores a stray commit_stall_us (stall == wall)
    stray = delta_table(cases, append_s, swap_s, commit_stall_us=99.0)
    assert stray[1].fence_stall_s == pytest.approx(stray[1].commit_s)
    # flip hold renders at µs precision (1.2 µs -> 0.0012 ms)
    md = format_delta_markdown(zs)
    assert "commit stall ms" in md and "0.0012" in md
