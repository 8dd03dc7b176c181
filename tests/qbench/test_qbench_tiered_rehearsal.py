"""The tiered kind of run, rehearsed on the CPU at a tiny test-only
configuration with a root of its own (tests/qbench/tiny1t): a quarter of the
table hot, the rest read from the host through `Feature`'s tiers and
`TrainPipeline`'s threads, the same pipeline object through the first steps
and the window, and the check against the host CSR, the host table (hot and
cold lanes both) and the plain reference. With a fault planted under the
step, or the step computed in bfloat16, ``correct`` comes out false. No
number of these runs is a measurement."""

import json
import os
import re

import pytest

from qbench import harness, limits_tiered, manifest, reduce, run
from qbench.reduce import Event, Trace

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny1t")
CELL = "tiny1t-sage.train-hot"
NUMBERS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap"}
MERGE = ("%select_select_fusion = f32[3840,24]{1,0:T(8,128)} fusion(f32[3840,24]{1,0:T(8,128)} "
         "%fusion, f32[3840,24]{1,0:T(8,128)} %fusion.1, pred[3840]{0:T(1024)(128)(4,1)S(1)} %gte.48)")
COLD = ("%fusion = f32[3840,24]{1,0:T(8,128)} fusion(f32[2048,24]{1,0:T(8,128)S(1)} %copy-done, "
        "s32[3840]{0:T(1024)S(1)} %get-tuple-element.50), kind=kCustom")
HOT = ("%fusion.1 = f32[3840,24]{1,0:T(8,128)} fusion(f32[1000,24]{1,0:T(8,128)} %hot.1, "
       "s32[3840]{0:T(1024)S(1)} %copy-done.4), kind=kCustom")
SOURCES = ("%fusion.2 = f32[2560,24]{1,0:T(8,128)} fusion(f32[3840,24]{1,0:T(8,128)} "
           "%select_select_fusion, s32[2560]{0:T(1024)S(1)} %broadcast_clamp_fusion.1), kind=kCustom")


def _run(seed=2**31 + 99, seconds=0.4, trace=0, **overrides):
    line = run.run(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                    "--trace", str(trace)], any_device=True, root=TINY, **overrides)
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and "memory_peak_bytes" in out["device"]
    return out


def test_the_cell_is_found_by_name_and_reports_every_list_less_train_metric():
    cell = manifest.load_cell(CELL, TINY)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_tiered"
    assert cell.traffic["layout"] == "flat" and cell.traffic["depth"] == 2
    names = {m["name"] for m in cell.per_layer}
    assert {"device_idle_pct.train", "host_gap_ms.train", "train_step_mfu", "cold_rows_per_step",
            "cold_gather_ms.train", "h2d_ms.train", "cold_merge_ms.train", "h2d_roofline",
            "feature_host_ms.train", "sampler_programs.train"} <= names
    for m in cell.per_layer:
        assert callable(manifest.load_reader(m["reader"]))


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    out = _run()
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"train_seeds_per_s", "setup_s"}
    compared = out["compared"]
    assert NUMBERS < set(compared)
    for name in ("not_edges", "wrong_fanout", "gather_rows_differ", "no_hot_rows_compared",
                 "no_cold_rows_compared", "cap_overflow", "cold_overflow", "compiled_in_window",
                 "nonfinite_losses", "no_pairs_sampled", "weights_differ"):
        assert compared[name] == {"value": 0.0, "limit": 0.0}, name
    # a quarter of the table is hot and lanes of both tiers were compared
    tiers = out["tiers"]
    assert tiers["hot_rows"] == 1000
    assert tiers["hot_rows_compared"] > 100 and tiers["cold_rows_compared"] > 100
    win = out["window"]
    assert 0 < win["cold_rows_per_step"] < 2048 and win["cold_overflow"] == 0
    assert out["sizes"]["rows_padded"] == 64 * 5 * 4 * 3
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def _fake_summary(self, keep=None):
    """A CPU trace has no device plane: stand in a hand-made one, the two
    programs of a step with the merge's operations on the ops line."""
    ms = 1e6
    ops = [Event(HOT, 1 * ms, 2 * ms), Event(COLD, 2 * ms, 2.5 * ms),
           Event(MERGE, 2.5 * ms, 2.75 * ms), Event(SOURCES, 2.75 * ms, 3 * ms)]
    modules = [Event("jit_sample_dense_program(3)", 0, 1 * ms),
               Event("jit_tiered_train_step(5)", 1 * ms, 4 * ms)]
    return reduce.TraceSummary(Trace(
        {0: ops}, {0: modules},
        [Event("qbench.sample_dense", 0, 1 * ms), Event("qbench.train_step", 1 * ms, 5 * ms)]))


def test_traced_run_reports_the_tiers_per_layer_metrics(monkeypatch):
    monkeypatch.setattr(harness.TraceWindow, "reduce", _fake_summary)
    out = _run(trace=1)
    assert out["correct"] is True
    # shares of a peak need the chip's peaks: off a TPU their readers find
    # nothing to read and the metrics are left out, never reported as 0
    assert set(out["metrics"]) == {
        "device_idle_pct.train", "host_gap_ms.train", "cold_rows_per_step",
        "cold_gather_ms.train", "h2d_ms.train", "cold_merge_ms.train", "sampler_host_ms.train",
        "feature_host_ms.train", "sampler_programs.train", "sampler_device_ms.train"}
    steps, m = out["attempted"], out["metrics"]
    # the library's counter, one count a step, against the pipeline's own
    assert m["cold_rows_per_step"]["value"] == pytest.approx(out["window"]["cold_rows_per_step"])
    assert 0 < m["cold_gather_ms.train"]["value"] < m["feature_host_ms.train"]["value"]
    assert m["h2d_ms.train"]["value"] > 0
    # the block's gather and the select; the hot gather and the model's are not the merge's
    assert m["cold_merge_ms.train"]["value"] == pytest.approx(0.75 / steps)
    assert m["sampler_programs.train"]["value"] == pytest.approx(1.0 / steps)  # one in the fake
    assert m["sampler_device_ms.train"]["value"] == pytest.approx(1.0 / steps)


def test_h2d_roofline_reads_the_span_against_the_stated_link(monkeypatch):
    from quiver_tpu import trace

    spec = manifest.load_json(os.path.join(manifest.HERE, "metrics", "h2d_roofline.json"))
    assert spec["params"]["link_source"] and spec["params"]["link_bytes_per_s"] > 1e9
    read = manifest.load_reader("h2d_roofline")
    ctx = {"work": {"h2d_bytes": 1e6}, "peaks": {"flops_per_s": 1.0}}
    trace.trace_report(reset=True)
    assert read(ctx, **spec["params"]) is None           # a parent commit: no such span
    monkeypatch.setenv(trace.TRACE_ENV, "1")
    trace.observe("quiver.feature.h2d", [0.001, 0.001, 0.002])
    share = read(ctx, **spec["params"])                   # 3 MB in 4 ms = 0.75 GB/s
    assert share == pytest.approx(100 * 0.75e9 / spec["params"]["link_bytes_per_s"])
    assert read(dict(ctx, peaks=None), **spec["params"]) is None  # off a TPU: never a share
    trace.trace_report(reset=True)


def test_merge_patterns_take_the_blocks_gather_and_the_select_only():
    include = manifest.load_json(os.path.join(
        manifest.HERE, "metrics", "cold_merge_ms.train.json"))["params"]["include"]
    for name, hit in ((COLD, True), (MERGE, True), (HOT, False), (SOURCES, False)):
        assert any(re.search(p, name) for p in include) is hit, name


@pytest.mark.parametrize("overrides,failing", [
    ({"fault": "half_batch"}, {"loss1_gap", "grad1_norm_gap"}),
    ({"fault": "state_unchanged"}, {"loss2_gap", "loss3_gap", "grad1_norm_gap",
                                    "dparam3_norm_gap"}),
    ({"compute_dtype": "bfloat16"}, {"grad1_norm_gap"})],
    ids=["half_batch", "state_unchanged", "bfloat16_control"])
def test_broken_tiered_step_is_not_correct(overrides, failing):
    out = _run(**overrides)
    assert out["correct"] is False
    failed = {k for k, c in out["compared"].items() if not c["value"] <= c["limit"]}
    assert failing <= failed <= NUMBERS, failed


def test_a_cold_block_too_narrow_is_counted_and_fails_the_run(monkeypatch):
    real = manifest.load_cell

    def narrow(name, root=manifest.ROOT):
        cell = real(name, root)
        return cell._replace(traffic=dict(cell.traffic, cold_cap=64))

    monkeypatch.setattr(manifest, "load_cell", narrow)
    out = _run()
    assert out["correct"] is False
    assert out["compared"]["cold_overflow"]["value"] > 0
    assert out["compared"]["gather_rows_differ"]["value"] == 0  # answered whole all the same


def test_the_kinds_table_is_graphgens_byte_for_byte(monkeypatch):
    import numpy as np

    from qbench import graphgen
    from qbench.kinds import train_tiered

    monkeypatch.setattr(train_tiered, "ADD_ROWS", 1000)
    want, want_labels = graphgen.features_and_labels(50003, 24, 7, 2**31 + 5, label_signal=1.3)
    got, labels = train_tiered.features_and_labels(50003, 24, 7, 2**31 + 5, label_signal=1.3)
    assert (got.view(np.uint32) == want.view(np.uint32)).all() and (labels == want_labels).all()


def test_the_oracle_over_the_chips_lane_rows_answers_as_the_hosts_does():
    import numpy as np

    from qbench import graphgen
    from qbench.kinds import train_sharded, train_tiered
    from quiver_tpu import CSRTopo
    from quiver_tpu.pyg import GraphSageSampler

    g = graphgen.powerlaw_graph(3001, 40000, 11, alpha=2.0, shift=0.0, max_degree=400)
    topo = CSRTopo(indptr=g.indptr, indices=g.indices)
    with pytest.raises(ValueError, match="no device layout"):
        topo.drop_host_edges()
    sampler = GraphSageSampler(topo, [3, 2], mode="TPU", layout="flat", seed=1)
    _, rows = sampler.lazy_init_quiver()
    data = train_tiered.HostData.__new__(train_tiered.HostData)
    data.graph = g
    assert data.drop_edges(topo, rows) == {"placed_edges_differ": 0}
    assert topo.indices is None and data.graph.indices is None and topo.edge_count == 40000
    nodes = np.random.default_rng(0).integers(0, 3001, 500)
    want = train_sharded.RowOracle(g.indptr, g.indices, nodes)
    got = train_tiered.DeviceRowOracle(g.indptr, rows, nodes)
    np.testing.assert_array_equal(got.keys, want.keys)
    np.testing.assert_array_equal(got.degree, want.degree)
    ds = sampler.sample_dense(np.arange(16))  # the sampler reads the chip's copy alone
    assert int(ds.count) > 16
    # a corrupted upload is counted
    data.graph = g
    bad = rows.at[3, 5].add(1)
    topo2 = CSRTopo(indptr=g.indptr, indices=g.indices)
    topo2.to_device_lane_rows()
    assert data.drop_edges(topo2, bad) == {"placed_edges_differ": 1}


def test_same_seed_same_losses_other_seed_other_losses():
    a, b, c = _run(seed=7), _run(seed=7), _run(seed=8)
    assert a["window"]["loss_first"] == b["window"]["loss_first"]
    assert a["window"]["loss_first"] != c["window"]["loss_first"]


def test_no_chips_no_result(capsys):
    with pytest.raises(SystemExit):
        run.run(["--workload", CELL, "--seed", "1", "--seconds", "0.1", "--trace", "0"],
                root=TINY)
    assert capsys.readouterr().out == ""


def test_limits_and_calibrations_are_read_from_the_same_cell(tmp_path):
    out = tmp_path / "limits.json"
    report = limits_tiered.main(
        ["--workload", CELL, "--seeds", "2", "--others", "1", "--calibrate", "4",
         "--out", str(out), "--any-device"], root=TINY)
    assert json.loads(out.read_text())["summary"].keys() == report["summary"].keys()
    program, half = report["summary"]["program"], report["summary"]["fault_half_batch"]
    assert program["loss1_gap"]["max"] < 1e-5 < half["loss1_gap"]["min"]
    assert report["summary"]["fault_state_unchanged"]["dparam3_norm_gap"]["min"] > 0.5
    assert all(r["not_edges"] == 0 and r["gather_rows_differ"] == 0
               and r["cold_rows_compared"] > 0 for r in report["rows"])
    cal = report["calibrations"]
    assert len(cal["caps"]) == 3 and cal["cold_cap"] % 4096 == 0 and report["cold_overflow"] == 0
