"""The four-chip kind of run, rehearsed on four of the CPU's virtual devices
at a tiny test-only configuration with a root of its own (tests/qbench/tiny4):
the library's placement, its one-program step and its sample program, the
window, and the check against the host CSR, the host table and the plain
reference. With a fault planted under the step, or the step computed in
bfloat16, ``correct`` comes out false. No number of these runs is a
measurement."""

import json
import os

import pytest

from qbench import harness, limits_sharded, reduce, run
from qbench.reduce import Event, Trace

TINY4 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny4")
CELL = "tiny4-sage.train-sharded4"
NUMBERS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap"}


def _run(seed=2**31 + 99, seconds=0.3, trace=0, **overrides):
    line = run.run(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                    "--trace", str(trace)], any_device=True, root=TINY4, **overrides)
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 4
    assert "memory_peak_bytes" in out["device"]
    return out


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    out = _run()
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_seeds_per_s", "setup_s"}
    assert all(m["value"] > 0 and m["unit"] for m in out["metrics"].values())
    compared = out["compared"]
    assert NUMBERS < set(compared)
    for name in ("not_edges", "wrong_fanout", "gather_rows_differ", "unsplit_arrays",
                 "chips_off_their_share", "compiled_in_window", "nonfinite_losses",
                 "no_pairs_sampled", "weights_differ"):
        assert compared[name] == {"value": 0.0, "limit": 0.0}, name
    # the graph and the table were split four ways, the layout came from the graph
    timing = out["timing"]
    assert timing["topology_layout"] == "ShardedTopology"
    assert timing["shapes"]["features"][0] % 4 == 0
    assert timing["shapes"]["topology.indices"][0] == 4
    assert out["sizes"]["rows_padded"] == 64 * 5 * 4 * 3
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def _fake_summary(self, keep=None):
    """A CPU trace has no device plane: stand in a hand-made one of two
    chips, a step program with its all-reduce on the ops line."""
    ms = 1e6
    ops = [Event("%fusion.3 = f32[3840,24]{1,0} fusion(%p0, %all-reduce.1)", 1 * ms, 3 * ms),
           Event("%all-reduce.1 = f32[3840,24]{1,0} all-reduce(%fusion.2), channel_id=1",
                 3 * ms, 4 * ms)]
    return reduce.TraceSummary(Trace(
        {0: ops, 1: ops},
        {0: [Event("jit_sharded_topo_train_step(5)", 1 * ms, 4 * ms)]},
        [Event("qbench.train_step", 0, 5 * ms), Event("qbench.wait", 5 * ms, 10 * ms)]))


def test_traced_run_reports_per_layer_metrics_and_breakdown(monkeypatch):
    monkeypatch.setattr(harness.TraceWindow, "reduce", _fake_summary)
    out = _run(trace=1)
    # shares of a peak need the chip's peaks: off a TPU their readers find
    # nothing to read and the metrics are left out, never reported as 0
    assert set(out["metrics"]) == {"device_idle_pct.train", "host_gap_ms.train",
                                   "collective_ms.train", "comm_bytes_per_step"}
    steps = out["attempted"]
    assert out["metrics"]["collective_ms.train"]["value"] == pytest.approx(1.0 / steps)
    # the library's own model of the step's collective bytes, one count a step
    from quiver_tpu.parallel import make_mesh, sampling_comm_bytes

    model = sampling_comm_bytes(make_mesh(4, dp=1), (4, 3, 2), 64, feature_dim=24)
    assert out["metrics"]["comm_bytes_per_step"]["value"] == pytest.approx(model["total_bytes"])
    assert out["device"]["busy_s"] == pytest.approx(3e-3)
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert out["correct"] is True


@pytest.mark.parametrize("overrides,failing", [
    ({"fault": "half_batch"}, {"loss1_gap", "grad1_norm_gap"}),
    ({"fault": "state_unchanged"}, {"loss2_gap", "loss3_gap", "grad1_norm_gap",
                                    "dparam3_norm_gap"}),
    ({"compute_dtype": "bfloat16"}, {"grad1_norm_gap"})],
    ids=["half_batch", "state_unchanged", "bfloat16_control"])
def test_broken_sharded_step_is_not_correct(overrides, failing):
    out = _run(**overrides)
    assert out["correct"] is False
    failed = {k for k, c in out["compared"].items() if not c["value"] <= c["limit"]}
    assert failing <= failed <= NUMBERS, failed


def test_same_seed_same_losses_other_seed_other_losses():
    a, b, c = _run(seed=7), _run(seed=7), _run(seed=8)
    assert a["window"]["loss_first"] == b["window"]["loss_first"]
    assert a["window"]["loss_first"] != c["window"]["loss_first"]


def test_no_chips_no_result(capsys):
    with pytest.raises(SystemExit):
        run.run(["--workload", CELL, "--seed", "1", "--seconds", "0.1", "--trace", "0"],
                root=TINY4)
    assert capsys.readouterr().out == ""


def test_limits_are_read_from_the_same_cell(tmp_path):
    out = tmp_path / "limits.json"
    report = limits_sharded.main(
        ["--workload", CELL, "--seeds", "2", "--others", "1", "--out", str(out),
         "--any-device"], root=TINY4)
    assert json.loads(out.read_text())["summary"].keys() == report["summary"].keys()
    program, half = report["summary"]["program"], report["summary"]["fault_half_batch"]
    assert program["loss1_gap"]["max"] < 1e-5 < half["loss1_gap"]["min"]
    assert report["summary"]["fault_state_unchanged"]["dparam3_norm_gap"]["min"] > 0.5
    assert all(r["not_edges"] == 0 and r["gather_rows_differ"] == 0 for r in report["rows"])
