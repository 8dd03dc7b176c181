"""The whole of a run, rehearsed on the CPU at a tiny test-only configuration
(tests/qbench/tiny): it skips the harness's look for a chip and drives the
rest. The result line has the contract's keys and names the device; with the
timed path broken underneath, or computed in bfloat16, ``correct`` comes out
false. No number of these runs is a measurement."""

import json
import os

import pytest

from qbench import harness, reduce, run
from qbench.reduce import Event, Trace

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
E2E = {"train": {"train_seeds_per_s", "setup_s"},
       "serve": {"serve_p50_ms", "serve_good_rps", "setup_s"}}


def _run(cell, seed=2**31 + 77, seconds=0.4, trace=0, **overrides):
    line = run.run(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                    "--trace", str(trace)], any_device=True, root=TINY, **overrides)
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert "memory_peak_bytes" in out["device"] and "kind" in out["device"]
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"}
    return out


@pytest.mark.parametrize("cell,kind", [("tiny-sage.train-fused", "train"),
                                       ("tiny-sage.train-dedup", "train"),
                                       ("tiny-sage.serve-zipf", "serve")])
def test_untraced_run_reports_the_end_to_end_metrics(cell, kind, capsys):
    out = _run(cell, seconds=1.0 if kind == "serve" else 0.4)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == E2E[kind]
    assert all(m["value"] > 0 and m["unit"] for m in out["metrics"].values())
    assert out["compared"]["compiled_in_window"]["value"] == 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def _fake_summary(self, keep=None):
    """A CPU trace has no device plane: stand in a hand-made one, so that the
    traced path of the harness (readers, breakdown, busy_s) is driven."""
    ms = 1e6
    return reduce.TraceSummary(Trace(
        {0: [Event("fusion.3", 1 * ms, 3 * ms), Event("gather.1", 5 * ms, 6 * ms)]},
        {0: [Event("jit_train_step(5)", 1 * ms, 3 * ms),
             Event("jit__padded_gather_ordered(9)", 5 * ms, 6 * ms),
             Event("jit_tiled_sample_layer(2)", 0.2 * ms, 0.6 * ms)]},
        [Event("qbench.window", 0, 10 * ms), Event("qbench.sample_dense", 0.1 * ms, 4 * ms),
         Event("qbench.wait", 4 * ms, 10 * ms)]))


@pytest.mark.parametrize("cell,wanted", [
    ("tiny-sage.train-fused", {"device_idle_pct.train", "host_gap_ms.train",
                               "sampler_device_ms.train"}),
    ("tiny-sage.serve-zipf", {"device_idle_pct.serve", "serve_execute_ms",
                              "serve_flush_width", "gen_late_ms", "serve_p99_ms"})])
def test_traced_run_reports_per_layer_metrics_and_breakdown(cell, wanted, monkeypatch):
    monkeypatch.setattr(harness.TraceWindow, "reduce", _fake_summary)
    out = _run(cell, trace=1)
    # rooflines and mfu need the chip's peaks: off a TPU their readers find
    # nothing to read and the metrics are left out, never reported as 0
    assert set(out["metrics"]) == wanted
    assert out["device"]["busy_s"] == pytest.approx(3e-3)
    assert out["device"]["window_s"] == pytest.approx(10e-3)
    assert len(out["breakdown"]["device_ops"]) <= 10 and out["breakdown"]["idle_gaps"]
    assert out["correct"] is True


@pytest.mark.parametrize("overrides,failing", [
    ({"fault": "state_unchanged"}, {"dparam3_norm_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap"}),
    ({"fault": "half_batch"}, {"grad1_norm_gap"}),
    ({"compute_dtype": "bfloat16"}, {"grad1_norm_gap"})],
    ids=["state_unchanged", "half_batch", "bfloat16_control"])
def test_broken_train_step_is_not_correct(overrides, failing):
    out = _run("tiny-sage.train-fused", **overrides)
    assert out["correct"] is False
    failed = {k for k, c in out["compared"].items() if not c["value"] <= c["limit"]}
    assert failing <= failed, failed


@pytest.mark.parametrize("overrides", [{"fault": "answer_altered"},
                                       {"compute_dtype": "bfloat16"}],
                         ids=["answer_altered", "bfloat16_control"])
def test_broken_serve_answers_are_not_correct(overrides):
    out = _run("tiny-sage.serve-zipf", seconds=1.0, **overrides)
    assert out["correct"] is False
    assert out["compared"]["logit_gap"]["value"] > out["compared"]["logit_gap"]["limit"]


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit):
        run.run(["--workload", "tiny-sage.train-fused", "--seed", "1", "--seconds", "0.1",
                 "--trace", "0"], root=TINY)
    assert capsys.readouterr().out == ""
