"""The attention kind of run (`qbench.kinds.train_gat`), rehearsed on the CPU
at a tiny test-only configuration with a root of its own (tests/qbench/tiny1g):
`train.TrainCell`'s loop over `models.GAT`, the same object through the first
steps and the window, and the check against the host CSR, the host table and
`qbench.reference_gat`. With a fault planted under the step, or the step
computed in bfloat16, ``correct`` comes out false. No number of these runs is a
measurement."""

import json
import os
import re

import pytest

from qbench import harness, limits_gat, manifest, reduce, run
from qbench.reduce import Event, Trace

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny1g")
CELL = "tiny1g-gat.train-dedup"
NUMBERS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap"}
OWN = ("model_device_ms.train", "gat_project_ms.train", "gat_project_mfu", "gat_edge_ms.train",
       "gat_edge_roofline")
# operations of the step as a device trace of the real cell names them (my chip run, PR 34)
PROJECT = ("%fusion.114 = f32[417792,512]{1,0:T(8,128)} fusion(f32[417792,1024]{1,0:T(8,128)} %x.1, "
           "f32[1024,512]{1,0:T(8,128)S(1)} %custom-call.43), kind=kOutput, calls=%fused_computation.245")
WEIGHT_GRAD = ("%fusion.83 = (f32[1024,512]{1,0:T(8,128)}, f32[1024,512]{1,0:T(8,128)}, "
               "f32[1024,512]{1,0:T(8,128)}) fusion(f32[1024,512]{1,0:T(8,128)S(1)} %custom-call.44, "
               "f32[]{:T(128)S(6)} %sub.45, f32[417792,1024]{1,0:T(8,128)} %x.1, "
               "bf16[417792,512]{1,0:T(8,128)(2,1)} %fusion.71), kind=kOutput")
FORWARD_LOOP = ("%while.14 = (s32[]{:T(128)}, f32[9,8192,512]{2,1,0:T(8,128)}, "
                "s32[9,8192,15]{1,2,0:T(8,128)}, pred[9,8192,15]{1,2,0:T(8,128)(4,1)}, "
                "f32[417792,512]{1,0:T(8,128)}) while((s32[]{:T(128)}, f32[9,8192,512]{2,1,0:T(8,128)}, "
                "s32[9,8192,15]{1,2,0:T(8,128)}) %tuple.1), condition=%cond, body=%body")
BACKWARD_LOOP = ("%while.16 = (s32[]{:T(128)}, f32[417792,512]{1,0:T(8,128)}, f32[4,128]{1,0:T(4,128)}, "
                 "s32[9,8192,15]{1,2,0:T(8,128)}, f32[9,8192,512]{2,1,0:T(8,128)}) while((s32[]{:T(128)}, "
                 "f32[417792,512]{1,0:T(8,128)}) %tuple.2), condition=%cond.1, body=%body.1")
SCATTER = ("%fusion.183 = f32[417792,512]{1,0:T(8,128)} fusion(f32[417792,512]{1,0:T(8,128)} %gte.1464, "
           "s32[122880]{0:T(1024)} %gte.1289, f32[122880,512]{1,0:T(8,128)} %bitcast.294), kind=kCustom")
SLOTS = ("%copy.295 = s32[9,8192,15]{1,2,0:T(8,128)} copy(s32[9,8192,15]{1,0,2:T(8,128)S(1)} %reshape.117)")
SECOND_LAYER = ("%while.17 = (s32[]{:T(128)}, f32[73728,76]{1,0:T(8,128)}, s32[2,5120,10]{1,2,0:T(8,128)S(1)}) "
                "while((s32[]{:T(128)}, f32[73728,76]{1,0:T(8,128)}) %tuple.3), condition=%c, body=%b")
ADAM = "%fusion.40 = (f32[512]{0:T(512)}, f32[512]{0:T(512)}) fusion(f32[512]{0:T(512)} %p), kind=kLoop"


def _run(seed=2**31 + 99, seconds=0.4, trace=0, **overrides):
    line = run.run(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                    "--trace", str(trace)], any_device=True, root=TINY, **overrides)
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and "memory_peak_bytes" in out["device"]
    return out


def test_the_cell_is_found_by_name_and_reports_the_siblings_metrics_and_its_own():
    cell = manifest.load_cell(CELL, TINY)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_gat" and cell.traffic["dedup"]
    names = {m["name"] for m in cell.per_layer}
    assert {"device_idle_pct.train", "host_gap_ms.train", "train_step_mfu", "gather_roofline",
            "sampler_device_ms.train", "sampler_host_ms.train", "feature_host_ms.train",
            "sampler_programs.train"} | set(OWN) == names
    for m in cell.per_layer:
        assert callable(manifest.load_reader(m["reader"]))


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    out = _run()
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"train_seeds_per_s", "setup_s"}
    compared = out["compared"]
    assert NUMBERS < set(compared)
    for name in ("not_edges", "wrong_fanout", "gather_rows_differ", "cap_overflow",
                 "compiled_in_window", "nonfinite_losses", "no_pairs_sampled", "weights_differ"):
        assert compared[name] == {"value": 0.0, "limit": 0.0}, name
    # what attention counts from: valid sources of each layer, targets whether or not
    # they drew a neighbour (the next layer's sources, the batch in the last)
    sizes = out["sizes"]
    assert sizes["sources"][0] == sizes["rows_valid"] and sizes["sources"][1] == sizes["targets"][0]
    assert sizes["targets"][1] == 64 and sizes["pairs"][0] > sizes["pairs"][1] > 64
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def _fake_summary(self, keep=None):
    """A CPU trace has no device plane: stand in a hand-made one, the three
    programs of a step and, on the ops line, the step's operations as the real
    cell's trace names them (a loop's body nests inside the loop's own event)."""
    ms = 1e6
    ops = [Event(PROJECT, 2.0 * ms, 2.5 * ms), Event(SLOTS, 2.5 * ms, 2.75 * ms),
           Event(FORWARD_LOOP, 3 * ms, 4 * ms), Event(SECOND_LAYER, 4 * ms, 4.5 * ms),
           Event(BACKWARD_LOOP, 5 * ms, 7 * ms), Event(SCATTER, 6 * ms, 6.5 * ms),
           Event(WEIGHT_GRAD, 7 * ms, 7.25 * ms), Event(ADAM, 7.25 * ms, 7.5 * ms)]
    modules = [Event("jit_sample_dense_program(3)", 0, 1 * ms),
               Event("jit__padded_gather(4)", 1 * ms, 2 * ms),
               Event("jit_train_step(5)", 2 * ms, 8 * ms)]
    return reduce.TraceSummary(Trace(
        {0: ops}, {0: modules},
        [Event("qbench.sample_dense", 0, 1 * ms), Event("qbench.train_step", 1 * ms, 9 * ms)]))


def test_traced_run_reports_the_attention_layers_metrics(monkeypatch):
    monkeypatch.setattr(harness.TraceWindow, "reduce", _fake_summary)
    out = _run(trace=1)
    assert out["correct"] is True
    # shares of a peak need the chip's peaks: off a TPU their readers find
    # nothing to read and the metrics are left out, never reported as 0
    assert set(out["metrics"]) == {
        "device_idle_pct.train", "host_gap_ms.train", "sampler_host_ms.train",
        "feature_host_ms.train", "sampler_programs.train", "sampler_device_ms.train",
        "model_device_ms.train", "gat_project_ms.train", "gat_edge_ms.train"}
    steps, m = out["attempted"], out["metrics"]
    assert m["model_device_ms.train"]["value"] == pytest.approx(6.0 / steps)
    assert m["sampler_device_ms.train"]["value"] == pytest.approx(1.0 / steps)
    # the projection's product and its weight gradient; not the loops, not Adam's other leaves
    assert m["gat_project_ms.train"]["value"] == pytest.approx(0.75 / steps)
    # both loops of the first layer whole and the slots' relayout before them; the scatter
    # inside the backward loop is not counted a second time, the second layer not at all
    assert m["gat_edge_ms.train"]["value"] == pytest.approx(3.25 / steps)


def test_the_shares_read_the_algorithms_work_over_the_same_operations():
    summary = _fake_summary(None)
    ctx = {"trace": summary, "units": {"steps": 2}, "work": {"project_flops": 1e9, "edge_bytes": 1e6},
           "peaks": {"flops_per_s": 1e13, "hbm_bytes_per_s": 1e10}}
    spec = {n: manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{n}.json")) for n in OWN}
    read = manifest.load_reader("roofline")
    # 1e9 FLOP a step at 1e13 FLOP/s = 0.1 ms a step, over 0.75 ms of products in 2 steps
    assert read(ctx, **spec["gat_project_mfu"]["params"]) == pytest.approx(100 * 0.1 * 2 / 0.75)
    # 1e6 B a step at 1e10 B/s = 0.1 ms a step, over 3.25 ms of per-edge operations in 2 steps
    assert read(ctx, **spec["gat_edge_roofline"]["params"]) == pytest.approx(100 * 0.1 * 2 / 3.25)
    assert read(dict(ctx, peaks=None), **spec["gat_edge_roofline"]["params"]) is None
    # a program without such operations (a parent commit, another model): nothing to read
    bare = reduce.TraceSummary(Trace({0: [Event(ADAM, 0, 1e6)]}, {0: [Event("jit_train_step(1)", 0, 1e6)]},
                                     [Event("qbench.train_step", 0, 1e6)]))
    for name in OWN[1:]:
        reader = manifest.load_reader(spec[name]["reader"])
        assert reader(dict(ctx, trace=bare), **spec[name]["params"]) is None, name


def test_the_patterns_take_the_first_layers_operations_only():
    include = {n: manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{n}.json"))[
        "params"]["include"] for n in ("gat_project_ms.train", "gat_edge_ms.train")}
    for name, project, edge in ((PROJECT, True, False), (WEIGHT_GRAD, True, False),
                                (FORWARD_LOOP, False, True), (BACKWARD_LOOP, False, True),
                                (SLOTS, False, True), (SCATTER, False, False),
                                (SECOND_LAYER, False, False), (ADAM, False, False)):
        assert any(re.search(p, name) for p in include["gat_project_ms.train"]) is project, name
        assert any(re.search(p, name) for p in include["gat_edge_ms.train"]) is edge, name


@pytest.mark.parametrize("overrides,failing", [
    ({"fault": "half_batch"}, {"loss1_gap", "grad1_norm_gap"}),
    ({"fault": "state_unchanged"}, {"loss2_gap", "loss3_gap", "grad1_norm_gap",
                                    "dparam3_norm_gap"}),
    ({"compute_dtype": "bfloat16"}, {"grad1_norm_gap"})],
    ids=["half_batch", "state_unchanged", "bfloat16_control"])
def test_broken_attention_step_is_not_correct(overrides, failing):
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
                root=TINY)
    assert capsys.readouterr().out == ""


def test_a_library_without_the_attention_sum_fails_at_once(monkeypatch):
    """What a parent commit does with this kind laid over it: an ImportError
    before any data is made."""
    from qbench.kinds import train, train_gat
    from quiver_tpu.ops import gather_sum

    monkeypatch.delattr(gather_sum, "gather_attention_sum")
    monkeypatch.setattr(train, "HostData", lambda *a: pytest.fail("data was made"))
    with pytest.raises(ImportError, match="gather_attention_sum"):
        train_gat.run(manifest.load_cell(CELL, TINY), seed=1, seconds=0.1, trace=False,
                      device={}, t_start=0.0)


def test_limits_are_read_from_the_same_cell(tmp_path):
    out = tmp_path / "limits.json"
    report = limits_gat.main(["--workload", CELL, "--seeds", "2", "--others", "1",
                              "--out", str(out), "--any-device"], root=TINY)
    assert json.loads(out.read_text())["summary"].keys() == report["summary"].keys()
    program, half = report["summary"]["program"], report["summary"]["fault_half_batch"]
    assert program["loss1_gap"]["max"] < 1e-5 < half["loss1_gap"]["min"]
    assert program["grad1_norm_gap"]["max"] < 1e-5 < report["summary"]["control_bfloat16"][
        "grad1_norm_gap"]["min"]
    assert report["summary"]["fault_state_unchanged"]["dparam3_norm_gap"]["min"] > 0.5
    assert all(r["not_edges"] == 0 and r["gather_rows_differ"] == 0 for r in report["rows"])
