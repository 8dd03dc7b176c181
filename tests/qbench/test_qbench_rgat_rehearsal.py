"""The relational attention kind of run (`qbench.kinds.train_rgat`), rehearsed
on the CPU at a tiny test-only configuration with a root of its own
(tests/qbench/tiny1h): `train.TrainCell`'s loop over a `HeteroSampler` and
`models.RGAT`, the same object through the first steps and the window, and
the check against the host CSRs, the host table and `qbench.reference_rgat`.
With a fault planted under the step, or the step computed in bfloat16,
``correct`` comes out false. No number of these runs is a measurement."""

import json
import os
import re

import pytest

from qbench import harness, limits_rgat, manifest, reduce, run
from qbench.reduce import Event, Trace

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny1h")
CELL = "tiny1h-rgat.train-dedup"
NUMBERS = {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap", "dparam3_norm_gap"}
OWN = ("rgat_project_mfu", "rgat_edge_ms.train", "rgat_edge_roofline", "typed_slot_valid_pct")
# operations of the step as the TPU's compiler names them at the real cell's shapes
# (tests/test_tpu_compile.py, PR 38), in the trace's form: operands with their shapes
PROJECT = ("%fusion.282 = f32[491520,512]{1,0:T(8,128)} fusion(f32[1024,512]{1,0:T(8,128)S(1)} "
           "%copy-done.201, bf16[1011712,1024]{1,0:T(8,128)(2,1)} %x.1), kind=kOutput")
WEIGHT_GRAD = ("%fusion.633 = (f32[1024,512]{1,0:T(8,128)}, f32[1024,512]{1,0:T(8,128)}, "
               "f32[1024,512]{1,0:T(8,128)}) fusion(f32[1024,512]{1,0:T(8,128)S(1)} %copy-done.178, "
               "f32[]{:T(128)S(6)} %sub.269, bf16[1011712,1024]{1,0:T(8,128)(2,1)} %x.1), kind=kOutput")
FORWARD_LOOP = ("%while.13 = (s32[]{:T(128)}, f32[16,7936,512]{2,1,0:T(8,128)}, "
                "s32[16,7936,5]{1,2,0:T(8,128)}, pred[16,7936,5]{1,2,0:T(8,128)(4,1)}, "
                "f32[16,7936,4]{1,2,0:T(4,128)S(1)}, /*index=5*/f32[491520,512]{1,0:T(8,128)}, "
                "f32[4,128]{1,0:T(4,128)S(1)}) while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b")
BACKWARD_LOOP = ("%while.22 = (s32[]{:T(128)}, f32[491520,512]{1,0:T(8,128)}, f32[4,128]{1,0:T(4,128)}, "
                 "f32[5,8192,4]{1,2,0:T(4,128)}, s32[5,8192,5]{1,2,0:T(8,128)}) while((s32[]{:T(128)}) "
                 "%tuple.2), condition=%c.1, body=%b.1")
SECOND_LAYER = ("%fusion.379 = f32[40960,512]{0,1:T(8,128)S(1)} fusion(f32[512,512]{1,0:T(8,128)} "
                "%p, f32[40960,512]{1,0:T(8,128)} %q), kind=kOutput")
ADAM = "%fusion.40 = (f32[512]{0:T(512)}, f32[512]{0:T(512)}) fusion(f32[512]{0:T(512)} %p), kind=kLoop"


def _run(seed=2**31 + 99, seconds=0.4, trace=0, **overrides):
    line = run.run(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                    "--trace", str(trace)], any_device=True, root=TINY, **overrides)
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and "memory_peak_bytes" in out["device"]
    return out


def test_the_cell_is_found_by_name_and_reports_the_train_metrics_and_its_own():
    cell = manifest.load_cell(CELL, TINY)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_rgat"
    names = {m["name"] for m in cell.per_layer}
    assert {"device_idle_pct.train", "host_gap_ms.train", "train_step_mfu", "gather_roofline",
            "sampler_device_ms.train", "sampler_host_ms.train", "feature_host_ms.train",
            "sampler_programs.train", "model_device_ms.train", "host_tick_late_ms.train",
            "host_stall_max_ms.train"} | set(OWN) == names
    for m in cell.per_layer:
        assert callable(manifest.load_reader(m["reader"]))


def test_untraced_run_reports_the_end_to_end_metrics(capsys):
    out = _run()
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3
    assert set(out["metrics"]) == {"train_seeds_per_s", "setup_s"}
    compared = out["compared"]
    assert NUMBERS < set(compared)
    for name in ("not_edges", "wrong_fanout", "not_unique", "gather_rows_differ", "cap_overflow",
                 "compiled_in_window", "nonfinite_losses", "no_pairs_sampled", "weights_differ"):
        assert compared[name] == {"value": 0.0, "limit": 0.0}, name
    # what the work is counted from: 7 relations in the first layer, 6 and 3 after
    layers = out["sizes"]["layers"]
    assert [len(rels) for rels in layers] == [7, 6, 3]
    assert all(d_in == 24 for d_in, *_ in layers[0]) and all(r[0] == 12 for r in layers[2])
    assert all(pairs > 0 for rels in layers for *_, pairs in rels)
    # no tiny segment spans two attention blocks: no relation runs as a loop
    assert out["sizes"]["looped"] == [[False] * len(rels) for rels in layers]
    # the leaf behind each norm gap is named
    assert set(out["worst_leaf"]) == {"grad1", "dparam3"}
    assert all(leaf.startswith("params/") for leaf in out["worst_leaf"].values()), out["worst_leaf"]
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ") and "limit" in err[-1]


def _fake_summary(self, keep=None):
    """A CPU trace has no device plane: stand in a hand-made one, the three
    programs of a step and, on the ops line, the step's operations as the
    real cell's program names them (a loop's body nests inside the loop)."""
    ms = 1e6
    ops = [Event(PROJECT, 2.0 * ms, 2.5 * ms), Event(FORWARD_LOOP, 3 * ms, 4 * ms),
           Event(SECOND_LAYER, 4 * ms, 4.5 * ms), Event(BACKWARD_LOOP, 5 * ms, 7 * ms),
           Event(WEIGHT_GRAD, 7 * ms, 7.25 * ms), Event(ADAM, 7.25 * ms, 7.5 * ms)]
    modules = [Event("jit_hetero_sample_program(3)", 0, 1 * ms),
               Event("jit__padded_gather(4)", 1 * ms, 2 * ms),
               Event("jit_train_step(5)", 2 * ms, 8 * ms)]
    return reduce.TraceSummary(Trace(
        {0: ops}, {0: modules},
        [Event("qbench.sample_dense", 0, 1 * ms), Event("qbench.train_step", 1 * ms, 9 * ms)]))


def test_traced_run_reports_the_typed_layers_metrics(monkeypatch):
    monkeypatch.setattr(harness.TraceWindow, "reduce", _fake_summary)
    out = _run(trace=1)
    assert out["correct"] is True
    # shares of a peak need the chip's peaks: off a TPU their readers find
    # nothing to read and the metrics are left out, never reported as 0
    assert set(out["metrics"]) == {
        "device_idle_pct.train", "host_gap_ms.train", "sampler_host_ms.train",
        "feature_host_ms.train", "sampler_programs.train", "sampler_device_ms.train",
        "model_device_ms.train",
        "rgat_edge_ms.train", "typed_slot_valid_pct", "host_tick_late_ms.train",
        "host_stall_max_ms.train"}
    steps, m = out["attempted"], out["metrics"]
    assert m["sampler_device_ms.train"]["value"] == pytest.approx(1.0 / steps)
    assert m["model_device_ms.train"]["value"] == pytest.approx(6.0 / steps)
    # both loops whole, nothing else
    assert m["rgat_edge_ms.train"]["value"] == pytest.approx(3.0 / steps)
    assert 0 < m["typed_slot_valid_pct"]["value"] < 100


def test_the_shares_read_the_algorithms_work_over_the_same_operations():
    summary = _fake_summary(None)
    ctx = {"trace": summary, "units": {"steps": 2}, "work": {"project_flops": 1e9, "edge_bytes": 1e6},
           "peaks": {"flops_per_s": 1e13, "hbm_bytes_per_s": 1e10}}
    spec = {n: manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{n}.json")) for n in OWN}
    read = manifest.load_reader("roofline")
    # 1e9 FLOP a step at 1e13 FLOP/s = 0.1 ms a step, over 0.75 ms of products in 2 steps
    assert read(ctx, **spec["rgat_project_mfu"]["params"]) == pytest.approx(100 * 0.1 * 2 / 0.75)
    # 1e6 B a step at 1e10 B/s = 0.1 ms a step, over 3 ms of loops in 2 steps
    assert read(ctx, **spec["rgat_edge_roofline"]["params"]) == pytest.approx(100 * 0.1 * 2 / 3.0)
    # a program without such operations (a parent commit, another model): nothing to read
    bare = reduce.TraceSummary(Trace({0: [Event(ADAM, 0, 1e6)]}, {0: [Event("jit_train_step(1)", 0, 1e6)]},
                                     [Event("qbench.train_step", 0, 1e6)]))
    for name in OWN[:3]:
        reader = manifest.load_reader(spec[name]["reader"])
        assert reader(dict(ctx, trace=bare), **spec[name]["params"]) is None, name


def test_the_edge_bytes_count_the_relations_whose_loops_are_timed_alone():
    """`rgat_edge_roofline` times the attention `while` loops; a relation of
    one block of targets has none (the compiler unrolls it), so its bytes
    are left out of the share's numerator as its time is of the denominator."""
    from qbench import work_rgat
    from qbench.kinds import train_rgat

    cfg = manifest.load_cell(CELL, TINY).config
    layers = [[[24, 90.0, 40.0, 300.0], [24, 50.0, 30.0, 120.0]], [[12, 40.0, 8.0, 30.0]]]
    sizes = {"layers": layers, "looped": [[True, False], [False]], "rows_valid": 10.0}
    work = train_rgat.work_of(cfg, sizes, 8)
    width = cfg["hidden_dim"]
    assert work["edge_bytes"] == work_rgat.edge_bytes([[tuple(layers[0][0])], []], width, True)
    every = dict(sizes, looped=[[True, True], [True]])
    assert train_rgat.work_of(cfg, every, 8)["edge_bytes"] == work_rgat.edge_bytes(
        [[tuple(r) for r in rels] for rels in layers], width, True)
    # the step's and the projections' work count every relation whatever it loops
    assert train_rgat.work_of(cfg, every, 8)["step_flops"] == work["step_flops"]


def test_the_patterns_take_the_first_layers_products_and_every_attention_loop():
    include = {n: manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{n}.json"))[
        "params"]["include"] for n in ("rgat_project_mfu", "rgat_edge_ms.train")}
    for name, project, edge in ((PROJECT, True, False), (WEIGHT_GRAD, True, False),
                                (FORWARD_LOOP, False, True), (BACKWARD_LOOP, False, True),
                                (SECOND_LAYER, False, False), (ADAM, False, False)):
        assert any(re.search(p, name) for p in include["rgat_project_mfu"]) is project, name
        assert any(re.search(p, name) for p in include["rgat_edge_ms.train"]) is edge, name


@pytest.mark.parametrize("overrides,failing", [
    ({"fault": "half_batch"}, {"loss1_gap", "grad1_norm_gap"}),
    ({"fault": "state_unchanged"}, {"loss2_gap", "loss3_gap", "grad1_norm_gap",
                                    "dparam3_norm_gap"}),
    ({"compute_dtype": "bfloat16"}, {"grad1_norm_gap"})],
    ids=["half_batch", "state_unchanged", "bfloat16_control"])
def test_broken_relational_step_is_not_correct(overrides, failing):
    out = _run(**overrides)
    assert out["correct"] is False
    failed = {k for k, c in out["compared"].items() if not c["value"] <= c["limit"]}
    assert failing <= failed <= NUMBERS, failed


def test_same_seed_same_losses_other_seed_other_losses():
    a, b, c = _run(seed=7), _run(seed=7), _run(seed=8)
    assert a["window"]["loss_first"] == b["window"]["loss_first"]
    assert a["window"]["loss_first"] != c["window"]["loss_first"]


def test_a_library_without_typed_sampling_fails_at_once(monkeypatch):
    """What a parent commit does with this kind laid over it: an ImportError
    before any data is made."""
    import sys

    from qbench.kinds import train_rgat

    monkeypatch.setitem(sys.modules, "quiver_tpu.pyg.hetero_sampler", None)
    monkeypatch.setattr(train_rgat, "HostData", lambda *a: pytest.fail("data was made"))
    with pytest.raises(ImportError):
        train_rgat.run(manifest.load_cell(CELL, TINY), seed=1, seconds=0.1, trace=False,
                       device={}, t_start=0.0)


def test_limits_are_read_from_the_same_cell(tmp_path):
    out = tmp_path / "limits.json"
    report = limits_rgat.main(["--workload", CELL, "--seeds", "2", "--others", "1",
                               "--out", str(out), "--any-device"], root=TINY)
    assert json.loads(out.read_text())["summary"].keys() == report["summary"].keys()
    program, half = report["summary"]["program"], report["summary"]["fault_half_batch"]
    assert program["loss1_gap"]["max"] < 1e-5 < half["loss1_gap"]["min"]
    assert program["grad1_norm_gap"]["max"] < 1e-5 < report["summary"]["control_bfloat16"][
        "grad1_norm_gap"]["min"]
    assert report["summary"]["fault_state_unchanged"]["dparam3_norm_gap"]["min"] > 0.5
    assert all(r["not_edges"] == 0 and r["not_unique"] == 0 for r in report["rows"])
    assert all(r["grad1_worst_leaf"].startswith("params/") for r in report["rows"])
