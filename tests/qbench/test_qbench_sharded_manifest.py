"""The four-chip cell and what it added, found by name alone; and EVERY
assertion of the two tests of the first benchmark that tests/conftest.py
expects to fail (`OUTGROWN`), for every cell of the benchmark, in a form that
the next cell, kind or metric keeps: only the literal list of kinds
(``("train", "serve")``) and the place of PR 26's seven metrics at the very
end of ``per_layer`` are left out, because a new kind and an appended metric
are what an addition is."""

import os
import re

import pytest

from qbench import manifest
from qbench.reduce import Event, Trace, TraceSummary
from quiver_tpu import trace as qtrace

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
TINY4 = os.path.join(manifest.ROOT, "tests", "qbench", "tiny4")
CELL = "papers100M-sage.train-sharded4"
OWN = ("collective_ms.train", "exchange_roofline", "comm_bytes_per_step",
       "shard_sample_ms.train", "shard_gather_ms.train")
KINDS = sorted(f[:-3] for f in os.listdir(os.path.join(manifest.HERE, "kinds"))
               if f.endswith(".py") and f != "__init__.py")
# PR 26's metrics and the library span each reads (test_qbench_program_names.py)
SCOPE_METRICS = {
    "sampler_host_ms.train": "quiver.sample",
    "feature_host_ms.train": "quiver.feature.lookup",
    "serve_submit_ms": "quiver.serve.submit",
    "serve_queue_ms": "quiver.serve.queue",
    "serve_device_wait_ms": "quiver.serve.device",
    "serve_resolve_ms": "quiver.serve.resolved",
}


def cells_of(root):
    bench = manifest.load_json(os.path.join(root, "BENCHMARK.json"))
    return [(root, w["name"]) for w in bench["workloads"]]


@pytest.mark.parametrize("root,cell", [(manifest.ROOT, CELL),
                                       (TINY4, "tiny4-sage.train-sharded4")],
                         ids=["benchmark", "tiny4"])
def test_a_four_chip_cell_loads_by_name(root, cell):
    loaded = manifest.load_cell(cell, root)
    assert loaded.chips == 4 and loaded.traffic["kind"] == "train_sharded"
    assert loaded.traffic["dp"] == 1 and loaded.traffic["pipeline"] == "fused"
    assert callable(manifest.load_kind(loaded.traffic["kind"]).run)
    assert {m["name"] for m in loaded.end_to_end} == {"train_seeds_per_s", "setup_s"}
    names = {m["name"] for m in loaded.per_layer}
    assert set(OWN) <= names and "device_idle_pct.train" in names
    # one program a step: no sampler program and no gather program to read
    assert not names & {"sampler_device_ms.train", "gather_roofline",
                        "sampler_programs.train", "sampler_host_ms.train"}
    for m in loaded.per_layer:
        assert callable(manifest.load_reader(m["reader"]))


ALL_CELLS = cells_of(manifest.ROOT) + cells_of(TINY4)


@pytest.mark.parametrize("root,name", ALL_CELLS, ids=[name for _, name in ALL_CELLS])
def test_every_cell_loads_by_name(root, name):
    """test_qbench_manifest.py::test_every_cell_loads_by_name, every assertion,
    the kind held against the files of qbench/kinds instead of two names."""
    cell = manifest.load_cell(name, root)
    assert cell.traffic["kind"] in KINDS
    assert callable(manifest.load_kind(cell.traffic["kind"]).run)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert callable(manifest.load_reader(m["reader"]))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_pr26s_metrics_load_for_the_cells_that_report_what_they_move():
    """test_qbench_program_names.py::test_new_metrics_load_for_the_cells_that_
    report_what_they_move, every assertion: the seven still stand together in
    the order they were appended (what later PRs appended comes after them),
    and they list every cell that reports what they move and runs the spans'
    code: all of them but the one-program sharded steps, which go through
    neither `GraphSageSampler.sample_dense` nor `Feature.lookup_padded`."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    sharded = {w["name"] for w in BENCH["workloads"]
               if manifest.load_cell(w["name"]).traffic["kind"] == "train_sharded"}
    new = [m for m in BENCH["per_layer"]
           if m["name"] in SCOPE_METRICS or m["name"] == "sampler_programs.train"]
    assert len(new) == 7
    at = BENCH["per_layer"].index(new[0])
    assert BENCH["per_layer"][at:at + 7] == new  # appended together, in order
    assert all(set(m["workloads"]) == {CELL} for m in BENCH["per_layer"][at + 7:])
    for m in new:
        assert set(m["workloads"]) == set(e2e[m["moves"]]["workloads"]) - sharded
        for cell_name in m["workloads"]:
            cell = manifest.load_cell(cell_name)
            (loaded,) = [p for p in cell.per_layer if p["name"] == m["name"]]
            assert callable(manifest.load_reader(loaded["reader"]))
            if m["name"] in SCOPE_METRICS:
                assert loaded["reader"] == "scope" and m["source"] == "program_span"
                assert loaded["params"]["name"] == SCOPE_METRICS[m["name"]]


def test_the_configuration_states_its_cut_of_scale():
    cfg = manifest.load_cell(CELL).config
    pub, held = cfg["published"], cfg["deployment"]
    assert cfg["reduced"] == ["n_nodes", "n_edges", "train_nodes", "dropout"]
    assert cfg["n_nodes"] * 2 == pub["n_nodes"] and cfg["n_edges"] * 2 == pub["n_edges"]
    assert cfg["train_nodes"] == -(-pub["train_nodes"] // 2) and held["chips"] == 8
    # the published shapes stay: mean degree, row width, classes, model, traffic
    assert round(cfg["n_edges"] / cfg["n_nodes"], 2) == pub["mean_degree"] == 14.55
    assert (cfg["feat_dim"], cfg["classes"], cfg["hidden_dim"], cfg["num_layers"],
            cfg["fanout"], cfg["batch"]) == (128, 172, 256, 3, [15, 10, 5], 1024)
    assert pub["feature_bytes"] == pub["n_nodes"] * cfg["feat_dim"] * 4
    # a chip's share is a deployment's: 25% of a chip is 4.0 GiB
    per_chip = (cfg["n_nodes"] * (cfg["feat_dim"] * 4 + 4) + cfg["n_edges"] * 4) / 4
    assert 0.40 * 16 * 2**30 < per_chip < 0.55 * 16 * 2**30


def test_the_metrics_this_cell_added_come_last_and_are_its_own():
    tail = BENCH["per_layer"][-len(OWN):]
    assert [m["name"] for m in tail] == list(OWN)
    for m in tail:
        assert m["workloads"] == [CELL] and m["moves"] == "train_seeds_per_s"
        assert m["layer"] == ("sampler" if m["name"] == "shard_sample_ms.train"
                              else "row exchange")
    assert [m["source"] for m in tail] == ["device_trace", "device_trace", "program_counter",
                                           "device_trace", "device_trace"]


def test_listed_metrics_name_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        if "workloads" not in m:
            continue
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
        for cell_name in m["workloads"]:
            (loaded,) = [p for p in manifest.load_cell(cell_name).per_layer
                         if p["name"] == m["name"]]
            assert callable(manifest.load_reader(loaded["reader"]))


def test_step_program_names_are_declared_apart_and_match_the_steps_patterns():
    assert not set(qtrace.STEP_PROGRAM_NAMES) & set(qtrace.PROGRAM_NAMES)
    for name in qtrace.STEP_PROGRAM_NAMES:
        module = f"jit_{name}"
        for metric, reads_it in (("sampler_device_ms.train", False),
                                 ("sampler_programs.train", False),
                                 ("gather_roofline", False)):
            params = manifest.load_json(os.path.join(
                manifest.HERE, "metrics", f"{metric}.json"))["params"]
            inc, exc = params.get("include", ()), params.get("exclude", ())
            hit = ((not inc or any(re.search(p, module) for p in inc))
                   and not any(re.search(p, module) for p in exc))
            assert hit is reads_it, (metric, module)


def test_the_library_jits_the_sharded_step_under_its_declared_name():
    import jax
    import numpy as np
    import optax

    from quiver_tpu import CSRTopo
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import (make_mesh, make_sharded_topo_train_step, replicate,
                                     shard_feature_rows, shard_topology_rows)
    from quiver_tpu.pyg.sage_sampler import sample_dense_fused

    rng = np.random.default_rng(0)
    topo = CSRTopo(edge_index=rng.integers(0, 40, (2, 300)))
    mesh = make_mesh(4, dp=1)
    model, tx = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=1, dropout=0.0), optax.adam(1e-3)
    feat = np.zeros((40, 4), np.float32)
    ds = sample_dense_fused(*topo.to_device()[:2], jax.random.key(0),
                            np.arange(4, dtype=np.int32), (2,))
    params = model.init(jax.random.key(1), feat[np.zeros(ds.n_id.shape[0], int)], ds.adjs)
    step = make_sharded_topo_train_step(mesh, model, tx, (2,), pipeline="fused")
    text = step.lower(replicate(mesh, params), replicate(mesh, tx.init(params)),
                      jax.random.key(0), shard_topology_rows(mesh, topo),
                      shard_feature_rows(mesh, feat), replicate(mesh, np.zeros(40, np.int32)),
                      np.arange(4, dtype=np.int32)).as_text()
    (name,) = qtrace.STEP_PROGRAM_NAMES
    assert f"module @jit_{name} " in text


# -- the readers and patterns this cell added ----------------------------------

AR = ("%all-reduce.27 = (s32[15,1024]{1,0:T(8,128)}, s32[1024,15]{0,1:T(8,128)}) "
      "all-reduce(%select_bitcast_fusion.1, %fusion.166), channel_id=1")
PSUM = "%psum.75 = f32[180224,5,128]{2,0,1:T(8,128)} all-reduce(%bitcast.137), channel_id=1"
USER = "%fusion.9 = f32[180224,128]{1,0:T(8,128)} fusion(%psum.75, %all-reduce.27), kind=kLoop"
START = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p), channel_id=2"


def summary(ops):
    return TraceSummary(Trace({0: ops, 1: ops}, {0: [Event("jit_sharded_topo_train_step(1)", 0, 90)]},
                              [Event("qbench.train_step", 0, 100)]))


def spec(name):
    return manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{name}.json"))


def test_collective_pattern_matches_the_operation_not_its_users():
    (pattern,) = spec("collective_ms.train")["params"]["include"]
    assert spec("exchange_roofline")["params"]["include"] == [pattern]
    for name, hit in ((AR, True), (PSUM, True), (START, True), (USER, False),
                      ("%fusion.1 = f32[1081344,128]{1,0} fusion(%p0, %p1)", False)):
        assert bool(re.search(pattern, name)) is hit, name


GATHER = ("%fusion.3 = f32[901120,128]{1,0:T(8,128)} fusion(f32[13882495,128]{1,0:T(8,128)} "
          "%param.86, s32[901120]{0:T(1024)S(1)} %broadcast_clamp_fusion), kind=kCustom")
FILL = ("%select_select_fusion.3 = f32[901120,128]{1,0:T(8,128)} fusion(f32[901120,128]"
        "{1,0:T(8,128)} %fusion.3, pred[901120]{0:T(1024)(128)(4,1)S(1)} %compare_and_fusion.6, "
        "pred[901120]{0:T(1024)(128)(4,1)S(1)} %compare_and_fusion.7), kind=kLoop")
LANE_ROWS = ("%fusion.34 = s32[180224,128]{1,0:T(8,128)S(1)} fusion(s32[1638400,128]"
             "{1,0:T(8,128)} %bitcast.21, s32[180224]{0:T(1024)S(1)} %fusion.120), kind=kCustom")
LOOP = ("%while.56 = (s32[]{:T(128)}, s32[180224,5]{0,1:T(8,128)S(1)}, s32[180224,5]"
        "{0,1:T(8,128)S(1)}) while((s32[]{:T(128)}, s32[180224,5]{0,1:T(8,128)S(1)}) %tuple.9)")
MODEL = ("%multiply_reduce_fusion = f32[180224,128]{1,0:T(8,128)S(1)} fusion(f32[180224,5,128]"
         "{2,0,1:T(8,128)} %psum.75, f32[180224,5]{0,1:T(8,128)S(1)} %convert_element_type.262)")


def hits(metric, name):
    params = spec(metric)["params"]
    return (any(re.search(p, name) for p in params["include"])
            and not any(re.search(p, name) for p in params.get("exclude", ())))


def test_sampling_and_gather_patterns_split_the_one_program_by_what_an_operation_makes():
    """Operation names as the chip's trace gives them (PR 28's first traced
    run). The sampler's share of the step is every operation with an integer
    result but the collectives (and the loops, whose bodies are events of their
    own); the gather's is the fusions that read rows of a float PARAMETER by an
    index vector and the owner-mask selects over what they made. The step
    compiled for a described v5e holds both against the whole program
    (tests/test_tpu_compile.py)."""
    sample, gather = "shard_sample_ms.train", "shard_gather_ms.train"
    for name, in_sample, in_gather in ((LANE_ROWS, True, False), (GATHER, False, True),
                                       (FILL, False, True), (AR, False, False),
                                       (PSUM, False, False), (LOOP, False, False),
                                       (MODEL, False, False), (USER, False, False)):
        assert hits(sample, name) is in_sample, name
        assert hits(gather, name) is in_gather, name
    ops = [Event(LANE_ROWS, 0, 10), Event(GATHER, 10, 40), Event(FILL, 40, 44),
           Event(PSUM, 44, 70), Event(MODEL, 70, 90)]
    ctx = {"trace": summary(ops), "units": {"steps": 2}}
    read = manifest.load_reader("device_time")
    assert read(ctx, **spec(sample)["params"]) == pytest.approx(1e3 * 10e-9 / 2)
    assert read(ctx, **spec(gather)["params"]) == pytest.approx(1e3 * 34e-9 / 2)
    assert read(dict(ctx, trace=summary([Event(MODEL, 0, 9)])), **spec(gather)["params"]) is None


def test_collective_ms_and_exchange_roofline_read_the_ops_line():
    ops = [Event(AR, 0, 10), Event(USER, 10, 50), Event(PSUM, 50, 80), Event(START, 80, 82)]
    ctx = {"trace": summary(ops), "units": {"steps": 2},
           "work": {"exchange_bytes": 800.0}, "peaks": {"flops_per_s": 1.0}}
    ms = manifest.load_reader("device_time")(ctx, **spec("collective_ms.train")["params"])
    assert ms == pytest.approx(1e3 * 42e-9 / 2)  # mean over the two chips, per step
    params = dict(spec("exchange_roofline")["params"], link_bytes_per_s=1e9)
    share = manifest.load_reader("link_roofline")(ctx, **params)
    assert share == pytest.approx(100.0 * (800.0 / 1e9) * 2 / 42e-9)
    # nothing to read: off a TPU (no peaks), without steps, without a collective
    read = manifest.load_reader("link_roofline")
    assert read(dict(ctx, peaks=None), **params) is None
    assert read(dict(ctx, units={}), **params) is None
    assert read(dict(ctx, trace=summary([Event(USER, 0, 9)])), **params) is None
    stated = spec("exchange_roofline")["params"]
    assert stated["link_bytes_per_s"] == 1600e9 / 8 and "TPU v5e" in stated["link_source"]


def test_registry_reads_a_counters_mean_and_nothing_from_an_empty_one(monkeypatch):
    read = manifest.load_reader("registry")
    params = spec("comm_bytes_per_step")["params"]
    qtrace.trace_report(reset=True)
    assert read({}, **params) is None
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    for _ in range(3):
        qtrace.observe(params["name"], 2.5e9)
    assert read({}, **params) == pytest.approx(2.5e9)
    assert read({}, name="quiver.renamed") is None
    qtrace.trace_report(reset=True)
