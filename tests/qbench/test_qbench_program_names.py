"""Program names as a contract, held at both ends: the patterns by which the
benchmark's metrics find the library's programs in a device trace against the
names the library declares (`quiver_tpu.trace.PROGRAM_NAMES`), and those
names against the callables jitted at their sites. A rename in the library
fails here instead of silencing a reader. Also: the metrics and the two
readers that ISSUE 26 added load by name and return nothing where there is
nothing to read."""

import os
import re

import numpy as np
import pytest

import jax.numpy as jnp

from qbench import manifest, reduce
from qbench.reduce import Event, Trace
from quiver_tpu import trace as qtrace

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
METRICS = os.path.join(manifest.HERE, "metrics")
OWN_PROGRAMS = ("train_step",)  # jitted by the benchmark itself, kinds/train.py
SCOPE_METRICS = {
    "sampler_host_ms.train": "quiver.sample",
    "feature_host_ms.train": "quiver.feature.lookup",
    "serve_submit_ms": "quiver.serve.submit",
    "serve_queue_ms": "quiver.serve.queue",
    "serve_device_wait_ms": "quiver.serve.device",
    "serve_resolve_ms": "quiver.serve.resolved",
}


def patterns():
    found = []
    for f in sorted(os.listdir(METRICS)):
        params = manifest.load_json(os.path.join(METRICS, f))["params"]
        if params.get("line", "modules") == "modules":
            found += [(f, p) for key in ("include", "exclude") for p in params.get(key, ())]
    return found


def test_there_are_patterns_to_hold():
    assert {p for _, p in patterns()} >= {"padded_gather", "train_step"}


@pytest.mark.parametrize("metric_file,pattern", patterns())
def test_every_pattern_matches_a_declared_program(metric_file, pattern):
    modules = [f"jit_{name}" for name in qtrace.PROGRAM_NAMES + OWN_PROGRAMS]
    assert any(re.search(pattern, m) for m in modules), (
        f"{metric_file}: {pattern!r} matches none of {modules}")


def jitted_at_their_sites():
    from quiver_tpu import CSRTopo, feature
    from quiver_tpu.inference import BucketPrograms
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.ops import reindex, sample
    from quiver_tpu.pyg import GraphSageSampler

    rng = np.random.default_rng(0)
    topo = CSRTopo(edge_index=rng.integers(0, 50, (2, 400)))
    sampler = GraphSageSampler(topo, [2, 2], mode="TPU", seed=0)
    model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2, dropout=0.0)
    programs = BucketPrograms(model, sampler, np.zeros((50, 4), np.float32))
    return {"tiled_sample_layer": sample.tiled_sample_layer,
            "local_reindex": reindex.local_reindex,
            "_padded_gather": feature._padded_gather,
            "_padded_gather_ordered": feature._padded_gather_ordered,
            "serve_step": programs._jit}


def test_every_declared_name_is_the_jitted_callable_at_its_site():
    sites = jitted_at_their_sites()
    assert set(sites) == set(qtrace.PROGRAM_NAMES)
    for name, fn in sites.items():
        assert hasattr(fn, "lower"), f"{name} is not jitted at its site"
        assert fn.__name__ == name
    # and the name is what XLA calls the module, which the trace shows
    table, ids = jnp.zeros((8, 4)), jnp.zeros(3, jnp.int32)
    assert "module @jit__padded_gather " in sites["_padded_gather"].lower(
        table, ids).as_text()
    assert "module @jit__padded_gather_ordered " in sites["_padded_gather_ordered"].lower(
        table, jnp.arange(8), ids).as_text()


def test_the_benchmarks_own_step_keeps_its_name():
    import optax

    from qbench.kinds.train import make_train_step

    assert make_train_step(None, optax.adam(1e-3), None).__name__ in OWN_PROGRAMS


# -- the metrics and readers this issue added ---------------------------------


def test_new_metrics_load_for_the_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    new = [m for m in BENCH["per_layer"]
           if m["name"] in SCOPE_METRICS or m["name"] == "sampler_programs.train"]
    assert len(new) == 7 and BENCH["per_layer"][-7:] == new  # appended, in order
    for m in new:
        assert set(m["workloads"]) == set(e2e[m["moves"]]["workloads"])
        for cell_name in m["workloads"]:
            cell = manifest.load_cell(cell_name)
            (loaded,) = [p for p in cell.per_layer if p["name"] == m["name"]]
            assert callable(manifest.load_reader(loaded["reader"]))
            if m["name"] in SCOPE_METRICS:
                assert loaded["reader"] == "scope" and m["source"] == "program_span"
                assert loaded["params"]["name"] == SCOPE_METRICS[m["name"]]


def test_scope_reads_the_registry_and_nothing_from_an_empty_one(monkeypatch):
    read = manifest.load_reader("scope")
    qtrace.trace_report(reset=True)
    ctx = {"units": {"steps": 4}}
    assert read(ctx, "quiver.sample", per="steps") is None
    assert read(ctx, "quiver.serve.queue") is None
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    qtrace.observe("quiver.sample", np.full(8, 0.002))
    qtrace.observe("quiver.serve.queue", np.asarray([0.001, 0.003]))
    assert read(ctx, "quiver.sample", per="steps") == pytest.approx(4.0)  # 16 ms / 4
    assert read(ctx, "quiver.serve.queue") == pytest.approx(2.0)          # the mean
    assert read({"units": {}}, "quiver.sample", per="steps") is None
    assert read(ctx, "quiver.renamed") is None
    qtrace.trace_report(reset=True)


def test_module_count_clips_to_the_window_and_reads_nothing_without_a_match():
    read = manifest.load_reader("module_count")
    modules = [Event("jit_tiled_sample_layer(3)", 5, 8), Event("jit_fold_in(4)", 8, 9),
               Event("jit__padded_gather_ordered(2)", 20, 40),
               Event("jit_train_step(1)", 50, 90),
               Event("jit_tiled_sample_layer(3)", 95, 120),   # runs past the end
               Event("jit_tiled_sample_layer(3)", 130, 140)]  # outside
    spans = [Event("qbench.sample_dense", 0, 100)]
    ctx = {"trace": reduce.TraceSummary(Trace({0: [Event("fusion", 5, 90)]},
                                              {0: modules}, spans)),
           "units": {"steps": 2}}
    sampler = dict(per="steps", exclude=["padded_gather", "train_step"])
    assert read(ctx, **sampler) == pytest.approx(1.5)
    assert read(ctx, per="steps", include=["padded_gather"]) == pytest.approx(0.5)
    assert read(ctx, per="steps", include=["nothing_like_this"]) is None
    assert read(dict(ctx, units={}), **sampler) is None


def test_module_count_on_the_recorded_chip_trace():
    """Five products steps recorded on the chip (PR 25): the sampler's
    programs per step, by the pattern `sampler_programs.train` uses."""
    import gzip
    import shutil
    import tempfile

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "products_fused_5steps.xplane.pb.gz")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.xplane.pb")
        with gzip.open(src, "rb") as f, open(path, "wb") as out:
            shutil.copyfileobj(f, out)
        summary = reduce.summarize(path)
    steps = summary.span_count("qbench.train_step")
    spec = manifest.load_json(os.path.join(METRICS, "sampler_programs.train.json"))
    ctx = {"trace": summary, "units": {"steps": steps}}
    per_step = manifest.load_reader("module_count")(ctx, **spec["params"])
    assert steps == 5 and per_step == pytest.approx(38.0)
    names = {e.name for evs in summary.trace.modules.values() for e in evs}
    for program in ("tiled_sample_layer", "_padded_gather_ordered", "train_step"):
        assert any(re.search(rf"jit_{program}\b", n) for n in names), program
