"""The tiered path's names as a contract (PR 32): the step's declared name
against its jitted site and against every pattern of qbench/metrics/*.json,
the device half's against its site, and the spans and counters the tiered
lookup records against docs/api.md's table and the metric files that read
them."""

import json
import os
import re

import numpy as np
import optax
import pytest

from quiver_tpu import feature, pipeline
from quiver_tpu import trace as qtrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = os.path.join(REPO, "qbench", "metrics")
STEP, GATHER = qtrace.TIERED_PROGRAM_NAMES
SPANS = ("quiver.feature.lookup", "quiver.feature.cold_gather", "quiver.feature.h2d")
COUNTERS = ("quiver.feature.cold_rows", "quiver.feature.cold_overflow")


def module_patterns():
    found = []
    for f in sorted(os.listdir(METRICS)):
        with open(os.path.join(METRICS, f)) as fh:
            params = json.load(fh)["params"]
        patterned = params.get("include") or params.get("exclude")
        if patterned and params.get("line", "modules") == "modules":
            found.append((f, tuple(params.get("include", ())), tuple(params.get("exclude", ()))))
    return found


def reads(include, exclude, module):
    return ((not include or any(re.search(p, module) for p in include))
            and not any(re.search(p, module) for p in exclude))


def test_the_names_are_declared_apart_and_are_the_jitted_callables_at_their_sites():
    declared = (qtrace.PROGRAM_NAMES + qtrace.STEP_PROGRAM_NAMES + qtrace.SAMPLE_PROGRAM_NAMES)
    assert not set(qtrace.TIERED_PROGRAM_NAMES) & set(declared)
    assert len(qtrace.PROGRAM_NAMES) == 5 and len(qtrace.STEP_PROGRAM_NAMES) == 1
    from quiver_tpu.models import GraphSAGE

    model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2, dropout=0.0)
    step = pipeline.make_tiered_train_step(model, optax.adam(1e-3), np.zeros(4, np.int32),
                                           np.zeros((4, 8), np.float32))
    assert hasattr(step.program, "lower") and step.program.__name__ == STEP
    assert "train_step" in STEP  # what the benchmark's patterns call "the step"
    site = feature._padded_gather_tiered
    assert hasattr(site, "lower") and site.__name__ == GATHER
    import jax.numpy as jnp

    text = site.lower(jnp.zeros((8, 4)), jnp.zeros(3, jnp.int32), jnp.zeros((2, 4))).as_text()
    assert f"module @jit_{GATHER} " in text
    assert pipeline._key_chain.__name__ == "_key_chain" and hasattr(pipeline._key_chain, "lower")


@pytest.mark.parametrize("metric_file,include,exclude", module_patterns())
def test_every_module_pattern_reads_the_tiered_programs_as_what_they_are(
        metric_file, include, exclude):
    step, gather = f"jit_{STEP}(7)", f"jit_{GATHER}(3)"
    name = metric_file[:-len(".json")]
    if name in ("sampler_device_ms.train", "sampler_programs.train"):
        # every program but the gather's and the step's: neither of these
        assert not reads(include, exclude, step) and not reads(include, exclude, gather)
        assert reads(include, exclude, "jit_sample_dense_program(2)")
    elif name == "gather_roofline":
        assert reads(include, exclude, gather) and not reads(include, exclude, step)
    elif name == "model_device_ms.train":  # PR 34: "the step", whichever program is it
        assert reads(include, exclude, step) and not reads(include, exclude, gather)
        assert not reads(include, exclude, "jit_sample_dense_program(2)")
    else:  # the serve step's metrics: no program of this path
        assert not reads(include, exclude, step) and not reads(include, exclude, gather)


def test_spans_and_counters_are_in_the_docs_table_and_read_by_their_metrics():
    with open(os.path.join(REPO, "docs", "api.md")) as f:
        table = [line for line in f if line.startswith("| `quiver.")]
    for name in SPANS + COUNTERS:
        (row,) = [line for line in table if line.startswith(f"| `{name}` ")]
        assert row.rstrip().endswith("| span |" if name in SPANS else "| counter (`observe`) |")
    assert not any("pipeline." in line.split("|")[1] for line in table)  # moved under quiver.
    read_by = {"feature_host_ms.train": SPANS[0], "cold_gather_ms.train": SPANS[1],
               "h2d_ms.train": SPANS[2], "h2d_roofline": SPANS[2],
               "cold_rows_per_step": COUNTERS[0]}
    for metric, name in read_by.items():
        with open(os.path.join(METRICS, f"{metric}.json")) as f:
            assert json.load(f)["params"]["name"] == name, metric
    for module in (feature, pipeline):
        with open(module.__file__) as f:
            source = f.read()
        assert '"pipeline.' not in source
    with open(feature.__file__) as f:
        source = f.read()
    for name in SPANS + COUNTERS:
        assert f'"{name}"' in source, name
