"""`qbench.reduce` on hand-made events (every number by hand) and on a small
trace recorded on the chip."""

import os

import pytest

from qbench import reduce
from qbench.reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _trace():
    # window 0..100 (the spans' extent). Device: ops at 10-30, 20-40 (overlap),
    # 60-70, and one 95-120 that runs past the window's end.
    ops = [Event("fusion.1", 10, 30), Event("fusion.2", 20, 40),
           Event("gather.7", 60, 70), Event("fusion.1", 95, 120)]
    modules = [Event("jit_train_step(1)", 10, 40), Event("jit__padded_gather(2)", 60, 70),
               Event("jit_train_step(1)", 95, 120)]
    spans = [Event("qbench.sample_dense", 0, 50), Event("qbench.lookup_padded", 50, 65),
             Event("qbench.wait", 65, 100)]
    return Trace({0: ops}, {0: modules}, spans)


def test_busy_union_and_idle_share():
    s = reduce.TraceSummary(_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy[0] == [(10, 40), (60, 70), (95, 100)]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)


def test_pattern_sums_clip_to_the_window():
    s = reduce.TraceSummary(_trace())
    assert s.device_seconds(["train_step"]) == pytest.approx(35e-9)  # 30 + 5 of 25
    assert s.device_seconds(["padded_gather"]) == pytest.approx(10e-9)
    assert s.device_seconds(exclude=["padded_gather", "train_step"]) is None
    assert s.device_seconds(["fusion"], line="ops") == pytest.approx(45e-9)
    assert s.device_seconds(["nothing_like_this"]) is None


def test_gap_attribution():
    s = reduce.TraceSummary(_trace())
    idle = s.idle_by_span()
    # gaps: 0-10 and 40-50 under sample_dense, 50-60 under lookup_padded,
    # 70-95 under wait
    assert idle == {"qbench.sample_dense": pytest.approx(20e-9),
                    "qbench.lookup_padded": pytest.approx(10e-9),
                    "qbench.wait": pytest.approx(25e-9)}
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(25e-9)]
    assert b["idle_gaps"][0][0] == "qbench.wait"


def test_nested_spans_and_uncovered_idle():
    spans = [Event("qbench.window", 0, 100), Event("qbench.submit", 20, 30)]
    idle = reduce.attribute([(10, 40), (90, 110)], spans)
    assert idle == {"qbench.window": 30, "qbench.submit": 10, "(outside spans)": 10}


def test_a_trace_without_spans_or_device_is_refused():
    with pytest.raises(RuntimeError):
        reduce.TraceSummary(Trace({0: [Event("a", 0, 1)]}, {}, []))
    with pytest.raises(RuntimeError):
        reduce.TraceSummary(Trace({}, {}, [Event("qbench.wait", 0, 1)]))


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """Five steps of products-sage.train-fused, traced on a TPU v5e by
    `qbench/run.py --seconds 0.25 --trace 1 --keep-trace` (PR 25), gzipped."""
    import gzip
    import shutil

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "products_fused_5steps.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce.summarize(str(path))


def test_recorded_chip_trace_reduces_to_what_the_run_printed(chip_trace):
    s = chip_trace
    # the run's own result line: "busy_s": 0.229629201, "window_s": 0.278054047
    assert s.window_s == pytest.approx(0.278054047, rel=1e-6)
    assert s.busy_s == pytest.approx(0.229629201, rel=1e-6)
    assert 100 * s.idle_share == pytest.approx(17.4156, rel=1e-4)
    assert s.span_count("qbench.wait") == 5 and s.span_count("qbench.sample_dense") == 5
    assert sorted(s.trace.modules) == [0] and len(s.trace.modules[0]) == 200  # 40 programs a step


def test_recorded_chip_trace_pattern_sums_and_gaps(chip_trace):
    s = chip_trace
    gather = s.device_seconds(["padded_gather"])
    step = s.device_seconds(["train_step"])
    sampler = s.device_seconds(exclude=["padded_gather", "train_step"])
    assert gather == pytest.approx(0.127745928, rel=1e-6)      # 25.5 ms a step
    assert step == pytest.approx(0.038550891, rel=1e-6)        # 7.7 ms a step
    assert sampler == pytest.approx(0.063422169, rel=1e-6)     # 12.7 ms a step
    assert gather + step + sampler == pytest.approx(s.device_seconds(), rel=1e-9)
    # programs run one at a time, so they cover what the operations cover
    assert s.device_seconds() == pytest.approx(s.busy_s, rel=0.02)
    idle = s.idle_by_span()
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert max(idle, key=idle.get) == "qbench.sample_dense"
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "fusion.1 f32[1081344,100]" and len(top) == 10
