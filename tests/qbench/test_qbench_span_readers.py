"""What ISSUE 36 added to the benchmark, without a run: the two readers
(`span_device_gap`, which lays the library's timeline on a device trace, and
`registry_max`) on hand-made events, what every new reader does on a tree
whose library keeps no timeline or recorded nothing (None, and no error: the
driver runs the parent commit under these files), and the thirteen manifest
entries, found by name."""

import os
import re
import types

import pytest

from qbench import manifest
from qbench.readers import registry_max, span_device_gap
from qbench.reduce import Event, Trace
from quiver_tpu import trace as qtrace

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
SERVE = "igb-small-sage.serve-zipf"
TRAIN = [w["name"] for w in BENCH["workloads"] if w["name"] != SERVE]
PER_FLUSH = ("serve_seq_wait_ms", "serve_window_wait_ms", "serve_assemble_ms", "serve_seal_ms",
             "serve_dispatch_ms")
OWN = ("serve_pending_ms",) + PER_FLUSH + (
    "serve_launch_gap_ms", "serve_fetch_gap_ms", "serve_pumps_per_flush",
    "host_tick_late_ms.serve", "host_stall_max_ms.serve",
    "host_tick_late_ms.train", "host_stall_max_ms.train")
PR34_LAST = "gat_edge_roofline"
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
OFFSET = 7_000_000_000.0  # the trace's clock minus the timeline's, ns
GAP = {"anchor": ["qbench.submit", "quiver.serve.submit"], "span": "quiver.serve.dispatch",
       "module": "serve_step"}


@pytest.fixture(autouse=True)
def clean(monkeypatch):
    monkeypatch.delenv(qtrace.TRACE_ENV, raising=False)
    qtrace.trace_report(reset=True)
    qtrace._timeline.clear()
    yield
    qtrace.trace_report(reset=True)
    qtrace._timeline.clear()


def outer_and_inner(n=10, lead_ns=2e3, tail_ns=5e3):
    """``n`` benchmark spans of 100 us, 1 ms apart, in the trace's clock, and
    the library span each encloses, in the timeline's."""
    outer = [Event("qbench.submit", 1e6 * i, 1e6 * i + 100e3) for i in range(n)]
    inner = [(o.start_ns + lead_ns - OFFSET, o.end_ns - tail_ns - OFFSET) for o in outer]
    return outer, inner


# -- the anchor -----------------------------------------------------------------


def test_anchor_finds_the_offset_to_the_shortest_lead():
    outer, inner = outer_and_inner()
    inner[3] = (inner[3][0] - 1.5e3, inner[3][1])  # this one opened 0.5 us after its outer
    assert span_device_gap.anchor_offset(outer, inner) == pytest.approx(OFFSET - 0.5e3)


def test_anchor_refuses_unequal_counts_and_empty_sides():
    outer, inner = outer_and_inner()
    assert span_device_gap.anchor_offset(outer, inner[:-1]) is None
    assert span_device_gap.anchor_offset(outer[1:], inner) is None
    assert span_device_gap.anchor_offset([], []) is None


def test_anchor_refuses_library_spans_that_stick_out_of_their_enclosing_span():
    outer, inner = outer_and_inner(n=100)
    inner[7] = (inner[7][0], inner[7][1] + 200e3)   # one in a hundred, by 195 us: kept
    assert span_device_gap.anchor_offset(outer, inner) is not None
    inner[8] = (inner[8][0], inner[8][1] + 200e3)   # two in a hundred: refused
    assert span_device_gap.anchor_offset(outer, inner) is None
    outer, inner = outer_and_inner(n=100)
    inner[7] = (inner[7][0], inner[7][1] + 40e3)    # within the 50 us: it fits
    inner[8] = (inner[8][0], inner[8][1] + 40e3)
    assert span_device_gap.anchor_offset(outer, inner) is not None


# -- programs inside spans --------------------------------------------------------


def test_two_overlapping_dispatch_spans_take_one_program_each_in_start_order():
    spans = [(1000.0, 5000.0), (2000.0, 7000.0)]          # two flushes in flight
    programs = [Event("jit_serve_step", 4000.0, 4400.0),  # handed over unsorted
                Event("jit_serve_step", 2500.0, 2900.0)]
    assert span_device_gap.program_gaps(spans, programs) == [
        (1500.0, 2100.0), (2000.0, 2600.0)]
    # the later span's program may start inside the earlier span as well: the
    # earlier span takes the first, the later one what is left
    assert span_device_gap.program_gaps(list(reversed(spans)), programs) == [
        (1500.0, 2100.0), (2000.0, 2600.0)]


def test_a_span_with_no_program_inside_it_reads_none():
    spans = [(1000.0, 2000.0), (3000.0, 4000.0), (5000.0, 6000.0)]
    programs = [Event("jit_serve_step", 1200.0, 1300.0),
                Event("jit_serve_step", 3900.0, 4100.0),   # ends after its span: not inside
                Event("jit_serve_step", 4500.0, 4600.0)]   # between two spans
    assert span_device_gap.program_gaps(spans, programs) == [(200.0, 700.0), None, None]


def fake_run(monkeypatch, n=20, with_program=20, extra_modules=True):
    """A ctx and a timeline of ``n`` requests and ``n`` flushes: each flush's
    dispatch span is 3 ms, its program starts 2.0 ms in and runs 0.4 ms."""
    outer, inner = outer_and_inner(n)
    entries = [("quiver.serve.submit", t0 * 1e-9, t1 * 1e-9, 1, None) for t0, t1 in inner]
    modules = []
    for i in range(n):
        s0 = 1e6 * i + 200e3
        entries.append(("quiver.serve.dispatch", (s0 - OFFSET) * 1e-9,
                        (s0 + 3e6 - OFFSET) * 1e-9, 2 + i % 2, {"fid": i + 1}))
        if i < with_program:
            modules.append(Event("jit_serve_step(123)", s0 + 2.0e6, s0 + 2.4e6))
        if extra_modules:  # the eager key derivation, inside the span too: not the step
            modules.append(Event("jit__threefry_split", s0 + 0.1e6, s0 + 0.11e6))
    monkeypatch.setattr(qtrace, "trace_timeline", lambda reset=False: tuple(entries))
    trace = Trace(ops={0: []}, modules={0: modules}, spans=outer)
    return {"trace": types.SimpleNamespace(trace=trace), "units": {"dispatches": n}}


def test_launch_and_fetch_gaps_of_a_run_add_up_to_the_span_less_the_program(monkeypatch):
    ctx = fake_run(monkeypatch)
    launch = span_device_gap.read(ctx, which="launch", **GAP)
    fetch = span_device_gap.read(ctx, which="fetch", **GAP)
    # the anchor's offset is short by the 2 us the benchmark's span led by
    assert launch == pytest.approx(2.0 + 0.002, abs=1e-6)
    assert fetch == pytest.approx(0.6 - 0.002, abs=1e-6)
    assert launch + 0.4 + fetch == pytest.approx(3.0, abs=1e-6)
    with pytest.raises(ValueError):
        span_device_gap.read(ctx, which="middle", **GAP)


def test_gaps_need_nine_spans_in_ten_with_a_program_and_a_fitting_anchor(monkeypatch):
    assert span_device_gap.read(fake_run(monkeypatch, with_program=18), which="launch", **GAP) \
        == pytest.approx(2.002, abs=1e-6)
    assert span_device_gap.read(fake_run(monkeypatch, with_program=17), which="launch", **GAP) is None
    ctx = fake_run(monkeypatch)
    ctx["trace"].trace.spans.pop()  # a benchmark span lost: the counts differ
    assert span_device_gap.read(ctx, which="launch", **GAP) is None


# -- nothing to read ----------------------------------------------------------------


def loaded(name):
    """The metric's manifest entry merged with its file, through a cell that lists it."""
    cell = manifest.load_cell(TRAIN[0] if name.endswith(".train") else SERVE)
    (m,) = [m for m in cell.per_layer if m["name"] == name]
    return m


def empty_ctx():
    trace = Trace(ops={0: []}, modules={0: []}, spans=[Event("qbench.submit", 0.0, 1.0)])
    return {"trace": types.SimpleNamespace(trace=trace),
            "units": {"dispatches": 10, "requests": 10, "steps": 10}, "counters": {}}


@pytest.mark.parametrize("name", OWN)
def test_every_new_metric_reads_nothing_from_an_empty_registry_and_timeline(name):
    m = loaded(name)
    assert qtrace.trace_report() == {} and qtrace.trace_timeline() == ()
    assert manifest.load_reader(m["reader"])(empty_ctx(), **m["params"]) is None


@pytest.mark.parametrize("name", OWN)
def test_every_new_metric_reads_nothing_on_a_tree_without_the_timeline(name, monkeypatch):
    """The parent commit: `trace_timeline` does not exist, nothing of this
    PR's is in the registry, other spans are. (Two of the names are older
    than this PR, ``quiver.serve.dispatch`` and ``quiver.serve.assemble``,
    which was the drain AND the seal: a traced parent reports those two.)"""
    monkeypatch.delattr(qtrace, "trace_timeline")
    monkeypatch.delattr(qtrace, "stall_report")
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    monkeypatch.setattr(qtrace, "_start_watch", lambda: None)  # the parent has no watch
    with qtrace.trace_scope("quiver.serve.submit"):
        pass
    qtrace.observe("quiver.serve.queue", [0.001, 0.002])
    m = loaded(name)
    assert manifest.load_reader(m["reader"])(empty_ctx(), **m["params"]) is None


def test_registry_max_reads_the_longest_in_milliseconds(monkeypatch):
    monkeypatch.setenv(qtrace.TRACE_ENV, "1")
    monkeypatch.setattr(qtrace, "_start_watch", lambda: None)
    qtrace.observe("quiver.host.tick", [0.0001, 0.0925, 0.002])
    assert registry_max.read({}, name="quiver.host.tick") == pytest.approx(92.5)
    assert registry_max.read({}, name="quiver.host.nothing") is None


# -- the manifest's entries -----------------------------------------------------------


def test_the_thirteen_are_appended_after_pr34s_in_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR34_LAST) + 1
    assert names[at:at + len(OWN)] == list(OWN)


def test_each_entry_lists_the_cells_that_have_something_to_read():
    for m in BENCH["per_layer"]:
        if m["name"] not in OWN:
            continue
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["better"] == "lower"
        if m["name"].endswith(".train"):
            assert m["workloads"] == TRAIN and m["moves"] == "train_seeds_per_s"
        else:
            assert m["workloads"] == [SERVE] and m["moves"] == "serve_p50_ms"
        assert m["unit"] == ("calls" if m["name"] == "serve_pumps_per_flush" else "ms")
        if m["name"].startswith("host_"):
            assert (m["layer"], m["source"]) == ("host process", "program_counter")
        elif m["name"].endswith("_gap_ms"):
            assert (m["layer"], m["source"]) == ("serve device step", "device_trace")
        elif m["name"] == "serve_pumps_per_flush":
            assert (m["layer"], m["source"]) == ("serve front end", "program_counter")
        else:
            assert m["source"] == "program_span"
            assert m["layer"] == ("serve device step" if m["name"] == "serve_dispatch_ms"
                                  else "serve front end")


@pytest.mark.parametrize("name", OWN)
def test_each_new_metric_loads_by_name_and_reads_a_name_the_library_records(name):
    cells = TRAIN if name.endswith(".train") else [SERVE]
    for cell in cells:
        (m,) = [m for m in manifest.load_cell(cell).per_layer if m["name"] == name]
        assert callable(manifest.load_reader(m["reader"]))
    params = m["params"]
    if name in PER_FLUSH:
        assert m["reader"] == "scope" and params["per"] == "dispatches"
        assert params["name"] == "quiver.serve." + name[len("serve_"):-len("_ms")]
    elif name.endswith("_gap_ms"):
        assert m["reader"] == "span_device_gap" and params == dict(
            GAP, which=name.split("_")[1])
        assert re.search(params["module"], "jit_serve_step") and "serve_step" in qtrace.PROGRAM_NAMES
    elif name.startswith("host_"):
        assert m["reader"] == ("scope" if "tick_late" in name else "registry_max")
        assert params == {"name": "quiver.host.tick"}
    else:
        assert "per" not in params  # a mean per event: per request, per flush
    # the name is one the library's source records under, letter for letter
    src = "".join(open(os.path.join(manifest.ROOT, "quiver_tpu", f)).read()
                  for f in ("trace.py", os.path.join("serve", "engine.py")))
    for read_name in [params.get("name"), params.get("span"), (params.get("anchor") or [None, None])[1]]:
        assert read_name is None or f'"{read_name}"' in src, read_name


# -- a whole traced serve run, rehearsed on the CPU ------------------------------------


def test_a_traced_serve_run_reports_the_eleven_and_their_sums_hold(tmp_path, monkeypatch):
    """The serve kind at the tiny test-only configuration under a real CPU
    profiler session, with this PR's entries appended to a copy of the tiny
    manifest. A CPU trace has no device plane, so one program is made up in
    the middle third of every ``quiver.serve.dispatch`` event of the trace:
    the reader has to find that third again from the library's timeline and
    the anchor's offset. No number of this run is a measurement."""
    import json
    import shutil

    from jax.profiler import ProfileData

    from qbench import harness, reduce, run

    root = str(tmp_path / "root")
    shutil.copytree(TINY, root)
    cell = "tiny-sage.serve-zipf"
    bench = manifest.load_json(os.path.join(root, "BENCHMARK.json"))
    serve_own = [n for n in OWN if not n.endswith(".train")]
    bench["per_layer"] += [dict(m, workloads=[cell]) for m in BENCH["per_layer"]
                           if m["name"] in serve_own + ["serve_queue_ms"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    def reduce_with_a_made_up_device(self, keep=None):
        path = reduce.find_xplane(self.dir)
        programs = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                programs += [Event("jit_serve_step(7)", e.start_ns + e.duration_ns / 3,
                                   e.start_ns + 2 * e.duration_ns / 3)
                             for e in line.events if e.name == "quiver.serve.dispatch"]
        spans = reduce.load_xplane(path).spans
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduce.TraceSummary(Trace({0: programs}, {0: programs}, spans))

    monkeypatch.setattr(harness.TraceWindow, "reduce", reduce_with_a_made_up_device)
    out = json.loads(run.run(["--workload", cell, "--seed", str(2**31 + 36), "--seconds", "1.0",
                              "--trace", "1"], any_device=True, root=root))
    assert out["correct"] is True and out["failed"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(serve_own) <= set(got)
    assert all(got[n] >= 0.0 for n in serve_own)
    # a third of the span before its program, a third after it, to 50 us
    third = got["serve_dispatch_ms"] / 3
    assert got["serve_launch_gap_ms"] == pytest.approx(third, abs=0.05)
    assert got["serve_fetch_gap_ms"] == pytest.approx(third, abs=0.05)
    assert got["serve_execute_ms"] == pytest.approx(third, abs=0.05)
    # a request's queue stage is its pending stage and its flush's own part;
    # the per-flush means weigh every flush alike, the per-request mean by width
    in_flush = sum(got[f"serve_{s}_ms"] for s in ("seq_wait", "assemble", "window_wait", "seal"))
    assert got["serve_pending_ms"] <= got["serve_queue_ms"]
    assert got["serve_pending_ms"] + in_flush == pytest.approx(got["serve_queue_ms"], rel=0.5)
    assert got["serve_pumps_per_flush"] >= 1.0
    assert got["host_stall_max_ms.serve"] >= got["host_tick_late_ms.serve"] >= 0.0


def test_the_stall_hunt_writes_each_windows_ticks_and_what_was_open(tmp_path):
    """`qbench/stalls.py`, rehearsed on the CPU at the tiny configuration: two
    short windows in one process under ``QUIVER_ENABLE_TRACE``, a threshold
    low enough that some tick is over it. No number of it is a measurement."""
    import json

    from qbench import stalls

    out = tmp_path / "stalls.json"
    stalls.main(["--workload", "tiny-sage.serve-zipf", "--seed", str(2**31 + 9), "--seconds", "0.6",
                 "--windows", "2", "--threshold-ms", "0.05", "--out", str(out),
                 "--any-device", "--root", TINY])
    assert os.environ.get(qtrace.TRACE_ENV) is None and qtrace.STALL_S == 0.030
    report = json.loads(out.read_text())
    assert report["threshold_ms"] == pytest.approx(0.05) and len(report["windows"]) == 2
    for row in report["windows"]:
        assert row["requests"] > 0 and row["p50_ms"] > 0 and row["ticks"] > 10
        assert row["tick_max_ms"] >= row["tick_mean_ms"] >= 0 and isinstance(row["full_gc_ms"], list)
    found = [s for row in report["windows"] for s in row["stalls"]]
    assert found, "no tick was 0.05 ms late in 1.2 s of serving"
    for s in found:
        assert set(s) == {"at_s", "wall_s", "cpu_s", "runq_wait_s", "minor_faults", "major_faults",
                          "invol_switches", "open"}
        assert s["wall_s"] > 0.05e-3 and s["at_s"] >= 0
        for thread, spans in s["open"].items():
            assert isinstance(thread, str)
            assert all(name.startswith("quiver.") and ms >= 0 for name, ms, _ in spans)
