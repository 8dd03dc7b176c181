"""Lint of BENCHMARK.json and of the files it names: the limits of the
benchmark's contract that a file can break without any run, and that every
cell, configuration, metric and reader is found by its name alone."""

import os
import re

import pytest

from qbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
TINY = os.path.join(manifest.ROOT, "tests", "qbench", "tiny")


def test_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert BENCH["paths"] == ["qbench", "tests/qbench"]
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    # a full check with 24 cells has to fit: 2 + 14 x cells runs
    cells = 24
    total = (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entries():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if "roofline" in m["name"] or "mfu" in re.split(r"[_.]", m["name"]):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("qbench/") and 1 <= len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_moves_is_reported_by_the_same_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        reporting = set(target.get("workloads", cells))
        mine = set(m.get("workloads", reporting))
        assert mine and mine <= reporting, (m["name"], mine - reporting)
    for cell in cells:
        mine = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)


def test_configs_state_their_cuts():
    widths = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head")
    for c in BENCH["configs"]:
        cfg = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not widths.search(key)
            assert key in cfg and key in cfg["published"] and key in cfg["assumed"]


@pytest.mark.parametrize("root", [manifest.ROOT, TINY], ids=["benchmark", "tiny"])
def test_every_cell_loads_by_name(root):
    bench = manifest.load_json(os.path.join(root, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"], root)
        assert cell.traffic["kind"] in ("train", "serve")
        assert callable(manifest.load_kind(cell.traffic["kind"]).run)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, "a cell reports at least one per-layer metric"
        for m in cell.per_layer:
            assert callable(manifest.load_reader(m["reader"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_metric_file_and_reader_is_used_and_loads():
    listed = {m["name"] for m in BENCH["per_layer"]}
    tiny = {m["name"] for m in manifest.load_json(os.path.join(TINY, "BENCHMARK.json"))["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(manifest.HERE, "metrics"))}
    assert listed <= files and files <= listed | tiny
    for name in files:
        spec = manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{name}.json"))
        assert set(spec) == {"layer", "unit", "moves", "reader", "params"}
        assert callable(manifest.load_reader(spec["reader"]))
    for m in BENCH["per_layer"]:
        spec = manifest.load_json(os.path.join(manifest.HERE, "metrics", f"{m['name']}.json"))
        assert (spec["layer"], spec["unit"], spec["moves"]) == (m["layer"], m["unit"], m["moves"])


def test_peaks_table_refuses_an_unknown_device():
    assert manifest.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        manifest.load_peaks("cpu")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        manifest.load_cell("no-such.cell")
