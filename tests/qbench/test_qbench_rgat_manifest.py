"""The typed cell, its configuration and what PR 38 appended, found by name
alone; and every other assertion of the four tests tests/conftest.py expects
to fail since PR 38 (`OUTGROWN`), in a form the next appended cell keeps: the
attention cell, its configuration and its metrics as they were, with no last
place."""

import os

import pytest

from qbench import manifest

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
CELL, CONFIG = "igbh-small-rgat.train-dedup", "igbh-small-rgat"
GAT, SIBLING = "igb-small-gat.train-dedup", "igb-small-sage.train-dedup"
GAT_OWN = ("model_device_ms.train", "gat_project_ms.train", "gat_project_mfu",
           "gat_edge_ms.train", "gat_edge_roofline")
OWN = ("rgat_project_mfu", "rgat_edge_ms.train", "rgat_edge_roofline", "typed_slot_valid_pct")
# metrics of the other train cells this cell reports too: their readers see its programs
JOINED = ("train_seeds_per_s", "sampler_device_ms.train", "gather_roofline",
          "sampler_host_ms.train", "feature_host_ms.train", "sampler_programs.train",
          "model_device_ms.train", "host_tick_late_ms.train", "host_stall_max_ms.train")
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def _entry(name):
    (m,) = [m for m in BENCH["end_to_end"] + BENCH["per_layer"] if m["name"] == name]
    return m


def test_this_prs_metrics_come_together_after_pr34s():
    at = PER_LAYER.index(GAT_OWN[0])
    assert PER_LAYER[at:at + len(GAT_OWN)] == list(GAT_OWN)
    at = PER_LAYER.index(OWN[0])
    assert PER_LAYER[at:at + len(OWN)] == list(OWN) and at > PER_LAYER.index(GAT_OWN[-1])


def test_the_metrics_this_cell_added_are_its_own():
    added = [m for m in BENCH["per_layer"] if m["name"] in OWN]
    assert [m["name"] for m in added] == list(OWN)
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_seeds_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert [m["unit"] for m in added] == ["%", "ms", "%", "%"]
    assert [m["better"] for m in added] == ["higher", "lower", "higher", "higher"]
    assert [m["layer"] for m in added] == ["model", "model", "model", "sampler"]
    assert [m["source"] for m in added] == ["device_trace"] * 3 + ["program_counter"]


@pytest.mark.parametrize("name", OWN)
def test_each_new_metric_loads_by_name_with_a_reader_the_benchmark_had(name):
    (loaded,) = [m for m in manifest.load_cell(CELL).per_layer if m["name"] == name]
    assert loaded["reader"] in ("device_time", "roofline", "registry")
    params = loaded["params"]
    if name == "typed_slot_valid_pct":
        assert params == {"name": "quiver.sample.hetero.valid_slot_pct"}
    else:  # by operation shape, on the operations' line
        assert params["line"] == "ops" and params["include"] and params["per"] == "steps"
    if name == "rgat_project_mfu":
        assert params["flops_key"] == "project_flops"
    if name == "rgat_edge_roofline":
        assert params["bytes_key"] == "edge_bytes"
        assert params["include"] == manifest.load_json(os.path.join(
            manifest.HERE, "metrics", "rgat_edge_ms.train.json"))["params"]["include"]
    for w in BENCH["workloads"]:
        if w["name"] != CELL:
            assert name not in {m["name"] for m in manifest.load_cell(w["name"]).per_layer}


def test_the_cell_loads_by_name_with_per_type_caps():
    cell = manifest.load_cell(CELL)
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200 and entry["config"] == CONFIG
    t = cell.traffic
    assert t["kind"] == "train_rgat" and "caps_from" in t and "limits_from" in t
    cfg = cell.config
    assert set(t["caps"]) == set(cfg["node_types"])
    assert all(len(c) == len(cfg["fanout"]) and all(v % 4096 == 0 for v in c)
               for c in t["caps"].values())
    assert set(t["layouts"]) == {"__".join(r["relation"]) for r in cfg["relations"]}
    assert t["layouts"]["paper__cites__paper"] == "tiled"
    assert set(t["limits"]) == {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap",
                                "dparam3_norm_gap"}
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(OWN) | set(JOINED[1:]) | {
        "device_idle_pct.train", "host_gap_ms.train", "train_step_mfu"}
    for name in JOINED:
        assert CELL in _entry(name)["workloads"]
    assert callable(manifest.load_kind(t["kind"]).run)


def test_the_configuration_writes_down_every_cut_and_assumption():
    cfg = manifest.load_cell(CELL).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["file"] == "qbench/configs/igbh-small-rgat.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["dropout", "feature_dtype"]
    assert cfg["published"] == {"dropout": 0.2, "feature_dtype": "float32", "batch": cfg["batch"]}
    assert cfg["node_types"] == {"paper": 1000000, "author": 1926066, "institute": 14751,
                                 "fos": 190449}
    assert len(cfg["relations"]) == 7 and cfg["seed_type"] == "paper"
    assert (cfg["feat_dim"], cfg["classes"], cfg["hidden_dim"], cfg["heads"], cfg["num_layers"],
            cfg["fanout"], cfg["batch"]) == (1024, 2983, 512, 4, 3, [15, 10, 5], 1024)
    assert (cfg["feature_dtype"], cfg["matmul_operands"], cfg["attention_dtype"]) == (
        "bfloat16", "bfloat16", "float32")
    assert {"node_types", "relations", "graph", "model", "relation_aggr", "train_split",
            "feature_dtype", "dropout"} <= set(cfg["assumed"])
    # cites is igb-small-sage's graph block and edge count, seed for seed
    sage = manifest.load_cell(SIBLING).config
    (cites,) = [r for r in cfg["relations"] if r["relation"] == ["paper", "cites", "paper"]]
    assert cites["graph"] == sage["graph"] and cites["n_edges"] == sage["n_edges"]
    # the bytes a deployment holds: the table alone is more than a quarter of a chip
    assert sum(cfg["node_types"].values()) * cfg["feat_dim"] * 2 > 0.25 * 16.9e9


# -- what the outgrown tests asserted besides a last place -------------------


def test_the_attention_cells_metrics_are_its_own_and_the_model_time_is_the_attention_cells():
    """test_qbench_gat_manifest.py::test_the_metrics_this_cell_added_are_its_own and its
    model_device_ms.train case of test_each_new_metric_loads_by_name_..."""
    added = [m for m in BENCH["per_layer"] if m["name"] in GAT_OWN]
    assert [m["name"] for m in added] == list(GAT_OWN)
    for m in added:
        assert m["workloads"][0] == GAT and m["moves"] == "train_seeds_per_s"
        assert m["layer"] == "model" and m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] == ([GAT, CELL] if m["name"] == "model_device_ms.train" else [GAT])
    assert [m["unit"] for m in added] == ["ms", "ms", "%", "ms", "%"]
    assert [m["better"] for m in added] == ["lower", "lower", "higher", "lower", "higher"]
    (loaded,) = [m for m in manifest.load_cell(GAT).per_layer if m["name"] == "model_device_ms.train"]
    assert loaded["params"] == {"per": "steps", "include": ["train_step"]}


def test_the_attention_cell_loads_by_name_with_the_siblings_traffic():
    """test_qbench_gat_manifest.py::test_the_cell_loads_by_name_with_the_siblings_traffic."""
    cell, sibling = manifest.load_cell(GAT), manifest.load_cell(SIBLING)
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == GAT]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    t = cell.traffic
    assert t["kind"] == "train_gat" and t["dedup"] is True and "caps_from" in t
    assert t["caps"] == sibling.traffic["caps"] == [73728, 417792]
    assert all(0 < v < 0.1 for v in t["limits"].values())
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in sibling.per_layer} | set(GAT_OWN)
    for name in ("train_seeds_per_s", "sampler_device_ms.train", "gather_roofline",
                 "sampler_host_ms.train", "feature_host_ms.train", "sampler_programs.train"):
        assert {GAT, SIBLING} <= set(_entry(name)["workloads"])


def test_the_attention_configuration_is_the_siblings_with_the_model_changed():
    """test_qbench_gat_manifest.py::test_the_configuration_is_the_siblings_with_the_model_changed."""
    cfg, sage = manifest.load_cell(GAT).config, manifest.load_cell(SIBLING).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "igb-small-gat"]
    assert entry["file"] == "qbench/configs/igb-small-gat.json" and entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["dropout"] and cfg["published"] == {"dropout": 0.2}
    for key in ("n_nodes", "n_edges", "feat_dim", "classes", "train_nodes", "hidden_dim",
                "num_layers", "fanout", "batch", "optimizer", "lr", "dropout", "matmul_operands",
                "param_dtype", "compute_dtype", "label_signal", "graph"):
        assert cfg[key] == sage[key], key
    assert (cfg["model"], cfg["heads"], cfg["out_heads"], cfg["activation"]) == ("gat", 4, 4, "relu")


def test_the_sampler_span_metric_reads_the_typed_sampler_too():
    """`HeteroSampler.sample_dense` opens `quiver.sample` (and nests
    `quiver.sample.hetero` inside it), so `sampler_host_ms.train` lists the
    typed cell with every other unsharded train cell."""
    moved = _entry("train_seeds_per_s")["workloads"]
    sharded = {c for c in moved if manifest.load_cell(c).traffic["kind"] == "train_sharded"}
    m = _entry("sampler_host_ms.train")
    assert CELL in m["workloads"] and set(m["workloads"]) == set(moved) - sharded
    for cell_name in m["workloads"]:
        (loaded,) = [p for p in manifest.load_cell(cell_name).per_layer
                     if p["name"] == "sampler_host_ms.train"]
        assert loaded["reader"] == "scope" and loaded["params"]["name"] == "quiver.sample"
