"""The attention cell, its configuration and what PR 34 appended, found by name
alone; and every assertion of the one test of test_qbench_tiered_manifest.py
that tests/conftest.py expects to fail since PR 34 (`OUTGROWN`: it wants PR
32's five metrics to be the LAST of ``per_layer``), in a form the next appended
metric keeps: what each PR appended stands together, in order, and nothing is
said of what follows."""

import os

import pytest

from qbench import manifest

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
CELL, SIBLING = "igb-small-gat.train-dedup", "igb-small-sage.train-dedup"
PR26 = ("sampler_host_ms.train", "feature_host_ms.train", "sampler_programs.train",
        "serve_submit_ms", "serve_queue_ms", "serve_device_wait_ms", "serve_resolve_ms")
PR28 = ("collective_ms.train", "exchange_roofline", "comm_bytes_per_step",
        "shard_sample_ms.train", "shard_gather_ms.train")
PR32 = ("cold_rows_per_step", "cold_gather_ms.train", "h2d_ms.train", "cold_merge_ms.train",
        "h2d_roofline")
OWN = ("model_device_ms.train", "gat_project_ms.train", "gat_project_mfu", "gat_edge_ms.train",
       "gat_edge_roofline")
SHARED = ("train_seeds_per_s", "sampler_device_ms.train", "gather_roofline",
          "sampler_host_ms.train", "feature_host_ms.train", "sampler_programs.train")
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def test_what_each_pr_appended_stands_together_in_order():
    at = PER_LAYER.index(PR26[0])
    for own in (PR26, PR28, PR32, OWN):
        assert PER_LAYER[at:at + len(own)] == list(own)
        at += len(own)


def test_the_metrics_this_cell_added_are_its_own():
    added = [m for m in BENCH["per_layer"] if m["name"] in OWN]
    assert [m["name"] for m in added] == list(OWN)
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_seeds_per_s"
        assert m["layer"] == "model" and m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert [m["unit"] for m in added] == ["ms", "ms", "%", "ms", "%"]
    assert [m["better"] for m in added] == ["lower", "lower", "higher", "lower", "higher"]


@pytest.mark.parametrize("name", OWN)
def test_each_new_metric_loads_by_name_with_a_reader_the_benchmark_had(name):
    (loaded,) = [m for m in manifest.load_cell(CELL).per_layer if m["name"] == name]
    assert callable(manifest.load_reader(loaded["reader"]))
    assert loaded["reader"] in ("device_time", "roofline") and loaded["params"]["per"] == "steps"
    if name == "model_device_ms.train":
        assert loaded["params"] == {"per": "steps", "include": ["train_step"]}
    else:  # by operation shape, on the operations' line
        assert loaded["params"]["line"] == "ops" and loaded["params"]["include"]
    if name == "gat_project_mfu":
        assert loaded["params"]["flops_key"] == "project_flops"
        assert loaded["params"]["include"] == manifest.load_json(os.path.join(
            manifest.HERE, "metrics", "gat_project_ms.train.json"))["params"]["include"]
    if name == "gat_edge_roofline":
        assert loaded["params"]["bytes_key"] == "edge_bytes"
        assert loaded["params"]["include"] == manifest.load_json(os.path.join(
            manifest.HERE, "metrics", "gat_edge_ms.train.json"))["params"]["include"]
    # no other cell reports it
    for w in BENCH["workloads"]:
        if w["name"] != CELL:
            assert name not in {m["name"] for m in manifest.load_cell(w["name"]).per_layer}


def test_the_cell_loads_by_name_with_the_siblings_traffic():
    cell, sibling = manifest.load_cell(CELL), manifest.load_cell(SIBLING)
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry == BENCH["workloads"][-1] and entry["chips"] == 1 and len(entry["why"]) <= 200
    t = cell.traffic
    assert t["kind"] == "train_gat" and t["dedup"] is True and "caps_from" in t
    assert t["caps"] == sibling.traffic["caps"] == [73728, 417792]
    assert set(t["limits"]) == {"loss1_gap", "loss2_gap", "loss3_gap", "grad1_norm_gap",
                                "dparam3_norm_gap"}
    assert all(0 < v < 0.1 for v in t["limits"].values())  # read on the chip, not a placeholder
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {m["name"] for m in sibling.per_layer} | set(OWN)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in SHARED:
            assert m["workloads"][-1] == CELL and SIBLING in m["workloads"]
    assert callable(manifest.load_kind(t["kind"]).run)


def test_the_configuration_is_the_siblings_with_the_model_changed():
    cfg, sage = manifest.load_cell(CELL).config, manifest.load_cell(SIBLING).config
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "igb-small-gat"]
    assert entry == BENCH["configs"][-1] and entry["file"] == "qbench/configs/igb-small-gat.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["dropout"] and cfg["published"] == {"dropout": 0.2}
    for key in ("n_nodes", "n_edges", "feat_dim", "classes", "train_nodes", "hidden_dim",
                "num_layers", "fanout", "batch", "optimizer", "lr", "dropout", "matmul_operands",
                "param_dtype", "compute_dtype", "label_signal", "graph"):
        assert cfg[key] == sage[key], key
    assert (cfg["model"], cfg["heads"], cfg["out_heads"], cfg["activation"],
            cfg["negative_slope"], cfg["attention_dtype"]) == ("gat", 4, 4, "relu", 0.2, "float32")
    assert set(sage["assumed"]) <= set(cfg["assumed"])
    assert {"self_edge", "attention_dropout", "attention_dtype"} <= set(cfg["assumed"])
    assert cfg["deployment"]["chips"] == 1 and "none but dropout" in cfg["deployment"]["cut"]
    # the bytes a deployment holds: the table alone is a quarter of a chip
    assert cfg["n_nodes"] * cfg["feat_dim"] * 4 > 0.25 * 16e9
