"""The tiered cell, its control and what PR 32 appended, found by name alone;
and EVERY assertion of the two tests of test_qbench_sharded_manifest.py that
tests/conftest.py expects to fail since PR 32 (`OUTGROWN`), in a form the next
appended metric keeps: only the place of the four-chip cell's five metrics at
the very end of ``per_layer`` is left out (what a PR adds is appended, so each
PR's own come after the last one's), and "everything after PR 26's seven is
the four-chip cell's" is held PR by PR instead."""

import os

import pytest

from qbench import manifest

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
SHARDED = "papers100M-sage.train-sharded4"
TIERED = "papers100M-sage-tiered.train-hot6g"
CONTROL = "products-sage.train-dedup"
PR28 = ("collective_ms.train", "exchange_roofline", "comm_bytes_per_step",
        "shard_sample_ms.train", "shard_gather_ms.train")
OWN = ("cold_rows_per_step", "cold_gather_ms.train", "h2d_ms.train", "cold_merge_ms.train",
       "h2d_roofline")
SCOPE_METRICS = {
    "sampler_host_ms.train": "quiver.sample",
    "feature_host_ms.train": "quiver.feature.lookup",
    "serve_submit_ms": "quiver.serve.submit",
    "serve_queue_ms": "quiver.serve.queue",
    "serve_device_wait_ms": "quiver.serve.device",
    "serve_resolve_ms": "quiver.serve.resolved",
}
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def test_what_each_pr_appended_stands_together_in_order_and_this_prs_comes_last():
    seven = [m["name"] for m in BENCH["per_layer"]
             if m["name"] in SCOPE_METRICS or m["name"] == "sampler_programs.train"]
    assert len(seven) == 7
    at = PER_LAYER.index(seven[0])
    assert PER_LAYER[at:at + 7] == seven
    assert PER_LAYER[at + 7:at + 12] == list(PR28)
    assert PER_LAYER[at + 12:] == list(OWN) == PER_LAYER[-len(OWN):]


@pytest.mark.parametrize("own,cell", [(PR28, SHARDED), (OWN, TIERED)], ids=["pr28", "pr32"])
def test_the_metrics_a_cell_added_are_its_own(own, cell):
    added = [m for m in BENCH["per_layer"] if m["name"] in own]
    assert [m["name"] for m in added] == list(own)
    for m in added:
        assert m["workloads"] == [cell] and m["moves"] == "train_seeds_per_s"
    if cell == SHARDED:
        assert [m["layer"] for m in added] == ["row exchange"] * 3 + ["sampler", "row exchange"]
        assert [m["source"] for m in added] == ["device_trace", "device_trace", "program_counter",
                                                "device_trace", "device_trace"]
    else:
        assert {m["layer"] for m in added} == {"feature store"}
        assert [m["source"] for m in added] == ["program_counter", "program_span", "program_span",
                                                "device_trace", "program_span"]


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS) + ["sampler_programs.train"])
def test_pr26s_metrics_list_every_cell_that_runs_their_spans(name):
    """test_qbench_sharded_manifest.py::test_pr26s_metrics_load_for_the_cells_
    that_report_what_they_move, every assertion past the position, one case a
    metric."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    sharded = {c for c in cells if manifest.load_cell(c).traffic["kind"] == "train_sharded"}
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    moved = e2e[m["moves"]]
    assert set(m.get("workloads", cells)) == set(moved.get("workloads", cells)) - sharded
    for cell_name in m.get("workloads", cells):
        (loaded,) = [p for p in manifest.load_cell(cell_name).per_layer if p["name"] == name]
        assert callable(manifest.load_reader(loaded["reader"]))
        if name in SCOPE_METRICS:
            assert loaded["reader"] == "scope" and m["source"] == "program_span"
            assert loaded["params"]["name"] == SCOPE_METRICS[name]


def test_the_tiered_cell_loads_by_name_with_the_issues_traffic():
    cell = manifest.load_cell(TIERED)
    t = cell.traffic
    assert cell.chips == 1 and t["kind"] == "train_tiered" and t["dedup"] is True
    assert (t["hot_bytes"], t["depth"], t["layout"]) == (6442450944, 2, "flat")
    assert len(t["caps"]) == 3 and all(c % 4096 == 0 for c in list(t["caps"]) + [t["cold_cap"]])
    assert "caps_from" in t
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(OWN) <= names and "gather_roofline" not in names
    assert {"device_idle_pct.train", "host_gap_ms.train", "train_step_mfu", "sampler_host_ms.train",
            "feature_host_ms.train", "sampler_programs.train", "sampler_device_ms.train"} <= names
    assert not names & set(PR28)


def test_the_control_cell_is_the_accepted_configuration_under_the_dedup_sampler():
    cell, fused = manifest.load_cell(CONTROL), manifest.load_cell("products-sage.train-fused")
    assert cell.config == fused.config and cell.chips == 1
    assert cell.traffic["kind"] == "train" and cell.traffic["dedup"] is True
    assert "caps_from" in cell.traffic and len(cell.traffic["caps"]) == 3
    names = {m["name"] for m in cell.per_layer}
    assert names == {m["name"] for m in fused.per_layer} and not names & set(OWN)


def test_the_tiered_configuration_states_its_cut_and_its_deployment():
    cfg, accepted = manifest.load_cell(TIERED).config, manifest.load_cell(SHARDED).config
    pub, held = cfg["published"], cfg["deployment"]
    assert cfg["reduced"] == ["n_nodes", "n_edges", "train_nodes", "dropout"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "papers100M-sage-tiered"]
    assert entry["reduced"] == cfg["reduced"] and len(entry["source"]) <= 200
    # the same cut of scale and the same published shapes as the accepted papers100M-sage
    for key in ("n_nodes", "n_edges", "train_nodes", "feat_dim", "classes", "hidden_dim",
                "num_layers", "fanout", "batch"):
        assert cfg[key] == accepted[key], key
    assert cfg["n_nodes"] * 2 == pub["n_nodes"] and cfg["n_edges"] * 2 == pub["n_edges"]
    assert held["chips"] == 1
    # the table cannot fit one chip; the hot rows are a fifth of it and over 25% of the chip
    table = cfg["n_nodes"] * cfg["feat_dim"] * 4
    hot = manifest.load_cell(TIERED).traffic["hot_bytes"]
    assert table > 16 * 2**30 and 0.2 < hot / table < 0.25 and hot > 0.25 * 16 * 2**30
