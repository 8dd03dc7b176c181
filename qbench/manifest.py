"""Everything the harness knows about a cell comes from data found by name:
`BENCHMARK.json` at the root of the checkout names the cell, its
configuration's file and the metrics; ``qbench/workloads/<cell>.json`` holds
the traffic's parameters and the kind of run; ``qbench/metrics/<metric>.json``
names a per-layer metric's reader (``qbench/readers/<reader>.py``) and the
reader's parameters. Adding a cell, a configuration, a metric or a reader is
adding files; no file here is edited for it."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List, NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "qbench")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]       # the configuration's file, as it is run
    traffic: Dict[str, Any]      # qbench/workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]  # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]   # manifest entries + their metric file


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "qbench", "workloads", f"{name}.json"))
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in bench["per_layer"]:
        if _reports(m, name) and m["moves"] in reported:
            spec = load_json(os.path.join(HERE, "metrics", f"{m['name']}.json"))
            per_layer.append({**m, **spec})
    return Cell(name, int(entry["chips"]), config, traffic, end_to_end, per_layer)


def load_kind(kind: str):
    """The runner of one kind of cell: ``qbench/kinds/<kind>.py``."""
    return importlib.import_module(f"qbench.kinds.{kind}")


def load_reader(name: str):
    """A per-layer metric's reader: ``qbench/readers/<name>.py`` with
    ``read(ctx, **params) -> float | None``."""
    return importlib.import_module(f"qbench.readers.{name}").read


def load_peaks(device_kind: str) -> Dict[str, float]:
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise SystemExit(f"device kind {device_kind!r} is not in qbench/peaks.json; "
                         "a device without published peaks is an error, not a default")
    return peaks[device_kind]
