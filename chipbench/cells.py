"""Cells, configurations, traffic mixes and metrics as data: everything is
found by the names in ``BENCHMARK.json``, in files under this directory.
A later PR adds files and appends entries; no file that exists is edited."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

from . import traffic

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    kind: str  # "serve" or "train": which child runs the cell
    config_name: str
    config_path: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: dict[str, dict]  # name -> BENCHMARK.json entry
    per_layer: dict[str, dict]

    def units(self, traced: bool) -> dict[str, str]:
        src = self.per_layer if traced else self.end_to_end
        return {name: m["unit"] for name, m in src.items()}


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_root: str = BENCH_ROOT) -> Cell:
    bench = _json(os.path.join(bench_root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config_path = os.path.join(bench_root, conf_entry["file"])
    here = os.path.join(bench_root, "chipbench")
    workload = _json(os.path.join(here, "workloads", f"{name}.json"))
    if workload["config"] != entry["config"] or workload["traffic"] != entry["traffic"]:
        raise ValueError(f"workloads/{name}.json disagrees with BENCHMARK.json")
    config = _json(config_path)
    config["model"] = config  # the published keys sit at the top level of the file
    e2e = {m["name"]: m for m in bench["end_to_end"] if _reports(m, name)}
    layer = {m["name"]: m for m in bench["per_layer"] if _reports(m, name) and m["moves"] in e2e}
    return Cell(
        name=name, chips=entry["chips"], kind=config["kind"],
        config_name=entry["config"], config_path=config_path, config=config,
        traffic_name=entry["traffic"],
        traffic=traffic.load(os.path.join(here, "traffic", f"{entry['traffic']}.json")),
        end_to_end=e2e, per_layer=layer,
    )


def load_reader(metric: str, bench_root: str = BENCH_ROOT):
    """The ``read(ctx)`` of ``chipbench/readers/<metric>.py``, or of the
    reader that ``metrics/<metric>.json`` names under ``reader`` (one
    quantity split by the end-to-end metric it moves has one reader).  A
    reader returns a number, or None where it finds nothing to read."""
    here = os.path.join(bench_root, "chipbench")
    entry = os.path.join(here, "metrics", f"{metric}.json")
    module_name = _json(entry).get("reader", metric) if os.path.exists(entry) else metric
    path = os.path.join(here, "readers", f"{module_name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_reader_{module_name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
