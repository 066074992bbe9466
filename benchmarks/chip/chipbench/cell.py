"""A cell of BENCHMARK.json and the files it names.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<mix>.json``, a per-layer metric ``metrics/<name>.py`` with a
``read(run)`` function; the harness finds each by its name, so a cell or
a metric is added by adding files and entries, never by editing one.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = HERE.parents[1]  # the checkout


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]  # the cell's entries of "end_to_end"
    per_layer: list[dict]  # the cell's entries of "per_layer"


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, bench: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(bench.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    module = "chipbench_metric_" + metric.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
