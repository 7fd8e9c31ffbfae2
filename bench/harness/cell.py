"""Find everything a cell needs by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are found here and nowhere else:

* configuration: the ``file`` of its ``configs`` entry;
* traffic mix:   ``bench/traffic/<traffic>.json``;
* per-layer metric: ``bench/metrics/<metric name>.py`` with ``read(rec)``;
* surface module: ``bench/surfaces/<config["surface"]>.py``.

Adding a cell or a metric therefore adds files and entries and edits
no file that is already there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


class CellError(RuntimeError):
    """The benchmark definition does not describe the requested run."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def find_workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json")


def find_config(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise CellError(f"no config {name!r} in BENCHMARK.json")


def load_cell(bm: dict, name: str, root: Path = ROOT):
    """``(workload entry, config dict, traffic dict)`` of cell ``name``."""
    w = find_workload(bm, name)
    cfg = load_json(root / find_config(bm, w["config"])["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return w, cfg, traffic


def applies(metric: dict, cell: str) -> bool:
    """Whether ``metric`` is reported in ``cell``: its ``workloads`` list
    names the cell, or it has none (a per-layer reader that finds nothing
    to read in a cell leaves its metric out there)."""
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bm: dict, cell: str) -> List[dict]:
    return [m for m in bm["end_to_end"] if applies(m, cell)]


def per_layer(bm: dict, cell: str) -> List[dict]:
    return [m for m in bm["per_layer"] if applies(m, cell)]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise CellError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"per-layer metric {name!r} has no reader {path}")
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def surface(name: str, root: Path = ROOT):
    path = root / "bench" / "surfaces" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no surface module {path}")
    return load_module(path, "bench_surface_" + name)


def read_per_layer(metrics: List[dict], rec: dict,
                   root: Path = ROOT) -> Dict[str, dict]:
    """Run each metric's reader over the traced run's record; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = metric_reader(m["name"], root)(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
