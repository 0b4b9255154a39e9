"""Find a cell's pieces by name, from files alone.

A cell ``<config>.<mix>`` is an entry of ``BENCHMARK.json``'s
``workloads``. Its configuration is ``bench/configs/<config>.json``, its
traffic ``bench/traffic/<mix>.json``, and each metric it reports is a
reader ``bench/metrics/<metric>.py`` that defines ``read(run)``. Adding
a configuration, a mix or a metric is adding files and entries; nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, mix or metric that the files do not define."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str]       # per-layer metrics only
    read: Callable             # read(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    bench_dir: Path
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read`` of ``<bench_dir>/metrics/<name>.py``, loaded by path (a
    metric's name holds dots, so it is no importable module name)."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` applies to exactly those cells; an
    end-to-end metric without it applies to every cell, a per-layer one
    to every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in reported
    return True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    config = load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]

    def metric(m: dict) -> Metric:
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      m.get("layer"), load_reader(m["name"], bench_dir))

    return Cell(name, config, traffic, int(w["chips"]), bench_dir,
                [metric(m) for m in e2e], [metric(m) for m in layer])


def read_metrics(metrics: List[Metric], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for every reader that found
    something; a reader that returns ``None`` leaves its metric out."""
    out = {}
    for m in metrics:
        value = m.read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
