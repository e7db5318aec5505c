"""Find a cell and its files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
CHECKOUT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
    chips: int
    end_to_end: List[Dict[str, Any]]   # metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark() -> Dict[str, Any]:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(by_name))}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(CHECKOUT / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric readers are named after metrics,
    which may hold a dot)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: Dict[str, Any]) -> ModuleType:
    return load_module(BENCH_DIR / "references" / f"{config['reference']}.py")


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")
