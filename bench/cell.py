"""Find a cell's files by the names in ``BENCHMARK.json``.

A later change adds a configuration, a traffic mix, a per-layer metric or
a cell by adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path
    )
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # configs/<config>.json
    model: ModuleType  # configs/<config>.py: reference, init, FLOPs
    traffic: Dict[str, Any]  # traffic/<mix>.json
    limits: Dict[str, float]  # limits/<workload>.json
    end_to_end: List[Dict[str, Any]]  # this cell's end-to-end metrics
    per_layer: List[Dict[str, Any]]  # this cell's per-layer metrics
    readers: Dict[str, ModuleType]  # metrics/<metric>.py by metric name


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: pathlib.Path = ROOT,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``; raises KeyError or OSError where the
    benchmark does not define it completely."""
    spec = _read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if workload not in entries:
        raise KeyError(
            f"no workload {workload!r}; known: {sorted(entries)}"
        )
    w = entries[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_file = root / cfg_entry["file"]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_read_json(cfg_file),
        model=load_module(cfg_file.with_suffix(".py")),
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench_dir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        readers={
            m["name"]: load_module(bench_dir / "metrics" / f"{m['name']}.py")
            for m in per_layer
        },
    )
