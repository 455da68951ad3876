"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name under the
benchmark's directory of the checkout:

    bench/configs/<config>.json    sizes as served (``file`` in the manifest)
    bench/models/<family>.py       plain reference of the config's family
    bench/traffic/<traffic>.json   parameters of the one traffic generator
    bench/cells/<workload>.json    limits of the correctness comparison
    bench/metrics/<metric>.py      reader of one per-layer metric
    bench/peaks.json               published peaks by ``device_kind``

A new cell, config, mix or metric is new files and manifest entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]     # manifest entries this cell reports
    per_layer: List[Dict]


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def reports(metric: Dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def resolve(root: Path, workload: str) -> Cell:
    root = Path(root)
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"names {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    c = configs[w["config"]]
    b = bench_dir(root)
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=c["name"], config=load_json(root / c["file"]),
        traffic=load_json(b / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(b / "cells" / f"{workload}.json"),
        end_to_end=[m for m in manifest["end_to_end"]
                    if reports(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if reports(m, workload)])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cell: Cell):
    """The plain reference of the cell's model family."""
    fam = cell.config["family"]
    return load_module(bench_dir(cell.root) / "models" / f"{fam}.py",
                       f"bench_family_{fam}")


def metric_reader(root: Path, name: str):
    return load_module(bench_dir(root) / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))


def peaks(root: Path, device_kind: str) -> Dict:
    table = load_json(bench_dir(root) / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no published peaks for {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]
