"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell, model
family or per-layer metric is a file of its own, found by name under the
benchmark's directory of the checkout:

    bench/configs/<config>.json    sizes as served (``file`` in the manifest)
    bench/models/<family>.py       plain float32 reference of the family
    bench/programs/<family>.py     the family's adapter to the program
    bench/counts/<family>.py       the family's operations and bytes
    bench/traffic/<traffic>.json   parameters of the one traffic generator
    bench/cells/<workload>.json    limits of the correctness comparison
    bench/metrics/<metric>.py      reader of one per-layer metric
    bench/peaks.json               published peaks by ``device_kind``

A model family is the three files named by the configuration's
``family``; a cell whose family lacks one stops here, naming it (there is
no fallback family: a dense count of an expert layer would be wrong
silently). What each holds:

* reference: ``sizes(config)``, a dict that gives ``V`` plus whatever the
  family's adapter and counts read; ``init(s, key)``, the weights in the
  served dtype, one traceable function; ``forward(s, w, tokens,
  quant=None)``, float32 logits of one sequence, and with
  ``quant="fp8"`` the control one precision step below;
* adapter: ``arch_config(config, s)``, the program's ``ArchConfig``, and
  ``to_program(cfg, w)``, the reference's weights as the program's
  parameter tree (checked against the program's own before any run);
* counts: ``decode_flops(s, context)``, ``prefill_flops(s, n)``,
  ``paged_attn_bytes(s, contexts)`` and ``paged_attn_flops(s,
  contexts)``, as ``bench/metrics/`` readers call them (``ctx.counts``).
  A family whose layers differ (windows, dense against expert layers,
  per-layer head counts) counts each layer kind itself.

A new cell, config, family, mix or metric is new files and manifest
entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import types
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
    family_files: Dict[str, Path]   # reference, adapter, counts


@dataclasses.dataclass
class Family:
    """A model family's three files, loaded."""
    reference: types.ModuleType
    adapter: types.ModuleType
    counts: types.ModuleType


FAMILY_DIRS = {"reference": "models", "adapter": "programs",
               "counts": "counts"}


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
    config = load_json(root / c["file"])
    b = bench_dir(root)
    files = {part: b / d / f"{config['family']}.py"
             for part, d in FAMILY_DIRS.items()}
    missing = [str(p.relative_to(root)) for p in files.values()
               if not p.is_file()]
    if missing:
        raise SystemExit(f"model family {config['family']!r} of "
                         f"{c['name']} has no {' or '.join(missing)}")
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=c["name"], config=config,
        traffic=load_json(b / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(b / "cells" / f"{workload}.json"),
        end_to_end=[m for m in manifest["end_to_end"]
                    if reports(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if reports(m, workload)],
        family_files=files)


def load_module(path: Path):
    """The module of one file under ``bench/<dir>/``, named after both."""
    name = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cell: Cell) -> Family:
    """The cell's model family: its reference, adapter and counts."""
    return Family(**{part: load_module(path)
                     for part, path in cell.family_files.items()})


def metric_reader(root: Path, name: str):
    return load_module(bench_dir(root) / "metrics" / f"{name}.py")


def peaks(root: Path, device_kind: str) -> Dict:
    table = load_json(bench_dir(root) / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no published peaks for {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]
