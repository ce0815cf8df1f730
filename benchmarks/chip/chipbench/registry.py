"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A configuration is ``configs/<config>.json`` (its plain reference, where it
has one, is ``configs/<config>.ref.py``), a traffic mix is
``traffic/<mix>.json``, a consumer is ``consumers/<name>.py`` and a metric
is ``metrics/<name>.py``, all under the benchmark's directory. Adding a
cell, a configuration, a consumer or a metric adds files; nothing here
names one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


def repo_root(bench_dir: pathlib.Path = BENCH_DIR) -> pathlib.Path:
    """The checkout root: the directory that holds ``BENCHMARK.json``."""
    for d in (bench_dir, *bench_dir.parents):
        if (d / "BENCHMARK.json").is_file():
            return d
    raise FileNotFoundError(f"no BENCHMARK.json above {bench_dir}")


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: pathlib.Path

    def metrics(self, trace: bool) -> List[Dict]:
        """The metrics this cell reports: end-to-end untraced, per-layer
        traced, each only where its ``workloads`` (if any) name the cell."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]

    def consumer(self):
        return load_module(
            self.bench_dir / "consumers" / f"{self.traffic['consumer']}.py",
            f"chipbench_consumer_{self.traffic['consumer']}")

    def reference(self):
        return load_module(
            self.bench_dir / "configs" / f"{self.config['name']}.ref.py",
            f"chipbench_ref_{self.config['name'].replace('-', '_')}")


def metric_reader(bench_dir: pathlib.Path, name: str):
    return load_module(bench_dir / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}")


def load_cell(workload: str, root: Optional[pathlib.Path] = None,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    root = root or repo_root(bench_dir)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    return build_cell(workload, w["config"], w["traffic"], int(w["chips"]),
                      spec, bench_dir)


def build_cell(name: str, config: str, traffic: str, chips: int, spec: Dict,
               bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of a configuration and a traffic mix, with the
    metrics of ``spec`` (a ``BENCHMARK.json``), whether or not ``spec``
    lists the cell."""
    return Cell(name=name, chips=chips,
                config=json.loads(
                    (bench_dir / "configs" / f"{config}.json").read_text()),
                traffic=json.loads(
                    (bench_dir / "traffic" / f"{traffic}.json").read_text()),
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"],
                bench_dir=bench_dir)
