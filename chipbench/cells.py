"""Finds what `BENCHMARK.json` names, by name, in files of their own:

  configuration  the `file` of its `configs` entry: the published config,
                 and under `as_run` the values the program departs to
                 (`as_run` below), with the reference module that the file
                 names ("reference")
  cell           chipbench/workloads/<cell>.json: the engine's slots and
                 positions, and the correctness check's sample and limit
  traffic mix    chipbench/traffic/<traffic>.json, read by `arrivals.py`
  metric         chipbench/metrics/<metric>.py, a `read(run)` that returns a
                 number, or None where it finds nothing to read

A cell, configuration, traffic mix or metric is added by adding files and
entries; nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench import arrivals

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    entry: dict               # the `workloads` entry of BENCHMARK.json
    config: dict              # the configuration file, as run
    sizing: dict              # chipbench/workloads/<name>.json
    mix: dict                 # chipbench/traffic/<traffic>.json
    end_to_end: List[dict]    # the metrics this cell reports with --trace 0
    per_layer: List[dict]     # ... and with --trace 1

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def reference(self):
        """The configuration's plain reference module."""
        return importlib.import_module(
            f"chipbench.{self.config.get('reference', 'reference')}")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def as_run(config: dict) -> dict:
    """The configuration as the program runs it: the published keys, with
    those it departs on replaced by their `as_run` values. The program is
    held to this and the reference computes this."""
    return {**config, **config.get("as_run", {})}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find(bench: dict, name: str, root: Path = ROOT) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    return make(bench, by_name[name], root)


def make(bench: dict, entry: dict, root: Path = ROOT) -> Cell:
    """The cell a `workloads` entry describes, listed in `bench` or not
    yet (`sweep.py` sizes a cell's rate before it is listed)."""
    name = entry["name"]
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    data = root / "chipbench"
    return Cell(
        name=name, entry=entry,
        config=as_run(json.loads((root / conf["file"]).read_text())),
        sizing=json.loads((data / "workloads" / f"{name}.json").read_text()),
        mix=arrivals.load_mix(data / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


_readers: Dict[str, Callable] = {}


def reader(metric: str) -> Callable[[object], Optional[float]]:
    """chipbench/metrics/<metric>.py's `read`, loaded by its path (a
    metric's name may hold dots)."""
    if metric not in _readers:
        path = HERE / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{len(_readers)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _readers[metric] = mod.read
    return _readers[metric]
