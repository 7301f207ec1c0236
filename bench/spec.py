"""Finds everything one cell needs by the names in ``BENCHMARK.json``.

A cell (a ``workloads`` entry) names a configuration and a traffic mix.
Its files are found by name:

- ``bench/configs/<config>.json``: the configuration as it is run, with the
  family of its architecture (``bench/families/<family>.py``), which gives
  ``Shape`` (``Shape.of(config)``, with ``vocab``), ``arch_config``,
  ``make_weights``, ``program_params``, ``served_gaps``, ``logit_gaps``,
  ``decode_cost``, ``request_flops`` and ``tiny``
  (``bench/families/dense_decoder.py`` says what each is);
- ``bench/traffic/<traffic>.json``: the mix that ``bench/traffic.py``
  generates, and the pod that serves it;
- ``bench/cells/<cell>.json``: the output check (sample size, limit);
- ``bench/metrics/<metric>.py``: one reader per metric the cell reports,
  taken from ``BENCHMARK.json``'s lists by its ``workloads`` key.

Adding a cell, a mix, a configuration or a metric adds files and entries;
it edits none that exist.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Imports the file at ``path`` (whose name may hold dots)."""
    name = f"bench.{path.parent.name}.{path.stem.replace('.', '_')}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod   # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str            # "end_to_end" | "per_layer"
    reader: ModuleType   # has read(ctx) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict         # bench/configs/<config>.json
    family: ModuleType   # bench/families/<family>.py
    traffic: dict        # bench/traffic/<traffic>.json
    check: dict          # bench/cells/<cell>.json
    metrics: List[Metric]

    def metrics_of(self, kind: str) -> List[Metric]:
        return [m for m in self.metrics if m.kind == kind]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` of ``benchmark``; raises ``KeyError`` for a name
    it does not list and ``FileNotFoundError`` for a missing file."""
    bm = _read_json(benchmark)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
    config = _read_json(benchmark.parent / conf_entry["file"])
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bm[kind]:
            if name in m.get("workloads", [name]):
                metrics.append(Metric(
                    m["name"], m["unit"], kind,
                    load_module(bench_dir / "metrics" / f"{m['name']}.py")))
    return Cell(
        name=name, chips=w["chips"], config=config,
        family=load_module(bench_dir / "families"
                           / f"{config['family']}.py"),
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        check=_read_json(bench_dir / "cells" / f"{name}.json"),
        metrics=metrics)
