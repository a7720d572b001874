"""A cell, as the files under ``bench/`` define it.

``BENCHMARK.json`` names the cell's configuration and traffic; each lives in
a file of its own (``configs/<name>.json``, ``traffic/<name>.json``), the
limits of its output check in ``limits/<cell>.json``, and each per-layer
metric's reader in ``metrics/<name>.py``.  A new cell, configuration,
traffic mix or metric is a new file and a new entry: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic file's parameters
    limits: dict  # {number: limit} of the output check
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @property
    def family(self) -> str:
        return self.config["family"]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = _json(BENCH / "limits" / f"{name}.json")["limits"]
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module: a family's reference or FLOP
    count (by family name), or a per-layer metric's reader."""
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {kind} file for {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
