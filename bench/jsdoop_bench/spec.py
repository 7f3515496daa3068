"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List

#: the benchmark's own directory (``bench/``)
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
#: the checkout's root, where ``BENCHMARK.json`` lives
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, file or entry that the benchmark cannot resolve."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_config(name: str) -> Dict[str, Any]:
    cfg = _read_json(BENCH_DIR / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise SpecError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def load_traffic(name: str) -> Dict[str, Any]:
    mix = _read_json(BENCH_DIR / "traffic" / f"{name}.json")
    for key in ("dialect", "volunteers", "policy", "think_ms",
                "warmup_commits", "trace_seconds", "checked_commits"):
        if key not in mix:
            raise SpecError(f"traffic/{name}.json has no {key!r}")
    return mix


def load_limits(cell: str) -> Dict[str, float]:
    return _read_json(BENCH_DIR / "limits" / f"{cell}.json")["limits"]


@functools.lru_cache(maxsize=None)
def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import one file of the benchmark by its path, once."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(family: str) -> ModuleType:
    return load_module(BENCH_DIR / "families" / f"{family}.py",
                       f"bench_family_{family}")


def load_reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    mod = load_module(BENCH_DIR / "metrics" / f"{metric}.py",
                      f"bench_metric_{metric.replace('.', '_')}")
    return mod.read


def _for_cell(metrics: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(w["config"]),
                traffic=load_traffic(w["traffic"]),
                limits=load_limits(name),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))
