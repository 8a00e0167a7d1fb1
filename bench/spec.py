"""Find a cell's pieces by name: its configuration and the
configuration's family, traffic mix, limits and metric readers, each in
a file of its own under ``bench/``.

A cell is one ``workloads`` entry of ``BENCHMARK.json``. Nothing here
knows a cell, a configuration or a metric by name: adding one means
adding files and an entry, not editing this module.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, read from files."""
    name: str
    config: Dict[str, Any]        # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json
    limits: Dict[str, float]      # bench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    chips: int = 1


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load_cell(name: str, benchmark: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    spec = load_json(benchmark or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=name,
        config=load_json(ROOT / configs[w["config"]]["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        chips=int(w["chips"]),
    )


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots),
    executed once per process, as an import would be: a reference's
    jitted functions then compile once, not once per comparison."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} module for {name!r} at {path}")
    return _exec(path)


@functools.lru_cache(maxsize=None)
def _exec(path: Path):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_"
        f"{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def family(conf: Dict[str, Any]):
    """The module of the configuration's family,
    ``bench/families/<family>.py`` (``"dense"`` where the file names
    none); ``bench/families/__init__.py`` gives what it defines."""
    return load_module("families", conf.get("family", "dense"))


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(conf).model_config(conf)
