"""Find a cell's pieces by name: its configuration, traffic mix, limits
and metric readers, each in a file of its own under ``bench/``.

A cell is one ``workloads`` entry of ``BENCHMARK.json``. Nothing here
knows a cell, a configuration or a metric by name: adding one means
adding files and an entry, not editing this module.
"""

from __future__ import annotations

import dataclasses
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
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} module for {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file written
    with the published ``config.json`` keys (Qwen3 dense layout)."""
    from repro.configs.base import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: the program "
                         "serves swiglu MLPs only")
    if float(conf.get("rms_norm_eps", 1e-6)) != 1e-6:
        raise ValueError("the program's rmsnorm uses eps 1e-6")
    dtype = conf.get("torch_dtype", "bfloat16")
    return ModelConfig(
        name=f"{conf['model_type']}-{conf['attention_backend']}",
        family="dense",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        qk_norm=conf["model_type"] == "qwen3",
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        attention_backend=conf["attention_backend"],
        decode_kernel=conf.get("decode_kernel", "auto"),
        dtype=dtype,
        param_dtype=dtype,
    )
