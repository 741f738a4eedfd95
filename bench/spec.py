"""What one cell of the benchmark is, read from data files by name.

``BENCHMARK.json`` (at the checkout root) lists the cells.  A cell names a
configuration and a traffic mix; the harness finds

* ``bench/configs/<config>.json``  the model as run: the source's own keys
  (Hugging Face ``config.json`` names) with the cut listed in ``reduced``;
* ``bench/traffic/<traffic>.json`` the parameters of the traffic generator;
* ``bench/cells/<cell>.json``      the engine settings of the cell (slots,
  positions, pool, chunk) and the limits of its correctness check;
* ``bench/e2e/<metric>.py`` and ``bench/metrics/<metric>.py``, one reader
  per end-to-end and per-layer metric;
* ``bench/arch/<model_type>.py``   one module per architecture: the
  program's fields, the canonical weights, the plain forward and the work
  counts of a configuration whose ``model_type`` it is named after.

Nothing here imports JAX, so the tests of the data files run anywhere; an
architecture module does, once it is first asked for.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ARCH = BENCH / "arch"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # bench/configs/<config>.json
    traffic: Dict[str, Any]     # bench/traffic/<traffic>.json
    settings: Dict[str, Any]    # bench/cells/<cell>.json
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries it reports
    per_layer: List[Dict[str, Any]]

    @property
    def engine(self) -> Dict[str, Any]:
        return self.settings["engine"]

    @property
    def limits(self) -> Dict[str, float]:
        return self.settings["limits"]


def _reports(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                settings=load_json(BENCH / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer)


def model_fields(config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ModelConfig fields for a configuration file: its
    architecture module's, and the served dtype."""
    out = arch(config["model_type"]).model_fields(config)
    out["dtype"] = out["param_dtype"] = config["torch_dtype"]
    return out


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for ``config``: its registry entry
    (``arch``) with every size and setting replaced by the file's."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    return dataclasses.replace(base, **model_fields(config))


def arch(model_type: str) -> ModuleType:
    """The module of ``bench/arch/<model_type>.py``; a model type without
    one is an error that names the file to add."""
    path = ARCH / f"{model_type}.py"
    if not path.exists():
        raise SystemExit(f"no architecture module for model_type "
                         f"{model_type!r}: add bench/arch/{model_type}.py "
                         f"(looked for {path})")
    return _load(path, f"bench_arch_{model_type}")


@functools.lru_cache(maxsize=None)
def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(kind: str, metric: str) -> Callable:
    """``read(run)`` of ``bench/<kind>/<metric>.py``."""
    path = BENCH / kind / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
