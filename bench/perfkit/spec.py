"""Find a cell's pieces by name: the cell in ``BENCHMARK.json``, its
configuration file, the model module of the configuration's
``model_type``, its traffic mix and the per-layer metric readers.

Adding a configuration, a model, a mix or a metric means adding a file and
an entry; nothing here names one."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import types
from typing import Callable, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    pass


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r}; known: "
                    f"{[c['name'] for c in bench['workloads']]}")


def find_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            cfg = json.loads((root / entry["file"]).read_text())
            cfg["name"] = name
            return cfg
    raise SpecError(f"no configuration {name!r}")


def load_mix(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``. A mix may name a
    ``base`` mix whose keys it overrides (the same traffic at another rate,
    say), so a variant is a data file of a few lines."""
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    base = mix.pop("base", None)
    if base is not None:
        merged = load_mix(base, bench_dir)
        merged.update(mix)
        mix = merged
    mix["name"] = name
    return mix


def metrics_for(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that
    ``cell`` reports: those without a ``workloads`` key, and those that
    list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def _load(kind: str, name: str, path: pathlib.Path) -> types.ModuleType:
    """Load ``path`` afresh as a module, registered under its name while
    it runs (a dataclass looks its module up there)."""
    spec = importlib.util.spec_from_file_location(
        f"perfkit_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg: dict, bench_dir: pathlib.Path = BENCH_DIR) -> types.ModuleType:
    """The module ``bench/models/<model_type>.py`` of a configuration's
    published ``model_type``: its shape, weights, reference and work counts
    (the contract is in ``bench/models/__init__.py``)."""
    name = cfg.get("model_type")
    if not name:
        raise SpecError(f"configuration {cfg.get('name')!r} names no model_type")
    path = bench_dir / "models" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no model module for model_type {name!r} at {path}")
    return _load("model", name, path)


def metric_reader(name: str,
                  bench_dir: pathlib.Path = BENCH_DIR) -> Callable[..., Optional[float]]:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    return _load("metric", name, path).read
