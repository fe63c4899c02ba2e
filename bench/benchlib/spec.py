"""Reads BENCHMARK.json and finds each cell's parts by name.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found from the name that BENCHMARK.json gives:

  * a configuration entry's ``file`` (``bench/configs/<config>.json``): its sizes,
    source and guarantees, and the ``family`` whose module
    (``bench/configs/<family>.py``) makes its data and holds its plain reference;
  * a traffic mix ``bench/traffic/<traffic>.json``: parameters that the general
    generator (:mod:`benchlib.traffic`) reads;
  * a per-layer metric ``bench/metrics/<metric>.py``: a ``read(run)`` function.

A new cell, configuration, mix or metric is new files plus new entries in
BENCHMARK.json; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def checked_name(name: str) -> str:
    """A name as BENCHMARK.json allows it; it is also a file name under bench/."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"bad name {name!r}")
    return name


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no {path}") from None


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], checked_name(name), "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration file of config ``name``, as it is run."""
    entry = _entry(bench["configs"], checked_name(name), "config")
    path = root / entry["file"]
    if not path.is_file():
        raise SpecError(f"config file {path} is missing")
    cfg = json.loads(path.read_text())
    cfg.setdefault("name", name)
    return cfg


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    path = bench_dir / "traffic" / f"{checked_name(name)}.json"
    if not path.is_file():
        raise SpecError(f"traffic mix {path} is missing")
    mix = json.loads(path.read_text())
    mix.setdefault("name", name)
    return mix


def _load_module(path: Path, prefix: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    mod_name = prefix + re.sub(r"[^A-Za-z0-9_]", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module      # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def family(cfg: dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The module that makes a configuration's data and its plain reference."""
    return _load_module(
        bench_dir / "configs" / f"{checked_name(cfg['family'])}.py", "benchfamily_"
    )


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _load_module(bench_dir / "metrics" / f"{checked_name(name)}.py", "benchmetric_")


def _applies(metric: dict, cell: str) -> bool:
    cells: Optional[List[str]] = metric.get("workloads")
    return cells is None or cell in cells


def cell_metrics(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics that cell ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    reported = {m["name"] for m in e2e}
    layer = [
        m for m in bench["per_layer"]
        if _applies(m, cell) and m["moves"] in reported
    ]
    return {"end_to_end": e2e, "per_layer": layer}
