"""Finding a cell's files by name: ``BENCHMARK.json`` names each workload's
configuration and traffic mix, and the files are found from those names.

* ``bench/configs/<config>.json``: the model as it is run (``model``), its
  registry name (``arch``), source, cuts, assumptions and deployment.
* ``bench/traffic/<mix>.json``: sequence length, batch per worker, workers,
  operator, inner optimizer, pool of batches.
* ``bench/limits/<workload>.json``: the numbers that decide ``correct`` and
  the limit of each, with the readings each limit was set from.
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
* ``bench/layers/<kind>.py``: the reference of one layer kind of a
  configuration's ``pattern`` (``bench/reference.py``).
* ``bench/readings/<name>.py``: a number the comparison reads beside its
  built-in ones (``bench/compare.py``).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from typing import Any, Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named(kind: str, name: str, ext: str = ".json") -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return os.path.join(BENCH, kind, name + ext)


def config_path(name: str) -> str:
    return _named("configs", name)


def traffic_path(name: str) -> str:
    return _named("traffic", name)


def limits_path(workload: str) -> str:
    return _named("limits", workload)


def metric_path(metric: str) -> str:
    return _named("metrics", metric, ".py")


def resolve(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell needs, found by the workload's name."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_doc = load_json(config_path(cell["config"]))
    traffic = load_json(traffic_path(cell["traffic"]))
    lp = limits_path(workload)
    limits = load_json(lp) if os.path.exists(lp) else None
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return dict(cell=cell, config=configs[cell["config"]], config_doc=config_doc,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, run_seconds=bench["run_seconds"])


def module_path(directory: str, name: str, what: str) -> str:
    """``<directory>/<name>.py``; a name with no file is an error that names
    the file to add."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {what} name {name!r}")
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no {what} {name!r}: add {os.path.relpath(path, ROOT)}")
    return path


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """The module of one file found by name, loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return load_module(metric_path(metric)).read
