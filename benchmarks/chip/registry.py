"""Find a cell's configuration, traffic and per-layer metric readers.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- a workload entry of ``BENCHMARK.json`` names its configuration, its
  traffic and the chips it needs;
- ``configs/<config>.json`` (the configuration's ``file``) holds the
  model as it is run, under its source's own keys;
- ``traffic/<traffic>.json`` holds the traffic mix as parameters that
  one general driver reads;
- ``metrics/<metric>.py`` is the reader of one per-layer metric, with a
  ``read(window) -> float | None`` function.

Adding a cell, a configuration or a metric therefore adds files and
entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class UnknownWorkload(KeyError):
    pass


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownWorkload(
        f"no workload {name!r} in BENCHMARK.json; known: "
        f"{[w['name'] for w in bench['workloads']]}")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = next((c for c in bench["configs"] if c["name"] == name), None)
    if entry is None:
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def load_traffic(name: str, here: Path = HERE) -> dict:
    path = Path(here) / "traffic" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic file {path}")
    with open(path) as f:
        return json.load(f)


def load_reader(metric: str, here: Path = HERE):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = Path(here) / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise KeyError(f"no reader {path} for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
