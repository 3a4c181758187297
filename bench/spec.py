"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout root lists the cells.  Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a
file of its own, found by the name that ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the model as it is run;
- ``bench/traffic/<traffic>.json``: batch, sequence length, optimizer and
  schedule of the training traffic;
- ``bench/metrics/<metric>.py``: a reader with ``read(record)`` that returns
  the metric's number, or None where the run holds nothing to read.  A
  metric named ``<base>.<part>`` reads with ``<base>``'s reader where it
  has none of its own: the same quantity in cells that report another
  end-to-end metric (``step.mfu.host_bound`` beside ``step.mfu``);
- ``bench/models/<family>.py``: a model family's reference layers and the
  least FLOPs of its training step, by the configuration's ``family``;
- ``bench/kernels/<kernel>.py``: the least work of one call of a kernel, by
  its ``pallas_call`` name.

Adding a cell, configuration or metric adds files and entries; no file that
is already here needs an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def has_module(kind: str, name: str) -> bool:
    return os.path.exists(os.path.join(BENCH, kind, f"{name}.py"))


@functools.cache
def load_module(kind: str, name: str):
    """The module of ``bench/<kind>/<name>.py``, loaded once."""
    if not has_module(kind, name):
        raise KeyError(f"no file {kind}/{name}.py under bench/")
    tag = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        tag, os.path.join(BENCH, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``, or of the
    longest ``.``-separated head of ``name`` that has a file."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        head = ".".join(parts[:n])
        if has_module("metrics", head):
            return load_module("metrics", head).read
    raise KeyError(f"no reader for metric {name!r} under bench/metrics/")


def cell(name: str, bench: dict | None = None) -> dict:
    """One cell with its configuration, traffic and metric lists resolved."""
    bench = bench or load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    reported = {m["name"] for m in end_to_end}

    def applies(m):
        """A per-layer metric without a list of cells is reported wherever
        the end-to-end metric it moves is."""
        if "workloads" in m:
            return name in m["workloads"]
        return m["moves"] in reported

    return {
        "workload": w,
        "config": load_config(w["config"]),
        "traffic": load_traffic(w["traffic"]),
        "end_to_end": end_to_end,
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }
