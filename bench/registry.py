"""Finds a cell, a configuration, a traffic mix and a metric by name.

Each lives in a file of its own under the benchmark's directory, so a later
change adds one by adding a file:

* ``workloads/<cell>.json`` — configuration and traffic names, rate,
  warm-up seconds, the correctness sample's size and the limits;
* ``configs/<config>.json`` — the model block the program runs, its
  published source, what was reduced or assumed, and the serving layout;
* ``traffic/<mix>.json`` — the parameters `generator.Mix` reads;
* ``metrics/<metric>.py`` — ``value(record)`` returning a number, or None
  when the run holds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def _json(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def cell(name: str, root: str = BENCH) -> dict:
    d = _json(root, "workloads", name)
    for key in ("config", "traffic", "rate", "warmup_s", "sample_tokens",
                "sample_requests", "limits"):
        if key not in d:
            raise ValueError(f"cell {name}: missing {key!r}")
    return d


def config(name: str, root: str = BENCH) -> dict:
    return _json(root, "configs", name)


def traffic(name: str, root: str = BENCH):
    from generator import Mix

    return Mix.from_dict(name, _json(root, "traffic", name))


def metric(name: str, root: str = BENCH):
    """The ``value`` function of ``metrics/<name>.py``."""
    path = os.path.join(root, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no metric named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.value
