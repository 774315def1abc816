"""The benchmark's own copy of the paper's Sec. 6.1 world generators.

Copied from ``repro.data.partition`` (availability, processor budgets)
and ``repro.fl.experiments`` (``align_task_caps``, ``build_setting``'s
order of draws), so that a later change to the program cannot move the
yardstick.  Each task is made by its kind's module,
``bench/kinds/<kind>.py`` (``load_kind``), which holds the copy of the
program's generator for that task.  ``bench/tests/test_world_pin.py``
checks that this copy and the program's generators give bit-identical
worlds for every configuration file.

The world is driven by a configuration file (``bench/configs/*.json``):
its ``tasks`` list, in order, consumes one numpy generator seeded by
``data_seed``, exactly as ``build_setting`` does.  ``build`` returns plain
numpy arrays and the harness wraps them.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List, Tuple

import numpy as np

PROBE_TAKE = 64   # samples per client the engine's loss probe reads
HERE = os.path.dirname(os.path.abspath(__file__))
# where a task kind's module is looked for, in order
KIND_DIRS = [os.path.join(HERE, "kinds")]
_KINDS: Dict[str, Any] = {}


def load_kind(kind: str):
    """The module of a task kind: ``bench/kinds/<kind>.py``, loaded as
    ``run.load_reader`` loads a metric's reader."""
    for d in KIND_DIRS:
        path = os.path.join(d, kind + ".py")
        if path in _KINDS:
            return _KINDS[path]
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                "bench_kind_" + kind.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _KINDS[path] = mod
            return mod
    raise ValueError(f"unknown task kind {kind!r}: no module "
                     f"bench/kinds/{kind}.py")


def availability(rng, n_clients: int, n_models: int, frac_all: float
                 ) -> np.ndarray:
    avail = np.ones((n_clients, n_models), bool)
    limited = rng.choice(n_clients,
                         max(0, int(round((1 - frac_all) * n_clients))),
                         replace=False)
    for i in limited:
        avail[i, rng.integers(n_models)] = False
    return avail


def processor_budgets(rng, avail: np.ndarray) -> np.ndarray:
    n = avail.shape[0]
    si = avail.sum(axis=1)
    u = rng.permutation(n)
    B = np.empty(n, np.int64)
    q1, q2 = n // 4, n // 4 + n // 2
    B[u[:q1]] = si[u[:q1]]
    B[u[q1:q2]] = np.ceil(si[u[q1:q2]] / 2).astype(np.int64)
    B[u[q2:]] = 1
    return np.maximum(B, 1)


def _shape_key(task: Dict[str, Any]) -> Tuple:
    """Every data and test shape but the sample cap: tasks that agree on
    it are aligned to one cap, as ``align_task_caps`` does."""
    d, t = task["data"], task["test"]
    return (tuple((k, v.shape[:1] + v.shape[2:], str(v.dtype))
                  for k, v in sorted(d.items()) if k != "count"),
            tuple((k, v.shape, str(v.dtype)) for k, v in sorted(t.items())))


def align_caps(tasks: List[Dict[str, Any]]) -> None:
    """Wrap-pad the sample axis of same-shaped tasks to their largest cap
    (``align_task_caps``); caps under the probe boundary stay as drawn."""
    target: Dict[Tuple, int] = {}
    for t in tasks:
        key = _shape_key(t)
        target[key] = max(target.get(key, 0), t["data"]["x"].shape[1])
    for t in tasks:
        cap, to = t["data"]["x"].shape[1], target[_shape_key(t)]
        if cap == to or cap < PROBE_TAKE:
            continue
        wrap = np.arange(to) % cap
        for k in ("x", "y"):
            t["data"][k] = t["data"][k][:, wrap]


def build(cfg: Dict[str, Any]
          ) -> Tuple[List[Dict[str, Any]], np.ndarray, np.ndarray]:
    """(tasks, B, avail) for a configuration.  Each task is
    ``{"spec", "data": {"x", "y", "count"}, "test": {"x", "y"}}`` of numpy
    arrays, ``spec`` being the configuration's entry for it, made by its
    kind's ``make_task``."""
    rng = np.random.default_rng(cfg["data_seed"])
    n = cfg["n_clients"]
    tasks = [load_kind(spec["kind"]).make_task(rng, spec, cfg)
             for spec in cfg["tasks"]]
    avail = availability(rng, n, len(tasks), cfg["avail_frac_all"])
    B = processor_budgets(rng, avail)
    align_caps(tasks)
    return tasks, B, avail
