"""The benchmark's own copy of the paper's Sec. 6.1 world generators.

Copied from ``repro.data.synthetic`` (image tasks),
``repro.data.partition`` (label shards, availability, processor budgets)
and ``repro.fl.experiments`` (``_image_task``, ``align_task_caps``,
``build_setting``), so that a later change to the program cannot move the
yardstick.  ``bench/tests/test_world_pin.py`` checks that this copy and
the program's generators give bit-identical worlds for every
configuration file.

The world is driven by a configuration file (``bench/configs/*.json``):
its ``tasks`` list, in order, consumes one numpy generator seeded by
``data_seed``, exactly as ``build_setting`` does.  Everything here is
numpy; ``build`` returns plain arrays and the harness wraps them.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

PROBE_TAKE = 64   # samples per client the engine's loss probe reads


def make_image_task(rng, n_classes: int, n_per_class: int, side: int = 28):
    freqs = rng.normal(size=(n_classes, 4, 4))
    xs, ys = [], []
    grid = np.stack(np.meshgrid(np.linspace(0, 1, side),
                                np.linspace(0, 1, side)), -1)
    for c in range(n_classes):
        tpl = np.zeros((side, side))
        for i in range(4):
            for j in range(4):
                tpl += freqs[c, i, j] * np.sin(
                    np.pi * ((i + 1) * grid[..., 0] + (j + 1) * grid[..., 1]))
        tpl = tpl / (np.abs(tpl).max() + 1e-9)
        noise = rng.normal(scale=0.35, size=(n_per_class, side, side))
        xs.append(np.clip(tpl[None] + noise, -2, 2))
        ys.append(np.full((n_per_class,), c))
    x = np.concatenate(xs)[..., None].astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]


def label_shard_partition(rng, x, y, n_clients: int, label_frac: float,
                          high_frac: float = 0.1, n_high: int = 120,
                          n_low: int = 12) -> Dict[str, np.ndarray]:
    n_labels = int(y.max() + 1)
    k_labels = max(1, int(round(label_frac * n_labels)))
    by_label = [np.where(y == c)[0] for c in range(n_labels)]
    high = np.zeros(n_clients, bool)
    high[rng.choice(n_clients, max(1, int(high_frac * n_clients)),
                    replace=False)] = True
    counts = np.where(high, n_high, n_low)
    counts = np.maximum(2, (counts * rng.uniform(0.8, 1.2, n_clients))
                        .astype(np.int64))
    cap = int(counts.max())
    xs = np.zeros((n_clients, cap) + x.shape[1:], x.dtype)
    ys = np.zeros((n_clients, cap) + y.shape[1:], y.dtype)
    for i in range(n_clients):
        labels = rng.choice(n_labels, k_labels, replace=False)
        pool = np.concatenate([by_label[c] for c in labels])
        take = rng.choice(pool, counts[i], replace=counts[i] > len(pool))
        pad = np.resize(take, cap)
        xs[i], ys[i] = x[pad], y[pad]
    return {"x": xs, "y": ys, "count": counts.astype(np.int32)}


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


def _image_task(rng, spec: Dict[str, Any], n_clients: int, n_per_class: int,
                label_frac: float) -> Dict[str, Any]:
    x, y = make_image_task(rng, spec["n_classes"], n_per_class)
    n_test = max(64, len(y) // 10)
    part = label_shard_partition(rng, x[n_test:], y[n_test:], n_clients,
                                 label_frac)
    return {"spec": spec, "data": part,
            "test": {"x": x[:n_test], "y": y[:n_test]}}


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
    arrays, ``spec`` being the configuration's entry for it."""
    rng = np.random.default_rng(cfg["data_seed"])
    n = cfg["n_clients"]
    tasks = []
    for spec in cfg["tasks"]:
        if spec["kind"] != "image":
            raise ValueError(f"unknown task kind {spec['kind']!r}")
        tasks.append(_image_task(rng, spec, n, cfg["n_per_class"],
                                 cfg["label_frac"]))
    avail = availability(rng, n, len(tasks), cfg["avail_frac_all"])
    B = processor_budgets(rng, avail)
    align_caps(tasks)
    return tasks, B, avail
