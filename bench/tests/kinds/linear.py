"""Kind ``linear``, for ``bench/tests/test_kinds.py`` only: a separable
linear-softmax task, ``{"kind": "linear", "n_feat", "n_classes"}``.

It lives outside ``bench/kinds/`` to show that a kind the harness has never
seen enters through a module of its own.  The program's model is
``fl.experiments._linear_adapter``; the reference's is written here."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

CAP = 64   # samples a client holds at most


def make_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]
              ) -> Dict[str, Any]:
    n, f, k = cfg["n_clients"], spec["n_feat"], spec["n_classes"]
    W = rng.normal(size=(f, k))
    x = rng.normal(size=(n, CAP, f)).astype(np.float32)
    y = np.argmax(x @ W + 0.5 * rng.normal(size=(n, CAP, k)),
                  axis=-1).astype(np.int32)
    count = rng.integers(8, CAP + 1, n).astype(np.int32)
    xt = rng.normal(size=(256, f)).astype(np.float32)
    yt = np.argmax(xt @ W, axis=-1).astype(np.int32)
    return {"spec": spec, "data": {"x": x, "y": y, "count": count},
            "test": {"x": xt, "y": yt}}


def adapter(spec: Dict[str, Any]):
    from repro.fl.experiments import _linear_adapter
    return _linear_adapter(spec["n_feat"], spec["n_classes"])


def init(key, spec: Dict[str, Any]):
    k1, _ = jax.random.split(key)
    return {"w": 0.01 * jax.random.normal(
                k1, (spec["n_feat"], spec["n_classes"])),
            "b": jnp.zeros((spec["n_classes"],))}


def logits(p, x):
    return x @ p["w"] + p["b"]


def forward_flops(spec: Dict[str, Any]) -> int:
    return 2 * spec["n_feat"] * spec["n_classes"]
