"""Kind ``image``: the paper's Sec. 6.1 Fashion-MNIST-like CNN task.

A configuration's task entry ``{"kind": "image", "n_classes", "channels"}``
(and the configuration's ``n_clients``, ``n_per_class``, ``label_frac``; an
entry may hold an ``n_per_class`` of its own) resolves here.  The faces a
kind module gives the harness:

  * ``make_task(rng, spec, cfg)``: the benchmark's copy of the program's
    generator (``repro.data.synthetic.make_image_task``,
    ``repro.data.partition.label_shard_partition``,
    ``repro.fl.experiments._image_task``), numpy only;
  * ``program_task(rng, spec, cfg)``: the program's own generator, which
    ``bench/tests/test_world_pin.py`` holds the copy to, bit for bit;
  * ``adapter(spec)``: the program's ``ModelAdapter`` for the task;
  * ``init(key, spec)`` and ``logits(params, x)``: the plain ``jax.numpy``
    reference model, importing nothing of the program;
  * ``forward_flops(spec)``: model FLOPs of one sample's forward pass.

Only ``program_task`` and ``adapter`` import the program, each inside the
function.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# generator (numpy)
# ---------------------------------------------------------------------------
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


def n_per_class(spec: Dict[str, Any], cfg: Dict[str, Any]) -> int:
    """The task's own ``n_per_class`` where it has one (the 5-model world's
    EMNIST-like task holds half as many), else the configuration's."""
    return spec.get("n_per_class", cfg["n_per_class"])


def make_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]
              ) -> Dict[str, Any]:
    x, y = make_image_task(rng, spec["n_classes"], n_per_class(spec, cfg))
    n_test = max(64, len(y) // 10)
    part = label_shard_partition(rng, x[n_test:], y[n_test:],
                                 cfg["n_clients"], cfg["label_frac"])
    return {"spec": spec, "data": part,
            "test": {"x": x[:n_test], "y": y[:n_test]}}


def program_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]):
    from repro.fl.experiments import _image_task
    return _image_task(rng, spec["name"], cfg["n_clients"],
                       n_classes=spec["n_classes"],
                       channels=spec["channels"],
                       n_per_class=n_per_class(spec, cfg),
                       label_frac=cfg["label_frac"])


# ---------------------------------------------------------------------------
# the program's model
# ---------------------------------------------------------------------------
def adapter(spec: Dict[str, Any]):
    from repro.fl.experiments import _cnn_adapter
    return _cnn_adapter(spec["n_classes"], spec["channels"])


# ---------------------------------------------------------------------------
# plain reference model
# ---------------------------------------------------------------------------
def cnn_init(key, n_classes: int, channels: int, in_ch: int = 1,
             side: int = 28):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv(k, cin, cout):
        s = 1.0 / math.sqrt(9 * cin)
        return jax.random.uniform(k, (3, 3, cin, cout), jnp.float32, -s, s)

    c2, hidden = 2 * channels, 128
    flat = (side // 4) ** 2 * c2
    sf, sh = 1 / math.sqrt(flat), 1 / math.sqrt(hidden)
    return {
        "conv1": {"w": conv(k1, in_ch, channels),
                  "b": jnp.zeros((channels,), jnp.float32)},
        "conv2": {"w": conv(k2, channels, c2),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": jax.random.uniform(k3, (flat, hidden), jnp.float32,
                                        -sf, sf),
                "b": jnp.zeros((hidden,), jnp.float32)},
        "fc2": {"w": jax.random.uniform(k4, (hidden, n_classes),
                                        jnp.float32, -sh, sh),
                "b": jnp.zeros((n_classes,), jnp.float32)},
    }


def cnn_logits(p, x):
    """2 conv (3x3, SAME) + 2 max-pool (2x2) + 2 linear, ReLU between."""
    def conv(q, h):
        return jax.lax.conv_general_dilated(
            h, q["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + q["b"]

    def pool(h):
        n, hh, ww, c = h.shape
        return h.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))

    h = pool(jax.nn.relu(conv(p["conv1"], x)))
    h = pool(jax.nn.relu(conv(p["conv2"], h)))
    h = jax.nn.relu(h.reshape(h.shape[0], -1) @ p["fc1"]["w"]
                    + p["fc1"]["b"])
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def init(key, spec: Dict[str, Any]):
    return cnn_init(key, spec["n_classes"], spec["channels"])


logits = cnn_logits


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------
def cnn_forward_flops(n_classes: int, channels: int, in_ch: int = 1,
                      side: int = 28, hidden: int = 128) -> int:
    """Per sample: conv1, conv2 (3x3, SAME, the second at half the side
    after pooling), fc1, fc2."""
    c2, half, flat = 2 * channels, side // 2, (side // 4) ** 2 * 2 * channels
    return (side * side * 9 * in_ch * channels * 2
            + half * half * 9 * channels * c2 * 2
            + flat * hidden * 2
            + hidden * n_classes * 2)


def forward_flops(spec: Dict[str, Any]) -> int:
    return cnn_forward_flops(spec["n_classes"], spec["channels"])
