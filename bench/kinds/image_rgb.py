"""Kind ``image_rgb``: the ``image`` kind's CNN task at a given image side
and channel count, as CIFAR-10's 32x32x3.

A configuration's task entry ``{"kind": "image_rgb", "n_classes",
"channels", "side", "in_ch"}`` (and the configuration's ``n_clients``,
``n_per_class``, ``label_frac``; an entry may hold an ``n_per_class`` of
its own) resolves here.  The generator is the benchmark's copy of
``repro.data.synthetic.make_image_task`` at ``in_ch`` channels (one
low-frequency template per channel and class, noise per channel); the
partition, the plain CNN (``cnn_init`` and ``cnn_logits`` take the side
and the channels) and the FLOP count are the ``image`` kind's own
(``bench/kinds/image.py``), whose faces this module gives too.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from world import load_kind

image = load_kind("image")


def make_image_task(rng, n_classes: int, n_per_class: int, side: int,
                    in_ch: int):
    freqs = rng.normal(size=(n_classes, in_ch, 4, 4))
    xs, ys = [], []
    grid = np.stack(np.meshgrid(np.linspace(0, 1, side),
                                np.linspace(0, 1, side)), -1)
    for c in range(n_classes):
        tpl = np.zeros((side, side, in_ch))
        for i in range(4):
            for j in range(4):
                tpl += freqs[c, :, i, j] * np.sin(
                    np.pi * ((i + 1) * grid[..., 0]
                             + (j + 1) * grid[..., 1]))[..., None]
        tpl = tpl / (np.abs(tpl).max(axis=(0, 1)) + 1e-9)
        noise = rng.normal(scale=0.35, size=(n_per_class, side, side, in_ch))
        xs.append(np.clip(tpl[None] + noise, -2, 2))
        ys.append(np.full((n_per_class,), c))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]


def make_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]
              ) -> Dict[str, Any]:
    x, y = make_image_task(rng, spec["n_classes"],
                           image.n_per_class(spec, cfg), spec["side"],
                           spec["in_ch"])
    n_test = max(64, len(y) // 10)
    part = image.label_shard_partition(rng, x[n_test:], y[n_test:],
                                       cfg["n_clients"], cfg["label_frac"])
    return {"spec": spec, "data": part,
            "test": {"x": x[:n_test], "y": y[:n_test]}}


def program_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]):
    from repro.fl.experiments import _image_task
    return _image_task(rng, spec["name"], cfg["n_clients"],
                       n_classes=spec["n_classes"],
                       channels=spec["channels"],
                       n_per_class=image.n_per_class(spec, cfg),
                       label_frac=cfg["label_frac"], side=spec["side"],
                       in_ch=spec["in_ch"])


def adapter(spec: Dict[str, Any]):
    from repro.fl.experiments import _cnn_adapter
    return _cnn_adapter(spec["n_classes"], spec["channels"], spec["in_ch"],
                        spec["side"])


def init(key, spec: Dict[str, Any]):
    return image.cnn_init(key, spec["n_classes"], spec["channels"],
                          spec["in_ch"], spec["side"])


logits = image.cnn_logits


def forward_flops(spec: Dict[str, Any]) -> int:
    return image.cnn_forward_flops(spec["n_classes"], spec["channels"],
                                   spec["in_ch"], spec["side"])
