"""Small CNN matching the paper's §6.1 classifier: 2 conv + 2 pool + 2 linear.

Used by the faithful-reproduction experiments (Fashion-MNIST / EMNIST-like
synthetic 28x28x1 tasks, CIFAR-like 32x32x3, model-specific output sizes)."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def init(key, n_classes: int, channels: int = 16, in_ch: int = 1,
         dtype=jnp.float32, side: int = 28) -> Dict:
    """Params for ``side`` x ``side`` x ``in_ch`` inputs (28x28x1 for the
    Fashion-MNIST/EMNIST-like tasks, 32x32x3 for CIFAR-10)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv_init(k, kh, kw, cin, cout):
        scale = 1.0 / math.sqrt(kh * kw * cin)
        return jax.random.uniform(k, (kh, kw, cin, cout), dtype, -scale, scale)

    c2 = channels * 2
    flat = (side // 4) ** 2 * c2  # two 2x2 pools: 28 -> 7, 32 -> 8
    hidden = 128
    return {
        "conv1": {"w": conv_init(k1, 3, 3, in_ch, channels),
                  "b": jnp.zeros((channels,), dtype)},
        "conv2": {"w": conv_init(k2, 3, 3, channels, c2),
                  "b": jnp.zeros((c2,), dtype)},
        "fc1": {"w": jax.random.uniform(k3, (flat, hidden), dtype,
                                        -1 / math.sqrt(flat), 1 / math.sqrt(flat)),
                "b": jnp.zeros((hidden,), dtype)},
        "fc2": {"w": jax.random.uniform(k4, (hidden, n_classes), dtype,
                                        -1 / math.sqrt(hidden), 1 / math.sqrt(hidden)),
                "b": jnp.zeros((n_classes,), dtype)},
    }


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _pool_reshaped(x):
    n, h, w, c = x.shape
    x = x[:, :h // 2 * 2, :w // 2 * 2]
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


@jax.custom_jvp
def _pool(x):
    """2x2 stride-2 max-pool, VALID (an odd last row or column is dropped).

    Forward-only (loss probe, evaluation: shared params over a large batch)
    it is a reduce_window, which the TPU fuses into the conv's output as laid
    out; a reshape there splits the batch on the lanes and copies the whole
    activation.  Differentiated (local SGD, vmapped over per-client params)
    it is a reshape-and-max: the grouped conv puts W on the lanes, where a
    reduce_window strides along them and its backward is a
    select-and-scatter, while the reshape lowers to plain reduces.  Both
    take the max of the same four values; a gradient at a tie is split
    evenly rather than given to the first."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


@_pool.defjvp
def _pool_jvp(primals, tangents):
    return jax.jvp(_pool_reshaped, primals, tangents)


def apply(params, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, side, side, in_ch] -> logits [B, n_classes]."""
    h = jax.nn.relu(_conv(params["conv1"], x))
    h = _pool(h)
    h = jax.nn.relu(_conv(params["conv2"], h))
    h = _pool(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def loss_fn(params, batch) -> jnp.ndarray:
    logits = apply(params, batch["x"])
    labels = batch["y"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def accuracy(params, batch) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(apply(params, batch["x"]), -1) == batch["y"])
                    .astype(jnp.float32))
