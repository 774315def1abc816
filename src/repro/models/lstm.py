"""Character-level LSTM language model (paper §6.1 Shakespeare task):
embedding + 2-layer LSTM + linear head.  Pure JAX (lax.scan over time,
under the scope ``lstm.recurrence``).  Gates i, f, g, o stacked in that
order on the last axis of ``wx``/``wh``/``b``; no peepholes; zero initial
state."""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _lstm_cell_init(key, d_in: int, d_h: int, dtype):
    k1, k2 = jax.random.split(key)
    s_in, s_h = 1 / math.sqrt(d_in), 1 / math.sqrt(d_h)
    return {
        "wx": jax.random.uniform(k1, (d_in, 4 * d_h), dtype, -s_in, s_in),
        "wh": jax.random.uniform(k2, (d_h, 4 * d_h), dtype, -s_h, s_h),
        "b": jnp.zeros((4 * d_h,), dtype),
    }


def init(key, vocab: int, d_embed: int = 32, d_hidden: int = 128,
         dtype=jnp.float32) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "embed": jax.random.normal(k1, (vocab, d_embed), dtype) * 0.02,
        "cell1": _lstm_cell_init(k2, d_embed, d_hidden, dtype),
        "cell2": _lstm_cell_init(k3, d_hidden, d_hidden, dtype),
        "head": {"w": jax.random.uniform(k4, (d_hidden, vocab), dtype,
                                         -1 / math.sqrt(d_hidden),
                                         1 / math.sqrt(d_hidden)),
                 "b": jnp.zeros((vocab,), dtype)},
    }


def _cell(p, carry, x):
    h, c = carry
    z = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h


def apply(params, tokens: jnp.ndarray) -> jnp.ndarray:
    """tokens [B,S] -> logits [B,S,V]."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)          # [B,S,E]
    d_h = params["cell1"]["wh"].shape[0]

    def step(carry, x_t):
        (h1, c1), (h2, c2) = carry
        (h1, c1), y1 = _cell(params["cell1"], (h1, c1), x_t)
        (h2, c2), y2 = _cell(params["cell2"], (h2, c2), y1)
        return ((h1, c1), (h2, c2)), y2

    zeros = jnp.zeros((B, d_h), x.dtype)
    init_carry = ((zeros, zeros), (zeros, zeros))
    # the serial part of the model, named for a profiler trace
    with jax.named_scope("lstm.recurrence"):
        _, ys = jax.lax.scan(step, init_carry, x.swapaxes(0, 1))
    h = ys.swapaxes(0, 1)                                   # [B,S,H]
    return h @ params["head"]["w"] + params["head"]["b"]


def loss_fn(params, batch) -> jnp.ndarray:
    """Next-char CE.  batch: {"x": [B,S] int, "y": [B,S] int}."""
    logits = apply(params, batch["x"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def accuracy(params, batch) -> jnp.ndarray:
    logits = apply(params, batch["x"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
