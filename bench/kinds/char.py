"""Kind ``char``: the Shakespeare character-level language model of the
paper's Sec. 6.1 5-model setting.

The model is the char LSTM that federated-learning papers use for
Shakespeare (McMahan et al., arXiv:1602.05629 Sec. 3; LEAF,
arXiv:1812.01097): an embedding of ``d_embed`` over ``vocab`` characters,
two LSTM layers of ``d_hidden`` units and a softmax head over the
characters, unrolled over ``seq_len`` steps, one next-character loss per
step.

A configuration's task entry ``{"kind": "char", "vocab", "seq_len",
"d_embed", "d_hidden", "n_streams", "chars_mu", "chars_sigma",
"chars_min", "chars_max", "test_frac"}`` resolves here: ``n_streams``
speakers, speaker k's stream of L_k characters with L_k lognormal
(``chars_mu``, ``chars_sigma``) clipped to [``chars_min``, ``chars_max``]
and giving L_k // ``seq_len`` sequences; the first ``test_frac`` of all
sequences are the test set, and each of the configuration's clients is
one of the remaining speakers.  The faces are ``bench/kinds/image.py``'s:

  * ``make_task(rng, spec, cfg)``: the benchmark's copy of the program's
    generator (``repro.data.synthetic.make_char_task``,
    ``repro.data.partition.stream_partition``,
    ``repro.fl.experiments._char_task``), numpy only;
  * ``program_task(rng, spec, cfg)``: the program's own generator;
  * ``adapter(spec)``: the program's ``ModelAdapter``
    (``repro.fl.experiments._lstm_adapter``);
  * ``init(key, spec)`` and ``logits(params, x)``: a plain ``jax.numpy``
    LSTM written from the published equations, importing nothing of the
    program; it draws its weights as ``repro.models.lstm.init`` draws them
    and holds them in its layout (gates i, f, g, o side by side), since
    the reference follows the program's random streams;
  * ``forward_flops(spec)``: model FLOPs of one sequence's forward pass.
"""
from __future__ import annotations

import bisect
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# generator (numpy)
# ---------------------------------------------------------------------------
def stream_lengths(rng, spec: Dict[str, Any]) -> np.ndarray:
    """Characters per speaker: lognormal, clipped, whole characters."""
    lens = rng.lognormal(spec["chars_mu"], spec["chars_sigma"],
                         spec["n_streams"])
    return np.clip(lens, spec["chars_min"], spec["chars_max"]).astype(
        np.int64)


def n_test(spec: Dict[str, Any], lens: np.ndarray) -> int:
    """The first ``test_frac`` of all the speakers' sequences."""
    return int(spec["test_frac"] * int((lens // spec["seq_len"]).sum()))


def make_char_task(rng, vocab: int, lens: np.ndarray, seq_len: int):
    """One Markov chain per speaker over a sparse transition matrix of its
    own; each step an inverse-CDF draw, one uniform a step."""
    xs, ys, sid = [], [], []
    for s, n in enumerate(lens.tolist()):
        trans = rng.dirichlet(np.full(vocab, 0.05), size=vocab)
        cdf = trans.cumsum(axis=1)
        cdf /= cdf[:, -1:]
        rows = cdf.tolist()
        stream = np.zeros(n + 1, np.int32)
        c = stream[0] = rng.integers(vocab)
        for t, u in enumerate(rng.random(n).tolist(), start=1):
            c = stream[t] = bisect.bisect_right(rows[c], u)
        for k in range(n // seq_len):
            seg = stream[k * seq_len: (k + 1) * seq_len + 1]
            xs.append(seg[:-1])
            ys.append(seg[1:])
            sid.append(s)
    return (np.stack(xs).astype(np.int32), np.stack(ys).astype(np.int32),
            np.asarray(sid, np.int32))


def stream_partition(rng, x, y, stream_id, n_clients: int
                     ) -> Dict[str, np.ndarray]:
    """Each client one speaker, drawn without replacement; its sequences
    wrap-padded to the largest count."""
    streams = rng.choice(np.unique(stream_id), n_clients, replace=False)
    counts = np.array([(stream_id == s).sum() for s in streams])
    cap = int(counts.max())
    xs = np.zeros((n_clients, cap) + x.shape[1:], x.dtype)
    ys = np.zeros((n_clients, cap) + y.shape[1:], y.dtype)
    for i, s in enumerate(streams):
        pad = np.resize(np.where(stream_id == s)[0], cap)
        xs[i], ys[i] = x[pad], y[pad]
    return {"x": xs, "y": ys, "count": counts.astype(np.int32)}


def make_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]
              ) -> Dict[str, Any]:
    lens = stream_lengths(rng, spec)
    x, y, sid = make_char_task(rng, spec["vocab"], lens, spec["seq_len"])
    k = n_test(spec, lens)
    part = stream_partition(rng, x[k:], y[k:], sid[k:], cfg["n_clients"])
    return {"spec": spec, "data": part, "test": {"x": x[:k], "y": y[:k]}}


def program_task(rng, spec: Dict[str, Any], cfg: Dict[str, Any]):
    from repro.fl.experiments import _char_task
    lens = stream_lengths(rng, spec)
    return _char_task(rng, spec["name"], cfg["n_clients"],
                      vocab=spec["vocab"], seq_len=spec["seq_len"],
                      stream_lens=lens, n_test=n_test(spec, lens),
                      d_embed=spec["d_embed"], d_hidden=spec["d_hidden"])


# ---------------------------------------------------------------------------
# the program's model
# ---------------------------------------------------------------------------
def adapter(spec: Dict[str, Any]):
    from repro.fl.experiments import _lstm_adapter
    return _lstm_adapter(spec["vocab"], spec["d_embed"], spec["d_hidden"])


# ---------------------------------------------------------------------------
# plain reference model
# ---------------------------------------------------------------------------
def lstm_init(key, vocab: int, d_embed: int, d_hidden: int):
    """Embedding N(0, 0.02^2); each layer's input and recurrent weights
    uniform in +-1/sqrt(fan-in), gates i, f, g, o side by side; head
    uniform in +-1/sqrt(d_hidden); biases zero."""
    k_emb, k_1, k_2, k_head = jax.random.split(key, 4)

    def layer(k, d_in):
        k_x, k_h = jax.random.split(k)
        s_x, s_h = 1 / math.sqrt(d_in), 1 / math.sqrt(d_hidden)
        return {"wx": jax.random.uniform(k_x, (d_in, 4 * d_hidden),
                                         jnp.float32, -s_x, s_x),
                "wh": jax.random.uniform(k_h, (d_hidden, 4 * d_hidden),
                                         jnp.float32, -s_h, s_h),
                "b": jnp.zeros((4 * d_hidden,), jnp.float32)}

    s = 1 / math.sqrt(d_hidden)
    return {
        "embed": jax.random.normal(k_emb, (vocab, d_embed),
                                   jnp.float32) * 0.02,
        "cell1": layer(k_1, d_embed),
        "cell2": layer(k_2, d_hidden),
        "head": {"w": jax.random.uniform(k_head, (d_hidden, vocab),
                                         jnp.float32, -s, s),
                 "b": jnp.zeros((vocab,), jnp.float32)},
    }


def lstm_layer(p, xs):
    """One LSTM layer over time, xs [S, B, D] -> hs [S, B, H]:
        i = s(x W_xi + h W_hi + b_i)   f = s(x W_xf + h W_hf + b_f)
        g = tanh(x W_xg + h W_hg + b_g)   o = s(x W_xo + h W_ho + b_o)
        c' = f c + i g                  h' = o tanh(c')
    from h = c = 0."""
    H = p["wh"].shape[0]
    gate = lambda x, h, k: (x @ p["wx"][:, k * H:(k + 1) * H]
                            + h @ p["wh"][:, k * H:(k + 1) * H]
                            + p["b"][k * H:(k + 1) * H])

    def step(carry, x):
        h, c = carry
        i = jax.nn.sigmoid(gate(x, h, 0))
        f = jax.nn.sigmoid(gate(x, h, 1))
        g = jnp.tanh(gate(x, h, 2))
        o = jax.nn.sigmoid(gate(x, h, 3))
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((xs.shape[1], H), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero), xs)
    return hs


def lstm_logits(p, tokens):
    """tokens [B, S] int -> next-character logits [B, S, vocab]."""
    xs = p["embed"][tokens].swapaxes(0, 1)
    hs = lstm_layer(p["cell2"], lstm_layer(p["cell1"], xs))
    return hs.swapaxes(0, 1) @ p["head"]["w"] + p["head"]["b"]


def init(key, spec: Dict[str, Any]):
    return lstm_init(key, spec["vocab"], spec["d_embed"], spec["d_hidden"])


logits = lstm_logits


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------
def lstm_forward_flops(vocab: int, seq_len: int, d_embed: int,
                       d_hidden: int) -> int:
    """Per sequence: at each step the four gates' input and recurrent
    products of both layers, and the head."""
    H = d_hidden
    per_step = (2 * 4 * H * (d_embed + H) + 2 * 4 * H * (H + H)
                + 2 * H * vocab)
    return seq_len * per_step


def forward_flops(spec: Dict[str, Any]) -> int:
    return lstm_forward_flops(spec["vocab"], spec["seq_len"],
                              spec["d_embed"], spec["d_hidden"])
