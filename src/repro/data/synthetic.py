"""Synthetic stand-ins for the paper's datasets (repro band 2/5: the data
gate is simulated, per the harness instructions).

* ``make_image_task``  — Fashion-MNIST/EMNIST-like 28x28x1 (CIFAR-like
  32x32x3) class-conditional Gaussian-blob images.  Classes are genuinely
  separable so the CNN's accuracy trajectory is meaningful (orderings
  between FL methods are the claims under test, not absolute accuracy).
* ``make_char_task``   — Shakespeare-like character stream from a per-client
  Markov chain (naturally non-iid across "speakers").
* ``make_token_stream``— token corpus for the production-arch examples.
"""
from __future__ import annotations

import bisect
from typing import Sequence, Tuple, Union

import numpy as np


def make_image_task(rng: np.random.Generator, n_classes: int = 10,
                    n_per_class: int = 400, side: int = 28, in_ch: int = 1
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Class-conditional images: each class = fixed random low-frequency
    template per channel + per-sample noise.  Returns
    (x [M, side, side, in_ch], y [M]).  ``in_ch=1`` draws exactly what the
    one-channel generator always drew (a [.., 1] draw consumes the stream
    as the squeezed one does)."""
    # low-frequency class templates, one per channel
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


def make_char_task(rng: np.random.Generator, vocab: int = 64,
                   n_streams: int = 128,
                   stream_len: Union[int, Sequence[int]] = 512,
                   seq_len: int = 32
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-stream Markov chains (one per Shakespeare "speaker").
    ``stream_len`` is one length for every stream or one per stream (then
    ``n_streams`` of them): a stream of L characters gives L // seq_len
    sequences.  Returns (x [M,seq], y [M,seq], stream_id [M]) with y =
    next-char targets.

    Each step is an inverse-CDF draw from the current character's row,
    one uniform a step: the draws ``rng.choice(vocab, p=row)`` makes, taken
    for the whole stream at once."""
    lens = (np.full(n_streams, stream_len, np.int64)
            if np.ndim(stream_len) == 0 else np.asarray(stream_len, np.int64))
    if lens.shape != (n_streams,):
        raise ValueError(f"{lens.shape[0]} stream lengths for {n_streams} "
                         "streams")
    xs, ys, sid = [], [], []
    for s in range(n_streams):
        # each speaker has its own sparse transition matrix
        trans = rng.dirichlet(np.full(vocab, 0.05), size=vocab)
        cdf = trans.cumsum(axis=1)
        cdf /= cdf[:, -1:]
        rows = cdf.tolist()
        n = int(lens[s])
        stream = np.zeros(n + 1, np.int32)
        c = stream[0] = rng.integers(vocab)
        for t, u in enumerate(rng.random(n).tolist(), start=1):
            c = stream[t] = bisect.bisect_right(rows[c], u)
        n_seq = n // seq_len
        for k in range(n_seq):
            seg = stream[k * seq_len: (k + 1) * seq_len + 1]
            xs.append(seg[:-1])
            ys.append(seg[1:])
            sid.append(s)
    return (np.stack(xs).astype(np.int32), np.stack(ys).astype(np.int32),
            np.asarray(sid, np.int32))


def make_token_stream(rng: np.random.Generator, vocab: int, n_tokens: int,
                      order: int = 2) -> np.ndarray:
    """Zipf-ish token stream with local structure for LM examples."""
    base = rng.zipf(1.3, size=n_tokens).astype(np.int64)
    toks = (base + rng.integers(0, 7, size=n_tokens)) % vocab
    return toks.astype(np.int32)


def make_token_task(rng: np.random.Generator, vocab: int, n_clients: int,
                    cap: int, seq_len: int, n_test: int = 16
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-client LM shards for the real-model task worlds.

    Each client's rows come from its own Zipf stream shifted by a
    client-specific token offset (non-iid vocabulary slices across
    clients, the LM analogue of the label shards).  Returns
    (x [n_clients, cap, seq_len] int32, test_x [n_test, seq_len] int32);
    next-token targets are the sequences themselves (the model's loss
    shifts internally)."""
    x = np.empty((n_clients, cap, seq_len), np.int32)
    for c in range(n_clients):
        stream = make_token_stream(rng, vocab, cap * seq_len)
        x[c] = ((stream + (c * 7) % vocab) % vocab).reshape(cap, seq_len)
    test = make_token_stream(rng, vocab, n_test * seq_len)
    return x, test.reshape(n_test, seq_len)
