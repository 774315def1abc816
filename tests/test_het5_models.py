"""The 5-model world's models against the benchmark's plain references, on
the CPU with seeded random weights: the char LSTM (``models.lstm``) against
``bench/kinds/char.py`` and the 32x32x3 CNN (``models.cnn``) against
``bench/kinds/image.py``, both loaded by path as the harness loads a task
kind (``bench/world.load_kind``); the generators' draws; and the signature
groups of a small world shaped like ``bench/configs/mmfl-het5.json``.

Tolerances: program and reference compute the same float32 arithmetic in
a different order (the program's fused [i f g o] gate product against
the reference's four per-gate products; its step-interleaved layers
against layer after layer), so they part by a few float32 roundings
(~1e-7 relative) a step.  1e-5 relative holds that over six steps with a
hundredfold room, and is ten times under the change a 1e-4 error in one
gate would make."""
import json
import os

import jax
import numpy as np
import pytest

from repro.core.engine import group_tasks
from repro.data import synthetic
from repro.fl.experiments import align_task_caps
from repro.models import cnn, lstm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
RTOL = 1e-5
VOCAB, EMBED, HIDDEN, SEQ = 12, 4, 16, 6
CHAR = {"name": "shakespeare", "kind": "char", "vocab": VOCAB,
        "seq_len": SEQ, "d_embed": EMBED, "d_hidden": HIDDEN}


@pytest.fixture(scope="module")
def kinds():
    """The kind modules, loaded by path from ``bench/kinds``."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(BENCH)
        import world
        yield {k: world.load_kind(k) for k in ("char", "image", "image_rgb")}


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rtol=RTOL):
    """|a - b| <= rtol * max|b| elementwise: relative to the array's
    scale, since single entries of a gradient sit near zero."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * np.abs(b).max())


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, VOCAB)


def test_lstm_init_draws_the_reference_weights(kinds):
    key = jax.random.PRNGKey(7)
    prog = lstm.init(key, VOCAB, d_embed=EMBED, d_hidden=HIDDEN)
    ref = kinds["char"].init(key, CHAR)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lstm_logits_match_reference(kinds, highest):
    p = kinds["char"].init(jax.random.PRNGKey(1), CHAR)
    x = _tokens(2, (5, SEQ))
    out = lstm.apply(p, x)
    assert out.shape == (5, SEQ, VOCAB)
    _close(out, kinds["char"].logits(p, x))


def test_lstm_loss_and_grads_match_reference(kinds, highest):
    from reference import cross_entropy
    p = kinds["char"].init(jax.random.PRNGKey(3), CHAR)
    batch = {"x": _tokens(4, (5, SEQ)), "y": _tokens(5, (5, SEQ))}

    def ref_loss(q):
        return cross_entropy(kinds["char"].logits(q, batch["x"]),
                             batch["y"])

    _close(lstm.loss_fn(p, batch), ref_loss(p))
    g_prog = jax.grad(lstm.loss_fn)(p, batch)
    g_ref = jax.grad(ref_loss)(p)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        _close(a, b)


def test_cnn_at_32x32x3_matches_reference(kinds, highest):
    spec = {"kind": "image_rgb", "n_classes": 10, "channels": 12,
            "side": 32, "in_ch": 3}
    key = jax.random.PRNGKey(9)
    prog = cnn.init(key, 10, 12, 3, side=32)
    ref = kinds["image_rgb"].init(key, spec)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert prog["fc1"]["w"].shape == (8 * 8 * 24, 128)
    assert sum(a.size for a in jax.tree.leaves(prog)) == 200_978
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 32, 32, 3))
    out = cnn.apply(prog, x)
    assert out.shape == (4, 10)
    _close(out, kinds["image"].cnn_logits(ref, x))


def test_char_generator_draws_what_rng_choice_draws():
    """Each step of a speaker's chain is the draw ``rng.choice(vocab,
    p=row)`` makes: the streams and the generator's state after them are
    those of a step-by-step ``rng.choice`` chain."""
    vocab, lens, seq = 48, [256, 97, 400], 24
    rng = np.random.default_rng(11)
    x, y, sid = synthetic.make_char_task(rng, vocab=vocab, n_streams=3,
                                         stream_len=lens, seq_len=seq)
    plain = np.random.default_rng(11)
    rows = []
    for n in lens:
        trans = plain.dirichlet(np.full(vocab, 0.05), size=vocab)
        stream = np.zeros(n + 1, np.int32)
        stream[0] = plain.integers(vocab)
        for t in range(n):
            stream[t + 1] = plain.choice(vocab, p=trans[stream[t]])
        rows += [stream[k * seq:(k + 1) * seq + 1] for k in range(n // seq)]
    np.testing.assert_array_equal(x, np.stack(rows)[:, :-1])
    np.testing.assert_array_equal(y, np.stack(rows)[:, 1:])
    np.testing.assert_array_equal(np.bincount(sid), [n // seq for n in lens])
    assert rng.random() == plain.random()


def test_image_generator_one_channel_draws_unchanged():
    """``in_ch=1`` draws what the one-channel generator drew."""
    def one_channel(rng, n_classes, n_per_class, side=28):
        freqs = rng.normal(size=(n_classes, 4, 4))
        grid = np.stack(np.meshgrid(np.linspace(0, 1, side),
                                    np.linspace(0, 1, side)), -1)
        xs, ys = [], []
        for c in range(n_classes):
            tpl = np.zeros((side, side))
            for i in range(4):
                for j in range(4):
                    tpl += freqs[c, i, j] * np.sin(np.pi * (
                        (i + 1) * grid[..., 0] + (j + 1) * grid[..., 1]))
            tpl = tpl / (np.abs(tpl).max() + 1e-9)
            noise = rng.normal(scale=0.35, size=(n_per_class, side, side))
            xs.append(np.clip(tpl[None] + noise, -2, 2))
            ys.append(np.full((n_per_class,), c))
        x = np.concatenate(xs)[..., None].astype(np.float32)
        y = np.concatenate(ys).astype(np.int32)
        perm = rng.permutation(len(y))
        return x[perm], y[perm]

    a = synthetic.make_image_task(np.random.default_rng(4), 5, 20)
    b = one_channel(np.random.default_rng(4), 5, 20)
    for u, v in zip(a, b):
        assert u.dtype == v.dtype
        np.testing.assert_array_equal(u, v)
    x, _ = synthetic.make_image_task(np.random.default_rng(4), 3, 2,
                                     side=32, in_ch=3)
    assert x.shape == (6, 32, 32, 3) and x.dtype == np.float32


def test_het5_shaped_world_groups_by_architecture(kinds):
    """The configuration's five tasks at small sizes, each made by its
    kind's ``program_task``: the two FMNIST CNNs fuse; the 32x32x3 CIFAR
    CNN, the 26-class EMNIST CNN and the LSTM are groups of their own."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "mmfl-het5.json")))
    small = {"n_per_class": 30}
    tasks_cfg = [dict(t, **small) if t["kind"] != "char" else
                 dict(t, n_streams=24, chars_mu=5.0, chars_sigma=0.5)
                 for t in cfg["tasks"]]
    cfg = dict(cfg, n_clients=12, n_per_class=30, tasks=tasks_cfg)
    rng = np.random.default_rng(cfg["data_seed"])
    tasks = align_task_caps([kinds[t["kind"]].program_task(rng, t, cfg)
                             for t in cfg["tasks"]])
    assert [t.name for t in tasks] == ["fmnist-0", "fmnist-1", "cifar",
                                       "emnist", "shakespeare"]
    assert tasks[2].data["x"].shape[2:] == (32, 32, 3)
    assert tasks[4].data["x"].shape[2:] == (80,)
    assert group_tasks(tasks) == [[0, 1], [2], [3], [4]]
