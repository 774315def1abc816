"""The benchmark's model operation counts against hand counts, and the
counts of the ``mmfl-cnn3`` cells' world."""
import json
import os

import numpy as np

import flops
import world
from conftest import BENCH

CNN = {"kind": "image", "n_classes": 10, "channels": 8}


def test_cnn_forward_matches_hand_count():
    conv1 = 28 * 28 * 9 * 1 * 8 * 2
    conv2 = 14 * 14 * 9 * 8 * 16 * 2
    fc1 = 784 * 128 * 2
    fc2 = 128 * 10 * 2
    assert (conv1, conv2, fc1, fc2) == (112_896, 451_584, 200_704, 2_560)
    assert flops.forward_flops(CNN) == conv1 + conv2 + fc1 + fc2


def test_round_flops_counts_probe_and_training():
    counts = np.array([[10], [100]])
    per = flops.round_flops([CNN], counts, np.array([2.0]), local_steps=5,
                            batch=16)
    fwd = flops.forward_flops(CNN)
    assert per == fwd * (10 + 64) + 3 * fwd * 5 * 16 * 2


def test_cnn3_world_counts_unchanged():
    """``round_flops`` and ``eval_flops`` over ``mmfl-cnn3``'s world, as
    the harness counted them before task kinds had modules of their own."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "mmfl-cnn3.json")))
    tasks, _, _ = world.build(cfg)
    specs = [t["spec"] for t in tasks]
    counts = np.stack([t["data"]["count"] for t in tasks], 1)
    assert flops.round_flops(specs, counts, np.array([7.6, 8.0, 8.4]),
                             local_steps=5, batch=16) == 9_027_901_696.0
    assert flops.eval_flops(specs, [len(t["test"]["y"]) for t in tasks]) \
        == 13_819_392_000.0
