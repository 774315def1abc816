"""The 5-model world's new kinds through the harness: a run, past its look
for a chip, on a small world of one ``image`` task, one ``image_rgb``
task at 32x32x3 and one ``char`` task, on the CPU, with ``correct`` true;
an error of 1% planted in the char reference's logits turns it false.
The FLOP counts of the new kinds against hand counts at the published
widths."""
import json
import os

import pytest

import flops
import run
import world
from conftest import BENCH

TINY = json.load(open(os.path.join(BENCH, "tests", "tiny.json")))
HET5 = json.load(open(os.path.join(BENCH, "configs", "mmfl-het5.json")))
CHAR = {"name": "shakespeare", "kind": "char", "vocab": 12, "seq_len": 6,
        "d_embed": 4, "d_hidden": 16, "n_streams": 30, "chars_mu": 3.5,
        "chars_sigma": 0.8, "chars_min": 13, "chars_max": 120,
        "test_frac": 0.1}
RGB = {"name": "cifar", "kind": "image_rgb", "n_classes": 10,
       "channels": 4, "side": 32, "in_ch": 3}
THREE_KINDS = dict(TINY, tasks=[TINY["tasks"][0], RGB, CHAR])
IMAGE_CHAR = dict(TINY, tasks=[TINY["tasks"][0], CHAR])


def drive(cfg):
    """One run of the cell on ``cfg``, judged by the cell's own limits."""
    return run.run_cell("het5-lvr", 2_147_483_647, 0.1, False,
                        require_chip=False, cfg_override=cfg)


@pytest.mark.parametrize("cfg", [THREE_KINDS, IMAGE_CHAR],
                         ids=["image-rgb-char", "image-char"])
def test_image_and_char_world_runs_correct(cfg):
    res = drive(cfg)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 10


def test_error_in_char_reference_is_caught(monkeypatch):
    """Logits 1% too large: at these small random weights the loss moves
    by ~1e-5 of itself, each round, which the median loss gap holds out
    where half of the world's tasks are the char task's."""
    char = world.load_kind("char")
    plain = char.logits
    monkeypatch.setattr(char, "logits", lambda p, x: 1.01 * plain(p, x))
    res = drive(IMAGE_CHAR)
    assert not res["correct"]
    assert res["checks"]["loss_median_gap"]["value"] > \
        res["checks"]["loss_median_gap"]["limit"]


def test_char_forward_flops_at_published_widths():
    spec = next(t for t in HET5["tasks"] if t["kind"] == "char")
    per_step = (2 * 4 * 256 * (8 + 256)        # layer 1: input, recurrent
                + 2 * 4 * 256 * (256 + 256)    # layer 2
                + 2 * 256 * 80)                # head
    assert per_step * 80 == 130_416_640
    assert flops.forward_flops(spec) == 130_416_640


def test_image_forward_flops_at_published_widths():
    cifar, emnist = (next(t for t in HET5["tasks"] if t["name"] == n)
                     for n in ("cifar", "emnist"))
    conv1 = 32 * 32 * 9 * 3 * 12 * 2
    conv2 = 16 * 16 * 9 * 12 * 24 * 2
    fc1 = 8 * 8 * 24 * 128 * 2
    assert flops.forward_flops(cifar) == conv1 + conv2 + fc1 + 128 * 10 * 2
    assert flops.forward_flops(cifar) == 2_386_432
    assert flops.forward_flops(emnist) == 771_840

