"""The benchmark's copy of the world generators gives the program's worlds
bit for bit: ``build_setting``'s own world (200 images per class), and
each configuration file's world built from the program's generators in
``build_setting``'s order at the file's ``n_per_class``."""
import glob
import json
import os

import numpy as np
import pytest

import world
from conftest import BENCH

CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))


def program_world(cfg):
    """``build_setting``'s steps on the program's generators, with the
    configuration's sizes."""
    from repro.data import partition
    from repro.fl.experiments import _image_task, align_task_caps
    rng = np.random.default_rng(cfg["data_seed"])
    n = cfg["n_clients"]
    tasks = [_image_task(rng, t["name"], n, n_classes=t["n_classes"],
                         channels=t["channels"],
                         n_per_class=cfg["n_per_class"],
                         label_frac=cfg["label_frac"]) for t in cfg["tasks"]]
    avail = partition.availability(rng, n, len(tasks),
                                   frac_all=cfg["avail_frac_all"])
    B = partition.processor_budgets(rng, avail)
    return align_task_caps(tasks), B, avail


def assert_same(bench_world, prog_world):
    tasks, B, avail = bench_world
    p_tasks, p_B, p_avail = prog_world
    np.testing.assert_array_equal(B, p_B)
    np.testing.assert_array_equal(avail, p_avail)
    assert len(tasks) == len(p_tasks)
    for t, pt in zip(tasks, p_tasks):
        assert t["spec"]["name"] == pt.name
        for part, p_part in ((t["data"], pt.data), (t["test"], pt.test)):
            assert sorted(part) == sorted(p_part)
            for k in part:
                a, b = np.asarray(part[k]), np.asarray(p_part[k])
                assert a.dtype == b.dtype and a.shape == b.shape, k
                np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_world_matches_build_setting(path):
    from repro.fl.experiments import build_setting
    cfg = dict(json.load(open(path)), n_per_class=200)
    assert_same(world.build(cfg),
                build_setting(len(cfg["tasks"]), n_clients=cfg["n_clients"],
                              seed=cfg["data_seed"]))


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_world_matches_program_generators(path):
    cfg = json.load(open(path))
    assert_same(world.build(cfg), program_world(cfg))
