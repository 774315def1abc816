"""The benchmark's copy of the world generators gives the program's worlds
bit for bit: each configuration file's world built from the program's
generators (each task's through its kind's ``program_task``,
``bench/kinds/<kind>.py``) in ``build_setting``'s order at the file's
sizes, and, for the configurations that are ``build_setting``'s own
worlds, that world itself (200 images per class)."""
import glob
import json
import os

import numpy as np
import pytest

import world
from conftest import BENCH

CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
# the configurations whose world is ``build_setting(len(tasks))``'s own
BUILD_SETTING_WORLDS = [os.path.join(BENCH, "configs", "mmfl-cnn3.json")]


def program_world(cfg):
    """``build_setting``'s steps on the program's generators, with the
    configuration's sizes."""
    from repro.data import partition
    from repro.fl.experiments import align_task_caps
    rng = np.random.default_rng(cfg["data_seed"])
    n = cfg["n_clients"]
    tasks = [world.load_kind(t["kind"]).program_task(rng, t, cfg)
             for t in cfg["tasks"]]
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


@pytest.mark.parametrize("path", BUILD_SETTING_WORLDS, ids=os.path.basename)
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


def test_task_n_per_class_overrides_the_configuration():
    """An ``image`` task with an ``n_per_class`` of its own (the 5-model
    world's EMNIST-like task) draws that many, as the program's generator
    does, and its neighbours draw the configuration's."""
    cfg = dict(json.load(open(os.path.join(BENCH, "tests", "tiny.json"))))
    cfg["tasks"] = [cfg["tasks"][0],
                    {"name": "emnist", "kind": "image", "n_classes": 26,
                     "channels": 8, "n_per_class": 30}]
    built = world.build(cfg)
    assert [len(t["test"]["y"]) for t in built[0]] == [64, 78]
    assert_same(built, program_world(cfg))
