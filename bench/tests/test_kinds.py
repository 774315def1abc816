"""A task kind the harness has never seen enters through new files only: a
run of the harness, past its look for a chip, on a small world of one
``image`` task and one ``linear`` task, whose kind module lives in
``bench/tests/kinds/`` and nowhere in ``bench/kinds/``."""
import json
import os

import numpy as np
import pytest

import flops
import run
import world
from conftest import BENCH

TINY = json.load(open(os.path.join(BENCH, "tests", "tiny.json")))
LINEAR = {"name": "linear-0", "kind": "linear", "n_feat": 16,
          "n_classes": 4}
TWO_KINDS = dict(TINY, tasks=[TINY["tasks"][0], LINEAR])


@pytest.fixture
def test_kinds(monkeypatch):
    monkeypatch.setattr(world, "KIND_DIRS", world.KIND_DIRS + [
        os.path.join(BENCH, "tests", "kinds")])


def test_second_kind_runs_correct(test_kinds):
    res = run.run_cell("cnn3-stalevre", 2_147_483_647, 0.1, False,
                       require_chip=False, cfg_override=TWO_KINDS)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 10

    tasks, _, _ = world.build(TWO_KINDS)
    specs = [t["spec"] for t in tasks]
    counts = np.stack([t["data"]["count"] for t in tasks], 1)
    trained = np.array([3.0, 2.0])
    per_task = [flops.forward_flops(s) * (
        np.minimum(counts[:, i], 64).sum() + 3 * 5 * 16 * trained[i])
        for i, s in enumerate(specs)]
    assert per_task[1] == 2 * 16 * 4 * (counts[:, 1].sum() + 3 * 5 * 16 * 2)
    assert flops.round_flops(specs, counts, trained, local_steps=5,
                             batch=16) == pytest.approx(sum(per_task))


def test_unknown_kind_names_its_module():
    cfg = dict(TINY, tasks=[dict(LINEAR, kind="sparse_mlp")])
    with pytest.raises(ValueError, match="bench/kinds/sparse_mlp.py"):
        run.run_cell("cnn3-lvr", 1, 0.1, False, require_chip=False,
                     cfg_override=cfg)
