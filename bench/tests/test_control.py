"""The control on the chip: the reference computed at precision ``high``
(three bf16 passes) in the program's place, at each cell's own size, on
three seeds, must come out not correct against the cell's limits.

Needs a TPU (elsewhere ``high`` computes exactly as ``highest``):
``python3 -m pytest bench/tests/test_control.py`` on the chip."""
import json
import os

import jax
import pytest

import calibrate
import check
import run
import world
from conftest import BENCH, ROOT
from reference import Reference

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("precision high differs from highest only on the chip")
    _, _, cfg, traffic = run.cell_files(workload)
    run.setup_jax(cfg["matmul_precision"])
    limits = json.load(open(os.path.join(BENCH, "limits",
                                         workload + ".json")))
    w = world.build(cfg)
    ref = Reference(cfg, traffic, w, precision=cfg["matmul_precision"])
    seeds = calibrate.seeds_from(2_147_483_001, 3)
    readings = calibrate.stand_in_gaps(cfg, traffic, w, seeds, ref,
                                       precision="high")
    for seed, gaps in readings.items():
        assert not check.judge(gaps, limits), (seed, gaps)
