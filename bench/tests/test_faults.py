"""A run of the harness, past its look for a chip, on a small world on
the CPU, with the timed path broken underneath: ``correct`` must come
out false for every fault a one-chip training cell can have.

  * a round that returns its state unchanged;
  * half of each batch left out, the mean taken over the rest;
  * an answer altered where it is produced: one drawn participant of
    each round's cohort dropped.

(The exchange between chips does not exist on one chip.)  A sound run on
the same world comes out correct.  The limits are those of the
``cnn3-stalevre`` cell."""
import json
import os

import pytest

import run
from conftest import BENCH

TINY = json.load(open(os.path.join(BENCH, "tests", "tiny.json")))
LIMITS = json.load(open(os.path.join(BENCH, "limits",
                                     "cnn3-stalevre.json")))


def drive(seed=2_147_483_647):
    return run.run_cell("cnn3-stalevre", seed, 0.1, False,
                        require_chip=False, cfg_override=TINY, limits=LIMITS)


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 10


def test_state_left_unchanged(monkeypatch):
    from repro.core.engine import RoundEngine
    orig = RoundEngine.round_step_fn

    def stuck(self, state, world=None):
        new, mets = orig(self, state, world)
        return new._replace(params=state.params), mets

    monkeypatch.setattr(RoundEngine, "round_step_fn", stuck)
    res = drive()
    assert not res["correct"]
    assert res["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out(monkeypatch):
    from repro.models import cnn
    orig = cnn.loss_fn

    def half(params, batch):
        n = batch["x"].shape[0] // 2
        return orig(params, {"x": batch["x"][:n], "y": batch["y"][:n]})

    monkeypatch.setattr(cnn, "loss_fn", half)
    assert not drive()["correct"]


def test_drawn_participant_dropped(monkeypatch):
    import jax.numpy as jnp
    from repro.core import sampling
    orig = sampling.sample_assignment

    def altered(key, p, offset=0):
        active = orig(key, p, offset)
        first = jnp.argmax(active.sum(axis=1) > 0)
        return active.at[first].set(0.0)

    monkeypatch.setattr(sampling, "sample_assignment", altered)
    res = drive()
    assert not res["correct"]
    assert res["checks"]["h1_gap"]["value"] > LIMITS["h1_gap"]
