"""The round's phase names (``jax.named_scope`` in ``core.engine``) reach
the compiled programs, and a state of its own per ``init_state``.

A profiler trace ties a device operation to a round phase through the
``op_name`` metadata of the compiled HLO; these tests read that metadata
on the CPU.  That the scopes change no number is pinned by the bitwise
batteries (tests/test_task_fusion.py, tests/test_world_padding.py,
tests/test_sharding.py and the goldens of tests/test_methods.py)."""
import jax
import numpy as np
import pytest

from repro.core.engine import RoundEngine, ServerConfig
from repro.fl.experiments import build_linear_setting

ROUND_PHASES = ("mmfl.stats", "mmfl.sample", "mmfl.local_train",
                "mmfl.aggregate")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """The persistent cache's key leaves op metadata out, so a program
    cached by code without the scopes would be loaded with its old names:
    compile these afresh."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _engine(method):
    tasks, B, avail = build_linear_setting(n_models=3, n_clients=8, seed=0)
    cfg = ServerConfig(method=method, local_epochs=2, seed=1,
                       active_rate=0.3, batch_size=8)
    return RoundEngine(tasks, B, avail, cfg)


def _op_names(compiled_text):
    return [ln for ln in compiled_text.splitlines() if "op_name=" in ln]


@pytest.mark.parametrize("method", ["lvr", "stalevre"])
def test_compiled_programs_carry_phase_names(method):
    eng = _engine(method)
    state, _ = eng.rollout(eng.init_state(seed=3), 2)
    lines = _op_names(eng._rollout_cache[2].lower(state).compile().as_text())
    for phase in ROUND_PHASES:
        assert any(phase in ln for ln in lines), phase
    assert not any("mmfl.evaluate" in ln for ln in lines)
    for fn, args in ((jax.jit(eng.evaluate_fn), (state,)),
                     (eng.eval_jit[0], (eng.task_params(state, 0),
                                        eng.task_test(0)))):
        lines = _op_names(fn.lower(*args).compile().as_text())
        assert any("mmfl.evaluate" in ln for ln in lines)
        assert not any(p in ln for ln in lines for p in ROUND_PHASES)


def test_second_init_state_after_rollout():
    """``rollout`` donates its state; the state must not hold the
    engine's own buffers, or the next ``init_state`` reads a deleted one."""
    eng = _engine("stalevre")
    first, _ = eng.rollout(eng.init_state(seed=5), 2)
    second, _ = eng.rollout(eng.init_state(seed=5), 2)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(second)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(eng.world.client_mask),
                                  np.asarray(second.client_mask))
