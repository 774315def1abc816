"""The round's phase names (``jax.named_scope`` in ``core.engine``) reach
the compiled programs, and a state of its own per ``init_state``.

A profiler trace ties a device operation to a round phase through the
``op_name`` metadata of the compiled HLO; these tests read that metadata
on the CPU.  That the scopes change no number is pinned by the bitwise
batteries (tests/test_task_fusion.py, tests/test_world_padding.py,
tests/test_sharding.py and the goldens of tests/test_methods.py)."""
import contextlib
import os
import re

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


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name="([^"]*)"')


def _cnn_lstm_engine():
    """Two tiny CNN tasks (one signature group) and a tiny char LSTM."""
    from repro.fl.experiments import (_char_task, _image_task,
                                      align_task_caps)
    rng = np.random.default_rng(0)
    n = 8
    tasks = [_image_task(rng, f"img-{i}", n, n_classes=4, channels=2,
                         n_per_class=20, side=8) for i in range(2)]
    tasks.append(_char_task(rng, "chars", n, vocab=12, seq_len=6,
                            stream_lens=[40] * (n + 4), n_test=8,
                            d_embed=4, d_hidden=8))
    B = np.full(n, 2)
    avail = np.ones((n, 3), bool)
    cfg = ServerConfig(method="lvr", local_epochs=2, seed=1,
                       active_rate=0.3, batch_size=4)
    return RoundEngine(align_task_caps(tasks), B, avail, cfg)


def _instr_op_names(eng):
    state = eng.init_state(seed=3)
    text = jax.jit(lambda s: eng.round_step_fn(s)).lower(
        state).compile().as_text()
    return dict(m.groups() for m in map(_INSTR.match, text.splitlines())
                if m)


def test_group_and_recurrence_scopes_keep_every_phase(monkeypatch):
    """A CNN-plus-LSTM world's compiled round names the LSTM's time scan
    (``lstm.recurrence``) and each signature group (``group.<first task
    name>``), and every operation keeps the phase
    (``bench/trace_scopes.phase_of``) it has without those scopes."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    monkeypatch.syspath_prepend(bench)
    import trace_scopes

    eng = _cnn_lstm_engine()
    assert eng.groups == [[0, 1], [2]]
    scoped = _instr_op_names(eng)
    names = list(scoped.values())
    for scope in ("lstm.recurrence", "group.img-0", "group.chars"):
        assert any(scope in n for n in names), scope
    assert any("group.chars" in n and "lstm.recurrence" in n
               and trace_scopes.phase_of(n) == "local_train" for n in names)
    assert any("group.chars" in n and "lstm.recurrence" in n
               and trace_scopes.phase_of(n) == "stats" for n in names)

    plain_scope = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        _NoScope() if name.startswith(("group.", "lstm.")) else
        plain_scope(name)))
    bare = _instr_op_names(_cnn_lstm_engine())
    assert not any("group." in n or "lstm." in n for n in bare.values())
    assert scoped.keys() == bare.keys()
    for instr, name in scoped.items():
        assert (trace_scopes.phase_of(name)
                == trace_scopes.phase_of(bare[instr])), instr
