"""The phase reduction (``trace_scopes``): on hand-made events laid out as
a TPU trace lays them out, and on a small trace of a scoped, scanned
function recorded on the CPU (whose XLA operations run on host threads and
carry their program id as a stat, where a TPU trace has an ``XLA Modules``
line)."""
import json
import math
import os
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

import run
import trace_reduce as tr
import trace_scopes as ts
from conftest import ROOT

TPU = "/device:TPU:0"


def test_innermost_scope_wins():
    assert ts.phase_of("jit(roll)/while/body/closed_call/vmap(mmfl.aggregate)"
                       "/mmfl.local_train/vmap()/jvp()/reduce_window_max") \
        == "local_train"
    assert ts.phase_of("jit(roll)/mmfl.stats/transpose(jvp(mmfl.local_train"
                       "))/mul") == "local_train"
    assert ts.phase_of("jit(roll)/while/body/closed_call") is None
    assert ts.phase_of("jit(f)/add;jit(f)/mmfl.sample/mul") == "sample"
    assert ts.phase_of("jit(f)/mmfl.sampler/mul") is None


# a chunk as the cnn3 cells trace it: the scanned rollout, a gap the host
# leaves, then the accuracy program; names as the device line prints them
ROLL, EVAL = 101, 202
OP_NAMES = {
    ROLL: {"while.3": "jit(roll)/while",
           "fusion.1": "jit(roll)/while/body/closed_call/mmfl.stats/mul",
           "reduce-window.84": "jit(roll)/while/body/closed_call/vmap("
                               "mmfl.aggregate)/mmfl.local_train/vmap()/"
                               "jvp()/reduce_window_max",
           "fusion.2": "jit(roll)/while/body/closed_call/vmap("
                       "mmfl.aggregate)/dot_general",
           "sort.1": "jit(roll)/while/body/closed_call/mmfl.sample/sort",
           "copy.9": "jit(roll)/while/body/closed_call"},
    EVAL: {"fusion.5": "jit(<lambda>)/mmfl.evaluate/conv_general_dilated"},
}


def _events():
    op = lambda name, s, e: (TPU, "XLA Ops", f"%{name} = f32[8] op()", s, e)
    return [
        ("/host:CPU", "python", "dispatch", 0, 10),
        ("/host:CPU", "python", "block", 10, 120),
        ("/host:CPU", "python", "evaluate", 120, 200),
        (TPU, "XLA Modules", f"jit_roll({ROLL})", 5, 100),
        op("while.3", 6, 99),
        op("fusion.1", 6, 10),
        op("sort.1", 10, 12),
        op("reduce-window.84", 15, 60),
        op("fusion.2", 60, 70),
        op("copy.9", 70, 80),
        op("copy-done.1", 80, 90),           # no HLO metadata: unscoped
        (TPU, "XLA Modules", f"jit__lambda({EVAL})", 130, 150),
        op("fusion.5", 130, 150),
        (TPU, "XLA Modules", "jit_squeeze(7)", 160, 161),
        op("copy.1", 160, 161),              # a program with no scope
    ]


def _phases(events, op_names=OP_NAMES):
    return ts.Phases(tr.device_ops(events),
                     tr.device_ops(events, line="XLA Modules"), op_names,
                     0, 200)


def test_each_op_goes_to_its_innermost_phase():
    ph = _phases(_events())
    assert ph.seconds == pytest.approx({"stats": 4e-9, "sample": 2e-9,
                                        "local_train": 45e-9,
                                        "aggregate": 10e-9,
                                        "evaluate": 20e-9}, abs=1e-15)
    assert ph.round_programs == {ROLL}


def test_phases_and_unscoped_sum_to_the_round_program():
    summary, ph = tr.Summary(_events()), _phases(_events())
    round_ops = [(n, s, e) for n, s, e in summary.leaves if 6 <= s < 99]
    busy = sum(e - s for _, s, e in round_ops) * 1e-9
    assert ph.round_busy_s == pytest.approx(busy, abs=1e-15)
    scoped = sum(ph.seconds[p] for p in ts.ROUND_PHASES)
    assert scoped + ph.unscoped_s == pytest.approx(ph.round_busy_s,
                                                   abs=1e-15)
    assert ph.unscoped_s == pytest.approx(20e-9, abs=1e-15)


def test_idle_split_sums_to_the_idle_share():
    events = _events()
    out = ts.summarize(events, OP_NAMES, rounds=5)
    idle_share = _reader("device_idle_share").read(
        {"trace": tr.Summary(events)})
    assert out["device_idle_share"] == pytest.approx(idle_share, abs=1e-9)
    # window 0-200, busy 6-99 (the while loop), 130-150, 160-161; of the
    # idle time only 5-6 and 99-100 fall inside a program (5-100)
    assert out["idle.in_program_share"] == pytest.approx(100 * 2 / 200)
    assert out["idle.between_programs_share"] + out[
        "idle.in_program_share"] == pytest.approx(idle_share, abs=1e-9)
    assert out["round.local_train.device_ms"] == pytest.approx(
        1e3 * 45e-9 / 5)
    assert out["round.unscoped_share"] == pytest.approx(100 * 20 / 81)
    assert out["chunks"] == 0 and out["evaluate.device_ms"] is None


def test_engine_spans_make_the_window():
    """An operator's own trace: no harness spans, the engine's only."""
    events = [ev for ev in _events() if ev[0] != "/host:CPU"] + [
        ("/host:CPU", "python", "mmfl.rollout", 2, 8),
        ("/host:CPU", "python", "mmfl.evaluate", 125, 190)]
    out = ts.summarize(events, OP_NAMES, rounds=5)
    assert out["window_s"] == pytest.approx(188e-9)
    assert out["chunks"] == 1
    assert out["evaluate.device_ms"] == pytest.approx(1e3 * 20e-9)


def test_unscoped_program_reads_nothing():
    """A program built without the scopes (the engine before them): the
    per-phase numbers are None, the idle split is still read."""
    out = ts.summarize(_events(), {}, rounds=5)
    for name in ("round.stats.device_ms", "round.aggregate.device_ms",
                 "round.unscoped_share", "evaluate.device_ms"):
        assert out[name] is None, name
    assert out["idle.in_program_share"] is not None


def _reader(name):
    from test_trace_reduce import _reader_module
    return _reader_module(name)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """The xplane file of a scoped scan traced on the CPU."""
    def f(x):
        def body(c, _):
            with jax.named_scope("mmfl.stats"):
                y = jnp.tanh(c @ c)
            with jax.named_scope("mmfl.aggregate"):
                with jax.named_scope("mmfl.local_train"):
                    y = jnp.sin(y @ y)
                y = jnp.cos(y) * 0.5
            return y, None
        return jax.lax.scan(body, x, None, length=3)[0]

    d = str(tmp_path_factory.mktemp("scoped_trace"))
    from jax.experimental.compilation_cache import compilation_cache
    # the persistent cache's key leaves op metadata out: compile afresh
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        jf = jax.jit(f)
        x = jnp.ones((128, 128))
        jf(x).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(d)
    for _ in range(2):
        with TraceAnnotation("dispatch"):
            y = jf(x)
        with TraceAnnotation("block"):
            y.block_until_ready()
        with TraceAnnotation("evaluate"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = tr.find_xplane(d)
    yield path
    shutil.rmtree(d, ignore_errors=True)


def test_recorded_trace_names_each_op(cpu_trace):
    """The HLO metadata decoded from the trace names each operation of the
    scanned program by its innermost scope; the reduction over the CPU's
    operations (each its own execution) sums as on the chip."""
    from jax.profiler import ProfileData
    names = ts.program_op_names(cpu_trace)
    ops, modules = [], []
    for plane in ProfileData.from_file(cpu_trace).planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if st.get("hlo_module") != "jit_f":
                    continue
                s = int(ev.start_ns)
                e = s + max(int(ev.duration_ns), 1)
                ops.append((st["hlo_op"], s, e))
                modules.append((f"jit_f({st['program_id']})", s, e))
    assert ops, "no XLA operation events of the scanned program"
    pid = int(modules[0][0][6:-1])
    # XLA fuses sin into the aggregate's multiply here; each dot stays
    expect = {"tanh": {"stats"}, "mul": {"aggregate"},
              "dot_general": {"stats", "local_train"}}
    seen = {}
    for op, _, _ in ops:
        stack = names[pid].get(op, "")
        leaf = stack.rsplit("/", 1)[-1]
        if leaf in expect:
            seen.setdefault(leaf, set()).add(ts.phase_of(stack))
    assert seen == expect
    lo = min(s for _, s, _ in ops)
    hi = max(e for _, _, e in ops)
    ph = ts.Phases(ops, modules, names, lo, hi)
    assert ph.round_programs == {pid}
    scoped = sum(ph.seconds[p] for p in ts.ROUND_PHASES)
    assert scoped > 0
    assert scoped + ph.unscoped_s == pytest.approx(ph.round_busy_s)
    busy = tr.union(((s, e) for _, s, e in ops), lo, hi)
    idle = 1 - sum(e - s for s, e in busy) * 1e-9 / ph.window_s
    split = (ph.idle_between_programs_s + ph.idle_in_program_s) \
        / ph.window_s
    assert split == pytest.approx(idle, abs=1e-12)


def test_cli_reads_a_recorded_trace(cpu_trace, capsys):
    """The whole path on the recorded file: the harness's spans make the
    window; the CPU has no device plane, so there is nothing to divide."""
    ts.main([cpu_trace])
    out = json.loads(capsys.readouterr().out)
    assert out["window_s"] > 0 and out["rounds"] == 0
    assert out["round.stats.device_ms"] is None


# the per-layer metrics read from ``trace_scopes``'s reduction
PHASE_METRICS = ("round.stats.device_ms", "round.sample.device_ms",
                 "round.local_train.device_ms", "round.aggregate.device_ms",
                 "round.unscoped_share", "evaluate.device_ms",
                 "idle.between_programs_share", "idle.in_program_share")


def test_phase_readers_read_the_reduction():
    """Each reader returns its number of the reduction, and each is a
    per-layer metric of ``BENCHMARK.json``."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(PHASE_METRICS) <= {m["name"] for m in bench["per_layer"]}
    phases = ts.summarize(_events(), OP_NAMES, rounds=5)
    for name in PHASE_METRICS:
        assert run.load_reader(name)({"phases": phases}) == phases[name]
    assert run.load_reader("round.local_train.device_ms")(
        {"phases": phases}) == pytest.approx(1e3 * 45e-9 / 5)
    assert run.load_reader("round.stats.device_ms")({"phases": None}) is None


def test_traced_branch_reaches_the_readers(cpu_trace):
    """The recorded trace through ``run.read_trace``, as the traced run
    reads its window before deleting the trace, then through each reader:
    a finite number, or None where the CPU trace has no scope to read."""
    summary, phases = run.read_trace(cpu_trace, rounds=6)
    assert isinstance(summary, tr.Summary) and summary.window_s > 0
    assert phases["rounds"] == 6 and phases["window_s"] > 0
    ctx = {"trace": summary, "phases": phases, "rounds": 6}
    for name in PHASE_METRICS:
        value = run.load_reader(name)(ctx)
        assert value is None or math.isfinite(value), (name, value)
