"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a small trace recorded on the CPU (where XLA's
operations run on host threads, so they stand in for device events)."""
import time

import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr


def test_union_merges_and_clips():
    assert tr.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 15) == \
        [(1, 4), (5, 8), (9, 15)]
    assert tr.union([(0, 1)], 2, 3) == []


def test_holes_cover_the_rest_of_the_window():
    busy = tr.union([(2, 4), (6, 7)], 0, 10)
    assert tr.holes(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.holes([], 0, 10) == [(0, 10)]


def test_gap_named_by_the_covering_span():
    spans = [("dispatch", 0, 3), ("block", 3, 4), ("evaluate", 4, 10)]
    assert tr.name_gap((2, 9), spans) == "evaluate"
    assert tr.name_gap((11, 12), spans) == "other"


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(d)
    for _ in range(3):
        with TraceAnnotation("dispatch"):
            y = f(x)
        with TraceAnnotation("block"):
            y.block_until_ready()
        with TraceAnnotation("evaluate"):
            time.sleep(0.1)
    jax.profiler.stop_trace()
    return tr.read_events(tr.find_xplane(d))


def test_recorded_trace_reduces(cpu_trace):
    spans = tr.host_spans(cpu_trace)
    assert [n for n, _, _ in spans] == ["dispatch", "block", "evaluate"] * 3
    ops = [(n, s, e) for p, ln, n, s, e in cpu_trace
           if n.startswith(("dot_general", "wrapped_tanh"))]
    assert ops, "no XLA operation events on the CPU threads"
    sm = tr.Summary(cpu_trace, ops=ops)
    assert 0 < sm.busy_s < sm.window_s
    # three sleeps of 100 ms with no operation running: the longest gaps,
    # each inside an evaluate span
    gaps = sm.top_gaps(3)
    assert [g[0] for g in gaps] == ["evaluate"] * 3
    assert all(g[1] >= 0.099 for g in gaps)
    names = [n for n, _ in sm.top_ops()]
    assert set(names) <= {n for n, _, _ in ops}


# kernel events as a TPU v5e trace of the cnn3-stalevre cell prints them
REFRESH = ("%closed_call.41 = (f32[808,128]{1,0:T(8,128)S(1)}, f32[120,808,128]"
           "{2,1,0:T(8,128)S(1)}) custom-call(s32[24]{0:T(128)S(1)} %fusion.491"
           ", f32[24]{0:T(128)S(1)} %fusion.492, f32[24]{0:T(128)S(1)} "
           "%fusion.493, f32[24]{0:T(128)S(1)} %fusion.494, f32[24,808,128]"
           "{2,1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.9, "
           "f32[120,808,128]{2,1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion"
           ".10, f32[808,128]{1,0:T(8,128)S(1)} %fusion.495), "
           'custom_call_target="tpu_custom_call"')
DOT = ("%vmap_jit_batched_dot__.14 = (f32[3,24,8,128]{3,2,1,0:T(8,128)S(1)}, "
       "f32[3,24,8,128]{3,2,1,0:T(8,128)S(1)}) custom-call(f32[3,24,880,128]"
       "{3,2,1,0:T(8,128)} %bitcast.600, f32[3,24,880,128]{3,2,1,0:T(8,128)"
       'S(1)} %bitcast.599), custom_call_target="tpu_custom_call"')
FUSION = ("%fusion.478 = f32[16,14,14,72,16]{4,0,3,2,1:T(8,128)S(1)} fusion("
          "f32[16,14,14,72,8]{0,4,3,2,1:T(8,128)} %bitcast.580), kind=kOutput")


def _reader_module(name):
    import importlib.util
    import os
    from conftest import BENCH
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_events_are_told_apart():
    refresh = _reader_module("stale_agg_refresh.device_us").is_refresh
    dot = _reader_module("batched_dot.device_us").is_batched_dot
    assert tr.op_name(REFRESH) == "closed_call.41"
    assert tr.operand_shapes(DOT) == [("f32", (3, 24, 880, 128))] * 2
    assert refresh(REFRESH) and not refresh(DOT)
    assert dot(DOT) and not dot(REFRESH)
    assert tr.is_kernel(REFRESH) and tr.is_kernel(DOT)
    assert not tr.is_kernel(FUSION)


def test_kernel_seconds_per_round():
    ev = [("/host:CPU", "python", "dispatch", 0, 10),
          ("/host:CPU", "python", "evaluate", 10, 100),
          ("/device:TPU:0", "XLA Ops", "%while.1 = () while()", 1, 90),
          ("/device:TPU:0", "XLA Ops", REFRESH, 2, 12),
          ("/device:TPU:0", "XLA Ops", DOT, 20, 25),
          ("/device:TPU:0", "XLA Ops", REFRESH, 30, 40),
          ("/device:TPU:0", "XLA Ops", FUSION, 50, 80)]
    sm = tr.Summary(ev)
    refresh = _reader_module("stale_agg_refresh.device_us")
    dot = _reader_module("batched_dot.device_us")
    assert sm.kernel_seconds(refresh.is_refresh) == 20e-9
    assert refresh.read({"trace": sm, "rounds": 2}) == 1e6 * 10e-9
    assert dot.read({"trace": sm, "rounds": 5}) == 1e6 * 1e-9
    assert sm.busy_s == 89e-9                   # the while loop counts
    assert [n for n, _ in sm.top_ops(2)] == ["fusion.478", "closed_call.41"]
