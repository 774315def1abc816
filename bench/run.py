"""The MMFL round-engine benchmark: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: the world and the server settings) and a
traffic mix (``bench/traffic/<traffic>.json``: the method and the eval
cadence).  The run

  1. builds the world from the benchmark's own generators
     (``bench/world.py``, fixed by the configuration's ``data_seed``) and
     hands it to the program's ``RoundEngine`` with the program's model
     adapters; a task's ``kind`` names the module that gives both,
     ``bench/kinds/<kind>.py``;
  2. sets up: ``init_state(seed)``, then one chunk of the timed loop (a
     ``rollout`` of ``eval_every`` rounds and an ``evaluate``), which
     compiles or loads every program the window uses and whose results
     are kept for the check;
  3. measures for ``--seconds``: whole chunks of ``rollout`` + host sync
     of the metrics + ``evaluate``, as ``fl.experiments.run_experiment``
     loops, until the time is spent (``rounds_per_s`` = rounds over the
     window's wall time);
  4. reads the peak device memory, frees the program, and runs the plain
     reference over the set-up chunk (``bench/check.py``).

With ``--trace 1`` the window runs under the profiler and the run reports
the per-layer metrics instead, each computed by its reader
``bench/metrics/<metric>.py`` from the trace's reduction
(``bench/trace_reduce.py``, ``bench/trace_scopes.py``).  The last line of
standard output is the result as JSON.  Without a TPU, or with fewer chips
than the cell asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_files(workload: str):
    """(benchmark, cell, configuration, traffic) for a workload name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, traffic


def setup_jax(precision: str):
    """The compile cache lives in the checkout and is never evicted: a
    size cap set by the environment (a machine may cap its own cache near
    200 MB) would refuse the round program, whose constants alone are
    hundreds of MB, and evict the rest, so that every run compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision", precision)
    return jax


class CompileCounter:
    """Counts backend compiles and persistent-cache loads while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and ("backend_compile" in event
                           or "cache_retrieval" in event):
            self.count += 1


def make_engine(cfg, traffic, world_np, seed: int):
    """The program under test: ``RoundEngine`` over the benchmark's world,
    with the program's own model adapters (each task's from its kind's
    ``adapter``, ``bench/kinds/<kind>.py``)."""
    import jax.numpy as jnp
    from repro.core.engine import RoundEngine, ServerConfig, Task
    from world import load_kind

    tasks_np, B, avail = world_np
    tasks = []
    for t in tasks_np:
        sp = t["spec"]
        model = load_kind(sp["kind"]).adapter(sp)
        tasks.append(Task(name=sp["name"], model=model,
                          data={k: jnp.asarray(v)
                                for k, v in t["data"].items()},
                          test={k: jnp.asarray(v)
                                for k, v in t["test"].items()}))
    server = ServerConfig(method=traffic["method"], seed=seed,
                          **cfg["server"])
    return RoundEngine(tasks, B, avail, server)


def host(tree):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


def nonfinite_rounds(mets) -> int:
    import numpy as np
    bad = None
    for v in mets.values():
        row = ~np.isfinite(v).reshape(v.shape[0], -1).all(axis=1)
        bad = row if bad is None else bad | row
    return int(bad.sum())


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_trace(path: str, rounds: int):
    """The traced window's ``.xplane.pb``, read once: the reduction the
    trace's readers share (``trace_reduce.Summary``) and the per-phase
    device times and idle split (``trace_scopes.summarize``) of its
    ``rounds`` rounds."""
    import trace_reduce as tr
    import trace_scopes
    events = tr.read_events(path)
    return tr.Summary(events), trace_scopes.summarize(
        events, trace_scopes.program_op_names(path), rounds)


def cell_metrics(bench, cell, kind: str):
    """The cell's metric entries of ``end_to_end`` or ``per_layer``."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True,
             cfg_override: Optional[Dict[str, Any]] = None,
             limits: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One run of a cell; returns the result object (``main`` prints it).

    ``require_chip=False``, ``cfg_override`` and ``limits`` serve the
    benchmark's own CPU tests, which drive a run on a small world."""
    bench, cell, cfg, traffic = cell_files(workload)
    if cfg_override is not None:
        cfg = cfg_override
    jax = setup_jax(cfg["matmul_precision"])
    import numpy as np

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(3)
    dev = devices[0]
    sys.path.insert(0, HERE)
    peak = None
    if trace:
        from peaks import peaks
        peak = peaks(dev.device_kind)
    import check
    import world
    from reference import Reference

    ev = int(traffic["eval_every"])
    phases = {"start": time.perf_counter() - T_START}
    t = time.perf_counter()
    world_np = world.build(cfg)
    counter = CompileCounter()
    phases["world"] = time.perf_counter() - t

    # ---- set-up: the program, one chunk of the timed loop ----------------
    t = time.perf_counter()
    engine = make_engine(cfg, traffic, world_np, seed)
    phases["engine"] = time.perf_counter() - t
    t_warm = t = time.perf_counter()
    state = engine.init_state(seed=seed)
    phases["init_state"] = time.perf_counter() - t
    t = time.perf_counter()
    state, mets = engine.rollout(state, ev)
    mets1 = host(mets)
    phases["rollout"] = time.perf_counter() - t
    t = time.perf_counter()
    acc1 = engine.evaluate(state)
    phases["evaluate"] = time.perf_counter() - t
    prog = check.program_record(mets1, host(engine.per_task_params(state)),
                                acc1)
    # a run that compiled has just written hundreds of MB to the compile
    # cache; flush them now, so that their write-back does not land in the
    # window (a cold run lost 1.4 s in its 3rd and 4th chunks without it)
    t = time.perf_counter()
    os.sync()
    phases["sync"] = time.perf_counter() - t
    warmup_s = time.perf_counter() - t_warm
    setup_s = time.perf_counter() - T_START
    failed = nonfinite_rounds(mets1)

    # ---- the measured window ---------------------------------------------
    from jax.profiler import TraceAnnotation
    rounds = chunks = 0
    eval_s = 0.0
    chunk_s = []
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    counter.armed = True
    t0 = tc = time.perf_counter()
    while True:
        with TraceAnnotation("dispatch"):
            state, mets = engine.rollout(state, ev)
        with TraceAnnotation("block"):
            mets = host(mets)
        te = time.perf_counter()
        with TraceAnnotation("evaluate"):
            engine.evaluate(state)
        now = time.perf_counter()
        eval_s += now - te
        rounds += ev
        chunks += 1
        failed += nonfinite_rounds(mets)
        chunk_s.append((te - tc, now - te))
        tc = now
        if now - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    if not all(np.isfinite(x).all()
               for x in jax.tree.leaves(host(state.params))):
        failed = max(failed, ev)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # ---- free the program, then the reference over the set-up chunk ------
    del state, mets, engine
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = Reference(cfg, traffic, world_np, precision=cfg["matmul_precision"])
    rr = check.follow(ref, seed, ev, prog["H1"])
    values = check.gaps(prog, rr)
    ref_s = time.perf_counter() - t_ref
    if limits is None:
        limits = load_json(HERE, "limits", workload + ".json")
    correct = failed == 0 and check.judge(values, limits)

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": rounds + ev,
        "failed": failed, "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    trained = [np.mean([len(a[s]) for a in rr["active"]])
               for s in range(len(world_np[0]))]
    ctx = {"cfg": cfg, "world": world_np, "rounds": rounds,
           "chunks": chunks, "window_s": window_s, "eval_s": eval_s,
           "warmup_s": warmup_s, "trained": trained, "peak": peak,
           "trace": None, "phases": None}
    if trace:
        import trace_reduce as tr
        summary, ctx["phases"] = read_trace(tr.find_xplane(TRACE_DIR),
                                            rounds)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx["trace"] = summary
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        kind = "per_layer"
    else:
        kind = "end_to_end"
    for m in cell_metrics(bench, cell, kind):
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"] == "rounds_per_s":
            value = rounds / window_s if window_s else None
        else:
            value = load_reader(m["name"])(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    if trace:
        result["breakdown"] = {"device_ops": ctx["trace"].top_ops(),
                               "idle_gaps": ctx["trace"].top_gaps()}
    print("setup phases (s): " + ", ".join(f"{k} {v:.3f}"
                                           for k, v in phases.items()),
          file=sys.stderr)
    print(f"setup {setup_s:.3f} s (warm-up chunk {warmup_s:.3f} s), "
          f"window {window_s:.3f} s, {rounds} rounds in {chunks} chunks, "
          f"{counter.count} compiles or cache loads in the window, "
          f"reference {ref_s:.3f} s, draws near a boundary {rr['near']}",
          file=sys.stderr)
    print("chunk times, rollout and host copy / evaluate (s): " + " ".join(
        f"{a:.4f}/{b:.4f}" for a, b in chunk_s), file=sys.stderr)
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in values.items()}
    checks["failed_rounds"] = {"value": failed, "limit": 0}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
