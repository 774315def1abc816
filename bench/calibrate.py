"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip:

  * the program: a dozen seeds or more, each through the timed path's own
    set-up chunk (``init_state``, one ``rollout`` of ``eval_every``
    rounds, ``evaluate``) on one engine, against the reference;
  * the control: the reference at the precision just below the
    configuration's (``high``, three bf16 passes, for float32 at
    ``highest``) in the program's place, on three seeds or more;
  * faults planted in the reference in the program's place
    (``reference.Reference``: ``half_batch``, ``drop_update``).

    python3 bench/calibrate.py --workload cnn3-stalevre --seeds 12 \
        --control-seeds 3 --out calib-cnn3-stalevre.json

The lower reading of a number is the largest over the program's seeds;
the upper one the smallest over the control's (and, where lower, over a
fault's that reads ten times the lower reading or more).  A state left
unchanged reads 1 on ``delta_gap`` by construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import run  # noqa: E402

FAULTS = ("half_batch", "drop_update")


def seeds_from(base: int, n: int):
    return [base + 7919 * i for i in range(n)]


def program_records(cfg, traffic, world_np, seeds):
    """The timed path's set-up chunk for each seed, on one engine."""
    import check
    import jax
    import jax.numpy as jnp
    engine = run.make_engine(cfg, traffic, world_np, seeds[0])
    ev = int(traffic["eval_every"])
    out = {}
    for seed in seeds:
        # ``init_state`` hands the engine's own client mask into the
        # state, which ``rollout`` donates: copy it, or the next seed's
        # state would carry a deleted buffer
        state = jax.tree.map(jnp.copy, engine.init_state(seed=seed))
        state, mets = engine.rollout(state, ev)
        mets = run.host(mets)
        acc = engine.evaluate(state)
        out[seed] = check.program_record(
            mets, run.host(engine.per_task_params(state)), acc)
        del state
    return out


def stand_in_gaps(cfg, traffic, world_np, seeds, ref, **stand):
    """Gaps of the reference put in the program's place (``stand``: its
    precision or a planted fault) against the reference."""
    import check
    from reference import Reference
    ev = int(traffic["eval_every"])
    sub = Reference(cfg, traffic, world_np, **stand)
    out = {}
    for seed in seeds:
        rec = check.follow(sub, seed, ev, None)
        out[seed] = check.gaps(rec, check.follow(ref, seed, ev, rec["H1"]))
    return out


def summarize(readings):
    keys = sorted({k for r in readings.values() for k in r})
    return {k: {"max": max(r[k] for r in readings.values()),
                "min": min(r[k] for r in readings.values())} for k in keys}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seed-base", type=int, default=2_147_483_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    _, cell, cfg, traffic = run.cell_files(args.workload)
    jax = run.setup_jax(cfg["matmul_precision"])
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        sys.exit(3)
    import check
    import numpy as np
    import world
    from reference import Reference

    world_np = world.build(cfg)
    seeds = seeds_from(args.seed_base, args.seeds)
    t0 = time.perf_counter()
    prog = program_records(cfg, traffic, world_np, seeds)
    t_prog = time.perf_counter() - t0
    gc.collect()
    jax.clear_caches()

    ev = int(traffic["eval_every"])
    ref = Reference(cfg, traffic, world_np, precision=cfg["matmul_precision"])
    t0 = time.perf_counter()
    program, near, worst = {}, {}, {}
    for seed in seeds:
        rr = check.follow(ref, seed, ev, prog[seed]["H1"])
        program[seed] = check.gaps(prog[seed], rr)
        near[seed] = [rr["near"], rr["flips"], rr["settled_margin"]]
        worst[seed] = check.worst_leaf(prog[seed], rr)
        lp, lr = np.asarray(prog[seed]["loss"]), rr["loss"]
        worst[seed]["loss_gap_by_round"] = (
            np.abs(lp - lr) / np.abs(lr)).max(axis=1).tolist()
    t_ref = (time.perf_counter() - t0) / len(seeds)
    ctl_seeds = seeds_from(args.seed_base + 1, args.control_seeds)
    control = stand_in_gaps(cfg, traffic, world_np, ctl_seeds, ref,
                            precision="high")
    faults = {f: stand_in_gaps(cfg, traffic, world_np, ctl_seeds, ref,
                               fault=f) for f in FAULTS}
    out = {
        "workload": args.workload, "device": dev.device_kind,
        "program_s_per_seed": t_prog / len(seeds),
        "reference_s_per_seed": t_ref,
        "program": {str(k): v for k, v in program.items()},
        "near_flips_margin": {str(k): v for k, v in near.items()},
        "worst_leaf": {str(k): v for k, v in worst.items()},
        "control": {str(k): v for k, v in control.items()},
        "faults": {f: {str(k): v for k, v in r.items()}
                   for f, r in faults.items()},
        "summary": {"program": summarize(program),
                    "control": summarize(control),
                    **{f: summarize(r) for f, r in faults.items()}},
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]))


if __name__ == "__main__":
    main()
