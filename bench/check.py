"""The comparison that decides ``correct``.

What is compared is the first chunk of rounds of the timed path: set-up
drives the engine from the seed through one ``rollout`` of ``eval_every``
rounds and one ``evaluate``, through the same compiled programs the
window then keeps calling, and keeps on the host what they returned.  The
reference (``bench/reference.py``) follows those rounds from the same
seed once the window has closed.  Numbers, each held to a limit of the
cell's own (``bench/limits/<workload>.json``):

  loss_gap   worst relative gap, over rounds and tasks, of the round's
             loss monitor sum_i d_i f_i (the probe losses at each round's
             start: local training, aggregation and every earlier cohort);
  loss_median_gap  the median of the same gaps over rounds and tasks;
  h1_gap     worst absolute gap of the monitor H1 = sum of the active
             aggregation coefficients d/(B p) (sampling distribution and
             drawn cohort);
  delta_gap  worst leaf, over tasks, of the gap between the norms of the
             params' change over the chunk, against the larger of that
             leaf's reference norm and the median leaf's (leaves the
             reference leaves unmoved, under a thousandth of the median,
             are not compared);
  beta_gap   worst absolute gap of the per-client staleness coefficient
             beta over rounds, tasks and clients (stale methods only:
             the beta kernel and the stale store);
  acc_gap    worst absolute gap of the evaluation accuracy after the
             chunk (``evaluate``);
  settled_flips   the most draws in one round that the reference moved
             across a boundary of its CDF to match the program's H1
             (``reference.Reference``: only draws near a boundary may be
             moved, so a draw far from one that the program got wrong
             shows in ``h1_gap``);
  settled_margin  how far from its boundary the farthest moved draw lay:
             a float32 sampler may round across a boundary only where the
             uniform lies within its rounding of it.

The worst-case numbers catch a wrong update, cohort or evaluation.  On a
seed in ten or so, a ReLU or max-pool branch that rounding sends the
other way parts one task's trajectory by as much as a lower precision
would; the median loss gap, which one task's departure does not move, is
what holds the control (the reference one precision step lower) out.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np


def follow(ref, seed: int, rounds: int, program_h1: np.ndarray):
    """Run the reference through ``rounds`` rounds; ``program_h1`` [R, S]
    settles draws near a CDF boundary (``reference.Reference.round``)."""
    st = ref.init(seed)
    w0 = [jax.tree.map(np.asarray, p) for p in st["params"]]
    recs = [ref.round(st, program_h1[r] if program_h1 is not None else None)
            for r in range(rounds)]
    return {
        "w0": w0,
        "params": [jax.tree.map(np.asarray, p) for p in st["params"]],
        "loss": np.stack([r["loss"] for r in recs]),
        "H1": np.stack([r["H1"] for r in recs]),
        "beta": (np.stack([r["beta"] for r in recs])
                 if "beta" in recs[0] else None),
        "acc": ref.accuracy(st),
        "near": sum(r["near"] for r in recs),
        "flips": max(r["flips"] for r in recs),
        "settled_margin": max(r["settled_margin"] for r in recs),
        "active": [r["active"] for r in recs],
    }


def _leaf_norms(tree_list: List[Any], base: List[Any]) -> np.ndarray:
    out = []
    for t, b in zip(tree_list, base):
        for a, z in zip(jax.tree.leaves(t), jax.tree.leaves(b)):
            out.append(np.linalg.norm((np.asarray(a, np.float64)
                                       - np.asarray(z, np.float64)).ravel()))
    return np.asarray(out)


def worst_leaf(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Where ``delta_gap`` is read: the leaf (in task-major flattening
    order), its reference change norm and the median leaf's."""
    nr = _leaf_norms(ref["params"], ref["w0"])
    npg = _leaf_norms(prog["params"], ref["w0"])
    med = float(np.median(nr))
    rel = np.where(nr >= 1e-3 * med, np.abs(npg - nr) / np.maximum(nr, med),
                   0.0)
    paths = [f"{s}/{jax.tree_util.keystr(k)}" for s, p in enumerate(
        ref["params"]) for k, _ in jax.tree_util.tree_flatten_with_path(p)[0]]
    i = int(np.argmax(rel))
    return {"leaf": paths[i], "gap": float(rel[i]), "norm": float(nr[i]),
            "median": med}


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared, program (or stand-in) against reference."""
    out = {}
    lr = np.asarray(ref["loss"], np.float64)
    lp = np.asarray(prog["loss"], np.float64)
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-12)
    out["loss_gap"] = float(np.max(rel))
    out["loss_median_gap"] = float(np.median(rel))
    out["h1_gap"] = float(np.max(np.abs(np.asarray(prog["H1"], np.float64)
                                        - ref["H1"])))
    nr = _leaf_norms(ref["params"], ref["w0"])
    npg = _leaf_norms(prog["params"], ref["w0"])
    med = float(np.median(nr))
    moved = nr >= 1e-3 * med
    out["delta_gap"] = float(np.max(
        np.abs(npg - nr)[moved] / np.maximum(nr[moved], med)))
    if ref["beta"] is not None:
        out["beta_gap"] = float(np.max(np.abs(
            np.asarray(prog["beta"], np.float64) - ref["beta"])))
    out["acc_gap"] = float(np.max(np.abs(
        np.asarray(prog["acc"], np.float64) - ref["acc"])))
    out["settled_flips"] = float(ref["flips"])
    out["settled_margin"] = float(ref["settled_margin"])
    for k, v in out.items():
        if not np.isfinite(v):
            out[k] = float("inf")
    return out


def program_record(mets: Dict[str, np.ndarray], params: List[Any],
                   acc) -> Dict[str, Any]:
    """What the timed path returned for the chunk, in the reference's
    layout: monitors [R, S], beta [R, S, N], per-task params, accuracies."""
    return {"loss": mets["loss"], "H1": mets["H1"],
            "beta": mets.get("beta"), "params": params,
            "acc": np.asarray(acc, np.float64)}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    missing = set(values) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    return all(values[k] <= limits[k] for k in values)
