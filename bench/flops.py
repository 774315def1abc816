"""Model operations counted from shapes, for ``round_mfu``.  Nothing here
reads a compiled program's cost analysis: the counts follow the
algorithm, so a change to the program cannot move them.

Model FLOPs count a multiply-add as 2 and leave out biases, activations,
pooling and the softmax.  Backward is twice the forward.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from world import load_kind


def forward_flops(spec: Dict[str, Any]) -> int:
    """Per sample, one task's model: its kind's count
    (``bench/kinds/<kind>.py``)."""
    return load_kind(spec["kind"]).forward_flops(spec)


def round_flops(specs: Sequence[Dict[str, Any]], counts: np.ndarray,
                trained: np.ndarray, local_steps: int, batch: int,
                probe_take: int = 64) -> float:
    """Model FLOPs one round requires: the loss probe's forward over
    min(count, probe_take) real samples of every client, and forward plus
    backward over ``local_steps`` minibatches of ``batch`` samples for the
    clients each task trains (``trained[s]``, a mean over rounds)."""
    total = 0.0
    for s, spec in enumerate(specs):
        fwd = forward_flops(spec)
        total += fwd * float(np.minimum(counts[:, s], probe_take).sum())
        total += 3 * fwd * local_steps * batch * float(trained[s])
    return total


def eval_flops(specs: Sequence[Dict[str, Any]],
               test_sizes: Sequence[int]) -> float:
    """One ``evaluate``: a forward over every task's test set."""
    return float(sum(forward_flops(sp) * n
                     for sp, n in zip(specs, test_sizes)))
