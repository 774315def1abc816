"""Model operations counted from shapes, for ``round_mfu``.  Nothing here
reads a compiled program's cost analysis: the counts follow the
algorithm, so a change to the program cannot move them.

Model FLOPs count a multiply-add as 2 and leave out biases, activations,
pooling and the softmax.  Backward is twice the forward.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def cnn_forward_flops(n_classes: int, channels: int, in_ch: int = 1,
                      side: int = 28, hidden: int = 128) -> int:
    """Per sample: conv1, conv2 (3x3, SAME, the second at half the side
    after pooling), fc1, fc2."""
    c2, half, flat = 2 * channels, side // 2, (side // 4) ** 2 * 2 * channels
    return (side * side * 9 * in_ch * channels * 2
            + half * half * 9 * channels * c2 * 2
            + flat * hidden * 2
            + hidden * n_classes * 2)


def forward_flops(spec: Dict[str, Any]) -> int:
    if spec["kind"] != "image":
        raise ValueError(f"no operation count for kind {spec['kind']!r}")
    return cnn_forward_flops(spec["n_classes"], spec["channels"])


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
