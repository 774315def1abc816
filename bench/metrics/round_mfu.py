"""round_mfu (%): model FLOPs of the traced window over its length and the
chip's bf16 peak.

The FLOPs are counted from the world's shapes (``bench/flops.py``): per
round the loss probe over min(count, 64) real samples of every client and
forward plus backward over the local minibatches of the clients each task
trains (the mean active cohort of the checked rounds), and one test-set
forward per ``evaluate``.  Padding, the cohort slots that train without a
drawn client, and the sampler's arithmetic are not counted."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import flops  # noqa: E402


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or ctx["rounds"] == 0:
        return None
    tasks, _, avail = ctx["world"]
    specs = [t["spec"] for t in tasks]
    counts = np.stack([t["data"]["count"] for t in tasks], 1)
    srv = ctx["cfg"]["server"]
    per_round = flops.round_flops(specs, counts, np.asarray(ctx["trained"]),
                                  srv["local_epochs"], srv["batch_size"])
    per_eval = flops.eval_flops(specs, [
        int(np.prod(t["test"]["y"].shape[:1])) for t in tasks])
    total = ctx["rounds"] * per_round + ctx["chunks"] * per_eval
    return 100.0 * total / tr.window_s / ctx["peak"]["flops_bf16"]
