"""round.stats_mfu (%): the loss probe's model FLOPs a round over the
device time of the round's ``mmfl.stats`` phase (``round.stats.device_ms``)
and the chip's bf16 peak.

The FLOPs are the probe's part of ``flops.round_flops``: a forward over
min(count, 64) real samples of every client, for each task at its kind's
``forward_flops``.  The padded rows the probe also reads are not
counted."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import flops  # noqa: E402


def read(ctx):
    phases = ctx["phases"]
    ms = None if phases is None else phases["round.stats.device_ms"]
    if not ms:
        return None
    tasks = ctx["world"][0]
    specs = [t["spec"] for t in tasks]
    counts = np.stack([t["data"]["count"] for t in tasks], 1)
    srv = ctx["cfg"]["server"]
    per_round = flops.round_flops(specs, counts, np.zeros(len(specs)),
                                  srv["local_epochs"], srv["batch_size"])
    return 100.0 * per_round / (ms / 1e3) / ctx["peak"]["flops_bf16"]
