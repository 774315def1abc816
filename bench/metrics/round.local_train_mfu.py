"""round.local_train_mfu (%): local training's model FLOPs a round over
the device time of the round's ``mmfl.local_train`` phase
(``round.local_train.device_ms``) and the chip's bf16 peak.

The FLOPs are the training part of ``flops.round_flops``: forward plus
backward (three forwards) over ``local_epochs`` minibatches of
``batch_size`` samples for the clients each task trains (the mean active
cohort of the checked rounds).  The cohort slots that train without a
drawn client are not counted."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import flops  # noqa: E402


def read(ctx):
    phases = ctx["phases"]
    ms = None if phases is None else phases["round.local_train.device_ms"]
    if not ms:
        return None
    tasks = ctx["world"][0]
    specs = [t["spec"] for t in tasks]
    no_probe = np.zeros((len(tasks[0]["data"]["count"]), len(specs)))
    srv = ctx["cfg"]["server"]
    per_round = flops.round_flops(specs, no_probe, np.asarray(ctx["trained"]),
                                  srv["local_epochs"], srv["batch_size"])
    return 100.0 * per_round / (ms / 1e3) / ctx["peak"]["flops_bf16"]
