"""round.aggregate.device_ms (ms/round): device time of the round's
operations under the engine's scope ``mmfl.aggregate`` and outside
``mmfl.local_train`` (coefficients, stale store, the kernels), per round
of the traced window.  Operations are taken innermost and named by their
program's HLO op metadata (``bench/trace_scopes.py``)."""


def read(ctx):
    phases = ctx["phases"]
    return None if phases is None else phases["round.aggregate.device_ms"]
