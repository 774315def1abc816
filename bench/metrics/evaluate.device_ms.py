"""evaluate.device_ms (ms/chunk): device time of the operations under the
engine's scope ``mmfl.evaluate`` (test accuracy of every task), per
``evaluate`` of the traced window.  Operations are taken innermost and
named by their program's HLO op metadata (``bench/trace_scopes.py``)."""


def read(ctx):
    phases = ctx["phases"]
    return None if phases is None else phases["evaluate.device_ms"]
