"""idle.in_program_share (%): the share of the traced window in which the
device idles inside a program's execution.  Operations are taken innermost
and named by their program's HLO op metadata (``bench/trace_scopes.py``)."""


def read(ctx):
    phases = ctx["phases"]
    return None if phases is None else phases["idle.in_program_share"]
