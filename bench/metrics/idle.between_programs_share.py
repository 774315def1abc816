"""idle.between_programs_share (%): the share of the traced window in which
the device idles between two programs' executions; with
``idle.in_program_share`` it sums to ``device_idle_share``.  Operations
are taken innermost and named by their program's HLO op metadata
(``bench/trace_scopes.py``)."""


def read(ctx):
    phases = ctx["phases"]
    return None if phases is None else phases["idle.between_programs_share"]
