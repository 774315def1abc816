"""round.unscoped_share (%): the share of the round program's device time
that lies under no ``mmfl.*`` scope (the scan's plumbing, the copies XLA
inserts).  Operations are taken innermost and named by their program's HLO
op metadata (``bench/trace_scopes.py``)."""


def read(ctx):
    phases = ctx["phases"]
    return None if phases is None else phases["round.unscoped_share"]
