"""setup.warmup_s (s): host clock around the set-up chunk, from
``init_state`` through the first ``rollout`` and ``evaluate``: the compile
or the compile-cache load of every program the window uses, and their
first execution."""


def read(ctx):
    return ctx["warmup_s"]
