"""eval_share (%): host-clock seconds spent in ``RoundEngine.evaluate``
over the window's wall time (the traced run's window)."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * ctx["eval_s"] / ctx["window_s"]
