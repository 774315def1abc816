"""batched_dot.device_us (us/round): device time of the Eq. 20 beta
kernel (``kernels.batched_dot``: <G, h> and ||h||^2 per cohort row) per
round of the traced window.  The trace names the call after its jitted
wrapper (``vmap_jit_batched_dot__.<n>``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce  # noqa: E402


def is_batched_dot(text):
    return "batched_dot" in trace_reduce.op_name(text)


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["rounds"] == 0:
        return None
    spent = tr.kernel_seconds(is_batched_dot)
    return None if spent is None else 1e6 * spent / ctx["rounds"]
