"""stale_agg_refresh.device_us (us/round): device time of the fused
Eq. 18 delta + stale-store refresh kernel (``kernels.stale_agg``) per
round of the traced window.

The engine calls it from inside its task vmap, and the trace names the
call ``closed_call.<n>``, not after the kernel; it is picked by its call
signature instead: four scalar-prefetched [C] operands (s32 idx, f32
coeff, beta, act), then G [C, R, 128], the store [N, R, 128] and the stale
sum [R, 128] (``kernels/stale_agg/stale_agg.py::stale_agg_refresh``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce  # noqa: E402


def is_refresh(text):
    if "stale_agg_refresh" in trace_reduce.op_name(text):
        return True
    ops = trace_reduce.operand_shapes(text)
    return (len(ops) == 7 and ops[0][0] == "s32"
            and all(t == "f32" for t, _ in ops[1:])
            and len({d for _, d in ops[:4]}) == 1 and len(ops[0][1]) == 1
            and [len(d) for _, d in ops[4:]] == [3, 3, 2])


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["rounds"] == 0:
        return None
    spent = tr.kernel_seconds(is_refresh)
    return None if spent is None else 1e6 * spent / ctx["rounds"]
