"""Kernels layer: the least time this chip could take for the qmatmul
kernel calls in the traced window (per call, the larger of work.py's
operations over the int8 peak and bytes over HBM bandwidth) over the
kernel's device time in the trace, in percent."""
import trace_reduce

OP = "qmatmul"          # the kernel's operation in the trace
KERNEL = "qmatmul"


def read(run):
    if run.trace is None:
        return None
    least, _, _ = run.work(run.traced_calls(), KERNEL)
    sec, n = trace_reduce.op_seconds(run.trace, OP)
    return 100.0 * least / sec if n and sec > 0 and least > 0 else None
