"""Model-step layer: device time of the decode program (jit_step_fn) per
execution in the traced window, in ms."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    sec, n = trace_reduce.module_seconds(run.trace, "jit_step_fn")
    return 1e3 * sec / n if n else None
