"""Model-step layer: device time of the admission-prefill program
(jit_admit_fn) in the traced window per thousand real (unpadded) prompt
tokens it prefilled."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    sec, n = trace_reduce.module_seconds(run.trace, "jit_admit_fn")
    tokens = sum(int(sum(c[2][3])) for c in run.traced_calls()
                 if c[0] == "prefill")
    return 1e6 * sec / tokens if n and tokens else None
