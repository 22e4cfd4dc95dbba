"""Engine layer: executions of jitted programs on the device (the ``XLA
Modules`` line: the model's programs and every eager operation) in the
traced window, per engine step begun while the trace was on."""


def read(run):
    if run.trace is None:
        return None
    steps = sum(1 for t0, _, _ in run.steps
                if run.trace_on <= t0 <= run.trace_off)
    if not steps:
        return None
    return sum(n for _, n in run.trace["modules"].values()) / steps
