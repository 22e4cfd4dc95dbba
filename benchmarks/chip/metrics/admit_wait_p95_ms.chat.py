"""Engine layer: 95th percentile of the wait from submit to admission on
the engine's own stamps (``Request.submit_s`` to ``Request.admit_s``),
over the requests admitted inside the window, in ms."""
import numpy as np


def read(run):
    waits = []
    for r in run.reqs:
        req = r.get("req")
        admit = getattr(req, "admit_s", None)
        if admit is not None and run.t_open <= admit <= run.t_close:
            waits.append((admit - req.submit_s) * 1e3)
    return float(np.percentile(waits, 95)) if waits else None
