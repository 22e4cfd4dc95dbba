"""Engine layer: 95th percentile of the time from admission to the first
token on the engine's own stamps (``Request.admit_s`` to
``Request.first_token_s``, taken when the token is read back), over the
requests whose first token came inside the window, in ms."""
import numpy as np


def read(run):
    phases = []
    for r in run.reqs:
        req = r.get("req")
        first = getattr(req, "first_token_s", None)
        if first is not None and run.t_open <= first <= run.t_close:
            phases.append((first - req.admit_s) * 1e3)
    return float(np.percentile(phases, 95)) if phases else None
