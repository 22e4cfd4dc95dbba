"""95th percentile of time to first token over the requests due in the
window, each timed from when it was due (host clock); a request that
never got its first token counts as missing (+inf)."""
import math

import numpy as np


def read(run):
    ttft = [(r["times"][0] - run.t_open - r["spec"].due_s) * 1e3
            if r["times"] else math.inf for r in run.window_reqs
            if r["spec"].due_s is not None]
    if not ttft:
        return None
    value = float(np.percentile(ttft, 95))
    return value if math.isfinite(value) else None
