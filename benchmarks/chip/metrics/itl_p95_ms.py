"""95th percentile of every gap between consecutive output tokens of a
request, over the gaps that end inside the window (host clock)."""
import numpy as np


def read(run):
    gaps = []
    for r in run.reqs:
        t = r["times"]
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:])
                    if run.t_open <= b <= run.t_close)
    return float(np.percentile(gaps, 95)) if gaps else None
