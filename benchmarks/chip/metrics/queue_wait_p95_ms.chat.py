"""Engine layer: 95th percentile of the wait from submit to leaving
QUEUED, over requests admitted inside the window (harness timestamps,
polled after every engine step)."""
import numpy as np


def read(run):
    waits = [(r["t_admit"] - r["t_submit"]) * 1e3 for r in run.reqs
             if r["t_admit"] is not None
             and run.t_open <= r["t_admit"] <= run.t_close]
    return float(np.percentile(waits, 95)) if waits else None
