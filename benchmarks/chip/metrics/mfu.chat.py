"""Whole step: the model's operations for the work done in the window
(work.model_ops) over the host time of the engine steps that ran a
program, times the chip's int8 peak, in percent."""
import work


def read(run):
    calls = [c for c in run.calls if run.t_open <= c[1] <= run.t_close]
    busy = sum(t1 - t0 for t0, t1, did in run.steps
               if did and run.t_open <= t0 and t1 <= run.t_close)
    if not calls or busy <= 0:
        return None
    ops = work.model_ops(run.config, calls)
    return 100.0 * ops / (busy * run.peak("int8_ops_per_s"))
