"""Host time of one scheduler iteration outside the backend's calls: the
span around ``Scheduler.step`` minus the host time of that iteration's
prefill chunk and decode round (``StepRecord.wall_s``), mean over the
window's iterations that called the backend, ms."""
import numpy as np


def read(run):
    v = [t1 - t0 - busy for t0, t1, busy in run.iters if run.in_window(t0)]
    return float(np.mean(v)) * 1e3 if v else None
