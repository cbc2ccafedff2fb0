"""Mean host time of a prefill chunk's backend call in the window, ms."""
import numpy as np


def read(run):
    v = [t1 - t0 for t0, t1, _, _ in run.chunks if run.in_window(t0)]
    return float(np.mean(v)) * 1e3 if v else None
