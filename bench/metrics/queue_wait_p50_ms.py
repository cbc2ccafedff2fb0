"""Median wait in the scheduler's queue (admission minus due time) of the
requests that arrived in the window, ms."""
import numpy as np


def read(run):
    v = [r.admitted - r.arrival for r in run.window
         if not np.isnan(r.admitted)]
    return float(np.median(v)) * 1e3 if v else None
