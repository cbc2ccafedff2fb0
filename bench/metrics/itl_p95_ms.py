"""95th percentile of every gap between consecutive tokens of one request,
over every request that arrived in the window, ms.  A token's time is when
the backend call that made it returned."""
import numpy as np


def read(run):
    g = [np.diff(r.times) for r in run.window if len(r.times) > 1]
    g = np.concatenate(g) if g else np.zeros(0)
    return float(np.percentile(g, 95)) * 1e3 if g.size else None
