"""75th percentile of time to first token over every request that arrived
in the window, from its due time, ms.  The highest percentile with ten
requests beyond it in the chat cell's window (41 requests)."""
import numpy as np


def read(run):
    v = [r.first_token - r.arrival for r in run.window
         if not np.isnan(r.first_token)]
    return float(np.percentile(v, 75)) * 1e3 if v else None
