"""Share of the traced window, while at least one request is in flight,
in which no operation runs on the device (averaged over the chips), %."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    iv = sorted((max(r.arrival, 0.0),
                 run.seconds if np.isnan(r.finished)
                 else min(r.finished, run.seconds))
                for r in run.requests.values() if r.arrival < run.seconds)
    merged = []
    for a, b in iv:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    if not merged:
        return None
    s = run.to_trace([a for a, _ in merged])
    e = run.to_trace([b for _, b in merged])
    span = float(np.sum(e - s)) * 1e-9
    return 100.0 * (1.0 - run.trace.busy_in(s, e) / span) if span else None
