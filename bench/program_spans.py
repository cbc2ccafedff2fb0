"""The program's own spans (``repro.runtime.tracing``), as the readers of
``program_span`` metrics take them: each decode round of the window, with
the host time of the spans inside it.

Span times are ``time.perf_counter()`` seconds.  Every ``sched.step`` span
carries the scheduler clock's reading at its start (``clock``), and the
run gives the scheduler its window clock, so a span inside a step goes on
the window clock by that step's offset.  A round is in the window when its
``sched.round`` span starts inside it.

A program without the module, or a run that left no such spans, gives
``None``: the metric is left out of the result line.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

ROUND = "sched.round"
STEP = "sched.step"


def decode_rounds(run) -> Optional[List[Dict[str, float]]]:
    """Per ``sched.round`` started in the window: seconds of the spans
    inside it, summed by name; None when there is no such round."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    spans = tracing.spans()
    by_id = {s.id: s for s in spans}

    def enclosing(s, name):
        while s.parent is not None:
            s = by_id.get(s.parent)
            if s is None or s.name == name:
                return s
        return None

    rounds: Dict[int, Dict[str, float]] = {}
    for s in spans:
        if s.name != ROUND:
            continue
        step = enclosing(s, STEP)
        if step is not None and "clock" in step.counts \
                and run.in_window(s.t0 - step.t0 + step.counts["clock"]):
            rounds[s.id] = {}
    for s in spans:
        r = enclosing(s, ROUND)
        if r is not None and r.id in rounds:
            parts = rounds[r.id]
            parts[s.name] = parts.get(s.name, 0.0) + s.seconds
    return list(rounds.values()) or None


def mean_ms(run, names: Sequence[str]) -> Optional[float]:
    """Mean over the window's decode rounds of the host time spent in the
    spans ``names``, ms."""
    rounds = decode_rounds(run)
    if not rounds:
        return None
    total = sum(r.get(n, 0.0) for r in rounds for n in names)
    return 1e3 * total / len(rounds)
