"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
operation and idle gaps, each gap labelled by the benchmark's host span
that was open when it happened.

Device operations are the events of a device plane's "XLA Ops" line (one
plane per chip, ``/device:TPU:<n>``).  A trace recorded on the CPU has no
device plane; there the operations are the host events that carry an
``hlo_op`` stat, grouped by their ``device_ordinal``, so the same code can
be checked on a small CPU trace.  Host spans are the events whose names
start with ``bench.`` (``jax.profiler.TraceAnnotation`` in the harness);
``bench.window`` marks the measured window.

All times are in the trace's own nanoseconds; ``Reduced`` reports
seconds.  Busy time is the union of a device's operation intervals, so
operations that overlap (a ``while`` and the ops of its body) are counted
once.  An operation is named by its HLO instruction name (the text before
`` = `` in the event's name, without the ``%``) and matched by its opcode
(``psum.40`` is an ``all-reduce``).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# opcodes of ops that contain other ops (a loop's body runs as ops of its
# own): kept for the busy union, left out of time per op
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
# innermost first: a gap inside a decode call is the decode call's
LABEL_ORDER = ("bench.chunk", "bench.decode", "bench.step")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


class _Union:
    """Sorted disjoint intervals with a running total, for "how much of
    [s, e) is covered" in O(log n)."""

    def __init__(self, starts: np.ndarray, ends: np.ndarray):
        order = np.argsort(starts, kind="stable")
        s, e = starts[order], ends[order]
        if len(s):
            run_end = np.maximum.accumulate(e)
            new = np.ones(len(s), bool)
            new[1:] = s[1:] > run_end[:-1]
            idx = np.flatnonzero(new)
            self.a = s[idx]
            self.b = np.maximum.reduceat(run_end, idx) if len(idx) else s[:0]
        else:
            self.a, self.b = s, e
        self.cum = np.concatenate([[0.0], np.cumsum(self.b - self.a)])

    def covered_before(self, t: np.ndarray) -> np.ndarray:
        t = np.asarray(t, np.float64)
        i = np.searchsorted(self.a, t, side="right") - 1
        ic = np.clip(i, 0, None)
        inside = np.clip(t - self.a[ic] if len(self.a) else t * 0, 0,
                         (self.b[ic] - self.a[ic]) if len(self.a) else 0)
        return np.where(i >= 0, self.cum[ic] + inside, 0.0)

    def covered(self, s, e) -> float:
        """Total covered length inside the intervals [s_k, e_k)."""
        s, e = np.asarray(s, np.float64), np.asarray(e, np.float64)
        if not len(self.a) or not s.size:
            return 0.0
        return float(np.sum(self.covered_before(e) - self.covered_before(s)))

    def gaps(self, w0: float, w1: float) -> Tuple[np.ndarray, np.ndarray]:
        """Uncovered stretches inside [w0, w1)."""
        a = np.clip(self.a, w0, w1)
        b = np.clip(self.b, w0, w1)
        keep = b > a
        a, b = a[keep], b[keep]
        gs = np.concatenate([[w0], b])
        ge = np.concatenate([a, [w1]])
        keep = ge > gs
        return gs[keep], ge[keep]


class Reduced:
    """What a trace says, restricted to the measured window."""

    def __init__(self, ops: Dict[int, tuple],
                 spans: Dict[str, Tuple[np.ndarray, np.ndarray]],
                 window: Tuple[float, float]):
        self.ops = ops          # device -> (start, end, names, opcodes)
        self.spans = spans                  # kind -> (start, end) sorted
        self.w0, self.w1 = window
        self.unions = {d: _Union(v[0], v[1]) for d, v in ops.items()}

    # ------------------------------------------------------------ basics
    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def _clip(self, s, e):
        s = np.clip(np.asarray(s, np.float64), self.w0, self.w1)
        e = np.clip(np.asarray(e, np.float64), self.w0, self.w1)
        keep = e > s
        return s[keep], e[keep]

    def span_times(self, kind: str) -> Tuple[np.ndarray, np.ndarray]:
        s, e = self.spans.get(kind, (np.zeros(0), np.zeros(0)))
        return self._clip(s, e)

    def count(self, kind: str) -> int:
        s, e = self.spans.get(kind, (np.zeros(0), np.zeros(0)))
        return int(np.sum((s >= self.w0) & (s < self.w1)))

    # ------------------------------------------------------------ time
    def busy_in(self, s, e) -> float:
        """Seconds of device busy time inside the intervals [s, e) (trace
        ns, clipped to the window), averaged over devices."""
        s, e = self._clip(s, e)
        if not self.unions:
            return 0.0
        return float(np.mean([u.covered(s, e) for u in self.unions.values()])
                     ) * 1e-9

    def busy_s(self, kind: Optional[str] = None) -> float:
        """Busy seconds in the window, or inside its spans of ``kind``."""
        if kind is None:
            return self.busy_in([self.w0], [self.w1])
        return self.busy_in(*self.span_times(kind))

    def op_s(self, match: Sequence[str], kind: Optional[str] = None
             ) -> Optional[float]:
        """Seconds of the operations whose HLO opcode is one of ``match``
        (or starts with it, as ``all-reduce-start`` does), started in the
        window (or inside its spans of ``kind``), averaged over devices;
        None when no operation matches."""
        ss, se = (np.array([self.w0]), np.array([self.w1])) if kind is None \
            else self.span_times(kind)
        match = tuple(match)
        tot, seen = [], False
        for s, e, _, ops in self.ops.values():
            pick = np.fromiter((op.startswith(match) for op in ops), bool,
                               len(ops))
            seen = seen or bool(pick.any())
            st, du = s[pick], (e - s)[pick]
            i = np.searchsorted(ss, st, side="right") - 1
            inside = (i >= 0) & (st < se[np.clip(i, 0, None)]) if len(ss) \
                else np.zeros(len(st), bool)
            tot.append(float(du[inside].sum()))
        return float(np.mean(tot)) * 1e-9 if seen else None

    # ------------------------------------------------------------ breakdown
    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations (loops left out, their bodies' ops counted)
        with most device time in the window, [name, seconds averaged over
        devices]."""
        acc: Dict[str, float] = {}
        for s, e, names, ops in self.ops.values():
            inside = (s >= self.w0) & (s < self.w1)
            for name, op, d in zip(np.asarray(names, object)[inside],
                                   np.asarray(ops, object)[inside],
                                   (e - s)[inside]):
                if _leaf(op):
                    acc[name] = acc.get(name, 0.0) + float(d)
        k = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, v * 1e-9 / k] for name, v in top]

    def label(self, t: float) -> str:
        for kind in LABEL_ORDER:
            s, e = self.spans.get(kind, (np.zeros(0), np.zeros(0)))
            i = np.searchsorted(s, t, side="right") - 1
            if i >= 0 and t < e[i]:
                return kind[len(SPAN_PREFIX):]
        return "outside_step"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle stretches of the first device in the
        window, [host span open at the time, seconds]."""
        if not self.unions:
            return []
        gs, ge = self.unions[self.devices[0]].gaps(self.w0, self.w1)
        order = np.argsort(gs - ge)[:n]
        return [[self.label(0.5 * (gs[i] + ge[i])),
                 float(ge[i] - gs[i]) * 1e-9] for i in order]


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def opcode(name: str) -> str:
    """``%psum.4 = bf16[16,4096]{1,0} all-reduce(%x), ...`` ->
    ``all-reduce``; a name without an HLO text is its own opcode."""
    if " = " not in name:
        return name
    m = _OPCODE.search(name.split(" = ", 1)[1])
    return m.group(1) if m else short_name(name)


def _leaf(op: str) -> bool:
    return not op.startswith(CONTAINERS)


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def reduce(path: str, devices: Optional[Sequence[int]] = None) -> Reduced:
    """Read the trace at ``path``.  ``devices`` keeps only those device
    ids (the chips the cell uses)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    spans: Dict[str, list] = {}
    cpu_ops: Dict[int, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops.setdefault(dev, []).extend(
                    (ev.start_ns, ev.end_ns, short_name(ev.name),
                     opcode(ev.name)) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        spans.setdefault(name, []).append(
                            (ev.start_ns, ev.end_ns))
                    elif line.name != "python" and ev.duration_ns > 0:
                        op = _stat(ev, "hlo_op")
                        if op is not None:
                            d = _stat(ev, "device_ordinal") or 0
                            cpu_ops.setdefault(int(d), []).append(
                                (ev.start_ns, ev.end_ns, name,
                                 name.rsplit(".", 1)[0]))
    if not ops:
        ops = cpu_ops
    if devices is not None:
        ops = {d: v for d, v in ops.items() if d in set(devices)}
    arr_ops = {}
    for d, evs in ops.items():
        evs.sort()
        arr_ops[d] = (np.array([e[0] for e in evs], np.float64),
                      np.array([e[1] for e in evs], np.float64),
                      [e[2] for e in evs], [e[3] for e in evs])
    arr_spans = {}
    for k, v in spans.items():
        v.sort()
        arr_spans[k] = (np.array([a for a, _ in v], np.float64),
                        np.array([b for _, b in v], np.float64))
    if WINDOW in arr_spans:
        w = (float(arr_spans[WINDOW][0][0]), float(arr_spans[WINDOW][1][0]))
    else:
        starts = [v[0][0] for v in arr_ops.values() if len(v[0])]
        ends = [v[1].max() for v in arr_ops.values() if len(v[1])]
        w = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Reduced(arr_ops, arr_spans, w)
