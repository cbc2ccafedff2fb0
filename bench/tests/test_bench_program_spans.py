"""The ``program_span`` readers on spans made by hand: their values, the
window they keep to, and nothing read where the program keeps no spans."""
import sys
import types

import pytest

from bench import modules
from repro.runtime import tracing
from repro.runtime.tracing import Span

READERS = ("decode_pages_ms", "decode_dispatch_ms", "decode_sample_ms")


def _run(seconds=10.0):
    return types.SimpleNamespace(in_window=lambda t: 0.0 <= t < seconds)


class _Spans:
    """Builds one serving loop's spans the way the program closes them:
    children before parents, ids in opening order."""

    def __init__(self):
        self.out, self.ids = [], iter(range(10 ** 6))

    def step(self, t0, clock, rounds):
        """A ``sched.step`` at perf-counter ``t0`` whose scheduler clock
        reads ``clock``; ``rounds``: per round, (name, seconds) parts."""
        sid, t = next(self.ids), t0 + 0.001
        for parts in rounds:
            rid, r0 = next(self.ids), t
            for name, sec in parts:
                self.out.append(Span(next(self.ids), name, t, t + sec, rid,
                                     {}, None))
                t += sec
            self.out.append(Span(rid, "sched.round", r0, t, sid, {"live": 2},
                                 None))
        self.out.append(Span(sid, "sched.step", t0, t + 0.001, None,
                             {"clock": clock}, None))


ROUND = (("backend.pages", 0.0004), ("backend.dispatch", 0.0003),
         ("backend.wait", 0.060), ("backend.fetch", 0.002),
         ("backend.argmax", 0.0015))


def _read(monkeypatch, spans):
    monkeypatch.setattr(tracing, "spans", lambda: list(spans))
    return {n: modules.load("metrics", n).read(_run()) for n in READERS}


def test_readers_take_the_mean_per_round(monkeypatch):
    b = _Spans()
    slow = tuple((n, 2 * s) for n, s in ROUND)
    b.step(1000.0, 2.0, [ROUND])
    b.step(1000.5, 2.5, [slow])
    got = _read(monkeypatch, b.out)
    assert got["decode_pages_ms"] == pytest.approx(0.6)
    assert got["decode_dispatch_ms"] == pytest.approx(0.45)
    assert got["decode_sample_ms"] == pytest.approx(5.25)


def test_readers_keep_to_the_window(monkeypatch):
    """A round is in the window when its start, put on the window clock
    by its step's ``clock``, is in [0, seconds): here the perf counter
    runs 1000 s ahead of the window clock, and only the middle step's
    round starts inside."""
    b = _Spans()
    slow = tuple((n, 10 * s) for n, s in ROUND)
    b.step(999.0, -1.0, [slow])          # before the window opens
    b.step(1001.0, 1.0, [ROUND])
    b.step(1010.0, 10.0, [slow])         # after it closes
    got = _read(monkeypatch, b.out)
    assert got["decode_pages_ms"] == pytest.approx(0.4)
    assert got["decode_sample_ms"] == pytest.approx(3.5)


def test_spans_outside_a_step_or_a_round_are_not_read(monkeypatch):
    """Warm-up calls the backend with no scheduler around it, and a chunk
    is a step's child but no round's: neither is a decode round's."""
    b = _Spans()
    b.step(1001.0, 1.0, [ROUND])
    b.out.append(Span(10 ** 7, "backend.pages", 1001.0, 1002.0, None, {},
                      None))
    b.out.append(Span(10 ** 7 + 1, "backend.fetch", 1003.0, 1004.0, 0, {},
                      None))
    got = _read(monkeypatch, b.out)
    assert got["decode_pages_ms"] == pytest.approx(0.4)
    assert got["decode_sample_ms"] == pytest.approx(3.5)


def test_nothing_is_read_without_rounds(monkeypatch):
    assert _read(monkeypatch, []) == dict.fromkeys(READERS)
    b = _Spans()
    b.step(1020.0, 20.0, [ROUND])        # only after the window
    assert _read(monkeypatch, b.out) == dict.fromkeys(READERS)


def test_nothing_is_read_without_the_module(monkeypatch):
    """A program that has no ``repro.runtime.tracing``, as before the
    spans were added: every reader gives None and none raises."""
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    monkeypatch.delattr(sys.modules["repro.runtime"], "tracing",
                        raising=False)
    assert {n: modules.load("metrics", n).read(_run()) for n in READERS} \
        == dict.fromkeys(READERS)


def test_a_traced_run_reports_the_split(monkeypatch, tmp_path):
    """The harness's traced run at the tiny CPU size: the three readers
    find the program's rounds in the window, and their parts fit inside
    the probe's decode call time.  (Its trace goes to a directory of its
    own: another file's traced run may share the process's machine.)"""
    from bench import harness
    from bench.tests import test_bench_correct as tiny
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    res = tiny._run(tiny._spec(), monkeypatch, trace=True)
    got = {n: res["metrics"][n]["value"] for n in READERS}
    assert all(v > 0 for v in got.values()), got
    assert sum(got.values()) < res["metrics"]["decode_call_ms"]["value"]
