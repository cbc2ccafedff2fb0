"""Spans inside the serving path (``repro.runtime.tracing``).

A short chunked serve over the paged GSPMD backend, on a reduced dense
config, checked for: the parts of every decode round and prefill chunk, in
order and inside their parent; ``StepRecord.wall_s`` as its span's
duration; one offset between the spans' clock and the scheduler's; the
ring's bound; a span whose body raises; the span names in a profiler
trace; and the named scopes of the paged step in its lowered HLO.
"""
import gc
import glob
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.transformer import get_model
from repro.runtime import tracing
from repro.runtime.backends import make_backend
from repro.runtime.request import Request
from repro.runtime.scheduler import Scheduler
from repro.runtime.tracing import span

ROUND_PARTS = ["backend.pages", "backend.dispatch", "backend.wait",
               "backend.fetch", "backend.argmax"]
CHUNK_PARTS = ROUND_PARTS[1:]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("internlm2-1.8b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n=4):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, 9 + 3 * i)
                    .astype(np.int32), max_new_tokens=4 + i)
            for i in range(n)]


def _serve(cfg, params):
    """(the serve's spans, its StepRecords)."""
    backend = make_backend("gspmd", cfg, params, num_slots=4, max_len=64,
                           paged=True, page_size=8)
    first = max((s.id for s in tracing.spans()), default=-1)
    gc.disable()          # a collection between two stamps of one bridge
    try:
        steps = Scheduler(backend, chunk_size=8).run(_requests(cfg)).steps
    finally:
        gc.enable()
    return [s for s in tracing.spans() if s.id > first], steps


@pytest.fixture(scope="module")
def served(setup):
    return _serve(*setup)


def _children(spans, parent):
    return sorted((s for s in spans if s.parent == parent.id),
                  key=lambda s: s.id)


def test_every_round_has_its_parts_in_order(served):
    spans, _ = served
    rounds = [s for s in spans if s.name == "sched.round"]
    assert len(rounds) >= 5
    for r in rounds:
        kids = _children(spans, r)
        assert [k.name for k in kids] == ROUND_PARTS
        assert r.t0 <= kids[0].t0 and kids[-1].t1 <= r.t1
        for a, b in zip(kids, kids[1:]):
            assert a.t0 <= a.t1 <= b.t0
        assert r.counts["live"] >= 1 and r.error is None
        assert kids[3].counts["bytes"] > 0
    assert sum(_children(spans, r)[0].counts["pages"] for r in rounds) > 0


def test_every_chunk_has_its_parts_in_order(served):
    spans, _ = served
    chunks = [s for s in spans if s.name == "sched.chunk"]
    assert len(chunks) >= 4
    for c in chunks:
        kids = _children(spans, c)
        assert [k.name for k in kids] == CHUNK_PARTS
        assert c.t0 <= kids[0].t0 and kids[-1].t1 <= c.t1
        assert 1 <= c.counts["tokens"] <= 8 and "rid" in c.counts


def test_wall_s_is_the_span_duration(served):
    spans, steps = served
    for phase, name in (("decode", "sched.round"), ("prefill", "sched.chunk")):
        recs = [r.wall_s for r in steps if r.phase == phase]
        got = [s.seconds for s in sorted(spans, key=lambda s: s.id)
               if s.name == name]
        assert recs and recs == got


def test_rounds_and_chunks_sit_in_steps(served):
    spans, _ = served
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name in ("sched.round", "sched.chunk"):
            assert by_id[s.parent].name == "sched.step"
        if s.name == "sched.step":
            assert s.parent is None


def test_one_clock_offset_across_steps(served):
    """The scheduler's wall clock is the perf counter less its start, so
    every step's ``clock`` bridge gives the same offset."""
    spans, _ = served
    off = np.array([s.counts["clock"] - s.t0 for s in spans
                    if s.name == "sched.step"])
    assert len(off) >= 5
    assert np.ptp(off) < 50e-6


def test_ring_holds_at_its_bound():
    first = max((s.id for s in tracing.spans()), default=-1)
    for i in range(tracing.RING + 7):
        with span("ring.fill", i=i):
            pass
    got = tracing.spans()
    assert len(got) == tracing.RING
    assert [s.counts["i"] for s in got[:2]] == [7, 8]
    assert got[-1].counts["i"] == tracing.RING + 6
    assert all(s.id > first for s in got)


def test_a_span_whose_body_raises_still_closes():
    with span("outer") as outer:
        with pytest.raises(ValueError):
            with span("inner") as inner:
                raise ValueError("bad")
        with span("after") as after:
            pass
    got = {s.id: s for s in tracing.spans()[-3:]}
    assert got[inner.id].error == "ValueError"
    assert got[inner.id].parent == outer.id
    assert got[after.id].parent == outer.id and got[after.id].error is None
    assert got[outer.id].error is None
    assert inner.t0 <= inner.t1 <= after.t0
    with span("top") as top:
        pass
    assert top.parent is None


def test_spans_show_on_a_host_line_of_a_profiler_trace(setup, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(*setup)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    names = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    names.setdefault(ev.name, line.name)
    for name in ["sched.step", "sched.round", "sched.chunk"] + ROUND_PARTS:
        assert name in names, name


def test_paged_step_carries_named_scopes(setup):
    cfg, params = setup
    model = get_model(cfg)
    cache = model.init_paged_cache(9, 8)
    args = (params, cache, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32))
    hlo = jax.jit(model.paged_step).lower(*args).as_text(dialect="hlo",
                                                         debug_info=True)
    # a scan body is its own computation: its ops' names start at its scope
    names = ["/" + n for n in re.findall(r'op_name="([^"]*)"', hlo)]
    for scope in ("embed", "layers", "attention/page_write",
                  "attention/page_gather", "attention", "mlp", "head"):
        assert any(f"/{scope}/" in n for n in names), scope
