"""The traffic generator on the CPU: each mix names generators that
exist, the same seed gives the same requests, every seed the same work,
lengths stay in their clips and multiples, and Poisson arrivals keep
their rate."""
import os

import numpy as np
import pytest

from bench import harness, loadgen, modules

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                       "traffic"))
               if f.endswith(".json"))


def _mix(name):
    return loadgen.load_mix(os.path.join(harness.BENCH_DIR, "traffic",
                                         name + ".json"))


def _items(mix, seed):
    return loadgen.open_schedule(mix, seed, 40.0, 92544)


@pytest.mark.parametrize("name", MIXES)
def test_mix_names_its_generators(name):
    mix = _mix(name)
    assert callable(modules.load("traffic", mix["arrivals"]).gaps)
    assert callable(modules.load("traffic", mix["tokens"]).prompts)
    for key in ("prompt", "output"):
        assert callable(modules.load("traffic",
                                     mix[key]["lengths"]).lengths)
    with pytest.raises(KeyError, match="no traffic named"):
        loadgen.open_schedule(dict(mix, arrivals="no-such-kind"), 1, 40.0,
                              92544)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a, b = _items(mix, 2 ** 33 + 5), _items(mix, 2 ** 33 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.rid, x.max_new_tokens, x.due) == (y.rid, y.max_new_tokens,
                                                    y.due)
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = _items(mix, 2 ** 33 + 6)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work_other_order(name):
    mix = _mix(name)
    a, b = _items(mix, 11), _items(mix, 2 ** 31 + 3)
    for seg in {x.segment for x in a}:
        sa = [x for x in a if x.segment == seg]
        sb = [x for x in b if x.segment == seg]
        assert sorted(len(x.prompt) for x in sa) == \
            sorted(len(x.prompt) for x in sb)
        assert sorted(x.max_new_tokens for x in sa) == \
            sorted(x.max_new_tokens for x in sb)
        if mix["arrivals"] == "poisson":
            span = {"ramp": mix["ramp_s"], "window": 40.0,
                    "tail": mix["tail_s"]}[seg]
            q = modules.load("traffic", "poisson").quantile_gaps(len(sa),
                                                                 span)
            for s in (sa, sb):     # the gaps are the quantiles, one left over
                d = np.diff([x.due for x in s])
                assert np.allclose(np.min(np.abs(d[:, None] - q[None]), 1), 0)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_clips_and_multiples(name):
    mix = _mix(name)
    p, o = mix["prompt"], mix["output"]
    for x in _items(mix, 7):
        assert p["min"] <= len(x.prompt) <= p["max"]
        assert len(x.prompt) % p.get("multiple", 1) == 0
        assert o["min"] <= x.max_new_tokens <= o["max"]
        assert x.prompt.min() >= 2 and x.prompt.max() < 92544
    lens = loadgen.lengths(p, 4096)
    assert abs(np.median(lens) - p["median"]) <= p.get("multiple", 1)


def test_open_loop_rate():
    mix = {"arrivals": "poisson", "tokens": "uniform", "rate_per_s": 2.5,
           "ramp_s": 8, "tail_s": 20,
           "prompt": {"lengths": "lognormal", "median": 512, "sigma": 0.5,
                      "min": 64, "max": 1024, "multiple": 64},
           "output": {"lengths": "lognormal", "median": 64, "sigma": 0.5,
                      "min": 8, "max": 256}}
    items = loadgen.open_schedule(mix, 3, 40.0, 1000)
    win = [x for x in items if x.segment == "window"]
    assert len(win) == 100
    due = np.array([x.due for x in win])
    assert due.min() == 0.0 and due.max() < 40.0
    assert np.all(np.diff([x.due for x in items]) > 0)
    gaps = np.diff(due)
    assert abs(gaps.mean() - 1 / 2.5) < 0.05
    # exponential: the gaps' spread equals their mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert sum(x.segment == "ramp" for x in items) == 20
    assert all(x.due < 0 for x in items if x.segment == "ramp")

