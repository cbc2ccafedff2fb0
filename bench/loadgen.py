"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is data, and the code it names is found by name in the same
directory.  ``arrivals`` names a module ``bench/traffic/<arrivals>.py``
whose ``gaps(mix, span, rng)`` gives a segment's inter-arrival gaps;
``prompt`` and ``output`` each name a module by their ``lengths`` key,
whose ``lengths(spec, n)`` gives ``n`` lengths; ``tokens`` names the
module whose ``prompts(mix, lengths, rng, vocab)`` fills the prompts with
token ids.  A length module returns the same multiset for every seed, and
the generator orders it by the seed; so does ``poisson.py`` with its
gaps.  Two seeds then differ in the order of arrivals and lengths, never
in how much there is to do, and the spread between runs measures the
system, not the draw.

Requests are open-loop: each is due at its time, whatever the server
does.  A schedule has three segments, each drawn on its own: ``ramp``
(``ramp_s`` seconds before the window opens, so the window starts in a
steady state), ``window`` (the measured seconds), and ``tail`` (``tail_s``
seconds of further arrivals, so the load stays on while the window's
requests finish).

The generator imports nothing of the program; it returns plain numbers
and numpy arrays.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List

import numpy as np

from bench import modules


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    due: float = 0.0            # seconds from the window's opening
    segment: str = ""           # "ramp" | "window" | "tail"


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from the module that ``spec["lengths"]`` names."""
    return modules.load("traffic", spec["lengths"]).lengths(spec, n)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def open_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Item]:
    """Every request of a run, due times relative to the window opening
    (the ramp's are negative), in due order."""
    arrivals = modules.load("traffic", mix["arrivals"])
    tokens = modules.load("traffic", mix["tokens"])
    rng = _rng(seed, 1)
    ramp, tail = float(mix.get("ramp_s", 0)), float(mix.get("tail_s", 0))
    items: List[Item] = []
    for name, span, start in (("ramp", ramp, -ramp),
                              ("window", float(seconds), 0.0),
                              ("tail", tail, float(seconds))):
        gaps = np.asarray(arrivals.gaps(mix, span, rng), np.float64)
        n = len(gaps)
        if n == 0:
            continue
        plens = rng.permutation(lengths(mix["prompt"], n))
        olens = rng.permutation(lengths(mix["output"], n))
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        prompts = tokens.prompts(mix, plens, rng, vocab)
        items += [Item(rid=len(items) + i, prompt=prompts[i],
                       max_new_tokens=int(olens[i]), due=float(due[i]),
                       segment=name)
                  for i in range(n)]
    return items
