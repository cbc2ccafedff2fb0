"""Code the benchmark finds by name: ``bench/<kind>/<name>.py``."""
from __future__ import annotations

import functools
import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def path(kind: str, name: str) -> str:
    return os.path.join(BENCH_DIR, kind, name + ".py")


@functools.lru_cache(maxsize=None)
def load(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    p = path(kind, name)
    if not os.path.exists(p):
        raise KeyError(f"no {kind} named {name!r}: {p} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
