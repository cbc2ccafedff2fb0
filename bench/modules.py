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


# What an architecture module gives: the check of the program's config
# against the file, the weight layout, and the counts the metric readers
# take from shapes.
ARCH_API = ("check_program", "leaf_specs", "param_count",
            "kv_bytes_per_token", "pass_flops", "decode_flops",
            "decode_least_bytes")


def arch(cfg: dict):
    """The architecture module a configuration file names under ``"arch"``
    (``bench/arch/<arch>.py``), with every function of ``ARCH_API``."""
    if "arch" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no \"arch\" "
                       f"(a module bench/arch/<arch>.py)")
    mod = load("arch", cfg["arch"])
    missing = [fn for fn in ARCH_API if not callable(getattr(mod, fn, None))]
    if missing:
        raise KeyError(f"{path('arch', cfg['arch'])} lacks {missing}")
    return mod
