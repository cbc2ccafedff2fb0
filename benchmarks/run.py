"""Benchmark aggregator: one module per paper table/figure + assigned-scope
benches.  Prints ``name,us_per_call,derived`` CSV.

Each module runs in its own child process (``--module NAME``), one after
another, and this parent never imports JAX: a process that has touched
JAX holds the accelerator, and a module that starts children of its own
(decode_bench, fig4_validation) would then find it taken.  Exits 1 when
any module fails.
"""
from __future__ import annotations

import os
import subprocess
import sys

MODULES = [
    "benchmarks.table3_tp",
    "benchmarks.table4_models",
    "benchmarks.table5_pp",
    "benchmarks.table6_hybrid",
    "benchmarks.fig6_volume",
    "benchmarks.fig7_scaling",
    "benchmarks.fig8_9_10_slo",
    "benchmarks.fig4_validation",
    "benchmarks.planner_bench",
    "benchmarks.kernel_bench",
    "benchmarks.roofline_table",
    "benchmarks.perf_variants",
    "benchmarks.decode_bench",
]
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_module(modname: str) -> None:
    """Print one module's rows as CSV lines (runs in the child)."""
    import importlib
    for name, us, derived in importlib.import_module(modname).rows():
        print(f"{name},{us:.2f},{derived}", flush=True)


def main() -> None:
    print("name,us_per_call,derived", flush=True)
    failures = []
    for modname in MODULES:
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--module", modname],
            cwd=REPO)
        if r.returncode:
            failures.append(modname)
    if failures:
        print(f"# FAILED modules: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    if "--module" in sys.argv:
        run_module(sys.argv[sys.argv.index("--module") + 1])
    else:
        main()
