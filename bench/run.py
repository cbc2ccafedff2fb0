"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it starts
on: makes the weights and the traffic from ``--seed``, warms every shape
the cell uses, ramps the load, measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` records a profiler trace of the
window and reports its per-layer metrics instead.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that ``bench/peaks.json`` lacks,
and when anything compiles after warm-up.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        from bench import harness
        import repro  # noqa: F401  (the system under test must be here)
    except ImportError as e:
        log(f"cannot load the benchmark or the program: {e}")
        return 2
    try:
        spec = harness.resolve(harness.load_benchmark(ROOT), args.workload)
        log(f"compile cache: {harness.use_compile_cache()}")
        res = harness.execute(spec, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, log=log)
    except (harness.NoChip, harness.RunFailed, KeyError) as e:
        log(f"{type(e).__name__}: {e}")
        return 1
    for name, c in res["checks"].items():
        log(f"{name} {c['value']} limit {c['limit']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
