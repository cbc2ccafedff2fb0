"""Readings for the limits of ``correct``, and the rate of an open-loop cell.

    python3 bench/calibrate.py limits --workload <cell> --seconds 10 --seeds 1 2 3 ...
    python3 bench/calibrate.py knee --workload <cell> --seconds 40 --seeds 1 2 --rates 1.2 1.5 ...

``limits`` serves a short window at the cell's own load for each seed in
one process and reads, on the same sampled requests, the widest logit gap
of the program's served tokens and of the float8 control's first choices
(``harness.gaps(control=True)``).  Both go through the harness's own
``checks`` and ``passes`` at the committed limit: the program has to come
out correct and the control not.  The limit in ``limits/<cell>.json`` is
set between the program's largest reading and the control's smallest.

``knee`` serves the cell's mix at each rate in turn on one backend, for
each seed, and reports whether the backlog grew over the window: the
requests waiting for a slot, in the window's first and second half.  The
highest rate whose backlog does not grow is the knee.

Neither is part of a benchmark run; both print one JSON object per reading
and need the chip, as ``run.py`` does.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(spec, run, gap_list):
    """``checks`` with the control's first choices in the served tokens'
    place: what ``correct`` would say of the control."""
    from bench import harness
    return harness.checks(spec, run, [{"served": g["control"]}
                                      for g in gap_list])


def _limits(spec, args):
    from bench import harness
    harness.chips(spec.chips)
    pcfg = harness.program_config(spec.cfg)
    for seed in args.seeds:
        t0 = time.perf_counter()
        with harness.CompileLog() as clog:
            params = harness.make_weights(spec.cfg, pcfg, seed)
            backend = harness.build_backend(pcfg, params, spec)
            harness.warm(backend, spec)
            run = harness.serve(spec, backend, seed, args.seconds, False,
                                t0, clog)
        del backend
        gc.collect()
        reqs = harness.sample(run, seed)
        g = harness.gaps(spec, params, reqs, control=True)
        prog, ctrl = harness.checks(spec, run, g), control_checks(spec, run, g)
        out = {"seed": seed,
               "program_max_gap": prog["max_logit_gap"]["value"],
               "control_max_gap": ctrl["max_logit_gap"]["value"],
               "limit": prog["max_logit_gap"]["limit"],
               "program_correct": harness.passes(prog),
               "control_correct": harness.passes(ctrl),
               "tokens": prog["tokens_compared"]["value"],
               "requests": [int(r.rid) for r in reqs],
               "window_requests": len(run.window),
               "unfinished": prog["requests_unfinished"]["value"],
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
        del params
        gc.collect()


def _knee(spec, args):
    from bench import harness
    harness.chips(spec.chips)
    pcfg = harness.program_config(spec.cfg)
    params = harness.make_weights(spec.cfg, pcfg, args.seeds[0])
    backend = harness.build_backend(pcfg, params, spec)
    harness.warm(backend, spec)
    base = dict(spec.mix)
    for seed in args.seeds:
        for rate in args.rates:
            spec.mix = dict(base, rate_per_s=rate)
            with harness.CompileLog() as clog:
                run = harness.serve(spec, backend, seed, args.seconds, False,
                                    time.perf_counter(), clog, drain=False)
            backend.free_slots(range(backend.num_slots))
            q = [(t, n) for t, n in run.queued if run.in_window(t)]
            half = [np.mean([n for t, n in q if (t >= run.seconds / 2) == h]
                            or [np.nan]) for h in (False, True)]
            dec = [t1 - t0 for t0, t1, _, _ in run.decodes
                   if run.in_window(t0)]
            chk = [t1 - t0 for t0, t1, _, _ in run.chunks
                   if run.in_window(t0)]
            live = [len(r) for t0, _, r, _ in run.decodes
                    if run.in_window(t0)]
            out = {"seed": seed, "rate": rate,
                   "window_requests": len(run.window),
                   "queued_first_half": float(half[0]),
                   "queued_second_half": float(half[1]),
                   "queued_at_close": q[-1][1] if q else 0,
                   "decode_call_ms": 1e3 * float(np.mean(dec or [np.nan])),
                   "chunk_call_ms": 1e3 * float(np.mean(chk or [np.nan])),
                   "mean_live_slots": float(np.mean(live or [np.nan])),
                   "sent_late_max_s": max(run.late, default=0.0)}
            print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("limits", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    spec = harness.resolve(harness.load_benchmark(ROOT), args.workload)
    harness.use_compile_cache()
    (_limits if args.what == "limits" else _knee)(spec, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
