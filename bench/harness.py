"""One benchmark run: resolve a cell by name, build it, serve, measure, check.

Everything a cell needs is found by name under ``bench/``:

- ``BENCHMARK.json`` (the checkout's root) lists the cell's configuration,
  traffic mix and chips, and the metrics;
- ``configs/<config>.json``: the model's published sizes, the program's
  model id, its dtype, the reference it is checked against
  (``reference/<reference>.py``) and its architecture (``arch/<arch>.py``:
  the check of the program's config, the weight layout, and the FLOP and
  byte counts the metric readers take);
- ``deploy/<cell>.json``: the keyword arguments of the program's
  ``make_backend`` (``make_backend``) and ``Scheduler`` (``scheduler``),
  passed on as they are; a key that either does not take is an error;
- ``traffic/<mix>.json``: the mix that ``loadgen.py`` reads, naming its
  arrival and length generators (``traffic/<kind>.py``);
- ``limits/<cell>.json``: the limit of each number that ``correct``
  compares, with the readings it was set from;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``.

The window is timed on the served path: ``Scheduler.step()`` over
``make_backend(<kind>, paged=True)``, with the backend behind a thin probe
that stamps every decode round and prefill chunk on the host clock.
"""
from __future__ import annotations

import dataclasses
import gc
import inspect
import json
import os
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen, modules, trace_reduce, weights

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_S = 10.0               # a traced run's window: writing a trace out
#                              stalls the host for about as long again, so
#                              a traced run measures a shorter window
DRAIN_CAP_S = 120.0          # most seconds the window's requests may take
#                              to finish after it closes
SAMPLE = 5                   # requests compared with the reference, besides
#                              the longest


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


class RunFailed(RuntimeError):
    """The run broke a rule of measurement; it prints no result."""


# ---------------------------------------------------------------- the cell
def _json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Spec:
    """A cell with everything it names, read from its files."""

    name: str
    chips: int
    cfg: dict
    deploy: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def backend_kw(self) -> dict:
        return self.deploy["make_backend"]

    @property
    def sched_kw(self) -> dict:
        return self.deploy.get("scheduler", {})


def _takes(fn, kw: dict, given: tuple, where: str) -> None:
    """``kw`` may hold only keyword arguments that ``fn`` takes."""
    params = set(inspect.signature(fn).parameters) - set(given)
    bad = sorted(set(kw) - params)
    if bad:
        raise KeyError(f"{where}: {fn.__qualname__} takes no {bad}; it "
                       f"takes {sorted(params)}")


def check_deploy(deploy: dict, where: str) -> None:
    """The deploy file's keys against the program's signatures, and the
    timed path it must describe (paged, with chunked prefill)."""
    from repro.runtime.backends import make_backend
    from repro.runtime.scheduler import Scheduler
    bad = sorted(set(deploy) - {"make_backend", "scheduler", "why"})
    if bad:
        raise KeyError(f"{where}: unknown keys {bad}")
    _takes(make_backend, deploy["make_backend"], ("cfg", "params"), where)
    _takes(Scheduler, deploy.get("scheduler", {}), ("self", "backend",
                                                    "clock"), where)
    if deploy["make_backend"].get("paged") is not True \
            or not deploy.get("scheduler", {}).get("chunk_size"):
        raise KeyError(f"{where}: the timed path is the paged backend with "
                       f"chunked prefill (paged true, a chunk_size)")


def resolve(bench: dict, name: str) -> Spec:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    deploy = _json("deploy", name + ".json")
    check_deploy(deploy, f"bench/deploy/{name}.json")
    return Spec(
        name=name, chips=int(w["chips"]),
        cfg=_json("configs", w["config"] + ".json"),
        deploy=deploy,
        mix=loadgen.load_mix(os.path.join(BENCH_DIR, "traffic",
                                          w["traffic"] + ".json")),
        limits=_json("limits", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


# ---------------------------------------------------------------- the chip
def chips(n: int):
    """The first ``n`` TPU devices and their row of ``peaks.json``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    peaks = _json("peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:n], peaks[kind]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``.jax_cache`` in the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileLog:
    """Counts XLA compilations (persistent-cache loads included) from the
    duration event JAX records around each, and real compiles apart."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count, self.hits, self.seconds = 0, 0, 0.0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._hit)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _hit(self, event, **_):
        if event == self.HIT:
            self.hits += 1


# ---------------------------------------------------------------- building
def program_config(cfg: dict):
    """The program's config for this file, checked by the file's
    architecture module (``bench/arch/<arch>.py``)."""
    from repro.configs import get_config
    pc = get_config(cfg["model_id"])
    try:
        modules.arch(cfg).check_program(cfg, pc)
    except ValueError as e:
        raise RunFailed(f"the program's {cfg['model_id']} is not the "
                        f"configuration file's, by bench/arch/"
                        f"{cfg['arch']}.py: {e}") from e
    return pc


def make_weights(cfg: dict, pcfg, seed: int):
    import jax
    from repro.models.transformer import get_model
    want = jax.eval_shape(get_model(pcfg).init, jax.random.PRNGKey(0))
    params = weights.make_params(cfg, seed)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise RunFailed(f"weights layout differs from the program's: "
                        f"{got} != {exp}")
    return jax.block_until_ready(params)


def build_backend(pcfg, params, spec: Spec):
    from repro.runtime.backends import make_backend
    return make_backend(cfg=pcfg, params=params, **spec.backend_kw)


class Clock:
    """Host clock, seconds from the window's opening (negative before)."""

    def __init__(self, t_open: float):
        self.t_open = t_open

    def now(self) -> float:
        return time.perf_counter() - self.t_open

    def wait_until(self, t: float) -> None:
        delta = t - self.now()
        if delta > 0:
            time.sleep(delta)


def _span(name: str, on: bool):
    if not on:
        import contextlib
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Probe:
    """Stands in front of the backend: every decode round and prefill
    chunk passes through it and is stamped on the run's clock (and, in a
    traced run, wrapped in a host span)."""

    def __init__(self, inner, clock: Clock, spans: bool):
        self._inner = inner
        self._clock = clock
        self._spans = spans
        self.sched = None
        self.decodes: List[tuple] = []   # (t0, t1, rids, positions)
        self.chunks: List[tuple] = []    # (t0, t1, tokens, start)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode_step(self, tokens, pos):
        live = sorted(self.sched.active.items())
        t0 = self._clock.now()
        with _span("bench.decode", self._spans):
            out = self._inner.decode_step(tokens, pos)
        t1 = self._clock.now()
        self.decodes.append((t0, t1, [st.req.rid for _, st in live],
                             [int(pos[slot]) for slot, _ in live]))
        return out

    def prefill_chunk(self, slot, tokens, start):
        t0 = self._clock.now()
        with _span("bench.chunk", self._spans):
            out = self._inner.prefill_chunk(slot, tokens, start)
        self.chunks.append((t0, self._clock.now(), len(tokens), int(start)))
        return out


def warm(backend, spec: Spec) -> None:
    """Compile every shape the cell's traffic uses, and no other: the
    decode round over all slots, and each last-chunk length a prompt of
    the mix can leave (prompts are multiples of the mix's ``multiple``)."""
    mix = spec.mix
    chunk = int(spec.sched_kw["chunk_size"])
    m = int(mix["prompt"].get("multiple", 1))
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    tails = sorted({(n - 1) % chunk + 1 for n in range(lo, hi + 1)
                    if n % m == 0})
    for n in tails:
        backend.begin_prefill(0, n)
        backend.prefill_chunk(0, np.full(n, 2, np.int32), 0)
        backend.free_slots([0])
    slots = backend.num_slots
    backend.begin_prefill(0, 1)
    backend.finish_prefill(0)
    backend.decode_step(np.full(slots, 2, np.int32), np.ones(slots, np.int64))
    backend.free_slots([0])


# ---------------------------------------------------------------- a run
@dataclasses.dataclass
class ReqRec:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float               # due time
    in_window: bool
    admitted: float = float("nan")
    first_token: float = float("nan")
    finished: float = float("nan")
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    reason: str = ""

    @property
    def ok(self) -> bool:
        return self.reason == "length" \
            and len(self.tokens) == self.max_new_tokens \
            and len(self.times) == len(self.tokens)


@dataclasses.dataclass
class Run:
    """What a run saw; the metric readers take their numbers from it."""

    spec: Spec
    seconds: float
    setup_s: float
    requests: Dict[int, ReqRec]
    decodes: List[tuple]
    chunks: List[tuple]
    iters: List[tuple]           # (t0, t1, seconds in backend calls)
    queued: List[tuple]          # (t, requests waiting) at each iteration
    peak: Optional[dict]
    trace: Optional[trace_reduce.Reduced] = None
    trace_offset_ns: float = 0.0     # trace time of the window's opening
    late: List[float] = dataclasses.field(default_factory=list)

    @property
    def window(self) -> List[ReqRec]:
        return [r for r in self.requests.values() if r.in_window]

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.seconds

    def to_trace(self, t: np.ndarray) -> np.ndarray:
        return self.trace_offset_ns + np.asarray(t, np.float64) * 1e9

    @property
    def t(self) -> int:
        return int(self.spec.backend_kw.get("t", 1))


def serve(spec: Spec, backend, seed: int, seconds: float, trace: bool,
          t_process: float, clog: CompileLog, drain: bool = True) -> Run:
    """Ramp, then the window, then (with ``drain``) serve until every
    request that arrived in the window has finished."""
    from repro.runtime.request import Request
    from repro.runtime.scheduler import Scheduler
    import jax

    ramp = float(spec.mix.get("ramp_s", 0.0))
    clock = Clock(time.perf_counter() + ramp)
    probe = Probe(backend, clock, spans=trace)
    sched = Scheduler(probe, clock=clock, **spec.sched_kw)
    probe.sched = sched
    recs: Dict[int, ReqRec] = {}
    vocab = int(spec.cfg["vocab_size"])
    due = loadgen.open_schedule(spec.mix, seed, seconds, vocab)[::-1]
    late: List[float] = []                # how late each was sent
    iters: List[tuple] = []
    queued: List[tuple] = []
    done: set = set()
    c_warm = clog.count
    opened = closed_at = None
    window_span = None
    while True:
        now = clock.now()
        if opened is None and now >= 0.0:
            opened = True
            if trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # the benchmark's spans only
                opts.host_tracer_level = 1
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
                window_span = jax.profiler.TraceAnnotation("bench.window")
                window_span.__enter__()
        if opened and closed_at is None and now >= seconds:
            closed_at = now
            if window_span is not None:
                window_span.__exit__(None, None, None)
                window_span = None
                jax.profiler.stop_trace()
            if clog.count != c_warm:
                raise RunFailed(f"{clog.count - c_warm} compile(s) after "
                                f"warm-up, inside the ramp or the window")
            if not drain:
                break
        if closed_at is not None:
            if all(r.rid in done for r in recs.values() if r.in_window) \
                    or now > seconds + DRAIN_CAP_S:
                break
        while due and due[-1].due <= now:
            it = due.pop()
            late.append(now - it.due)
            recs[it.rid] = ReqRec(it.rid, it.prompt, it.max_new_tokens,
                                  it.due, 0.0 <= it.due < seconds)
            sched.submit(Request(rid=it.rid, prompt=it.prompt,
                                 max_new_tokens=it.max_new_tokens,
                                 arrival=it.due))
        if due and not (sched.queue or sched.active or sched.prefilling):
            # idle until the next arrival, or the window's next boundary
            marks = [due[-1].due] + [m for m in (0.0, seconds) if m > now]
            clock.wait_until(min(marks))
            continue
        queued.append((now, len(sched.queue)))
        n_log = len(sched.step_log)
        t0 = clock.now()
        with _span("bench.step", trace):
            more = sched.step()
        t1 = clock.now()
        if len(sched.step_log) > n_log:
            iters.append((t0, t1, sum(r.wall_s
                                      for r in sched.step_log[n_log:])))
        done.update(m.rid for m in sched.finished[len(done):])
        if not more and not due:
            break

    for m in sched.finished:
        r = recs[m.rid]
        r.admitted, r.first_token, r.finished = m.admitted, m.first_token, \
            m.finished
        r.tokens, r.reason = list(m.tokens), m.finish_reason
    times: Dict[int, List[float]] = {
        r.rid: [r.first_token] for r in recs.values()
        if not np.isnan(r.first_token)}
    for _, t1, rids, _ in probe.decodes:
        for rid in rids:
            if rid in times:
                times[rid].append(t1)
    for rid, ts in times.items():
        recs[rid].times = ts
    return Run(spec=spec, seconds=float(seconds),
               setup_s=clock.t_open - t_process, requests=recs,
               decodes=probe.decodes, chunks=probe.chunks, iters=iters,
               queued=queued, peak=None, late=late)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------- correct
def sample(run: Run, seed: int) -> List[ReqRec]:
    """The requests compared with the reference: ``SAMPLE`` of the
    window's finished requests drawn from the seed, and the longest."""
    done = sorted((r for r in run.window if r.ok), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 3])
    pick = rng.choice(len(rest), min(SAMPLE, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(spec: Spec, params, reqs: List[ReqRec], control: bool = False):
    """Per compared request: how far below the float32 reference's best
    logit each served token's logit lies (and, with ``control``, the gap of
    the float8 control's first choice at the same positions)."""
    ref = modules.load("reference", spec.cfg["reference"])
    S = int(spec.backend_kw["max_len"])
    out = []
    for r in reqs:
        P, n = len(r.prompt), len(r.tokens)
        seq = np.zeros(S, np.int32)
        seq[:P] = r.prompt
        seq[P:P + n - 1] = r.tokens[:-1]
        tgt = np.zeros(S, np.int32)
        tgt[P - 1:P + n - 1] = r.tokens
        res = ref.score(spec.cfg, params, seq, tgt, control=control)
        sl = slice(P - 1, P + n - 1)
        g = {"served": res["best"][sl] - res["served"][sl]}
        if control:
            g["control"] = res["best"][sl] - res["control"][sl]
        out.append(g)
    return out


def checks(spec: Spec, run: Run, gap_list) -> Dict[str, dict]:
    """Each number ``correct`` compares, beside its limit."""
    widest = max((float(g["served"].max()) for g in gap_list),
                 default=float("inf"))
    failed = sum(1 for r in run.window if not r.ok)
    return {
        "max_logit_gap": {"value": widest,
                          "limit": float(spec.limits["max_logit_gap"]
                                         ["limit"])},
        "tokens_compared": {"value": int(sum(len(g["served"])
                                             for g in gap_list)),
                            "limit": 1},
        "requests_unfinished": {"value": failed, "limit": 0},
    }


def passes(c: Dict[str, dict]) -> bool:
    return (c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
            and c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
            and c["requests_unfinished"]["value"]
            <= c["requests_unfinished"]["limit"])


# ---------------------------------------------------------------- metrics
def read_metrics(run: Run, entries: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in entries:
        v = modules.load("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def reduce_trace(run: Run, devices) -> None:
    path = trace_reduce.find_xplane(TRACE_DIR)
    if path is None:
        raise RunFailed("the traced run wrote no trace")
    ids = [d.id for d in devices] if devices[0].platform == "tpu" else None
    red = trace_reduce.reduce(path, ids)
    run.trace = red
    run.trace_offset_ns = red.w0
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def execute(spec: Spec, seed: int, seconds: float, trace: bool,
            t_process: float, log) -> dict:
    """A whole run; returns the result line's object.  ``log`` takes the
    run's report lines (standard error, in ``run.py``).  A traced run's
    window closes after ``TRACE_S`` seconds."""
    import jax
    if trace:
        seconds = min(seconds, TRACE_S)
    devices, peaks = chips(spec.chips)
    pcfg = program_config(spec.cfg)
    with CompileLog() as clog:
        t0 = time.perf_counter()
        params = make_weights(spec.cfg, pcfg, seed)
        t1 = time.perf_counter()
        backend = build_backend(pcfg, params, spec)
        warm(backend, spec)
        t2 = time.perf_counter()
        log(f"set-up: {t0 - t_process:.3f} s to the weights, weights "
            f"{t1 - t0:.3f} s, backend and warm-up {t2 - t1:.3f} s; "
            f"compiles {clog.count} (persistent-cache hits {clog.hits}) in "
            f"{clog.seconds:.3f} s")
        run = serve(spec, backend, seed, seconds, trace, t_process, clog)
    run.peak = peaks
    mem = memory_peak(devices)
    w = run.window
    live = [len(r) for t0, _, r, _ in run.decodes if run.in_window(t0)]
    log(f"window {seconds} s: {len(w)} requests arrived, "
        f"{sum(r.ok for r in w)} finished whole; "
        f"{len(live)} decode rounds (mean live slots "
        f"{np.mean(live) if live else 0:.2f}), "
        f"{sum(1 for c in run.chunks if run.in_window(c[0]))} chunks; "
        f"{len(run.requests)} requests sent in all; "
        f"memory_peak_bytes {mem}")
    ttft = [r.first_token - r.arrival for r in w
            if not np.isnan(r.first_token)]
    itl = np.concatenate([np.diff(r.times) for r in w if len(r.times) > 1]
                         or [np.zeros(0)])
    pct = lambda v, qs: " ".join(
        f"p{q} {1e3 * np.percentile(v, q):.2f}" for q in qs) if len(v) \
        else "none"
    log(f"ttft_ms {pct(ttft, (50, 75, 90, 95))}; itl_ms "
        f"{pct(itl, (50, 90, 95, 99))} ({len(itl)} gaps); sent late by "
        f"max {1e3 * max(run.late, default=0.0):.2f} ms")
    del backend                 # the KV pages: the reference runs alone
    gc.collect()
    c = checks(spec, run, gaps(spec, params, sample(run, seed)))
    attempted = len(run.window)
    if trace:
        reduce_trace(run, devices)
        metrics = read_metrics(run, spec.per_layer)
    else:
        metrics = read_metrics(run, spec.end_to_end)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    res = {"correct": passes(c), "attempted": attempted,
           "failed": c["requests_unfinished"]["value"],
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        res["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    res["checks"] = c
    del params
    jax.clear_caches()
    return res
