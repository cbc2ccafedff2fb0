"""Run the paged serving path on a TPU at InternLM2-1.8B's published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one v5e:2x2 host

One chip: random bf16 weights from ``--seed`` at the published widths and
full depth (24 layers, d_model 2048), ``make_backend("gspmd", paged=True)``
with 8 slots of up to 2048 positions (1025 pages of 16 tokens), and
``Scheduler(chunk_size=256)`` serving a closed batch of 12 requests (prompts
of 256-1024 tokens, 32 new tokens each) after one warm-up pass.  It checks
that every request finishes with its token count, that the page pool ends
empty, and that the paged path's prefill logits of two prompts agree with a
float32 ``jax.numpy`` reference forward (``repro.models.reference``).

``--four-chips`` serves the same requests, one layout after another in this
one process, with the one-chip GSPMD backend on device 0 (the baseline) and
then ``tp`` t=4, ``pp`` p=4 and ``pp`` t=2 p=2, each freed before the next.
Each layout's first tokens and prefill logits are checked against the
baseline, and its per-device memory is printed.

What it prints are facts of this run (set-up and compile times, compile
counts, requests and tokens, logit errors, device memory), not benchmark
metrics.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every check passed.  Without a TPU, or when any check
fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Relative L2 error of the bf16 paged path's last-position logits against
# the float32 reference, over the whole vocabulary.  bf16 keeps 8
# significant bits (unit roundoff 2^-9, an RMS rounding error of
# 2^-9/sqrt(3) ~ 0.11% per rounding).  The path rounds the residual stream
# twice per layer and every matmul output once; 2L = 48 independent
# roundings of the stream add up to ~sqrt(48) * 0.11% ~ 0.8% on the final
# hidden state, which the logits inherit.  The bound allows ~6x that.  One
# fp8-e4m3 rounding (unit roundoff 2^-4, ~3.6% RMS) of each activation
# would exceed it within two layers.
LOGIT_RTOL = 0.05
# A layout's first token may differ from the baseline's only on a near-tie:
# when the baseline's own logits rank the two tokens closer than this many
# RMS differences between the two layouts' logits of that prompt.
TIE_SIGMAS = 4.0


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one smoke run serves (defaults: the chip run)."""

    slots: int = 8
    max_len: int = 2048
    page_size: int = 16
    chunk: int = 256
    prompt_lens: tuple = (256, 512, 768, 1024)   # multiples of ``chunk``:
    #                                             one compiled chunk shape
    n_requests: int = 12
    new_tokens: int = 32


class CompileLog:
    """Counts XLA compilations and sums their time, from the duration event
    JAX records around every backend compile (persistent-cache hits
    included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def make_requests(cfg, plan: Plan, seed: int):
    import numpy as np
    from repro.runtime.request import Request
    rng = np.random.default_rng(seed)
    lens = [plan.prompt_lens[i % len(plan.prompt_lens)]
            for i in range(plan.n_requests)]
    rng.shuffle(lens)
    return [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, n),
                    max_new_tokens=plan.new_tokens)
            for i, n in enumerate(lens)]


def serve(name, backend, requests, plan: Plan, clog: CompileLog, log):
    """Warm-up pass (one short request per prompt length, as
    ``examples/serve_demo.py`` does), then the served pass; checks every
    request's token count and that the page pool drained."""
    import numpy as np
    from repro.runtime.request import Request
    from repro.runtime.scheduler import Scheduler
    rng = np.random.default_rng(1)
    warm = [Request(rid=10_000 + j, prompt=rng.integers(2, 100, n),
                    max_new_tokens=2)
            for j, n in enumerate(sorted({r.prompt_len for r in requests}))]
    t0, c0 = time.perf_counter(), clog.count
    Scheduler(backend, chunk_size=plan.chunk).run(warm)
    log(f"{name} warmup_wall_s {time.perf_counter() - t0:.3f} "
        f"(compiles in warm-up: {clog.count - c0})")
    t0, c0 = time.perf_counter(), clog.count
    report = Scheduler(backend, chunk_size=plan.chunk).run(requests)
    log(f"{name} served_pass_wall_s {time.perf_counter() - t0:.3f} "
        f"(compiles in served pass: {clog.count - c0})")
    by_rid = {m.rid: m for m in report.metrics}
    for r in requests:
        m = by_rid.get(r.rid)
        if m is None or m.finish_reason != "length" \
                or m.num_generated != r.max_new_tokens:
            raise SystemExit(f"request {r.rid} did not finish with "
                             f"{r.max_new_tokens} tokens: {m}")
    pool = backend.pool
    if pool.owners() or pool.free_pages != pool.num_pages - 1:
        raise SystemExit(f"page pool leaked: owners {pool.owners()}, "
                         f"{pool.free_pages}/{pool.num_pages - 1} free")
    log(f"{name} requests_served {len(requests)} tokens_generated "
        f"{report.total_tokens} pages_leaked 0 of {pool.num_pages}")
    return report.tokens_by_rid()


def prefill_logits(backend, prompt, plan: Plan, vocab: int):
    """float32 [vocab] logits of the prompt's last position, through the
    backend's chunked paged prefill (the pass the scheduler runs)."""
    import numpy as np
    backend.begin_prefill(0, len(prompt))
    for start in range(0, len(prompt), plan.chunk):
        logits = backend.prefill_chunk_logits(
            0, prompt[start:start + plan.chunk], start)
    backend.free_slots([0])
    return np.asarray(logits, np.float32)[:vocab]


def rel_err(a, ref) -> float:
    import numpy as np
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def reference_requests(requests):
    """The two requests checked against the reference: the shortest prompt
    and the longest (the most prefill chunks)."""
    by_len = sorted(requests, key=lambda r: (r.prompt_len, r.rid))
    return [by_len[0], by_len[-1]]


def check_reference(cfg, params, backend, requests, plan, log, name):
    """Prefill logits of the ``reference_requests`` against the float32
    reference; returns {rid: reference logits}."""
    from repro.models.reference import reference_last_logits
    refs = {}
    for r in reference_requests(requests):
        ref = reference_last_logits(cfg, params, r.prompt)
        err = rel_err(prefill_logits(backend, r.prompt, plan,
                                     cfg.vocab_size), ref)
        log(f"{name} rid {r.rid} (prompt {r.prompt_len}): logit rel L2 "
            f"error vs float32 reference {err:.6f} (limit {LOGIT_RTOL})")
        if not err <= LOGIT_RTOL:
            raise SystemExit(f"{name}: logit error {err} > {LOGIT_RTOL}")
        refs[r.rid] = ref
    return refs


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id}: in_use {st.get('bytes_in_use', 'n/a')} "
                     f"peak {st.get('peak_bytes_in_use', 'n/a')}")
    return "; ".join(parts)


def describe(cfg, params, log):
    import jax
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"config {cfg.name}: layers {cfg.num_layers} d_model {cfg.d_model} "
        f"heads {cfg.num_heads} kv_heads {cfg.num_kv_heads} head_dim "
        f"{cfg.head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size} dtype "
        f"{cfg.dtype} param_bytes {nbytes}")


def baseline(cfg, plan: Plan, seed: int, clog: CompileLog, log):
    """The one-chip GSPMD run on the default device.  Returns (params,
    backend, requests, served tokens by rid)."""
    import jax
    from repro.models.transformer import get_model
    from repro.runtime.backends import make_backend
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(get_model(cfg).init)(jax.random.PRNGKey(seed)))
    backend = make_backend("gspmd", cfg, params, num_slots=plan.slots,
                           max_len=plan.max_len, paged=True,
                           page_size=plan.page_size)
    log(f"gspmd setup_s {time.perf_counter() - t0:.3f} (random init + "
        f"backend; {backend.pool.num_pages} pages of {plan.page_size})")
    describe(cfg, params, log)
    requests = make_requests(cfg, plan, seed)
    tokens = serve("gspmd", backend, requests, plan, clog, log)
    return params, backend, requests, tokens


def one_chip(cfg, plan: Plan, seed: int, clog: CompileLog, log) -> None:
    params, backend, requests, tokens = baseline(cfg, plan, seed, clog, log)
    check_reference(cfg, params, backend, requests, plan, log, "gspmd")
    for r in reference_requests(requests):
        first = int(prefill_logits(backend, r.prompt, plan,
                                   cfg.vocab_size).argmax())
        if first != tokens[r.rid][0]:
            raise SystemExit(f"rid {r.rid}: served first token "
                             f"{tokens[r.rid][0]} != prefill argmax {first}")


LAYOUTS = (("tp4", "tp", 4, 1), ("pp4", "pp", 1, 4), ("tp2pp2", "pp", 2, 2))


def four_chips(cfg, plan: Plan, seed: int, clog: CompileLog, log) -> None:
    import jax
    import numpy as np
    from repro.runtime.backends import make_backend
    params, backend, requests, base_tok = baseline(cfg, plan, seed, clog,
                                                   log)
    refs = check_reference(cfg, params, backend, requests, plan, log,
                           "gspmd")
    base_logits = {r.rid: prefill_logits(backend, r.prompt, plan,
                                         cfg.vocab_size) for r in requests}
    host = jax.device_get(params)
    del params, backend
    gc.collect()
    devices = jax.devices()[:4]
    log(f"gspmd memory: {memory_line(devices)}")
    for name, kind, t, p in LAYOUTS:
        t0 = time.perf_counter()
        backend = make_backend(kind, cfg, host, num_slots=plan.slots,
                               max_len=plan.max_len, t=t, p=p, paged=True,
                               page_size=plan.page_size)
        log(f"{name} setup_s {time.perf_counter() - t0:.3f}")
        tokens = serve(name, backend, requests, plan, clog, log)
        exact = ties = agree = 0
        worst = 0.0
        for r in requests:
            logits = prefill_logits(backend, r.prompt, plan, cfg.vocab_size)
            got, want = tokens[r.rid][0], base_tok[r.rid][0]
            if got != int(logits.argmax()):
                raise SystemExit(f"{name} rid {r.rid}: served first token "
                                 f"{got} != its prefill argmax")
            base = base_logits[r.rid]
            # both within LOGIT_RTOL of the reference: within twice that
            # of each other
            drift = rel_err(logits, base)
            worst = max(worst, drift)
            if not drift <= 2 * LOGIT_RTOL:
                raise SystemExit(f"{name} rid {r.rid}: logit rel L2 "
                                 f"difference from the baseline {drift}")
            if got == want:
                exact += 1
            else:
                margin = float(base[want] - base[got])
                noise = float(np.sqrt(np.mean((logits - base) ** 2)))
                if not margin <= TIE_SIGMAS * noise:
                    raise SystemExit(
                        f"{name} rid {r.rid}: first token {got} != {want} "
                        f"with baseline margin {margin:.4f} > "
                        f"{TIE_SIGMAS} x logit RMS difference {noise:.4f}")
                ties += 1
                log(f"{name} rid {r.rid}: first token {got} != {want}, a "
                    f"near-tie (baseline margin {margin:.4f}, logit RMS "
                    f"difference {noise:.4f})")
            if r.rid in refs:
                err = rel_err(logits, refs[r.rid])
                log(f"{name} rid {r.rid}: logit rel L2 error vs float32 "
                    f"reference {err:.6f} (limit {LOGIT_RTOL})")
                if not err <= LOGIT_RTOL:
                    raise SystemExit(f"{name}: logit error {err}")
            agree += next((i for i, (a, b) in enumerate(
                zip(tokens[r.rid], base_tok[r.rid])) if a != b),
                len(tokens[r.rid]))
        total = sum(len(v) for v in tokens.values())
        log(f"{name} first_tokens_equal {exact}/{len(requests)} "
            f"near_ties {ties}; greedy tokens agreeing before the first "
            f"divergence {agree}/{total}; largest logit rel L2 difference "
            f"from the baseline {worst:.6f} (limit {2 * LOGIT_RTOL})")
        log(f"{name} memory: {memory_line(devices)}")
        del backend
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="tp4, pp4 and tp2xpp2 against one chip (v5e:2x2)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke needs {need} TPU device(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    from repro.configs import get_config

    d = devices[0]
    print(f"platform {d.platform} device_kind {d.device_kind} "
          f"device_count {len(devices)} compile_cache {cache}", flush=True)
    log = lambda msg: print(msg, flush=True)
    cfg = get_config("internlm2-1.8b")
    with CompileLog() as clog:
        (four_chips if args.four_chips else one_chip)(cfg, Plan(), args.seed,
                                                      clog, log)
    log(f"compiles {clog.count} compile_s {clog.seconds:.3f}")
    log(f"peak_bytes_in_use {memory_line(devices[:need])}")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
