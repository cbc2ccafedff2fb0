"""Decode hot-path benchmark: TP (unrolled/scanned/fused) vs PP vs TP×PP.

A CPU tool: its child process runs on ``JAX_PLATFORMS=cpu`` with four
forced host devices, so its times are CPU wall times, never device
metrics.  It stays that until a benchmark measured on the chip replaces
it; ``chip_smoke.py`` is what runs on the chip today.

Times seven decode strategies on a 4-device host-platform mesh (reduced
configs, CPU-sized):

  unrolled   seed behaviour — one jit dispatch per token, Python-unrolled
             layer loop, cache re-stacked every step (paper-parity mode)
  scanned    one dispatch per token, lax.scan layers + donated cache
  fused      ``tp_generate`` — N tokens per dispatch (lax.fori_loop)
  pp4        PipelineEngine t=1 p=4 ``generate`` — per-stage caches, one
             dispatch per stage per token + 2 boundary transfers per hop
  tp2pp2     hybrid t=2 p=2 ``generate`` — per-stage TP collectives plus
             boundary shards (the paper's TP-vs-PP decode tradeoff, Fig. 9)
  fused-q8   ``tp_generate`` with int8 two-step collectives (DESIGN.md §12):
             every per-layer decode psum runs quantize → reduce-scatter →
             all-gather → dequant on the wire
  tp2pp2-q8  the hybrid engine with the same quantized decode collectives
             inside each stage's TP group
  fused-q4 / tp2pp2-q4
             the same two shapes with the nibble-packed int4 wire — half
             the int8 payload again, the aggressive end of the
             accuracy/bandwidth tradeoff

Each quant record carries an accuracy contract next to the timing:
``token_match_rate`` and ``max_logit_drift`` are measured teacher-forced —
the quantized path replays the bf16 greedy token stream, so every step sees
identical *inputs* and the drift is the quantization's alone (compounded
through the KV cache, which is the honest part), while ``token_match_rate``
is the fraction of (step, sequence) argmax choices that agree with the bf16
pick.  ``benchmarks/check_baselines.py`` gates both against
``kernels.quant_collective.QUANT_TOLERANCE`` and pins the deterministic
``predicted_decode_wire_ratio`` against a per-quant ceiling (closed form;
int8 must stay < 0.6 of the bf16 all-reduce wire, packed int4 < 0.35).

Emits ``BENCH_decode.json`` at the repo root (tokens/sec and ms/token per
arch × variant) so the perf trajectory is tracked across PRs.  Every record
also carries the *predicted* per-step decode collective counts from
``commodel`` — deterministic fields the CI bench-regression gate
(`benchmarks/check_baselines.py`) diffs against the checked-in baseline.
Runs in a subprocess so the device-count flag stays contained.  ``--dry-run``
times a single reduced arch with a short generation and writes
``results/BENCH_decode.dryrun.json`` (the CI artifact) instead of the
full series.
"""
import json
import os
import subprocess
import sys
import time

MODELS = ["llama32-3b", "llama31-8b", "internlm2-1.8b"]
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OUT_PATH = os.path.join(REPO, "BENCH_decode.json")
DRY_PATH = os.path.join(REPO, "results", "BENCH_decode.dryrun.json")

N_TOKENS = 32
BATCH = 4
PREFILL = 16
REPEAT = 3


def _measure(dry_run: bool = False):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import parallel_exec as px
    from repro.models.transformer import get_model

    models = MODELS[:1] if dry_run else MODELS
    n_tokens = 4 if dry_run else N_TOKENS
    repeat = 1 if dry_run else REPEAT
    cache_w = PREFILL + n_tokens

    def time_loop(step_fn, params, cache, tok, pos):
        """Per-token dispatch loop; returns (seconds, final cache)."""
        t0 = time.perf_counter()
        for i in range(n_tokens):
            logits, cache = step_fn(params, cache, tok, jnp.int32(pos + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        tok.block_until_ready()
        return time.perf_counter() - t0, cache

    results = []
    for arch in models:
        cfg = get_config(arch).reduced(num_layers=4)
        mesh = px.make_tp_mesh(4)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PREFILL), 2,
                                  cfg.vocab_size)
        prefill = px.tp_prefill(cfg, mesh, cache_w=cache_w, unroll=True)
        logits, cache0 = prefill(params, toks)
        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = PREFILL

        variants = {}
        step_u = px.tp_decode_step(cfg, mesh, unroll=True)
        step_s = px.tp_decode_step(cfg, mesh, unroll=False)
        gen = px.tp_generate(cfg, mesh, n_tokens)

        def fresh():
            return jax.tree.map(jnp.copy, cache0)

        # warmup (compile) once per variant, then best-of-repeat
        time_loop(step_u, params, fresh(), tok0, pos)
        variants["unrolled"] = min(
            time_loop(step_u, params, fresh(), tok0, pos)[0]
            for _ in range(repeat))
        time_loop(step_s, params, fresh(), tok0, pos)
        variants["scanned"] = min(
            time_loop(step_s, params, fresh(), tok0, pos)[0]
            for _ in range(repeat))
        gen(params, fresh(), tok0, jnp.int32(pos))[0].block_until_ready()

        def fused_once():
            c = fresh()
            t0 = time.perf_counter()
            out, _ = gen(params, c, tok0, jnp.int32(pos))
            out.block_until_ready()
            return time.perf_counter() - t0
        variants["fused"] = min(fused_once() for _ in range(repeat))

        # pipelined decode: per-stage caches + fused per-stage decode steps
        pp_engines = {}
        layouts = {"pp4": (1, 4), "tp2pp2": (2, 2)}
        for name, (t, p) in layouts.items():
            eng = px.PipelineEngine(cfg, t=t, p=p, unroll=False)
            staged = eng.prepare(params)
            _, caches0 = eng.prefill_with_cache(staged, toks, cache_w)
            pp_engines[name] = (eng, staged, caches0)

            def pp_once(eng=eng, staged=staged, caches0=caches0):
                # generate donates the caches; run each repeat on copies
                caches = [jax.tree.map(jnp.copy, c) for c in caches0]
                t0 = time.perf_counter()
                out, _ = eng.generate(staged, caches, tok0, pos, n_tokens)
                out.block_until_ready()
                return time.perf_counter() - t0

            pp_once()                                  # warmup / compile
            variants[name] = min(pp_once() for _ in range(repeat))

        # accuracy: teacher-forced per-step logits vs the bf16 reference
        def record_tp(step_fn, forced=None):
            cache, tok = fresh(), tok0
            logits_all, toks_all = [], []
            for i in range(n_tokens):
                logits, cache = step_fn(params, cache, tok,
                                        jnp.int32(pos + i))
                choice = jnp.argmax(logits, -1).astype(jnp.int32)
                logits_all.append(logits)
                toks_all.append(choice)
                tok = choice if forced is None else forced[i]
            return jnp.stack(logits_all), jnp.stack(toks_all)

        def record_pp(eng_, staged_, caches_, forced=None):
            caches = [jax.tree.map(jnp.copy, c) for c in caches_]
            tok = tok0
            logits_all, toks_all = [], []
            for i in range(n_tokens):
                logits, caches = eng_.decode_once(staged_, caches, tok,
                                                  pos + i)
                choice = jnp.argmax(logits, -1).astype(jnp.int32)
                logits_all.append(logits)
                toks_all.append(choice)
                tok = choice if forced is None else forced[i]
            return jnp.stack(logits_all), jnp.stack(toks_all)

        def drift_metrics(ref, quant):
            """(token_match_rate, max_logit_drift) of a teacher-forced
            quant run against its bf16 reference."""
            (r_logits, r_toks), (q_logits, q_toks) = ref, quant
            match = float(jnp.mean((q_toks == r_toks).astype(jnp.float32)))
            drift = float(jnp.max(jnp.abs(q_logits - r_logits)))
            return round(match, 4), round(drift, 6)

        # ---- quant series (DESIGN.md §12): low-bit two-step collectives,
        # int8 and the packed int4 wire side by side ----
        ref_tp = record_tp(step_u)
        ref_pp = record_pp(*pp_engines["tp2pp2"])
        quant_metrics, variant_quant = {}, {}
        for quant, tag in (("int8", "q8"), ("int4", "q4")):
            gen_q = px.tp_generate(cfg, mesh, n_tokens,
                                   quant_collectives=quant)
            gen_q(params, fresh(), tok0,
                  jnp.int32(pos))[0].block_until_ready()

            def fused_q_once(gen_q=gen_q):
                c = fresh()
                t0 = time.perf_counter()
                out, _ = gen_q(params, c, tok0, jnp.int32(pos))
                out.block_until_ready()
                return time.perf_counter() - t0
            variants[f"fused-{tag}"] = min(
                fused_q_once() for _ in range(repeat))

            eng_q = px.PipelineEngine(cfg, t=2, p=2, unroll=False,
                                      quant_collectives=quant)
            staged_q = eng_q.prepare(params)
            _, qcaches0 = eng_q.prefill_with_cache(staged_q, toks, cache_w)

            def ppq_once(eng_q=eng_q, staged_q=staged_q, qcaches0=qcaches0):
                caches = [jax.tree.map(jnp.copy, c) for c in qcaches0]
                t0 = time.perf_counter()
                out, _ = eng_q.generate(staged_q, caches, tok0, pos,
                                        n_tokens)
                out.block_until_ready()
                return time.perf_counter() - t0

            ppq_once()                                 # warmup / compile
            variants[f"tp2pp2-{tag}"] = min(
                ppq_once() for _ in range(repeat))

            step_q = px.tp_decode_step(cfg, mesh, unroll=True,
                                       quant_collectives=quant)
            quant_metrics[f"fused-{tag}"] = drift_metrics(
                ref_tp, record_tp(step_q, forced=ref_tp[1]))
            quant_metrics[f"tp2pp2-{tag}"] = drift_metrics(
                ref_pp, record_pp(eng_q, staged_q, qcaches0,
                                  forced=ref_pp[1]))
            variant_quant[f"fused-{tag}"] = quant
            variant_quant[f"tp2pp2-{tag}"] = quant

        from repro.core import commodel as cm

        def decode_counts(t, p, quant=None):
            """Predicted per-step decode collective counts (drift-gate
            payload: deterministic, machine-independent)."""
            counts = {}
            for o in cm.comm_ops_for(cfg, 1, 2, t, p,
                                     gather_mode="allgather", quant=quant):
                if o.phase == "decode":
                    counts[o.collective] = counts.get(o.collective, 0) \
                        + o.count
            return counts

        parallelism = {"unrolled": (4, 1), "scanned": (4, 1), "fused": (4, 1),
                       "pp4": (1, 4), "tp2pp2": (2, 2),
                       "fused-q8": (4, 1), "tp2pp2-q8": (2, 2),
                       "fused-q4": (4, 1), "tp2pp2-q4": (2, 2)}
        for name, sec in variants.items():
            t, p = parallelism[name]
            quant = variant_quant.get(name)
            rec = {
                "arch": arch, "variant": name, "tp": t, "pp": p,
                "batch": BATCH, "n_tokens": n_tokens, "quant": quant,
                "tokens_per_s": n_tokens * BATCH / sec,
                "ms_per_token": sec / n_tokens * 1e3,
                "speedup_vs_unrolled": variants["unrolled"] / sec,
                "decode_collective_counts": decode_counts(t, p, quant),
            }
            if quant is not None:
                match, drift = quant_metrics[name]
                rec["token_match_rate"] = match
                rec["max_logit_drift"] = drift
                # closed form vs the bf16 (b=2) wire the two-step replaces;
                # t-invariant, pinned by the per-quant baseline ceiling
                rec["predicted_decode_wire_ratio"] = round(
                    cm.quant_ar_wire_ratio(cfg.d_model, t, quant=quant), 6)
            results.append(rec)
    print("DECODEJSON:" + json.dumps(results))


def _run_subprocess(dry_run: bool = False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    cmd = [sys.executable, "-m", "benchmarks.decode_bench", "--measure"]
    if dry_run:
        cmd.append("--dry-run")
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=REPO, timeout=1200)
    except subprocess.TimeoutExpired:
        return None, "timeout after 1200s"
    for line in r.stdout.splitlines():
        if line.startswith("DECODEJSON:"):
            return json.loads(line[len("DECODEJSON:"):]), None
    return None, r.stderr[-300:]


def rows(dry_run: bool = False):
    recs, err = _run_subprocess(dry_run)
    if recs is None:
        raise RuntimeError(f"decode_bench child failed: {err}")
    path = DRY_PATH if dry_run else OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(recs, f, indent=2, sort_keys=True)
    out = []
    for r in recs:
        note = (f"tok_per_s={r['tokens_per_s']:.1f};"
                f"ms_per_token={r['ms_per_token']:.2f};"
                f"speedup_vs_unrolled={r['speedup_vs_unrolled']:.2f}x")
        if r.get("quant"):
            note += (f";token_match={r['token_match_rate']:.4f};"
                     f"logit_drift={r['max_logit_drift']:.4f};"
                     f"wire_ratio={r['predicted_decode_wire_ratio']:.4f}")
        out.append((f"decode/{r['arch']}/t{r['tp']}p{r['pp']}/{r['variant']}",
                    r["ms_per_token"] * 1e3, note))
    return out


def main(dry_run: bool = False):
    mode = "dry-run smoke" if dry_run else f"fused×{N_TOKENS}"
    print(f"Decode paths — TP unrolled/scanned/fused vs PP vs TP×PP "
          f"({mode}, 4-device host mesh, B={BATCH})")
    for r in rows(dry_run):
        print(f"  {r[0]:46s} {r[2]}")
    if not dry_run and os.path.exists(OUT_PATH):
        print(f"  wrote {OUT_PATH}")


if __name__ == "__main__":
    if "--measure" in sys.argv:
        _measure(dry_run="--dry-run" in sys.argv)
    else:
        main(dry_run="--dry-run" in sys.argv)
