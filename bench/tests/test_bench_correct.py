"""``correct`` on the CPU at a tiny size: a whole run of the harness (past
its look for a chip) passes when the program is sound, and fails with each
fault the one-chip cell can have planted underneath the timed path: a
decode round that leaves the KV cache unchanged, half of the batch left
out, a token altered where it is produced.  The float8 control, judged by
the same checks at the committed limit, is not correct."""
import dataclasses
import time

import numpy as np
import pytest

from bench import harness, modules

# Eight layers: deep enough that the float8 control's rounding adds up
# past the cell's limit, as it does at the cell's own 24 layers.
CFG = {"name": "tiny", "model_id": "internlm2-1.8b", "arch": "dense_gqa",
       "reference": "dense_gqa", "dtype": "bfloat16",
       "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 8,
       "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32,
       "vocab_size": 1024, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "tie_word_embeddings": False}
MIX = {"arrivals": "poisson", "tokens": "uniform",
       "rate_per_s": 4, "ramp_s": 1, "tail_s": 5,
       "prompt": {"lengths": "lognormal", "median": 48, "sigma": 0.6,
                  "min": 32, "max": 96, "multiple": 32},
       "output": {"lengths": "lognormal", "median": 48, "sigma": 0.6,
                  "min": 16, "max": 128}}
SEED = 2 ** 33 + 17


def _spec():
    real = harness.resolve(harness.load_benchmark(), "internlm2-chat-rate")
    deploy = {"make_backend": {"kind": "gspmd", "paged": True,
                               "num_slots": 4, "max_len": 256,
                               "page_size": 16, "num_pages": 49},
              "scheduler": {"chunk_size": 64}}
    harness.check_deploy(deploy, "tiny")
    return harness.Spec(
        name="tiny", chips=1, cfg=CFG, deploy=deploy, mix=MIX,
        limits=real.limits, end_to_end=real.end_to_end,
        per_layer=real.per_layer)


def _tiny_program(monkeypatch):
    """The program's registry gives ``CFG``'s model at ``CFG``'s sizes: its
    own config with the fields the architecture module takes from the file
    replaced, so that the harness's check by that module runs as it is."""
    from repro import configs
    real = configs.get_config
    fields = modules.arch(CFG).program_fields(CFG)
    monkeypatch.setattr(configs, "get_config", lambda model_id: (
        dataclasses.replace(real(model_id), **fields)))


def _run(spec, monkeypatch, wrap=None, trace=False):
    """The harness's whole run, past its look for a chip (CPU devices, no
    peaks) and with the tiny config in the program's registry."""
    import jax
    monkeypatch.setattr(harness, "chips",
                        lambda n: (jax.devices()[:n], None))
    _tiny_program(monkeypatch)
    if wrap is not None:
        build = harness.build_backend
        monkeypatch.setattr(harness, "build_backend",
                            lambda *a: wrap(build(*a)))
    return harness.execute(spec, SEED, 2.0, trace, time.perf_counter(),
                           log=lambda m: None)


def _on_decode(fn):
    """Wrap the backend's jitted paged pass; ``fn`` rewrites decode calls."""
    def wrap(b):
        inner = b._paged_fn

        def paged(params, cache, tokens, pos, bt):
            if tokens.shape[1] != 1:
                return inner(params, cache, tokens, pos, bt)
            return fn(inner, params, cache, tokens, pos, bt)
        b._paged_fn = paged
        return b
    return wrap


@pytest.mark.parametrize("kind", [pytest.param("gspmd", id="gspmd-open")])
def test_sound_program_is_correct(kind, monkeypatch):
    spec = _spec()
    assert spec.backend_kw["kind"] == kind
    res = _run(spec, monkeypatch)
    c = res["checks"]
    assert res["correct"], c
    assert c["tokens_compared"]["value"] > 50
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for m in res["metrics"].values():
        assert np.isfinite(m["value"])


def test_traced_run(monkeypatch):
    """A traced run's window closes after ``TRACE_S``: it reports the
    per-layer metrics of that window, the trace's busy and window times
    and a breakdown, and ``correct`` and ``attempted`` cover that window."""
    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    spec = _spec()
    res = _run(spec, monkeypatch, trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == round(MIX["rate_per_s"] * 1.0)
    assert set(res["metrics"]) <= {m["name"] for m in spec.per_layer}
    assert "host_ms_per_iter" in res["metrics"]
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert abs(res["device"]["window_s"] - 1.0) < 0.25
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_cache_left_unchanged_fails(monkeypatch):
    import jax
    import jax.numpy as jnp

    def stale(inner, params, cache, tokens, pos, bt):
        keep = jax.tree.map(jnp.copy, cache)
        logits, _ = inner(params, cache, tokens, pos, bt)
        return logits, keep
    assert not _run(_spec(), monkeypatch, _on_decode(stale))["correct"]


def test_half_batch_left_out_fails(monkeypatch):
    def half(inner, params, cache, tokens, pos, bt):
        tokens, bt = np.array(tokens), np.array(bt)
        tokens[len(tokens) // 2:] = 0
        bt[len(bt) // 2:] = 0
        return inner(params, cache, tokens, pos, bt)
    assert not _run(_spec(), monkeypatch, _on_decode(half))["correct"]


def test_altered_token_fails(monkeypatch):
    def wrap(b):
        inner, calls = b.decode_step, [0]

        def decode(tokens, pos):
            out = inner(tokens, pos)
            calls[0] += 1
            return (out + 1) % CFG["vocab_size"] if calls[0] % 4 == 0 \
                else out
        b.decode_step = decode
        return b
    assert not _run(_spec(), monkeypatch, wrap)["correct"]


def test_control_reads_above_the_program(monkeypatch):
    """The float8 control, in the program's place, through the harness's
    own checks at the cell's committed limit: not correct, while the
    program on the same requests is."""
    from bench.calibrate import control_checks
    seen = {}
    gaps, checks = harness.gaps, harness.checks

    def both(spec, params, reqs, control=False):
        seen["gaps"] = gaps(spec, params, reqs, control=True)
        return seen["gaps"]

    def judged(spec, run, gap_list):
        if gap_list and "control" in gap_list[0]:    # not the control's own
            seen["control"] = control_checks(spec, run, gap_list)
        return checks(spec, run, gap_list)
    monkeypatch.setattr(harness, "gaps", both)
    monkeypatch.setattr(harness, "checks", judged)
    assert _run(_spec(), monkeypatch)["correct"]
    ctrl = seen["control"]
    assert not harness.passes(ctrl), ctrl
    assert ctrl["max_logit_gap"]["value"] > ctrl["max_logit_gap"]["limit"]
    program = max(float(g["served"].max()) for g in seen["gaps"])
    assert ctrl["max_logit_gap"]["value"] > 3 * program
