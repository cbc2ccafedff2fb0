"""Operations and bytes from shapes, and the table of peaks."""
import json
import os

import pytest

from bench import flops, harness

CONFIGS = {"internlm2-1.8b": 1_889_110_016}


def _cfg(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_count_ties_to_the_program(name):
    cfg = _cfg(name)
    assert flops.param_count(cfg) == CONFIGS[name]
    assert flops.param_count(cfg) == harness.program_config(cfg).param_count()


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(_cfg("internlm2-1.8b")) == 98_304


def test_pass_flops_and_least_bytes():
    cfg = _cfg("internlm2-1.8b")
    n = flops.param_count(cfg)
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    # one token at position 0: every weight but the embedding, once
    attn = 4 * 24 * 16 * 128
    assert flops.pass_flops(cfg, 1, 0) == pytest.approx(
        2 * (n - emb - (2 * 24 + 1) * 2048) + attn)
    # a chunk's attention grows with its start; the head counts once
    a, b = flops.pass_flops(cfg, 256, 0), flops.pass_flops(cfg, 256, 256)
    assert b - a == pytest.approx(4 * 24 * 16 * 128 * 256 * 256)
    one = flops.decode_least_bytes(cfg, [0])
    assert one == pytest.approx((n - emb) * 2 + 2048 * 2 + 98_304)
    two = flops.decode_least_bytes(cfg, [0, 1000])
    assert two - one == pytest.approx(2048 * 2 + 1001 * 98_304)
    per_chip = flops.decode_least_bytes(cfg, [0], t=4)
    assert per_chip < flops.decode_least_bytes(cfg, [0]) / 3


def test_peaks_table():
    with open(os.path.join(harness.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in peaks["source"]


def test_no_tpu_or_unknown_kind_is_an_error(monkeypatch):
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.chips(1)

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.NoChip, match="not in bench/peaks.json"):
        harness.chips(1)
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.chips(4)
    Dev.device_kind = "TPU v5 lite"
    devs, peak = harness.chips(1)
    assert peak["hbm_bytes_per_s"] == 819e9
