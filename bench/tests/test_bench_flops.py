"""Operations and bytes from shapes, through each configuration's
architecture module, and the table of peaks."""
import json
import os

import pytest

from bench import harness, modules

CONFIGS = {"internlm2-1.8b": 1_889_110_016}


def _cfg(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _arch(name):
    cfg = _cfg(name)
    return cfg, modules.arch(cfg)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_count_ties_to_the_program(name):
    cfg, arch = _arch(name)
    assert arch.param_count(cfg) == CONFIGS[name]
    assert arch.param_count(cfg) == harness.program_config(cfg).param_count()


def test_kv_bytes_per_token():
    cfg, arch = _arch("internlm2-1.8b")
    assert arch.kv_bytes_per_token(cfg) == 98_304


def test_pass_flops_and_least_bytes():
    cfg, arch = _arch("internlm2-1.8b")
    n = arch.param_count(cfg)
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    # one token at position 0: every weight but the embedding, once
    attn = 4 * 24 * 16 * 128
    assert arch.pass_flops(cfg, 1, 0) == pytest.approx(
        2 * (n - emb - (2 * 24 + 1) * 2048) + attn)
    # a chunk's attention grows with its start; the head counts once
    a, b = arch.pass_flops(cfg, 256, 0), arch.pass_flops(cfg, 256, 256)
    assert b - a == pytest.approx(4 * 24 * 16 * 128 * 256 * 256)
    one = arch.decode_least_bytes(cfg, [0])
    assert one == pytest.approx((n - emb) * 2 + 2048 * 2 + 98_304)
    two = arch.decode_least_bytes(cfg, [0, 1000])
    assert two - one == pytest.approx(2048 * 2 + 1001 * 98_304)
    per_chip = arch.decode_least_bytes(cfg, [0], t=4)
    assert per_chip < arch.decode_least_bytes(cfg, [0]) / 3


# internlm2-1.8b's counts at published widths, frozen: the ledger's
# decode_mfu and decode_step_roofline were read with these numbers, so
# moving or refactoring the counts may not change one of them.
FROZEN_PASS = [(1, 0, 3399155712.0), (1, 1000, 3595763712.0),
               (256, 0, 779940790272.0), (256, 256, 792825692160.0),
               (64, 1280, 210167660544.0)]
FROZEN_DECODE = [
    ([0], 3399155712.0, (3399262208.0, 1699731456.0, 849966080.0)),
    ([0, 1000], 6994919424.0, (3497668608.0, 1748934656.0, 874567680.0)),
    ([5, 17, 2047, 1023], 14204534784.0,
     (3703525376.0, 1851863040.0, 926031872.0)),
]


@pytest.mark.parametrize("q_len,start,want", FROZEN_PASS)
def test_pass_flops_frozen(q_len, start, want):
    cfg, arch = _arch("internlm2-1.8b")
    assert arch.pass_flops(cfg, q_len, start) == want
    assert arch.param_count(cfg) == 1_889_110_016
    assert arch.kv_bytes_per_token(cfg) == 98_304


@pytest.mark.parametrize("positions,want,least", FROZEN_DECODE)
def test_decode_counts_frozen(positions, want, least):
    cfg, arch = _arch("internlm2-1.8b")
    assert arch.decode_flops(cfg, positions) == want
    assert tuple(arch.decode_least_bytes(cfg, positions, t)
                 for t in (1, 2, 4)) == least


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
