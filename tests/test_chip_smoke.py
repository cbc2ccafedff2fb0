"""chip_smoke.py's phases, run on the CPU at a reduced size.

The script itself refuses to run without a TPU; these tests drive its
one-chip and four-layout phases directly on ``reduced()`` InternLM2 widths
in bf16 (the dtype the chip run serves), on the forced host devices.
"""
import dataclasses
import importlib.util
import os
import sys

import pytest

from repro.configs import get_config

_PATH = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

PLAN = chip_smoke.Plan(slots=4, max_len=128, chunk=16,
                       prompt_lens=(16, 32, 48), n_requests=6, new_tokens=5)


def _cfg():
    cfg = get_config("internlm2-1.8b").reduced(num_layers=4)
    return dataclasses.replace(cfg, dtype="bfloat16")


def test_main_refuses_without_a_tpu(capsys):
    """On the CPU the script exits non-zero and prints no result line."""
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "TPU" in out.err


def test_one_chip_phase_serves_and_matches_reference():
    lines = []
    with chip_smoke.CompileLog() as clog:
        chip_smoke.one_chip(_cfg(), PLAN, 0, clog, lines.append)
    assert clog.count > 0
    text = "\n".join(lines)
    assert "requests_served 6 tokens_generated 30 pages_leaked 0" in text
    assert text.count("error vs float32 reference") == 2


def test_four_layout_phase_matches_one_chip():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    lines = []
    with chip_smoke.CompileLog() as clog:
        chip_smoke.four_chips(_cfg(), PLAN, 0, clog, lines.append)
    text = "\n".join(lines)
    for name, *_ in chip_smoke.LAYOUTS:
        assert f"{name} first_tokens_equal" in text
    assert text.count("requests_served 6 tokens_generated 30") == 4
    # a pure pipeline runs the one-chip math stage by stage: same tokens
    assert "pp4 first_tokens_equal 6/6 near_ties 0" in text
