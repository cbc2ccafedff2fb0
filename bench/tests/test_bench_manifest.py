"""BENCHMARK.json against the contract it is held to, on the CPU with no
chip: every name resolves to its files, names and units keep to their
characters, each per-layer metric's cells report what it moves, and at
most half the cells take four chips."""
import json
import os
import re

import pytest

from bench import harness, modules

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_top_level_keys_and_command(bench):
    assert set(bench) == TOP_KEYS
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_units_and_text(bench):
    names = [c["name"] for c in bench["configs"]] \
        + [w["name"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]] \
        + [w["traffic"] for w in bench["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in bench[key]]
        assert len(got) == len(set(got)), key
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\t" not in m["layer"]


def test_every_cell_resolves_its_files(bench):
    """Each name resolves to its file: the configuration, the deploy file
    (whose keys the program's ``make_backend`` and ``Scheduler`` take), the
    traffic mix and the generators it names, the reference, the limits and
    every metric's reader."""
    for w in bench["workloads"]:
        spec = harness.resolve(bench, w["name"])
        assert spec.chips in (1, 4)
        assert spec.cfg["name"] == w["config"]
        assert spec.limits["max_logit_gap"]["limit"] > 0
        assert callable(modules.load("reference", spec.cfg["reference"]).score)
        assert callable(modules.load("traffic", spec.mix["arrivals"]).gaps)
        assert callable(modules.load("traffic", spec.mix["tokens"]).prompts)
        for key in ("prompt", "output"):
            assert callable(modules.load("traffic",
                                         spec.mix[key]["lengths"]).lengths)
        need = int(spec.mix["prompt"]["max"]) + int(spec.mix["output"]["max"])
        assert need - 1 <= spec.backend_kw["max_len"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(modules.load("metrics", m["name"]).read)


def test_deploy_keys_the_program_does_not_take_are_refused():
    good = {"make_backend": {"kind": "gspmd", "paged": True,
                             "num_slots": 2, "max_len": 64},
            "scheduler": {"chunk_size": 32}}
    harness.check_deploy(good, "good")
    for bad in ({**good, "make_backend": {**good["make_backend"], "p2": 2}},
                {**good, "scheduler": {"chunk_size": 32, "burst": 1}},
                {**good, "slots": 8},
                {**good, "make_backend": {**good["make_backend"],
                                          "paged": False}}):
        with pytest.raises(KeyError):
            harness.check_deploy(bad, "bad")
    with pytest.raises(KeyError, match="no metrics named"):
        modules.load("metrics", "no_such_metric")


def test_config_files(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        modules.arch(cfg)                  # with every function of its API
        harness.program_config(cfg)        # the program runs these sizes


def test_per_layer_moves_are_reported(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert harness.applies(e2e[m["moves"]], cell), (m["name"], cell)
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        got = [m["name"] for m in bench["end_to_end"]
               if harness.applies(m, cell)]
        assert "setup_s" in got and len(got) >= 2
        assert any(harness.applies(m, cell) for m in bench["per_layer"])


def test_four_chip_cells_at_most_half(bench):
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_weights_match_the_program_layout(bench):
    import jax
    from repro.models.transformer import get_model
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        arch = modules.arch(cfg)
        want = jax.eval_shape(get_model(harness.program_config(cfg)).init,
                              jax.random.PRNGKey(0))
        flat = dict(jax.tree_util.tree_flatten_with_path(want)[0])
        shapes = {tuple(k.key for k in path): leaf.shape
                  for path, leaf in flat.items()}
        assert shapes == {p: s[0] for p, s in arch.leaf_specs(cfg).items()}
        assert arch.param_count(cfg) == sum(
            int(leaf.size) for leaf in jax.tree.leaves(want))
