"""Architecture modules (``bench/arch/<arch>.py``) on the CPU: the dense
module's weights are bit for bit those of the layout it took over, and a
second architecture, the program's ``moe`` family at a tiny size, goes
through the harness's program check, weights and metric readers with a
module and a configuration file of its own and no other code."""
import json
import types

import numpy as np
import pytest

from bench import harness, modules, weights

SEED = 2 ** 33 + 5

# --------------------------------------------------------------- dense
DENSE = {"name": "tiny-dense", "arch": "dense_gqa", "dtype": "bfloat16",
         "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 300}


def _frozen_leaves(tied: bool) -> dict:
    """The dense layout at ``DENSE``'s sizes, written out leaf by leaf."""
    leaves = {
        ("blocks", "wq"): ((2, 64, 64), 1 / np.sqrt(64)),
        ("blocks", "wk"): ((2, 64, 32), 1 / np.sqrt(64)),
        ("blocks", "wv"): ((2, 64, 32), 1 / np.sqrt(64)),
        ("blocks", "wo"): ((2, 64, 64), 1 / np.sqrt(64)),
        ("blocks", "w1"): ((2, 64, 128), 1 / np.sqrt(64)),
        ("blocks", "w3"): ((2, 64, 128), 1 / np.sqrt(64)),
        ("blocks", "w2"): ((2, 128, 64), 1 / np.sqrt(128)),
        ("blocks", "ln1"): ((2, 64), 0.1),
        ("blocks", "ln2"): ((2, 64), 0.1),
        ("final_norm",): ((64,), 0.1),
        ("embed",): ((384, 64), 1.0),
    }
    if not tied:
        leaves[("lm_head",)] = ((64, 384), 1 / np.sqrt(64))
    return leaves


def _frozen_params(leaves: dict, seed: int, vocab: int) -> dict:
    """The seeding the benchmark's weights have always had: leaf ``i`` in
    sorted path order draws normal * std from ``fold_in(key, i)`` in
    float32, padding rows of the vocabulary zeroed, then bfloat16."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)),
                             np.uint32(seed >> 32))
    out = {}
    for i, (path, (shape, std)) in enumerate(sorted(leaves.items())):
        a = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        if path == ("embed",):
            a = a.at[vocab:].set(0.0)
        if path == ("lm_head",):
            a = a.at[:, vocab:].set(0.0)
        out[path] = np.asarray(a.astype(jnp.bfloat16))
    return out


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_dense_weights_bit_identical_to_the_frozen_layout(tied):
    import jax
    cfg = dict(DENSE, tie_word_embeddings=tied)
    leaves = _frozen_leaves(tied)
    assert modules.arch(cfg).leaf_specs(cfg) == leaves
    params = weights.make_params(cfg, SEED)
    got = {tuple(k.key for k in path): np.asarray(leaf) for path, leaf in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    want = _frozen_params(leaves, SEED, cfg["vocab_size"])
    assert sorted(got) == sorted(want)
    for path, a in want.items():
        assert got[path].dtype == a.dtype, path
        assert np.array_equal(got[path].view(np.uint16), a.view(np.uint16)), \
            path


# --------------------------------------------------------------- a second
# architecture, as files alone: the program's top-k routed GLU experts with
# shared experts (``repro.configs.deepseek_moe_16b`` at the program's own
# reduced size), under GQA attention.
MOE_ARCH = "test_routed_moe"
MOE_MODULE = '''
import numpy as np

from bench.weights import dtype_bytes, padded_vocab


def _d(cfg):
    return (int(cfg["hidden_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["n_routed_experts"]),
            int(cfg["num_experts_per_tok"]), int(cfg["moe_intermediate_size"]),
            int(cfg["n_shared_experts"]), int(cfg["vocab_size"]))


def check_program(cfg, pcfg):
    want = {"family": "moe", "activation": "swiglu",
            "d_model": cfg["hidden_size"], "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "dtype": cfg["dtype"],
            "moe.num_experts": cfg["n_routed_experts"],
            "moe.top_k": cfg["num_experts_per_tok"],
            "moe.expert_d_ff": cfg["moe_intermediate_size"],
            "moe.num_shared_experts": cfg["n_shared_experts"],
            "moe.shared_d_ff": cfg["moe_intermediate_size"]}
    get = lambda k: getattr(pcfg.moe, k[4:]) if k.startswith("moe.") \\
        else getattr(pcfg, k)
    bad = sorted(k for k, v in want.items() if get(k) != v)
    if bad:
        raise ValueError(f"fields differ: {bad}")


def leaf_specs(cfg):
    h, L, H, Hkv, D, E, _, f, S, _ = _d(cfg)
    pv, sf = padded_vocab(cfg), S * f
    s = lambda n: 1.0 / np.sqrt(n)
    return {
        ("blocks", "wq"): ((L, h, H * D), s(h)),
        ("blocks", "wk"): ((L, h, Hkv * D), s(h)),
        ("blocks", "wv"): ((L, h, Hkv * D), s(h)),
        ("blocks", "wo"): ((L, H * D, h), s(H * D)),
        ("blocks", "router"): ((L, h, E), s(h), "float32"),
        ("blocks", "we1"): ((L, E, h, f), s(h)),
        ("blocks", "we3"): ((L, E, h, f), s(h)),
        ("blocks", "we2"): ((L, E, f, h), s(f)),
        ("blocks", "sw1"): ((L, h, sf), s(h)),
        ("blocks", "sw3"): ((L, h, sf), s(h)),
        ("blocks", "sw2"): ((L, sf, h), s(sf)),
        ("blocks", "ln1"): ((L, h), 0.1),
        ("blocks", "ln2"): ((L, h), 0.1),
        ("final_norm",): ((h,), 0.1),
        ("embed",): ((pv, h), 1.0),
        ("lm_head",): ((h, pv), s(h)),
    }


def _layer(cfg, experts):
    """Matrix weights of one layer with ``experts`` routed experts."""
    h, _, H, Hkv, D, E, _, f, S, _ = _d(cfg)
    return h * (H * D + 2 * Hkv * D) + H * D * h + h * E \\
        + 3 * h * f * (experts + S)


def param_count(cfg):
    h, L, *_, E, _, _, _, V = _d(cfg)
    return L * (_layer(cfg, E) + 2 * h) + 2 * V * h + h


def kv_bytes_per_token(cfg):
    _, L, _, Hkv, D, *_ = _d(cfg)
    return 2 * L * Hkv * D * dtype_bytes(cfg)


def pass_flops(cfg, q_len, start):
    h, L, H, _, D, _, K, _, _, V = _d(cfg)
    ctx = q_len * start + q_len * (q_len + 1) / 2.0
    return 2.0 * L * _layer(cfg, K) * q_len + 4.0 * L * H * D * ctx \\
        + 2.0 * h * V


def decode_flops(cfg, positions):
    return sum(pass_flops(cfg, 1, int(p)) for p in positions)


def decode_least_bytes(cfg, positions, t=1):
    """At least the top-k experts are read, all tokens routed alike."""
    h, L, _, _, _, E, K, _, _, V = _d(cfg)
    b, positions = dtype_bytes(cfg), [int(p) for p in positions]
    w = (L * (_layer(cfg, K) - h * E) + V * h) * b / t \\
        + L * h * E * 4 + (2 * L * h + h) * b
    return w + len(positions) * h * b / t + kv_bytes_per_token(cfg) / t \\
        * (sum(positions) + len(positions))
'''

# The program's deepseek-moe-16b after ``reduced()``, in published keys.
MOE_CFG = {"name": "tiny-routed-moe", "model_id": "deepseek-moe-16b",
           "arch": MOE_ARCH, "dtype": "float32", "hidden_size": 256,
           "intermediate_size": 1024, "moe_intermediate_size": 512,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 4, "head_dim": 64, "n_routed_experts": 4,
           "num_experts_per_tok": 2, "n_shared_experts": 1,
           "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
           "tie_word_embeddings": False}


@pytest.fixture
def moe_files(tmp_path, monkeypatch):
    """The architecture module and the configuration file, written under
    ``tmp_path`` and found by name; the program's registry gives its
    deepseek-moe-16b at the reduced size."""
    from repro import configs
    (tmp_path / f"{MOE_ARCH}.py").write_text(MOE_MODULE)
    (tmp_path / "tiny-routed-moe.json").write_text(json.dumps(MOE_CFG))
    where = modules.path
    monkeypatch.setattr(modules, "path", lambda kind, name: (
        str(tmp_path / f"{name}.py") if kind == "arch" else where(kind, name)))
    real = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda model_id: real(model_id).reduced())
    return json.loads((tmp_path / "tiny-routed-moe.json").read_text())


def test_second_architecture_by_files_alone(moe_files):
    import jax
    cfg = moe_files
    pcfg = harness.program_config(cfg)
    assert pcfg.family == "moe" and pcfg.moe.num_experts == 4
    params = harness.make_weights(cfg, pcfg, SEED)
    assert params["blocks"]["router"].dtype == np.float32
    arch = modules.arch(cfg)
    n = arch.param_count(cfg)
    assert n == pcfg.param_count() == sum(
        int(a.size) for a in jax.tree.leaves(params))
    h, L, V = 256, 2, 512
    assert arch.kv_bytes_per_token(cfg) == 2 * L * 4 * 64 * 4
    # one token at position 0: every active weight but the embedding and
    # the norms, once, and attention over itself
    assert arch.pass_flops(cfg, 1, 0) == 2 * (
        pcfg.active_param_count() - V * h - (2 * L + 1) * h) + 4 * L * 4 * 64

    decodes = [(0.0, 0.02, [1, 2], [40, 90]), (0.02, 0.05, [1, 2], [41, 91]),
               (0.9, 1.2, [2], [150])]             # starts after the window
    run = types.SimpleNamespace(
        spec=types.SimpleNamespace(cfg=cfg, chips=1), t=1, decodes=decodes,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        in_window=lambda t: 0.0 <= t < 0.5,
        trace=types.SimpleNamespace(busy_s=lambda span=None: 0.04))
    mfu = modules.load("metrics", "decode_mfu").read(run)
    work = arch.decode_flops(cfg, [40, 90]) + arch.decode_flops(cfg, [41, 91])
    assert mfu == pytest.approx(100 * work / (0.05 * 197e12))
    roof = modules.load("metrics", "decode_step_roofline").read(run)
    least = sum(max(arch.decode_flops(cfg, p) / 197e12,
                    arch.decode_least_bytes(cfg, p) / 819e9)
                for p in ([40, 90], [41, 91]))
    assert roof == pytest.approx(100 * least / 0.04)
    assert 0 < mfu < roof < 100


@pytest.mark.parametrize("change,error,words", [
    ({"arch": None}, KeyError, ['names no "arch"']),
    ({"arch": "dense_gqa"}, harness.RunFailed,
     ["bench/arch/dense_gqa.py", "family", "'moe'", "'dense'"]),
    ({"moe_intermediate_size": 256}, harness.RunFailed,
     [f"bench/arch/{MOE_ARCH}.py", "moe.expert_d_ff", "moe.shared_d_ff"]),
    ({"arch": "no_such_arch"}, KeyError, ["no arch named 'no_such_arch'"]),
], ids=["arch-missing", "dense-module-on-moe", "expert-width", "no-module"])
def test_arch_missing_or_not_the_program_fails(moe_files, change, error,
                                               words):
    cfg = {k: v for k, v in {**moe_files, **change}.items() if v is not None}
    with pytest.raises(error) as e:
        harness.program_config(cfg)
    for w in words:
        assert w in str(e.value), (w, str(e.value))
