"""The float32 reference forward (``repro.models.reference``) against the
model trunk: at float32 on the CPU the two are the same mathematics."""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models.reference import reference_last_logits
from repro.models.transformer import get_model


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "llama32-3b"])
def test_reference_matches_model_forward_at_float32(arch):
    cfg = get_config(arch).reduced(num_layers=3)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(2, cfg.vocab_size, 24)
    want = np.asarray(model.forward(params, tokens[None])[0])[0, -1,
                                                             :cfg.vocab_size]
    got = reference_last_logits(cfg, params, tokens)
    assert got.shape == (cfg.vocab_size,)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_reference_rejects_what_it_does_not_model():
    cfg = get_config("rwkv6-7b").reduced()
    with pytest.raises(ValueError, match="reference covers"):
        reference_last_logits(cfg, {}, np.zeros(4, np.int32))
