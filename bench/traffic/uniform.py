"""Unshared prompts: token ids drawn uniformly from [2, vocab), so no two
prompts share a prefix and the prefix cache finds nothing.

``prompts(mix, lengths, rng, vocab)`` gives one int32 array per length.
"""
import numpy as np


def prompts(mix: dict, lengths, rng: np.random.Generator, vocab: int):
    return [rng.integers(2, vocab, int(n), dtype=np.int32) for n in lengths]
