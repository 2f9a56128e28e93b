"""Every random draw of a run comes from ``--seed`` through these streams.

``--seed`` may exceed 32 bits; JAX's ``PRNGKey`` keeps only the low 32
bits of an integer when 64-bit mode is off, so seeds are first hashed by
NumPy's ``SeedSequence`` into 32-bit words, one pair per named stream.
"""
from __future__ import annotations

import numpy as np

STREAMS = {"weights": 1, "frames": 2, "traffic": 3, "check": 4}


def words(seed: int, stream: str) -> np.ndarray:
    return np.random.SeedSequence(
        [int(seed) % 2 ** 64, STREAMS[stream]]).generate_state(2)


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(words(seed, stream))


def jax_key(seed: int, stream: str):
    import jax
    w = words(seed, stream)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))
