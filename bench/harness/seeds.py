"""Sub-seeds drawn from ``--seed``: every input of a run comes from here."""
from __future__ import annotations

import numpy as np

__all__ = ["sub_seed", "rng"]


def sub_seed(seed: int, *tags: int) -> int:
    """A seed in [0, 2**30) fixed by ``seed`` (any non-negative integer,
    however large) and ``tags``.  Below 2**30 it stays a valid 32-bit
    JAX PRNG seed after the program adds a bracket to it."""
    words = [int(seed) >> (32 * i) & 0xFFFFFFFF
             for i in range(max(1, (int(seed).bit_length() + 31) // 32))]
    ss = np.random.SeedSequence(words + [len(words)] + [int(t) for t in tags])
    return int(ss.generate_state(1, np.uint32)[0] >> 2)


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(sub_seed(seed, *tags))
