"""Shared by the traffic kinds.  The seed chooses token ids and ORDER,
never totals: every seed offers the same rows and tokens a step."""

import numpy as np


def rng_for(seed, stream):
    """Independent generator per (seed, stream); any whole-number seed."""
    return np.random.Generator(np.random.PCG64([int(seed), int(stream)]))


def token_ids(rng, n, vocab):
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)
