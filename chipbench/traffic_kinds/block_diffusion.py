"""``block_diffusion``: fixed-shape rows for block-diffusion training, the
NOISE part of the traffic: a clean row, its masked copy and each block's
noise level (BD3-LMs' vectorised training, arXiv:2503.09573 section 3;
SDAR, arXiv:2510.06303).

Parameters: ``batch`` (rows), ``seq`` (DATA tokens a row, ``L``), ``block``
(``B``, tokens a diffusion block; divides ``seq``), ``t_low``, ``t_high``.
Every step gets a fresh batch, made on the host from (seed, step), so that
program and reference see the same noise:

    ids     int32 [batch, seq]        data tokens, drawn from 0 .. vocab - 2
    t       float32 [batch, seq / B]  t_b ~ U(t_low, t_high), one a block
    masked  [batch, seq]              Bernoulli(t_blk(i)), each token alone
    noised  int32 [batch, seq]        MASK where masked, else ids

``MASK`` is the LAST row of the vocabulary held, ``vocab - 1``
(``Feed.mask_token_id``; the data never draws it).  ``feed(step)`` returns
``(ids, (noised, t))``: ``train.run`` hands both to the program's ``step``,
and the runner's program opens the second (``runners/sdar_train.py``).
``tokens_per_step`` counts DATA tokens, ``batch * seq``: the stack runs twice
as many positions, and the noised copy is not traffic anybody sent.
"""

import numpy as np

from ._common import rng_for, token_ids


class Feed:
    def __init__(self, params, seed, vocab):
        self.batch = int(params["batch"])
        self.seq = int(params["seq"])
        self.block = int(params["block"])
        if self.seq % self.block:
            raise ValueError(f"rows of {self.seq} are not whole blocks of "
                             f"{self.block}")
        self.t_low, self.t_high = float(params["t_low"]), \
            float(params["t_high"])
        self.tokens_per_step = self.batch * self.seq
        self.mask_token_id = vocab - 1
        self._seed = seed

    def __call__(self, step):
        """``(ids, (noised, t))`` for step ``step`` (0-based)."""
        rng = rng_for(self._seed, step)
        ids = token_ids(rng, self.tokens_per_step,
                        self.mask_token_id).reshape(self.batch, self.seq)
        t = rng.uniform(self.t_low, self.t_high,
                        (self.batch, self.seq // self.block)
                        ).astype(np.float32)
        masked = rng.random((self.batch, self.seq), np.float32) \
            < np.repeat(t, self.block, axis=1)
        noised = np.where(masked, np.int32(self.mask_token_id), ids)
        return ids, (noised, t)

    def offered(self):
        return {"rows_per_step": self.batch, "tokens_per_step":
                self.tokens_per_step, "positions_per_step":
                2 * self.tokens_per_step, "block": self.block}


def generate(params, seed, seconds, vocab):
    return Feed(params, seed, vocab)
