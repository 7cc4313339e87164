"""``pretrain``: fixed-shape causal-LM batches of seeded token ids.

Parameters: ``batch`` (rows), ``seq`` (tokens a row).  Every step gets a
fresh batch, made on the host from (seed, step) and moved to the device
by the runner; rows all differ.  Labels are the ids (the loss shifts).
"""

from ._common import rng_for, token_ids


class Feed:
    def __init__(self, params, seed, vocab):
        self.batch = int(params["batch"])
        self.seq = int(params["seq"])
        self.tokens_per_step = self.batch * self.seq
        self._seed, self._vocab = seed, vocab

    def __call__(self, step):
        """(ids, labels) for step ``step`` (0-based), int32 [batch, seq]."""
        ids = token_ids(rng_for(self._seed, step), self.tokens_per_step,
                        self._vocab).reshape(self.batch, self.seq)
        return ids, ids

    def offered(self):
        return {"rows_per_step": self.batch, "tokens_per_step":
                self.tokens_per_step}


def generate(params, seed, seconds, vocab):
    return Feed(params, seed, vocab)
