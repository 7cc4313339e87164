"""MoE gates: naive top-k, GShard top-2, Switch top-1.

Reference: python/paddle/incubate/distributed/models/moe/gate/{naive,gshard,
switch}_gate.py.  TPU redesign: gates are pure functions of the token batch
returning dense (combine, dispatch) tensors — the GShard einsum formulation —
so expert routing compiles to batched matmuls + all-to-all over the 'ep'
mesh axis instead of the reference's global_scatter host-side index plumbing
(paddle/fluid/operators/collective/global_scatter_op.cc).
"""

import jax
import jax.numpy as jnp


def _capacity(num_tokens, num_experts, top_k, capacity_factor):
    cap = int(num_tokens * top_k * capacity_factor / num_experts)
    return max(cap, top_k)


def _one_hot(idx, num):
    return jax.nn.one_hot(idx, num, dtype=jnp.float32)


def topk_gating(logits, top_k, capacity_factor, jitter_key=None,
                jitter_eps=0.0):
    """Dense GShard-style gating.

    logits: [S, E].  Returns dict with:
      combine  [S, E, C] — combine weights (0 for dropped tokens)
      dispatch [S, E, C] bool — routing mask
      aux_loss — load-balance loss (GShard eq.4 / Switch eq.4)
      probs    [S, E]
    """
    s, e = logits.shape
    c = _capacity(s, e, top_k, capacity_factor)
    if jitter_eps and jitter_key is not None:
        logits = logits + jitter_eps * jax.random.uniform(
            jitter_key, logits.shape, minval=-1.0, maxval=1.0)
    probs = jax.nn.softmax(logits, axis=-1)                    # [S, E]

    combine = jnp.zeros((s, e, c), jnp.float32)
    remaining = probs
    # fill counts per expert as we take top-1, top-2, ...
    counts = jnp.zeros((e,), jnp.int32)
    aux_me = jnp.mean(probs, axis=0)                           # [E]
    fracs = jnp.zeros((e,), jnp.float32)
    for k in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                   # [S]
        oh = _one_hot(idx, e)                                  # [S, E]
        # position of each token within its expert's capacity buffer
        pos = (jnp.cumsum(oh, axis=0) - 1.0) + counts[None, :].astype(
            jnp.float32)
        pos_tok = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)  # [S]
        keep = pos_tok < c
        gate_k = jnp.sum(probs * oh, axis=-1)                  # [S]
        comb_k = (gate_k * keep)[:, None, None] * oh[:, :, None] \
            * _one_hot(jnp.clip(pos_tok, 0, c - 1), c)[:, None, :]
        combine = combine + comb_k
        counts = counts + jnp.sum(oh * keep[:, None],
                                  axis=0).astype(jnp.int32)
        fracs = fracs + jnp.mean(oh, axis=0)
        remaining = remaining * (1.0 - oh)                     # mask chosen
    # normalize combine weights over selected experts (sum over E,C)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    dispatch = combine > 0.0
    aux_loss = e * jnp.sum(aux_me * (fracs / top_k))
    return {"combine": combine, "dispatch": dispatch, "aux_loss": aux_loss,
            "probs": probs}


def topk_routing(logits, top_k, jitter_key=None, jitter_eps=0.0):
    """The same choice with NO capacity, for a dropless dispatch: the
    ``top_k`` largest softmax probabilities of each token, weights normed
    over them (what ``topk_gating``'s combine tensor holds when nothing is
    dropped).  Returns (idx [S, k], weights [S, k], aux_loss)."""
    e = logits.shape[1]
    if jitter_eps and jitter_key is not None:
        logits = logits + jitter_eps * jax.random.uniform(
            jitter_key, logits.shape, minval=-1.0, maxval=1.0)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
    fracs = jnp.mean(_one_hot(idx, e), axis=(0, 1))
    return idx, w, e * jnp.sum(jnp.mean(probs, axis=0) * fracs)


class BaseGate:
    jitter_eps = 0.0

    def __init__(self, d_model, num_experts, top_k, capacity_factor):
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor

    def __call__(self, logits, jitter_key=None):
        raise NotImplementedError

    def route(self, logits, jitter_key=None):
        """This gate's choice without a capacity (``topk_routing``): what
        ``MoELayer(dropless=True)`` dispatches by."""
        return topk_routing(logits, self.top_k, jitter_key, self.jitter_eps)


class NaiveGate(BaseGate):
    """Reference naive_gate.py: plain top-k softmax routing."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=2.0):
        super().__init__(d_model, num_experts, top_k, capacity_factor)

    def __call__(self, logits, jitter_key=None):
        return topk_gating(logits, self.top_k, self.capacity_factor)


class GShardGate(BaseGate):
    """Reference gshard_gate.py: top-2 with load-balance aux loss."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=2.0):
        super().__init__(d_model, num_experts, top_k, capacity_factor)

    def __call__(self, logits, jitter_key=None):
        return topk_gating(logits, self.top_k, self.capacity_factor)


class SwitchGate(BaseGate):
    """Reference switch_gate.py: top-1 routing with jitter."""

    def __init__(self, d_model, num_experts, top_k=1, capacity_factor=1.25,
                 jitter_eps=0.1):
        super().__init__(d_model, num_experts, 1, capacity_factor)
        self.jitter_eps = jitter_eps

    def __call__(self, logits, jitter_key=None):
        return topk_gating(logits, 1, self.capacity_factor,
                           jitter_key=jitter_key, jitter_eps=self.jitter_eps)
