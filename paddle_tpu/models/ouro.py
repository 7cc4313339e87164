"""Decoder whose WHOLE layer stack runs several times over the same
parameters, the head reading the state after every pass and a learned exit
gate weighing each pass's loss (``model_type`` ``ouro``: Ouro-2.6B,
huggingface.co/ByteDance/Ouro-2.6B ``config.json``; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741).

The decoder is the shell of ``models/moe_decoder.py`` with its two options
``post_branch_norm`` and ``total_ut_steps`` on; this file gives a layer its
attention and a dense SwiGLU MLP.  With ``T`` tokens a row, ``H`` the hidden
size, ``n`` heads of ``D`` = ``head_dim``, ``R`` = ``total_ut_steps``, ``L``
layers, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    block(x):                                 (four norms a block: "sandwich")
      a = rms(x; g_1a);  q, k, v = W_q a, W_k a, W_v a -> [T, n, D]  (no bias)
      q, k <- rotary(q), rotary(k)   (rotate-half over all of D, base
                                      ``rope_theta``, positions 0 .. T - 1)
      o = causal softmax(q k^T / sqrt(D)) v;   x <- x + rms(W_o o; g_1b)
      b = rms(x; g_2a);  m = W_down(silu(W_gate b) * W_up b)
      x <- x + rms(m; g_2b)

    h_0 = E[ids]
    for r = 1 .. R:  h_r = rms(block_L(... block_1(h_{r-1})); g_f)
                     (the SAME L blocks and g_f every pass; the NORMED state
                      is what the next pass reads)
      logits_r = W_head h_r              [T, V], float32 for the loss
      lambda_r = sigmoid(w_g . h_r + b_g)      [T], r < R (one Linear(H, 1)
                                          shared by the passes; pass R's is
                                          not used)
      l_r[t]   = CE(logits_r[t], ids[t + 1])   t = 0 .. T - 2

    exit distribution, per token:  S_r = prod_{j<r} (1 - lambda_j)
      p_r = lambda_r S_r (r < R);  p_R = S_R                   (sums to 1)
    loss = mean over t of [ sum_r p_r[t] l_r[t]  -  beta H(p[t]) ]
      H(p) = - sum_r p_r log p_r,  beta = ``exit_entropy_beta`` (0.05)

A weight's gradient is the sum of what its ``R`` applications give; the
gate's gradient reaches every earlier pass through ``h_r``.  What the
source's config does not spell out (the sandwich norm, no bias, the rotary's
form, the normed state fed back, the gate, the objective and its ``beta``)
is listed, each with its reason, in ``chipbench/configs/ouro-2.6b-train-
ut4.json`` ``assumed``.

How the passes are built, what a pass saves and where head, loss and gate
run: ``MoeDecoderForCausalLM.looped``.  K and V go to
``F.scaled_dot_product_attention`` at their own head count (the flash
kernels on the TPU, the XLA composition elsewhere); the rotary tables and
rotation are ``models/laguna.py``'s, plain (no YaRN, all of ``D``).

Scopes (``docs/PROFILER.md``): the shell's, ``ln_1b`` / ``ln_2b`` for the
norms after a branch, ``exit_gate``.  Counters (``step_counters``):
``ouro_pass_loss`` and ``ouro_exit_mass``.  This file trains; it has no
decode path: ``early_exit_threshold`` (a token leaves after the first pass
at which the running sum of ``p_r`` passes it; 1.0 = never early) is kept
and not read, and a cache of ``R x L`` sets of pages is not built.
"""

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from .laguna import _rope, rope_tables
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM, linear


class OuroConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them;
    ``exit_entropy_beta`` is the objective's ``beta`` (the config has no
    key for it)."""

    def __init__(self, vocab_size=512, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=None,
                 head_dim=16, intermediate_size=160, total_ut_steps=4,
                 early_exit_threshold=1.0, rope_theta=1000000,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 exit_entropy_beta=0.05, post_branch_norm=True):
        kv = num_attention_heads if num_key_value_heads is None \
            else num_key_value_heads
        if num_attention_heads % kv:
            raise ValueError(f"{num_attention_heads} q heads over {kv} kv "
                             f"heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = kv
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = early_exit_threshold
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.exit_entropy_beta = exit_entropy_beta
        self.post_branch_norm = post_branch_norm

    def make_attention(self, layer_idx):
        return OuroAttention(self)

    def make_ffn(self, layer_idx):
        return self.dense_mlp(self.intermediate_size)


class OuroAttention(nn.Layer):
    """Causal attention of ``num_attention_heads`` heads of ``head_dim`` over
    ``num_key_value_heads`` kv heads, plain rotary on all of a head, no
    bias."""

    def __init__(self, config):
        super().__init__()
        c = config
        n, kv, d, h = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim, c.hidden_size)
        self.num_heads, self.num_kv_heads, self.head_dim = n, kv, d
        self._rope_params = {"rope_theta": c.rope_theta}
        self._tables = {}
        std = c.initializer_range
        self.q_proj = linear(h, n * d, std)
        self.k_proj = linear(h, kv * d, std)
        self.v_proj = linear(h, kv * d, std)
        self.o_proj = linear(n * d, h, c.out_std)

    def rope(self, seq):
        """cos, sin ``[seq, D / 2]`` for positions 0 .. seq - 1 (host
        arrays: a traced step bakes them in as constants)."""
        if seq not in self._tables:
            self._tables[seq] = rope_tables(self.head_dim, seq,
                                            self._rope_params)[:2]
        return self._tables[seq]

    def forward(self, x):
        b, t, _ = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_proj(x).reshape([b, t, n, d])
        k = self.k_proj(x).reshape([b, t, kv, d])
        v = self.v_proj(x).reshape([b, t, kv, d])
        cos, sin = self.rope(t)
        q, k = _rope(q, k, Tensor(jnp.asarray(cos)), Tensor(jnp.asarray(sin)))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(out.reshape([b, t, n * d]))


class OuroForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over :class:`OuroAttention`,
    its stack run ``total_ut_steps`` times (``looped``, ``looped_loss``)."""


def ouro_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return OuroForCausalLM(OuroConfig(**kw))


def ouro_2_6b(**kw):
    """Ouro-2.6B as its ``config.json`` states it (huggingface.co/
    ByteDance/Ouro-2.6B): 48 layers of hidden 2048, 16 heads of 128 with a
    kv head a q head, a gated MLP of 5,632, vocabulary 49,152 untied, the
    stack run four times.  Keyword arguments override (depth)."""
    cfg = dict(vocab_size=49152, hidden_size=2048, num_hidden_layers=48,
               num_attention_heads=16, num_key_value_heads=16, head_dim=128,
               intermediate_size=5632, total_ut_steps=4,
               early_exit_threshold=1.0, rope_theta=1000000,
               rms_norm_eps=1e-6, initializer_range=0.02)
    cfg.update(kw)
    return OuroForCausalLM(OuroConfig(**cfg))
