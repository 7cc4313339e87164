"""Byte-level decoder whose attention is exact inside a block-aligned window
and reads pooled chunk summaries of every window before it, with several
next-byte heads (``model_type`` ``evabyte``, ``attention_class`` ``eva``:
EvaByte, huggingface.co/EvaByte/EvaByte ``config.json``; the attention is
EVA, "Efficient Attention via Control Variates", arXiv:2302.04542, with
learned per-head pooling vectors in the place of sampled random features).

The decoder is the shell of ``models/moe_decoder.py`` with its three options
on (``fp32_skip_add``, ``norm_add_unit_offset``, ``num_pred_heads``); this
file gives a layer its attention and a dense SwiGLU MLP.  With ``T`` bytes a
row (a multiple of ``W``), ``n`` heads of ``D``, ``W`` = ``window_size``,
``C`` = ``chunk_size``, ``s = D ** -0.5``; window of token ``t``: ``t // W``;
chunk ``c`` holds tokens ``cC .. cC + C - 1`` and lies in window ``cC // W``:

    x            float32 [T, H]        (the residual stream and its adds)
    a = rms(x) * (1 + g_1)             (eps ``rms_norm_eps``)
    q, k, v = W_q a, W_k a, W_v a -> [T, n, D]           (no bias)
    q, k <- rotary(q), rotary(k)       (rotate-half, base ``rope_theta``, all
                                        of D, positions 0 .. T - 1)

    per head h, with learned mu_h, phi_h in R^D, per chunk c:
      alpha_j = softmax over j in c of  s * (mu_h . k_j)
      kt_c = sum_j alpha_j k_j                              (the chunk's key)
      gamma_j = softmax over j in c of  s * (phi_h . k_j - |k_j|^2 / 2)
      vt_c = sum_j gamma_j v_j                            (the chunk's value)

    query t sees  E(t) = { j : j // W = t // W, j <= t }   exact keys, causal
                  R(t) = { c : cC // W < t // W }          summaries of every
                                                           EARLIER window
      o_t = ( sum_{E(t)} exp(s q_t.k_j) v_j + sum_{R(t)} exp(s q_t.kt_c) vt_c )
          / ( sum_{E(t)} exp(s q_t.k_j)     + sum_{R(t)} exp(s q_t.kt_c) )

    x <- x + W_o concat_h(o)                                    (float32 add)
    b = rms(x) * (1 + g_2);  x <- x + W_down(silu(W_gate b) * W_up b)

    z = rms(x) * (1 + g_f);  logits = W_head z -> [T, P, V] in float32
    head p at position t predicts byte t + 1 + p (``P`` = ``num_pred_heads``)
    loss = mean over p of mean over { t : t + 1 + p < T } of
           CE(logits[t, p], ids[t + 1 + p])

The windows are blocks and not sliding, and a window's own chunks are
attended exactly and never through their summaries.  ``gamma`` is the
self-normalised ``exp(omega . k - |k|^2 / 2)`` weight of the paper's
control-variate estimate with a learned ``omega``; k's gradient therefore
arrives three ways (the exact scores, the pooled key, the value pooling's
weights).  What the source's config does not spell out is listed, with the
reason for each choice, in ``chipbench/configs/evabyte-6.5b-train-l4.json``
``assumed``.  Scores, the two poolings' and the attention's softmax are
float32 whatever the operands' dtype (``mixedp_attn``).

The aggregation is ``ops.pallas.eva_attention``: on the TPU the
``eva_attention_*`` kernels, which visit only the key and summary blocks
the mask keeps; elsewhere the dense XLA composition.  The pooling stays
XLA: it reads k and v once.

Scopes inside ``attn`` (``docs/PROFILER.md``): ``eva_prep`` round the pooling
(scores, two softmaxes, ``kt``, ``vt``), ``eva_agg`` round the aggregation.
Counters (``step_counters``): ``eva_pairs_scored`` and ``eva_pairs_needed``.
This file trains; it has no decode path (a cache of one window's K and V
beside chunk summaries that grow by ``W / C`` a window is not built).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Initializer
from ..ops import pallas
from ..ops.pallas import eva_attention_kernel
from ..ops.registry import op
from .laguna import _rope, rope_tables
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM, linear


class _ClippedNormal(Initializer):
    """``N(0, 1)`` clipped to ``[-1, 1]``, times ``scale``: the pooling
    vectors' start."""

    def __init__(self, scale):
        self.scale = scale

    def __call__(self, shape, dtype):
        from ..framework.random import get_rng_key

        draw = jax.random.normal(get_rng_key(), shape, jnp.float32)
        return (jnp.clip(draw, -1.0, 1.0) * self.scale).astype(dtype)


class EvaByteConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them (``init_std`` is the
    shell's ``initializer_range``)."""

    fp32_skip_add = True
    norm_add_unit_offset = True

    def __init__(self, vocab_size=320, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=None,
                 intermediate_size=128, window_size=32, chunk_size=4,
                 num_pred_heads=3, rope_theta=100000, rms_norm_eps=1e-5,
                 init_std=0.01275):
        if num_key_value_heads not in (None, num_attention_heads):
            raise NotImplementedError(
                f"{num_key_value_heads} kv heads under {num_attention_heads} "
                f"q heads: the summaries are pooled a q head")
        if hidden_size % num_attention_heads or window_size % chunk_size:
            raise ValueError(
                f"hidden {hidden_size} over {num_attention_heads} heads, "
                f"window {window_size} over chunks of {chunk_size}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.head_dim = hidden_size // num_attention_heads
        self.intermediate_size = intermediate_size
        self.window_size = window_size
        self.chunk_size = chunk_size
        self.num_pred_heads = num_pred_heads
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = init_std

    def make_attention(self, layer_idx):
        return EvaAttention(self)

    def make_ffn(self, layer_idx):
        return self.dense_mlp(self.intermediate_size)


@op("eva_chunk_pooling")
def _eva_prep(k, v, mu, phi, chunk):
    """``k, v [B, T, N, D]``, ``mu, phi [N, D]`` -> the chunks' keys and
    values ``kt, vt [B, T / chunk, N, D]``.  Scores, softmaxes and the two
    weighted sums in float32; the results in the operands' dtype."""
    with jax.named_scope("eva_prep"):
        b, t, n, d = k.shape
        s = float(d) ** -0.5
        f32 = jnp.float32
        kc = k.reshape(b, t // chunk, chunk, n, d).astype(f32)
        vc = v.reshape(b, t // chunk, chunk, n, d).astype(f32)
        alpha = jax.nn.softmax(
            s * jnp.sum(kc * mu.astype(f32), axis=-1), axis=2)
        gamma = jax.nn.softmax(
            s * (jnp.sum(kc * phi.astype(f32), axis=-1)
                 - 0.5 * jnp.sum(kc * kc, axis=-1)), axis=2)
        kt = jnp.sum(alpha[..., None] * kc, axis=2).astype(k.dtype)
        vt = jnp.sum(gamma[..., None] * vc, axis=2).astype(v.dtype)
        return kt, vt


@op("eva_attention")
def _eva_agg(q, k, v, kt, vt, window, chunk):
    with jax.named_scope("eva_agg"):
        return pallas.eva_attention(q, k, v, kt, vt, window, chunk)


class EvaAttention(nn.Layer):
    """One layer's attention; ``mu`` and ``phi`` ``[heads, head_dim]`` are
    its pooling vectors.  After a forward ``pairs`` is ``(scored, needed)``:
    the (query, key-or-summary) pairs one row and head's scores were formed
    for, by the path taken, and the pairs the mask holds."""

    def __init__(self, config):
        super().__init__()
        c = config
        n, d, h = c.num_attention_heads, c.head_dim, c.hidden_size
        self.num_heads, self.head_dim = n, d
        self.window, self.chunk = c.window_size, c.chunk_size
        self._rope_params = {"rope_theta": c.rope_theta}
        self._tables = {}
        self.pairs = None
        std = c.initializer_range
        self.q_proj = linear(h, n * d, std)
        self.k_proj = linear(h, n * d, std)
        self.v_proj = linear(h, n * d, std)
        self.o_proj = linear(n * d, h, c.out_std)
        start = _ClippedNormal(float(d) ** -0.5)
        self.mu = self.create_parameter((n, d), default_initializer=start)
        self.phi = self.create_parameter((n, d), default_initializer=start)

    def rope(self, seq):
        if seq not in self._tables:
            self._tables[seq] = rope_tables(self.head_dim, seq,
                                            self._rope_params)[:2]
        return self._tables[seq]

    def forward(self, x):
        b, t, _ = x.shape
        n, d, window, chunk = (self.num_heads, self.head_dim, self.window,
                               self.chunk)
        if t % window:
            raise ValueError(f"{t} tokens a row are not whole windows of "
                             f"{window}")
        q = self.q_proj(x).reshape([b, t, n, d])
        k = self.k_proj(x).reshape([b, t, n, d])
        v = self.v_proj(x).reshape([b, t, n, d])
        cos, sin = self.rope(t)
        q, k = _rope(q, k, Tensor(jnp.asarray(cos)), Tensor(jnp.asarray(sin)))
        kt, vt = _eva_prep(k, v, self.mu, self.phi, chunk)
        out = _eva_agg(q, k, v, kt, vt, window, chunk)
        self.pairs = (
            pallas.eva_pairs_scored(t, d, window, chunk),
            sum(eva_attention_kernel.pairs_needed(t, window, chunk)))
        return self.o_proj(out.reshape([b, t, n * d]))


class EvaByteForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over :class:`EvaAttention`,
    with the ``num_pred_heads`` next-byte heads and their loss."""

    def step_counters(self):
        """``eva_pairs_scored`` and ``eva_pairs_needed``, int32 ``[layers]``
        (``docs/PROFILER.md``): the (query, key-or-summary) pairs ONE row
        and head of a layer's attention formed scores for in the last
        forward, by the path and block sizes it took, and the pairs the
        mask holds.  A step's whole count is these times rows and heads
        (3.1e9 at 1 x 16,384 x 32 x 4: past an int32, which is why the
        counters are not summed here).  They are known when the step is
        traced; the step hands them back as it hands the experts'."""
        pairs = [layer.attn.pairs for layer in self.model.layers]
        if None in pairs:
            return {}
        scored, needed = zip(*pairs)
        return {"eva_pairs_scored": jnp.asarray(np.array(scored, np.int32)),
                "eva_pairs_needed": jnp.asarray(np.array(needed, np.int32))}


def evabyte_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return EvaByteForCausalLM(EvaByteConfig(**kw))


def evabyte_6_5b(**kw):
    """EvaByte as its ``config.json`` states it (huggingface.co/EvaByte/
    EvaByte): 32 layers of hidden 4096, 32 heads of 128, windows of 2,048
    bytes in chunks of 16, an 11,008-wide SwiGLU, 8 next-byte heads over a
    vocabulary of 320.  Keyword arguments override (depth)."""
    cfg = dict(vocab_size=320, hidden_size=4096, num_hidden_layers=32,
               num_attention_heads=32, num_key_value_heads=32,
               intermediate_size=11008, window_size=2048, chunk_size=16,
               num_pred_heads=8, rope_theta=100000, rms_norm_eps=1e-5,
               init_std=0.01275)
    cfg.update(kw)
    return EvaByteForCausalLM(EvaByteConfig(**cfg))
