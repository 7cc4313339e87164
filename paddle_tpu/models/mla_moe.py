"""Latent-attention decoder with routed and shared experts (the DeepSeek-V3
layer family: ``model_type`` ``deepseek_v3``).

The decoder itself (block, stack, untied head over a vocabulary slice,
``loss``, ``step_counters``) is the shell of ``models/moe_decoder.py``, which
``models/laguna.py`` shares; this file gives it its attention and says
which layers are dense.  Block: ``x <- x + attn(rms(x))``, ``x <- x +
ffn(rms(x))``, a final RMSNorm and an untied ``lm_head``.  The first
``first_k_dense_replace`` layers' ffn is a dense SwiGLU MLP (``mlp``);
every later layer's is the expert layer (``moe``:
``incubate/distributed/models/moe DroplessMoELayer``).

Attention is multi-head latent attention (MLA) in its expanded training
form, without a query latent (``q_lora_rank`` null):

    q = W_q x                      -> heads x (nope | rope)
    [c | k_r] = W_kva x            -> kv_lora_rank | rope
    [k_n | v] = W_kvb rms(c)       -> heads x (nope | v)
    k = [k_n | k_r]                   (k_r shared by every head)

with rotary positions on q's rope part and on k_r, interleaved as
published (``rope_interleave``: the pairs (0,1), (2,3), ... are
de-interleaved into halves, then rotate-half).  Scores are ``q . k /
sqrt(nope + rope)``, causal.  q and k are wider than v (192 against 128
at the published sizes); ``F.scaled_dot_product_attention`` takes the two
widths and the flash kernel serves them on the TPU.

Departures from the public modelling code, none of which changes a value:
gate and up projections are packed in one matrix (``gate_up``), as
``models/llama.py`` packs them; the experts held here are stacked on a
leading axis; the decode path (the absorbed form over a latent cache) is
not here: this file trains.

One chip's share of an expert-parallel layer is stated by
``num_local_experts`` and ``expert_offset`` (see ``DroplessMoELayer``);
``vocab_size`` is whatever slice of the vocabulary is held.

A decoder layer hands its expert counts on as an OUTPUT, so that
``jit.TrainStep(remat=...)`` can rematerialise each layer in the backward
pass (at the published widths and sequence 8192 the step does not fit
otherwise; ``remat=["flash_attention_out", "flash_attention_lse"]`` keeps
the flash forward's results so that the kernel is not run twice).
"""

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..ops import pallas
from ..ops.pallas import mla_rope as apply_rope     # noqa: F401 (the tests')
from ..ops.registry import op
from .llama import _rope_tables as rope_tables      # cos, sin [T, D/2]
from .moe_decoder import (MoeDecoderConfig, MoeDecoderForCausalLM,
                          linear as _linear)


class MlaMoeConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them, where it has one."""

    def __init__(self, vocab_size=1024, hidden_size=256, num_hidden_layers=4,
                 num_attention_heads=4, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
                 q_lora_rank=None, intermediate_size=512,
                 moe_intermediate_size=64, n_routed_experts=8,
                 n_shared_experts=2, num_experts_per_tok=2,
                 first_k_dense_replace=1, routed_scaling_factor=1.0,
                 norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
                 rope_interleave=True, max_position_embeddings=2048,
                 initializer_range=0.02, num_local_experts=None,
                 expert_offset=0):
        if q_lora_rank is not None:
            raise NotImplementedError(
                "a query latent (q_lora_rank) is not implemented: q is one "
                "projection here")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts      # the router's width
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_interleave = rope_interleave
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.num_local_experts = n_routed_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def make_attention(self, layer_idx):
        return MLAttention(self)

    def make_ffn(self, layer_idx):
        if layer_idx < self.first_k_dense_replace:
            return self.dense_mlp(self.intermediate_size)
        return self.expert_layer(
            self.moe_intermediate_size, self.n_routed_experts,
            self.num_experts_per_tok, self.n_shared_experts,
            self.routed_scaling_factor)


@op("mla_expand_qkv")
def _expand_qkv(q, kv_b, k_rope, cos, sin, *, nope, interleave):
    """``q [B, T, N, nope + rope]``, ``kv_b [B, T, N, nope + v]``, ``k_rope
    [B, T, rope]`` -> q, k ``[B, T, N, nope + rope]`` and v ``[B, T, N,
    v]`` with the rotary parts rotated (:func:`apply_rope`) and ``k_rope``
    given to every head: ``ops.pallas.mla_expand_qkv``, on the TPU ONE
    kernel forward and one backward that write the flash calls' layout,
    elsewhere the XLA composition."""
    with jax.named_scope("mla_expand"):
        return pallas.mla_expand_qkv(q, kv_b, k_rope, cos, sin, nope=nope,
                                     interleave=interleave)


class MLAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        std = c.initializer_range
        out_std = std / math.sqrt(2 * c.num_hidden_layers)
        self.num_heads = c.num_attention_heads
        self.nope, self.rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.v_dim, self.rank = c.v_head_dim, c.kv_lora_rank
        self.interleave = c.rope_interleave
        n = self.num_heads
        self.q_proj = _linear(c.hidden_size, n * (self.nope + self.rope), std)
        self.kv_a_proj = _linear(c.hidden_size, self.rank + self.rope, std)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = _linear(self.rank, n * (self.nope + self.v_dim), std)
        self.o_proj = _linear(n * self.v_dim, c.hidden_size, out_std)
        cos, sin = rope_tables(self.rope, c.max_position_embeddings,
                               c.rope_theta)
        self._cos, self._sin = jnp.asarray(cos), jnp.asarray(sin)

    def forward(self, x):
        b, t, _ = x.shape
        n = self.num_heads
        q = self.q_proj(x).reshape([b, t, n, self.nope + self.rope])
        kv_a = self.kv_a_proj(x)
        kv_b = self.kv_b_proj(self.kv_a_layernorm(kv_a[:, :, :self.rank]))
        q, k, v = _expand_qkv(
            q, kv_b.reshape([b, t, n, self.nope + self.v_dim]),
            kv_a[:, :, self.rank:], Tensor(self._cos[:t]),
            Tensor(self._sin[:t]), nope=self.nope,
            interleave=self.interleave)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(out.reshape([b, t, n * self.v_dim]))


class MlaMoeForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over :class:`MLAttention`
    (the class keeps its name: it is the root of the step's scope
    paths)."""


def mla_moe_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return MlaMoeForCausalLM(MlaMoeConfig(**kw))


def kanana_2_30b_a3b(**kw):
    """kanana-2-30b-a3b-instruct-2601 as its ``config.json`` states it
    (huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601): 48 layers,
    hidden 2048, 32 heads of 128 + 64 rotary over 128, latent 512, one
    dense layer of 6144, then 128 experts of 768, six a token, beside two
    shared ones.  Keyword arguments override (depth, the experts held)."""
    cfg = dict(vocab_size=128256, hidden_size=2048, num_hidden_layers=48,
               num_attention_heads=32, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
               intermediate_size=6144, moe_intermediate_size=768,
               n_routed_experts=128, n_shared_experts=2,
               num_experts_per_tok=6, first_k_dense_replace=1,
               routed_scaling_factor=2.448, norm_topk_prob=True,
               rms_norm_eps=1e-6, rope_theta=1e6, rope_interleave=True,
               max_position_embeddings=32768)
    cfg.update(kw)
    return MlaMoeForCausalLM(MlaMoeConfig(**cfg))
