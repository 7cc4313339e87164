"""Latent-attention decoder with routed and shared experts (the DeepSeek-V3
layer family: ``model_type`` ``deepseek_v3``).

Block: ``x <- x + attn(rms(x))``, ``x <- x + ffn(rms(x))``, a final RMSNorm
and an untied ``lm_head``.  The first ``first_k_dense_replace`` layers' ffn
is a dense SwiGLU MLP (``mlp``); every later layer's is the expert layer
(``moe``: ``incubate/distributed/models/moe DroplessMoELayer``).

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
from ..incubate.distributed.models.moe import DroplessMoELayer, SwiGLUMLP
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer_base import ParamAttr
from ..ops.registry import op
from .llama import _rope_tables as rope_tables      # cos, sin [T, D/2]


class MlaMoeConfig:
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


def apply_rope(x, cos, sin, interleave):
    """``x [..., T, N, D]`` rotated by ``cos``/``sin [T, D/2]``.
    ``interleave``: the published layout keeps a pair in neighbouring
    lanes; it is de-interleaved into halves first, and the result stays in
    halves (q and k are permuted alike, so the scores are those of the
    pairwise rotation)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


@op("mla_expand_qkv")
def _expand_qkv(q, kv_b, k_rope, cos, sin, *, nope, interleave):
    """``q [B, T, N, nope + rope]``, ``kv_b [B, T, N, nope + v]``, ``k_rope
    [B, T, rope]`` -> q, k ``[B, T, N, nope + rope]`` and v ``[B, T, N,
    v]`` with the rotary parts rotated and ``k_rope`` given to every head."""
    q_rot = apply_rope(q[..., nope:], cos, sin, interleave)
    k_rot = apply_rope(k_rope[:, :, None, :], cos, sin, interleave)
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [kv_b[..., :nope],
         jnp.broadcast_to(k_rot, q.shape[:3] + k_rot.shape[3:])], axis=-1)
    return q, k, kv_b[..., nope:]


def _linear(d_in, d_out, std):
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


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


class MlaMoeDecoderLayer(nn.Layer):
    """One block.  Returns ``(x, tokens_per_expert)``; a dense layer's
    count is an empty array, so every layer has the same outputs."""

    def __init__(self, config, layer_idx):
        super().__init__()
        c = config
        std = c.initializer_range
        out_std = std / math.sqrt(2 * c.num_hidden_layers)
        self.ln_1 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.attn = MLAttention(c)
        self.ln_2 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        if layer_idx < c.first_k_dense_replace:
            self.mlp = SwiGLUMLP(c.hidden_size, c.intermediate_size, std,
                                 out_std)
            self.moe = None
        else:
            self.mlp = None
            self.moe = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.n_shared_experts,
                c.routed_scaling_factor, c.norm_topk_prob,
                c.num_local_experts, c.expert_offset, std, out_std)

    def forward(self, x):
        x = x + self.attn(self.ln_1(x))
        if self.moe is None:
            return x + self.mlp(self.ln_2(x)), \
                Tensor(jnp.zeros((0,), jnp.int32))
        x = x + self.moe(self.ln_2(x))
        return x, self.moe.tokens_per_expert


class MlaMoeModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))
        self.layers = nn.LayerList([
            MlaMoeDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.ln_f = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.tokens_per_expert = None

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        counts = []
        for layer in self.layers:
            x, c = layer(x)
            if layer.moe is not None:
                counts.append(c._data if isinstance(c, Tensor) else c)
        self.tokens_per_expert = jnp.stack(counts) if counts else None
        return self.ln_f(x)


class MlaMoeForCausalLM(nn.Layer):
    """``forward`` returns logits over the vocabulary slice held, ``loss``
    is the shifted-label cross entropy, as ``GPTForCausalLM``'s."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = MlaMoeModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size,
                               config.initializer_range)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, logits, labels):
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        return F.cross_entropy(
            shift_logits.reshape([-1, logits.shape[-1]]),
            shift_labels.reshape([-1]))

    def step_counters(self):
        """What the last forward counted, for ``jit.TrainStep`` to hand
        back beside the loss (``docs/PROFILER.md``):
        ``moe_tokens_per_expert`` int32 ``[expert layers, local experts]``,
        the tokens each expert held here received.  Their sum is the
        assignments served here; none is ever dropped."""
        counts = self.model.tokens_per_expert
        return {} if counts is None else {"moe_tokens_per_expert": counts}


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
