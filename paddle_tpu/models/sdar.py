"""Expert decoder trained to generate by diffusion over blocks (``model_type``
``sdar_moe``: SDAR-30B-A3B-Chat, huggingface.co/JetLM/SDAR-30B-A3B-Chat
``config.json``; the objective is SDAR's, arXiv:2510.06303, in the one-pass
training form of BD3-LMs, arXiv:2503.09573 section 3).

The decoder is the shell of ``models/moe_decoder.py``; a layer's attention is
``models/laguna.py GroupedGatedAttention`` with its gate off and its three
options on, its expert layer ``DroplessMoELayer`` with no shared expert.
What is new is the ROW and the LOSS.  A data row ``x0`` of ``L`` tokens is
cut into blocks of ``B`` = ``block_length``; the feed draws a noise level
``t_b`` a block and masks each of the block's tokens with probability
``t_b``: ``xt_i = MASK if m_i else x0_i``.  The stack runs ONE row of ``2 L``
positions, ``[xt ; x0]``, position ids ``0 .. L - 1`` twice:

    block(x):  a = rms(x; g_1);  q, k, v = W_q a, W_k a, W_v a   (no bias)
               q <- rms_D(q) * g_q,  k <- rms_D(k) * g_k   (head by head, one
                                       gain [D] a layer, float32 statistics)
               rotate-half rotary over all of D at ``rope_theta`` by the
               position id ``i mod L``
               o = softmax(q k^T / sqrt(D) + mask) v;  x <- x + W_o o
               x <- x + moe(rms(x; g_2))
    mask, with blk(i) = (i mod L) // B: query i sees key j iff
               i <  L, j <  L:  blk(i) == blk(j)      (its own block, whole)
               i <  L, j >= L:  blk(j) <  blk(i)      (clean, earlier blocks)
               i >= L, j <  L:  never
               i >= L, j >= L:  blk(j) <= blk(i)      (block-causal)
    moe:       softmax over ALL ``num_experts`` in float32, the
               ``num_experts_per_tok`` largest, weights normed over them
               (``norm_topk_prob``), no scaling factor, no shared expert; an
               expert is a SwiGLU of ``moe_intermediate_size``.  All ``2 L``
               positions are routed.
    logits = W_head rms(x[:L]; g_f)        (the NOISED half alone; the clean
               half's final state feeds nothing, its keys and values do)
    loss   = 1 / (rows L) * sum over masked i of CE(logits_i, x0_i) / t_blk(i)
               (UNSHIFTED: position i predicts x0_i), float32

On the TPU the mask is the flash kernels' (``flash_blockdiff<B>_attention_*``,
``ops/pallas/attention_kernel.py``): no ``[2 L, 2 L]`` array exists.

What the source's config does not give (``block_length``, the noise
schedule, the loss weight, q/k norm, the label shift) is the benchmark
configuration's to state (``chipbench/configs/sdar-30b-a3b-chat-train-l6-ep8
.json`` ``assumed``).  Every layer has experts, as in the published model
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []: anything else is
refused).

Scopes (``docs/PROFILER.md``): ``noise`` round building the ``2 L`` row and
round the loss weights; inside ``attn``, ``attn_blockdiff`` round the whole
of a layer's attention and ``qk_norm`` round the two norms.  Counters
(``step_counters``): the experts' two, ``blockdiff_masked_tokens``,
``blockdiff_pairs_scored`` and ``blockdiff_pairs_needed``.  This file trains;
generation (a step that denoises a block over several passes and commits it
to the cache) is not built.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn import functional as F
from .laguna import GroupedGatedAttention
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM


def _data(x):
    return x._data if isinstance(x, Tensor) else x


class SdarConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them.  ``num_experts`` is
    the router's width; ``num_local_experts`` of them, from
    ``expert_offset`` on, are held (all by default).  ``block_length`` and
    ``mask_token_id`` are the objective's (the config has no key for
    either); the mask token is a row of the vocabulary held."""

    def __init__(self, vocab_size=512, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 intermediate_size=160, moe_intermediate_size=32,
                 num_experts=16, num_experts_per_tok=4, norm_topk_prob=True,
                 decoder_sparse_step=1, mlp_only_layers=(),
                 rope_theta=1000000, rms_norm_eps=1e-6,
                 initializer_range=0.02, block_length=4, mask_token_id=None,
                 num_local_experts=None, expert_offset=0):
        if decoder_sparse_step != 1 or tuple(mlp_only_layers):
            raise NotImplementedError(
                "a dense layer (decoder_sparse_step, mlp_only_layers) is "
                "not built: the published model has experts in every layer")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size        # published, unused
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts                # the router's width
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.block_length = block_length
        self.mask_token_id = vocab_size - 1 if mask_token_id is None \
            else mask_token_id
        self.num_local_experts = num_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    def make_attention(self, layer_idx):
        return GroupedGatedAttention(
            self.hidden_size, self.num_attention_heads,
            self.num_key_value_heads, self.head_dim,
            {"rope_theta": self.rope_theta}, self.initializer_range,
            self.out_std, gate=False, qk_norm_eps=self.rms_norm_eps,
            block_diffusion=self.block_length)

    def make_ffn(self, layer_idx):
        return self.expert_layer(
            self.moe_intermediate_size, self.num_experts,
            self.num_experts_per_tok, 0, 1.0, score_func="softmax")


class SdarForBlockDiffusion(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over the ``[noised ; clean]``
    row: ``forward(ids, noised_ids)`` gives the logits of the noised half,
    ``loss(logits, ids, noised_ids, noise)`` the block-diffusion loss."""

    def __init__(self, config):
        super().__init__(config)
        self.masked_tokens = None

    def forward(self, input_ids, noised_ids):
        """``input_ids`` (the data, ``x0``) and ``noised_ids`` (``xt``),
        both ``[rows, L]`` -> logits ``[rows, L, vocab]`` at the noised
        positions."""
        ids, noised = _data(input_ids), _data(noised_ids)
        seq = ids.shape[1]
        if noised.shape != ids.shape or seq % self.config.block_length:
            raise ValueError(f"ids {ids.shape} and noised ids {noised.shape} "
                             f"are one shape, whole blocks of "
                             f"{self.config.block_length}")
        with jax.named_scope("noise"):
            row = jnp.concatenate([noised, ids], axis=1)
            self.masked_tokens = jnp.sum(
                noised == self.config.mask_token_id, dtype=jnp.int32)
        return self.lm_head(self.model(Tensor(row))[:, :seq])

    def loss(self, logits, input_ids, noised_ids, noise):
        """``noise [rows, L / B]``: each block's ``t``.  The sum over the
        MASKED positions of ``CE(logits_i, ids_i) / t_blk(i)``, unshifted,
        over ``rows L``."""
        ids, noised = _data(input_ids), _data(noised_ids)
        with jax.named_scope("noise"):
            weight = jnp.where(
                noised == self.config.mask_token_id,
                1.0 / jnp.repeat(_data(noise).astype(jnp.float32),
                                 self.config.block_length, axis=1),
                0.0) / ids.size
        each = F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                               Tensor(ids.reshape(-1)), reduction="none")
        return (each * Tensor(weight.reshape(-1))).sum()

    def step_counters(self):
        """The experts' two (``MoeDecoderForCausalLM.step_counters``) and,
        of the last forward: ``blockdiff_masked_tokens`` int32, the loss
        terms of the step (the masked positions of the noised half);
        ``blockdiff_pairs_scored`` and ``blockdiff_pairs_needed`` int32
        ``[layers]``, the (query, key) pairs ONE row and head of a layer's
        attention formed scores for, by the path and block sizes it took,
        and the pairs the mask holds (a step's whole count is these times
        rows and heads: past an int32)."""
        counters = super().step_counters()
        pairs = [layer.attn.pairs for layer in self.model.layers]
        if self.masked_tokens is None or None in pairs:
            return counters
        scored, needed = zip(*pairs)
        return {**counters,
                "blockdiff_masked_tokens": self.masked_tokens,
                "blockdiff_pairs_scored": jnp.asarray(
                    np.array(scored, np.int32)),
                "blockdiff_pairs_needed": jnp.asarray(
                    np.array(needed, np.int32))}


def sdar_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return SdarForBlockDiffusion(SdarConfig(**kw))


def sdar_30b_a3b(**kw):
    """SDAR-30B-A3B-Chat as its ``config.json`` states it
    (huggingface.co/JetLM/SDAR-30B-A3B-Chat): 48 layers of hidden 2048, 32 q
    heads over 4 kv heads of 128, 128 experts of 768 in every layer, eight a
    token, no shared expert, vocabulary 151,936 untied.  ``block_length`` 4
    is the family's released default (the config has no key for it), the
    mask token the vocabulary's last row unless given.  Keyword arguments
    override (depth, the experts held, the vocabulary's slice)."""
    cfg = dict(vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
               num_attention_heads=32, num_key_value_heads=4, head_dim=128,
               intermediate_size=6144, moe_intermediate_size=768,
               num_experts=128, num_experts_per_tok=8, norm_topk_prob=True,
               decoder_sparse_step=1, mlp_only_layers=(),
               rope_theta=1000000, rms_norm_eps=1e-6, block_length=4)
    cfg.update(kw)
    return SdarForBlockDiffusion(SdarConfig(**cfg))
