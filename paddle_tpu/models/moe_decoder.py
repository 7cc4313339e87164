"""The decoder shell the expert models share (``models/mla_moe.py``,
``models/laguna.py``): a pre-norm residual block, the stack, a final
RMSNorm, an untied ``lm_head`` over whatever slice of the vocabulary is
held, the shifted-label loss and the step's counters.

    x <- x + attn(rms(x));  x <- x + ffn(rms(x));  logits = W_head rms(x)

What differs between the families is what a LAYER is made of, and the
shell asks the model's config for it, layer by layer:

- ``config.make_attention(layer_idx)``: the layer's attention module
  (``MLAttention``; ``GroupedGatedAttention`` with the layer's kind, head
  count and rotary table), ``[B, T, H] -> [B, T, H]``;
- ``config.make_ffn(layer_idx)``: a dense ``SwiGLUMLP`` (held as ``mlp``)
  or the expert layer ``DroplessMoELayer`` (held as ``moe``).

One chip's share of an expert-parallel layer is stated by
``num_local_experts`` and ``expert_offset`` (see ``DroplessMoELayer``);
``vocab_size`` is whatever slice of the vocabulary is held.

A decoder layer hands its expert counters on as OUTPUTS, so that
``jit.TrainStep(remat=...)`` can rematerialise each layer in the backward
pass.  Scopes: ``embeddings`` / ``layers.i`` / ``ln_1`` / ``attn`` /
``ln_2`` / ``mlp`` | ``moe`` / ``ln_f`` / ``lm_head`` (``docs/PROFILER.md``).
"""

import math

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models.moe import DroplessMoELayer, SwiGLUMLP
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer_base import ParamAttr


def linear(d_in, d_out, std):
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


class MoeDecoderConfig:
    """What the shell reads of a config: ``vocab_size``, ``hidden_size``,
    ``num_hidden_layers``, ``rms_norm_eps``, ``initializer_range``,
    ``norm_topk_prob``, ``num_local_experts``, ``expert_offset``, and the
    two factories."""

    @property
    def out_std(self):
        """The two residual projections' (``o_proj``, ``down``)."""
        return self.initializer_range / math.sqrt(2 * self.num_hidden_layers)

    def make_attention(self, layer_idx):
        raise NotImplementedError

    def make_ffn(self, layer_idx):
        raise NotImplementedError

    def dense_mlp(self, width):
        return SwiGLUMLP(self.hidden_size, width, self.initializer_range,
                         self.out_std)

    def expert_layer(self, width, router_experts, top_k, shared_experts,
                     scale, score_func="sigmoid"):
        """``router_experts`` is the router's width; the experts held
        here are ``num_local_experts`` from ``expert_offset`` on."""
        return DroplessMoELayer(
            self.hidden_size, width, router_experts, top_k, shared_experts,
            scale, self.norm_topk_prob, self.num_local_experts,
            self.expert_offset, self.initializer_range, self.out_std,
            score_func)


class MoeDecoderLayer(nn.Layer):
    """One block.  Returns ``(x, tokens_per_expert, rows_buffered)``; a
    dense layer's counters are empty arrays, so every layer has the same
    outputs."""

    def __init__(self, config, layer_idx):
        super().__init__()
        c = config
        self.ln_1 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.attn = c.make_attention(layer_idx)
        self.ln_2 = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        ffn = c.make_ffn(layer_idx)
        is_moe = isinstance(ffn, DroplessMoELayer)
        self.mlp = None if is_moe else ffn
        self.moe = ffn if is_moe else None

    def forward(self, x):
        x = x + self.attn(self.ln_1(x))
        if self.moe is None:
            none = Tensor(jnp.zeros((0,), jnp.int32))
            return x + self.mlp(self.ln_2(x)), none, none
        x = x + self.moe(self.ln_2(x))
        return x, self.moe.tokens_per_expert, self.moe.rows_buffered


class MoeDecoderModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))
        self.layers = nn.LayerList([
            MoeDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.ln_f = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.tokens_per_expert = self.rows_buffered = None

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        counters = []
        for layer in self.layers:
            x, *made = layer(x)
            if layer.moe is not None:
                counters.append([c._data if isinstance(c, Tensor) else c
                                 for c in made])
        self.tokens_per_expert, self.rows_buffered = \
            [jnp.stack(c) for c in zip(*counters)] or (None, None)
        return self.ln_f(x)


class MoeDecoderForCausalLM(nn.Layer):
    """``forward`` returns logits over the vocabulary slice held, ``loss``
    is the shifted-label cross entropy, as ``GPTForCausalLM``'s."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = MoeDecoderModel(config)
        self.lm_head = linear(config.hidden_size, config.vocab_size,
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
        assignments served here; none is ever dropped.
        ``moe_rows_buffered`` int32 ``[expert layers]``, the rows of the
        bucket each layer's buffers took (``dropless.row_buckets``): at
        least that sum; the worst case's rows mean the fallback ran."""
        counts = self.model.tokens_per_expert
        return {} if counts is None else {
            "moe_tokens_per_expert": counts,
            "moe_rows_buffered": self.model.rows_buffered}
