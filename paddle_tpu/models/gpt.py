"""GPT family — the flagship model (BASELINE.md smoke + north-star configs).

Architecture mirrors the reference fleet GPT used in hybrid-parallel tests
(reference test/collective/fleet/hybrid_parallel_mp_model.py et al.): pre-LN
transformer, learned positions, tied LM head.  TPU-first details:
- attention runs through the Pallas flash kernel ([B, T, N, H] layout);
- TP comes from mpu layers' sharding metadata (GSPMD inserts collectives);
- ``functional_decompose()`` splits the net into embed/block/head pure
  functions with per-layer params stacked on a leading axis — the form the
  pipelined SPMD trainer (paddle_tpu.parallel) shards over the 'pp' mesh axis.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.fleet.recompute import PROJECTIONS, tagged
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer_base import ParamAttr
from ..ops.registry import op


@op("gpt_qkv_projection")
def _qkv_by_heads(x, weight, bias):
    """q, k, v ``[b, t, heads, head_dim]`` from the fused projection held
    VIEWED: weight ``[h, 3, heads, head_dim]``, bias ``[3, heads,
    head_dim]`` (``Parameter.mesh_view``: a reshape of the stored column
    order, all of q, then k, then v, each head-major).  With the heads'
    axis over ``mp`` q, k and v leave the matmul sharded by heads, as the
    flash ``shard_map`` takes them, and no collective moves the
    activation, its gradient or the weight; each output column is still
    one full-``h`` dot on one chip.  The product is tagged as the
    column-parallel layers tag theirs (``fleet.recompute.PROJECTIONS``),
    for a rematerialised block to keep."""
    qkv = tagged(jnp.einsum("bth,hcnd->btcnd", x, weight) + bias,
                 PROJECTIONS[2])
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


@op("gpt_cp_attention")
def _cp_attention(q, k, v, mesh=None, axis="sep", mode="ring"):
    """Context-parallel causal attention as a registered op (so the eager
    autograd tape differentiates through the shard_map ring)."""
    from ..distributed.fleet.meta_parallel import context_parallel_attention
    return context_parallel_attention(q, k, v, mesh, axis=axis, mode=mode,
                                      is_causal=True)


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, initializer_range=0.02,
                 layer_norm_epsilon=1e-5, sequence_parallel=False,
                 use_flash_attention=True, cp_mode=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.layer_norm_epsilon = layer_norm_epsilon
        self.sequence_parallel = sequence_parallel
        self.use_flash_attention = use_flash_attention
        # context parallelism over the mesh 'sep' axis: None | 'ring' | 'ulysses'
        self.cp_mode = cp_mode

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


class GPTAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        h = config.hidden_size
        init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        proj_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.num_heads = config.num_attention_heads
        self.head_dim = config.head_dim
        self.qkv = ColumnParallelLinear(h, 3 * h, weight_attr=init,
                                        gather_output=False)
        # a contiguous half of the 3h columns is no set of heads: over a
        # mesh the leaves are held viewed, the heads' axis over mp
        thirds = (3, self.num_heads, self.head_dim)
        self.qkv.weight.mesh_view = ((h,) + thirds, (None, None, "mp", None))
        self.qkv.bias.mesh_view = (thirds, (None, "mp", None))
        self.proj = RowParallelLinear(h, h, weight_attr=proj_init,
                                      input_is_parallel=True)
        self.dropout_p = config.attention_probs_dropout_prob
        self.resid_drop = nn.Dropout(config.hidden_dropout_prob)
        self.cp_mode = config.cp_mode

    def forward(self, x):
        b, t, _ = x.shape
        if self.qkv.weight.ndim == 2:
            # the stored leaf (one chip, eager, the serving engine's source)
            qkv = self.qkv(x)
            qkv = qkv.reshape([b, t, 3, self.num_heads, self.head_dim])
            q, k, v = qkv.unbind(axis=2)
        else:
            # held viewed by a mesh trainer.  One path for both was tried
            # and is not taken: on one chip XLA compiled the head-structured
            # product to a step 1.1% slower (PERF.md section 6, PR 29)
            with jax.named_scope("qkv"):
                q, k, v = _qkv_by_heads(x, self.qkv.weight, self.qkv.bias)
        out = None
        # attention dropout is inactive in eval, so cp only yields to the
        # dense path when dropout would actually be applied
        cp_usable = self.dropout_p == 0.0 or not self.training
        if self.cp_mode and cp_usable:
            from ..distributed.fleet.spmd import current_mesh
            mesh = current_mesh()
            if mesh is not None and "sep" in mesh.axis_names:
                out = _cp_attention(q, k, v, mesh=mesh, axis="sep",
                                    mode=self.cp_mode)
        if out is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 dropout_p=self.dropout_p,
                                                 training=self.training)
        out = out.reshape([b, t, self.num_heads * self.head_dim])
        return self.resid_drop(self.proj(out))


class GPTMLP(nn.Layer):
    def __init__(self, config):
        super().__init__()
        h = config.hidden_size
        init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        proj_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.fc_in = ColumnParallelLinear(h, config.intermediate_size,
                                          weight_attr=init,
                                          gather_output=False)
        self.fc_out = RowParallelLinear(config.intermediate_size, h,
                                        weight_attr=proj_init,
                                        input_is_parallel=True)
        self.drop = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        return self.drop(self.fc_out(F.gelu(self.fc_in(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.sequence_parallel = config.sequence_parallel

    def forward(self, x):
        if self.sequence_parallel:
            # Megatron-style SP: the norm/residual segment lives seq-sharded
            # over the mp group; GSPMD inserts the reduce-scatter/all-gather
            # pair the reference would hand-write (SURVEY §5.7).
            from ..distributed.fleet.meta_parallel import mark_sequence_sharded
            x._data = mark_sequence_sharded(x._data, axis="mp", seq_dim=1)
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return x


class GPTEmbeddings(nn.Layer):
    def __init__(self, config):
        super().__init__()
        init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=init)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=init)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        t = input_ids.shape[-1]
        if position_ids is None:
            from ..ops.creation import arange
            position_ids = arange(t, dtype="int32")
        return self.dropout(self.word_embeddings(input_ids) +
                            self.position_embeddings(position_ids))


class GPTModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.h = nn.LayerList([GPTBlock(config)
                               for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        for block in self.h:
            x = block(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """GPT with tied LM head; ``forward`` returns logits, ``loss`` is the
    shifted-label CE (parallel-CE-compatible under mp sharding)."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)

    def forward(self, input_ids, position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        # tied head: logits = h @ wte^T (sharded over mp vocab dim via GSPMD)
        w = self.gpt.embeddings.word_embeddings.weight
        with jax.named_scope("lm_head"):    # no Layer of its own to name it
            return F.linear(hidden, w.T)

    def loss(self, logits, labels):
        """Causal LM loss: logits[:, :-1] vs labels[:, 1:]."""
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        return F.cross_entropy(
            shift_logits.reshape([-1, logits.shape[-1]]),
            shift_labels.reshape([-1]))

    # ---- functional decomposition for the pipelined SPMD trainer ----
    def functional_decompose(self):
        """Split into (embed/block/head) pure fns + params with per-layer
        block params stacked on axis 0 (the 'pp' sharding axis).

        Returns dict with: params {'embed','blocks','head'}, fns
        (embed_fn, block_fn, head_fn, loss_fn), spec pytrees mapping each
        leaf to mesh-axis names, and ``block_views``: for each block leaf
        whose layer declares a ``mesh_view``, the shape one layer's leaf
        is held in over a mesh and the stacked leaf's mesh axes there
        (``block_fn`` takes such a leaf stored or viewed).
        """
        from ..jit import functional_call

        embed = self.gpt.embeddings
        blocks = list(self.gpt.h)
        template = blocks[0]
        ln_f = self.gpt.ln_f

        embed_params = {k: v._data for k, v in embed.state_dict().items()}
        head_params = {k: v._data for k, v in ln_f.state_dict().items()}
        names = list(template.state_dict().keys())
        stacked = {}
        for name in names:
            stacked[name] = jnp.stack(
                [blk.state_dict()[name]._data for blk in blocks])

        def axes_of(sd, name):
            return getattr(sd[name], "mesh_axes", None)

        embed_specs = {k: axes_of(embed.state_dict(), k) for k in embed_params}
        head_specs = {k: None for k in head_params}
        block_specs, block_views = {}, {}
        tsd = template.state_dict()
        for name in names:
            axes = getattr(tsd[name], "mesh_axes", None) or \
                (None,) * len(tsd[name].shape)
            block_specs[name] = ("pp",) + tuple(axes)
            view = getattr(tsd[name], "mesh_view", None)
            if view is not None:
                block_views[name] = (tuple(view[0]), ("pp",) + tuple(view[1]))

        training = self.training

        def embed_fn(p, input_ids):
            out = functional_call(embed, p, Tensor(input_ids))
            return out

        def block_fn(p, hidden):
            prev_mode = template.training
            if training != prev_mode:
                template.train() if training else template.eval()
            try:
                out = functional_call(template, p, Tensor(hidden))
            finally:
                if training != prev_mode:
                    template.train() if prev_mode else template.eval()
            return out

        def head_fn(p, hidden, embed_p):
            h = functional_call(ln_f, p, Tensor(hidden))
            w = embed_p["word_embeddings.weight"]
            with jax.named_scope("lm_head"):
                return jnp.matmul(h, w.T)

        def loss_fn(logits, labels):
            shift_logits = logits[:, :-1, :].reshape((-1, logits.shape[-1]))
            shift_labels = labels[:, 1:].reshape((-1,))
            loss = F.cross_entropy(Tensor(shift_logits), Tensor(shift_labels))
            return loss._data

        return {
            "params": {"embed": embed_params, "blocks": stacked,
                       "head": head_params},
            "specs": {"embed": embed_specs, "blocks": block_specs,
                      "head": head_specs},
            "block_views": block_views,
            "fns": (embed_fn, block_fn, head_fn, loss_fn),
            "num_layers": len(blocks),
        }

    def load_stacked(self, params):
        """Write trainer params (stacked form) back into the Layer tree."""
        embed_sd = self.gpt.embeddings.state_dict()
        for k, v in params["embed"].items():
            embed_sd[k]._data = v
        head_sd = self.gpt.ln_f.state_dict()
        for k, v in params["head"].items():
            head_sd[k]._data = v
        for i, blk in enumerate(self.gpt.h):
            sd = blk.state_dict()
            for k, v in params["blocks"].items():
                sd[k]._data = v[i]


def gpt_tiny(**kw):
    """Test/dryrun config: a few tiny layers."""
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=4,
               num_attention_heads=4, max_position_embeddings=64,
               hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt_124m(**kw):
    cfg = dict(vocab_size=50304, hidden_size=768, num_layers=12,
               num_attention_heads=12, max_position_embeddings=1024)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt_350m(**kw):
    cfg = dict(vocab_size=50304, hidden_size=1024, num_layers=24,
               num_attention_heads=16, max_position_embeddings=1024)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt_1_3b(**kw):
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
               num_attention_heads=32, max_position_embeddings=2048)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt_6_7b(**kw):
    """The north-star pretrain config (BASELINE.md: Fleet hybrid on v5p)."""
    cfg = dict(vocab_size=50304, hidden_size=4096, num_layers=32,
               num_attention_heads=32, max_position_embeddings=2048)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))
