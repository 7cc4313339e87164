"""Decoder whose layers mix sliding-window and full attention over grouped
KV heads, with a per-head output gate and softmax-routed experts beside a
shared one (``model_type`` ``laguna``: Laguna-S-2.1,
huggingface.co/poolside/Laguna-S-2.1 ``config.json``).

The decoder itself is the shell of ``models/moe_decoder.py``, shared with
``models/mla_moe.py``; this file gives a layer its attention, which
DIFFERS BY LAYER (``layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters``), and says which layers are dense
(``mlp_layer_types``).  With ``a = rms(x)`` ``[T, H]`` and ``n`` the
layer's q heads over ``num_key_value_heads`` kv heads of ``head_dim``:

    q = W_q a -> [T, n, D];  k = W_k a, v = W_v a -> [T, kv, D]
    g = sigmoid(W_g a) -> [T, n]                  (``gating: per-head``)
    rotary, rotate-half, on the leading ``partial_rotary_factor * D`` dims
      sliding_attention: base 10,000, plain, all of D
      full_attention:    base 500,000, YaRN (:func:`yarn_inv_freq`), half of
                         D, cos and sin times ``attention_factor``
    o_h = softmax(causal(q_h . k_{h // (n / kv)} / sqrt(D))) v_{h // (n / kv)}
      sliding_attention: query t sees keys j with 0 <= t - j < sliding_window
    attn = concat_h(g[:, h] * o_h) W_o

K and V go to ``F.scaled_dot_product_attention`` at their own head count
with ``window=``: on the TPU the flash kernels read kv head ``h // group``
in place and visit only the key blocks a window can see
(``ops/pallas/attention_kernel.py``); elsewhere the XLA composition
computes the same function.

The expert layer is ``DroplessMoELayer`` with ``score_func="softmax"``:
softmax over all ``num_experts`` in float32, the ``num_experts_per_tok``
largest, weights normed over them and times ``moe_routed_scaling_factor``,
applied to the experts' outputs; one shared expert, added ungated.

What the source's config names and does not spell out is set by the
convention of the family its keys come from (``chipbench/configs/laguna-
s-2.1-train-l5-ep32.json`` ``assumed``): softmax scores, the headwise
sigmoid gate of arXiv:2505.06708 on the head's output before ``W_o``,
rotate-half and not interleaved, no q/k norm.  (:class:`GroupedGatedAttention`
has a q/k norm, position ids and a block-diffusion mask as options, off
here: ``models/sdar.py`` turns them on and the gate off.)

Scopes inside ``attn`` (``docs/PROFILER.md``): ``attn_window`` or
``attn_full`` (``attn_blockdiff`` under that mask) round the whole of a
layer's attention, by its kind, ``attn_gate`` round the gate's matmul and
product, ``qk_norm`` round the q/k norms where a model has them.  This file trains; it
has no decode path.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..ops import pallas
from ..ops.registry import op
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM, linear

FULL, WINDOW = "full_attention", "sliding_attention"

# the source's rope_parameters, as its config.json has them
LAGUNA_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000,
             "partial_rotary_factor": 1},
}


def yarn_correction_range(dim, base, original_max, beta_fast, beta_slow):
    """``(low, high)``: the rotary pair indices between which YaRN blends
    interpolated and extrapolated frequencies, as the public ``rope_type:
    yarn`` initialisation computes them: the index whose wavelength makes
    ``beta`` turns over the original context is ``dim * ln(original_max /
    (beta * 2 pi)) / (2 ln base)``; floor for ``beta_fast``, ceiling for
    ``beta_slow``, clipped to ``[0, dim - 1]``."""
    def index(beta):
        return dim * math.log(original_max / (beta * 2 * math.pi)) \
            / (2 * math.log(base))
    return (max(math.floor(index(beta_fast)), 0),
            min(math.ceil(index(beta_slow)), dim - 1))


def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """The ``dim / 2`` YaRN frequencies: ``f_i = base ** (-2i / dim)``
    blended ``f_i * (1 - m_i) / factor + f_i * m_i``, ``m_i = 1 - clip((i -
    low) / (high - low), 0, 1)`` (fast pairs keep their frequency, slow
    ones are interpolated by ``factor``)."""
    f = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    low, high = yarn_correction_range(dim, base, original_max, beta_fast,
                                      beta_slow)
    span = (high - low) or 0.001
    m = 1.0 - np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
    return f * (1.0 - m) / factor + f * m


def rope_tables(head_dim, seq, params, positions=None):
    """``(cos, sin) [seq, rot / 2]`` float32 and ``rot``, the leading dims
    of a head that are rotated, from one entry of ``rope_parameters``;
    ``positions [seq]``: each row's position id (``0 .. seq - 1`` unless
    given).  ``params`` ``None`` is a layer WITHOUT a position encoding:
    no tables and nothing rotated, ``(None, None, 0)``."""
    if params is None:
        return None, None, 0
    rot = int(head_dim * params.get("partial_rotary_factor", 1))
    base = float(params["rope_theta"])
    kind = params.get("rope_type", "default")
    if kind == "yarn":
        inv = yarn_inv_freq(rot, base, params["factor"],
                            params["original_max_position_embeddings"],
                            params["beta_fast"], params["beta_slow"])
        scale = params.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(params["factor"]) + 1.0
    elif kind == "default":
        inv = 1.0 / (base ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
        scale = 1.0
    else:
        raise NotImplementedError(f"rope_type {kind!r}")
    if positions is None:
        positions = np.arange(seq)
    ang = np.outer(np.asarray(positions, np.float64).reshape(seq), inv)
    return ((np.cos(ang) * scale).astype(np.float32),
            (np.sin(ang) * scale).astype(np.float32), rot)


def _rotate_half(x, cos, sin):
    """``x [B, T, N, D]``: the leading ``2 * cos.shape[-1]`` dims rotated
    (halves ``x1 | x2`` -> ``x1 cos - x2 sin | x2 cos + x1 sin``), the
    rest passed through; float32 inside, ``x``'s dtype out."""
    dt = x.dtype
    d2 = cos.shape[-1]
    x1 = x[..., :d2].astype(jnp.float32)
    x2 = x[..., d2:2 * d2].astype(jnp.float32)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [(x1 * c - x2 * s).astype(dt), (x2 * c + x1 * s).astype(dt),
         x[..., 2 * d2:]], axis=-1)


@op("partial_rope")
def _rope(q, k, cos, sin):
    return _rotate_half(q, cos, sin), _rotate_half(k, cos, sin)


@op("headwise_rms_norm")
def _head_norm(x, weight, eps):
    """``x [B, T, N, D]``: RMSNorm over a head's ``D`` dims times ``weight
    [D]``, one gain vector for all heads; statistics and product in float32,
    ``x``'s dtype out."""
    a = x.astype(jnp.float32)
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                          + eps)
    return (a * weight.astype(jnp.float32)).astype(x.dtype)


@op("headwise_gate")
def _gate(out, g):
    """``out [B, T, N, D]`` times ``sigmoid(g) [B, T, N]``, a scalar a
    head and token; the sigmoid in float32.

    The product is formed on the view ``[B, T / 8, 8, N, D]``: the flash
    kernels write ``out`` where ``o_proj`` reads it, ``[B, T, N * D]`` in
    (8, 128) tiles, and that view keeps a tile's eight rows together, so it
    is the array as it lies.  On the plain ``[B, T, N, D]`` view the
    compiler lays the gate's broadcast out whole and copies ``out`` and its
    gradient between two layouts (the v5e compiler's account of the
    laguna cell's step, PR 35: 5.9 GB more through HBM a step)."""
    gate = jax.nn.sigmoid(g.astype(jnp.float32)).astype(out.dtype)
    b, t, n, d = out.shape
    if t % 8:
        return out * gate[..., None]
    return (out.reshape(b, t // 8, 8, n, d)
            * gate.reshape(b, t // 8, 8, n, 1)).reshape(b, t, n, d)


class LagunaConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them.  ``num_experts`` is
    the router's width; ``num_local_experts`` of them, from
    ``expert_offset`` on, are held (all by default).  The per-layer lists
    are as long as ``num_hidden_layers``."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
                 num_attention_heads_per_layer=(4, 6, 6, 6, 4),
                 layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
                 mlp_layer_types=("dense", "sparse", "sparse", "sparse",
                                  "sparse"),
                 sliding_window=8, rope_parameters=None,
                 intermediate_size=256, moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, num_experts=16,
                 num_experts_per_tok=3, norm_topk_prob=True,
                 moe_routed_scaling_factor=2.5, gating="per-head",
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 num_local_experts=None, expert_offset=0):
        for name, per_layer in (
                ("num_attention_heads_per_layer",
                 num_attention_heads_per_layer),
                ("layer_types", layer_types),
                ("mlp_layer_types", mlp_layer_types)):
            if len(per_layer) != num_hidden_layers:
                raise ValueError(f"{name} has {len(per_layer)} entries for "
                                 f"{num_hidden_layers} layers")
        if gating != "per-head":
            raise NotImplementedError(f"gating {gating!r}: per-head only")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.num_attention_heads_per_layer = tuple(
            num_attention_heads_per_layer)
        self.layer_types = tuple(layer_types)
        self.mlp_layer_types = tuple(mlp_layer_types)
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters or LAGUNA_ROPE
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.num_experts = num_experts                # the router's width
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.num_local_experts = num_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    def make_attention(self, layer_idx):
        kind = self.layer_types[layer_idx]
        if kind not in (FULL, WINDOW):
            raise ValueError(f"layer type {kind!r}")
        return GroupedGatedAttention(
            self.hidden_size, self.num_attention_heads_per_layer[layer_idx],
            self.num_key_value_heads, self.head_dim,
            self.rope_parameters[kind], self.initializer_range, self.out_std,
            window=self.sliding_window if kind == WINDOW else None)

    def make_ffn(self, layer_idx):
        if self.mlp_layer_types[layer_idx] == "dense":
            return self.dense_mlp(self.intermediate_size)
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise NotImplementedError(
                "the shared expert is whole routed-expert widths wide here")
        return self.expert_layer(
            self.moe_intermediate_size, self.num_experts,
            self.num_experts_per_tok,
            self.shared_expert_intermediate_size
            // self.moe_intermediate_size,
            self.moe_routed_scaling_factor, score_func="softmax")


class GroupedGatedAttention(nn.Layer):
    """One layer's attention over grouped kv heads: its mask (causal,
    causal under a sliding ``window``, or ``block_diffusion``), its own q
    head count over the shared kv heads, its rotary table, and the per-head
    sigmoid gate on the heads' outputs.  Three options, as the families
    need them (``models/sdar.py`` sets all three; off, the layer is
    laguna's):

    - ``gate=False``: no ``g_proj``, the heads' outputs go on as they are;
    - ``qk_norm_eps``: q and k are RMS-normed head by head before the
      rotation, each under ONE gain ``[head_dim]`` shared by the layer's
      heads (``q_norm``, ``k_norm``; scope ``qk_norm``);
    - ``block_diffusion`` ``B``: the row is ``[noised ; clean]``, two copies
      of ``L = T / 2`` positions whose position ids both count ``0 .. L -
      1``, under the block-diffusion mask (``ops.pallas
      .block_diffusion_mask``) and not the causal one; after a forward
      ``pairs`` is ``(scored, needed)``, the (query, key) pairs one row and
      head's scores were formed for, by the path taken, and the pairs the
      mask holds.

    The whole of the layer's attention runs under a scope that says its
    mask: ``attn_window``, ``attn_blockdiff`` or ``attn_full``."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 rope_params, std, out_std, window=None, gate=True,
                 qk_norm_eps=None, block_diffusion=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} q heads over {num_kv_heads} kv "
                             f"heads")
        if window is not None and block_diffusion is not None:
            raise ValueError("a window and a block-diffusion mask exclude "
                             "each other")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window = head_dim, window
        self.block_diffusion, self.qk_norm_eps = block_diffusion, qk_norm_eps
        self.pairs = None
        self._rope_params = rope_params
        self._tables = {}
        n, kv, d, h = num_heads, num_kv_heads, head_dim, hidden_size
        self.q_proj = linear(h, n * d, std)
        self.k_proj = linear(h, kv * d, std)
        self.v_proj = linear(h, kv * d, std)
        self.g_proj = linear(h, n, std) if gate else None
        self.o_proj = linear(n * d, h, out_std)
        self.q_norm = self.k_norm = None
        if qk_norm_eps is not None:
            self.q_norm = nn.RMSNorm(d, epsilon=qk_norm_eps)
            self.k_norm = nn.RMSNorm(d, epsilon=qk_norm_eps)

    def rope(self, seq, positions=None):
        """cos, sin ``[seq, rot / 2]`` for the position ids ``positions
        [seq]`` (host integers; ``0 .. seq - 1`` unless given).  Host
        arrays: a step that is traced bakes them in as constants."""
        key = seq if positions is None else (seq, bytes(positions))
        if key not in self._tables:
            self._tables[key] = rope_tables(self.head_dim, seq,
                                            self._rope_params, positions)[:2]
        return self._tables[key]

    def forward(self, x):
        b, t, _ = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        block = self.block_diffusion
        scope = "attn_window" if self.window is not None else \
            "attn_full" if block is None else "attn_blockdiff"
        with jax.named_scope(scope):
            q = self.q_proj(x).reshape([b, t, n, d])
            k = self.k_proj(x).reshape([b, t, kv, d])
            v = self.v_proj(x).reshape([b, t, kv, d])
            if self.q_norm is not None:
                with jax.named_scope("qk_norm"):
                    q = _head_norm(q, self.q_norm.weight, self.qk_norm_eps)
                    k = _head_norm(k, self.k_norm.weight, self.qk_norm_eps)
            # both halves of a block-diffusion row count their own positions
            cos, sin = self.rope(t, None if block is None else np.tile(
                np.arange(t // 2, dtype=np.int32), 2))
            if cos is not None:
                q, k = _rope(q, k, Tensor(jnp.asarray(cos)),
                             Tensor(jnp.asarray(sin)))
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=block is None, window=self.window,
                block_diffusion=block)
            if block is not None:
                self.pairs = pallas.blockdiff_pairs(t, d, n, kv, block)
            if self.g_proj is not None:
                with jax.named_scope("attn_gate"):
                    out = _gate(out, self.g_proj(x))
            return self.o_proj(out.reshape([b, t, n * d]))


class LagunaForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over
    :class:`GroupedGatedAttention`."""


def laguna_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return LagunaForCausalLM(LagunaConfig(**kw))


def laguna_s_2_1(**kw):
    """Laguna-S-2.1 as its ``config.json`` states it
    (huggingface.co/poolside/Laguna-S-2.1): 48 layers of hidden 3072, a
    full-attention layer with 48 q heads every fourth and window layers
    (512 keys) with 72 between, all over 8 kv heads of 128; one dense
    layer of 12,288, then 256 experts of 1,024, ten a token, beside one
    shared expert.  Keyword arguments override (depth, the experts held):
    ``num_hidden_layers=n`` keeps the first ``n`` entries of the per-layer
    lists."""
    layers = int(kw.get("num_hidden_layers", 48))
    kinds = [FULL if i % 4 == 0 else WINDOW for i in range(layers)]
    cfg = dict(
        vocab_size=100352, hidden_size=3072, num_hidden_layers=layers,
        num_key_value_heads=8, head_dim=128,
        num_attention_heads_per_layer=[48 if k == FULL else 72
                                       for k in kinds],
        layer_types=kinds,
        mlp_layer_types=["dense" if i == 0 else "sparse"
                         for i in range(layers)],
        sliding_window=512, rope_parameters=LAGUNA_ROPE,
        intermediate_size=12288, moe_intermediate_size=1024,
        shared_expert_intermediate_size=1024, num_experts=256,
        num_experts_per_tok=10, norm_topk_prob=True,
        moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6)
    cfg.update(kw)
    return LagunaForCausalLM(LagunaConfig(**cfg))
