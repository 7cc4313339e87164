"""Hybrid decoder whose layers are a Mamba-2 mixer, a grouped-KV attention
or an expert layer in a latent, each ALONE in its block (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Super-120B-A12B,
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
``config.json``).

The decoder is the shell of ``models/moe_decoder.py`` with blocks of ONE
branch: block ``i`` is ``x <- x + f(rms(x) * g_i)`` and
``hybrid_override_pattern[i]`` says what ``f`` is.  With ``a`` the normed
input ``[T, H]``:

``M``, the Mamba-2 mixer (arXiv:2405.21060; ``nh`` = ``mamba_num_heads``
heads of ``P`` = ``mamba_head_dim``, ``d = nh P``, ``G`` = ``n_groups``
groups of B and C at state ``N`` = ``ssm_state_size``, head ``h`` on group
``h // (nh / G)``):

    [ z | xBC | dt ] = W_in a                  widths d | d + 2 G N | nh
    xBC <- silu(conv(xBC))                     causal, depthwise, ``conv_kernel`` taps, bias
    x | B | C = xBC
    Delta = softplus(dt + dt_bias)             float32
    A = -exp(A_log)
    S[t] = exp(Delta[t] A) S[t-1] + Delta[t] x[t] (outer) B[t];  y[t] = S[t] C[t] + D x[t]
    y <- rms_groups(y * silu(z)) * g_norm      the gate BEFORE the norm; statistics over each of the G groups of d / G
    out = W_out y

  the recurrence in its chunked form, ``F.ssd_scan`` at ``chunk_size``.  On
  the TPU, at shapes ``ops/pallas/ssd_scan_kernel.py supports`` takes (the
  published ones: chunk and state 128, 16 heads of 64 a group), that is the
  ``ssd_scan_fwd`` / ``ssd_scan_bwd`` kernels: a chunk's decay matrices, ``C
  B^T`` and the carried state stay in VMEM, and a block's backward keeps
  x, B, C, the step sizes and the chunks' entering states (float32 ``[T /
  chunk, nh, P, N]``), nothing ``[chunk, chunk]``.  Elsewhere (off the TPU,
  the tiny test config's chunk of 16, a GSPMD mesh) the XLA composition
  ``F._ssd_scan_row`` runs, differentiated as it stands.

  The convolution is ``F.causal_conv1d(..., activation="silu", start=)``,
  once each for x, B and C where they lie in ``W_in``'s result, so that no
  ``[T, d + 2 G N]`` slice is made before it and none of x, B, C after it.  On
  the TPU, at shapes ``ops/pallas/causal_conv_kernel.py supports`` takes (the
  published ones: 8192 | 1024 | 1024 channels at lanes 8192 | 16384 | 17408,
  4 taps), that is the ``causal_conv_fwd`` / ``causal_conv_bwd`` kernels: a
  block of rows crosses once each way in bfloat16, the taps' sum and the
  ``silu`` are float32 in VMEM, and the backward keeps x alone and sums the
  taps' and the bias's gradients inside the kernel.  Elsewhere (off the TPU,
  a width off the 128-lane tiles, a GSPMD mesh) the XLA composition
  ``F._causal_conv1d_silu`` runs on the slice.

  The gated norm is ``_gated_norm(y, W_in's result, g_norm, start=0)``: the
  gate z is read where it lies, the result's first ``d`` lanes.  On the TPU,
  at shapes ``ops/pallas/gated_norm_kernel.py supports`` takes (the published
  ones: 8 groups of 1,024 lanes), that is the ``gated_norm_fwd`` /
  ``gated_norm_bwd`` kernels: a program holds one group's lanes of a block of
  rows, so the view ``[T, G, d / G]`` is never formed, everything between
  the bfloat16 operands and the bfloat16 result is float32 in VMEM, and the
  backward keeps y and z alone.  Elsewhere (off the TPU, the tiny test
  config's groups of 32 lanes, a GSPMD mesh) the XLA composition
  ``_gated_norm_composed`` runs on the slice.

``*``, attention: ``num_attention_heads`` q heads over
``num_key_value_heads`` kv heads of ``head_dim``, causal, scale ``head_dim
** -0.5``; NO rotary, no q/k norm, no gate (the public ``nemotron_h``
attention applies none).  K and V go to ``F.scaled_dot_product_attention``
at their own head count (the flash kernels on the TPU).

``E``, the expert layer in a latent (``DroplessMoELayer`` with ``body=
"relu2"``, ``d_latent``, ``d_shared``): sigmoid scores in float32 over ALL
``n_routed_experts``, the ``num_experts_per_tok`` largest of score + bias,
weights normed over the chosen and times ``routed_scaling_factor``;

    l = W_dn a                                              H -> moe_latent_size
    routed = W_up sum_{e chosen, held here} w_e W2_e relu(W1_e l)^2
    out = routed + W2_s relu(W1_s a)^2                      the shared expert, on a itself

What the source's config does not spell out is listed, with its reason, in
``chipbench/configs/nemotron-3-super-120b-a12b-train-l11-ep64.json``
``assumed``.  ``A_log``, ``D`` and ``dt_bias`` (three numbers a head) are
marked ``amp_keep_float32``: ``amp.decorate`` O2 leaves them in float32, as
the public implementations keep them (a bfloat16 ``A_log`` near 4 moves in
steps of 0.03, so under a learning rate of 6e-5 only head 0's, which
starts at 0, would ever move).

Scopes (``docs/PROFILER.md``): ``layers.i`` / ``ln_1`` / ``mamba``
(``in_proj``, ``mamba_conv``, ``mamba_ssd``, ``gated_norm``, ``out_proj``)
| ``attn`` | ``moe`` (``router``, ``latent_down``, ``dispatch``,
``experts``, ``combine``, ``latent_up``, ``shared_experts``).  This file
trains; it has no decode path (no carried state between calls, no cache).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import functional as F
from ..nn.initializer import Assign, Constant, Uniform
from ..ops.registry import op
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM, linear

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


class NemotronHConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them.  ``n_routed_experts``
    is the router's width; ``num_local_experts`` of them, from
    ``expert_offset`` on, are held (all by default).  The pattern is as
    long as ``num_hidden_layers``."""

    branches_per_layer = 1

    def __init__(self, vocab_size=512, hidden_size=64, num_hidden_layers=11,
                 hybrid_override_pattern="MEMEMEM*EME", mamba_num_heads=8,
                 mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                 conv_kernel=4, chunk_size=16, use_conv_bias=True,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 n_routed_experts=16, num_experts_per_tok=4,
                 moe_intermediate_size=32, moe_latent_size=16,
                 moe_shared_expert_intermediate_size=64,
                 routed_scaling_factor=5.0, norm_topk_prob=True,
                 mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
                 initializer_range=0.02, time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=1e-4,
                 num_local_experts=None, expert_offset=0):
        if len(hybrid_override_pattern) != num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern has {len(hybrid_override_pattern)} "
                f"letters for {num_hidden_layers} layers")
        unknown = set(hybrid_override_pattern) - {MAMBA, ATTENTION, EXPERTS}
        if unknown:
            raise ValueError(f"pattern letters {sorted(unknown)}: "
                             f"{MAMBA}, {ATTENTION} or {EXPERTS}")
        if mlp_hidden_act != "relu2":
            raise NotImplementedError(f"mlp_hidden_act {mlp_hidden_act!r}")
        if mamba_num_heads % n_groups or \
                num_attention_heads % num_key_value_heads:
            raise ValueError("heads are shared by whole groups")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_override_pattern = hybrid_override_pattern
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.use_conv_bias = use_conv_bias
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts      # the router's width
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_latent_size = moe_latent_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.layer_norm_epsilon = self.rms_norm_eps = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.time_step_min, self.time_step_max = time_step_min, time_step_max
        self.time_step_floor = time_step_floor
        self.num_local_experts = n_routed_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    def make_mixer(self, layer_idx):
        kind = self.hybrid_override_pattern[layer_idx]
        if kind == MAMBA:
            return "mamba", Mamba2Mixer(self)
        if kind == ATTENTION:
            return "attn", GroupedAttention(self)
        return "moe", self.expert_layer(
            self.moe_intermediate_size, self.n_routed_experts,
            self.num_experts_per_tok, 1, self.routed_scaling_factor,
            body="relu2", d_latent=self.moe_latent_size,
            d_shared=self.moe_shared_expert_intermediate_size)


def _gated_norm_composed(y, z, weight, groups, epsilon, start=0):
    """:func:`_gated_norm` as the XLA composition, on the slice of ``z``."""
    z = z[..., start:start + y.shape[-1]]
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + epsilon)
    return (parts.reshape(g.shape) * weight.astype(jnp.float32)).astype(
        y.dtype)


@op("mamba_gated_rms_norm")
def _gated_norm(y, z, weight, groups, epsilon, start=0):
    """``rms_groups(y * silu(z)) * weight``: the gate BEFORE the norm, the
    statistics over each of ``groups`` equal parts of the last axis;
    float32 inside, ``y``'s dtype out.  The gate is lanes ``start .. start +
    C`` of ``z`` (all of them where z is as wide as y).
    ``ops.pallas.gated_rms_norm`` places the call: on the TPU, where
    ``gated_norm_kernel.supports`` takes the shapes (a group's width and
    ``start`` whole 128-lane tiles and whole groups, rows whole tiles of 16),
    the ``gated_norm_fwd`` / ``gated_norm_bwd`` kernels, which read the gate
    where it lies and whose backward keeps y and z alone; elsewhere (off the
    TPU; aloud on it, ``KernelFallbackWarning``, for other shapes or under a
    GSPMD mesh) :func:`_gated_norm_composed`, differentiated as it stands."""
    from ..ops import pallas

    return pallas.gated_rms_norm(y, z, weight, groups, epsilon, start)


@op("mamba_step_sizes")
def _step_sizes(dt, dt_bias, a_log):
    """``(softplus(dt + dt_bias), -exp(A_log))`` in float32."""
    return (jax.nn.softplus(dt.astype(jnp.float32)
                            + dt_bias.astype(jnp.float32)),
            -jnp.exp(a_log.astype(jnp.float32)))


def dt_bias_init(heads, dt_min, dt_max, floor, seed=0):
    """The inverse softplus of ``exp(U(log dt_min, log dt_max))`` floored
    at ``floor``: a head's first step sizes lie in ``[dt_min, dt_max]``."""
    u = np.random.default_rng(seed).uniform(size=heads)
    dt = np.maximum(np.exp(u * (math.log(dt_max) - math.log(dt_min))
                           + math.log(dt_min)), floor)
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


class Mamba2Mixer(nn.Layer):
    """The ``M`` layer of the module's docstring, ``[B, T, H] -> [B, T,
    H]``."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.heads, self.head_dim = c.mamba_num_heads, c.mamba_head_dim
        self.groups, self.state = c.n_groups, c.ssm_state_size
        self.chunk, self.epsilon = c.chunk_size, c.layer_norm_epsilon
        self.inner = d = self.heads * self.head_dim
        self.conv_dim = d + 2 * self.groups * self.state
        std = c.initializer_range
        self.in_proj = linear(c.hidden_size, d + self.conv_dim + self.heads,
                              std)
        # the public implementation leaves the convolution at its
        # framework's default: uniform in +- 1 / sqrt(taps)
        bound = 1.0 / math.sqrt(c.conv_kernel)
        self.conv_weight = self.create_parameter(
            (self.conv_dim, c.conv_kernel),
            default_initializer=Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            (self.conv_dim,), default_initializer=Uniform(-bound, bound)) \
            if c.use_conv_bias else None
        self.dt_bias = self.create_parameter(
            (self.heads,), default_initializer=Assign(dt_bias_init(
                self.heads, c.time_step_min, c.time_step_max,
                c.time_step_floor)))
        self.A_log = self.create_parameter(
            (self.heads,), default_initializer=Assign(
                np.log(np.arange(1, self.heads + 1, dtype=np.float32))))
        self.D = self.create_parameter(
            (self.heads,), default_initializer=Constant(1.0))
        for small in (self.dt_bias, self.A_log, self.D):
            small.amp_keep_float32 = True   # ``amp.decorate`` leaves them
        self.norm_weight = self.create_parameter(
            (d,), default_initializer=Constant(1.0))
        self.out_proj = linear(d, c.hidden_size, c.out_std)

    def forward(self, a):
        b, t, _ = a.shape
        d, gn = self.inner, self.groups * self.state
        zxbcdt = self.in_proj(a)
        with jax.named_scope("mamba_conv"):
            # x | B | C, each convolved where it lies in the projection's
            # result and written as the scan reads it
            x, b_t, c_t = (
                F.causal_conv1d(
                    zxbcdt, self.conv_weight[lo:hi],
                    None if self.conv_bias is None else self.conv_bias[lo:hi],
                    activation="silu", start=d + lo)
                for lo, hi in ((0, d), (d, d + gn), (d + gn, d + 2 * gn)))
        with jax.named_scope("mamba_ssd"):
            dt, a_head = _step_sizes(zxbcdt[..., d + self.conv_dim:],
                                     self.dt_bias, self.A_log)
            y = F.ssd_scan(
                x.reshape([b, t, self.heads, self.head_dim]), dt, a_head,
                b_t.reshape([b, t, self.groups, self.state]),
                c_t.reshape([b, t, self.groups, self.state]),
                self.D, chunk=self.chunk)
        with jax.named_scope("gated_norm"):
            # the gate z where it lies: the projection's first d lanes
            y = _gated_norm(y.reshape([b, t, d]), zxbcdt, self.norm_weight,
                            groups=self.groups, epsilon=self.epsilon,
                            start=0)
        return self.out_proj(y)


class GroupedAttention(nn.Layer):
    """The ``*`` layer: causal attention of q heads over fewer kv heads, no
    rotary, no gate, ``[B, T, H] -> [B, T, H]``."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.num_heads, self.num_kv_heads = (c.num_attention_heads,
                                             c.num_key_value_heads)
        self.head_dim = c.head_dim
        n, kv, d, h = (self.num_heads, self.num_kv_heads, self.head_dim,
                       c.hidden_size)
        std = c.initializer_range
        self.q_proj = linear(h, n * d, std)
        self.k_proj = linear(h, kv * d, std)
        self.v_proj = linear(h, kv * d, std)
        self.o_proj = linear(n * d, h, c.out_std)

    def forward(self, x):
        b, t, _ = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        out = F.scaled_dot_product_attention(
            self.q_proj(x).reshape([b, t, n, d]),
            self.k_proj(x).reshape([b, t, kv, d]),
            self.v_proj(x).reshape([b, t, kv, d]), is_causal=True)
        return self.o_proj(out.reshape([b, t, n * d]))


class NemotronHForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over blocks of one branch."""


def nemotron_h_tiny(**kw):
    """Test config: every mechanism, in the published pattern's first
    eleven letters, at a size the CPU runs."""
    return NemotronHForCausalLM(NemotronHConfig(**kw))


def nemotron_3_super_120b_a12b(**kw):
    """NVIDIA-Nemotron-3-Super-120B-A12B as its ``config.json`` states it:
    88 blocks of hidden 4096 (40 Mamba-2 mixers of 128 heads x 64 over 8
    groups at state 128, 40 expert layers of 512 experts of 2,688 in a
    1,024-wide latent, 22 a token, beside a shared expert of 5,376; 8
    attention layers of 32 q heads over 2 kv heads of 128).  Keyword
    arguments override (depth, the experts held):
    ``num_hidden_layers=n`` keeps the pattern's first ``n`` letters."""
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    layers = int(kw.get("num_hidden_layers", len(pattern)))
    cfg = dict(
        vocab_size=131072, hidden_size=4096, num_hidden_layers=layers,
        hybrid_override_pattern=pattern[:layers], mamba_num_heads=128,
        mamba_head_dim=64, n_groups=8, ssm_state_size=128, conv_kernel=4,
        chunk_size=128, use_conv_bias=True, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, n_routed_experts=512,
        num_experts_per_tok=22, moe_intermediate_size=2688,
        moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
        routed_scaling_factor=5.0, norm_topk_prob=True,
        layer_norm_epsilon=1e-5)
    cfg.update(kw)
    return NemotronHForCausalLM(NemotronHConfig(**cfg))
