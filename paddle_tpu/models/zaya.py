"""Expert decoder whose attention lives in a compressed, convolved latent
(``model_type`` ``zaya``: ZAYA1-8B, huggingface.co/Zyphra/ZAYA1-8B
``config.json``; Compressed Convolutional Attention, Zyphra 2025, and the
ZAYA1 technical report; the layer equations are ISSUE 47's, from the
config's keys and those two descriptions).

The decoder is the shell of ``models/moe_decoder.py`` with its three
``zaya`` options on (a router state carried from block to block, scaled
residual adds, a head tied to the embedding).  With ``H`` the hidden size,
``n`` q heads over ``kv`` kv heads of ``D`` (``g = n / kv``; ``n D`` is
HALF of ``H`` at the published sizes: attention runs in a compressed
latent), ``r_prev [T, S]`` the router state of the block before:

    a      = CCA(rms(x; g_1))
    x'     = (s1r * x  + b1r) + (s1o * a + b1o)
    m, r   = MoE(rms(x'; g_2), r_prev)
    x_next = (s2r * x' + b2r) + (s2o * m + b2o)        r goes on as r_prev

CCA(h), :class:`CompressedConvAttention`:

    q~ = h W_q [T, n, D];  k~ = h W_k [T, kv, D]                 (no bias)
    v: kv heads 0 .. kv/2 - 1 read h_t, the other half h_{t-1} (zero at
       t = 0): ``v_proj`` once, the second half of its result shifted one
       step in time (``F.time_shift``; the shift commutes with the
       projection)
    two causal convolutions over time on q~ and on k~, one after the other,
    positions before the row's first reading as zero:
       conv0, depthwise, ``cca_time0`` taps (``F.causal_conv1d``)
       conv1, ``cca_time1`` taps, FULL over a head's D channels and none
       across heads (``F.causal_conv1d_heads``, ``[heads, taps, D, D]``)
    -> q-, k-
    q^[h] = q-[h] + (q~[h] + k~[h // g]) / 2
    k^[j] = k-[j] + (mean_{h // g = j} q~[h] + k~[j]) / 2
    q^ <- sqrt(D) q^ / |q^|;  k^ <- tau_j sqrt(D) k^ / |k^|    float32
       statistics; ``tau`` a learned float32 temperature a kv head
    rotate-half rotary on the leading ``partial_rotary_factor * D`` dims
    o = softmax(q^ k^T / sqrt(D) + causal) v;   CCA = o W_o   [n D -> H]

K and V go to ``F.scaled_dot_product_attention`` at their own head count
(the flash kernels on the TPU, both operands groups in place).  Everything
between the projections and that call (the value shift, the convolutions,
the q-k mean, the norms, the rotation) is ONE op, :func:`_mix`, under the
scope ``cca_mix``, and ``ops.pallas.cca_mix`` places it.  On the TPU, at
shapes ``ops/pallas/cca_mix_kernel.py supports`` takes (the published ones:
heads of 128 lanes, q heads in whole groups over an even number of kv heads,
rows whole tiles of 16), that is the ``cca_mix_fwd`` / ``cca_mix_bwd``
kernels: a program holds a block of rows of ``[T, heads D]`` as the
projections leave it, a head is its own 128 lanes, everything between the
bfloat16 operands and the bfloat16 results is float32 in VMEM with conv1's
matmuls on the MXU, the results are where the flash kernels read them in
place, and the backward keeps q~ and k~ alone.  Elsewhere (off the TPU, the
tiny test config's heads of 16, a GSPMD mesh) the XLA composition
:func:`_mix_composed` runs: the ops named above, one after the other.

MoE(h, r_prev): ``DroplessMoELayer`` routed by ``StateMlpRouter``
(``incubate/distributed/models/moe/moe_layer.py``): ``r = h W_d + gamma *
r_prev``, a three-layer float32 MLP over ``rms(r)``, softmax over ALL
``num_experts``, the largest of ``p + bias`` (a zero buffer outside the
gradient), weight ``p[e*]`` NOT renormalised (top-1); gated ``silu`` experts
of ``moe_intermediate_size``, no shared expert.

What the source's config does not spell out is the benchmark
configuration's to state (``chipbench/configs/zaya1-8b-train-l6-ep2.json``
``assumed``).  ``tau``, the residual scales and everything of the router
from ``r`` on are ``amp_keep_float32`` (a bfloat16 value at 1 or 0.5 never
moves under a rate of 6e-5).

Scopes (``docs/PROFILER.md``): inside ``attn``, ``attn_cca`` round the whole
of a layer's attention and ``cca_mix`` inside it; ``residual_scale`` round
the scaled adds; inside ``moe``'s ``router``, ``router_down`` and
``router_mlp``.  Counters: the experts' two and ``router_state_rms``.  This
file trains; serving (a latent KV cache with the convolutions' and the
shift's last token beside it) is not built.
"""

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..ops.manipulation import concat
from ..ops.registry import op
from .laguna import _rope, rope_tables
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM, linear


@op("cca_qk_mean_norm")
def _qk_mean_norm(q_conv, k_conv, q_lat, k_lat, tau, eps):
    """Steps 3 and 4 of CCA on ``[B, T, heads, D]``: the mean of the
    PRE-convolution latents added to both convolved ones (a q head takes its
    kv head's ``k~``, a kv head the mean of its group's ``q~``), then the L2
    norm a head and position times ``sqrt(D)`` (an RMS norm with no gain),
    ``k`` times its kv head's temperature ``tau``.  Float32 inside, the
    operands' dtype out."""
    f32 = jnp.float32
    b, t, n, d = q_lat.shape
    kv = k_lat.shape[2]
    q32, k32 = q_lat.astype(f32), k_lat.astype(f32)
    q = q_conv.astype(f32) + 0.5 * (q32 + jnp.repeat(k32, n // kv, axis=2))
    k = k_conv.astype(f32) + 0.5 * (
        jnp.mean(q32.reshape(b, t, kv, n // kv, d), axis=3) + k32)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + eps)

    return (unit(q).astype(q_lat.dtype),
            (unit(k) * tau.astype(f32)[:, None]).astype(k_lat.dtype))


def _mix_composed(q_lat, k_lat, v, q_conv0, q_conv1, k_conv0, k_conv1, tau,
                  cos, sin, eps):
    """:func:`_mix` as the XLA composition, on arrays: steps 2 to 5 and the
    value shift, the ops of the module's docstring one after the other, each
    looked up where it is called (a test that stands in for one of them, as
    the benchmark's planted faults do, is met)."""
    q_lat, k_lat, v, q_conv0, q_conv1, k_conv0, k_conv1, tau, cos, sin = [
        Tensor(a) for a in (q_lat, k_lat, v, q_conv0, q_conv1, k_conv0,
                            k_conv1, tau, cos, sin)]
    b, t, n, d = q_lat.shape
    kv = k_lat.shape[2]

    def convolved(x, conv0, conv1):
        y = F.causal_conv1d(x.reshape([b, t, -1]), conv0)
        return F.causal_conv1d_heads(y.reshape(x.shape), conv1)

    q = convolved(q_lat, q_conv0, q_conv1)
    k = convolved(k_lat, k_conv0, k_conv1)
    q, k = _qk_mean_norm(q, k, q_lat, k_lat, tau, eps)
    q, k = _rope(q, k, cos, sin)
    now, before = v[:, :, :kv // 2], v[:, :, kv // 2:]
    v = concat([now, F.time_shift(before)], axis=2)
    return q._data, k._data, v._data


@op("cca_mix")
def _mix(q_lat, k_lat, v, q_conv0, q_conv1, k_conv0, k_conv1, tau, cos, sin,
         eps):
    """Steps 2 to 5 of CCA and the value shift on ``[B, T, heads, D]``: q^,
    k^, v' for the flash call.  ``ops.pallas.cca_mix`` places the call: on
    the TPU, where ``cca_mix_kernel.supports`` takes the shapes, the
    ``cca_mix_fwd`` / ``cca_mix_bwd`` kernels, whose backward keeps q~ and
    k~ alone; elsewhere (off the TPU; aloud on it, ``KernelFallbackWarning``,
    for other shapes or under a GSPMD mesh) :func:`_mix_composed`,
    differentiated as it stands."""
    from ..ops import pallas

    return pallas.cca_mix(q_lat, k_lat, v, q_conv0, q_conv1, k_conv0,
                          k_conv1, tau, cos, sin, eps)


class ZayaConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them.  ``num_experts`` is
    the router's width; ``num_local_experts`` of them, from
    ``expert_offset`` on, are held (all by default)."""

    residual_scale = True
    tie_word_embeddings = True

    def __init__(self, vocab_size=512, hidden_size=64, num_hidden_layers=3,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
                 rope_theta=5000000, moe_intermediate_size=32,
                 num_experts=4, num_experts_per_tok=1, router_hidden_size=8,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 tie_word_embeddings=True,
                 num_local_experts=None, expert_offset=0):
        if num_key_value_heads % 2:
            raise ValueError("half of the kv heads read the previous token: "
                             f"{num_key_value_heads} is odd")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.cca_time0, self.cca_time1 = cca_time0, cca_time1
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts                # the router's width
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = False     # the weight is the probability
        self.router_state_size = router_hidden_size
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings
        self.num_local_experts = num_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    def make_attention(self, layer_idx):
        return CompressedConvAttention(
            self.hidden_size, self.num_attention_heads,
            self.num_key_value_heads, self.head_dim,
            (self.cca_time0, self.cca_time1),
            {"rope_theta": self.rope_theta,
             "partial_rotary_factor": self.partial_rotary_factor},
            self.initializer_range, self.out_std, self.rms_norm_eps)

    def make_ffn(self, layer_idx):
        return self.expert_layer(
            self.moe_intermediate_size, self.num_experts,
            self.num_experts_per_tok, 0, 1.0, score_func="softmax",
            router_state={"state_size": self.router_state_size,
                          "epsilon": self.rms_norm_eps})


class CompressedConvAttention(nn.Layer):
    """CCA (the module's docstring): ``[B, T, H] -> [B, T, H]`` through ``n``
    q heads over ``kv`` kv heads of ``D`` with ``n D < H``.  ``taps`` are
    the two convolutions' (``cca_time0``, ``cca_time1``)."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim, taps,
                 rope_params, std, out_std, eps):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} q heads over {num_kv_heads} kv "
                             f"heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.eps = head_dim, eps
        self._rope_params, self._tables = rope_params, {}
        n, kv, d, h = num_heads, num_kv_heads, head_dim, hidden_size
        self.q_proj = linear(h, n * d, std)
        self.k_proj = linear(h, kv * d, std)
        self.v_proj = linear(h, kv * d, std)
        self.o_proj = linear(n * d, h, out_std)
        # taps that keep their input's scale, so that the convolved latent
        # and the q-k mean's pre-convolution term weigh alike
        for name, heads in (("q", n), ("k", kv)):
            setattr(self, f"{name}_conv0", self.create_parameter(
                (heads * d, taps[0]),
                default_initializer=Normal(0.0, taps[0] ** -0.5)))
            setattr(self, f"{name}_conv1", self.create_parameter(
                (heads, taps[1], d, d),
                default_initializer=Normal(0.0, (taps[1] * d) ** -0.5)))
        self.temperature = self.create_parameter(
            (kv,), default_initializer=Constant(1.0))
        self.temperature.amp_keep_float32 = True

    def rope(self, seq):
        if seq not in self._tables:
            self._tables[seq] = rope_tables(self.head_dim, seq,
                                            self._rope_params)[:2]
        return self._tables[seq]

    def mix(self, q_lat, k_lat, v):
        """Steps 2 to 5 and the value shift on ``[B, T, heads, D]``."""
        cos, sin = self.rope(q_lat.shape[1])
        return _mix(q_lat, k_lat, v, self.q_conv0, self.q_conv1,
                    self.k_conv0, self.k_conv1, self.temperature,
                    Tensor(jnp.asarray(cos)), Tensor(jnp.asarray(sin)),
                    self.eps)

    def forward(self, x):
        b, t, _ = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("attn_cca"):
            q = self.q_proj(x).reshape([b, t, n, d])
            k = self.k_proj(x).reshape([b, t, kv, d])
            v = self.v_proj(x).reshape([b, t, kv, d])
            with jax.named_scope("cca_mix"):
                q, k, v = self.mix(q, k, v)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            return self.o_proj(out.reshape([b, t, n * d]))


class ZayaForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` over
    :class:`CompressedConvAttention` and the state-routed experts."""


def zaya_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs."""
    return ZayaForCausalLM(ZayaConfig(**kw))


def zaya1_8b(**kw):
    """ZAYA1-8B as its ``config.json`` states it
    (huggingface.co/Zyphra/ZAYA1-8B): 40 layers of hidden 2,048, attention in
    a 1,024-wide latent (8 q heads over 2 kv heads of 128, two 2-tap
    convolutions, half of a head rotated at base 5,000,000), 16 experts of
    2,048, one a token, routed through a 256-wide MLP, vocabulary 262,272
    tied.  Keyword arguments override (depth, the experts held, the
    vocabulary's slice)."""
    cfg = dict(vocab_size=262272, hidden_size=2048, num_hidden_layers=40,
               num_attention_heads=8, num_key_value_heads=2, head_dim=128,
               cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
               rope_theta=5000000, moe_intermediate_size=2048,
               num_experts=16, num_experts_per_tok=1,
               router_hidden_size=256, rms_norm_eps=1e-5,
               tie_word_embeddings=True)
    cfg.update(kw)
    return ZayaForCausalLM(ZayaConfig(**cfg))
