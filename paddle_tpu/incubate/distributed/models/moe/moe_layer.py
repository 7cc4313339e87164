"""MoELayer — expert-parallel mixture of experts.

Reference: MoELayer (python/paddle/incubate/distributed/models/moe/
moe_layer.py:261) dispatching via global_scatter/global_gather collective ops
(paddle/fluid/operators/collective/global_scatter_op.cc).

TPU redesign: experts' weights are STACKED on a leading expert dim tagged
with mesh axis 'ep'; dispatch/combine are einsums against the gate's dense
[S, E, C] tensors.  Under pjit with an 'ep' axis, GSPMD turns the
dispatch einsum into exactly the all-to-all that global_scatter performs —
no index plumbing, and the expert FFN runs as one batched matmul on the MXU.
``global_scatter``/``global_gather`` are also provided directly (shard_map
all-to-all) for API parity.

Two dispatches, and why (ROADMAP D9).  The dense einsum above stays for an
``ep`` mesh axis under GSPMD: the einsum IS the exchange there, at the
price of a capacity and of dropped tokens.  The dropless dispatch
(``dropless.py``: sort by expert, a grouped matmul over ragged groups, a
weighted gather back) drops nothing and is told which experts it holds;
it has no exchange yet, so it serves one chip's share of an
expert-parallel layer.  ``MoELayer(dropless=True)`` takes it with the
gate's own choice (``gate.route``: its top-k and jitter, no capacity, so a
``capacity_factor`` is refused there); :class:`DroplessMoELayer` is the
routed-and-shared experts layer of the DeepSeek-V3 family built on it.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .....core.tensor import Tensor
from .....nn import functional as F
from .....nn.initializer import Normal, Constant
from .....nn.layer_base import Layer
from .....ops.registry import op
from . import dropless as _dl
from .gate import GShardGate, NaiveGate, SwitchGate

_GATES = {"gshard": GShardGate, "switch": SwitchGate, "naive": NaiveGate}


@op("moe_forward")
def _moe_forward(x2d, wg, w1, b1, w2, b2, *, gate, jitter_key=None,
                 activation="gelu"):
    """x2d: [S, H]; wg: [H, E]; w1: [E, H, F]; w2: [E, F, H].

    Returns (out [S, H], aux_loss scalar).
    """
    logits = x2d.astype(jnp.float32) @ wg.astype(jnp.float32)
    g = gate(logits, jitter_key=jitter_key)
    combine, dispatch = g["combine"], g["dispatch"]
    # dispatch: [S,E,C] x [S,H] -> [E,C,H]  (the global_scatter analog)
    xd = jnp.einsum("sec,sh->ech", dispatch.astype(x2d.dtype), x2d)
    h = jnp.einsum("ech,ehf->ecf", xd, w1) + b1[:, None, :]
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "silu": jax.nn.silu}[activation]
    h = act(h)
    eo = jnp.einsum("ecf,efh->ech", h, w2) + b2[:, None, :]
    # combine: [S,E,C] x [E,C,H] -> [S,H]  (the global_gather analog)
    out = jnp.einsum("sec,ech->sh", combine.astype(eo.dtype), eo)
    return out, g["aux_loss"]


@op("moe_forward_dropless")
def _moe_forward_dropless(x2d, wg, w1, b1, w2, b2, *, gate, jitter_key=None,
                          activation="gelu"):
    """The same experts behind the dropless dispatch, routed by the
    gate's own choice without its capacity (``gate.route``).  Returns
    (out, aux)."""
    e = wg.shape[1]
    logits = x2d.astype(jnp.float32) @ wg.astype(jnp.float32)
    idx, w, aux = gate.route(logits, jitter_key=jitter_key)
    order, inverse, counts = _dl.sort_by_expert(idx, 0, e)
    expert_of_row = jnp.repeat(jnp.arange(e), counts,
                               total_repeat_length=order.shape[0])
    xs = _dl.dispatch(x2d, order, inverse, counts)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "silu": jax.nn.silu}[activation]
    h = act(_dl.grouped_matmul(xs, w1, counts) + b1[expert_of_row])
    eo = _dl.grouped_matmul(h, w2, counts) + b2[expert_of_row]
    return _dl.combine(eo, w, order, inverse, counts), aux


class MoELayer(Layer):
    """Expert-parallel FFN block.

    >>> moe = MoELayer(d_model=64, d_hidden=256, num_experts=8, gate="gshard")
    >>> y = moe(x)           # x: [B, T, d_model]
    >>> loss = task_loss + 0.01 * moe.l_aux
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 top_k=None, capacity_factor=None, activation="gelu",
                 group=None, recompute_interval=0, name=None,
                 dropless=False):
        super().__init__()
        # dropless: no capacity, no dropped token (one chip; the dense
        # path is the one GSPMD turns into the ``ep`` exchange)
        self.dropless = dropless
        if dropless and capacity_factor is not None:
            raise ValueError(
                "MoELayer(dropless=True) has no capacity and drops no "
                f"token: capacity_factor={capacity_factor} would be ignored")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.activation = activation
        if isinstance(gate, str):
            cls = _GATES[gate]
            kw = {}
            if top_k is not None:
                kw["top_k"] = top_k
            if capacity_factor is not None:
                kw["capacity_factor"] = capacity_factor
            self.gate = cls(d_model, num_experts, **kw)
        else:
            self.gate = gate
        if dropless and not hasattr(self.gate, "route"):
            raise ValueError(
                "MoELayer(dropless=True) routes by gate.route(logits), "
                f"which {type(self.gate).__name__} does not define")
        init = Normal(0.0, 0.02)
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), default_initializer=init)
        self.w1 = self.create_parameter((num_experts, d_model, d_hidden),
                                        default_initializer=init)
        self.b1 = self.create_parameter((num_experts, d_hidden),
                                        default_initializer=Constant(0.0))
        self.w2 = self.create_parameter((num_experts, d_hidden, d_model),
                                        default_initializer=init)
        self.b2 = self.create_parameter((num_experts, d_model),
                                        default_initializer=Constant(0.0))
        # expert-parallel sharding metadata: stacked expert dim over 'ep'
        for p_ in (self.w1, self.b1, self.w2, self.b2):
            p_.mesh_axes = ("ep",) + (None,) * (len(p_.shape) - 1)
            p_.expert = True  # MoE-aware grad clip groups by this
        self.l_aux = None

    def forward(self, x):
        shape = x.shape
        x2d = x.reshape([-1, self.d_model])
        jitter_key = None
        if self.training and getattr(self.gate, "jitter_eps", 0.0):
            from .....framework.random import get_rng_key
            jitter_key = get_rng_key()
        forward = _moe_forward_dropless if self.dropless else _moe_forward
        out, aux = forward(
            x2d, self.gate_weight, self.w1, self.b1, self.w2, self.b2,
            gate=self.gate, jitter_key=jitter_key,
            activation=self.activation)
        self.l_aux = aux
        return out.reshape(shape)


# ------------------------------------- routed and shared experts, dropless --

class TopKRouter(Layer):
    """Float32 scores over ALL ``num_experts`` (the published width,
    whatever part of them this chip holds), the ``top_k`` largest, weights
    normed over the chosen and scaled.  ``score_func``:

    - ``"sigmoid"``: the ``noaux_tc`` router of the DeepSeek-V3 family:
      sigmoid scores, the largest of score + bias.
      ``e_score_correction_bias`` is a buffer (the published training
      moves it outside the gradient);
    - ``"softmax"``: the softmax-routed family (``norm_topk_prob`` beside a
      routed scaling factor): softmax over all the experts, the largest;
      no bias, no buffer.

    One matrix is the whole of this router.  :class:`StateMlpRouter` is the
    other kind: an MLP over a state that one layer's router hands to the
    next, softmax scores WITH a selection bias, top-1 by the probability
    itself."""

    def __init__(self, d_model, num_experts, top_k, scale=1.0,
                 norm_topk_prob=True, init_std=0.02, score_func="sigmoid"):
        super().__init__()
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {score_func!r}: sigmoid or softmax")
        self.top_k, self.scale = top_k, scale
        self.norm_topk_prob, self.score_func = norm_topk_prob, score_func
        self.weight = self.create_parameter(
            (d_model, num_experts), default_initializer=Normal(0.0, init_std))
        if score_func == "sigmoid":
            self.register_buffer(
                "e_score_correction_bias",
                Tensor(jnp.zeros((num_experts,), jnp.float32)))

    def forward(self, x2d):
        bias = self.e_score_correction_bias \
            if self.score_func == "sigmoid" else None
        return _route(x2d, self.weight, bias, top_k=self.top_k,
                      scale=self.scale, norm_topk=self.norm_topk_prob)


@op("moe_route_topk")
def _route(x2d, wg, bias, *, top_k, scale, norm_topk):
    """The one routing op: sigmoid scores where a selection ``bias`` comes
    with them, softmax where none does."""
    logits = jnp.matmul(x2d.astype(jnp.float32), wg.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if bias is None:
        return _dl.route_softmax_topk(logits, top_k, scale, norm_topk)
    return _dl.route_sigmoid_topk(logits, bias, top_k, scale, norm_topk)


@op("moe_routed_experts_dropless")
def _routed_experts(rows2d, idx, weights, w_in, w_out, *, expert_offset,
                    buckets, body="swiglu"):
    """The routed block (``dropless.routed_experts``) on ``rows2d [S, K]``
    -> ``(out [S, K], counts, rows_buffered)``: the tokens each expert held
    here received, and the rows of the one of ``buckets`` the buffers took
    this step."""
    num_local = w_in.shape[0]
    with jax.named_scope("dispatch"):
        counts = _dl.group_sizes(
            _dl.expert_keys(idx, expert_offset, num_local), num_local)
        rows = jnp.asarray(buckets, jnp.int32)[_dl.bucket_of(counts, buckets)]
    out = _dl.routed_experts(rows2d, weights, w_in, w_out, idx, expert_offset,
                             buckets, body)
    return out, counts, rows


class StateMlpRouter(Layer):
    """The router of the ``zaya`` family (``models/zaya.py``): an MLP over a
    ``state_size``-wide router state that carries a term from the layer
    BEFORE.  With ``x [S, H]`` the expert layer's input and ``r_prev [S,
    state_size]`` the state the previous layer's router made (zeros into
    the first):

        r = x W_down + gamma * r_prev                      (handed on as r)
        z = W_3 gelu(W_2 gelu(W_1 rms(r) + c_1) + c_2) + c_3
        p = softmax(z) over ALL num_experts;  the top_k largest of p + bias;
        weights p at the chosen, normed over them only with norm_topk_prob

    ``W_down`` is a plain projection in the model's dtype (its sum and its
    result float32); everything from ``r`` on is float32, parameters too
    (``amp_keep_float32``: ``gamma``, the norm's gain, the MLP; exact
    ``gelu``).  ``e_score_correction_bias`` is a zero buffer outside the
    gradient, as :class:`TopKRouter`'s.  At ``top_k`` 1 pass
    ``norm_topk_prob=False``: the weight is then the probability itself and
    the router has a gradient (normed, it is the constant 1).  Gradients
    reach the earlier layers' routers through ``r_prev``.

    ``forward(x2d, state)`` -> ``(idx, weights, r)``.  Scopes inside the
    layer's ``router``: ``router_down``, ``router_mlp``."""

    def __init__(self, d_model, num_experts, top_k, state_size, scale=1.0,
                 norm_topk_prob=False, init_std=0.02, epsilon=1e-5):
        super().__init__()
        self.top_k, self.scale = top_k, scale
        self.norm_topk_prob, self.epsilon = norm_topk_prob, epsilon
        self.state_size = state_size
        self.down = _linear(d_model, state_size, init_std)
        self.state_gain = self.create_parameter(
            (state_size,), default_initializer=Constant(0.5))
        self.norm_weight = self.create_parameter(
            (state_size,), default_initializer=Constant(1.0))
        widths = (state_size, state_size, state_size, num_experts)
        for i, (d_in, d_out) in enumerate(zip(widths, widths[1:]), start=1):
            setattr(self, f"fc{i}_weight", self.create_parameter(
                (d_in, d_out), default_initializer=Normal(0.0, init_std)))
            setattr(self, f"fc{i}_bias", self.create_parameter(
                (d_out,), default_initializer=Constant(0.0)))
        for name, p_ in self.named_parameters():
            if not name.startswith("down."):
                p_.amp_keep_float32 = True
        self.register_buffer(
            "e_score_correction_bias",
            Tensor(jnp.zeros((num_experts,), jnp.float32)))

    def forward(self, x2d, state):
        return _route_state_mlp(
            x2d, state, self.down.weight, self.state_gain, self.norm_weight,
            self.fc1_weight, self.fc1_bias, self.fc2_weight, self.fc2_bias,
            self.fc3_weight, self.fc3_bias, self.e_score_correction_bias,
            top_k=self.top_k, scale=self.scale,
            norm_topk=self.norm_topk_prob, eps=self.epsilon)


@op("moe_route_state_mlp")
def _route_state_mlp(x2d, state, down, gain, norm_weight, w1, b1, w2, b2, w3,
                     b3, bias, *, top_k, scale, norm_topk, eps):
    """:class:`StateMlpRouter`'s ``(idx, weights, r)``."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    with jax.named_scope("router_down"):
        r = jnp.matmul(x2d, down, preferred_element_type=f32) \
            + gain.astype(f32) * state.astype(f32)
    with jax.named_scope("router_mlp"):
        a = r * lax.rsqrt(jnp.mean(jnp.square(r), axis=-1, keepdims=True)
                          + eps) * norm_weight.astype(f32)
        for w, b in ((w1, b1), (w2, b2)):
            a = jax.nn.gelu(jnp.matmul(a, w.astype(f32), precision=hi)
                            + b.astype(f32), approximate=False)
        z = jnp.matmul(a, w3.astype(f32), precision=hi) + b3.astype(f32)
        idx, weights = _dl.route_softmax_topk(z, top_k, scale, norm_topk,
                                              bias)
    return idx, weights, r


def _linear(d_in, d_out, std):
    from .....nn.common import Linear
    from .....nn.layer_base import ParamAttr

    return Linear(d_in, d_out, bias_attr=False,
                  weight_attr=ParamAttr(initializer=Normal(0.0, std)))


class GroupedExperts(Layer):
    """The experts held here, stacked: the first matrix ``[G, K, 2I]``
    (gate | up, held as ``gate_up``) under the gated bodies ``swiglu`` and
    ``reglu``, ``[G, K, I]`` (held as ``up``) under ``relu2``; ``down [G, I,
    K]``.  ``K`` is the
    width of the rows the experts work on.  It holds them and no more: the
    layer's routed block multiplies them (``dropless.routed_experts``)."""

    def __init__(self, num_local, d_rows, d_expert, init_std=0.02,
                 down_std=None, body="swiglu"):
        super().__init__()
        self.body = body
        in_width = _dl.BODIES[body][1]
        self._in_name = "gate_up" if in_width == 2 else "up"
        setattr(self, self._in_name, self.create_parameter(
            (num_local, d_rows, in_width * d_expert),
            default_initializer=Normal(0.0, init_std)))
        self.down = self.create_parameter(
            (num_local, d_expert, d_rows),
            default_initializer=Normal(0.0, down_std or init_std))
        for p_ in (self.w_in, self.down):
            p_.mesh_axes = ("ep", None, None)
            p_.expert = True

    @property
    def w_in(self):
        return getattr(self, self._in_name)


class SwiGLUMLP(Layer):
    """``down(silu(gate(x)) * up(x))``, gate | up packed in one matmul, no
    bias: the dense layers' MLP and the shared expert."""

    def __init__(self, d_model, d_hidden, init_std=0.02, down_std=None):
        super().__init__()
        self.gate_up = _linear(d_model, 2 * d_hidden, init_std)
        self.down = _linear(d_hidden, d_model, down_std or init_std)
        self._hidden = d_hidden

    def forward(self, x):
        from .....incubate.nn.functional import swiglu

        gu = self.gate_up(x)
        return self.down(swiglu(gu[..., :self._hidden],
                                gu[..., self._hidden:]))


class Relu2MLP(Layer):
    """``down(relu(up(x)) ** 2)``, no gate, no bias: the shared expert of
    the ``relu2`` family."""

    def __init__(self, d_model, d_hidden, init_std=0.02, down_std=None):
        super().__init__()
        self.up = _linear(d_model, d_hidden, init_std)
        self.down = _linear(d_hidden, d_model, down_std or init_std)

    def forward(self, x):
        h = F.relu(self.up(x))
        return self.down(h * h)


_MLPS = {"swiglu": SwiGLUMLP, "relu2": Relu2MLP}


class DroplessMoELayer(Layer):
    """Routed and shared experts: ``sum_i w_i E_i(x) + S(x)``.

    ``num_experts`` is the router's width; ``num_local_experts`` of them,
    from ``expert_offset`` on, live here (all of them by default).  The
    layer routes over all, norms over the chosen ``top_k`` and computes
    the part its own experts give: what the absent experts would add is
    left out, and a token none of whose experts is local gets the shared
    expert only.  No token is dropped.  After a forward,
    ``tokens_per_expert`` holds the tokens each local expert received
    (int32 ``[num_local_experts]``) and ``rows_buffered`` (an int32
    scalar) the rows the routed block's buffers had: the smallest of
    ``dropless.row_buckets``, which follow the share of the router's width
    that is held here, that holds those tokens.  Both stay on the device;
    the worst case's rows mean the fallback ran.

    ``body`` is the experts' (``dropless.BODIES``: ``swiglu`` and ``reglu``
    gated, ``relu2`` not), routed and shared alike (no shared expert is
    built for ``reglu``).  With ``d_latent`` the routed
    experts work in a LATENT: ``latent_down [d_model, d_latent]`` before
    the dispatch and ``latent_up [d_latent, d_model]`` after the combine,
    so the rows that are sorted, gathered and summed are ``d_latent`` wide;
    the router and the shared expert read ``x`` itself.  ``d_shared`` is
    the shared expert's own width (``num_shared_experts * d_expert``
    without it).  ``bucket_headroom`` (an attribute, 2): the small bucket's
    rows over the rows expected here (``dropless.row_buckets``); a trainer
    whose router is NOT balanced sets it on the layer before the first step.

    ``router_state`` (a dict of :class:`StateMlpRouter`'s ``state_size`` and
    ``epsilon``) replaces the one-matrix router
    by the MLP over a carried state: ``forward(x, router_state)`` then takes
    the state the layer before made, ``[..., state_size]`` float32, and
    leaves its own in ``router_state_out`` (the block hands it on as an
    output).

    ``forward(x, router_input=r)``: the ROUTER reads ``r`` (of ``x``'s
    shape) and everything else, the latent and the shared expert too,
    reads ``x`` as before: a family whose router sits before attention
    hands the block's input here (``models/moe_decoder.py``
    ``router_reads_block_input``).  After a forward ``expert_idx`` is the
    router's choice ``[S, k]`` and :meth:`tokens_unserved` counts from it
    the tokens that got nothing from the routed experts here.

    Scopes: ``router``, ``latent_down``, ``dispatch``, ``experts``,
    ``combine``, ``latent_up``, ``shared_experts`` (``docs/PROFILER.md``).
    """

    bucket_headroom = 2

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 num_shared_experts=0, routed_scaling_factor=1.0,
                 norm_topk_prob=True, num_local_experts=None,
                 expert_offset=0, init_std=0.02, down_std=None,
                 score_func="sigmoid", body="swiglu", d_latent=None,
                 d_shared=None, router_state=None):
        super().__init__()
        num_local = num_experts if num_local_experts is None \
            else num_local_experts
        if not 0 <= expert_offset <= num_experts - num_local:
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset + num_local}) "
                f"are not among the router's {num_experts}")
        self.d_model, self.num_experts = d_model, num_experts
        self.num_local_experts, self.expert_offset = num_local, expert_offset
        if router_state is None:
            self.router = TopKRouter(d_model, num_experts, top_k,
                                     routed_scaling_factor, norm_topk_prob,
                                     init_std, score_func)
        else:
            self.router = StateMlpRouter(
                d_model, num_experts, top_k, scale=routed_scaling_factor,
                norm_topk_prob=norm_topk_prob, init_std=init_std,
                **router_state)
        self.router_state_out = None
        # in a latent the residual projection is ``latent_up``, not the
        # experts' own second matrix
        self.latent_down = self.latent_up = None
        if d_latent:
            self.latent_down = _linear(d_model, d_latent, init_std)
        self.experts = GroupedExperts(
            num_local, d_latent or d_model, d_expert, init_std,
            None if d_latent else down_std, body)
        if d_latent:
            self.latent_up = _linear(d_latent, d_model,
                                     down_std or init_std)
        if d_shared is None:
            d_shared = num_shared_experts * d_expert
        self.shared_experts = _MLPS[body](
            d_model, d_shared, init_std, down_std) if d_shared else None
        self.tokens_per_expert = self.rows_buffered = self.expert_idx = None

    def forward(self, x, router_state=None, router_input=None):
        shape = x.shape
        x2d = x.reshape([-1, self.d_model])
        r2d = x2d if router_input is None \
            else router_input.reshape([-1, self.d_model])
        if router_state is None:
            idx, weights = self.router(r2d)
        else:
            idx, weights, made = self.router(
                r2d, router_state.reshape([-1, self.router.state_size]))
            self.router_state_out = made.reshape(
                list(router_state.shape))
        self.expert_idx = idx
        rows = x2d if self.latent_down is None else self.latent_down(x2d)
        out, self.tokens_per_expert, self.rows_buffered = _routed_experts(
            rows, idx, weights, self.experts.w_in, self.experts.down,
            expert_offset=self.expert_offset,
            buckets=_dl.row_buckets(*idx.shape, self.num_local_experts,
                                    self.num_experts, self.bucket_headroom),
            body=self.experts.body)
        if self.latent_up is not None:
            out = self.latent_up(out)
        if self.shared_experts is not None:
            out = out + self.shared_experts(x2d)
        return out.reshape(shape)

    def tokens_unserved(self):
        """int32 scalar, on the device: the tokens of the last forward NONE
        of whose chosen experts is held here.  Without a shared expert such
        a token leaves the layer with exactly nothing.  Formed only where
        somebody asks (scope ``dispatch``): a step that does not ask traces
        what it traced before."""
        idx = getattr(self.expert_idx, "_data", self.expert_idx)
        with jax.named_scope("dispatch"):
            local = idx - self.expert_offset
            elsewhere = (local < 0) | (local >= self.num_local_experts)
            return jnp.sum(jnp.all(elsewhere, axis=1), dtype=jnp.int32)


# ----------------------------- global_scatter / global_gather parity ------

def global_scatter(x, local_count, global_count, group=None):
    """API-parity all-to-all token exchange over the expert group
    (reference global_scatter_op.cc semantics).  x: [S, H] already ordered
    by destination rank with per-rank counts; implemented as
    lax.all_to_all inside a shard_map over the group's axis."""
    from .....distributed.group import _ensure_default_group

    g = group or _ensure_default_group()
    # the tiled all_to_all below exchanges equal-size per-rank chunks; the
    # reference op supports ragged counts, which this path does not
    for counts in (local_count, global_count):
        if counts is not None:
            arr = np.asarray(counts)
            if arr.size and not (arr == arr.flat[0]).all():
                raise NotImplementedError(
                    "global_scatter/global_gather require uniform per-rank "
                    f"counts on TPU (got {arr.tolist()}); use MoELayer's "
                    "capacity-based dense dispatch for ragged routing")

    def run(xv):
        return lax.all_to_all(xv.reshape(g.nranks, -1, xv.shape[-1]),
                              g.axis, split_axis=0, concat_axis=0,
                              tiled=False).reshape(-1, xv.shape[-1])

    data = x._data if isinstance(x, Tensor) else x
    out = jax.shard_map(run, mesh=g.mesh, in_specs=P(g.axis),
                        out_specs=P(g.axis))(data)
    return Tensor(out) if isinstance(x, Tensor) else out


def global_gather(x, local_count, global_count, group=None):
    """Inverse of global_scatter (reference global_gather_op.cc)."""
    return global_scatter(x, global_count, local_count, group=group)
