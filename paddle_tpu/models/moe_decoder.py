"""The decoder shell the expert models share (``models/mla_moe.py``,
``models/laguna.py``, ``models/evabyte.py``, ``models/nemotron_h.py``,
``models/ouro.py``, ``models/sdar.py``, ``models/zaya.py``,
``models/smallthinker.py``):
pre-norm residual blocks, the stack, a final RMSNorm, an ``lm_head`` over
whatever slice of the vocabulary is held (untied, or the embedding itself
under ``tie_word_embeddings``), the shifted-label loss and the step's
counters.  A block has two branches,

    x <- x + attn(rms(x));  x <- x + ffn(rms(x));  logits = W_head rms(x)

or ONE, ``x <- x + mixer(rms(x))``.  What differs between the families is
what a LAYER is made of, and the shell asks the model's config for it,
layer by layer:

- ``config.make_attention(layer_idx)``: the layer's attention module
  (``MLAttention``; ``GroupedGatedAttention`` with the layer's kind, head
  count and rotary table), ``[B, T, H] -> [B, T, H]``;
- ``config.make_ffn(layer_idx)``: a dense ``SwiGLUMLP`` (held as ``mlp``)
  or the expert layer ``DroplessMoELayer`` (held as ``moe``);
- or ``config.make_mixer(layer_idx)`` -> ``(name, module)``: the block's
  ONLY module, held (and scoped) under ``name``: ``mamba``, ``attn`` or
  ``moe`` (the hybrid family, whose pattern gives a layer one of the
  three).  A config that returns nothing there (the default) has the two
  factories above asked, as before.

One chip's share of an expert-parallel layer is stated by
``num_local_experts`` and ``expert_offset`` (see ``DroplessMoELayer``);
``vocab_size`` is whatever slice of the vocabulary is held.

Three options of the shell, each off unless the config says otherwise (a
model that sets none computes what it computed without them):

- ``fp32_skip_add``: the residual stream, and every add into it, in
  float32 whatever the parameters' dtype; a norm's output goes on in the
  parameters' dtype;
- ``norm_add_unit_offset``: an RMSNorm gain is stored as its offset from
  one, ``rms(x) * (1 + g)``, ``g`` starting at 0 (:class:`UnitOffsetRMSNorm`);
- ``num_pred_heads`` ``P > 1``: the head gives ``P`` sets of logits a
  position, ``[B, T, P, V]`` in float32, head ``p`` at position ``t``
  predicting token ``t + 1 + p``; the loss is the mean over the heads of
  each head's mean cross entropy over the positions that have its target.

Two more, off by default too (the looped family, ``models/ouro.py``):

- ``post_branch_norm``: a second RMSNorm on what a branch made, before the
  add, ``x <- x + norm_b(f(norm_a(x)))`` (held as ``ln_1b`` / ``ln_2b``);
- ``total_ut_steps`` ``R > 1``: the WHOLE stack and ``ln_f`` run ``R``
  times over the same parameters, the normed state of one pass the input
  of the next.  Head, cross entropy and exit gate run INSIDE each pass
  (one pass's logits live at a time), and ``forward(ids, labels)`` returns,
  in float32, each pass's per-token cross entropy, the exit distribution
  and its entropy (:meth:`MoeDecoderForCausalLM.looped`); ``loss`` is
  their expectation less ``exit_entropy_beta`` times the entropy.

Three more, off by default too (the ``zaya`` family, ``models/zaya.py``):

- ``router_state_size`` ``S > 0``: a block's expert layer routes through
  an MLP over a ``[B, T, S]`` float32 router state that carries a term from
  the block BEFORE (``StateMlpRouter``): a block then takes ``(x, state)``
  and returns the state its router made as a fourth output, zeros go into
  the first block, and ``router_state_rms`` float32 ``[layers]`` (the RMS of
  the state ENTERING each block) joins the step's counters;
- ``residual_scale``: a residual add becomes ``(s_r * x + b_r) + (s_o *
  f(norm(x)) + b_o)``, four learned vectors ``[H]`` a branch
  (:class:`ResidualScale`, held as ``res_1`` / ``res_2``), the sum in
  float32 and rounded once;
- ``tie_word_embeddings``: no ``lm_head`` matrix; the logits are ``rms(x)
  E^T`` over the embedding, whose gradient is the sum of its two uses.

One more, off by default too (the ``smallthinker`` family,
``models/smallthinker.py``):

- ``router_reads_block_input``: a block's expert layer routes on the
  block's own INPUT, un-normed, as it was before attention, while the
  experts read the normed post-attention stream as ever
  (``DroplessMoELayer.forward(x, router_input=...)``; inside
  ``fleet.recompute`` too, the block's input being the recomputation's);
  the block then counts the tokens none of whose chosen experts is held here
  as one output more, and ``moe_tokens_unserved`` (float32, their MEAN over
  the expert layers) joins the step's counters.

A decoder layer hands its expert counters on as OUTPUTS, so that
``jit.TrainStep(remat=...)`` can rematerialise each layer in the backward
pass.  Scopes: ``embeddings`` / ``layers.i`` / ``ln_1`` / ``attn`` /
``ln_2`` / ``mlp`` | ``moe`` / ``ln_f`` / ``lm_head`` (a tied head's
matmul too); ``residual_scale`` round a scaled add; a block of one
branch ``layers.i`` / ``ln_1`` / ``mamba`` | ``attn`` | ``moe``; the
post-branch norms ``ln_1b`` / ``ln_2b``; in a looped model ``lm_head``,
``loss`` and ``exit_gate`` inside a pass and ``exit_gate`` round the exit
distribution (``docs/PROFILER.md``).
"""

import functools
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..framework import mode
from ..incubate.distributed.models.moe import DroplessMoELayer, SwiGLUMLP
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer_base import ParamAttr, run_as_block
from ..ops.registry import op


def linear(d_in, d_out, std):
    return nn.Linear(d_in, d_out, bias_attr=False,
                     weight_attr=ParamAttr(initializer=Normal(0.0, std)))


class UnitOffsetRMSNorm(nn.Layer):
    """``rms(x) * (1 + weight)``: the gain is held as its offset from one
    (``norm_add_unit_offset``), so that in bfloat16 it keeps, near one, the
    resolution a bfloat16 has near zero.  Statistics and product in
    float32, the result in ``x``'s dtype."""

    def __init__(self, hidden_size, epsilon=1e-6):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=Constant(0.0))

    def forward(self, x):
        out = F.rms_norm(x.astype("float32"), epsilon=self.epsilon) \
            * (self.weight.astype("float32") + 1.0)
        return out.astype(x.dtype)


class MoeDecoderConfig:
    """What the shell reads of a config: ``vocab_size``, ``hidden_size``,
    ``num_hidden_layers``, ``rms_norm_eps``, ``initializer_range``,
    ``norm_topk_prob``, ``num_local_experts``, ``expert_offset``, the
    factories (two for a block of two branches, ``make_mixer`` for a block
    of one), and the nine options of the module's docstring."""

    fp32_skip_add = False
    norm_add_unit_offset = False
    num_pred_heads = 1
    post_branch_norm = False
    total_ut_steps = 1          # passes of the stack over the same weights
    exit_entropy_beta = 0.0     # read where ``total_ut_steps`` > 1
    branches_per_layer = 2      # residual adds a block makes (``out_std``)
    router_state_size = 0       # width of the state a router hands on
    residual_scale = False
    tie_word_embeddings = False
    router_reads_block_input = False    # the router sits before attention

    def make_norm(self):
        cls = UnitOffsetRMSNorm if self.norm_add_unit_offset else nn.RMSNorm
        return cls(self.hidden_size, epsilon=self.rms_norm_eps)

    @property
    def out_std(self):
        """The residual projections' (``o_proj``, ``down``): the range over
        the root of the residual adds the layers HELD make, ``branches_per_
        layer * num_hidden_layers``.  A stack that runs ``total_ut_steps``
        times makes ``R`` times as many adds and the count does NOT grow
        by ``R``: a looped model norms a branch before the add
        (``post_branch_norm``), so the projection's scale does not reach
        the residual stream, and ``ln_f`` norms the state between passes."""
        return self.initializer_range / math.sqrt(
            self.branches_per_layer * self.num_hidden_layers)

    def make_mixer(self, layer_idx):
        """``(name, module)`` of a block of ONE branch; nothing for a block
        of two."""
        return None

    def make_attention(self, layer_idx):
        raise NotImplementedError

    def make_ffn(self, layer_idx):
        raise NotImplementedError

    def dense_mlp(self, width):
        return SwiGLUMLP(self.hidden_size, width, self.initializer_range,
                         self.out_std)

    def expert_layer(self, width, router_experts, top_k, shared_experts,
                     scale, score_func="sigmoid", **body_and_latent):
        """``router_experts`` is the router's width; the experts held
        here are ``num_local_experts`` from ``expert_offset`` on.
        ``body_and_latent``: ``DroplessMoELayer``'s ``body``, ``d_latent``,
        ``d_shared``, ``router_state``."""
        return DroplessMoELayer(
            self.hidden_size, width, router_experts, top_k, shared_experts,
            scale, self.norm_topk_prob, self.num_local_experts,
            self.expert_offset, self.initializer_range, self.out_std,
            score_func, **body_and_latent)


class ResidualScale(nn.Layer):
    """``(skip_scale * x + skip_bias) + (out_scale * made + out_bias)``, four
    vectors ``[H]`` (scales 1, biases 0 at the start): the residual add of
    a family that learns how much of the stream and of the branch goes on
    (``residual_scale``).  The vectors stay float32 under ``amp.decorate``
    (``amp_keep_float32``); float32 inside, ``x``'s dtype out; scope
    ``residual_scale``."""

    def __init__(self, hidden_size):
        super().__init__()
        for name, start in (("skip_scale", 1.0), ("skip_bias", 0.0),
                            ("out_scale", 1.0), ("out_bias", 0.0)):
            setattr(self, name, self.create_parameter(
                (hidden_size,), default_initializer=Constant(start)))
            # a bfloat16 scale at 1 moves in steps of 0.0078: never
            getattr(self, name).amp_keep_float32 = True

    def forward(self, x, made):
        with jax.named_scope("residual_scale"):
            return _scaled_add(x, made, self.skip_scale, self.skip_bias,
                               self.out_scale, self.out_bias)


@op("residual_scaled_add")
def _scaled_add(x, made, skip_scale, skip_bias, out_scale, out_bias):
    f32 = jnp.float32
    return ((skip_scale.astype(f32) * x.astype(f32) + skip_bias.astype(f32))
            + (out_scale.astype(f32) * made.astype(f32)
               + out_bias.astype(f32))).astype(x.dtype)


class MoeDecoderLayer(nn.Layer):
    """One block, of two branches or of one (the module's docstring).
    Returns ``(x, tokens_per_expert, rows_buffered)``; the counters of a
    layer without experts are empty arrays, so every layer has the same
    outputs.  Under ``router_state_size`` it takes ``(x, router_state)``
    and the state its router made is a fourth output.  Under
    ``router_reads_block_input`` the tokens left unserved follow the two
    counters."""

    def __init__(self, config, layer_idx):
        super().__init__()
        c = config
        self.ln_1 = c.make_norm()
        self.ln_1b = c.make_norm() if c.post_branch_norm else None
        self._fp32_skip_add = c.fp32_skip_add
        self._router_reads_input = c.router_reads_block_input
        self._mixer, module = c.make_mixer(layer_idx) or (None, None)
        if module is not None:
            self.ln_2 = self.ln_2b = None
            self.attn = self.mlp = self.moe = None
            setattr(self, self._mixer, module)
            return
        self.attn = c.make_attention(layer_idx)
        self.res_1 = self.res_2 = None
        if c.residual_scale:
            self.res_1 = ResidualScale(c.hidden_size)
            self.res_2 = ResidualScale(c.hidden_size)
        self.ln_2 = c.make_norm()
        self.ln_2b = c.make_norm() if c.post_branch_norm else None
        ffn = c.make_ffn(layer_idx)
        is_moe = isinstance(ffn, DroplessMoELayer)
        self.mlp = None if is_moe else ffn
        self.moe = ffn if is_moe else None

    def _branch(self, x, norm, f, post, add=None):
        """``x + f(norm(x))``, or ``x + post(f(norm(x)))`` with a norm
        after the branch; under ``fp32_skip_add`` the sum in float32 and
        ``f`` on the parameters' dtype; ``add(x, made)`` in the sum's place
        under ``residual_scale``."""
        a = norm(x)
        if self._fp32_skip_add:
            a = a.astype(norm.weight.dtype)
        made = f(a)
        if post is not None:
            made = post(made)
        if self._fp32_skip_add:
            made = made.astype("float32")
        return x + made if add is None else add(x, made)

    def forward(self, x, router_state=None):
        if self._mixer is not None:
            x = self._branch(x, self.ln_1, getattr(self, self._mixer),
                             self.ln_1b)
            if self.moe is None:
                none = Tensor(jnp.zeros((0,), jnp.int32))
                return x, none, none
            return x, self.moe.tokens_per_expert, self.moe.rows_buffered
        entered = x
        x = self._branch(x, self.ln_1, self.attn, self.ln_1b, self.res_1)
        if self.moe is None:
            none = Tensor(jnp.zeros((0,), jnp.int32))
            return self._branch(x, self.ln_2, self.mlp, self.ln_2b,
                                self.res_2), none, none
        moe = self.moe if router_state is None else functools.partial(
            self.moe, router_state=router_state)
        if self._router_reads_input:
            moe = functools.partial(moe, router_input=entered)
        x = self._branch(x, self.ln_2, moe, self.ln_2b, self.res_2)
        made = (x, self.moe.tokens_per_expert, self.moe.rows_buffered)
        if self._router_reads_input:
            with jax.named_scope("moe"):
                made += (Tensor(self.moe.tokens_unserved()),)
        return made if router_state is None \
            else made + (self.moe.router_state_out,)


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
        self.ln_f = config.make_norm()
        self.tokens_per_expert = self.rows_buffered = None
        self.tokens_unserved = self.router_state_rms = None

    def forward(self, input_ids):
        return self.stack(self.embed(input_ids))

    def embed(self, input_ids):
        x = self.embeddings(input_ids)
        if self.config.fp32_skip_add:
            x = x.astype("float32")
        return x

    def stack(self, x):
        """The layers and ``ln_f`` once, on the residual stream ``x``."""
        counters, state, entering = [], None, []
        if self.config.router_state_size:
            state = Tensor(jnp.zeros(
                (*x.shape[:-1], self.config.router_state_size), jnp.float32))
        for layer in self.layers:
            if state is None:
                x, *made = layer(x)
            else:
                with jax.named_scope("router"):
                    entering.append(jnp.sqrt(jnp.mean(jnp.square(
                        state._data))))
                x, *made, state = layer(x, state)
            if layer.moe is not None:
                counters.append([c._data if isinstance(c, Tensor) else c
                                 for c in made])
        self.tokens_per_expert, self.rows_buffered, *more = \
            [jnp.stack(c) for c in zip(*counters)] or (None, None)
        self.tokens_unserved = more[0] if more else None
        self.router_state_rms = jnp.stack(entering) if entering else None
        x = self.ln_f(x)
        if self.config.fp32_skip_add:
            x = x.astype(self.ln_f.weight.dtype)
        return x


@op("looped_exit_gate")
def _exit_gate(h, weight, bias):
    """``sigmoid(w . h + b)`` ``[B, T]``: the product, the sum over the
    hidden width and the sigmoid in float32 whatever the operands' dtype
    (a one-column matmul would round its operands on the MXU)."""
    f32 = jnp.float32
    return jax.nn.sigmoid(
        jnp.sum(h.astype(f32) * weight.astype(f32)[:, 0], axis=-1)
        + bias.astype(f32)[0])


@op("looped_exit_distribution")
def _exit_distribution(lam):
    """``lam [R, B, T]``, pass ``r``'s probability of leaving GIVEN that
    the token is still there (the last pass's is not read) -> ``(p [R, B,
    T], entropy [B, T])``: ``p_r = lam_r prod_{j<r} (1 - lam_j)``, ``p_R``
    what is left; ``H = - sum_r p_r log max(p_r, 1e-30)``."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)      # still there after r
    p = jnp.concatenate([lam[:1], lam[1:-1] * stay[:-1], stay[-1:]], axis=0)
    return p, -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)


class MoeDecoderForCausalLM(nn.Layer):
    """``forward`` returns logits over the vocabulary slice held, ``loss``
    is the shifted-label cross entropy, as ``GPTForCausalLM``'s.  With
    ``num_pred_heads`` ``P > 1``: logits ``[B, T, P, V]`` in float32 and
    :meth:`multi_head_loss`.  With ``total_ut_steps`` ``R > 1``:
    :meth:`looped` and :meth:`looped_loss`."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = MoeDecoderModel(config)
        if config.tie_word_embeddings:
            if config.num_pred_heads > 1 or config.total_ut_steps > 1:
                raise NotImplementedError(
                    "a tied head with several heads or a looped stack is "
                    "not built")
            self.lm_head = None
        else:
            self.lm_head = linear(config.hidden_size,
                                  config.num_pred_heads * config.vocab_size,
                                  config.initializer_range)
        self.pass_loss = self.exit_mass = None
        if config.total_ut_steps > 1:
            if config.fp32_skip_add or config.num_pred_heads > 1 or any(
                    layer.moe is not None for layer in self.model.layers):
                raise NotImplementedError(
                    "a looped stack with a float32 residual, several "
                    "heads or experts (their counters a pass) is not built")
            self.exit_gate = nn.Linear(
                config.hidden_size, 1, weight_attr=ParamAttr(
                    initializer=Normal(0.0, config.initializer_range)))

    def forward(self, input_ids, labels=None):
        if self.config.total_ut_steps > 1:
            return self.looped(input_ids,
                               input_ids if labels is None else labels)
        if self.lm_head is None:
            # tied: the embedding is the head, its gradient the sum of both
            hidden = self.model(input_ids)
            with jax.named_scope("lm_head"):
                return F.linear(hidden, self.model.embeddings.weight.T)
        logits = self.lm_head(self.model(input_ids))
        heads = self.config.num_pred_heads
        if heads == 1:
            return logits
        b, t, _ = logits.shape
        return logits.reshape([b, t, heads, -1]).astype("float32")

    def multi_head_loss(self, logits, labels):
        """``logits [B, T, P, V]``, ``labels [B, T]`` (the ids): head ``p``
        at position ``t`` is held to ``labels[t + 1 + p]``; the mean over
        the heads of each head's mean over its ``B (T - 1 - p)`` targets."""
        b, t, heads, vocab = logits.shape
        ids = labels._data if isinstance(labels, Tensor) else labels
        ahead = jnp.arange(t)[:, None] + 1 + jnp.arange(heads)[None, :]
        target = jnp.where(ahead < t, ids[:, jnp.minimum(ahead, t - 1)],
                           -100)                            # [B, T, P]
        each = F.cross_entropy(logits.reshape([-1, vocab]),
                               Tensor(target.reshape(-1)), reduction="none")
        weight = 1.0 / (heads * b * (t - 1 - jnp.arange(heads)))
        return (each.reshape([b * t, heads])
                * Tensor(weight.astype(jnp.float32))).sum()

    def looped(self, input_ids, labels):
        """The stack ``R`` = ``total_ut_steps`` times over the same
        parameters; ``labels [B, T]`` are the ids (position ``t`` is held to
        ``labels[t + 1]``; ``-100`` there leaves it out).  Returns float32
        ``(l [R, B, T], p [R, B, T], entropy [B, T], weight [B, T])``: pass
        ``r``'s cross entropy a position (0 where it has no target), the
        exit distribution over the passes and its entropy, and ``1 /
        (positions with a target)`` there, 0 elsewhere.

        The passes are ONE ``lax.scan`` of length ``R`` whose body is the
        stack, ``ln_f``, then exit gate, head and cross entropy: the body is
        traced and compiled once, the weights are closed over and the
        scan's transpose sums a weight's ``R`` gradients, in the weight's
        dtype.  Under ``jit.TrainStep(remat=...)`` every block is
        rematerialised as in any model, and so are head and loss of a pass
        (``run_as_block``): a pass saves its blocks' inputs and its normed
        state, its logits live only while it runs.  The body keeps no
        tape: the gradient is the enclosing trace's (``jit.TrainStep``,
        ``jax.grad`` over ``functional_call``)."""
        model, head, gate = self.model, self.lm_head, self.exit_gate
        ids = labels._data if isinstance(labels, Tensor) else labels
        target = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)

        def exit_head_loss(h, target):
            """One pass's ``(l [B, T], lam [B, T])`` of its normed state."""
            with jax.named_scope("exit_gate"):
                lam = _exit_gate(h, gate.weight, gate.bias)
            logits = head(h)
            with jax.named_scope("loss"):
                each = F.cross_entropy(
                    logits.reshape([-1, logits.shape[-1]]),
                    target.reshape([-1]), reduction="none")
            return each.reshape(target.shape), lam

        def one_pass(h, _):
            with mode.grad_enabled(False):
                h = model.stack(Tensor(h))
                each, lam = run_as_block(exit_head_loss, h, Tensor(target))
            return h._data, (each._data, lam._data)

        _, (each, lam) = jax.lax.scan(
            one_pass, model.embed(input_ids)._data, None,
            length=self.config.total_ut_steps)
        with jax.named_scope("exit_gate"):
            p, entropy = _exit_distribution(Tensor(lam))
            valid = (target != -100).astype(jnp.float32)
            weight = valid / jnp.maximum(jnp.sum(valid), 1.0)
            self.pass_loss = jnp.sum(each * weight, axis=(1, 2))
            self.exit_mass = jnp.sum(p._data * weight, axis=(1, 2))
        return Tensor(each), p, entropy, Tensor(weight)

    def looped_loss(self, out):
        """The expected cross entropy under the exit distribution less
        ``exit_entropy_beta`` times its entropy, the mean over the positions
        that have a target."""
        each, p, entropy, weight = out
        expected = (each * p).sum(axis=0)
        return ((expected - entropy * self.config.exit_entropy_beta)
                * weight).sum()

    def loss(self, logits, labels):
        if self.config.total_ut_steps > 1:
            return self.looped_loss(logits)
        if self.config.num_pred_heads > 1:
            return self.multi_head_loss(logits, labels)
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
        least that sum; the worst case's rows mean the fallback ran.
        A looped model (``total_ut_steps`` ``R > 1``): ``ouro_pass_loss``
        float32 ``[R]``, each pass's mean cross entropy, and
        ``ouro_exit_mass`` float32 ``[R]``, the mean over the positions
        with a target of the exit distribution (sums to 1).
        Under ``router_state_size``: ``router_state_rms`` float32
        ``[layers]``, the RMS of the router state ENTERING each block (0
        into the first): whether the carried term grows with depth.
        Under ``router_reads_block_input``: ``moe_tokens_unserved`` float32,
        the tokens none of whose chosen experts is held here, the MEAN over
        the expert layers (over the step's tokens it is a share)."""
        if self.pass_loss is not None:
            return {"ouro_pass_loss": self.pass_loss,
                    "ouro_exit_mass": self.exit_mass}
        counts = self.model.tokens_per_expert
        if counts is None:
            return {}
        counters = {"moe_tokens_per_expert": counts,
                    "moe_rows_buffered": self.model.rows_buffered}
        if self.model.router_state_rms is not None:
            counters["router_state_rms"] = self.model.router_state_rms
        if self.model.tokens_unserved is not None:
            counters["moe_tokens_unserved"] = jnp.mean(
                self.model.tokens_unserved.astype(jnp.float32))
        return counters
