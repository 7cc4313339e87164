"""Plain reference for an expert decoder whose attention lives in a
compressed, convolved latent (``model_type`` ``zaya``: ZAYA1-8B; the
equations are those of ISSUE 47, from the source's ``config.json``,
Compressed Convolutional Attention (Zyphra 2025) and the ZAYA1 technical
report).  With ``H`` the hidden size, ``n`` q heads over ``kv`` kv heads of
``D`` (``g = n / kv``), ``S`` the router's hidden size, ``rms(x; w) = x /
sqrt(mean(x^2) + eps) * w`` and ``r_prev [T, S]`` the router state of the
layer before (zeros into the first layer held):

    x = E[ids]
    block(x, r_prev):
      h  = rms(x; g_1)
      q~ = h W_q [T, n, D];  k~ = h W_k [T, kv, D]
      v  : the first kv / 2 heads h_t W_v[head], the others h_{t-1} W_v[head]
           (h_{-1} = 0)
      conv0 (depthwise, cca_time0 taps, tap j reads t - (K - 1) + j):
           y_t[c] = sum_j w0[c, j] q~_{t-(K-1)+j}[c]
      conv1 (cca_time1 taps, full over a head's D channels, none across
           heads):  z_t[h, :] = sum_j y_{t-(K-1)+j}[h, :] W1[h, j]
           positions before the row's first read as zero; k~ alike
      q^[h] = z_q[h] + (q~[h] + k~[h // g]) / 2
      k^[j] = z_k[j] + (mean_{h // g = j} q~[h] + k~[j]) / 2
      q^ <- sqrt(D) q^ / |q^|;   k^ <- tau_j sqrt(D) k^ / |k^|
      rotate-half rotary on the leading partial_rotary_factor * D dims
      o  = softmax(q^ k^T / sqrt(D) + causal) v;   a = o W_o
      x' = (s1r * x + b1r) + (s1o * a + b1o)
      b  = rms(x'; g_2)
      r  = b W_d + gamma * r_prev
      z  = W_3 gelu(W_2 gelu(W_1 rms(r; g_r) + c_1) + c_2) + c_3
      p  = softmax(z) over ALL the router's experts;  e* = argmax(p + bias),
           bias 0;  w = p[e*]  (top-1, not renormalised)
      m  = w E_{e*}(b) where e* is HELD HERE, else 0    (gated silu experts)
      x_next = (s2r * x' + b2r) + (s2o * m + b2o);   r goes to the next layer
    logits = rms(x; g_f) E^T                               (the head is TIED)
    loss   = mean over t < T - 1 of CE(logits_t, ids_{t+1})

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no grouped
matmul, no cache; shifts are explicit pads, the convolutions explicit sums
over their taps, the causal mask applied to explicit scores; loss and
gradients by ``jax.grad``.  The expert layer is a loop over the experts
held here, each applied to every position and weighted by what the router
gave it.  It imports nothing of the program; the small pieces the other
families' references already hold (``_rms``, ``_swiglu``, the rotate-half
``_rope``, the per-leaf ``norms``, the cosines, AdamW) are theirs.

**The chip's share.**  ``num_experts`` experts are held here, from
``expert_offset`` on, of the ``router_experts`` the router scores; a token
whose expert is held elsewhere gets nothing from this layer's experts, as
in the program.  An expert's seeded weights depend on its GLOBAL index, so
the shares of one seed are slices of one uncut layer.  ``vocab_size`` is the
slice of the vocabulary held.

Departures that change no value, made so that float32 at 16,384 positions
fits a 16 GB chip: attention runs a head at a time and within a head
``Q_ROWS`` query rows at a time, each recomputed for its backward; the
experts run one after the other, each recomputed; head and loss run
``Q_ROWS`` positions at a time, each recomputed; every layer is recomputed
for its backward.  Departures from a deployment: weights are random from
the seed (``_LAYER``: gains, scales and the temperature ``1 + N(0, std)``,
the router's ``gamma`` ``0.5 + N(0, std)``, biases ``N(0, ...)`` so that a
dropped one shows).  :data:`FLOAT32_LEAVES` are kept in float32 whatever the
parameters' dtype, as the program keeps them (``amp_keep_float32``).

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router from ``r``
on stays float32 there too, as the configuration states it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .evabyte import keyed, norms
from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .laguna import _rope
from .mla_moe import _norm, _rms, _swiglu
from .ouro import _unstacked, change_cosines

Q_ROWS = 2048       # query (and head) rows formed at a time

# leaf -> (shape over the sizes, kind); names are the program's
_RES = {f"res_{i}.{part}": (lambda z: (z["h"],), kind)
        for i in (1, 2)
        for part, kind in (("skip_scale", "f32_gain"),
                           ("skip_bias", "f32_bias"),
                           ("out_scale", "f32_gain"),
                           ("out_bias", "f32_bias"))}
_LAYER = {
    "ln_1.weight": (lambda z: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["d"]), "w"),
    "attn.k_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.v_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.o_proj.weight": (lambda z: (z["n"] * z["d"], z["h"]), "w_out"),
    "attn.q_conv0": (lambda z: (z["n"] * z["d"], z["t0"]), "conv0"),
    "attn.k_conv0": (lambda z: (z["kv"] * z["d"], z["t0"]), "conv0"),
    "attn.q_conv1": (lambda z: (z["n"], z["t1"], z["d"], z["d"]), "conv1"),
    "attn.k_conv1": (lambda z: (z["kv"], z["t1"], z["d"], z["d"]), "conv1"),
    "attn.temperature": (lambda z: (z["kv"],), "f32_gain"),
    **_RES,
    "ln_2.weight": (lambda z: (z["h"],), "gain"),
    "moe.router.down.weight": (lambda z: (z["h"], z["s"]), "w"),
    "moe.router.state_gain": (lambda z: (z["s"],), "f32_half"),
    "moe.router.norm_weight": (lambda z: (z["s"],), "f32_router_gain"),
    "moe.router.fc1_weight": (lambda z: (z["s"], z["s"]), "f32_mlp"),
    "moe.router.fc1_bias": (lambda z: (z["s"],), "f32_router_bias"),
    "moe.router.fc2_weight": (lambda z: (z["s"], z["s"]), "f32_mlp"),
    "moe.router.fc2_bias": (lambda z: (z["s"],), "f32_router_bias"),
    "moe.router.fc3_weight": (lambda z: (z["s"], z["router"]), "f32_mlp"),
    "moe.router.fc3_bias": (lambda z: (z["router"],), "f32_router_bias"),
    "moe.experts.gate_up": (lambda z: (z["h"], 2 * z["moe_i"]), "expert_w"),
    "moe.experts.down": (lambda z: (z["moe_i"], z["h"]), "expert_w_out"),
}
# the leaves a program keeps in float32 whatever its parameters' dtype
FLOAT32_LEAVES = tuple(n for n, (_, kind) in _LAYER.items()
                       if kind.startswith("f32_"))


def sizes(cfg):
    return {"h": int(cfg["hidden_size"]),
            "n": int(cfg["num_attention_heads"]),
            "kv": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]),
            "t0": int(cfg["cca_time0"]), "t1": int(cfg["cca_time1"]),
            "s": int(cfg["router_hidden_size"]),
            "moe_i": int(cfg["moe_intermediate_size"]),
            "held": int(cfg["num_experts"]),
            "router": int(cfg.get("router_experts", cfg["num_experts"])),
            "offset": int(cfg.get("expert_offset", 0)),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"])}


def group_of(cfg, layer):
    """Every layer is of one make (``runners/laguna_train.py`` asks)."""
    return "blocks"


def layer_ids(cfg):
    return {"blocks": list(range(int(cfg["num_hidden_layers"])))}


def _draw(key, shape, kind, cfg, dtype):
    """One seeded leaf.  ``w`` ``N(0, std)``, the residual projections
    (``w_out``) over the root of the residual adds the layers held make;
    ``conv0`` taps ``N(0, 1 / sqrt(K))`` and ``conv1`` ``N(0, 1 / sqrt(K
    D))`` (a convolution that keeps its input's scale: the q-k mean then
    adds a term of the same size); gains ``1 + N(0, std)``, ``gamma`` ``0.5 +
    N(0, std)``, biases ``N(0, std)``.  Where the configuration says so (it
    says why): the router MLP's matrices with orthonormal columns times
    ``router_mlp_orthogonal``, its biases ``N(0, router_bias_range)``, the
    gain of its RMSNorm ``router_norm_gain + N(0, std)``."""
    std = float(cfg.get("initializer_range", 0.02))
    x = jax.random.normal(key, shape, jnp.float32)
    if kind.startswith("f32_"):
        dtype = jnp.float32
    if kind in ("gain", "f32_gain"):
        x = 1.0 + std * x
    elif kind == "f32_router_gain":
        x = float(cfg.get("router_norm_gain", 1.0)) + std * x
    elif kind == "f32_half":
        x = 0.5 + std * x
    elif kind == "f32_mlp" and "router_mlp_orthogonal" in cfg:
        # orthonormal columns times a gain: no expert's logit is longer
        # than another's, and none leans on another's
        x = jnp.linalg.qr(x)[0] * float(cfg["router_mlp_orthogonal"])
    elif kind == "f32_mlp":
        x = x * std
    elif kind == "f32_router_bias":
        x = x * float(cfg.get("router_bias_range", std))
    elif kind == "conv0":
        x = x / math.sqrt(shape[-1])
    elif kind == "conv1":
        x = x / math.sqrt(shape[1] * shape[2])
    elif kind.endswith("w_out"):
        x = x * std / math.sqrt(2 * int(cfg["num_hidden_layers"]))
    else:
        x = x * std
    return to_grid(x, dtype).astype(dtype)


def layer_params(key, layer, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``.  An expert's leaf
    is drawn from its GLOBAL index, so a share holds a slice of the uncut
    layer's experts."""
    z = sizes(cfg)
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for j, (name, (shape, what)) in enumerate(_LAYER.items()):
        k = jax.random.fold_in(lkey, j)
        if what.startswith("expert_"):
            out[name] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape(z), what, cfg, dtype))(
                    z["offset"] + jnp.arange(z["held"]))
        else:
            out[name] = _draw(k, shape(z), what, cfg, dtype)
    return out


def outer_params(key, cfg, dtype):
    """The embedding (which is the head too), rows ``N(0,
    embedding_range)``, and the final norm, whose gain is ``final_norm_gain
    + N(0, std)`` (``initializer_range`` and 1 unless the configuration says
    otherwise: it says why)."""
    z = sizes(cfg)
    okey = jax.random.fold_in(key, 0)
    rows = dict(cfg, initializer_range=float(cfg.get(
        "embedding_range", cfg.get("initializer_range", 0.02))))
    gain = _draw(jax.random.fold_in(okey, 1), (z["h"],), "gain", cfg,
                 jnp.float32) + (float(cfg.get("final_norm_gain", 1.0)) - 1.0)
    return {"embed": {"weight": _draw(jax.random.fold_in(okey, 0),
                                      (z["vocab"], z["h"]), "w", rows,
                                      dtype)},
            "head": {"ln_f.weight": to_grid(gain, dtype).astype(dtype)}}


def init_params(seed, cfg, dtype):
    """``{"embed", "head", "blocks"}``; ``blocks`` holds the layers' leaves
    stacked on a leading axis.  One jitted call."""
    cfg = dict(cfg)

    @jax.jit
    def make(key):
        tree = outer_params(key, cfg, dtype)
        tree["blocks"] = jax.lax.map(
            lambda l: layer_params(key, l, cfg, dtype),
            jnp.arange(int(cfg["num_hidden_layers"])))
        return tree

    return make(seed_key(seed))


# ------------------------------------------------------------ forward ----
def rope_angles(cfg, seq):
    """``(cos, sin) [seq, rot / 2]`` float32, ``rot`` the rotated dims."""
    rot = int(int(cfg["head_dim"]) * float(cfg["partial_rotary_factor"]))
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def shift(x, steps=1):
    """``x [T, ...]`` ``steps`` later in time, zeros before the row's
    first."""
    if steps == 0:
        return x
    return jnp.pad(x, ((steps, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def conv_depthwise(x, w):
    """``x [T, C]``, ``w [C, K]``: ``y_t[c] = sum_j w[c, j] x_{t-(K-1)+j}
    [c]``."""
    taps = w.shape[1]
    return sum(shift(x, taps - 1 - j) * w[:, j] for j in range(taps))


def conv_heads(x, w):
    """``x [T, N, D]``, ``w [N, K, D, D]``: ``z_t[h] = sum_j x_{t-(K-1)+j}
    [h] @ w[h, j]``: full over a head's channels, none across heads."""
    taps = w.shape[1]
    return sum(jnp.einsum("tnd,nde->tne", shift(x, taps - 1 - j), w[:, j])
               for j in range(taps))


def unit(x, eps):
    """``sqrt(D) x / |x|`` over the last axis (``eps`` under the root, as
    an RMS norm's)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def attend(q, k, v, precision="float32"):
    """``o [T, n, D]`` of ``q [T, n, D]`` over ``k, v [T, kv, D]``: a head
    at a time and ``Q_ROWS`` query rows at a time, their ``[rows, T]``
    scores under the causal mask."""
    t, n, d = q.shape
    group = n // k.shape[1]
    rows = min(t, Q_ROWS)
    if t % rows:
        raise ValueError(f"{t} positions are not whole runs of {rows}")
    keys, starts = jnp.arange(t)[None, :], jnp.arange(0, t, rows)

    def head(qkv):
        q_h, k_h, v_h = qkv

        @jax.checkpoint
        def some_rows(first_and_q):
            first, q_r = first_and_q
            seen = keys <= first + jnp.arange(rows)[:, None]
            sc = jnp.where(seen, (q_r @ k_h.T) / math.sqrt(d), -jnp.inf)
            return _r(jax.nn.softmax(sc, axis=-1), precision) @ v_h

        return jax.lax.map(
            some_rows, (starts, q_h.reshape(-1, rows, d))).reshape(t, d)

    by_head = lambda a, rep: jnp.repeat(         # noqa: E731
        jnp.moveaxis(a, 1, 0), rep, axis=0)
    return jnp.moveaxis(
        jax.lax.map(head, (by_head(q, 1), by_head(k, group),
                           by_head(v, group))), 0, 1)


def cca(h, p, cfg, precision="float32"):
    """Compressed convolutional attention of the normed ``h [T, H]``."""
    z, eps = sizes(cfg), float(cfg["rms_norm_eps"])
    t, n, kv, d = h.shape[0], z["n"], z["kv"], z["d"]
    r = lambda a: _r(a, precision)      # noqa: E731
    q_lat = _mm(h, p["attn.q_proj.weight"], precision)          # [T, n D]
    k_lat = _mm(h, p["attn.k_proj.weight"], precision)
    now = kv // 2 * d           # the value heads that read h_t itself
    w_v = p["attn.v_proj.weight"]
    v = jnp.concatenate([_mm(h, w_v[:, :now], precision),
                         _mm(shift(h), w_v[:, now:], precision)],
                        axis=1).reshape(t, kv, d)

    def convolved(x, heads, w0, w1):
        y = r(conv_depthwise(x, w0)).reshape(t, heads, d)
        return r(conv_heads(y, w1))

    q_bar = convolved(q_lat, n, p["attn.q_conv0"], p["attn.q_conv1"])
    k_bar = convolved(k_lat, kv, p["attn.k_conv0"], p["attn.k_conv1"])
    q_lat, k_lat = q_lat.reshape(t, n, d), k_lat.reshape(t, kv, d)
    q = q_bar + 0.5 * (q_lat + jnp.repeat(k_lat, n // kv, axis=1))
    k = k_bar + 0.5 * (jnp.mean(q_lat.reshape(t, kv, n // kv, d), axis=2)
                       + k_lat)
    q = r(unit(q, eps))
    k = r(unit(k, eps) * p["attn.temperature"][:, None])
    cos, sin = rope_angles(cfg, t)
    q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    o = r(attend(q, k, v, precision)).reshape(t, n * d)
    return _mm(o, p["attn.o_proj.weight"], precision)


def route(b, r_prev, p, cfg, precision="float32"):
    """``(e* [T], w [T], r [T, S])``: the router's state, its float32 MLP,
    softmax over all the router's experts, top-1 of ``p + bias`` (bias 0),
    the weight the probability itself."""
    eps = float(cfg["rms_norm_eps"])
    r = _mm(b, p["moe.router.down.weight"], precision) \
        + p["moe.router.state_gain"] * r_prev
    a = _rms(r, p["moe.router.norm_weight"], eps)
    for i in (1, 2):
        a = jax.nn.gelu(jnp.matmul(a, p[f"moe.router.fc{i}_weight"])
                        + p[f"moe.router.fc{i}_bias"], approximate=False)
    z = jnp.matmul(a, p["moe.router.fc3_weight"]) + p["moe.router.fc3_bias"]
    prob = jax.nn.softmax(z, axis=-1)
    chosen = jnp.argmax(prob, axis=-1)
    return chosen, jnp.take_along_axis(prob, chosen[:, None], axis=1)[:, 0], r


def expert_ffn(b, r_prev, p, cfg, precision="float32"):
    """``(w E_{e*}(b) where e* is held here, r, positions per expert held
    here)``."""
    z = sizes(cfg)
    chosen, w, r = route(b, r_prev, p, cfg, precision)

    @jax.checkpoint
    def one(carry, e_w):
        e, gate_up, down = e_w
        hit = chosen == e + z["offset"]
        y = carry + jnp.where(hit, w, 0.0)[:, None] * _swiglu(
            b, gate_up, down, precision)
        return y, jnp.sum(hit, dtype=jnp.int32)

    routed, counts = jax.lax.scan(
        one, jnp.zeros_like(b),
        (jnp.arange(z["held"]), p["moe.experts.gate_up"],
         p["moe.experts.down"]))
    return _r(routed, precision), r, counts


def scaled_add(x, made, p, which):
    return (p[f"{which}.skip_scale"] * x + p[f"{which}.skip_bias"]) \
        + (p[f"{which}.out_scale"] * made + p[f"{which}.out_bias"])


def block(x, r_prev, p, cfg, precision="float32"):
    """One layer on ONE row ``x [T, H]``: ``(x, r, positions per expert held
    here)``."""
    eps = float(cfg["rms_norm_eps"])
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    r_ = lambda a: _r(a, precision)      # noqa: E731
    a = cca(r_(_rms(x, p["ln_1.weight"], eps)), p, cfg, precision)
    x = r_(scaled_add(x, a, p, "res_1"))
    b = r_(_rms(x, p["ln_2.weight"], eps))
    m, r, counts = expert_ffn(b, r_prev, p, cfg, precision)
    return r_(scaled_add(x, m, p, "res_2")), r, counts


def stack(params, ids, cfg, precision="float32"):
    """``(x [T, H] before the final norm, counts [layers, held], the RMS of
    the router state entering each layer)`` of ONE row."""
    x = params["embed"]["weight"].astype(jnp.float32)[ids]
    state = jnp.zeros((ids.shape[0], sizes(cfg)["s"]), jnp.float32)

    @jax.checkpoint
    def blk(carry, p):
        x, r_prev = carry
        x, r, counts = block(x, r_prev, p, cfg, precision)
        return (x, r), (counts, jnp.sqrt(jnp.mean(jnp.square(r_prev))))

    (x, _), (counts, entering) = jax.lax.scan(blk, (x, state),
                                              params["blocks"])
    return x, counts, entering


def logits_of(x, params, cfg, precision="float32"):
    """``rms(x; g_f) E^T``: the head is the embedding."""
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    e = params["embed"]["weight"].astype(jnp.float32)
    return _mm(_r(_rms(x, g, float(cfg["rms_norm_eps"])), precision), e.T,
               precision)


def row_loss_sum(params, ids, labels, cfg, precision="float32"):
    """``(sum over t < T - 1 of CE(logits_t, labels_{t+1}), (expert counts,
    router state RMS))`` of one row, head and loss ``Q_ROWS`` positions at
    a time."""
    x, counts, entering = stack(params, ids, cfg, precision)
    t = ids.shape[0]
    target = jnp.concatenate([labels[1:], jnp.full((1,), -1, labels.dtype)])
    rows = min(t, Q_ROWS)
    if t % rows:
        raise ValueError(f"{t} positions are not whole runs of {rows}")

    @jax.checkpoint
    def some_rows(x_and_target):
        x_r, target_r = x_and_target
        logp = jax.nn.log_softmax(logits_of(x_r, params, cfg, precision), -1)
        each = -jnp.take_along_axis(
            logp, jnp.maximum(target_r, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(target_r >= 0, each, 0.0))

    total = jnp.sum(jax.lax.map(
        some_rows, (x.reshape(-1, rows, x.shape[1]),
                    target.reshape(-1, rows))))
    return total, (counts, entering)


# ------------------------------------------------------ norms by leaf ----
def change_norms(seed, cfg, dtype, arrays):
    """``||a - p0||`` per leaf and layer of ``{(group.leaf, layer index |
    None): array}``, p0 the seeded starting weights, made again one layer
    at a time inside the jitted reduction."""
    key = seed_key(seed)
    cfg = dict(cfg)
    split = lambda n: n.split(".", 1)           # noqa: E731

    def gap(a, b):
        return _norm(a.astype(jnp.float32) - b.astype(jnp.float32), 0)

    @jax.jit
    def outer(k, got):
        p0 = outer_params(k, cfg, dtype)
        return {n: gap(a, p0[split(n)[0]][split(n)[1]])
                for n, a in got.items()}

    @jax.jit
    def one_layer(k, layer, got):
        p0 = layer_params(k, layer, cfg, dtype)
        return {n: gap(a, p0[split(n)[1]]) for n, a in got.items()}

    out = {}
    _by_layer(out, outer(key, {n: a for (n, layer), a in arrays.items()
                               if layer is None}), None)
    for layer in sorted({l for _, l in arrays if l is not None}):
        got = {n: a for (n, l), a in arrays.items() if l == layer}
        _by_layer(out, one_layer(key, jnp.int32(layer), got), layer)
    return out


# ----------------------------------------------------------- training ----
def train_reference(seed, cfg, batches, hp, param_dtype, precision="float32",
                    shard=None, against=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights, as ``reference/laguna.py train_reference`` does (float32
    arithmetic, every leaf back on the grid it is stored on after every
    step: ``param_dtype``'s, float32 for :data:`FLOAT32_LEAVES`; rows summed
    into a donated accumulator, earlier gradients waiting on the host so
    that the moments are formed again and never stored).  Returns
    ``losses``, ``first_grad_norms``, ``param_change_norms``, per step
    ``expert_counts`` (``[layers, held]``) and ``router_state_rms``
    (``[layers]``) and, with ``against`` (somebody else's parameters after
    the same steps, keyed ``(group.leaf, layer)``),
    ``param_change_cosines``, ``param_change_cosine_all`` and this run's own
    ``params`` on the host."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)

    def stored_as(name):
        return jnp.float32 if name in FLOAT32_LEAVES else param_dtype

    def start():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      init_params(seed, cfg, param_dtype))

    @jax.jit
    def row_grad(p, ids, labels):
        return jax.value_and_grad(row_loss_sum, has_aux=True)(
            p, ids, labels, cfg, precision)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scale(g, s):
        return jax.tree_util.tree_map(lambda a: a * s, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(p, grads_so_far, lr):
        def leaf(name, p, *gs):
            m = jnp.zeros_like(p)
            v = jnp.zeros_like(p)
            for k, g in enumerate(gs[:-1], start=1):
                _, m, v = adamw_update(p, g, m, v, k, lr, hp)
            return to_grid(adamw_update(p, gs[-1], m, v, len(gs), lr, hp)[0],
                           stored_as(name))
        return {group: {name: leaf(name, a, *(g[group][name]
                                              for g in grads_so_far))
                        for name, a in leaves.items()}
                for group, leaves in p.items()}

    def batch_grad(p, ids, labels):
        total, acc, counts, squares = 0.0, None, 0, 0.0
        for r in range(ids.shape[0]):
            (loss, (c, rms)), g = row_grad(p, ids[r], labels[r])
            total += float(loss)
            counts = counts + jax.device_get(c)
            squares = squares + np.square(jax.device_get(rms))
            acc = g if acc is None else add(acc, g)
            del g
        n = ids.shape[0] * (ids.shape[1] - 1)
        return (total / n, scale(acc, jnp.float32(1.0 / n)), counts,
                np.sqrt(squares / ids.shape[0]))

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms = [], [], None
        counts, state_rms = [], []
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g, c, rms = batch_grad(p, jnp.asarray(ids),
                                         jnp.asarray(labels))
            losses.append(loss)
            counts.append(c)
            state_rms.append(rms)
            if first_grad_norms is None:
                first_grad_norms = norms(keyed(g))
            earlier = tuple(jax.tree_util.tree_map(jnp.asarray, h)
                            for h in waiting)
            p = update(p, earlier + (g,), jnp.float32(hp["learning_rate"]))
            del earlier
            if k < len(batches):
                waiting.append(jax.device_get(g))
            del g
        out = {}
        if against is not None:
            out["params"] = _unstacked(jax.device_get(
                {group: {name: a.astype(stored_as(name))
                         for name, a in leaves.items()}
                 for group, leaves in p.items()}))
        p0 = start()
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b), donate_argnums=(0,))(p, p0)
        change = norms(keyed(delta))
        if against is not None:
            out["param_change_cosines"], out["param_change_cosine_all"] = \
                change_cosines(against, p0, delta)
    return {"losses": losses, "first_grad_norms": first_grad_norms,
            "param_change_norms": change, "expert_counts": counts,
            "router_state_rms": state_rms, **out}
