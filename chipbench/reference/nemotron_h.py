"""Plain reference for a hybrid decoder whose blocks hold ONE module each:
a Mamba-2 mixer, a grouped-KV attention or an expert layer in a latent
(``model_type`` ``nemotron_h``; the equations are those of ISSUE 38, from
the source's ``config.json`` and arXiv:2405.21060):

    x <- x + f_c(rms(x) * g_i),  c = hybrid_override_pattern[i];
    logits = W_head (rms(x) * g_f)

    M:  [z | xBC | dt] = W_in a;  xBC <- silu(conv(xBC)) (causal, depthwise,
        ``conv_kernel`` taps ending at t, bias);  x | B | C = xBC;
        Delta = softplus(dt + dt_bias);  A = -exp(A_log);
        S[t] = exp(Delta[t] A) S[t-1] + Delta[t] x[t] (outer) B[t];
        y[t] = S[t] C[t] + D x[t]        head h on group h // (nh / G);
        y <- rms_groups(y * silu(z)) * g_norm (the gate BEFORE the norm, G
        groups);  out = W_out y
    *:  q, k, v = W_q a, W_k a, W_v a; head h attends with kv head
        h // (n / kv), scale D ** -0.5, causal; NO rotary, no gate;
        out = W_o o
    E:  s = sigmoid(W_r a) in float32 over ALL the router's experts; the k
        largest; w = scale * s / (sum of the chosen);  l = W_dn a;
        out = W_up sum_{e chosen, held} w_e W2_e relu(W1_e l)^2
              + W2_s relu(W1_s a)^2

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  The recurrence is run AS the
recurrence, one position a step of a ``lax.scan``: no chunks, no decay
matrix, nothing of ``F.ssd_scan``.  The expert layer is a loop over the
experts held here, each applied to every token's latent and weighted by
what the router gave it (zero for a token that did not choose it): no
sort, no grouped matmul.  Attention scores against an explicit causal mask
over the whole T x T matrix.  It imports nothing of the program.

**The chip's share.**  ``n_routed_experts`` experts are held here, from
``expert_offset`` on, of the ``router_experts`` the router scores; what
the absent experts would add is left out, as the program leaves it out.
An expert's seeded weights depend on its GLOBAL index, so the shares of
one seed are slices of one uncut layer.  ``vocab_size`` is the slice of
the vocabulary held.

``init_params`` gives the layers of one kind stacked in a group
(``mamba``, ``attn``, ``moe``), in layer order, as the program's loader
reads them; the forward pass walks the layers in THEIR order.

Departures that change no value, made so that float32 at sequence 4096
fits a 16 GB chip: every block is recomputed for its backward; the
recurrence's positions are scanned in runs of 64, each run recomputed for
its backward (a run's 64 states of ``[nh, P, N]`` are 268 MB at the
published sizes; all 4,096 would be 17 GB); attention runs head by head,
the experts one after the other, each recomputed; the steps are followed
on the tree split by layer and the gradient is taken a BLOCK at a time
(:func:`row_loss_and_grad`: the chain rule by hand over ``jax.vjp`` of
each block, so one block's intermediates live at a time, a layer's
gradient is written once where it stays, and three kinds of block compile
instead of eleven blocks in one graph: as one graph the row's gradient
wanted 7.6 GiB of temporaries beside 9.0 of parameters and gradient, and
five minutes of the compiler); AdamW is applied a layer at a time, the
earlier step's gradient coming back from the host layer by layer.
Departures from a deployment: weights are random from the seed, gains are
1 + noise so that a dropped gain shows.

Seeded weights: ``N(0, std)``; the residual projections (``out_proj``,
``o_proj``, ``latent_up``, the shared expert's ``down``) times ``1 /
sqrt(L)`` (one residual add a block); the convolution's taps and bias
``U(-1/sqrt(K), 1/sqrt(K))`` (what the public implementation's framework
gives a depthwise convolution); ``A_log = log(1 .. nh)`` by head; ``D`` =
1; ``dt_bias`` the inverse softplus of ``exp(U(log time_step_min, log
time_step_max))`` floored at ``time_step_floor``; these three are float32
whatever the parameters' dtype (:data:`FLOAT32_LEAVES`), as the program
keeps them.

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router's own
matmul, the step sizes and the decay stay float32 there too, as the
configuration states them.
"""

import functools
import math

import jax
import jax.numpy as jnp

from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .mla_moe import _norm, _rms

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
GROUPS = {MAMBA: "mamba", ATTENTION: "attn", EXPERTS: "moe"}
SCAN_RUN = 64       # positions a recomputed run of the recurrence holds

# leaf -> (shape over the sizes, kind); names are the program's
_LEAVES = {
    "mamba": {
        "ln_1.weight": (lambda z: (z["h"],), "gain"),
        "mamba.in_proj.weight": (
            lambda z: (z["h"], z["d"] + z["conv"] + z["nh"]), "w"),
        "mamba.conv_weight": (lambda z: (z["conv"], z["taps"]), "conv"),
        "mamba.conv_bias": (lambda z: (z["conv"],), "conv"),
        "mamba.dt_bias": (lambda z: (z["nh"],), "dt_bias"),
        "mamba.A_log": (lambda z: (z["nh"],), "a_log"),
        "mamba.D": (lambda z: (z["nh"],), "one"),
        "mamba.norm_weight": (lambda z: (z["d"],), "gain"),
        "mamba.out_proj.weight": (lambda z: (z["d"], z["h"]), "w_out"),
    },
    "attn": {
        "ln_1.weight": (lambda z: (z["h"],), "gain"),
        "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["hd"]), "w"),
        "attn.k_proj.weight": (lambda z: (z["h"], z["kv"] * z["hd"]), "w"),
        "attn.v_proj.weight": (lambda z: (z["h"], z["kv"] * z["hd"]), "w"),
        "attn.o_proj.weight": (lambda z: (z["n"] * z["hd"], z["h"]),
                               "w_out"),
    },
    "moe": {
        "ln_1.weight": (lambda z: (z["h"],), "gain"),
        "moe.router.weight": (lambda z: (z["h"], z["router"]), "w"),
        "moe.latent_down.weight": (lambda z: (z["h"], z["latent"]), "w"),
        "moe.experts.up": (lambda z: (z["latent"], z["moe_i"]), "expert_w"),
        "moe.experts.down": (lambda z: (z["moe_i"], z["latent"]),
                             "expert_w"),
        "moe.latent_up.weight": (lambda z: (z["latent"], z["h"]), "w_out"),
        "moe.shared_experts.up.weight": (
            lambda z: (z["h"], z["shared_i"]), "w"),
        "moe.shared_experts.down.weight": (
            lambda z: (z["shared_i"], z["h"]), "w_out"),
    },
}


def sizes(cfg):
    nh, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {"h": int(cfg["hidden_size"]), "nh": nh, "p": p, "g": g,
            "state": n, "d": nh * p, "conv": nh * p + 2 * g * n,
            "taps": int(cfg["conv_kernel"]),
            "n": int(cfg["num_attention_heads"]),
            "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]),
            "moe_i": int(cfg["moe_intermediate_size"]),
            "latent": int(cfg["moe_latent_size"]),
            "shared_i": int(cfg["moe_shared_expert_intermediate_size"]),
            "held": int(cfg["n_routed_experts"]),
            "router": int(cfg.get("router_experts",
                                  cfg["n_routed_experts"])),
            "offset": int(cfg.get("expert_offset", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"])}


def group_of(cfg, layer):
    """``mamba``, ``attn`` or ``moe``: what layer ``layer`` holds."""
    return GROUPS[cfg["hybrid_override_pattern"][layer]]


def layer_ids(cfg):
    """{group: its layers' indices, in layer order}."""
    out = {}
    for layer in range(int(cfg["num_hidden_layers"])):
        out.setdefault(group_of(cfg, layer), []).append(layer)
    return out


# the leaves a program keeps in float32 whatever its parameters' dtype
# (``amp_keep_float32``): the reference stores what the program stores
FLOAT32_KINDS = ("a_log", "one", "dt_bias")
FLOAT32_LEAVES = tuple(n for n, (_, kind) in _LEAVES["mamba"].items()
                       if kind in FLOAT32_KINDS)


def _draw(key, shape, kind, cfg, dtype):
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    if kind in FLOAT32_KINDS:
        dtype = jnp.float32
    if kind == "a_log":
        x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    elif kind == "one":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "conv":
        bound = 1.0 / math.sqrt(z["taps"])
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "dt_bias":
        lo, hi = (math.log(float(cfg.get("time_step_min", 0.001))),
                  math.log(float(cfg.get("time_step_max", 0.1))))
        dt = jnp.maximum(
            jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)),
            float(cfg.get("time_step_floor", 1e-4)))
        x = dt + jnp.log(-jnp.expm1(-dt))           # softplus's inverse
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "gain":
            x = 1.0 + std * x
        else:
            x = x * (std / math.sqrt(z["layers"])
                     if kind.endswith("w_out") else std)
    return to_grid(x, dtype).astype(dtype)


def layer_params(key, layer, group, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``.  An expert's
    leaf is drawn from its GLOBAL index, so a share holds a slice of the
    uncut layer's experts."""
    z = sizes(cfg)
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for j, (name, (shape, what)) in enumerate(_LEAVES[group].items()):
        k = jax.random.fold_in(lkey, j)
        if what.startswith("expert_"):
            ids = z["offset"] + jnp.arange(z["held"])
            out[name] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape(z), what, cfg, dtype))(ids)
        else:
            out[name] = _draw(k, shape(z), what, cfg, dtype)
    return out


def outer_params(key, cfg, dtype):
    z = sizes(cfg)
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, cfg, dtype)
    return {"embed": {"weight": mk(0, (z["vocab"], z["h"]), "w")},
            "head": {"ln_f.weight": mk(1, (z["h"],), "gain"),
                     "lm_head.weight": mk(2, (z["h"], z["vocab"]), "w")}}


def init_params(seed, cfg, dtype):
    """``{"embed", "head", "mamba", "attn", "moe"}``; a group holds its
    layers' leaves stacked on a leading axis, in layer order.  One jitted
    call."""
    cfg = dict(cfg)
    ids = layer_ids(cfg)

    @jax.jit
    def make(key):
        tree = outer_params(key, cfg, dtype)
        for group, layers in ids.items():
            tree[group] = jax.lax.map(
                lambda l, group=group: layer_params(key, l, group, cfg,
                                                    dtype),
                jnp.asarray(layers))
        return tree

    return make(seed_key(seed))


def init_split(seed, cfg, dtype):
    """:func:`init_params`'s weights in float32 as the tree split by layer
    (:func:`split_layers`), made a layer at a time: no stack is ever held
    beside its slices."""
    cfg = dict(cfg)
    key = seed_key(seed)
    f32 = lambda t: jax.tree_util.tree_map(     # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    one = jax.jit(lambda k, layer, group: f32(
        layer_params(k, layer, group, cfg, dtype)), static_argnums=(2,))
    tree = jax.jit(lambda k: f32(outer_params(k, cfg, dtype)))(key)
    tree["layers"] = [one(key, jnp.int32(layer), group_of(cfg, layer))
                      for layer in range(int(cfg["num_hidden_layers"]))]
    return tree


# ------------------------------------------------------------ forward ----
def recurrence(x, dt, a_head, b, c, d_head):
    """``y [T, nh, P]`` of ``S[t] = exp(dt[t] A) S[t-1] + dt[t] x[t]
    (outer) B[t]; y[t] = S[t] C[t] + D x[t]`` from ``S[-1] = 0``, one
    position a step.  ``x [T, nh, P]``, ``dt [T, nh]``, ``b``, ``c`` ``[T,
    G, N]``; head ``h`` reads group ``h // (nh / G)``.  The positions are
    scanned in runs of ``SCAN_RUN``, each recomputed for its backward."""
    t, nh, p = x.shape
    per_group = nh // b.shape[1]

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(v, per_group, axis=0) for v in (b_t, c_t))
        s = jnp.exp(dt_t * a_head)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) \
            + d_head[:, None] * x_t

    @jax.checkpoint
    def run(s, ats):
        return jax.lax.scan(step, s, ats)

    pad = -t % SCAN_RUN      # steps of size zero leave the state as it is
    ats = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
        (-1, SCAN_RUN) + v.shape[1:]) for v in (x, dt, b, c)]
    s0 = jnp.zeros((nh, p, b.shape[2]), jnp.float32)
    y = jax.lax.scan(run, s0, tuple(ats))[1]
    return y.reshape((-1, nh, p))[:t]


def mamba(a, p, cfg, precision="float32"):
    """The mixer on ONE sequence's normed input ``a [T, H]``."""
    z = sizes(cfg)
    r = lambda v: _r(v, precision)      # noqa: E731
    t, d, gn = a.shape[0], z["d"], z["g"] * z["state"]
    zxbcdt = _mm(a, p["mamba.in_proj.weight"], precision)
    gate, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:d + z["conv"]],
                     zxbcdt[:, d + z["conv"]:])
    padded = jnp.pad(xbc, ((z["taps"] - 1, 0), (0, 0)))
    conv = p["mamba.conv_bias"] + sum(
        padded[k:k + t] * p["mamba.conv_weight"][:, k]
        for k in range(z["taps"]))
    xbc = r(jax.nn.silu(conv))
    delta = jax.nn.softplus(dt + p["mamba.dt_bias"])
    y = recurrence(
        xbc[:, :d].reshape(t, z["nh"], z["p"]), delta,
        -jnp.exp(p["mamba.A_log"]),
        xbc[:, d:d + gn].reshape(t, z["g"], z["state"]),
        xbc[:, d + gn:].reshape(t, z["g"], z["state"]), p["mamba.D"])
    y = r(y.reshape(t, d)) * jax.nn.silu(gate)
    parts = y.reshape(t, z["g"], d // z["g"])
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), -1, keepdims=True)
        + float(cfg["layer_norm_epsilon"]))
    y = r(parts.reshape(t, d) * p["mamba.norm_weight"])
    return _mm(y, p["mamba.out_proj.weight"], precision)


def attention(a, p, cfg, precision="float32"):
    """Causal attention over grouped KV heads on ONE sequence ``a [T,
    H]``: no rotary, no gate."""
    z = sizes(cfg)
    t, n, kv, d = a.shape[0], z["n"], z["kv"], z["hd"]
    r = lambda v: _r(v, precision)      # noqa: E731
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, kv, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, kv, d)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    k_heads, v_heads = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    @jax.checkpoint
    def head(q_and_kv):
        q_h, kv_h = q_and_kv
        s = (q_h @ k_heads[kv_h].T) / math.sqrt(d)
        w = r(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        return w @ v_heads[kv_h]

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(n) // (n // kv)))
    o = r(jnp.moveaxis(o, 0, 1)).reshape(t, n * d)
    return _mm(o, p["attn.o_proj.weight"], precision)


def route(a, wg, cfg):
    """(idx [T, k], weights [T, k]) in float32: sigmoid scores over all
    the router's experts, the k largest (the selection bias is zero from
    the seed; one group: no group limit), normed over them, scaled."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(jnp.matmul(a, wg))
    _, idx = jax.lax.top_k(s, z["top_k"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * float(cfg["routed_scaling_factor"])


def _relu2_mlp(a, up, down, precision):
    h = jnp.square(jax.nn.relu(_mm(a, up, precision)))
    return _mm(_r(h, precision), down, precision)


def experts(a, p, cfg, precision="float32"):
    """``(W_up sum_e w_e E_e(W_dn a) + S(a), the tokens each expert held
    here received)`` on ONE sequence ``a [T, H]``."""
    z = sizes(cfg)
    idx, w = route(a, p["moe.router.weight"], cfg)
    latent = _mm(a, p["moe.latent_down.weight"], precision)

    @jax.checkpoint
    def one(carry, e_w):
        e, up, down = e_w
        hit = idx == e + z["offset"]                            # [T, k]
        w_e = jnp.sum(jnp.where(hit, w, 0.0), axis=1)
        y = carry + w_e[:, None] * _relu2_mlp(latent, up, down, precision)
        return y, jnp.sum(hit, dtype=jnp.int32)

    routed, counts = jax.lax.scan(
        one, jnp.zeros_like(latent),
        (jnp.arange(z["held"]), p["moe.experts.up"], p["moe.experts.down"]))
    routed = _mm(_r(routed, precision), p["moe.latent_up.weight"], precision)
    shared = _relu2_mlp(a, p["moe.shared_experts.up.weight"],
                        p["moe.shared_experts.down.weight"], precision)
    return _r(routed + shared, precision), counts


def block(x, p, group, cfg, precision="float32"):
    """One block on ONE sequence: ``(x, tokens per expert held here |
    None)``."""
    p = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), p)
    a = _r(_rms(x, p["ln_1.weight"], float(cfg["layer_norm_epsilon"])),
           precision)
    counts = None
    if group == "mamba":
        out = mamba(a, p, cfg, precision)
    elif group == "attn":
        out = attention(a, p, cfg, precision)
    else:
        out, counts = experts(a, p, cfg, precision)
    return _r(x + out, precision), counts


def logits_of(x, params, cfg, precision="float32"):
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    w = params["head"]["lm_head.weight"].astype(jnp.float32)
    return _mm(_r(_rms(x, g, float(cfg["layer_norm_epsilon"])), precision),
               w, precision)


def split_layers(params, cfg):
    """Stacked tree -> ``{"embed", "head", "layers": [a layer's leaves, in
    layer order]}``: the form the steps are followed in, so that a layer's
    gradient is its own array."""
    ids = layer_ids(cfg)
    layers = []
    for layer in range(int(cfg["num_hidden_layers"])):
        group = group_of(cfg, layer)
        j = ids[group].index(layer)
        layers.append(jax.tree_util.tree_map(lambda v, j=j: v[j],
                                             params[group]))
    return {"embed": params["embed"], "head": params["head"],
            "layers": layers}


def forward_row(params, row, cfg, precision="float32"):
    """(logits [T, vocab], counts [expert layers, held]) of ONE row from a
    STACKED tree, as one graph: the layers in their own order.  What the
    CPU tests differentiate whole; :func:`row_loss_and_grad` is the same
    function taken a block at a time."""
    tree = split_layers(params, cfg)
    x = tree["embed"]["weight"].astype(jnp.float32)[row]
    counts = []
    for layer, leaves in enumerate(tree["layers"]):
        x, c = block(x, leaves, group_of(cfg, layer), cfg, precision)
        if c is not None:
            counts.append(c)
    return logits_of(x, tree, cfg, precision), \
        (jnp.stack(counts) if counts else None)


def _loss_sum(logits, labels):
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[1:, None], -1))


def _row_loss_sum(params, row, labels, cfg, precision):
    logits, counts = forward_row(params, row, cfg, precision)
    return _loss_sum(logits, labels), counts


@functools.lru_cache(maxsize=None)
def _block_programs(cfg_items, precision):
    """The jitted pieces :func:`row_loss_and_grad` is made of, one set a
    configuration and precision: a block forward and a block's transpose a
    KIND of layer (three kinds, so six programs whatever the depth), the
    embedding's two and the head with the loss."""
    cfg = dict(cfg_items)

    @functools.partial(jax.jit, static_argnums=(0,))
    def fwd(group, x, leaves):
        return block(x, leaves, group, cfg, precision)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def bwd(group, x, leaves, d_out):
        """``(d x, d leaves)``: the block run again from its input."""
        _, vjp = jax.vjp(
            lambda x, l: block(x, l, group, cfg, precision)[0], x, leaves)
        return vjp(d_out)

    @jax.jit
    def embed(weight, row):
        return weight.astype(jnp.float32)[row]

    @jax.jit
    def embed_bwd(d_x, weight, row):
        return jnp.zeros(weight.shape, jnp.float32).at[row].add(d_x)

    @jax.jit
    def head(x, leaves, labels):
        """``(loss sum, (d x, d head leaves))``."""
        return jax.value_and_grad(
            lambda x, h: _loss_sum(logits_of(x, {"head": h}, cfg, precision),
                                   labels), argnums=(0, 1))(x, leaves)

    return fwd, bwd, embed, embed_bwd, head


def row_loss_and_grad(tree, row, labels, cfg, precision="float32"):
    """``((loss sum, counts), gradient)`` of ONE row for a SPLIT tree
    (:func:`split_layers`), the gradient split alike.  The chain rule a
    block at a time, by hand: the forward pass keeps each block's input;
    the backward pass hands each block's transpose (``jax.vjp`` of the
    block, run again from that input) the gradient of its output.  One
    block's intermediates live at a time, and a layer's gradient is
    written once, where it stays."""
    frozen = tuple(sorted((k, v if not isinstance(v, list) else tuple(v))
                          for k, v in cfg.items()))
    fwd, bwd, embed, embed_bwd, head = _block_programs(frozen, precision)
    x = embed(tree["embed"]["weight"], row)
    inputs, counts = [], []
    for layer, leaves in enumerate(tree["layers"]):
        inputs.append(x)
        x, c = fwd(group_of(cfg, layer), x, leaves)
        if c is not None:
            counts.append(c)
    loss, (d_x, d_head) = head(x, tree["head"], labels)
    d_layers = [None] * len(inputs)
    for layer in reversed(range(len(inputs))):
        d_x, d_layers[layer] = bwd(group_of(cfg, layer), inputs.pop(),
                                   tree["layers"][layer], d_x)
    grad = {"embed": {"weight": embed_bwd(d_x, tree["embed"]["weight"],
                                          row)},
            "head": d_head, "layers": d_layers}
    return (loss, jnp.stack(counts) if counts else None), grad


# ------------------------------------------------------ norms by leaf ----
def keyed(tree, cfg):
    """Split tree -> ``{(group.leaf, layer index | None): array}``, the
    form a program's state is read in."""
    out = {(f"{g}.{n}", None): a for g in ("embed", "head")
           for n, a in tree[g].items()}
    for layer, leaves in enumerate(tree["layers"]):
        group = group_of(cfg, layer)
        out.update({(f"{group}.{n}", layer): a for n, a in leaves.items()})
    return out


def norms(arrays, cfg=None):
    """L2 norm per leaf and layer of ``{(group.leaf, layer index | None):
    array}``."""
    keys = sorted(arrays, key=str)
    got = jax.jit(lambda xs: [_norm(x, 0) for x in xs])(
        [arrays[k] for k in keys])
    return {k: float(v) for k, v in zip(keys, got)}


def change_norms(seed, cfg, dtype, arrays):
    """``||a - p0||`` per leaf and layer of ``{(group.leaf, layer index |
    None): array}``, p0 the seeded starting weights, made again one layer
    at a time inside the jitted reduction."""
    key = seed_key(seed)
    cfg = dict(cfg)
    split = lambda n: n.split(".", 1)           # noqa: E731

    @jax.jit
    def outer(k, got):
        p0 = outer_params(k, cfg, dtype)
        return {n: _norm(a.astype(jnp.float32)
                         - p0[split(n)[0]][split(n)[1]].astype(jnp.float32),
                         0) for n, a in got.items()}

    @functools.partial(jax.jit, static_argnums=(2,))
    def one_layer(k, layer, group, got):
        p0 = layer_params(k, layer, group, cfg, dtype)
        return {n: _norm(a.astype(jnp.float32)
                         - p0[split(n)[1]].astype(jnp.float32), 0)
                for n, a in got.items()}

    out = {}
    got = {n: a for (n, layer), a in arrays.items() if layer is None}
    _by_layer(out, outer(key, got), None)
    for layer in sorted({l for _, l in arrays if l is not None}):
        got = {n: a for (n, l), a in arrays.items() if l == layer}
        group = split(next(iter(got)))[0]
        _by_layer(out, one_layer(key, jnp.int32(layer), group, got), layer)
    return out


# ----------------------------------------------------------- training ----
def change_cosines(against, p0, delta):
    """``cos(a - p0, delta)`` per leaf and layer of ``against``
    (``{(group.leaf, layer): array}``, someone else's parameters after the
    same steps), and over all leaves at once.  ``p0`` and ``delta`` are
    keyed alike: the starting weights and the reference's own change.  A
    leaf neither side moved reads 1, a leaf only one side moved 0."""
    @jax.jit
    def dots(a, start, d):
        mine = a.astype(jnp.float32) - start
        return jnp.vdot(mine, d), jnp.vdot(mine, mine), jnp.vdot(d, d)

    out, total = {}, [0.0, 0.0, 0.0]
    for key, a in against.items():
        md, mm, dd = (float(v) for v in dots(jnp.asarray(a), p0[key],
                                             delta[key]))
        total = [t + v for t, v in zip(total, (md, mm, dd))]
        if mm == 0.0 or dd == 0.0:
            out[key] = 1.0 if mm == dd else 0.0
        else:
            out[key] = md / math.sqrt(mm * dd)
    return out, total[0] / max(math.sqrt(total[1] * total[2]), 1e-30)


def train_reference(seed, cfg, batches, hp, param_dtype, precision="float32",
                    shard=None, against=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights (float32 arithmetic, parameters on ``param_dtype``'s grid, the
    gradient a block at a time, rows summed into a donated accumulator,
    earlier gradients waiting on the host and coming back a layer at a
    time), and return ``losses``, ``first_grad_norms``,
    ``param_change_norms``, ``expert_counts`` (per step, the tokens each
    expert held here received in each expert layer) and, with ``against``
    (somebody else's parameters after the same steps, keyed ``(group.leaf,
    layer)``), ``param_change_cosines``, ``param_change_cosine_all`` and
    this run's own ``params`` on the host."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)

    def stored_as(name):
        return jnp.float32 if name in FLOAT32_LEAVES else param_dtype

    def start():
        return init_split(seed, cfg, param_dtype)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scale(g, s):
        return jax.tree_util.tree_map(lambda a: a * s, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(p, grads_so_far, lr):
        """One piece of the tree, ``{leaf name: array}``, after this
        step; every leaf back on the grid it is stored on."""
        def leaf(name, p, *gs):
            m = jnp.zeros_like(p)
            v = jnp.zeros_like(p)
            for k, g in enumerate(gs[:-1], start=1):
                _, m, v = adamw_update(p, g, m, v, k, lr, hp)
            return to_grid(adamw_update(p, gs[-1], m, v, len(gs), lr, hp)[0],
                           stored_as(name))
        return {name: leaf(name, a, *(g[name] for g in grads_so_far))
                for name, a in p.items()}

    def parts(tree):
        """The tree's pieces AdamW is applied to one after the other:
        ``(where it hangs, its key there)``."""
        return [(tree, "embed"), (tree, "head")] + [
            (tree["layers"], i) for i in range(len(tree["layers"]))]

    def batch_grad(p, ids, labels):
        total, acc, counts = 0.0, None, 0
        for r in range(ids.shape[0]):
            (loss, c), g = row_loss_and_grad(p, ids[r], labels[r], cfg,
                                             precision)
            total += float(loss)
            if c is not None:
                counts = counts + jax.device_get(c)
            acc = g if acc is None else add(acc, g)
            del g
        n = ids.shape[0] * (ids.shape[1] - 1)
        return total / n, scale(acc, jnp.float32(1.0 / n)), counts

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms, counts = [], [], None, []
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g, c = batch_grad(p, jnp.asarray(ids), jnp.asarray(labels))
            losses.append(loss)
            counts.append(c)
            if first_grad_norms is None:
                first_grad_norms = norms(keyed(g, cfg))
            lr = jnp.float32(hp["learning_rate"])
            held = [parts(h) for h in waiting]
            for i, ((at, key), (g_at, _)) in enumerate(zip(parts(p),
                                                           parts(g))):
                earlier = tuple(jax.tree_util.tree_map(
                    jnp.asarray, h[i][0][h[i][1]]) for h in held)
                at[key] = update(at[key], earlier + (g_at[key],), lr)
                del earlier
            if k < len(batches):
                waiting.append(jax.device_get(g))
            del g
        out = {}
        if against is not None:
            out["params"] = jax.device_get({
                key: a.astype(stored_as(key[0].split(".", 1)[1]))
                for key, a in keyed(p, cfg).items()})
        p0 = start()
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b), donate_argnums=(0,))(p, p0)
        p0, delta = keyed(p0, cfg), keyed(delta, cfg)
        change = norms(delta)
        if against is not None:
            out["param_change_cosines"], out["param_change_cosine_all"] = \
                change_cosines(against, p0, delta)
    return {"losses": losses, "first_grad_norms": first_grad_norms,
            "param_change_norms": change, "expert_counts": counts, **out}
