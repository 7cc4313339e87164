"""Plain reference for a decoder whose layers mix sliding-window and full
attention over grouped KV heads, with a per-head output gate and
softmax-routed experts beside a shared one (``model_type`` ``laguna``; the
equations are those of ISSUE 30, from the source's ``config.json``):

    x <- x + attn_l(rms(x));  x <- x + ffn_l(rms(x));  logits = W_head rms(x)

    attn_l: n_l q heads (``num_attention_heads_per_layer``) over
           ``num_key_value_heads`` kv heads of ``head_dim``:
           q = W_q a, k = W_k a, v = W_v a, g = sigmoid(W_g a) [T, n_l];
           rotary, rotate-half, on the leading rotary dims of each head
           (``rope_parameters[layer_types[l]]``: sliding layers all dims,
           base 10,000, plain; full layers half the dims, base 500,000,
           YaRN frequencies, cos and sin times ``attention_factor``);
           head h attends with kv head h // (n_l / kv), scale D ** -0.5,
           causal, and in a sliding layer query t sees keys j with
           0 <= t - j < ``sliding_window``;
           x + concat_h(g[:, h] * o_h) W_o
    ffn_l:  ``mlp_layer_types[l]`` ``dense``: down(silu(gate b) * up b);
           ``sparse``: s = softmax(W_r b) in float32 over ALL the router's
           experts; the k largest; w = scale * s / (sum of the chosen);
           sum_i w_i E_i(b) + S(b)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no grouped
matmul; scores against an EXPLICIT ``0 <= t - j < window`` mask over the
whole T x T matrix.  The expert layer is a loop over the experts held
here, each applied to every token and weighted by what the router gave it
(zero for a token that did not choose it).  It imports nothing of the
program.

**The chip's share.**  ``num_experts`` experts are held here, from
``expert_offset`` on, of the ``router_experts`` the router scores; the
part the absent experts would add is left out, as the program leaves it
out, and that partial result goes on to the next layer.  An expert's
seeded weights depend on its GLOBAL index, so the shares of one seed are
slices of one uncut layer.  ``vocab_size`` is the slice of the vocabulary
held: ids, logits and loss are over it.

Layers of one make are stacked: a group is named by its attention and its
ffn (``full_dense``, ``window_moe``, ``full_moe``), and holds its layers in
layer order; the forward pass walks the layers in THEIR order.

Departures that change no value, made so that float32 at sequence 8192
fits a 16 GB chip: attention runs head by head (``lax.map``, each head
recomputed for its backward: one head's float32 scores are 268 MB); the
experts run one after the other (``lax.scan``), each recomputed for its
backward; every layer is recomputed for its backward; rows are summed
into a donated accumulator, as ``reference/gpt.py`` does.  Departures from
a deployment: weights are random from the seed, gains are 1 + noise so
that a dropped gain shows.

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router's own
matmul stays float32 there too, as the configuration states it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .mla_moe import _draw, _norm, _rms, _swiglu

FULL, WINDOW = "full_attention", "sliding_attention"

# leaf -> (shape builder over the sizes and the layer's q heads, kind);
# names are the program's
_ATTN = {
    "ln_1.weight": (lambda z, n: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z, n: (z["h"], n * z["d"]), "w"),
    "attn.k_proj.weight": (lambda z, n: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.v_proj.weight": (lambda z, n: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.g_proj.weight": (lambda z, n: (z["h"], n), "w"),
    "attn.o_proj.weight": (lambda z, n: (n * z["d"], z["h"]), "w_out"),
    "ln_2.weight": (lambda z, n: (z["h"],), "gain"),
}
_FFN = {
    "dense": {
        "mlp.gate_up.weight": (lambda z, n: (z["h"], 2 * z["inter"]), "w"),
        "mlp.down.weight": (lambda z, n: (z["inter"], z["h"]), "w_out"),
    },
    "moe": {
        "moe.router.weight": (lambda z, n: (z["h"], z["router"]), "w"),
        "moe.experts.gate_up": (lambda z, n: (z["h"], 2 * z["moe_i"]),
                                "expert_w"),
        "moe.experts.down": (lambda z, n: (z["moe_i"], z["h"]),
                             "expert_w_out"),
        "moe.shared_experts.gate_up.weight": (
            lambda z, n: (z["h"], 2 * z["shared_i"]), "w"),
        "moe.shared_experts.down.weight": (
            lambda z, n: (z["shared_i"], z["h"]), "w_out"),
    },
}


def sizes(cfg):
    return {"h": int(cfg["hidden_size"]), "d": int(cfg["head_dim"]),
            "kv": int(cfg["num_key_value_heads"]),
            "inter": int(cfg["intermediate_size"]),
            "moe_i": int(cfg["moe_intermediate_size"]),
            "shared_i": int(cfg["shared_expert_intermediate_size"]),
            "held": int(cfg["num_experts"]),
            "router": int(cfg.get("router_experts", cfg["num_experts"])),
            "offset": int(cfg.get("expert_offset", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "layers": int(cfg["num_hidden_layers"]),
            "window": int(cfg["sliding_window"]),
            "vocab": int(cfg["vocab_size"])}


def group_of(cfg, layer):
    """``full_dense``, ``window_moe``, ...: what layer ``layer`` is made
    of."""
    attn = "full" if cfg["layer_types"][layer] == FULL else "window"
    ffn = "dense" if cfg["mlp_layer_types"][layer] == "dense" else "moe"
    return f"{attn}_{ffn}"


def layer_ids(cfg):
    """{group: its layers' indices, in layer order}."""
    out = {}
    for layer in range(int(cfg["num_hidden_layers"])):
        out.setdefault(group_of(cfg, layer), []).append(layer)
    return out


def _leaves(group):
    return {**_ATTN, **_FFN[group.split("_")[1]]}


def _heads(cfg, group):
    """q heads of a group's layers (one number: a group has one make)."""
    heads = {int(cfg["num_attention_heads_per_layer"][l])
             for l in layer_ids(cfg)[group]}
    if len(heads) != 1:
        raise ValueError(f"{group}: layers of {sorted(heads)} q heads")
    return heads.pop()


def layer_params(key, layer, group, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``.  An expert's
    leaf is drawn from its GLOBAL index, so a share holds a slice of the
    uncut layer's experts."""
    z = sizes(cfg)
    n = _heads(cfg, group)
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * z["layers"])
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for j, (name, (shape, what)) in enumerate(_leaves(group).items()):
        k = jax.random.fold_in(lkey, j)
        if what.startswith("expert_"):
            ids = z["offset"] + jnp.arange(z["held"])
            out[name] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape(z, n), what, std, out_std,
                dtype))(ids)
        else:
            out[name] = _draw(k, shape(z, n), what, std, out_std, dtype)
    return out


def outer_params(key, cfg, dtype):
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, std, std, dtype)
    return {"embed": {"weight": mk(0, (z["vocab"], z["h"]), "w")},
            "head": {"ln_f.weight": mk(1, (z["h"],), "gain"),
                     "lm_head.weight": mk(2, (z["h"], z["vocab"]), "w")}}


def init_params(seed, cfg, dtype):
    """``{"embed", "head", <group>...}``; a group holds its layers' leaves
    stacked on a leading axis, in layer order.  One jitted call."""
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


# ------------------------------------------------------------ forward ----
def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """``(frequencies [dim / 2], low, high)`` as the public ``rope_type:
    yarn`` initialisation computes them: ``f_i = base ** (-2i / dim)``,
    blended ``f_i (1 - m_i) / factor + f_i m_i`` with ``m_i = 1 - clip((i -
    low) / (high - low), 0, 1)``; ``low``, ``high`` the correction range of
    ``beta_fast`` (floor) and ``beta_slow`` (ceiling) at ``original_max``
    positions, ``dim ln(original_max / (beta 2 pi)) / (2 ln base)``, clipped
    to ``[0, dim - 1]``."""
    def corr(beta):
        return dim * math.log(original_max / (beta * 2.0 * math.pi)) \
            / (2.0 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dim)
    m = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - m) / factor + f * m, low, high


def rope_angles(cfg, kind, seq):
    """``(cos, sin) [seq, rot / 2]`` float32 of a layer kind, the
    attention factor multiplied in."""
    p = cfg["rope_parameters"][kind]
    rot = int(int(cfg["head_dim"]) * p.get("partial_rotary_factor", 1))
    if p.get("rope_type", "default") == "yarn":
        inv, _, _ = yarn_inv_freq(
            rot, float(p["rope_theta"]), float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p["beta_fast"]), float(p["beta_slow"]))
        factor = float(p["attention_factor"])
    else:
        inv = float(p["rope_theta"]) ** (
            -np.arange(0, rot, 2, dtype=np.float64) / rot)
        factor = 1.0
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def _rope(x, cos, sin):
    """``x [T, N, D]``: rotate-half over the leading ``2 * cos.shape[1]``
    dims of each head, the rest passed through."""
    r = cos.shape[1]
    a, b, rest = x[..., :r], x[..., r:2 * r], x[..., 2 * r:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def attention(x, p, kind, cfg, precision="float32"):
    """``x + attn(rms(x))`` on ONE sequence ``x [T, H]``."""
    z = sizes(cfg)
    eps = float(cfg["rms_norm_eps"])
    t, d, kv = x.shape[0], z["d"], z["kv"]
    n = p["attn.g_proj.weight"].shape[1]
    r = lambda a: _r(a, precision)      # noqa: E731
    a = r(_rms(x, p["ln_1.weight"], eps))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, kv, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, kv, d)
    g = r(jax.nn.sigmoid(_mm(a, p["attn.g_proj.weight"], precision)))
    cos, sin = rope_angles(cfg, kind, t)
    q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]      # t - j
    seen = back >= 0
    if kind == WINDOW:
        seen = seen & (back < z["window"])
    k_heads, v_heads = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    @jax.checkpoint
    def head(q_and_kv):
        q_h, kv_h = q_and_kv
        s = (q_h @ k_heads[kv_h].T) / math.sqrt(d)
        w = r(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        return w @ v_heads[kv_h]

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(n) // (n // kv)))
    o = r(jnp.moveaxis(o, 0, 1) * g[:, :, None]).reshape(t, n * d)
    return r(x + _mm(o, p["attn.o_proj.weight"], precision))


def route(a, wg, cfg):
    """(idx [T, k], weights [T, k]) in float32: softmax over all the
    router's experts, the k largest, normed over them, scaled."""
    z = sizes(cfg)
    s = jax.nn.softmax(jnp.matmul(a, wg), axis=-1)
    w, idx = jax.lax.top_k(s, z["top_k"])
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx, w * float(cfg["moe_routed_scaling_factor"])


def expert_ffn(a, p, cfg, precision="float32"):
    """``sum_i w_i E_i(a) + S(a)`` over the experts HELD HERE, and the
    tokens each of them received."""
    z = sizes(cfg)
    idx, w = route(a, p["moe.router.weight"], cfg)

    @jax.checkpoint
    def one(carry, e_w):
        e, gate_up, down = e_w
        hit = idx == e + z["offset"]                            # [T, k]
        w_e = jnp.sum(jnp.where(hit, w, 0.0), axis=1)
        y = carry + w_e[:, None] * _swiglu(a, gate_up, down, precision)
        return y, jnp.sum(hit, dtype=jnp.int32)

    routed, counts = jax.lax.scan(
        one, jnp.zeros_like(a),
        (jnp.arange(z["held"]), p["moe.experts.gate_up"],
         p["moe.experts.down"]))
    shared = _swiglu(a, p["moe.shared_experts.gate_up.weight"],
                     p["moe.shared_experts.down.weight"], precision)
    return _r(routed, precision) + shared, counts


def block(x, p, group, kind, cfg, precision="float32"):
    """One layer on ONE sequence: ``(x, tokens per expert held here)``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = attention(x, p, kind, cfg, precision)
    a = _r(_rms(x, p["ln_2.weight"], float(cfg["rms_norm_eps"])), precision)
    if group.endswith("_dense"):
        return _r(x + _swiglu(a, p["mlp.gate_up.weight"],
                              p["mlp.down.weight"], precision),
                  precision), None
    y, counts = expert_ffn(a, p, cfg, precision)
    return _r(x + y, precision), counts


def logits_of(x, params, cfg, precision="float32"):
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    w = params["head"]["lm_head.weight"].astype(jnp.float32)
    return _mm(_r(_rms(x, g, float(cfg["rms_norm_eps"])), precision), w,
               precision)


def layer_runs(cfg):
    """``[(group, kind, first index in the group's stack, layers)]``: the
    layers in their own order, neighbours of one make together (a run is
    one ``lax.scan``: its body is compiled once)."""
    ids, runs = layer_ids(cfg), []
    for layer in range(int(cfg["num_hidden_layers"])):
        group = group_of(cfg, layer)
        if runs and runs[-1][0] == group:
            runs[-1][3].append(layer)
        else:
            runs.append((group, cfg["layer_types"][layer],
                         ids[group].index(layer), [layer]))
    return runs


def forward_row(params, row, cfg, precision="float32"):
    """(logits [T, vocab], counts [expert layers, held]) of ONE row; the
    layers in their own order, each from its group's stack."""
    x = params["embed"]["weight"].astype(jnp.float32)[row]
    counts = []
    for group, kind, first, layers in layer_runs(cfg):
        blk = jax.checkpoint(lambda c, p, group=group, kind=kind: block(
            c, p, group, kind, cfg, precision))
        x, c = jax.lax.scan(blk, x, jax.tree_util.tree_map(
            lambda a: a[first:first + len(layers)], params[group]))
        if c is not None:
            counts.append(c)
    return logits_of(x, params, cfg, precision), \
        (jnp.concatenate(counts) if counts else None)


def _row_loss_sum(params, row, labels, cfg, precision):
    logits, counts = forward_row(params, row, cfg, precision)
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[1:, None], -1)), counts


# ------------------------------------------------------ norms by leaf ----
def keyed(tree, cfg):
    """Stacked tree -> ``{(group.leaf, "stacked" | None): array}``."""
    out = {(f"{g}.{n}", None): a for g in ("embed", "head")
           for n, a in tree[g].items()}
    for group in layer_ids(cfg):
        out.update({(f"{group}.{n}", "stacked"): a
                    for n, a in tree[group].items()})
    return out


def _unstacked(tree, cfg):
    """Stacked tree -> ``{(group.leaf, layer index | None): array}``, the
    form a program's state is read in."""
    ids = layer_ids(cfg)
    out = {}
    for (name, layer), a in keyed(tree, cfg).items():
        if layer is None:
            out[(name, None)] = a
        else:
            out.update({(name, l): a[j] for j, l in
                        enumerate(ids[name.split(".", 1)[0]])})
    return out


def norms(arrays, cfg=None):
    """L2 norm per leaf and layer of ``{(group.leaf, layer): array}``
    (``layer``: an index, ``None``, or ``"stacked"``, which needs ``cfg``
    to name the layers)."""
    keys = sorted(arrays, key=str)
    got = jax.jit(lambda xs: [_norm(x, 1 if layer == "stacked" else 0)
                              for (_, layer), x in zip(keys, xs)])(
        [arrays[k] for k in keys])
    out = {}
    for (name, layer), v in zip(keys, got):
        if layer == "stacked":
            ids = layer_ids(cfg)[name.split(".", 1)[0]]
            out.update({(name, l): float(x) for l, x in zip(ids, v)})
        else:
            out[(name, layer)] = float(v)
    return out


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
def change_cosines(against, p0, delta, cfg):
    """``cos(a - p0, delta)`` per leaf and layer of ``against``
    (``{(group.leaf, layer): array}``, someone else's parameters after the
    same steps), and over all leaves at once.  ``p0`` and ``delta`` are
    stacked trees: the starting weights and the reference's own change.  A
    leaf neither side moved reads 1, a leaf only one side moved 0."""
    ids = layer_ids(cfg)

    @jax.jit
    def dots(a, start, d):
        mine = a.astype(jnp.float32) - start
        return jnp.vdot(mine, d), jnp.vdot(mine, mine), jnp.vdot(d, d)

    out, total = {}, [0.0, 0.0, 0.0]
    for (name, layer), a in against.items():
        group, leaf = name.split(".", 1)
        start, d = p0[group][leaf], delta[group][leaf]
        if layer is not None:
            j = ids[group].index(layer)
            start, d = start[j], d[j]
        md, mm, dd = (float(v) for v in dots(jnp.asarray(a), start, d))
        total = [t + v for t, v in zip(total, (md, mm, dd))]
        if mm == 0.0 or dd == 0.0:
            out[(name, layer)] = 1.0 if mm == dd else 0.0
        else:
            out[(name, layer)] = md / math.sqrt(mm * dd)
    return out, total[0] / max(math.sqrt(total[1] * total[2]), 1e-30)


def train_reference(seed, cfg, batches, hp, param_dtype, precision="float32",
                    shard=None, against=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights, as ``reference/mla_moe.py train_reference`` does (float32
    arithmetic, parameters on ``param_dtype``'s grid, rows summed into a
    donated accumulator, earlier gradients waiting on the host), and
    return what it returns: ``losses``, ``first_grad_norms``,
    ``param_change_norms``, ``expert_counts`` (per step, the tokens each
    expert held here received in each expert layer) and, with ``against``
    (somebody else's parameters after the same steps, keyed ``(group.leaf,
    layer)``), ``param_change_cosines``, ``param_change_cosine_all`` and
    this run's own ``params`` on the host."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

    def start():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      init_params(seed, cfg, param_dtype))

    @jax.jit
    def row_grad(p, row, labels):
        return jax.value_and_grad(_row_loss_sum, has_aux=True)(
            p, row, labels, cfg, precision)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scale(g, s):
        return jax.tree_util.tree_map(lambda a: a * s, g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(p, grads_so_far, lr):
        def leaf(p, *gs):
            m = jnp.zeros_like(p)
            v = jnp.zeros_like(p)
            for k, g in enumerate(gs[:-1], start=1):
                _, m, v = adamw_update(p, g, m, v, k, lr, hp)
            return store(adamw_update(p, gs[-1], m, v, len(gs), lr, hp)[0])
        return jax.tree_util.tree_map(leaf, p, *grads_so_far)

    def batch_grad(p, ids, labels):
        total, acc, counts = 0.0, None, 0
        for r in range(ids.shape[0]):
            (loss, c), g = row_grad(p, ids[r], labels[r])
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
                first_grad_norms = norms(keyed(g, cfg), cfg)
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
                jax.tree_util.tree_map(lambda a: a.astype(param_dtype), p)),
                cfg)
        p0 = start()
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b), donate_argnums=(0,))(p, p0)
        change = norms(keyed(delta, cfg), cfg)
        if against is not None:
            out["param_change_cosines"], out["param_change_cosine_all"] = \
                change_cosines(against, p0, delta, cfg)
    return {"losses": losses, "first_grad_norms": first_grad_norms,
            "param_change_norms": change, "expert_counts": counts, **out}
