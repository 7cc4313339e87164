"""Plain reference for a latent-attention decoder with routed and shared
experts (``model_type`` ``deepseek_v3``; the equations are those of the
public modelling code and of the source's ``config.json``):

    x <- x + attn(rms(x));  x <- x + ffn(rms(x));  logits = W_head rms(x)

    attn:  q = W_q a -> heads x (nope | rope);  [c | k_r] = W_kva a;
           [k_n | v] = W_kvb rms(c) -> heads x (nope | v);
           rotary (theta, pairs (0,1), (2,3), ... as published) on q's rope
           part and on k_r, which every head shares;
           softmax(causal((q_n . k_n + q_r . k_r) / sqrt(nope + rope))) v
    ffn:   layer < first_k_dense_replace: down(silu(gate a) * up a);
           later:  s = sigmoid(W_g a) in float32 over ALL the router's
           experts; the k largest of s + b; w = s / (sum of the chosen +
           1e-20) * scale;  sum_i w_i E_i(a) + S(a)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no grouped
matmul.  The expert layer is a loop over the experts held here, each
applied to every token and weighted by what the router gave it (zero for
a token that did not choose it).  It imports nothing of the program.

**The chip's share.**  ``n_routed_experts`` experts are held here, from
``expert_offset`` on, of the ``router_experts`` the router scores; the
part the absent experts would add is left out, as the program leaves it
out, and that partial result goes on to the next layer.  An expert's
seeded weights depend on its GLOBAL index, so the shares of one seed are
slices of one uncut layer.  ``vocab_size`` is the slice of the vocabulary
held: ids, logits and loss are over it.

Departures that change no value, made so that float32 at sequence 8192
fits a 16 GB chip: the rotary pairs are rotated where they lie (the
published code de-interleaves them into halves first: the same scores);
attention runs head by head (``lax.map``, each head recomputed for its
backward: one head's float32 scores are 268 MB, all 32 would be 8.6 GB);
the experts run one after the other (``lax.scan``), each recomputed for its
backward; every layer is recomputed for its backward; rows are summed
into a donated accumulator, as ``reference/gpt.py`` does.  Departures from
a deployment: weights are random from the seed, gains are 1 + noise so
that a dropped gain shows, ``e_score_correction_bias`` is zero and fixed.

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router's own
matmul stays float32 there too, as the configuration states it.
"""

import functools
import math

import jax
import jax.numpy as jnp

from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid

# leaf -> (shape builder over the sizes, kind); names are the program's
_ATTN = {
    "ln_1.weight": (lambda z: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["qk"]), "w"),
    "attn.kv_a_proj.weight": (lambda z: (z["h"], z["rank"] + z["rope"]),
                              "w"),
    "attn.kv_a_layernorm.weight": (lambda z: (z["rank"],), "gain"),
    "attn.kv_b_proj.weight": (
        lambda z: (z["rank"], z["n"] * (z["nope"] + z["v"])), "w"),
    "attn.o_proj.weight": (lambda z: (z["n"] * z["v"], z["h"]), "w_out"),
    "ln_2.weight": (lambda z: (z["h"],), "gain"),
}
_DENSE = {
    "mlp.gate_up.weight": (lambda z: (z["h"], 2 * z["inter"]), "w"),
    "mlp.down.weight": (lambda z: (z["inter"], z["h"]), "w_out"),
}
_MOE = {
    "moe.router.weight": (lambda z: (z["h"], z["router"]), "w"),
    "moe.experts.gate_up": (lambda z: (z["h"], 2 * z["moe_i"]), "expert_w"),
    "moe.experts.down": (lambda z: (z["moe_i"], z["h"]), "expert_w_out"),
    "moe.shared_experts.gate_up.weight": (
        lambda z: (z["h"], 2 * z["shared_i"]), "w"),
    "moe.shared_experts.down.weight": (
        lambda z: (z["shared_i"], z["h"]), "w_out"),
}
_KINDS = {"dense": {**_ATTN, **_DENSE}, "moe": {**_ATTN, **_MOE}}


def sizes(cfg):
    z = {"h": int(cfg["hidden_size"]), "n": int(cfg["num_attention_heads"]),
         "nope": int(cfg["qk_nope_head_dim"]),
         "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
         "rank": int(cfg["kv_lora_rank"]),
         "inter": int(cfg["intermediate_size"]),
         "moe_i": int(cfg["moe_intermediate_size"]),
         "held": int(cfg["n_routed_experts"]),
         "router": int(cfg.get("router_experts", cfg["n_routed_experts"])),
         "offset": int(cfg.get("expert_offset", 0)),
         "top_k": int(cfg["num_experts_per_tok"]),
         "layers": int(cfg["num_hidden_layers"]),
         "first_k": int(cfg["first_k_dense_replace"]),
         "vocab": int(cfg["vocab_size"])}
    z["qk"] = z["nope"] + z["rope"]
    z["shared_i"] = int(cfg["n_shared_experts"]) * z["moe_i"]
    return z


_KEEP = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
         "intermediate_size", "moe_intermediate_size", "n_routed_experts",
         "router_experts", "expert_offset", "n_shared_experts",
         "num_experts_per_tok", "num_hidden_layers", "first_k_dense_replace",
         "vocab_size", "rms_norm_eps", "rope_theta", "routed_scaling_factor",
         "norm_topk_prob", "initializer_range")


def _freeze(cfg):
    return tuple((k, cfg[k]) for k in _KEEP if k in cfg)


def _draw(key, shape, kind, std, out_std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = 1.0 + std * x
    else:
        x = (out_std if kind.endswith("w_out") else std) * x
    return to_grid(x, dtype).astype(dtype)


def layer_params(key, layer, kind, cfg, dtype):
    """Layer ``layer``'s leaves (``kind`` ``"dense"`` or ``"moe"``).
    Traceable in ``layer``.  An expert's leaf is drawn from its GLOBAL
    index, so a share holds a slice of the uncut layer's experts."""
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * z["layers"])
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for j, (name, (shape, what)) in enumerate(_KINDS[kind].items()):
        k = jax.random.fold_in(lkey, j)
        if what.startswith("expert_"):
            ids = z["offset"] + jnp.arange(z["held"])
            out[name] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape(z), what, std, out_std,
                dtype))(ids)
        else:
            out[name] = _draw(k, shape(z), what, std, out_std, dtype)
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


def _layer_ids(cfg):
    z = sizes(cfg)
    return {"dense": list(range(min(z["first_k"], z["layers"]))),
            "moe": list(range(z["first_k"], z["layers"]))}


def init_params(seed, cfg, dtype):
    """``{"embed", "dense", "moe", "head"}``; the layer groups hold their
    leaves stacked on a leading axis, in layer order.  One jitted call."""
    frozen = _freeze(cfg)
    ids = _layer_ids(cfg)

    @jax.jit
    def make(key):
        c = dict(frozen)
        tree = outer_params(key, c, dtype)
        for kind, layers in ids.items():
            if layers:
                tree[kind] = jax.lax.map(
                    lambda l, kind=kind: layer_params(key, l, kind, c, dtype),
                    jnp.asarray(layers))
        return tree

    return make(seed_key(seed))


# ------------------------------------------------------------ forward ----
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotary positions on ``x [T, ..., D]``, the pairs (2i, 2i+1) rotated
    where they lie by ``t * theta ** (-2i / D)``."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # [T, D/2]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    rot = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return rot.reshape(x.shape)


def attention(x, p, cfg, precision="float32"):
    """``x + attn(rms(x))`` on ONE sequence ``x [T, H]``."""
    z = sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = x.shape[0]
    r = lambda a: _r(a, precision)      # noqa: E731
    a = r(_rms(x, p["ln_1.weight"], eps))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, z["n"], z["qk"])
    kva = _mm(a, p["attn.kv_a_proj.weight"], precision)
    c = r(_rms(kva[:, :z["rank"]], p["attn.kv_a_layernorm.weight"], eps))
    kvb = _mm(c, p["attn.kv_b_proj.weight"], precision).reshape(
        t, z["n"], z["nope"] + z["v"])
    q_r = r(_rope(q[..., z["nope"]:], theta))
    k_r = r(_rope(kva[:, z["rank"]:], theta))               # [T, rope]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q_n, q_rot, k_n, v = qkv
        s = (q_n @ k_n.T + q_rot @ k_r.T) / math.sqrt(z["qk"])
        w = r(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1))
        return w @ v

    per_head = lambda a: jnp.moveaxis(a, 1, 0)      # noqa: E731
    o = jax.lax.map(head, (per_head(q[..., :z["nope"]]), per_head(q_r),
                           per_head(kvb[..., :z["nope"]]),
                           per_head(kvb[..., z["nope"]:])))
    o = r(jnp.moveaxis(o, 0, 1).reshape(t, z["n"] * z["v"]))
    return r(x + _mm(o, p["attn.o_proj.weight"], precision))


def _swiglu(a, gate_up, down, precision):
    gu = _mm(a, gate_up, precision)
    inter = down.shape[0]
    return _mm(_r(jax.nn.silu(gu[:, :inter]) * gu[:, inter:], precision),
               down, precision)


def route(a, wg, cfg):
    """(idx [T, k], weights [T, k]) in float32, as published: no bias term
    is learnt here (``e_score_correction_bias`` zero), one group."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(jnp.matmul(a, wg))
    _, idx = jax.lax.top_k(s, z["top_k"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * float(cfg["routed_scaling_factor"])


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


def block(x, p, kind, cfg, precision="float32"):
    """One layer on ONE sequence: ``(x, tokens per expert held here)``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = attention(x, p, cfg, precision)
    a = _r(_rms(x, p["ln_2.weight"], float(cfg["rms_norm_eps"])), precision)
    if kind == "dense":
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


def forward_row(params, row, cfg, precision="float32"):
    """(logits [T, vocab], counts [expert layers, held]) of ONE row."""
    x = params["embed"]["weight"].astype(jnp.float32)[row]
    counts = None
    for kind in ("dense", "moe"):
        if kind in params:
            blk = jax.checkpoint(
                lambda c, p, kind=kind: block(c, p, kind, cfg, precision))
            x, counts = jax.lax.scan(blk, x, params[kind])
    return logits_of(x, params, cfg, precision), counts


def _row_loss_sum(params, row, labels, cfg, precision):
    logits, counts = forward_row(params, row, cfg, precision)
    lp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[1:, None], -1)), counts


# ------------------------------------------------------ norms by leaf ----
def keyed(tree, cfg):
    """Stacked tree -> ``{(group.leaf, "stacked" | None): array}``; the
    group says the kind of layer, and the result of :func:`norms` names
    each layer by its index in the model."""
    out = {(f"{g}.{n}", None): a for g in ("embed", "head")
           for n, a in tree[g].items()}
    for kind in ("dense", "moe"):
        out.update({(f"{kind}.{n}", "stacked"): a
                    for n, a in tree.get(kind, {}).items()})
    return out


def _unstacked(tree, cfg):
    """Stacked tree -> ``{(group.leaf, layer index | None): array}``, the
    form a program's state is read in."""
    out = {}
    for (name, layer), a in keyed(tree, cfg).items():
        if layer is None:
            out[(name, None)] = a
        else:
            ids = _layer_ids(cfg)[name.split(".", 1)[0]]
            out.update({(name, l): a[j] for j, l in enumerate(ids)})
    return out


def _norm(x, keep):
    x = jnp.square(x.astype(jnp.float32))
    return jnp.sqrt(jnp.sum(x, axis=tuple(range(keep, x.ndim))))


def _spread(out, name, layer, value, cfg):
    """One host float per layer under ``(group.leaf, layer index)``."""
    if layer == "stacked":
        ids = _layer_ids(cfg)[name.split(".", 1)[0]]
        out.update({(name, l): float(v) for l, v in zip(ids, value)})
    else:
        out[(name, layer)] = float(value)


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
        _spread(out, name, layer, v, cfg)
    return out


def change_norms(seed, cfg, dtype, arrays):
    """``||a - p0||`` per leaf and layer, p0 the seeded starting weights,
    made again one layer at a time inside the jitted reduction."""
    key = seed_key(seed)
    frozen = dict(_freeze(cfg))
    split = lambda n: n.split(".", 1)           # noqa: E731

    @jax.jit
    def outer(k, got):
        p0 = outer_params(k, frozen, dtype)
        return {n: _norm(a.astype(jnp.float32)
                         - p0[split(n)[0]][split(n)[1]].astype(jnp.float32),
                         0) for n, a in got.items()}

    @functools.partial(jax.jit, static_argnums=(2,))
    def one_layer(k, layer, kind, got):
        p0 = layer_params(k, layer, kind, frozen, dtype)
        return {n: _norm(a.astype(jnp.float32)
                         - p0[split(n)[1]].astype(jnp.float32), 0)
                for n, a in got.items()}

    out = {}
    got = {n: a for (n, layer), a in arrays.items() if layer is None}
    _by_layer(out, outer(key, got), None)
    for layer in sorted({l for _, l in arrays if isinstance(l, int)}):
        got = {n: a for (n, l), a in arrays.items() if l == layer}
        kind = split(next(iter(got)))[0]
        _by_layer(out, one_layer(key, jnp.int32(layer), kind, got), layer)
    ids = _layer_ids(cfg)
    for kind in ("dense", "moe"):
        got = {n: a for (n, l), a in arrays.items()
               if l == "stacked" and split(n)[0] == kind}
        for j, layer in enumerate(ids[kind] if got else ()):
            _by_layer(out, one_layer(key, jnp.int32(layer), kind,
                                     {n: a[j] for n, a in got.items()}),
                      layer)
    return out


# ----------------------------------------------------------- training ----
def change_cosines(against, p0, delta, cfg):
    """``cos(a - p0, delta)`` per leaf and layer of ``against``
    (``{(group.leaf, layer): array}``, someone else's parameters after the
    same steps), and over all leaves at once.  ``p0`` and ``delta`` are
    stacked trees: the starting weights and the reference's own change.  A
    leaf neither side moved (an update under a bfloat16 step is lost, on
    both sides alike) reads 1, a leaf only one side moved 0."""
    ids = _layer_ids(cfg)

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
    weights, as ``reference/gpt.py train_reference`` does (float32
    arithmetic, parameters on ``param_dtype``'s grid, rows summed into a
    donated accumulator, earlier gradients waiting on the host).  Beside
    losses, first gradient norms and parameter change norms it returns
    ``expert_counts``: per step, the tokens each expert held here
    received in each expert layer, summed over the rows.

    ``against`` (``{(group.leaf, layer): array}``: the parameters somebody
    else reached after the same steps, on the host) adds
    ``param_change_cosines`` (:func:`change_cosines`: which WAY each leaf
    moved, which the norms cannot say), ``param_change_cosine_all``, and
    ``params``: this run's own parameters after the steps, on the host in
    the same keyed form, for a later run to be held against."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    frozen = dict(_freeze(cfg))
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

    def start():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      init_params(seed, cfg, param_dtype))

    @jax.jit
    def row_grad(p, row, labels):
        return jax.value_and_grad(_row_loss_sum, has_aux=True)(
            p, row, labels, frozen, precision)

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
