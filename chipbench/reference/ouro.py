"""Plain reference for a decoder whose WHOLE layer stack runs ``R`` times
over the same parameters, the head reading the state after every pass and a
learned exit gate weighing each pass's loss (``model_type`` ``ouro``; the
equations are those of ISSUE 40, from the source's ``config.json`` and
arXiv:2510.25741).  With ``T`` tokens a row, ``H`` the hidden size, ``n``
heads of ``D``, ``R`` = ``total_ut_steps``, ``L`` layers, ``rms(x; g) = x /
sqrt(mean(x^2) + eps) * g``:

    block(x):
      a = rms(x; g_1a);  q, k, v = W_q a, W_k a, W_v a -> [T, n, D]
      q, k <- rotary (rotate-half over all of D, base ``rope_theta``)
      o = causal softmax(q k^T / sqrt(D)) v;   x <- x + rms(W_o o; g_1b)
      b = rms(x; g_2a);  x <- x + rms(W_down(silu(W_gate b) * W_up b); g_2b)

    h_0 = E[ids]
    for r = 1 .. R:  h_r = rms(block_L(... block_1(h_{r-1})); g_f)
      logits_r = W_head h_r;  lambda_r = sigmoid(w_g . h_r + b_g)
      l_r[t] = CE(logits_r[t], ids[t + 1]),  t = 0 .. T - 2
    S_r = prod_{j<r} (1 - lambda_j);  p_r = lambda_r S_r (r < R);  p_R = S_R
    loss = mean_t [ sum_r p_r[t] l_r[t] - beta H(p[t]) ]
      H(p) = - sum_r p_r log max(p_r, 1e-30)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel; the passes are a
plain Python loop over ONE stack of block parameters, so a weight's gradient
is the sum of what its ``R`` applications give by differentiation alone.  It
imports nothing of the program; the small pieces the other families'
references already hold (``_rms``, ``_swiglu``, the rotate-half ``_rope``,
the per-leaf ``norms``) are theirs.

Departures that change no value, made so that float32 at 4,096 tokens a row
and 49,152 logits a token fits a 16 GB chip: attention runs a head at a time
(``lax.map``; one head's scores are ``[T, T]``), each head recomputed for
its backward; every block application and every pass's head, cross entropy
and gate are recomputed for their backward.  Departures from a deployment:
weights are random from the seed (gains ``1 + N(0, std)`` and the gate's
bias ``N(0, std)`` instead of 1 and 0, so that a dropped gain or bias
changes the output).

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits).
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

# leaf -> (shape over the sizes, kind); names are the program's
_LAYER = {
    "ln_1.weight": (lambda z: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["d"]), "w"),
    "attn.k_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.v_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.o_proj.weight": (lambda z: (z["n"] * z["d"], z["h"]), "w_out"),
    "ln_1b.weight": (lambda z: (z["h"],), "gain"),
    "ln_2.weight": (lambda z: (z["h"],), "gain"),
    "mlp.gate_up.weight": (lambda z: (z["h"], 2 * z["inter"]), "w"),
    "mlp.down.weight": (lambda z: (z["inter"], z["h"]), "w_out"),
    "ln_2b.weight": (lambda z: (z["h"],), "gain"),
}
_HEAD = {
    "ln_f.weight": (lambda z: (z["h"],), "gain"),
    "lm_head.weight": (lambda z: (z["h"], z["vocab"]), "w"),
    "exit_gate.weight": (lambda z: (z["h"], 1), "w"),
    "exit_gate.bias": (lambda z: (1,), "w"),
}


def sizes(cfg):
    n = int(cfg["num_attention_heads"])
    return {"h": int(cfg["hidden_size"]), "n": n,
            "kv": int(cfg.get("num_key_value_heads") or n),
            "d": int(cfg["head_dim"]),
            "inter": int(cfg["intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "passes": int(cfg["total_ut_steps"]),
            "vocab": int(cfg["vocab_size"])}


def _draw(key, shape, kind, z, std, dtype):
    """One seeded leaf: weights (and the gate's bias) ``N(0, std)``, the two
    residual projections times ``1 / sqrt(2 L)`` (the layers HELD, whatever
    the passes); gains ``1 + N(0, std)``."""
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = 1.0 + std * x
    else:
        x = x * (std / math.sqrt(2 * z["layers"]) if kind == "w_out"
                 else std)
    return to_grid(x, dtype).astype(dtype)


def layer_params(key, layer, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``."""
    z, std = sizes(cfg), float(cfg.get("initializer_range", 0.02))
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _draw(jax.random.fold_in(lkey, j), shape(z), kind, z, std,
                        dtype)
            for j, (name, (shape, kind)) in enumerate(_LAYER.items())}


def outer_params(key, cfg, dtype):
    z, std = sizes(cfg), float(cfg.get("initializer_range", 0.02))
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, z, std, dtype)
    return {"embed": {"weight": mk(0, (z["vocab"], z["h"]), "w")},
            "head": {name: mk(1 + j, shape(z), kind)
                     for j, (name, (shape, kind)) in enumerate(
                         _HEAD.items())}}


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
    z = sizes(cfg)
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, z["d"], 2, dtype=np.float64) / z["d"])
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def attend(q, k, v, precision="float32"):
    """``o [T, n, D]`` of ``q [T, n, D]`` over ``k, v [T, kv, D]``: a head
    at a time, its ``[T, T]`` scores under the causal mask."""
    t, n, d = q.shape
    group = n // k.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        q_h, k_h, v_h = qkv
        sc = jnp.where(causal, (q_h @ k_h.T) / math.sqrt(d), -jnp.inf)
        return _r(jax.nn.softmax(sc, axis=-1), precision) @ v_h

    by_head = lambda a, rep: jnp.repeat(         # noqa: E731
        jnp.moveaxis(a, 1, 0), rep, axis=0)
    return jnp.moveaxis(
        jax.lax.map(head, (by_head(q, 1), by_head(k, group),
                           by_head(v, group))), 0, 1)


def block(x, p, cfg, precision="float32"):
    """One layer on ONE sequence ``x [T, H]``."""
    z, eps = sizes(cfg), float(cfg["rms_norm_eps"])
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t, n, kv, d = x.shape[0], z["n"], z["kv"], z["d"]
    r = lambda a: _r(a, precision)      # noqa: E731
    a = r(_rms(x, p["ln_1.weight"], eps))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, kv, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, kv, d)
    cos, sin = rope_angles(cfg, t)
    q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    o = r(attend(q, k, v, precision)).reshape(t, n * d)
    x = r(x + r(_rms(_mm(o, p["attn.o_proj.weight"], precision),
                     p["ln_1b.weight"], eps)))
    b = r(_rms(x, p["ln_2.weight"], eps))
    m = _swiglu(b, p["mlp.gate_up.weight"], p["mlp.down.weight"], precision)
    return r(x + r(_rms(m, p["ln_2b.weight"], eps)))


def exit_head_loss(h, head, labels, precision="float32"):
    """One pass's ``(l [T - 1], lambda [T - 1])`` of its normed state ``h
    [T, H]``: position ``t`` is held to ``labels[t + 1]``."""
    w = head["lm_head.weight"].astype(jnp.float32)
    lp = jax.nn.log_softmax(_mm(h[:-1], w, precision), -1)
    each = -jnp.take_along_axis(lp, labels[1:, None], -1)[:, 0]
    lam = jax.nn.sigmoid(
        h[:-1] @ head["exit_gate.weight"].astype(jnp.float32)[:, 0]
        + head["exit_gate.bias"].astype(jnp.float32)[0])
    return each, lam


def exit_distribution(lams):
    """``lams``: the passes' ``lambda [T']`` (the last one's is not read)
    -> ``(p [R, T'], entropy [T'])``."""
    left, p = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    p = jnp.stack(p + [left])
    return p, -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)


def passes_of(params, row, labels, cfg, precision="float32"):
    """``(l [R, T - 1], lambda [R, T - 1])`` of ONE row: the stack over the
    one list of block parameters, ``R`` times."""
    eps = float(cfg["rms_norm_eps"])
    blk = jax.checkpoint(lambda c, p: (block(c, p, cfg, precision), None))
    tail = jax.checkpoint(
        lambda h, head: exit_head_loss(h, head, labels, precision))
    g_f = params["head"]["ln_f.weight"].astype(jnp.float32)
    x = params["embed"]["weight"].astype(jnp.float32)[row]
    each, lams = [], []
    for _ in range(sizes(cfg)["passes"]):
        x, _ = jax.lax.scan(blk, x, params["blocks"])
        x = _r(_rms(x, g_f, eps), precision)
        l_r, lam_r = tail(x, params["head"])
        each.append(l_r)
        lams.append(lam_r)
    return jnp.stack(each), jnp.stack(lams)


def row_loss(params, row, labels, cfg, precision="float32", beta=None):
    """``(loss, (pass_loss [R], exit_mass [R]))`` of one row."""
    beta = float(cfg["exit_entropy_beta"]) if beta is None else beta
    each, lams = passes_of(params, row, labels, cfg, precision)
    p, entropy = exit_distribution(list(lams))
    loss = jnp.mean(jnp.sum(p * each, axis=0) - beta * entropy)
    return loss, (jnp.mean(each, axis=1), jnp.mean(p, axis=1))


# ------------------------------------------------------ norms by leaf ----
def _unstacked(tree):
    """Stacked tree -> ``{(group.leaf, layer index | None): array}``, the
    form a program's state is read in."""
    out = {}
    for (name, layer), a in keyed(tree).items():
        if layer is None:
            out[(name, None)] = a
        else:
            out.update({(name, l): a[l] for l in range(a.shape[0])})
    return out


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


def change_cosines(against, p0, delta):
    """``cos(a - p0, delta)`` per leaf and layer of ``against``
    (``{(group.leaf, layer): array}``, someone else's parameters after the
    same steps), and over all leaves at once.  ``p0`` and ``delta`` are
    stacked trees: the starting weights and the reference's own change.  A
    leaf neither side moved (an update under a bfloat16 step is lost, on
    both sides alike: the gains) reads 1, a leaf only one side moved 0."""
    @jax.jit
    def dots(a, start, d):
        mine = a.astype(jnp.float32) - start
        return jnp.vdot(mine, d), jnp.vdot(mine, mine), jnp.vdot(d, d)

    out, total = {}, [0.0, 0.0, 0.0]
    for (name, layer), a in against.items():
        group, leaf = name.split(".", 1)
        start, d = p0[group][leaf], delta[group][leaf]
        if layer is not None:
            start, d = start[layer], d[layer]
        md, mm, dd = (float(v) for v in dots(jnp.asarray(a), start, d))
        total = [t + v for t, v in zip(total, (md, mm, dd))]
        if mm == 0.0 or dd == 0.0:
            out[(name, layer)] = 1.0 if mm == dd else 0.0
        else:
            out[(name, layer)] = md / math.sqrt(mm * dd)
    return out, total[0] / max(math.sqrt(total[1] * total[2]), 1e-30)


# ----------------------------------------------------------- training ----
def train_reference(seed, cfg, batches, hp, param_dtype, precision="float32",
                    shard=None, against=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights, as ``reference/laguna.py train_reference`` does (float32
    arithmetic, parameters on ``param_dtype``'s grid after every step, rows
    summed into a donated accumulator, earlier gradients waiting on the
    host so that the moments are formed again and never stored), and
    return ``losses``, ``first_grad_norms``, ``param_change_norms``, per
    step ``pass_losses`` and ``exit_masses`` (``[R]`` each) and, with
    ``against`` (somebody else's parameters after the same steps, keyed
    ``(group.leaf, layer)``), ``param_change_cosines``,
    ``param_change_cosine_all`` and this run's own ``params`` on the
    host."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

    def start():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      init_params(seed, cfg, param_dtype))

    @jax.jit
    def row_grad(p, row, labels):
        return jax.value_and_grad(row_loss, has_aux=True)(
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
        n, total, acc, seen = ids.shape[0], 0.0, None, 0.0
        for r in range(n):
            (loss, aux), g = row_grad(p, ids[r], labels[r])
            total += float(loss)
            seen = seen + np.asarray(jax.device_get(aux), np.float64)
            acc = g if acc is None else add(acc, g)
            del g
        return total / n, (acc if n == 1 else scale(
            acc, jnp.float32(1.0 / n))), seen / n

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms, seen = [], [], None, []
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g, aux = batch_grad(p, jnp.asarray(ids),
                                      jnp.asarray(labels))
            losses.append(loss)
            seen.append(aux)
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
                jax.tree_util.tree_map(lambda a: a.astype(param_dtype), p)))
        p0 = start()
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b), donate_argnums=(0,))(p, p0)
        change = norms(keyed(delta))
        if against is not None:
            out["param_change_cosines"], out["param_change_cosine_all"] = \
                change_cosines(against, p0, delta)
    return {"losses": losses, "first_grad_norms": first_grad_norms,
            "param_change_norms": change,
            "pass_losses": [s[0].tolist() for s in seen],
            "exit_masses": [s[1].tolist() for s in seen], **out}
