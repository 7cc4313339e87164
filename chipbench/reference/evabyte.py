"""Plain reference for a byte-level decoder whose attention is exact inside
a block-aligned window and reads pooled chunk summaries of every window
before it, with several next-byte heads (``model_type`` ``evabyte``,
``attention_class`` ``eva``; the equations are those of ISSUE 32, from the
source's ``config.json`` and arXiv:2302.04542).  With ``W`` =
``window_size``, ``C`` = ``chunk_size``, ``n`` heads of ``D``, ``s = D **
-0.5``, ``T`` a multiple of ``W``:

    x float32 [T, H];  a = rms(x) * (1 + g_1)
    q, k, v = W_q a, W_k a, W_v a -> [T, n, D];  rotary (rotate-half, base
    ``rope_theta``, all of D) on q and k
    per head h and chunk c (tokens cC .. cC + C - 1), learned mu_h, phi_h:
      alpha_j = softmax_{j in c}  s * (mu_h . k_j);   kt_c = sum_j alpha_j k_j
      gamma_j = softmax_{j in c}  s * (phi_h . k_j - |k_j|^2 / 2)
      vt_c = sum_j gamma_j v_j
    query t attends, under ONE softmax of scale s, to the exact keys
      E(t) = { j : j // W = t // W, j <= t }  and the summaries
      R(t) = { c : cC // W < t // W }  (every chunk of every EARLIER window)
    x <- x + W_o concat_h(o);  b = rms(x) * (1 + g_2)
    x <- x + W_down(silu(W_gate b) * W_up b)
    z = rms(x) * (1 + g_f);  logits = W_head z -> [T, P, V]
    loss = mean over p of mean over { t : t + 1 + p < T } of
           CE(logits[t, p], ids[t + 1 + p])

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel; the scores of a
query against EVERY key and EVERY summary of the row, under explicit masks
built from the positions.  It imports nothing of the program.

Departures that change no value, made so that float32 at 16,384 bytes a row
fits a 16 GB chip: attention runs a head and a block of query rows at a
time (``lax.map``; one block's scores are ``[rows, T + T / C]``), each head
and each block of rows recomputed for its backward; the MLP runs a block of
rows at a time; every layer is recomputed for its backward.  Departures
from a deployment: weights are random from the seed.

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .laguna import _rope
from .mla_moe import _norm, _swiglu

ROWS = 2048         # query (and MLP) rows a block, where the row is longer

# leaf -> (shape over the sizes, kind); names are the program's
_LAYER = {
    "ln_1.weight": (lambda z: (z["h"],), "offset"),
    "attn.mu": (lambda z: (z["n"], z["d"]), "pool"),
    "attn.phi": (lambda z: (z["n"], z["d"]), "pool"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["h"]), "w"),
    "attn.k_proj.weight": (lambda z: (z["h"], z["h"]), "w"),
    "attn.v_proj.weight": (lambda z: (z["h"], z["h"]), "w"),
    "attn.o_proj.weight": (lambda z: (z["h"], z["h"]), "w_out"),
    "ln_2.weight": (lambda z: (z["h"],), "offset"),
    "mlp.gate_up.weight": (lambda z: (z["h"], 2 * z["inter"]), "w"),
    "mlp.down.weight": (lambda z: (z["inter"], z["h"]), "w_out"),
}


def sizes(cfg):
    h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"h": h, "n": n, "d": h // n,
            "inter": int(cfg["intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "window": int(cfg["window_size"]),
            "chunk": int(cfg["chunk_size"]),
            "heads_out": int(cfg["num_pred_heads"]),
            "vocab": int(cfg["vocab_size"])}


def _draw(key, shape, kind, z, std, dtype):
    """One seeded leaf: weights ``N(0, std)``, the two residual projections
    times ``1 / sqrt(2 L)``; a gain's offset from one 0; a pooling vector
    ``N(0, 1)`` clipped to ``[-1, 1]`` times ``D ** -0.5``."""
    if kind == "offset":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "pool":
        x = jnp.clip(x, -1.0, 1.0) * z["d"] ** -0.5
    else:
        x = x * (std / math.sqrt(2 * z["layers"]) if kind == "w_out"
                 else std)
    return to_grid(x, dtype).astype(dtype)


def layer_params(key, layer, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``."""
    z, std = sizes(cfg), float(cfg["init_std"])
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _draw(jax.random.fold_in(lkey, j), shape(z), kind, z, std,
                        dtype)
            for j, (name, (shape, kind)) in enumerate(_LAYER.items())}


def outer_params(key, cfg, dtype):
    z, std = sizes(cfg), float(cfg["init_std"])
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, z, std, dtype)
    return {"embed": {"weight": mk(0, (z["vocab"], z["h"]), "w")},
            "head": {"ln_f.weight": mk(1, (z["h"],), "offset"),
                     "lm_head.weight": mk(
                         2, (z["h"], z["heads_out"] * z["vocab"]), "w")}}


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
def _rms1(x, g, eps):
    """``rms(x) * (1 + g)``: the gain is stored as its offset from one."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g)


def rope_angles(cfg, seq):
    z = sizes(cfg)
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, z["d"], 2, dtype=np.float64) / z["d"])
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def pool(k, v, mu, phi, cfg, precision="float32"):
    """``k, v [T, n, D]`` -> the chunks' keys and values ``[T / C, n, D]``."""
    z = sizes(cfg)
    t, c, s = k.shape[0], z["chunk"], z["d"] ** -0.5
    kc = k.reshape(t // c, c, z["n"], z["d"])
    vc = v.reshape(t // c, c, z["n"], z["d"])
    alpha = jax.nn.softmax(s * jnp.einsum("gcnd,nd->gcn", kc, mu), axis=1)
    gamma = jax.nn.softmax(
        s * (jnp.einsum("gcnd,nd->gcn", kc, phi)
             - 0.5 * jnp.sum(kc * kc, axis=-1)), axis=1)
    r = lambda a: _r(a, precision)      # noqa: E731
    return (r(jnp.einsum("gcn,gcnd->gnd", r(alpha), kc)),
            r(jnp.einsum("gcn,gcnd->gnd", r(gamma), vc)))


def seen(rows, t, cfg):
    """The explicit mask of queries ``rows`` (positions) over ``[T exact
    keys | T / C summaries]``: an exact key of the query's own window up to
    the query itself; a summary whose chunk lies in an earlier window."""
    z = sizes(cfg)
    w, c = z["window"], z["chunk"]
    keys, chunks = jnp.arange(t), jnp.arange(t // c)
    own = (keys[None, :] // w == rows[:, None] // w) \
        & (keys[None, :] <= rows[:, None])
    earlier = (chunks[None, :] * c) // w < rows[:, None] // w
    return jnp.concatenate([own, earlier], axis=1)


def attend(q, k, v, kt, vt, cfg, precision="float32", rows=ROWS):
    """``o [T, n, D]``: a head and a block of query rows at a time, the
    scores of the block against every key and every summary, masked."""
    z = sizes(cfg)
    t, s = q.shape[0], z["d"] ** -0.5
    rows = min(rows, t)
    if t % rows:
        raise ValueError(f"{t} rows in blocks of {rows}")
    starts = jnp.arange(0, t, rows)

    @jax.checkpoint
    def head(qkv):
        q_h, keys, values = qkv                 # [T, D], [T + T/C, D] x 2

        @jax.checkpoint
        def block(start):
            pos = start + jnp.arange(rows)
            q_b = jax.lax.dynamic_slice_in_dim(q_h, start, rows)
            sc = jnp.where(seen(pos, t, cfg), s * (q_b @ keys.T), -jnp.inf)
            return _r(jax.nn.softmax(sc, axis=-1), precision) @ values

        return jax.lax.map(block, starts).reshape(t, z["d"])

    by_head = lambda a, b: jnp.concatenate(     # noqa: E731
        [jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)], axis=1)
    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), by_head(k, kt),
                           by_head(v, vt)))
    return jnp.moveaxis(o, 0, 1)


def attention(x, p, cfg, precision="float32"):
    """``x + attn(rms(x))`` on ONE sequence ``x [T, H]``."""
    z = sizes(cfg)
    t, n, d = x.shape[0], z["n"], z["d"]
    r = lambda a: _r(a, precision)      # noqa: E731
    a = r(_rms1(x, p["ln_1.weight"], float(cfg["rms_norm_eps"])))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, n, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, n, d)
    cos, sin = rope_angles(cfg, t)
    q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    kt, vt = pool(k, v, p["attn.mu"], p["attn.phi"], cfg, precision)
    o = r(attend(q, k, v, kt, vt, cfg, precision)).reshape(t, n * d)
    return r(x + _mm(o, p["attn.o_proj.weight"], precision))


def mlp(x, p, cfg, precision="float32", rows=ROWS):
    """``x + mlp(rms(x))``, a block of rows at a time."""
    t = x.shape[0]
    rows = min(rows, t)

    @jax.checkpoint
    def block(x_b):
        b = _r(_rms1(x_b, p["ln_2.weight"], float(cfg["rms_norm_eps"])),
               precision)
        return _r(x_b + _swiglu(b, p["mlp.gate_up.weight"],
                                p["mlp.down.weight"], precision), precision)

    return jax.lax.map(block, x.reshape(t // rows, rows, -1)) \
        .reshape(x.shape)


def block(x, p, cfg, precision="float32"):
    """One layer on ONE sequence."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    return mlp(attention(x, p, cfg, precision), p, cfg, precision)


def logits_of(x, params, cfg, precision="float32"):
    """``[T, P, V]``."""
    z = sizes(cfg)
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    w = params["head"]["lm_head.weight"].astype(jnp.float32)
    out = _mm(_r(_rms1(x, g, float(cfg["rms_norm_eps"])), precision), w,
              precision)
    return out.reshape(x.shape[0], z["heads_out"], z["vocab"])


def forward_row(params, row, cfg, precision="float32"):
    """Logits ``[T, P, V]`` of ONE row."""
    x = params["embed"]["weight"].astype(jnp.float32)[row]
    blk = jax.checkpoint(lambda c, p: (block(c, p, cfg, precision), None))
    x, _ = jax.lax.scan(blk, x, params["blocks"])
    return logits_of(x, params, cfg, precision)


def row_loss(params, row, labels, cfg, precision="float32"):
    """One row's loss: the mean over the heads of head ``p``'s mean cross
    entropy over the positions ``t`` with ``t + 1 + p < T``."""
    lp = jax.nn.log_softmax(forward_row(params, row, cfg, precision), -1)
    t, heads = lp.shape[0], lp.shape[1]
    total = 0.0
    for p in range(heads):
        picked = jnp.take_along_axis(lp[:t - 1 - p, p],
                                     labels[1 + p:, None], -1)
        total = total - jnp.mean(picked)
    return total / heads


# ------------------------------------------------------ norms by leaf ----
def keyed(tree):
    """Stacked tree -> ``{(group.leaf, "stacked" | None): array}``."""
    out = {(f"{g}.{n}", None): a for g in ("embed", "head")
           for n, a in tree[g].items()}
    out.update({(f"blocks.{n}", "stacked"): a
                for n, a in tree["blocks"].items()})
    return out


def norms(arrays):
    """L2 norm per leaf and layer of ``{(group.leaf, layer): array}``
    (``layer``: an index, ``None``, or ``"stacked"``: one norm a layer)."""
    names = sorted(arrays, key=str)
    got = jax.jit(lambda xs: [_norm(x, 1 if layer == "stacked" else 0)
                              for (_, layer), x in zip(names, xs)])(
        [arrays[k] for k in names])
    out = {}
    for (name, layer), v in zip(names, got):
        if layer == "stacked":
            out.update({(name, l): float(x) for l, x in enumerate(v)})
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
                    shard=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights, as ``reference/laguna.py train_reference`` does (float32
    arithmetic, parameters on ``param_dtype``'s grid after every step, rows
    summed into a donated accumulator, earlier gradients waiting on the
    host so that the moments are formed again and never stored), and
    return ``losses``, ``first_grad_norms`` and ``param_change_norms``."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

    def start():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      init_params(seed, cfg, param_dtype))

    @jax.jit
    def row_grad(p, row, labels):
        return jax.value_and_grad(row_loss)(p, row, labels, cfg, precision)

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
        total, acc = 0.0, None
        for r in range(ids.shape[0]):
            loss, g = row_grad(p, ids[r], labels[r])
            total += float(loss)
            acc = g if acc is None else add(acc, g)
            del g
        n = ids.shape[0]
        return total / n, (acc if n == 1
                           else scale(acc, jnp.float32(1.0 / n)))

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms = [], [], None
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g = batch_grad(p, jnp.asarray(ids), jnp.asarray(labels))
            losses.append(loss)
            if first_grad_norms is None:
                first_grad_norms = norms(keyed(g))
            earlier = tuple(jax.tree_util.tree_map(jnp.asarray, h)
                            for h in waiting)
            p = update(p, earlier + (g,), jnp.float32(hp["learning_rate"]))
            del earlier
            if k < len(batches):
                waiting.append(jax.device_get(g))
            del g
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b), donate_argnums=(0,))(p, start())
        change = norms(keyed(delta))
    return {"losses": losses, "first_grad_norms": first_grad_norms,
            "param_change_norms": change}
