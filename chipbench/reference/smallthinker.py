"""Plain reference for an expert decoder whose ROUTER reads a block's input
before attention and whose experts are ReGLU (``model_name``
``smallthinker_21b_instruct``; the equations are those of ISSUE 51, from the
source's ``config.json`` and SmallThinker, arXiv:2507.20984).  With ``H`` the
hidden size, ``n`` q heads over ``kv`` kv heads of ``D``, ``rms(x; g) = x /
sqrt(mean(x^2) + eps) * g``, layer ``i`` on ONE row ``x [T, H]``:

    r    = x W_r                      [T, E]: the block's INPUT, un-normed
    a    = rms(x; g_1);  q, k, v = W_q a, W_k a, W_v a          (no bias)
    if rope_layout[i]:  rotate-half rotary over all of D at ``rope_theta``
                        by the position t            (else: nothing at all)
    o_h  = softmax(mask(q_h k_{h // (n / kv)}^T / sqrt(D))) v_{h // (n / kv)}
           mask: query t sees key j iff 0 <= t - j, and
           if sliding_window_layout[i]: t - j < ``sliding_window_size``
    x'   = x + concat_h(o_h) W_o
    b    = rms(x'; g_2)
    S    = the k largest of r, a token;  w = softmax(r[S]) over the chosen
    x''  = x' + sum_{e in S, held here} w_e W_down,e (relu(W_gate,e b)
                                                     * W_up,e b)     ReGLU
    logits = W_head rms(x_L; g_f)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no grouped
matmul; one head after the other, the mask written from index arithmetic
and applied to explicit scores; one expert after the other, each applied to
every token and weighted by what the router gave it (zero for a token that
did not choose it).  It imports nothing of the program; the small pieces the
other families' references already hold (``_rms``, the rotate-half
``_rope``, the per-leaf ``norms``, the cosines) are theirs.

**The chip's share.**  ``moe_num_primary_experts`` experts are held here,
from ``expert_offset`` on, of the ``router_experts`` the router scores; the
part the absent experts would add is left out, as the program leaves it out:
with no shared expert a token none of whose chosen experts is held leaves
the layer with nothing (``tokens_unserved`` counts them).  An expert's
seeded weights depend on its GLOBAL index, so the shares of one seed are
slices of one uncut layer.  ``vocab_size`` is the slice of the vocabulary
held: ids, logits and loss are over it.

Departures that change no value, made so that float32 at 16,384 positions
fits a 16 GB chip beside nothing else: attention runs a head at a time and
within a head ``Q_ROWS`` query rows at a time (``lax.map``; one head's whole
scores are 1.07 GB), each recomputed for its backward; the experts run one
after the other, each recomputed; every layer is recomputed for its
backward; head and loss run ``Q_ROWS`` positions at a time.  Departures from
a deployment: weights are random from the seed, gains are ``1 + N(0, std)``
so that a dropped gain shows, the embedding's rows ``N(0,
embedding_range)`` (the configuration says why).

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router's own
matmul, its top-k and its softmax stay float32 there too, as the
configuration states them.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .evabyte import keyed, norms
from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .laguna import _rope
from .mla_moe import _draw, _norm, _rms
from .ouro import _unstacked, change_cosines

Q_ROWS = 2048       # query rows whose scores (and logits) are formed at a time

# leaf -> (shape over the sizes, kind); names are the program's
_LAYER = {
    "ln_1.weight": (lambda z: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["d"]), "w"),
    "attn.k_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.v_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.o_proj.weight": (lambda z: (z["n"] * z["d"], z["h"]), "w_out"),
    "ln_2.weight": (lambda z: (z["h"],), "gain"),
    "moe.router.weight": (lambda z: (z["h"], z["router"]), "w"),
    "moe.experts.gate_up": (lambda z: (z["h"], 2 * z["moe_i"]), "expert_w"),
    "moe.experts.down": (lambda z: (z["moe_i"], z["h"]), "expert_w_out"),
}


def sizes(cfg):
    held = int(cfg["moe_num_primary_experts"])
    return {"h": int(cfg["hidden_size"]),
            "n": int(cfg["num_attention_heads"]),
            "kv": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]),
            "moe_i": int(cfg["moe_ffn_hidden_size"]),
            "held": held,
            "router": int(cfg.get("router_experts", held)),
            "offset": int(cfg.get("expert_offset", 0)),
            "top_k": int(cfg["moe_num_active_primary_experts"]),
            "layers": int(cfg["num_hidden_layers"]),
            "window": int(cfg["sliding_window_size"]),
            "vocab": int(cfg["vocab_size"])}


def group_of(cfg, layer):
    """Every layer holds the same leaves (``runners/laguna_train.py``
    asks); what differs by layer is the mask and the rotation."""
    return "blocks"


def layer_ids(cfg):
    return {"blocks": list(range(int(cfg["num_hidden_layers"])))}


def layer_kind(cfg, layer):
    """``(rotated, windowed)`` of layer ``layer``, from the two layouts."""
    return (bool(cfg["rope_layout"][layer]),
            bool(cfg["sliding_window_layout"][layer]))


def layer_params(key, layer, cfg, dtype):
    """Layer ``layer``'s leaves.  Traceable in ``layer``.  An expert's leaf
    is drawn from its GLOBAL index, so a share holds a slice of the uncut
    layer's experts."""
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * z["layers"])
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for j, (name, (shape, what)) in enumerate(_LAYER.items()):
        k = jax.random.fold_in(lkey, j)
        if what.startswith("expert_"):
            out[name] = jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape(z), what, std, out_std,
                dtype))(z["offset"] + jnp.arange(z["held"]))
        else:
            out[name] = _draw(k, shape(z), what, std, out_std, dtype)
    return out


def outer_params(key, cfg, dtype):
    """Embedding (rows ``N(0, embedding_range)``), final norm and head."""
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind, std=std: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, std, std, dtype)
    return {"embed": {"weight": mk(0, (z["vocab"], z["h"]), "w",
                                   float(cfg.get("embedding_range", std)))},
            "head": {"ln_f.weight": mk(1, (z["h"],), "gain"),
                     "lm_head.weight": mk(2, (z["h"], z["vocab"]), "w")}}


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
    """``(cos, sin) [seq, D / 2]`` float32: plain rotary over all of D."""
    d = int(cfg["head_dim"])
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def sees(t, j, window=None):
    """Whether query ``t`` sees key ``j`` (index arrays that broadcast):
    ``0 <= t - j``, and ``t - j < window`` under a window."""
    back = t - j
    return (back >= 0) if window is None else (back >= 0) & (back < window)


def attend(q, k, v, window=None, precision="float32"):
    """``o [T, n, D]`` of ``q [T, n, D]`` over ``k, v [T, kv, D]``: a head
    at a time and ``Q_ROWS`` query rows at a time, their ``[rows, T]``
    scores under the explicit mask."""
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
            seen = sees(first + jnp.arange(rows)[:, None], keys, window)
            sc = jnp.where(seen, (q_r @ k_h.T) / math.sqrt(d), -jnp.inf)
            return _r(jax.nn.softmax(sc, axis=-1), precision) @ v_h

        return jax.lax.map(
            some_rows, (starts, q_h.reshape(-1, rows, d))).reshape(t, d)

    by_head = lambda a, rep: jnp.repeat(         # noqa: E731
        jnp.moveaxis(a, 1, 0), rep, axis=0)
    return jnp.moveaxis(
        jax.lax.map(head, (by_head(q, 1), by_head(k, group),
                           by_head(v, group))), 0, 1)


def route(x, wg, cfg):
    """(idx [T, k], weights [T, k]) in float32 from the block's INPUT ``x``:
    the k largest logits, a softmax over the chosen."""
    top, idx = jax.lax.top_k(jnp.matmul(x, wg), sizes(cfg)["top_k"])
    return idx, jax.nn.softmax(top, axis=-1)


def reglu(b, gate_up, down, precision):
    """``down(relu(gate(b)) * up(b))``, gate | up in one matrix."""
    gu = _mm(b, gate_up, precision)
    inter = down.shape[0]
    return _mm(_r(jax.nn.relu(gu[:, :inter]) * gu[:, inter:], precision),
               down, precision)


def expert_ffn(b, idx, w, p, cfg, precision="float32"):
    """``sum_i w_i E_i(b)`` over the experts HELD HERE, the tokens each of
    them received, and the tokens that chose none of them."""
    z = sizes(cfg)

    @jax.checkpoint
    def one(carry, e_w):
        e, gate_up, down = e_w
        hit = idx == e + z["offset"]                            # [T, k]
        w_e = jnp.sum(jnp.where(hit, w, 0.0), axis=1)
        y = carry + w_e[:, None] * reglu(b, gate_up, down, precision)
        return y, jnp.sum(hit, dtype=jnp.int32)

    routed, counts = jax.lax.scan(
        one, jnp.zeros_like(b),
        (jnp.arange(z["held"]), p["moe.experts.gate_up"],
         p["moe.experts.down"]))
    here = (idx >= z["offset"]) & (idx < z["offset"] + z["held"])
    unserved = jnp.sum(~jnp.any(here, axis=1), dtype=jnp.int32)
    return _r(routed, precision), counts, unserved


def block(x, p, kind, cfg, precision="float32"):
    """One layer of ``kind`` = ``(rotated, windowed)`` on ONE row ``x [T,
    H]``: ``(x, tokens per expert held here, tokens unserved)``."""
    z, eps = sizes(cfg), float(cfg["rms_norm_eps"])
    rotated, windowed = kind
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t, n, kv, d = x.shape[0], z["n"], z["kv"], z["d"]
    r = lambda a: _r(a, precision)      # noqa: E731
    idx, w = route(x, p["moe.router.weight"], cfg)      # before attention
    a = r(_rms(x, p["ln_1.weight"], eps))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, kv, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, kv, d)
    if rotated:
        cos, sin = rope_angles(cfg, t)
        q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    o = r(attend(q, k, v, z["window"] if windowed else None,
                 precision)).reshape(t, n * d)
    x = r(x + _mm(o, p["attn.o_proj.weight"], precision))
    b = r(_rms(x, p["ln_2.weight"], eps))
    y, counts, unserved = expert_ffn(b, idx, w, p, cfg, precision)
    return r(x + y), counts, unserved


def layer_runs(cfg):
    """``[(kind, first layer, layers)]``: the layers in their own order,
    neighbours of one kind together (a run is one ``lax.scan``: its body is
    compiled once)."""
    runs = []
    for layer in range(int(cfg["num_hidden_layers"])):
        kind = layer_kind(cfg, layer)
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((kind, layer, 1))
    return runs


def stack(params, ids, cfg, precision="float32"):
    """``(x [T, H] before the final norm, counts [layers, held], unserved
    [layers])`` of ONE row."""
    x = params["embed"]["weight"].astype(jnp.float32)[ids]
    counts, unserved = [], []
    for kind, first, n in layer_runs(cfg):
        @jax.checkpoint
        def blk(x, p, kind=kind):
            x, c, u = block(x, p, kind, cfg, precision)
            return x, (c, u)

        x, (c, u) = jax.lax.scan(blk, x, jax.tree_util.tree_map(
            lambda a: a[first:first + n], params["blocks"]))
        counts.append(c)
        unserved.append(u)
    return x, jnp.concatenate(counts), jnp.concatenate(unserved)


def logits_of(x, params, cfg, precision="float32"):
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    w = params["head"]["lm_head.weight"].astype(jnp.float32)
    return _mm(_r(_rms(x, g, float(cfg["rms_norm_eps"])), precision), w,
               precision)


def forward_row(params, ids, cfg, precision="float32"):
    """(logits ``[T, vocab]``, counts ``[layers, held]``) of ONE row, all at
    once (the tests' sizes)."""
    x, counts, _ = stack(params, ids, cfg, precision)
    return logits_of(x, params, cfg, precision), counts


def row_loss_sum(params, ids, labels, cfg, precision="float32"):
    """``(sum over t < T - 1 of CE(logits_t, labels_{t+1}), (expert counts,
    tokens unserved))`` of one row, head and loss ``Q_ROWS`` positions at a
    time."""
    x, counts, unserved = stack(params, ids, cfg, precision)
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
    return total, (counts, unserved)


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
    arithmetic, parameters on ``param_dtype``'s grid after every step, rows
    summed into a donated accumulator, earlier gradients waiting on the
    host so that the moments are formed again and never stored).  Returns
    ``losses``, ``first_grad_norms``, ``param_change_norms``, per step
    ``expert_counts`` (``[layers, held]``) and ``tokens_unserved``
    (``[layers]``) and, with ``against`` (somebody else's parameters after
    the same steps, keyed ``(group.leaf, layer)``),
    ``param_change_cosines``, ``param_change_cosine_all`` and this run's own
    ``params`` on the host."""
    if shard is not None:
        raise NotImplementedError("one chip: the reference is not placed")
    cfg = dict(cfg)
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

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
        def leaf(p, *gs):
            m = jnp.zeros_like(p)
            v = jnp.zeros_like(p)
            for k, g in enumerate(gs[:-1], start=1):
                _, m, v = adamw_update(p, g, m, v, k, lr, hp)
            return store(adamw_update(p, gs[-1], m, v, len(gs), lr, hp)[0])
        return jax.tree_util.tree_map(leaf, p, *grads_so_far)

    def batch_grad(p, ids, labels):
        total, acc, counts, unserved = 0.0, None, 0, 0
        for r in range(ids.shape[0]):
            (loss, (c, u)), g = row_grad(p, ids[r], labels[r])
            total += float(loss)
            counts = counts + jax.device_get(c)
            unserved = unserved + jax.device_get(u)
            acc = g if acc is None else add(acc, g)
            del g
        n = ids.shape[0] * (ids.shape[1] - 1)
        return total / n, scale(acc, jnp.float32(1.0 / n)), counts, unserved

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms = [], [], None
        counts, unserved = [], []
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g, c, u = batch_grad(p, jnp.asarray(ids),
                                       jnp.asarray(labels))
            losses.append(loss)
            counts.append(c)
            unserved.append(u)
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
            "param_change_norms": change, "expert_counts": counts,
            "tokens_unserved": unserved, **out}
