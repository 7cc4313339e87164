"""Plain reference for an expert decoder trained to generate by diffusion
over blocks (``model_type`` ``sdar_moe``; the equations are those of ISSUE
44, from the source's ``config.json``, SDAR arXiv:2510.06303 and the
vectorised training of BD3-LMs, arXiv:2503.09573 section 3).  A data row
``x0`` of ``L`` tokens in blocks of ``B`` = ``block_length``; the feed gives
the masked copy ``xt`` (``MASK`` where a token was masked) and each block's
noise level ``t``.  With ``H`` the hidden size, ``n`` q heads over ``kv`` kv
heads of ``D``, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    row = [xt ; x0]  (2 L positions);  pos(i) = i mod L;  x = E[row]
    block(x):  a = rms(x; g_1);  q, k, v = W_q a, W_k a, W_v a
               q <- rms(q_h; g_q),  k <- rms(k_h; g_k)       head by head
               rotate-half rotary over all of D at ``rope_theta`` by pos(i)
               o = softmax(q k^T / sqrt(D) + mask) v;  x <- x + W_o o
               b = rms(x; g_2);  s = softmax(W_r b) over ALL the router's
               experts; the k largest; w = s / (sum of the chosen);
               x <- x + sum_i w_i E_i(b)         (no shared expert, scale 1)
    mask, blk(i) = (i mod L) // B: query i sees key j iff
               i <  L, j <  L:  blk(i) == blk(j)
               i <  L, j >= L:  blk(j) <  blk(i)
               i >= L, j <  L:  never
               i >= L, j >= L:  blk(j) <= blk(i)
    logits = W_head rms(x[:L]; g_f)                     the noised half alone
    loss   = 1 / (rows L) sum_{i masked} CE(logits_i, x0_i) / t_blk(i)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no grouped
matmul; the mask is written as the four cases above from index arithmetic
and applied to explicit scores.  The expert layer is a loop over the experts
held here, each applied to every position and weighted by what the router
gave it.  It imports nothing of the program; the small pieces the other
families' references already hold (``_rms``, ``_swiglu``, the rotate-half
``_rope``, the per-leaf ``norms``, the cosines) are theirs.

**The chip's share.**  ``num_experts`` experts are held here, from
``expert_offset`` on, of the ``router_experts`` the router scores; the part
the absent experts would add is left out, as the program leaves it out.  An
expert's seeded weights depend on its GLOBAL index, so the shares of one
seed are slices of one uncut layer.  ``vocab_size`` is the slice of the
vocabulary held; its last row stands for the mask token
(``mask_token_id``).

Departures that change no value, made so that float32 at ``2 L`` = 16,384
positions fits a 16 GB chip: attention runs a head at a time and within a
head ``Q_ROWS`` query rows at a time (``lax.map``; one head's whole scores
are 1.07 GB), each recomputed for its backward; the experts run one after
the other, each recomputed; every layer is recomputed for its backward.
Departures from a deployment: weights are random from the seed, gains (the
q/k gains too) are ``1 + N(0, std)`` so that a dropped gain shows.

``precision="int8"`` / ``"fp8"`` are the CONTROLS of ``reference/gpt.py``
(every matmul operand and every activation in 8 bits); the router's own
matmul stays float32 there too, as the configuration states it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .evabyte import keyed, norms
from .gpt import _by_layer, _mm, _r, adamw_update, seed_key, to_grid
from .laguna import _rope
from .mla_moe import _draw, _norm, _rms, _swiglu
from .ouro import _unstacked, change_cosines

Q_ROWS = 2048       # query rows whose scores are formed at a time

# leaf -> (shape over the sizes, kind); names are the program's
_LAYER = {
    "ln_1.weight": (lambda z: (z["h"],), "gain"),
    "attn.q_proj.weight": (lambda z: (z["h"], z["n"] * z["d"]), "w"),
    "attn.k_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.v_proj.weight": (lambda z: (z["h"], z["kv"] * z["d"]), "w"),
    "attn.o_proj.weight": (lambda z: (z["n"] * z["d"], z["h"]), "w_out"),
    "attn.q_norm.weight": (lambda z: (z["d"],), "qk_gain"),
    "attn.k_norm.weight": (lambda z: (z["d"],), "qk_gain"),
    "ln_2.weight": (lambda z: (z["h"],), "gain"),
    "moe.router.weight": (lambda z: (z["h"], z["router"]), "w"),
    "moe.experts.gate_up": (lambda z: (z["h"], 2 * z["moe_i"]), "expert_w"),
    "moe.experts.down": (lambda z: (z["moe_i"], z["h"]), "expert_w_out"),
}


def sizes(cfg):
    return {"h": int(cfg["hidden_size"]),
            "n": int(cfg["num_attention_heads"]),
            "kv": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]),
            "moe_i": int(cfg["moe_intermediate_size"]),
            "held": int(cfg["num_experts"]),
            "router": int(cfg.get("router_experts", cfg["num_experts"])),
            "offset": int(cfg.get("expert_offset", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "layers": int(cfg["num_hidden_layers"]),
            "block": int(cfg["block_length"]),
            "mask_id": int(cfg["mask_token_id"]),
            "vocab": int(cfg["vocab_size"])}


def group_of(cfg, layer):
    """Every layer is of one make (``runners/laguna_train.py`` asks)."""
    return "blocks"


def layer_ids(cfg):
    return {"blocks": list(range(int(cfg["num_hidden_layers"])))}


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
        elif what == "qk_gain":
            # ``qk_norm_gain + N(0, std)``: the configuration says why the
            # q/k gains do not start at 1 as the other gains do
            gain = _draw(k, shape(z), "gain", std, out_std, jnp.float32) \
                + (float(cfg.get("qk_norm_gain", 1.0)) - 1.0)
            out[name] = to_grid(gain, dtype).astype(dtype)
        else:
            out[name] = _draw(k, shape(z), what, std, out_std, dtype)
    return out


def outer_params(key, cfg, dtype):
    """Embedding, final norm and head.  The embedding's rows are drawn ``N(0,
    embedding_range)`` (the configuration says why that is not
    ``initializer_range``); with ``mask_row`` ``"mean"`` the mask token's row
    is the mean of the other rows, as a token ADDED to a trained vocabulary
    is commonly started."""
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind, std=std: _draw(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, std, std, dtype)
    embed = mk(0, (z["vocab"], z["h"]), "w",
               float(cfg.get("embedding_range", std)))
    if cfg.get("mask_row", "seeded") == "mean":
        others = jnp.arange(z["vocab"]) != z["mask_id"]
        mean = jnp.sum(jnp.where(others[:, None], embed.astype(jnp.float32),
                                 0.0), axis=0) / (z["vocab"] - 1)
        embed = embed.at[z["mask_id"]].set(to_grid(mean, dtype).astype(dtype))
    return {"embed": {"weight": embed},
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
def rope_angles(cfg, positions):
    """``(cos, sin) [len(positions), D / 2]`` float32."""
    d = int(cfg["head_dim"])
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def sees(i, j, half, block):
    """Whether query ``i`` sees key ``j`` (index arrays that broadcast) in
    a row of two copies of ``half`` positions: the four cases."""
    blk_i, blk_j = (i % half) // block, (j % half) // block
    noised_i, noised_j = i < half, j < half
    return ((noised_i & noised_j & (blk_i == blk_j))
            | (noised_i & ~noised_j & (blk_j < blk_i))
            | (~noised_i & ~noised_j & (blk_j <= blk_i)))


def attend(q, k, v, block, precision="float32"):
    """``o [T, n, D]`` of ``q [T, n, D]`` over ``k, v [T, kv, D]``, ``T = 2
    L``: a head at a time and ``Q_ROWS`` query rows at a time, their ``[rows,
    T]`` scores under the block-diffusion mask."""
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
            seen = sees(first + jnp.arange(rows)[:, None], keys, t // 2,
                        block)
            sc = jnp.where(seen, (q_r @ k_h.T) / math.sqrt(d), -jnp.inf)
            return _r(jax.nn.softmax(sc, axis=-1), precision) @ v_h

        return jax.lax.map(
            some_rows, (starts, q_h.reshape(-1, rows, d))).reshape(t, d)

    by_head = lambda a, rep: jnp.repeat(         # noqa: E731
        jnp.moveaxis(a, 1, 0), rep, axis=0)
    return jnp.moveaxis(
        jax.lax.map(head, (by_head(q, 1), by_head(k, group),
                           by_head(v, group))), 0, 1)


def route(a, wg, cfg):
    """(idx [T, k], weights [T, k]) in float32: softmax over all the
    router's experts, the k largest, normed over them."""
    s = jax.nn.softmax(jnp.matmul(a, wg), axis=-1)
    w, idx = jax.lax.top_k(s, sizes(cfg)["top_k"])
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx, w


def expert_ffn(a, p, cfg, precision="float32"):
    """``sum_i w_i E_i(a)`` over the experts HELD HERE, and the positions
    each of them received."""
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
    return _r(routed, precision), counts


def block(x, p, cfg, precision="float32"):
    """One layer on ONE row ``x [2 L, H]``: ``(x, positions per expert held
    here)``."""
    z, eps = sizes(cfg), float(cfg["rms_norm_eps"])
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    t, n, kv, d = x.shape[0], z["n"], z["kv"], z["d"]
    r = lambda a: _r(a, precision)      # noqa: E731
    a = r(_rms(x, p["ln_1.weight"], eps))
    q = _mm(a, p["attn.q_proj.weight"], precision).reshape(t, n, d)
    k = _mm(a, p["attn.k_proj.weight"], precision).reshape(t, kv, d)
    v = _mm(a, p["attn.v_proj.weight"], precision).reshape(t, kv, d)
    q = r(_rms(q, p["attn.q_norm.weight"], eps))
    k = r(_rms(k, p["attn.k_norm.weight"], eps))
    cos, sin = rope_angles(cfg, np.arange(t) % (t // 2))
    q, k = r(_rope(q, cos, sin)), r(_rope(k, cos, sin))
    o = r(attend(q, k, v, z["block"], precision)).reshape(t, n * d)
    x = r(x + _mm(o, p["attn.o_proj.weight"], precision))
    b = r(_rms(x, p["ln_2.weight"], eps))
    y, counts = expert_ffn(b, p, cfg, precision)
    return r(x + y), counts


def forward_row(params, ids, noised, cfg, precision="float32"):
    """(logits ``[L, vocab]`` at the noised positions, counts ``[layers,
    held]``) of ONE data row and its masked copy."""
    x = params["embed"]["weight"].astype(jnp.float32)[
        jnp.concatenate([noised, ids])]
    blk = jax.checkpoint(lambda c, p: block(c, p, cfg, precision))
    x, counts = jax.lax.scan(blk, x, params["blocks"])
    g = params["head"]["ln_f.weight"].astype(jnp.float32)
    w = params["head"]["lm_head.weight"].astype(jnp.float32)
    h = _r(_rms(x[:ids.shape[0]], g, float(cfg["rms_norm_eps"])), precision)
    return _mm(h, w, precision), counts


def row_loss_sum(params, ids, noised, noise, cfg, precision="float32"):
    """``(sum over the masked positions of CE(logits_i, ids_i) / t_blk(i),
    (expert counts, masked positions))`` of one row; ``noise [L / B]``."""
    z = sizes(cfg)
    logits, counts = forward_row(params, ids, noised, cfg, precision)
    each = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[:, None], -1)[:, 0]
    masked = noised == z["mask_id"]
    weight = jnp.where(masked, 1.0 / jnp.repeat(noise, z["block"]), 0.0)
    return jnp.sum(each * weight), (counts, jnp.sum(masked, dtype=jnp.int32))


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
    host so that the moments are formed again and never stored).  A batch
    is the feed's ``(ids, (noised ids, noise))``.  Returns ``losses``,
    ``first_grad_norms``, ``param_change_norms``, per step
    ``expert_counts`` (``[layers, held]``) and ``masked_tokens`` (the loss
    terms) and, with ``against`` (somebody else's parameters after the same
    steps, keyed ``(group.leaf, layer)``), ``param_change_cosines``,
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
    def row_grad(p, ids, noised, noise):
        return jax.value_and_grad(row_loss_sum, has_aux=True)(
            p, ids, noised, noise, cfg, precision)

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

    def batch_grad(p, ids, noised, noise):
        total, acc, counts, masked = 0.0, None, 0, 0
        for r in range(ids.shape[0]):
            (loss, (c, m)), g = row_grad(p, ids[r], noised[r], noise[r])
            total += float(loss)
            counts = counts + jax.device_get(c)
            masked += int(m)
            acc = g if acc is None else add(acc, g)
            del g
        n = ids.shape[0] * ids.shape[1]
        return total / n, scale(acc, jnp.float32(1.0 / n)), counts, masked

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms = [], [], None
        counts, masked = [], []
        for k, (ids, (noised, noise)) in enumerate(batches, start=1):
            loss, g, c, m = batch_grad(
                p, jnp.asarray(ids), jnp.asarray(noised),
                jnp.asarray(noise, jnp.float32))
            losses.append(loss)
            counts.append(c)
            masked.append(m)
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
            "masked_tokens": masked, **out}
