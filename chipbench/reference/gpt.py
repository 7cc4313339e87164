"""Plain reference for the GPT-2/GPT-3 block (Brown et al. 2020, Table 2.1;
the block is GPT-2's: learned positions, pre-LayerNorm, fused q|k|v
projection, causal softmax attention, GELU(tanh) 4x MLP, tied head).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching tricks.  It imports nothing of the program and takes nothing the
program has made; the weights both sides run on come from
:func:`init_params`, which makes them on the device from the seed.

Departures from the published description, each noted where it is made:
weights are random (a seed stands in for a checkpoint), the vocabulary is
padded to 50,304, and biases and LayerNorm gains are perturbed so that a
path that drops one shows.

``precision="int8"`` and ``"fp8"`` are CONTROLS (never the reference):
the same forward with every matmul operand AND every activation between
operations rounded to 8 bits (symmetric integers, or float8 e4m3; weights
per output channel, activations per row) -- 8 bits wherever the program
keeps bfloat16.  The comparison that
decides ``correct`` must reject the one a configuration names.
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

# leaf name -> (shape builder over (H, I), kind); names follow the
# GPT-2 checkpoint layout so the same tree feeds program and reference
_BLOCK_LEAVES = {
    "ln_1.weight": (lambda h, i: (h,), "gain"),
    "ln_1.bias": (lambda h, i: (h,), "bias"),
    "attn.qkv.weight": (lambda h, i: (h, 3 * h), "w"),
    "attn.qkv.bias": (lambda h, i: (3 * h,), "bias"),
    "attn.proj.weight": (lambda h, i: (h, h), "w_out"),
    "attn.proj.bias": (lambda h, i: (h,), "bias"),
    "ln_2.weight": (lambda h, i: (h,), "gain"),
    "ln_2.bias": (lambda h, i: (h,), "bias"),
    "mlp.fc_in.weight": (lambda h, i: (h, i), "w"),
    "mlp.fc_in.bias": (lambda h, i: (i,), "bias"),
    "mlp.fc_out.weight": (lambda h, i: (i, h), "w_out"),
    "mlp.fc_out.bias": (lambda h, i: (h,), "bias"),
}


def seed_key(seed):
    """Raw threefry key from any non-negative whole number (the driver's
    seeds pass 2**31, which ``jax.random.PRNGKey`` refuses without x64)."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def _leaf(key, shape, kind, std, out_std, dtype):
    """One seeded leaf: N(0, std) weights (GPT-2's 0.02; the two
    residual projections scaled by 1/sqrt(2L)), biases N(0, std) and
    LayerNorm gains 1 + N(0, std) instead of the usual 0 and 1, so that
    a dropped bias or gain changes the output."""
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = 1.0 + std * x
    elif kind == "w_out":
        x = out_std * x
    else:
        x = std * x
    return to_grid(x, dtype).astype(dtype)


def to_grid(x, dtype):
    """Round float32 ``x`` to ``dtype``'s grid and stay float32.  An
    explicit ``reduce_precision``: on the TPU XLA drops a float32 ->
    bfloat16 -> float32 convert pair (``xla_allow_excess_precision``),
    and a value meant to be a bfloat16 parameter then keeps digits no
    bfloat16 holds -- wherever it is made and used in one program."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _sizes(cfg):
    h = int(cfg["hidden_size"])
    return (h, int(cfg.get("intermediate_size") or 4 * h),
            int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]),
            int(cfg["max_position_embeddings"]))


def layer_params(key, layer, cfg, dtype):
    """Block ``layer``'s leaves.  Traceable; ``layer`` may be a tracer."""
    h, inter, n_layers, _, _ = _sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * n_layers)
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _leaf(jax.random.fold_in(lkey, j), shape(h, inter), kind,
                        std, out_std, dtype)
            for j, (name, (shape, kind)) in enumerate(_BLOCK_LEAVES.items())}


def outer_params(key, cfg, dtype):
    """Embeddings and the final LayerNorm (the head is tied to wte)."""
    h, _, _, vocab, npos = _sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    okey = jax.random.fold_in(key, 0)
    mk = lambda j, shape, kind: _leaf(          # noqa: E731
        jax.random.fold_in(okey, j), shape, kind, std, std, dtype)
    return {"embed": {"word_embeddings.weight": mk(0, (vocab, h), "w"),
                      "position_embeddings.weight": mk(1, (npos, h), "w")},
            "head": {"weight": mk(2, (h,), "gain"),
                     "bias": mk(3, (h,), "bias")}}


def init_params(seed, cfg, dtype):
    """The whole seeded tree in ONE jitted call, on the default device,
    already in ``dtype``: ``{"embed", "blocks", "head"}`` with the block
    leaves stacked on a leading layer axis.  Layers are made one after
    the other (``lax.map``), so the float32 draw of a leaf never exists
    for more than one layer at a time."""
    n_layers = _sizes(cfg)[2]
    frozen = _freeze(cfg)

    @jax.jit
    def make(key):
        c = dict(frozen)
        tree = outer_params(key, c, dtype)
        tree["blocks"] = jax.lax.map(
            lambda l: layer_params(key, l, c, dtype), jnp.arange(n_layers))
        return tree

    return make(seed_key(seed))


def _freeze(cfg):
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "vocab_size", "max_position_embeddings", "initializer_range",
            "num_attention_heads", "layer_norm_epsilon")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


# ------------------------------------------------------------ forward ----
def _q8(x, axis):
    """Symmetric 8-bit rounding along ``axis`` (the control's step);
    straight-through under ``jax.grad``, as quantised training is."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return x + jax.lax.stop_gradient(jnp.round(x / scale) * scale - x)


def _qf8(x, axis):
    """Rounding to float8 e4m3 (3 mantissa bits, exponents down to 2**-6,
    largest 448) after scaling ``axis`` to the format's range; written
    in float32 arithmetic so that it runs wherever the reference does."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    y = x / scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(e - 3.0)
    return x + jax.lax.stop_gradient(jnp.round(y / step) * step * scale - x)


_ROUND = {"int8": _q8, "fp8": _qf8}


def _r(x, precision, axis=-1):
    """Round an intermediate as the stated precision stores it: nothing
    for the float32 reference; for a control every activation is kept in
    its 8 bits, as every activation of a bfloat16 program is kept in
    bfloat16 -- not the matmul operands alone."""
    if precision == "float32":
        return x
    if precision not in _ROUND:
        raise ValueError(f"unknown precision {precision!r}")
    return _ROUND[precision](x, axis)


def _mm(x, w, precision):
    return _r(jnp.matmul(_r(x, precision), _r(w, precision, 0)), precision)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, cfg, precision="float32"):
    """One transformer block on ONE sequence ``x [T, H]`` (float32)."""
    nh = int(cfg["num_attention_heads"])
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    t, h = x.shape
    hd = h // nh
    r = lambda a: _r(a, precision)      # noqa: E731
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    a = r(_layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps))
    qkv = r(_mm(a, p["attn.qkv.weight"], precision) + p["attn.qkv.bias"])
    q, k, v = (qkv[:, i * h:(i + 1) * h].reshape(t, nh, hd)
               for i in range(3))
    s = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    w = r(jax.nn.softmax(s, axis=-1))
    o = r(jnp.einsum("nqk,knd->qnd", w, v).reshape(t, h))
    x = r(x + _mm(o, p["attn.proj.weight"], precision)
          + p["attn.proj.bias"])
    a = r(_layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps))
    f = r(_gelu_tanh(_mm(a, p["mlp.fc_in.weight"], precision)
                     + p["mlp.fc_in.bias"]))
    return r(x + _mm(f, p["mlp.fc_out.weight"], precision)
             + p["mlp.fc_out.bias"])


def embed(ids, outer):
    wte = outer["embed"]["word_embeddings.weight"].astype(jnp.float32)
    wpe = outer["embed"]["position_embeddings.weight"].astype(jnp.float32)
    return wte[ids] + wpe[jnp.arange(ids.shape[-1])]


def head_logits(x, outer, cfg, precision="float32"):
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    g = outer["head"]["weight"].astype(jnp.float32)
    b = outer["head"]["bias"].astype(jnp.float32)
    wte = outer["embed"]["word_embeddings.weight"].astype(jnp.float32)
    return _mm(_r(_layer_norm(x, g, b, eps), precision), wte.T, precision)


def _row_loss_sum(params, row, labels, cfg, precision):
    """Summed next-token cross entropy of ONE row.  Rematerialised per
    layer and per row (memory only: the arithmetic is unchanged), so
    that float32 activations of a 2,048-token row at 4096 wide do not
    pile up under ``jax.grad``."""
    blk = jax.checkpoint(lambda c, p: block(c, p, cfg, precision))
    x = embed(row, params)
    x, _ = jax.lax.scan(lambda c, p: (blk(c, p), None), x, params["blocks"])
    lp = jax.nn.log_softmax(head_logits(x, params, cfg, precision)[:-1], -1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[1:, None], -1))


# ----------------------------------------------------------- training ----
def adamw_update(p, g, m, v, step, lr, hp):
    """Decoupled AdamW (Loshchilov & Hutter 2019), bias-corrected."""
    b1, b2 = hp["beta1"], hp["beta2"]
    p = p - lr * hp["weight_decay"] * p
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    return p - lr * mhat / (jnp.sqrt(vhat) + hp["epsilon"]), m, v


# a fused leaf is compared by the parts of its last axis: the key third
# of the q|k|v bias has NO true gradient (softmax does not see a constant
# added to every key), so it must not hide inside the leaf's norm
_FUSED = {"attn.qkv.bias": ("q", "k", "v")}


def _norms_of(name, x, keep):
    """L2 norm(s) of leaf ``group.leaf`` over all but its first ``keep``
    axes, as ``{key: array}``: one key, or one per part of a fused leaf
    (``group.leaf[part]``)."""
    x = jnp.square(x.astype(jnp.float32))
    parts = _FUSED.get(name.split(".", 1)[1])
    if parts is None:
        return {name: jnp.sqrt(jnp.sum(x, axis=tuple(range(keep, x.ndim))))}
    x = x.reshape(x.shape[:-1] + (len(parts), -1))
    x = jnp.sqrt(jnp.sum(x, axis=tuple(range(keep, x.ndim - 2)) + (-1,)))
    return {f"{name}[{part}]": x[..., i] for i, part in enumerate(parts)}


def _by_layer(out, res, layer):
    """Jitted ``{key: scalar | [layers]}`` -> host floats under
    ``(key, layer)``; a stacked result gets one entry per layer."""
    for key, v in res.items():
        if layer == "stacked":
            out.update({(key, l): float(x) for l, x in enumerate(v)})
        else:
            out[(key, layer)] = float(v)


def change_norms(seed, cfg, dtype, arrays):
    """``||a - p0||`` for ``arrays = {(group.leaf, layer): a}``, where p0
    are the seeded starting weights -- made again here, inside the jitted
    reduction and one layer at a time, so that no second copy of the
    model is ever held beside a program's state.  ``layer`` is a block's
    index, ``None`` for embeddings and head, or ``"stacked"`` for a leaf
    that holds every layer on its leading axis; the result has one entry
    per unstacked leaf (or part of a fused leaf) either way."""
    key = seed_key(seed)
    frozen = dict(_freeze(cfg))

    def diff(n, a, b):
        return _norms_of(n, a.astype(jnp.float32) - b.astype(jnp.float32), 0)

    def merged(dicts):
        return {k: v for d in dicts for k, v in d.items()}

    @jax.jit
    def outer(k, got):
        p0 = outer_params(k, frozen, dtype)
        return merged(diff(n, a, p0[n.split(".", 1)[0]][n.split(".", 1)[1]])
                      for n, a in got.items())

    @jax.jit
    def one_layer(k, layer, got):
        p0 = layer_params(k, layer, frozen, dtype)
        return merged(diff(n, a, p0[n.split(".", 1)[1]])
                      for n, a in got.items())

    @jax.jit
    def all_layers(k, got):
        n_layers = next(iter(got.values())).shape[0]
        return jax.lax.map(lambda la: one_layer(k, la[0], la[1]),
                           (jnp.arange(n_layers), got))

    out = {}
    got = {n: a for (n, layer), a in arrays.items() if layer is None}
    _by_layer(out, outer(key, got), None)
    got = {n: a for (n, layer), a in arrays.items() if layer == "stacked"}
    if got:
        _by_layer(out, all_layers(key, got), "stacked")
    for layer in sorted({l for _, l in arrays if isinstance(l, int)}):
        got = {n: a for (n, l), a in arrays.items() if l == layer}
        _by_layer(out, one_layer(key, jnp.int32(layer), got), layer)
    return out


def norms(arrays):
    """L2 norms for the same keys as :func:`change_norms` takes."""
    keys = sorted(arrays, key=str)

    @jax.jit
    def f(xs):
        return [_norms_of(name, x, 1 if layer == "stacked" else 0)
                for (name, layer), x in zip(keys, xs)]

    out = {}
    for (_, layer), res in zip(keys, f([arrays[k] for k in keys])):
        _by_layer(out, res, layer)
    return out


def keyed(tree):
    """Stacked tree -> ``{(group.leaf, None | "stacked"): array}``, the keys
    :func:`norms` and :func:`change_norms` take."""
    out = {(f"{g}.{n}", None): a for g in ("embed", "head")
           for n, a in tree[g].items()}
    out.update({(f"blocks.{n}", "stacked"): a
                for n, a in tree["blocks"].items()})
    return out


def train_reference(seed, cfg, batches, hp, param_dtype, precision="float32",
                    shard=None):
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  Arithmetic is float32 throughout; the PARAMETERS are
    rounded to ``param_dtype`` at the start and after every update,
    because that is what the configuration states (amp O2: parameters
    stored in bfloat16, no float32 master copy) -- an update smaller
    than a bfloat16 step is lost on both sides.  Returns per-step
    losses, the first gradient's norm per leaf and the parameter
    change's norm per leaf after the last step, as host floats.

    Memory is the constraint at 4096 wide (a float32 tree of the 4-layer
    model is 4.1 GB of a 16.9 GB chip): the batch's gradient is summed
    row by row into a donated accumulator (parameters + accumulator +
    one row's gradient), the gradients of the steps already taken wait
    on the HOST, and moments are rebuilt from them per leaf inside the
    update instead of being kept as two more trees.  ``shard`` places
    params and batch rows (four-chip cells); ``None`` is one device.
    """
    frozen = dict(_freeze(cfg))
    store = lambda a: to_grid(a, param_dtype)   # noqa: E731

    def start():
        p = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32),
            init_params(seed, cfg, param_dtype))
        return p if shard is None else shard["params"](p)

    @jax.jit
    def row_grad(p, row, labels):
        return jax.value_and_grad(_row_loss_sum)(p, row, labels, frozen,
                                                 precision)

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
        n = ids.shape[0] * (ids.shape[1] - 1)
        return total / n, scale(acc, jnp.float32(1.0 / n))

    with jax.default_matmul_precision("highest"):
        p = start()
        losses, waiting, first_grad_norms = [], [], None
        for k, (ids, labels) in enumerate(batches, start=1):
            loss, g = batch_grad(p, jnp.asarray(ids), jnp.asarray(labels))
            losses.append(loss)
            if first_grad_norms is None:
                first_grad_norms = norms(keyed(g))
            earlier = tuple(
                jax.tree_util.tree_map(jnp.asarray, h) if shard is None
                else shard["params"](h) for h in waiting)
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
