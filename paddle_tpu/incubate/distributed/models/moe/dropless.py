"""Dropless expert dispatch: sort the assignments by expert, one grouped
matmul over the experts held here, a weighted gather back.

The dense ``[S, E, C]`` einsum of ``moe_layer._moe_forward`` pays for
every (token, expert, slot) triple and drops what passes the capacity.
Here no token is dropped whatever the imbalance, and the expert matmuls'
work follows the rows that are really there:

1. :func:`route_sigmoid_topk` -- the published ``noaux_tc`` router
   (sigmoid scores, a selection bias, weights normed over the chosen few),
   or :func:`route_softmax_topk` (softmax over all the experts, the largest
   few, normed over them); any other router that yields ``(idx [S, k],
   weights [S, k])`` serves.
2. :func:`sort_by_expert` -- the ``S * k`` assignments in expert order, the
   ones whose expert does not live here (``expert_offset``,
   ``num_local_experts``: this chip's share of an expert-parallel layer)
   behind all the others, and the tokens each local expert received.
3. :func:`dispatch` / :func:`grouped_matmul` / :func:`combine` -- a row
   gather into that order, a grouped matmul over the ragged groups
   (``ops.pallas.grouped_matmul``: on the TPU a Pallas kernel whose tiles
   follow ``group_sizes``, with its transposes for the backward; elsewhere
   ``jax.lax.ragged_dot``), and a gather back with the routing weights.

The buffers hold the worst case (every assignment local: ``S * min(k,
num_local)`` rows, :func:`sorted_rows`: a token's experts are distinct, so
it has at most ``num_local`` of them here; ``S * k`` where the chip holds
at least ``k`` experts); the arithmetic does not: rows behind the last group belong to no group and
no tile of the grouped matmul visits them.  What such rows hold is never
defined and never used: wherever sorted rows go back to their tokens, the
assignments served elsewhere are masked inside that reduction.  The
gathers are permutations whose inverse is known, so their transposes are
gathers too (``custom_vjp``): no scatter-add in either direction.

On one chip the layer runs without its exchange: the tokens whose experts
live elsewhere would be sent there, and theirs would arrive here.  Nothing
stands in for that traffic; a token none of whose experts is local leaves
this layer with nothing from the routed experts.
"""


import jax
import jax.numpy as jnp


def route_sigmoid_topk(logits, bias, top_k, scale, norm_topk=True):
    """``logits [S, E]`` float32 -> ``(idx [S, k] int32, weights [S, k])``.

    ``s = sigmoid(logits)``; the ``k`` largest of ``s + bias`` are chosen
    (the bias steers the choice only); weights are ``s`` at the chosen,
    normed over them (``+ 1e-20``) and scaled.  Gradient reaches the
    router through the weights."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def route_softmax_topk(logits, top_k, scale, norm_topk=True):
    """``logits [S, E]`` float32 -> ``(idx [S, k] int32, weights [S, k])``.

    ``s = softmax(logits)`` over ALL the experts; the ``k`` largest are
    chosen; weights are ``s`` at the chosen, normed over them, and scaled
    (the softmax-routed family: ``norm_topk_prob``, a routed scaling
    factor; no selection bias)."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(s, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def sorted_rows(tokens, top_k, num_local):
    """Rows of the sorted buffers: the most assignments ``tokens`` tokens
    can have among ``num_local`` experts, each token's ``top_k`` experts
    being distinct.  Exact, not a capacity: nothing is ever dropped."""
    return tokens * min(top_k, num_local)


def sort_by_expert(idx, expert_offset, num_local):
    """The ``A = S * k`` assignments in the order the grouped matmul wants.

    Assignments are numbered SLOT-MAJOR, ``a = slot * S + token``: the
    ``[A, H]`` buffers then reshape to ``[k, S, H]`` for nothing (a
    ``[S, k, H]`` view would pad k to the 8 sublanes and cost a copy each
    way, 12 ms a layer and step at 98,304 x 2048 on the v5e).

    Returns ``(order, inverse, counts)``: ``order[j]`` is the assignment at
    sorted row ``j``, local experts first in expert order, everything
    routed elsewhere behind them; ``inverse`` undoes it; ``counts
    [num_local]`` int32 are the group sizes, i.e. the tokens each expert
    held here received.  ``order`` is cut to :func:`sorted_rows` rows (no
    local assignment lies behind them); ``inverse`` keeps all ``A``
    entries, and those of assignments served elsewhere may point past the
    cut: :func:`_unsort` clamps them, and nobody reads what they fetch."""
    tokens, top_k = idx.shape
    local = idx.T.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < num_local), local, num_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    counts = jnp.sum(key[:, None] == jnp.arange(num_local)[None, :], axis=0,
                     dtype=jnp.int32)
    rows = sorted_rows(tokens, top_k, num_local)
    if rows < order.shape[0]:
        order = order[:rows]
    return order, inverse, counts


def _unsort(ys, inverse):
    """Sorted rows ``ys`` back in assignment order (slot-major, all ``A``
    of them).  Where the sorted buffer was cut short of ``A`` the assignments
    served elsewhere would read past its end: they are clamped to its last
    row, and every consumer masks them (:func:`_slots`)."""
    if ys.shape[0] < inverse.shape[0]:
        inverse = jnp.minimum(inverse, ys.shape[0] - 1)
    return ys[inverse]


def _slots(by_slot, served):
    """Unsorted rows ``[k * S, H]`` (slot-major) -> the ``k`` float32
    ``[S, H]`` slices, zero where the assignment's expert is not held
    here (its sorted row lay behind the last group and held nothing
    defined).  Static slices and a select: the consumer's fusion reads the
    stored rows once, and no float32 copy of them is ever written (a
    reduction over a ``[k, S, H]`` view made XLA write one, 2 ms a time at
    98,304 x 2048 on the v5e)."""
    k, tokens = served.shape
    return [jnp.where(served[j][:, None],
                      by_slot[j * tokens:(j + 1) * tokens]
                      .astype(jnp.float32), 0.0) for j in range(k)]


def _served(inverse, counts, tokens):
    return (inverse < jnp.sum(counts)).reshape(-1, tokens)


@jax.custom_vjp
def dispatch(x, order, inverse, counts):
    """``x [S, H]`` -> ``[S * k, H]`` in sorted order: row ``j`` is the
    token of assignment ``order[j]``.  Rows behind the last group (experts
    held elsewhere) are never read: the grouped matmul skips them.  The
    transpose is a gather by ``inverse`` and a sum over the slots, never a
    scatter-add."""
    return x[order % x.shape[0]]


def _dispatch_fwd(x, order, inverse, counts):
    return x[order % x.shape[0]], (inverse, counts, x.shape[0])


def _dispatch_bwd(res, g):
    inverse, counts, tokens = res
    dx = sum(_slots(_unsort(g, inverse), _served(inverse, counts, tokens)))
    return dx.astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def grouped_matmul(xs, w, counts):
    """``xs [A, K]`` @ ``w [G, K, N]`` by groups of ``counts`` rows."""
    from .....ops import pallas

    return pallas.grouped_matmul(xs, w, counts)


@jax.custom_vjp
def combine(ys, weights, order, inverse, counts):
    """Sorted expert outputs ``ys [S * k, H]`` and ``weights [S, k]`` ->
    ``[S, H]``: each token's weighted sum over the slots whose expert lives
    here.  The weights stay float32, as published: products and the sum in
    float32, rounded once."""
    return _combine_fwd(ys, weights, order, inverse, counts)[0]


def _combine_fwd(ys, weights, order, inverse, counts):
    by_slot = _unsort(ys, inverse)
    served = _served(inverse, counts, weights.shape[0])
    w32 = weights.astype(jnp.float32)
    out = sum(y * w32[:, j:j + 1]
              for j, y in enumerate(_slots(by_slot, served)))
    return out.astype(ys.dtype), (by_slot, served, weights, order)


def _combine_bwd(res, g):
    by_slot, served, weights, order = res
    g32, w32 = g.astype(jnp.float32), weights.astype(jnp.float32)
    # d ys: each assignment's share of its token's gradient, back in sorted
    # order (rows behind the last group get what nobody reads)
    d_by_slot = jnp.concatenate(
        [(g32 * w32[:, j:j + 1]).astype(by_slot.dtype)
         for j in range(weights.shape[1])])
    d_w = jnp.stack([jnp.sum(y * g32, axis=-1)
                     for y in _slots(by_slot, served)], axis=1)
    return d_by_slot[order], d_w.astype(weights.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def swiglu_experts(xs, w_gate_up, w_down, counts):
    """Every local expert's gated MLP on its own rows: ``w_gate_up [G, H,
    2I]`` (gate | up), ``w_down [G, I, H]``."""
    inter = w_down.shape[1]
    gu = grouped_matmul(xs, w_gate_up, counts)
    h = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
    return grouped_matmul(h, w_down, counts)
