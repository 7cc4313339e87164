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

The buffers hold the rows of a BUCKET, not the worst case's
(:func:`routed_experts`, the layer's routed block).  The worst case
is every assignment local: ``S * min(k, num_local)`` rows
(:func:`sorted_rows`: a token's experts are distinct, so it has at most
``num_local`` of them here).  What a chip that holds ``num_local`` of ``E``
experts expects is ``S * k * num_local / E``, 3% to 12% of that where the
layer is cut 32 or 8 ways.  :func:`row_buckets` states ONE static row
count between the two (twice the expectation, in whole tiles of the
grouped matmul) before the worst case itself; the compiled step takes it
where it holds ``sum(counts)`` (``lax.switch`` on the device, the host
reads no count) and every buffer between the tokens and the tokens again
has that many rows.  Dropless stays dropless: the last bucket is the worst
case, so no routing, however skewed, loses a row.
Where nothing is cut (``num_local == E``) the expectation IS the worst
case, there is one bucket and no switch.

Within a bucket rows behind the last group belong to no group and no tile
of the grouped matmul visits them.  What such rows hold is never defined
and never used: wherever sorted rows go back to their tokens, they are
masked inside that reduction.  The gathers are permutations whose inverse
is known, so their transposes are gathers too (``custom_vjp``): no
scatter-add in either direction.  Back at the tokens a small bucket's rows
are summed BY RUN (:func:`_sum_by_runs`: the rows in token order, each run
of a token added up, one row gathered a token: ``S + R`` rows move); the
worst case's one row a SLOT (:func:`_sum_by_slots`: ``k * S`` rows).

What an expert IS stays a parameter of that one path: its BODY
(:data:`BODIES`: ``swiglu``, ``down(silu(gate) * up)`` with gate | up in one
matrix; ``relu2``, ``down(relu(up) ** 2)``, not gated) and the width ``K``
of the rows it works on, which need not be the router's input width (experts
in a latent: the layer projects the tokens down before the dispatch and up
after the combine, and every buffer here is ``K`` wide).

On one chip the layer runs without its exchange: the tokens whose experts
live elsewhere would be sent there, and theirs would arrive here.  Nothing
stands in for that traffic; a token none of whose experts is local leaves
this layer with nothing from the routed experts.
"""

import functools

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


def _sum_by_slots(rows, weights, inverse, counts, tokens):
    """Sorted ``rows [R, H]`` -> ``[S, H]``: each token's sum over its
    slots served here, times ``weights [S, k]`` where given.  One row is
    gathered a SLOT, ``k * S`` in all; products and the sum in float32,
    rounded once."""
    slots = _slots(_unsort(rows, inverse), _served(inverse, counts, tokens))
    if weights is not None:
        w32 = weights.astype(jnp.float32)
        slots = [y * w32[:, j:j + 1] for j, y in enumerate(slots)]
    return sum(slots).astype(rows.dtype)


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
    return _sum_by_slots(g, None, inverse, counts, tokens), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def grouped_matmul(xs, w, counts, transpose_w=False):
    """``xs [A, K]`` @ ``w [G, K, N]`` by groups of ``counts`` rows (``w
    [G, N, K]`` with ``transpose_w``)."""
    from .....ops import pallas

    return pallas.grouped_matmul(xs, w, counts, transpose_w)


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


def _swiglu(gu):
    """``silu(gate) * up`` of ``gu [R, 2I]`` (gate | up)."""
    inter = gu.shape[1] // 2
    return jax.nn.silu(gu[:, :inter]) * gu[:, inter:]


def _relu2(h):
    """``relu(h) ** 2`` of ``h [R, I]``: no gate."""
    return jnp.square(jax.nn.relu(h))


# an expert's BODY: what stands between its two matrices, and so how wide
# the first is for an inner width ``I`` (``w_in [G, K, in_width * I]``)
BODIES = {"swiglu": (_swiglu, 2), "relu2": (_relu2, 1)}


def experts_mlp(xs, w_in, w_out, counts, body="swiglu"):
    """Every local expert's MLP on its own rows: ``w_in [G, K, 2I]`` (gate
    | up) under ``swiglu``, ``[G, K, I]`` under ``relu2``; ``w_out [G, I,
    K]``.  ``K`` is the rows' width, whatever the router read."""
    return grouped_matmul(BODIES[body][0](grouped_matmul(xs, w_in, counts)),
                          w_out, counts)


def _experts_mlp_vjp(xs, w_in, w_out, counts, body):
    """``(ys, d_ys -> (d_xs, d_in, d_out))``: what ``jax.vjp`` of
    :func:`experts_mlp` gives, the grouped matmuls' transposes called by
    name (``ops.pallas.grouped_matmul_dw`` says why)."""
    from .....ops.pallas import grouped_matmul_dw

    pre = grouped_matmul(xs, w_in, counts)
    h, body_vjp = jax.vjp(BODIES[body][0], pre)

    def vjp(d_ys):
        d_pre, = body_vjp(grouped_matmul(d_ys, w_out, counts, True))
        return (grouped_matmul(d_pre, w_in, counts, True),
                grouped_matmul_dw(xs, d_pre, counts),
                grouped_matmul_dw(h, d_ys, counts))

    return grouped_matmul(h, w_out, counts), vjp


# ------------------------------------------- the routed block, by bucket --

ROW_TILE = 512      # the grouped matmul's row tile (``ops.pallas``)


def row_buckets(tokens, top_k, num_local, num_experts, headroom=2):
    """The static row counts a layer's buffers may have, ascending; the
    last is the worst case (:func:`sorted_rows`).

    ``tokens * top_k * num_local / num_experts`` rows are expected here;
    the small bucket holds ``headroom`` times that (twice, unless the
    layer says otherwise), in whole row tiles of the grouped matmul.  It is left out where it would reach the worst case: where
    nothing is cut (``num_local == num_experts``) the worst case is the
    only bucket.

    One small bucket, not a ladder (PR 31, on the v5e): every bucket is a
    branch of two switches a layer, and each cost both expert cells about
    2 s of set-up (tracing, and 27 MB of program to load) on 41; with
    buckets at 1.25, 2 and 4 times the expectation a seeded router with no
    balancing term took the first in 99% of layer steps and never passed
    1.6 times; laguna's step was 0.9% faster than with this one bucket,
    kanana's 0.5% slower."""
    worst = sorted_rows(tokens, top_k, num_local)
    rows = -(-headroom * tokens * top_k * num_local // num_experts)
    rows = -(-rows // ROW_TILE) * ROW_TILE
    return (rows, worst) if rows < worst else (worst,)


def bucket_of(counts, buckets):
    """Index (int32 scalar, on the device) of the smallest of ``buckets``
    that holds the ``sum(counts)`` rows served here."""
    return jnp.sum(jnp.sum(counts) > jnp.asarray(buckets[:-1], jnp.int32),
                   dtype=jnp.int32)


def _run_passes(top_k, num_local):
    """Doubling passes that cover a token's longest run of rows here."""
    return (min(top_k, num_local) - 1).bit_length()


def _by_runs(rows, tokens, top_k, num_local):
    """Whether a bucket of ``rows`` goes back to its tokens by runs or by
    slots.  Timed alone on the v5e (PR 31, ms a call): by slots is one
    gather that WRITES ``k * S`` rows and a sum that reads them, whatever
    the bucket: 1.57 at 81,920 x 3,072 (laguna's layer) and 1.32 at 98,304
    x 2,048 (kanana's), where the worst case's took 4.8 and 4.0 (a small
    bucket's clamped reads hit one row).  By runs moves ``S + R`` rows and
    makes three float32 passes over ``[R, H]``, each a shifted copy: 0.53
    at 3,584 x 3,072, 1.11 at 5,120, 3.59 at 10,240; 4.0 at 15,360 x
    2,048.  The crossover lies near a twelfth of the slots' rows in both
    layers: laguna's bucket of 5,120 goes by runs, kanana's of 24,576 by
    slots."""
    return 4 * max(_run_passes(top_k, num_local), 1) * rows < top_k * tokens


def _sum_by_runs(rows, row_weights, order, inverse, counts, tokens):
    """What :func:`_sum_by_slots` gives, formed over the ``R`` rows of a
    bucket instead of the ``k * S`` slots: the rows in token order (a sort
    of ``R`` keys), each run of one token added up (a token's experts are
    distinct, so a run is at most ``min(k, num_local)`` long: doubling
    shifted, masked float32 adds cover it), and ONE row gathered a token,
    zero where a token has none here.  ``S + R`` rows move.  ``row_weights
    [R]`` float32 or ``None``."""
    n, top_k = rows.shape[0], inverse.shape[0] // tokens
    valid = jnp.arange(n) < jnp.sum(counts)
    token = jnp.where(valid, order % tokens, tokens)
    by_token = jnp.argsort(token).astype(jnp.int32)
    token = token[by_token]
    acc = rows[by_token].astype(jnp.float32)
    if row_weights is not None:
        acc = acc * row_weights[by_token][:, None]
    acc = jnp.where((token < tokens)[:, None], acc, 0.0)
    # after the pass at ``step``, acc[i] holds rows i .. i + 2 * step - 1
    # of i's run
    for step in (1 << p for p in range(_run_passes(top_k, counts.shape[0]))):
        same = jnp.pad(token[step:] == token[:-step], (0, step))
        acc = acc + jnp.where(same[:, None],
                              jnp.pad(acc[step:], ((0, step), (0, 0))), 0.0)
    acc = acc.astype(rows.dtype)
    here = jnp.sum(_served(inverse, counts, tokens), axis=0, dtype=jnp.int32)
    first = jnp.minimum(jnp.cumsum(here) - here, n - 1)
    return jnp.where((here > 0)[:, None], acc[first], 0)


def _to_tokens(rows, weights, order, inverse, counts, tokens):
    """Sorted ``rows [R, H]`` of a bucket -> ``[S, H]``: each token's sum
    over its rows, times its ``weights [S, k]`` where given."""
    top_k = inverse.shape[0] // tokens
    if not _by_runs(rows.shape[0], tokens, top_k, counts.shape[0]):
        return _sum_by_slots(rows, weights, inverse, counts, tokens)
    row_weights = None if weights is None else \
        weights.astype(jnp.float32).T.reshape(-1)[order]
    return _sum_by_runs(rows, row_weights, order, inverse, counts, tokens)


def _routed_fwd_rows(body, rows, x, weights, w_in, w_out, order, inverse,
                     counts):
    """The routed block, its experts of ``body``, in a bucket of ``rows``
    rows."""
    tokens, order = x.shape[0], order[:rows]
    with jax.named_scope("dispatch"):
        xs = x[order % tokens]
    with jax.named_scope("experts"):
        ys = experts_mlp(xs, w_in, w_out, counts, body)
    with jax.named_scope("combine"):
        return _to_tokens(ys, weights, order, inverse, counts, tokens)


def _routed_bwd_rows(body, rows, x, weights, w_in, w_out, order, inverse,
                     counts, g):
    """Its transpose in the same bucket, from the block's INPUTS: ``xs`` and
    the experts' intermediate values are rebuilt at ``rows`` rows."""
    tokens, order = x.shape[0], order[:rows]
    token = order % tokens
    with jax.named_scope("dispatch"):
        xs = x[token]
    with jax.named_scope("experts"):
        ys, experts_vjp = _experts_mlp_vjp(xs, w_in, w_out, counts, body)
    with jax.named_scope("combine"):
        # ONE gather of the tokens' gradient: times the row's weight for
        # d ys, dotted with ys for the weight's own
        g32 = g[token].astype(jnp.float32)
        w32 = weights.astype(jnp.float32).T.reshape(-1)[order]
        d_ys = (g32 * w32[:, None]).astype(ys.dtype)
        d_w = jnp.sum(ys.astype(jnp.float32) * g32, axis=-1)
        served = inverse < jnp.sum(counts)
        d_w = jnp.where(served, d_w[jnp.minimum(inverse, rows - 1)], 0.0)
        d_w = d_w.reshape(-1, tokens).T.astype(weights.dtype)
    with jax.named_scope("experts"):
        d_xs, d_in, d_out = experts_vjp(d_ys)
    with jax.named_scope("dispatch"):
        d_x = _to_tokens(d_xs, None, order, inverse, counts, tokens)
    return d_x, d_w, d_in, d_out


def _in_bucket(rows_fn, buckets, counts, *operands):
    """``rows_fn(rows, *operands)`` at the bucket ``counts`` asks for."""
    if len(buckets) == 1:
        return rows_fn(buckets[0], *operands)
    return jax.lax.switch(
        bucket_of(counts, buckets),
        [functools.partial(rows_fn, rows) for rows in buckets], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def routed_experts(x, weights, w_in, w_out, order, inverse, counts, buckets,
                   body="swiglu"):
    """``x [S, K]`` -> ``[S, K]``: dispatch, the experts held here
    (:func:`experts_mlp` with ``body``) and combine, every buffer in
    between at the rows of the smallest of ``buckets``
    (:func:`row_buckets`) that holds ``sum(counts)``.

    ONE ``custom_vjp`` round the three, because the choice is a
    ``lax.switch``: differentiated THROUGH, each branch would write zeros
    for every other branch's residuals, the worst case's among them.  The
    residuals here are the block's inputs, whose shapes no bucket changes;
    the backward opens its own switch."""
    return _routed_fwd(x, weights, w_in, w_out, order, inverse, counts,
                       buckets, body)[0]


def _routed_fwd(x, weights, w_in, w_out, order, inverse, counts, buckets,
                body):
    operands = (x, weights, w_in, w_out, order, inverse, counts)
    return _in_bucket(functools.partial(_routed_fwd_rows, body),
                      buckets, counts, *operands), operands


def _routed_bwd(buckets, body, operands, g):
    grads = _in_bucket(functools.partial(_routed_bwd_rows, body),
                       buckets, operands[-1], *operands, g)
    return (*grads, None, None, None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)
