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
2. A SORT of the ``A = S * k`` assignments by expert (:func:`expert_keys`,
   :func:`_sorted_by`): the ones whose expert does not live here
   (``expert_offset``, ``num_local_experts``: this chip's share of an
   expert-parallel layer) behind all the others, and the tokens each local
   expert received (:func:`group_sizes`).  The routing weights ride that
   sort as its payload.  :func:`sort_by_expert` is the same sort with the
   inverse permutation beside it (a second sort), for the capacity-free
   ``MoELayer``'s :func:`dispatch` / :func:`combine`.
3. :func:`routed_experts` -- the layers' routed block: a row gather into
   that order, a grouped matmul over the ragged groups
   (``ops.pallas.grouped_matmul``: on the TPU a Pallas kernel whose tiles
   follow ``group_sizes``, with its transposes for the backward; elsewhere
   ``jax.lax.ragged_dot``), and the rows summed back to their tokens by run
   (:func:`_sum_by_runs`), times the routing weights.

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
masked inside that reduction.  Back at the tokens EVERY bucket's rows are
summed BY RUN (:func:`_sum_by_runs`, since PR 45: the rows in token order,
each run of a token added up by the ``moe_run_sum`` kernel, one row
gathered a token: ``S + 3 R`` rows move), combine and the dispatch's
transpose alike.  Between the router and the tokens again nothing is sized
by the ``A`` assignments but integer sorts and elementwise integer ops: no
row buffer, no gather and no scatter of ``A`` entries (the parent gathered
one row a SLOT, ``k * S`` rows each way, three quarters of them a clamped
row nobody read, and brought the weights' gradient back through a gather of
``A`` scalars; :func:`_sum_by_slots` is that form, kept for
``MoELayer``'s :func:`dispatch`).  The permutations' transposes are gathers
too (``custom_vjp``): no scatter-add in either direction.

What an expert IS stays a parameter of that one path: its BODY
(:data:`BODIES`: ``swiglu``, ``down(silu(gate) * up)`` with gate | up in one
matrix; ``reglu``, ``down(relu(gate) * up)`` in the same layout; ``relu2``,
``down(relu(up) ** 2)``, not gated) and the width ``K``
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


def route_softmax_topk(logits, top_k, scale, norm_topk=True, bias=None):
    """``logits [S, E]`` float32 -> ``(idx [S, k] int32, weights [S, k])``.

    ``s = softmax(logits)`` over ALL the experts; the ``k`` largest are
    chosen; weights are ``s`` at the chosen, normed over them, and scaled
    (the softmax-routed family: ``norm_topk_prob``, a routed scaling
    factor).  With a selection ``bias`` the ``k`` largest of ``s + bias``
    are chosen and the weights stay ``s`` at the chosen (the bias steers the
    choice only).  At ``top_k`` 1 a normed weight is the constant 1 and the
    router has no gradient: a top-1 router passes ``norm_topk=False`` and
    its weight is the probability itself."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if bias is None:
        w, idx = jax.lax.top_k(s, top_k)
    else:
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def sorted_rows(tokens, top_k, num_local):
    """Rows of the sorted buffers: the most assignments ``tokens`` tokens
    can have among ``num_local`` experts, each token's ``top_k`` experts
    being distinct.  Exact, not a capacity: nothing is ever dropped."""
    return tokens * min(top_k, num_local)


def expert_keys(idx, expert_offset, num_local):
    """``idx [S, k]`` -> int32 ``[A]``, ``A = S * k``: each assignment's
    expert as the chip numbers the ``num_local`` it holds, ``num_local``
    itself for every expert held elsewhere.  Assignments are numbered
    SLOT-MAJOR, ``a = slot * S + token``."""
    local = idx.T.reshape(-1) - expert_offset
    return jnp.where((local >= 0) & (local < num_local), local, num_local)


def group_sizes(key, num_local):
    """int32 ``[num_local]``: the assignments of :func:`expert_keys` each
    expert held here received."""
    return jnp.sum(key[:, None] == jnp.arange(num_local)[None, :], axis=0,
                   dtype=jnp.int32)


def _sorted_by(key, *payloads):
    """``(key sorted, its permutation, each of payloads permuted alike)``:
    ONE stable sort whose operands ride along, where ``payload[argsort(key)]``
    would be a gather an element (7 ns each on the v5e, whatever it
    fetches)."""
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, iota) + payloads, num_keys=1, is_stable=True)


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
    cut: :func:`_unsort` clamps them, and nobody reads what they fetch.

    Both permutations come out of a SORT: ``inverse`` is the payload of
    sorting ``order`` (its keys are distinct), not ``zeros.at[order].set(
    iota)``, which the v5e runs as a scatter of ``A`` scalars at six times
    the sort's cost (0.455 ms for 0.078 at 98,304, PR 42's trace).  The
    layers' routed block (:func:`routed_experts`) sorts for itself and needs
    no inverse at all; this is the capacity-free ``MoELayer``'s."""
    tokens, top_k = idx.shape
    key = expert_keys(idx, expert_offset, num_local)
    order = _sorted_by(key)[1]
    inverse = _sorted_by(order)[1]
    rows = sorted_rows(tokens, top_k, num_local)
    return order[:rows], inverse, group_sizes(key, num_local)


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


def _reglu(gu):
    """``relu(gate) * up`` of ``gu [R, 2I]`` (gate | up)."""
    inter = gu.shape[1] // 2
    return jax.nn.relu(gu[:, :inter]) * gu[:, inter:]


def _relu2(h):
    """``relu(h) ** 2`` of ``h [R, I]``: no gate."""
    return jnp.square(jax.nn.relu(h))


# an expert's BODY: what stands between its two matrices, and so how wide
# the first is for an inner width ``I`` (``w_in [G, K, in_width * I]``)
BODIES = {"swiglu": (_swiglu, 2), "reglu": (_reglu, 2), "relu2": (_relu2, 1)}


def experts_mlp(xs, w_in, w_out, counts, body="swiglu"):
    """Every local expert's MLP on its own rows: ``w_in [G, K, 2I]`` (gate
    | up) under ``swiglu`` and ``reglu``, ``[G, K, I]`` under ``relu2``;
    ``w_out [G, I, K]``.  ``K`` is the rows' width, whatever the router
    read.  The body runs under the scope ``expert_body``."""
    pre = grouped_matmul(xs, w_in, counts)
    with jax.named_scope("expert_body"):
        h = BODIES[body][0](pre)
    return grouped_matmul(h, w_out, counts)


def _experts_mlp_vjp(xs, w_in, w_out, counts, body):
    """``(ys, d_ys -> (d_xs, d_in, d_out))``: what ``jax.vjp`` of
    :func:`experts_mlp` gives, the grouped matmuls' transposes called by
    name (``ops.pallas.grouped_matmul_dw`` says why)."""
    from .....ops.pallas import grouped_matmul_dw

    pre = grouped_matmul(xs, w_in, counts)
    with jax.named_scope("expert_body"):
        h, body_vjp = jax.vjp(BODIES[body][0], pre)

    def vjp(d_ys):
        d_h = grouped_matmul(d_ys, w_out, counts, True)
        with jax.named_scope("expert_body"):
            d_pre, = body_vjp(d_h)
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


def _rows_behind(token, tokens, max_run):
    """int32 ``[R]``: the rows of its own run that lie BEHIND each row of
    ``token [R]`` (rows in token order; ``tokens`` marks a row of no token,
    which is in no run).  A run is at most ``max_run`` long."""
    behind = sum(
        jnp.pad(token[i:] == token[:-i], (0, i)).astype(jnp.int32)
        for i in range(1, max_run)) if max_run > 1 else jnp.zeros_like(token)
    return jnp.where(token < tokens, behind, 0)


def _run_sums(rows, rem, weights=None, *, max_run):
    """``rows [R, H]`` in token order -> ``[R, H]``: at the first row of
    every run the float32 sum of the run's rows, each times its float32
    weight where given, rounded once; ``rem`` is :func:`_rows_behind`'s.
    Doubling shifted, masked adds: after the pass at ``step`` row ``j``
    holds the rows ``j .. j + 2 step - 1`` of its run.  The XLA composition
    of ``ops/pallas/moe_run_sum_kernel.py``, bit for bit: each pass is a
    shifted float32 copy of ``[R, H]`` through HBM."""
    acc = rows.astype(jnp.float32)
    if weights is not None:
        acc = acc * weights[:, None]
    for step in (1 << p for p in range((max_run - 1).bit_length())):
        acc = acc + jnp.where((rem >= step)[:, None],
                              jnp.pad(acc[step:], ((0, step), (0, 0))), 0.0)
    return acc.astype(rows.dtype)


def _sum_by_runs(rows, token, row_weights, here, max_run):
    """Expert-sorted ``rows [R, H]`` of a bucket -> ``[S, H]``: each token's
    float32 sum over its rows here, times the row's float32 weight where
    given, rounded once.  ``token [R]`` int32 is each row's token, ``S`` for
    the rows behind the last group (they hold anything, NaN included, and
    reach no sum); ``here [S]`` int32 the rows each token has here; a
    token's experts are distinct, so it has at most ``max_run``.

    The rows go into token order (ONE stable sort of ``R`` keys with the
    weights as its payload, one gather of ``R`` rows), each run of one token
    is added up (``ops.pallas.moe_run_sum``: the ``moe_run_sum`` kernel
    where the shapes allow, else :func:`_run_sums`), and ONE row is gathered
    a token, zero where a token has none here.  Nothing is sized by the ``k *
    S`` assignments."""
    from .....ops import pallas

    n, tokens = rows.shape[0], here.shape[0]
    token, by_token, *weights = _sorted_by(
        token, *(() if row_weights is None else (row_weights,)))
    acc = pallas.moe_run_sum(
        rows[by_token], _rows_behind(token, tokens, max_run), *weights,
        max_run=max_run)
    first = jnp.minimum(jnp.cumsum(here) - here, n - 1)
    return jnp.where((here > 0)[:, None], acc[first], 0)


def _to_tokens(rows, row_weights, token, here, counts, max_run):
    """Sorted ``rows [R, H]`` of a bucket -> ``[S, H]``: each token's sum
    over its rows, times ``row_weights [R]`` where given.  THE way back to
    the tokens, combine and the dispatch's transpose alike, whatever the
    bucket: :func:`_sum_by_runs`, its passes over ``[R, H]`` one Pallas
    kernel where the shapes allow.  ``token [R]`` is each sorted row's
    token.

    Until PR 45 ``_by_runs`` chose between this form (its passes XLA's) and
    the sum BY SLOTS (:func:`_sum_by_slots`: one row gathered a slot, ``k *
    S`` rows written whatever the bucket) at a twelfth of the slots' rows.
    With the kernel in place the crossover is gone.  Timed on the v5e IN THE
    TRACE, each way alone on seeded uniform routings at the four cells'
    small buckets (``chiprun_out/pr45/micro_*.json``, PR 45; ms a call, by
    runs with the kernel | by runs, XLA's passes | by slots): sdar ``[32768,
    2048]`` of 16,384 tokens at top-8 2.13 | 7.79 | 5.57; kanana ``[24576,
    2048]`` at top-6 1.73 | 5.50 | 1.29; laguna ``[5120, 3072]`` of 8,192 at
    top-10 0.30 | 1.00 | 1.54; the hybrid cell ``[7168, 1024]`` of 4,096 at
    top-22 0.093 | 0.124 | 0.615.  The pass alone: 0.44 | 6.16, 0.33 | 4.53,
    0.10 | 0.75, 0.053 | 0.086.  What is left of a call by runs is XLA's two
    row gathers, and what THEY cost is the compiler's memory assignment, not
    the rows': 6.4 ns a row where it holds the operand in VMEM, 27-36 ns
    where it reads HBM (an operand of 128 MiB, sdar's bucket, never fits;
    by slots paid 36 ns for each of sdar's 131,072 rows, 4.7 ms a call, and
    6.3 ns for kanana's 98,304: ``PERF.md`` section 5, PR 45).  Alone kanana's
    bucket went faster by slots (its gathers read HBM there); in the cell's
    step, where they sit in VMEM, it does not: dispatch and combine 26.95 ->
    16.97 ms a step, sdar's 90.30 -> 35.97, laguna's 15.58 -> 4.09, the
    hybrid cell's 13.55 -> 3.61 (section 6).  The worst-case bucket, which
    no run has taken, costs about what it did (a gather of ``A`` random rows
    either way)."""
    valid = jnp.arange(rows.shape[0]) < jnp.sum(counts)
    return _sum_by_runs(rows, jnp.where(valid, token, here.shape[0]),
                        row_weights, here, max_run)


def _routed_fwd_rows(body, max_run, rows, x, w_in, w_out, order, w_sorted,
                     here, counts):
    """The routed block, its experts of ``body``, in a bucket of ``rows``
    rows."""
    token = order[:rows] % x.shape[0]
    with jax.named_scope("dispatch"):
        xs = x[token]
    with jax.named_scope("experts"):
        ys = experts_mlp(xs, w_in, w_out, counts, body)
    with jax.named_scope("combine"):
        return _to_tokens(ys, w_sorted[:rows], token, here, counts, max_run)


def _routed_bwd_rows(body, max_run, rows, x, weights, w_in, w_out, order,
                     w_sorted, here, counts, g):
    """Its transpose in the same bucket, from the block's INPUTS and the
    forward's sort: ``xs`` and the experts' intermediate values are rebuilt
    at ``rows`` rows."""
    order, w_rows = order[:rows], w_sorted[:rows]
    token = order % x.shape[0]
    with jax.named_scope("dispatch"):
        xs = x[token]
    with jax.named_scope("experts"):
        ys, experts_vjp = _experts_mlp_vjp(xs, w_in, w_out, counts, body)
    with jax.named_scope("combine"):
        # ONE gather of the tokens' gradient: times the row's weight for
        # d ys, dotted with ys for the weight's own
        g32 = g[token].astype(jnp.float32)
        d_ys = (g32 * w_rows[:, None]).astype(ys.dtype)
        d_w = jnp.sum(ys.astype(jnp.float32) * g32, axis=-1)
        # back at its slots by ONE scatter of the bucket's rows, each to
        # its own assignment (rows behind the last group bring zero)
        d_w = jnp.where(jnp.arange(rows) < jnp.sum(counts), d_w, 0.0)
        d_w = jnp.zeros(weights.size, jnp.float32).at[order].set(
            d_w, unique_indices=True, mode="promise_in_bounds")
        d_w = d_w.reshape(weights.shape[::-1]).T.astype(weights.dtype)
    with jax.named_scope("experts"):
        d_xs, d_in, d_out = experts_vjp(d_ys)
    with jax.named_scope("dispatch"):
        d_x = _to_tokens(d_xs, None, token, here, counts, max_run)
    return d_x, d_w, d_in, d_out


def _in_bucket(rows_fn, buckets, counts, *operands):
    """``rows_fn(rows, *operands)`` at the bucket ``counts`` asks for."""
    if len(buckets) == 1:
        return rows_fn(buckets[0], *operands)
    return jax.lax.switch(
        bucket_of(counts, buckets),
        [functools.partial(rows_fn, rows) for rows in buckets], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def routed_experts(x, weights, w_in, w_out, idx, expert_offset, buckets,
                   body="swiglu"):
    """``x [S, K]`` -> ``[S, K]``: the sort of the router's ``idx [S, k]``
    (``weights [S, k]`` float32) by the experts held here (``w_in``'s
    ``num_local``, from ``expert_offset``), dispatch, those experts
    (:func:`experts_mlp` with ``body``) and combine, every buffer in
    between at the rows of the smallest of ``buckets``
    (:func:`row_buckets`) that holds the rows served here.

    Between the router and the tokens again nothing is sized by the ``A =
    k * S`` assignments but integer sorts and elementwise integer ops: the
    weights ride the sort as its payload, no inverse permutation is formed
    (:func:`_to_tokens` needs none), and the weights' gradient goes back to
    its slots by one scatter of the bucket's rows.

    ONE ``custom_vjp`` round the lot, because the choice is a
    ``lax.switch``: differentiated THROUGH, each branch would write zeros
    for every other branch's residuals, the worst case's among them, and the
    sort's payload would be transposed as a scatter-add.  The residuals here
    are the block's inputs and the sort's results, whose shapes no bucket
    changes; the backward opens its own switch."""
    return _routed_fwd(x, weights, w_in, w_out, idx, expert_offset, buckets,
                       body)[0]


def _routed_fwd(x, weights, w_in, w_out, idx, expert_offset, buckets, body):
    (tokens, top_k), num_local = idx.shape, w_in.shape[0]
    max_run = min(top_k, num_local)
    with jax.named_scope("dispatch"):
        key = expert_keys(idx, expert_offset, num_local)
        _, order, w_sorted = _sorted_by(
            key, weights.astype(jnp.float32).T.reshape(-1))
        routing = (order[:buckets[-1]], w_sorted[:buckets[-1]],
                   jnp.sum((key < num_local).reshape(top_k, tokens), axis=0,
                           dtype=jnp.int32),
                   group_sizes(key, num_local))
    out = _in_bucket(functools.partial(_routed_fwd_rows, body, max_run),
                     buckets, routing[-1], x, w_in, w_out, *routing)
    return out, (x, weights, w_in, w_out, *routing)


def _routed_bwd(expert_offset, buckets, body, operands, g):
    max_run = min(operands[1].shape[1], operands[2].shape[0])
    grads = _in_bucket(functools.partial(_routed_bwd_rows, body, max_run),
                       buckets, operands[-1], *operands, g)
    return (*grads, None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)
