"""Attention over two KINDS of key under one softmax, as Pallas TPU kernels
(forward + backward): the exact keys of a query's own BLOCK-aligned window,
causally, and pooled chunk summaries of every EARLIER window (EVA,
arXiv:2302.04542, in the form ``models/evabyte.py`` runs).

With ``W`` the window, ``C`` the chunk (``W / C`` summaries a window),
query ``t`` in window ``w = t // W``:

    E(t) = { j : j // W = w, j <= t }        exact keys k_j, v_j
    R(t) = { c : c C // W < w }              summaries kt_c, vt_c
    o_t  = softmax over E(t) | R(t) of  scale * q_t . [k_j | kt_c]
           applied to [v_j | vt_c]           ONE maximum, ONE normaliser

The windows are blocks and not sliding (query ``W`` sees one exact key, not
``W``: ``attention_kernel``'s ``window=`` is the sliding one), and a window's
own summaries are never read: its chunks are attended exactly.

ONE fused launch a pass, not the causal flash kernel on folded rows plus a
second launch over the summaries: the two parts share q, the running
maximum, the normaliser and the output block, so a merge by row statistics
would write and read o and lse twice, carry the statistics' cotangent
through the backward, and launch five kernels a layer for three.  The rows
ARE folded: q, k, v ``[batch * heads, T, D]`` are viewed ``[batch * heads *
T / W, W, D]`` (free), and a program holds its q block, its window's K and V
(``W`` rows, not the sequence: 16,384 rows of K would not fit VMEM) and the
head's summaries whole (``T / C`` rows).

Work follows the mask.  A q block visits the key blocks of its own window
up to its diagonal, then the summary blocks that hold a row of an earlier
window (``w * W / C`` rows; the mask inside a summary block is applied
where the summary block does not divide a window's summaries, as the
default of two windows' summaries a block does not).  Four
kernels, by what a program OWNS:

- ``eva_attention_fwd``      a q block: o and the row statistics (lse);
- ``eva_attention_bwd_dq``   a q block: dq, over the same two loops;
- ``eva_attention_bwd_dkv``  a block of exact keys: dk, dv over the q blocks
  of its window from its diagonal on (plain causal flash dk/dv on the
  folded rows, with the joint statistics; the flash backward proper has
  fused its dq into this loop since PR 33, which the joint softmax over two
  kinds of key does not allow here);
- ``eva_attention_bwd_dkv_summaries``  a block of summaries: dkt, dvt over
  the q blocks of every LATER window; a third grid axis runs over those
  windows and sums their parts in a float32 VMEM scratch, as the flash
  dk/dv kernel sums a group's q heads.

What the MXU is handed is ``attention_kernel``'s rule: operands in the
dtype they are stored in, float32 accumulation, float32 scores, mask, exp
and statistics.

The forward's results are tagged for a rematerialisation policy as
``eva_attention_out`` and ``eva_attention_lse``.  The statistics are saved
as ROWS, ``[rows, W / block_q, block_q]`` (the form the dk/dv kernels read):
the forward kernel writes them ``[rows, W, 1]``, which the chip pads 128
times (268 MB a layer at 32 heads x 16,384), and that form lives only from
the kernel to the reshape beside it.

Constraints (else the dispatcher takes the XLA composition, aloud):
:func:`supports`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import (_NEG_INF, _NN, _NT, _dot, _mask_below_diagonal, _rows,
                     pick_block)

SAVED_BY_NAME = ("eva_attention_out", "eva_attention_lse")


def default_blocks(window, chunk):
    """``(block_q, block_k, block_s)``: q and exact-key blocks the largest
    of 512 .. 8 that divides the window (the flash kernels' finding: a
    block step is bound by its dot -> softmax -> dot chain, and 512 x 512
    runs three times as fast as 128 x 128); summary blocks of TWO windows'
    summaries, 256 at most.  Measured on the v5e at ``[1, 16384, 32, 128]``
    bf16, W 2048, C 16 (PR 32; forward, and forward + backward, of one
    call): 512 | 512 | 128 (a window's summaries a block, no masked pair
    among them) 8.31, 22.67 ms; 512 | 512 | 256: 7.37, 21.13; 512 | 512 |
    512: 7.00, 21.05 with 1.43 times the summary pairs scored; 256 x 256
    exact blocks 11.27, 31.98; 1024 x 1024: 8.17, 22.55."""
    block = pick_block(window, 512)
    return block, block, pick_block(2 * (window // chunk), 256)


def supports(seq, head_dim, window, chunk, blocks=None):
    """T a multiple of the window, the window of the chunk; one head width
    of at most 128 for q, k, v and the summaries; blocks that divide the
    window (and, the summaries', 8 at least)."""
    if not (window >= 1 and chunk >= 1 and seq % window == 0
            and window % chunk == 0 and head_dim <= 128):
        return False
    bq, bk, bs = blocks or default_blocks(window, chunk)
    return None not in (bq, bk, bs) and window % bq == 0 \
        and window % bk == 0


def pairs_needed(seq, window, chunk):
    """(query, key-or-summary) pairs of ``E`` and ``R`` over one row and
    head: ``(exact, summaries)``."""
    nw, per_window = seq // window, window // chunk
    return (nw * window * (window + 1) // 2,
            window * per_window * (nw * (nw - 1) // 2))


def pairs_scored(seq, window, chunk, blocks=None):
    """The pairs the forward (and dq) grid scores over one row and head at
    these block sizes, masked ones included: ``(exact, summaries)``."""
    bq, bk, bs = blocks or default_blocks(window, chunk)
    nw, per_window = seq // window, window // chunk
    exact = sum(-(-(i + 1) * bq // bk) * bk * bq
                for i in range(window // bq))
    summaries = sum(-(-w * per_window // bs) * bs * window
                    for w in range(nw))
    return nw * exact, summaries


def pairs_dense(seq, chunk):
    """What the XLA composition scores: the whole ``[T, T + T / C]``
    matrix."""
    return seq * (seq + seq // chunk)


# ---------------------------------------------------------- inner loops --

def _mask_later_summaries(s, col0, visible, col_axis):
    """Keep ``s`` where the summary row ``col0 + col`` is one of the
    ``visible`` first (those of earlier windows)."""
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, col_axis)
    return jnp.where(cols < visible, s, _NEG_INF)


def _visits(q_ref, k_ref, v_ref, kt_ref, vt_ref, *, block_k, block_s, nw,
            per_window, scale):
    """The two loops of a q-block program, as ``run(step, carry)``:
    ``step(s, k, v, carry)`` is called with the masked float32 scores of
    each visited block of exact keys, then of each visited summary
    block."""
    q = q_ref[0]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    w = jax.lax.rem(pl.program_id(0), nw)
    visible = w * per_window
    mask_summaries = per_window % block_s != 0

    def run(step, carry):
        def exact(j, carry):
            k = _rows(k_ref, j, block_k)
            v = _rows(v_ref, j, block_k)
            s = _dot(q, k, _NT) * scale
            s = _mask_below_diagonal(s, qi * block_q, j * block_k, 0)
            return step(s, k, v, carry)

        def summaries(j, carry):
            k = _rows(kt_ref, j, block_s)
            v = _rows(vt_ref, j, block_s)
            s = _dot(q, k, _NT) * scale
            if mask_summaries:
                s = _mask_later_summaries(s, j * block_s, visible, 1)
            return step(s, k, v, carry)

        end = ((qi + 1) * block_q + block_k - 1) // block_k
        carry = jax.lax.fori_loop(0, end, exact, carry)
        end = (visible + block_s - 1) // block_s
        return jax.lax.fori_loop(0, end, summaries, carry)

    return q, run


def _fwd_kernel(q_ref, k_ref, v_ref, kt_ref, vt_ref, o_ref, lse_ref, **kw):
    """One (window row, q block) program: ONE online softmax over the
    window's keys to the diagonal and the earlier windows' summaries."""
    q, run = _visits(q_ref, k_ref, v_ref, kt_ref, vt_ref, **kw)

    def step(s, k, v, carry):
        o_acc, m, l = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (o_acc * alpha + _dot(p.astype(v.dtype), v, _NN), m_new,
                l * alpha + jnp.sum(p, axis=1, keepdims=True))

    rows = q.shape[0]
    o_acc, m, l = run(step, (
        jnp.zeros((rows, v_ref.shape[2]), jnp.float32),
        jnp.full((rows, 1), _NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o_acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, kt_ref, vt_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, **kw):
    q, run = _visits(q_ref, k_ref, v_ref, kt_ref, vt_ref, **kw)
    do, lse, delta = do_ref[0], lse_ref[0], delta_ref[0]

    def step(s, k, v, dq_acc):
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, _NT) - delta)
        return dq_acc + _dot(ds.astype(k.dtype), k, _NN)

    dq = run(step, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = (dq * kw["scale"]).astype(dq_ref.dtype)


def _first_window(s, block_s, per_window):
    """The first window whose queries see a row of summary block ``s``."""
    return (s * block_s) // per_window + 1


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q, scale):
    """One (row, block of exact keys) program over the q blocks of the
    window from the block's diagonal on.  The scores are formed transposed,
    [Bk, Bq] = K Q^T, so that P^T dO and dS^T Q are plain matmuls; lse and
    delta come as rows, one [1, Bq] row of a [num_qb, Bq] block per q block
    (a [W, 1] block would be lane-padded 128x in VMEM)."""
    k = k_ref[0]                                               # [Bk, H]
    v = v_ref[0]
    block_k = k.shape[0]
    ki = pl.program_id(1)

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = _rows(q_ref, i, block_q)
        do = _rows(do_ref, i, block_q)
        lse = lse_ref[0, pl.ds(i, 1), :]                       # [1, Bq]
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = _dot(k, q, _NT) * scale                           # [Bk, Bq]
        st = _mask_below_diagonal(st, i * block_q, ki * block_k, 1)
        pt = jnp.exp(st - lse)
        dv_new = dv_acc + _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v, do, _NT) - delta)
        dk_new = dk_acc + _dot(dst.astype(q.dtype), q, _NN)
        return dk_new, dv_new

    zero = jnp.zeros(k.shape, jnp.float32)
    # q blocks before this k block's diagonal contribute nothing
    dk, dv = jax.lax.fori_loop((ki * block_k) // block_q,
                               q_ref.shape[1] // block_q, body, (zero, zero))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dkv_summaries_kernel(q_ref, kt_ref, vt_ref, do_ref, lse_ref,
                              delta_ref, dkt_ref, dvt_ref, dk_sum, dv_sum,
                              *, block_q, nw, per_window, scale):
    """One (head, summary block, q window) program.  The scores are formed
    transposed, ``[Bs, Bq] = Kt Q^T``, as the flash dk/dv kernel forms
    them; the parts of the windows that see the block are summed in the
    float32 scratch and written once, at the last window."""
    kt, vt = kt_ref[0], vt_ref[0]
    block_s = kt.shape[0]
    s_i, jw = pl.program_id(1), pl.program_id(2)
    visible = jw * per_window
    mask = per_window % block_s != 0

    @pl.when(jw == 0)
    def _():
        dk_sum[...] = jnp.zeros(dk_sum.shape, jnp.float32)
        dv_sum[...] = jnp.zeros(dv_sum.shape, jnp.float32)

    @pl.when(jw >= _first_window(s_i, block_s, per_window))
    def _():
        def body(i, carry):
            dk_acc, dv_acc = carry
            q = _rows(q_ref, i, block_q)
            do = _rows(do_ref, i, block_q)
            lse = lse_ref[0, pl.ds(i, 1), :]                   # [1, Bq]
            delta = delta_ref[0, pl.ds(i, 1), :]
            st = _dot(kt, q, _NT) * scale                      # [Bs, Bq]
            if mask:
                st = _mask_later_summaries(st, s_i * block_s, visible, 0)
            pt = jnp.exp(st - lse)
            dv_new = dv_acc + _dot(pt.astype(do.dtype), do, _NN)
            dst = pt * (_dot(vt, do, _NT) - delta)
            return dk_acc + _dot(dst.astype(q.dtype), q, _NN), dv_new

        zero = jnp.zeros(kt.shape, jnp.float32)
        dk, dv = jax.lax.fori_loop(0, q_ref.shape[1] // block_q, body,
                                   (zero, zero))
        dk_sum[...] += dk
        dv_sum[...] += dv

    @pl.when(jw == nw - 1)
    def _():
        dkt_ref[0] = (dk_sum[...] * scale).astype(dkt_ref.dtype)
        dvt_ref[0] = dv_sum[...].astype(dvt_ref.dtype)


# ---------------------------------------------------------------- launches --
#
# q, k, v, o, do: [R, W, D], R = batch * heads * T / W window rows;
# kt, vt: [batch * heads, S, D], S the summaries padded to whole blocks.

def _q_block_specs(window, head, summaries, block_q, nw):
    q_block = pl.BlockSpec((1, block_q, head), lambda r, i: (r, i, 0))
    own = pl.BlockSpec((1, window, head), lambda r, i: (r, 0, 0))
    all_summaries = pl.BlockSpec((1, summaries, head),
                                 lambda r, i: (r // nw, 0, 0))
    stat = pl.BlockSpec((1, block_q, 1), lambda r, i: (r, i, 0))
    return q_block, own, all_summaries, stat


def _eva_fwd(q, k, v, kt, vt, nw, per_window, scale, blocks, interpret):
    rows, window, head = q.shape
    block_q, block_k, block_s = blocks
    q_block, own, all_summaries, stat = _q_block_specs(
        window, head, kt.shape[1], block_q, nw)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, block_s=block_s,
                          nw=nw, per_window=per_window, scale=scale),
        name="eva_attention_fwd",
        grid=(rows, window // block_q),
        in_specs=[q_block, own, own, all_summaries, all_summaries],
        out_specs=[q_block, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((rows, window, 1), jnp.float32)],
        interpret=interpret,
    )(q, k, v, kt, vt)


def _eva_bwd(q, k, v, kt, vt, out, lse_rows, do, nw, per_window, scale,
             blocks, interpret):
    rows, window, head = q.shape
    block_q, block_k, block_s = blocks
    num_qb = window // block_q
    summaries = kt.shape[1]
    # delta = rowsum(dO * O): cheap elementwise, left to XLA's fusion
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    q_block, own, all_summaries, stat = _q_block_specs(
        window, head, summaries, block_q, nw)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, block_s=block_s,
                          nw=nw, per_window=per_window, scale=scale),
        name="eva_attention_bwd_dq",
        grid=(rows, num_qb),
        in_specs=[q_block, own, own, all_summaries, all_summaries, q_block,
                  stat, stat],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, kt, vt, do, lse_rows.reshape(rows, window, 1), delta)

    delta_rows = delta.reshape(lse_rows.shape)
    stat_rows = (1, num_qb, block_q)
    k_block = pl.BlockSpec((1, block_k, head), lambda r, j: (r, j, 0))
    whole = lambda r, j: (r, 0, 0)                          # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, scale=scale),
        name="eva_attention_bwd_dkv",
        grid=(rows, window // block_k),
        in_specs=[pl.BlockSpec((1, window, head), whole), k_block, k_block,
                  pl.BlockSpec((1, window, head), whole),
                  pl.BlockSpec(stat_rows, whole),
                  pl.BlockSpec(stat_rows, whole)],
        out_specs=[k_block, k_block],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(q, k, v, do, lse_rows, delta_rows)

    def seeing(b, s, jw):
        # the window whose rows the step reads: a step that sees nothing
        # of the block asks for the block that is (or will be) there
        first = jnp.minimum(_first_window(s, block_s, per_window), nw - 1)
        return (b * nw + jnp.maximum(jw, first), 0, 0)

    s_block = pl.BlockSpec((1, block_s, head), lambda b, s, jw: (b, s, 0))
    dkt, dvt = pl.pallas_call(
        functools.partial(_bwd_dkv_summaries_kernel, block_q=block_q,
                          nw=nw, per_window=per_window, scale=scale),
        name="eva_attention_bwd_dkv_summaries",
        grid=(kt.shape[0], summaries // block_s, nw),
        in_specs=[pl.BlockSpec((1, window, head), seeing), s_block, s_block,
                  pl.BlockSpec((1, window, head), seeing),
                  pl.BlockSpec(stat_rows, seeing),
                  pl.BlockSpec(stat_rows, seeing)],
        out_specs=[s_block, s_block],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((block_s, head), jnp.float32),
                        pltpu.VMEM((block_s, head), jnp.float32)],
        interpret=interpret,
    )(q, kt, vt, do, lse_rows, delta_rows)
    return dq, dk, dv, dkt, dvt


# ------------------------------------------------------------- public API --

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _eva_attention_rows(q, k, v, kt, vt, nw, per_window, scale, blocks,
                        interpret):
    out, _ = _fwd_rule(q, k, v, kt, vt, nw, per_window, scale, blocks,
                       interpret)
    return out


def _fwd_rule(q, k, v, kt, vt, nw, per_window, scale, blocks, interpret):
    out, lse = _eva_fwd(q, k, v, kt, vt, nw, per_window, scale, blocks,
                        interpret)
    rows, window, _ = q.shape
    lse_rows = lse.reshape(rows, window // blocks[0], blocks[0])
    out = checkpoint_name(out, SAVED_BY_NAME[0])
    lse_rows = checkpoint_name(lse_rows, SAVED_BY_NAME[1])
    return out, (q, k, v, kt, vt, out, lse_rows)


def _bwd_rule(nw, per_window, scale, blocks, interpret, res, do):
    return _eva_bwd(*res, do, nw, per_window, scale, blocks, interpret)


_eva_attention_rows.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/evabyte.py``
    trains and has no decode path); the lint sweeps one training-shaped
    case, forward and backward, at the engine's head width."""
    h = engine.head_dim
    seq = engine.max_model_len
    chunk = 4
    # four windows a row, or as many as leave a window 8 summaries
    window = next((w for w in (seq // 4, seq // 2, seq)
                   if w and supports(seq, h, w, chunk)), None)
    if window is None:
        return
    sds = jax.ShapeDtypeStruct
    x = sds((1, seq, 2, h), engine.dtype)
    xs = sds((1, seq // chunk, 2, h), engine.dtype)

    def vjp(q, k, v, kt, vt):
        def loss(*a):
            return jnp.sum(eva_attention_pallas(
                *a, window=window, chunk=chunk).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, kt, vt)

    yield registry.KernelCase(f"vjp[s{seq},w{window},c{chunk}]", vjp,
                              (x, x, x, xs, xs), None)


@registry.register_kernel(
    "eva_attention",
    fallback="paddle_tpu.ops.pallas:_xla_eva_attention",
    parity="tests/test_eva_attention_kernel.py::test_kernels_match_the_xla_"
           "composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def eva_attention_pallas(q, k, v, kt, vt, window, chunk, interpret=False,
                         blocks=None):
    """q, k, v: ``[batch, T, heads, D]``; kt, vt: ``[batch, T / chunk,
    heads, D]``, the chunk summaries (paddle flash-attn layout).  Returns
    ``[batch, T, heads, D]``; differentiable in all five.  ``blocks``
    ``(block_q, block_k, block_s)`` overrides :func:`default_blocks`."""
    b, t, n, d = q.shape
    blocks = tuple(blocks or default_blocks(window, chunk))
    if kt.shape != (b, t // chunk, n, d) or not supports(t, d, window,
                                                         chunk, blocks):
        raise ValueError(
            f"eva attention does not serve q{tuple(q.shape)} "
            f"kt{tuple(kt.shape)} window={window} chunk={chunk} "
            f"blocks={blocks}: see eva_attention_kernel.supports")
    nw, per_window = t // window, window // chunk
    pad = -(t // chunk) % blocks[2]

    def heads_first(x, rows):
        return x.transpose(0, 2, 1, 3).reshape(b * n * rows, -1, d)

    def summaries(x):
        # whole summary blocks: the padding rows are behind every visible
        # row, so no program reads them unmasked
        x = x.transpose(0, 2, 1, 3).reshape(b * n, t // chunk, d)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    out = _eva_attention_rows(
        heads_first(q, nw), heads_first(k, nw), heads_first(v, nw),
        summaries(kt), summaries(vt), nw, per_window, float(d) ** -0.5,
        blocks, interpret)
    return out.reshape(b, n, t, d).transpose(0, 2, 1, 3)
