"""Pallas ragged paged attention — ONE kernel for every serving phase.

Ragged Paged Attention (arxiv 2604.15464) folds chunked prefill, plain
decode, and speculative verify into a single launch over a flat ragged
batch: the step's query tokens are packed back-to-back along one token
axis, and each batch row is described by a ``(query_start, query_len,
context_len)`` descriptor instead of by its own executable.  A decode
row is simply a one-token chunk; a verify row is a K+1-token chunk; a
prefill chunk is a C-token chunk — the causal rule is identical for all
of them, because the token at absolute position ``p`` sees exactly the
``p + 1`` pool positions ``0..p``.  Shapes:

    q             [T, Nq, D]      T packed query tokens (GQA: G =
                                  Nq//Nkv query heads per KV head)
    k_pages       [NB, Nkv, bs, D] the whole paged pool, HEAD-MAJOR:
    v_pages       [NB, Nkv, bs, D] NB pages of bs tokens per kv head
    block_tables  [R, P] int32    page id of row r's p-th page
    row_start     [R]    int32    first flat token of row r
    row_qlen      [R]    int32    query tokens of row r (0: dead row)
    row_pos0      [R]    int32    absolute position of row r's first
                                  query token

Host contract (the engine packs exactly this): ``row_start`` is
non-decreasing, ``row_start[r] + row_qlen[r] <= T``, and a dead row
(``row_qlen == 0``) owns no tokens.  Token ``i`` of row ``r`` sits at
absolute position ``row_pos0[r] + i`` and attends over pool positions
``0 .. row_pos0[r] + i`` through row r's block table.  Tokens outside
every row (padding) come back as EXACT ZEROS.

Kernel layout: grid (Nkv, R, P), block tables and row descriptors as
scalar-prefetch operands so the BlockSpec index map dereferences
``block_tables[r, p]`` — each (kv head, row) pair walks only the pages
that row owns, with the online-softmax state held in VMEM scratch over
the padded flat token axis.  The page axis is innermost, so scratch
carries across a row's pages; the row axis is next, so a later row's
init pass reclaims whatever an earlier row's tail chunk spilled past
its own tokens (the flat axis is padded by one chunk of slack for
that spill); the output block is indexed by the kv head only and is
zeroed once per head, which is what makes dead tokens exact zeros.
Unlike the retired per-phase kernels, NOTHING is replicated on the
host: speculative verify used to materialize
``jnp.repeat(block_tables, K+1, axis=0)`` — here every row's K+1
tokens share one descriptor and one block-table row.

What Mosaic accepts (checked by tests/test_tpu_lowering.py, and by an
ahead-of-time v5e compile in its slow tier) shapes two choices here:

- the pool is head-major so one (page, kv head) block is a contiguous
  ``[bs, D]`` tile whose last two dims ARE the array's last two dims.
  A token-major ``[NB, bs, Nkv, D]`` pool needs a ``(1, bs, 1, D)``
  block, which squeezes the second-minor axis — refused at lowering —
  and walking heads inside a whole-page block needs a dynamic index
  on a packed (bf16/int8) sublane axis, which Mosaic refuses too;
- q and the output cross the kernel boundary in float32.  A row
  starts at ANY flat token, so its ``pl.ds(off, tqg)`` slices are
  dynamic and unaligned; Mosaic proves alignment only for 32-bit
  rows.  The kernel accumulated in f32 already, so the staging casts
  (bf16 -> f32 in, f32 -> bf16 out) are the same two roundings the
  in-kernel casts performed — results are unchanged.

Like the other kernels, the 1/sqrt(D) scale is applied INSIDE; the
masked-XLA fallback (inference/llm/paged_attention.py) computes
bitwise-defined identical semantics everywhere the kernel is gated
off, and is what the engine-vs-dense token-exactness tests pin.

Under tensor parallelism the pool is sharded along the Nkv axis and
the kernel runs inside ``jax.shard_map`` with PER-SHARD head counts
and the full local pool; the scalar-prefetched descriptors (which
GSPMD could not partition through the index map) arrive replicated.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _NEG_INF

# the Mosaic custom call's kernel_name in a lowered step, and the name
# a device trace shows
KERNEL_NAME = "ragged_paged_attention"
# query tokens processed per inner chunk: one f32 sublane tile when
# G == 1, a multiple of it otherwise — the flat axis is padded by one
# chunk so a row's tail chunk can spill without leaving the block
_TQ = 8


def supports(block_size, head_dim, num_q_heads, num_kv_heads,
             total_tokens):
    """Shape gate: lane-sized head_dim, sublane-tiled pages, whole GQA
    groups, and a flat token axis the _TQ chunk walk divides."""
    return (head_dim <= 128 and block_size % 8 == 0
            and num_q_heads % num_kv_heads == 0
            and total_tokens % _TQ == 0 and total_tokens > 0)


def _ragged_kernel(bt_ref, start_ref, qlen_ref, pos0_ref,
                   *refs, block_size, group, nc, quant=False):
    """One (kv_head, row, page) program.

    Row r's tokens live at flat rows [start*G, (start+qlen)*G) of the
    padded [TG, D] query/output blocks; the chunk walk visits them
    ``_TQ`` tokens at a time with a dynamic trip count (dead rows cost
    zero chunks, a decode row exactly one).  A tail chunk may spill
    into the next row's region: spilled scratch is re-initialized by
    that row's own p == 0 pass before it is read, and spilled output
    is never written at all (the finalize store blends against the
    token-validity mask), so the zero-filled padding region stays
    exactly zero.

    ``quant=True`` (static) adds two page-scale operands after the K/V
    blocks, each the page's whole [Nkv, bs] scale tile; this head's
    row is a [1, bs] LANE vector, so the dequant multiply lands where
    slots are lanes — on the scores (``(q . k8) * ks``) and on the
    probabilities (``(p * vs) @ v8``) — which equals dequantizing the
    page rows first; no dequantized copy of the pool ever exists.
    """
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         o_scr, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, o_scr, m_scr, l_scr = refs
    j = pl.program_id(0)
    r = pl.program_id(1)
    p = pl.program_id(2)
    num_pages = pl.num_programs(2)
    d = q_ref.shape[2]
    tqg = _TQ * group
    start = start_ref[r]
    qlen = qlen_ref[r]
    pos0 = pos0_ref[r]

    @pl.when((r == 0) & (p == 0))
    def _zero_output():
        # the one full-block store: every token the finalize blend
        # skips — padding, dead rows, spill — reads back exact zeros
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def each_chunk(body):
        """Run ``body(c)`` for every chunk holding live tokens of this
        row — trip count is data-dependent, structure is static."""
        def step(c, carry):
            @pl.when(c * _TQ < qlen)
            def _():
                body(c)
            return carry
        jax.lax.fori_loop(0, nc, step, 0)

    @pl.when(p == 0)
    def _init():
        def init_chunk(c):
            off = (start + c * _TQ) * group
            o_scr[pl.ds(off, tqg), :] = jnp.zeros((tqg, d), jnp.float32)
            m_scr[pl.ds(off, tqg), :] = jnp.full((tqg, 1), _NEG_INF,
                                                 jnp.float32)
            l_scr[pl.ds(off, tqg), :] = jnp.zeros((tqg, 1), jnp.float32)
        each_chunk(init_chunk)

    base = p * block_size

    # pages at or past the row's deepest context hold nothing any of
    # its tokens may see; page 0 is always visible (every live token's
    # causal window contains position 0), so valid tokens accumulate
    # real state before any fully-masked page can touch them
    @pl.when(base < pos0 + qlen)
    def _accumulate():
        k = k_ref[0, 0].astype(jnp.float32)                 # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)                 # [bs, D]
        if quant:
            # per-slot dequant scales of this head: [1, bs] lane rows
            ks = ks_ref[0, pl.ds(j, 1), :]
            vs = vs_ref[0, pl.ds(j, 1), :]

        def acc_chunk(c):
            off = (start + c * _TQ) * group
            q = q_ref[0, pl.ds(off, tqg), :]
            s = q @ k.T / jnp.sqrt(jnp.asarray(d, jnp.float32))
            if quant:
                s = s * ks
            # flat row i of the chunk is query token c*_TQ + i//G of
            # this batch row, at absolute position pos0 + that index
            ti = c * _TQ + jax.lax.broadcasted_iota(
                jnp.int32, (tqg, block_size), 0) // group
            kpos = base + jax.lax.broadcasted_iota(
                jnp.int32, (tqg, block_size), 1)
            s = jnp.where((kpos <= pos0 + ti) & (ti < qlen), s,
                          _NEG_INF)
            m_prev = m_scr[pl.ds(off, tqg), :]
            l_prev = l_scr[pl.ds(off, tqg), :]
            o_prev = o_scr[pl.ds(off, tqg), :]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            pe = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            pv = (pe * vs if quant else pe) @ v
            o_scr[pl.ds(off, tqg), :] = o_prev * alpha + pv
            m_scr[pl.ds(off, tqg), :] = m_new
            l_scr[pl.ds(off, tqg), :] = \
                l_prev * alpha + pe.sum(axis=1, keepdims=True)
        each_chunk(acc_chunk)

    @pl.when(p == num_pages - 1)
    def _finalize():
        def fin_chunk(c):
            off = (start + c * _TQ) * group
            ti = c * _TQ + jax.lax.broadcasted_iota(
                jnp.int32, (tqg, 1), 0) // group
            o = o_scr[pl.ds(off, tqg), :] \
                / jnp.maximum(l_scr[pl.ds(off, tqg), :], 1e-30)
            cur = o_ref[0, pl.ds(off, tqg), :]
            o_ref[0, pl.ds(off, tqg), :] = jnp.where(ti < qlen, o, cur)
        each_chunk(fin_chunk)


def _cases(engine, quant):
    """Every launch the serving engine makes IS this kernel now: one
    case per token bucket of the collapsed ``_bucket_grid()`` family,
    with the fixed [max_batch, max_pages] descriptor rails.  The
    scalar_bounds let K003 prove the block-table prefetch indirection
    in-bounds (page ids in [0, num_blocks - 1]) and bound the row
    descriptors by the token bucket / model horizon.  ``quant`` yields
    the int8-KV family instead: int8 pools, each with its
    [NB, Nkv, bs] f32 page-scale operand."""
    nkv = max(engine.num_heads // engine.tp, 1)
    d = engine.head_dim
    sds = jax.ShapeDtypeStruct
    kp = sds((engine.num_blocks, nkv, engine.block_size, d),
             jnp.int8 if quant else engine.dtype)
    sp = sds((engine.num_blocks, nkv, engine.block_size), jnp.float32)
    pools = (kp, kp, sp, sp) if quant else (kp, kp)
    fn = paged_ragged_attention_quant_pallas if quant \
        else paged_ragged_attention_pallas
    rmax = engine.max_batch
    for kind, tb in engine._bucket_grid():
        if kind != "ragged":
            continue
        if not supports(engine.block_size, d, nkv, nkv, tb):
            continue
        bounds = {0: (0, engine.num_blocks - 1), 1: (0, tb),
                  2: (0, tb), 3: (0, engine.max_model_len - 1)}
        yield registry.KernelCase(
            f"ragged{'_quant' if quant else ''}[{tb}]", fn,
            (sds((tb, nkv, d), engine.dtype),) + pools
            + (sds((rmax, engine.max_pages), jnp.int32),
               sds((rmax,), jnp.int32), sds((rmax,), jnp.int32),
               sds((rmax,), jnp.int32)), bounds)


def _engine_cases(engine):
    return _cases(engine, quant=False)


@registry.register_kernel(
    "paged_ragged_attention",
    fallback="paddle_tpu.inference.llm.paged_attention:"
             "paged_ragged_attention_xla",
    parity="tests/test_pallas_kernels.py::TestRaggedAttention::"
           "test_mixed_batch_parity",
    engine_shapes=_engine_cases,
    supports=supports)
def paged_ragged_attention_pallas(q, k_pages, v_pages, block_tables,
                                  row_start, row_qlen, row_pos0,
                                  interpret=False):
    """Ragged paged attention over T packed query tokens.

    Returns [T, Nq, D]; tokens outside every row are exact zeros.  See
    the module docstring for the row-descriptor layout and the host
    packing contract.
    """
    return _launch(q, k_pages, v_pages, (), block_tables, row_start,
                   row_qlen, row_pos0, interpret)


def _launch(q, k_pages, v_pages, scales, block_tables, row_start,
            row_qlen, row_pos0, interpret):
    """The one pallas_call behind both entry points; ``scales`` is ()
    or the int8 pool's (k_scales, v_scales)."""
    t, nq, d = q.shape
    _, nkv, bs, _ = k_pages.shape
    r, num_pages = block_tables.shape
    g = nq // nkv
    nc = t // _TQ
    tg = (t + _TQ) * g          # one chunk of spill slack
    # [T, Nkv, G, D] -> [Nkv, T*G, D] in f32 (see module docstring):
    # flat row i of head j is query token i // G, padded so a tail
    # chunk never leaves the block
    qg = q.astype(jnp.float32).reshape(t, nkv, g, d).transpose(1, 0, 2, 3)
    qg = jnp.pad(qg.reshape(nkv, t * g, d), ((0, 0), (0, _TQ * g),
                                             (0, 0)))

    q_spec = pl.BlockSpec((1, tg, d),
                          lambda j, rr, p, bt, st, ql, p0: (j, 0, 0))
    page_spec = pl.BlockSpec((1, 1, bs, d),
                             lambda j, rr, p, bt, st, ql, p0:
                             (bt[rr, p], j, 0, 0))
    # a (1, 1, bs) block of the [NB, Nkv, bs] scales would squeeze the
    # second-minor axis; the whole-page tile is Nkv*bs floats
    scale_spec = pl.BlockSpec((1, nkv, bs),
                              lambda j, rr, p, bt, st, ql, p0:
                              (bt[rr, p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nkv, r, num_pages),
        in_specs=[q_spec, page_spec, page_spec]
        + [scale_spec] * len(scales),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((tg, d), jnp.float32),
            pltpu.VMEM((tg, 1), jnp.float32),
            pltpu.VMEM((tg, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, block_size=bs, group=g,
                          nc=nc, quant=len(scales) > 0),
        name=KERNEL_NAME + ("_int8" if scales else ""),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nkv, tg, d), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), row_start.astype(jnp.int32),
      row_qlen.astype(jnp.int32), row_pos0.astype(jnp.int32),
      qg, k_pages, v_pages, *scales)
    return out[:, :t * g].reshape(nkv, t, g, d).transpose(
        1, 0, 2, 3).reshape(t, nq, d).astype(q.dtype)


def _quant_engine_cases(engine):
    """Yielded only for a KV-quantized engine (a full-precision engine
    never launches this kernel, so its sweep stays the bf16 entry's)."""
    if getattr(engine, "_kv_quant", False):
        yield from _cases(engine, quant=True)


@registry.register_kernel(
    "paged_ragged_attention_quant",
    fallback="paddle_tpu.inference.llm.paged_attention:"
             "paged_ragged_attention_quant_xla",
    parity="tests/test_pallas_kernels.py::TestRaggedAttentionQuant::"
           "test_mixed_batch_parity",
    engine_shapes=_quant_engine_cases,
    supports=supports)
def paged_ragged_attention_quant_pallas(q, k_pages, v_pages, k_scales,
                                        v_scales, block_tables,
                                        row_start, row_qlen, row_pos0,
                                        interpret=False):
    """Ragged paged attention over an INT8 pool with in-kernel dequant.

    Same contract as :func:`paged_ragged_attention_pallas`, plus
    ``k_scales``/``v_scales`` [NB, Nkv, bs] float32 — one symmetric
    dequant scale per (page, kv head, slot), written by the engine's
    quantized append (inference/llm/quant.py).  Each (kv head, row,
    page) program loads its int8 [bs, D] page block and the page's
    scale tile, dequantizes in f32 registers, and runs the identical
    online-softmax walk — HBM reads stay 1 byte per pool element."""
    return _launch(q, k_pages, v_pages, (k_scales, v_scales),
                   block_tables, row_start, row_qlen, row_pos0, interpret)
