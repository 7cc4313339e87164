"""Paged attention — backend dispatch behind ONE ragged entry point.

Every serving phase is the same computation: a query token at absolute
position ``p`` attends over pool positions ``0..p`` through its row's
block table.  A decode row is a one-token chunk, a speculative-verify
row is a K+1-token chunk, a prefill chunk is a C-token chunk — so the
engine launches a single ragged kernel over the step's packed query
tokens, against one layer's view of the KV cache (kv_cache.py), whose
leaves say whether the pool is int8.

Two implementations with identical semantics:

- TPU: the Pallas ragged kernel (ops/pallas/ragged_attention_kernel.py)
  DMAs exactly the pages a row owns via scalar-prefetched block tables
  and per-row ``(query_start, query_len, context_len)`` descriptors.
- everywhere else (and under jit on CPU test rigs): gather each token's
  pages into the dense ragged layout and run the masked attention —
  bitwise the same per-element reductions as the retired per-phase
  fallbacks (same einsum contraction order, f32 softmax, -1e30 mask),
  which is what keeps the engine-vs-dense token-exactness tests
  meaningful across the refactor.

On a TPU the masked-XLA path is never taken silently: it runs only
when the kernel's ``supports()`` refuses the shape, and the refusal warns
once with the shape and the reason (``ops.pallas.KernelFallbackWarning``).

The pool is HEAD-MAJOR, ``[NB, Nkv, bs, D]`` — the layout the kernel's
page block needs (see the kernel module); the fallbacks gather pages
and swap the head and slot axes back into the dense ``[S, Nkv, D]``
order their einsums always used.

Like the kernel, the 1/sqrt(D) scale is applied inside.  Note the
engine no longer pre-scales query heads before calling in — the old
decode/verify paths multiplied by ``scale * sqrt(head_dim)`` (exactly
1.0 for every power-of-two head_dim the models here use) and the
ragged path drops that identity dance outright.

A sequence's K+1 speculative-verify tokens share one row descriptor
and ONE block-table row: no per-token table replication is
materialized.

Tensor parallelism: the ragged entry point is head-count generic and
attention never mixes heads — the TP engine calls it UNCHANGED from
inside ``jax.shard_map`` with per-shard shapes (q [T, Nq/mp, D], pool
[NB, Nkv/mp, bs, D], block tables and row descriptors replicated).
Each shard runs its head slice against its LOCAL pool shard; no
collective is needed until the row-parallel output projection.  This
is also why the Pallas path survives the mesh: scalar-prefetched
block-table indexing cannot be GSPMD-partitioned, but under shard_map
it only ever sees fully local operands.
"""

import jax
import jax.numpy as jnp

from ...ops.pallas import _use_pallas, warn_fallback
from ...ops.pallas import ragged_attention_kernel as _kernel


def _take_kernel(q_shape, k_pages, interpret):
    """True when the ragged Pallas kernel runs these packed query
    tokens ``q_shape`` [T, Nq, D] against ``k_pages``; a TPU shape the
    kernel refuses warns once (module docstring) and takes XLA."""
    if not (_use_pallas() or interpret):
        return False
    t, nq, d = q_shape
    _, nkv, bs, _ = k_pages.shape
    if _kernel.supports(bs, d, nq, nkv, t):
        return True
    warn_fallback(
        "paged_ragged_attention",
        f"q{tuple(q_shape)} pool{tuple(k_pages.shape)}",
        "the kernel needs head_dim <= 128, block_size % 8 == 0, "
        "Nq % Nkv == 0 and tokens % 8 == 0")
    return False


def _gather_dense(pages, block_tables, scales=None):
    """[NB, Nkv, bs, D] pool x [R, P] tables -> dense [R, P*bs, Nkv, D]
    (each row's pages in table order, slot-major within a page).  An
    int8 pool passes its [NB, Nkv, bs] ``scales``: the GATHERED pages
    dequantize in f32 (``int8 * scale``), never the whole pool."""
    r, num_pages = block_tables.shape
    _, nkv, bs, d = pages.shape
    pg = pages[block_tables]                        # [R, P, Nkv, bs, D]
    if scales is not None:
        pg = pg.astype(jnp.float32) * scales[block_tables][..., None]
    return pg.transpose(0, 1, 3, 2, 4).reshape(r, num_pages * bs, nkv, d)


def paged_ragged_attention_xla(q, k_pages, v_pages, block_tables, ctx,
                               rows):
    """Masked-XLA fallback for the ragged batch, per-token form.

    q [T, Nq, D] packed query tokens; ``rows`` [T] maps each token to
    its block-table row, ``ctx`` [T] is each token's visible context
    length (0 for dead/padding tokens -> exact-zero output).  Gathers
    every token's pages and runs decode_attention_xla's exact masked
    chain (same einsum contraction order, f32 softmax, -1e30 mask), so
    each output token is bitwise the single-token decode the engine
    would have run at that position.
    """
    k = _gather_dense(k_pages, block_tables)[rows]
    v = _gather_dense(v_pages, block_tables)[rows]
    return _ragged_masked_chain(q, k, v, ctx)


def _ragged_masked_chain(q, k, v, ctx):
    """The shared per-token masked attention chain: q [T, Nq, D]
    against gathered k/v [T, S_max, Nkv, D] with per-token visible
    context ``ctx`` [T].  Extracted verbatim from the full-precision
    fallback so the int8 fallback reuses the exact same per-element
    reductions after its dequant gather."""
    t, nq, d = q.shape
    s_max, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(t, nkv, g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("tngd,tsnd->tngs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.arange(s_max)[None, None, None, :] < \
        ctx[:, None, None, None]
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("tngs,tsnd->tngd", p, v.astype(jnp.float32))
    out = jnp.where(ctx[:, None, None, None] > 0, out, 0.0)
    return out.reshape(t, nq, d).astype(q.dtype)


def paged_ragged_attention_quant_xla(q, k_pages, v_pages, k_scales,
                                     v_scales, block_tables, ctx, rows):
    """Masked-XLA fallback for the INT8 ragged batch.

    ``k_pages``/``v_pages`` [NB, Nkv, bs, D] int8 and
    ``k_scales``/``v_scales`` [NB, Nkv, bs] float32 — one symmetric
    dequant scale per (page, kv head, slot), as written by the
    engine's quantized append.  Gathers each token's pages AND scale
    rows, dequantizes in f32 (the same ``int8 * scale`` product the
    Pallas kernel applies per loaded slot), then runs the identical
    masked chain as :func:`paged_ragged_attention_xla`."""
    return _ragged_masked_chain(
        q, _gather_dense(k_pages, block_tables, k_scales)[rows],
        _gather_dense(v_pages, block_tables, v_scales)[rows], ctx)


def paged_ragged_attention(q, cache_l, block_tables, ctx, rows,
                           row_start, row_qlen, row_pos0,
                           interpret=False):
    """Ragged paged attention over T packed query tokens -> [T, Nq, D].

    ``cache_l`` is one layer's view of the KV cache (kv_cache.py):
    ``{"k", "v"}`` pools [NB, Nkv, bs, D] and, for an int8 pool,
    ``{"k_scale", "v_scale"}`` [NB, Nkv, bs] — the leaves the view has
    decide whether the read dequantizes (in-kernel on the Pallas path,
    on the gathered pages on the XLA path; no float copy of the pool is
    ever materialized).

    Carries BOTH descriptor forms because the two backends want
    different shapes of the same fact: the XLA fallback is per-token
    (``ctx`` [T], ``rows`` [T]) while the Pallas kernel is per-row
    (``row_start``/``row_qlen``/``row_pos0``, each [R], against
    block_tables [R, P]).  The caller packs rows back-to-back; token
    ``i`` of row ``r`` sits at absolute position ``row_pos0[r] + i``,
    so ``ctx`` for it must be ``row_pos0[r] + i + 1`` and 0 outside
    every row.  Tokens outside every row come back as exact zeros on
    both paths.
    """
    k_pages, v_pages = cache_l["k"], cache_l["v"]
    scales = ((cache_l["k_scale"], cache_l["v_scale"])
              if "k_scale" in cache_l else ())
    if _take_kernel(q.shape, k_pages, interpret):
        kernel = (_kernel.paged_ragged_attention_quant_pallas if scales
                  else _kernel.paged_ragged_attention_pallas)
        return kernel(q, k_pages, v_pages, *scales, block_tables,
                      row_start, row_qlen, row_pos0, interpret=interpret)
    fallback = (paged_ragged_attention_quant_xla if scales
                else paged_ragged_attention_xla)
    return fallback(q, k_pages, v_pages, *scales, block_tables, ctx, rows)
