"""The one helper that turns the per-phase shapes tests think in — B
sequences of T query slots each — into the ragged row descriptors of
``paged_ragged_attention`` (inference/llm/paged_attention.py).

A decode batch is ``T == 1`` with ``ctx = lengths[:, None]``; a
speculative-verify batch is T consecutive positions per sequence; one
prefill chunk is ``B == 1`` with ``ctx = start + 1 + arange(C)``.
"""

import jax.numpy as jnp

from paddle_tpu.inference.llm.paged_attention import (
    paged_ragged_attention,
    paged_ragged_attention_xla,
)


def rows_attention(q, k_pages, v_pages, block_tables, ctx, interpret=None):
    """q [B, T, Nq, D] against a head-major pool through
    ``block_tables`` [B, P]; ``ctx`` [B, T] is each slot's visible
    context length (0 = dead slot -> exact-zero output; a sequence's
    live slots are a prefix at consecutive positions).  Sequence b
    becomes ragged row (start=b*T, qlen=#live slots, pos0=ctx[b,0]-1)
    sharing ONE block-table row.  ``interpret=None`` runs the masked
    XLA path, ``True`` the dispatcher with the Pallas kernel
    interpreted.  ``ctx`` may be traced.  Returns [B, T, Nq, D]."""
    b, t, nq, d = q.shape
    ctx = jnp.asarray(ctx, jnp.int32)
    qf, ctx_f = q.reshape(b * t, nq, d), ctx.reshape(b * t)
    rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), t)
    if interpret is None:
        out = paged_ragged_attention_xla(qf, k_pages, v_pages,
                                         block_tables, ctx_f, rows)
    else:
        out = paged_ragged_attention(
            qf, {"k": k_pages, "v": v_pages}, block_tables, ctx_f, rows,
            jnp.arange(b, dtype=jnp.int32) * t,
            (ctx > 0).astype(jnp.int32).sum(axis=1),
            jnp.maximum(ctx[:, 0] - 1, 0), interpret=interpret)
    return out.reshape(b, t, nq, d)
