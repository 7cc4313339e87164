"""Pallas decode attention — single-token query over a ragged KV cache.

The generative-decode hot loop (reference analog: the masked attention
inside fused_multi_transformer_op.cu's decode branch).  Shapes:

    q        [B, Nq, D]          one new token per sequence
    k_cache  [B, S_max, Nkv, D]  Nq % Nkv == 0 (GQA: G = Nq//Nkv query
    v_cache  [B, S_max, Nkv, D]  heads share one KV head)
    lengths  [B] int32           valid cache prefix per sequence

Kernel layout: one program per (batch, kv_head); the program streams the
KV cache in S-blocks from VMEM, computing all G grouped query heads at
once ([G, D] @ [D, S_blk] rides the MXU), with an online softmax across
blocks and per-position masking by ``lengths`` — ragged sequences cost
only their occupied blocks' bandwidth, never S_max compute on the VPU
path.

Shape constraints: D <= 128, S_max % block_s == 0.  ``supports``
gates callers; the XLA fallback computes the same masked attention
densely.

INTERPRET MODE ONLY today — see ``TPU_REFUSAL``.  The TPU gates
(framework/ir.py, incubate.nn.functional.ragged_decode_attention) raise
it where they would select this kernel, so a TPU caller learns why
instead of silently getting the XLA composition.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry
from .common import _NEG_INF, pick_block

DEFAULT_BLOCK_S = 128

# What the Pallas TPU lowering says to this kernel (jax 0.9.0, checked
# by tests/test_tpu_lowering.py).  The ragged paged kernel had the same
# fault and was repaired by making its pool head-major; here the cache
# layout belongs to the callers (FusedMultiTransformer, llama decode),
# so the repair is theirs to make first.
TPU_REFUSAL = (
    "decode_attention_pallas does not compile for TPU: its "
    "(1, s_max, 1, d) cache block and (1, g, 1, d) q/out blocks squeeze "
    "the second-minor (head) axis of [B, S_max, Nkv, D], and its (1,) "
    "lengths block is not a VMEM tile — Mosaic needs the last two block "
    "dims tile-aligned or whole.  It needs a head-major dense cache; "
    "until it has one, call decode_attention_xla.")


def supports(s_max, head_dim, num_q_heads, num_kv_heads):
    return (head_dim <= 128 and pick_block(s_max, DEFAULT_BLOCK_S) is not None
            and num_q_heads % num_kv_heads == 0)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *, block_s):
    """One (batch, kv_head) program: G query heads over the KV prefix."""
    q = q_ref[0, :, 0, :].astype(jnp.float32)          # [G, D]
    s_max = k_ref.shape[1]
    g, d = q.shape
    length = len_ref[0]

    def body(i, carry):
        o, m, l = carry
        k = k_ref[0, pl.ds(i * block_s, block_s), 0, :] \
            .astype(jnp.float32)                        # [S, D]
        v = v_ref[0, pl.ds(i * block_s, block_s), 0, :] \
            .astype(jnp.float32)                        # [S, D]
        s = q @ k.T / jnp.sqrt(jnp.float32(d))          # [G, S]
        pos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_s), 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # [G, S]
        alpha = jnp.exp(m - m_new)
        o = o * alpha + p @ v                           # [G, D]
        l = l * alpha[:, 0] + p.sum(axis=1)
        return o, m_new, l

    num_blocks = s_max // block_s
    o0 = jnp.zeros((g, d), jnp.float32)
    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, num_blocks, body, (o0, m0, l0))
    # lengths[b] == 0: every position is masked, the running max collapses
    # to the mask value so p == 1 everywhere and o/l silently averages the
    # whole (uninitialized) cache — emit zeros for empty sequences instead
    safe = jnp.where(length > 0, o / jnp.maximum(l[:, None], 1e-30), 0.0)
    o_ref[0, :, 0, :] = safe.astype(o_ref.dtype)


def _engine_cases(engine):
    """Dense-cache decode at power-of-two batch buckets up to the
    engine's max_batch (S_max is the paged pool's token horizon,
    per-shard head counts under tp).  The serving engine's own bucket
    grid is a single ragged-token family now, so the batch buckets are
    enumerated directly here rather than read off ``_bucket_grid()``."""
    nkv = max(engine.num_heads // engine.tp, 1)
    d = engine.head_dim
    s_max = engine.max_pages * engine.block_size
    if not supports(s_max, d, nkv, nkv):
        return
    sds = jax.ShapeDtypeStruct
    bkt = 1
    while True:
        q = sds((bkt, nkv, d), engine.dtype)
        kc = sds((bkt, s_max, nkv, d), engine.dtype)
        yield registry.KernelCase(
            f"decode[{bkt}]", decode_attention_pallas,
            (q, kc, kc, sds((bkt,), jnp.int32)), None)
        if bkt >= engine.max_batch:
            break
        bkt = min(bkt * 2, engine.max_batch)


@registry.register_kernel(
    "decode_attention",
    fallback="paddle_tpu.ops.pallas.decode_attention_kernel:"
             "decode_attention_xla",
    parity="tests/test_pallas_kernels.py::TestDecodeAttention::"
           "test_matches_xla_reference_ragged_gqa",
    engine_shapes=_engine_cases,
    supports=supports,
    tpu_refusal=TPU_REFUSAL)
def decode_attention_pallas(q, k_cache, v_cache, lengths, block_s=None,
                            interpret=False):
    """Returns [B, Nq, D] attention outputs for one decode step."""
    b, nq, d = q.shape
    s_max, nkv = k_cache.shape[1], k_cache.shape[2]
    g = nq // nkv
    block_s = block_s or pick_block(s_max, DEFAULT_BLOCK_S)
    # regroup query heads by their kv head: [B, Nkv, G, D]
    qg = q.reshape(b, nkv, g, d)
    lengths = lengths.astype(jnp.int32)

    grid = (b, nkv)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s),
        name="decode_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, 1, d), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, s_max, 1, d), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, s_max, 1, d), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((1, g, 1, d), lambda i, j: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, nkv, d), q.dtype),
        interpret=interpret,
    )(qg.transpose(0, 2, 1, 3), k_cache, v_cache, lengths)
    # out [B, G, Nkv, D] -> [B, Nq, D]
    return out.transpose(0, 2, 1, 3).reshape(b, nq, d)


def decode_attention_xla(q, k_cache, v_cache, lengths):
    """Dense masked reference/fallback (same semantics)."""
    b, nq, d = q.shape
    s_max, nkv = k_cache.shape[1], k_cache.shape[2]
    g = nq // nkv
    qg = q.reshape(b, nkv, g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bngd,bsnd->bngs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(s_max)[None, None, None, :] < \
        lengths[:, None, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngs,bsnd->bngd", p,
                     v_cache.astype(jnp.float32))
    # empty sequences: the all-masked softmax degenerates to a uniform
    # average over the cache — zero those rows (matches the Pallas kernel)
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, nq, d).astype(q.dtype)
