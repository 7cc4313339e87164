"""Latent attention's q, k and v expanded for the flash calls as Pallas TPU
kernels: ONE forward, ``mla_expand_fwd``, and ONE backward,
``mla_expand_bwd``, under one ``jax.custom_vjp``.

The op (``models/mla_moe.py``; the XLA composition is
``ops.pallas._xla_mla_expand_qkv``), a head ``nope + rope`` wide in q and
``nope + v`` wide in ``kv_b``:

    q'  = [q_nope | rot(q_rope)]         heads x (nope + rope)
    k   = [kv_b's nope | rot(k_rope)]    k_rope [B, T, rope]: ONE for all heads
    v   = kv_b's v                       heads x v

It is linear in q, ``kv_b`` and ``k_rope`` and elementwise but for the
backward's sum of ``k_rope``'s gradient over the heads; XLA compiled it as
float32 halves, strided de-interleaves, padded concatenations, slices and
the layout copies of both flash calls (15.7% of the kanana cell's step,
``PERF.md`` section 6, PR 42).

Both kernels run the grid ``(batch, row block, heads / hp)``, ``hp`` the
fewest heads whose lanes are whole 128-lane tiles in q AND ``kv_b`` (two at
128 + 64 | 128).  The forward reads a ``[rows, hp (nope + rope)]`` block of
q's ``[B, T, N (nope + rope)]`` view and a ``[rows, hp (nope + v)]`` block
of ``kv_b``'s ``[B, T, N (nope + v)]`` view, where the projections leave
them, and writes q, k ``[B, N, T, nope + rope]`` and v ``[B, N, T, v]``
blocks, where the flash kernels read them: the entry point returns the
``transpose(0, 2, 1, 3)`` VIEW, against which ``attention_kernel._to_kernel``'s
transpose cancels in XLA.  ``k_rope``'s row block is rotated ONCE, at the
first step along the head axis, into a VMEM scratch every head's k is
written from.  The backward is the transposed map and keeps no residual but
the tables: dq's and dk's first ``nope`` lanes and dv are moved back,
dq's rotary lanes rotated back, and ``k_rope``'s gradient is the float32
SUM of dk's rotary lanes over the heads, carried in a VMEM scratch down the
head axis and rotated back at the last step.

The rotation, with ``x1``, ``x2`` the halves of a pair's members (the
published ``rope_interleave`` layout holds a pair in neighbouring lanes;
the result stays in halves, q and k alike):

    [x1 c - x2 s | x2 c + x1 s] = (x P_d) * [c | c] + (x P_s) * [s | s]

``P_d`` de-interleaves (the identity without ``interleave``) and ``P_s``
gives ``[-x2 | x1]``: ``rope x rope`` matrices of 0 and +-1, so each dot
moves one operand to each lane and changes no bit (float32 accumulation;
``HIGHEST`` on float32 operands), on an MXU that has nothing else to do
here.  Backward ``(g P_d^T) * c_i + (g P_s^T) * s_i`` with the tables in the
INPUT's lane order.  Rounding points, ``models/mla_moe.py apply_rope``'s:
operands to float32, two products and their sum in float32, ONE cast to the
storage dtype; the head sum in float32 (the composition rounds it to the
storage dtype before rotating it back).

Constraints (else the dispatcher ``ops.pallas.mla_expand_qkv`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, _NN, _dot_for

# what one grid step's blocks may hold, in and out (Pallas keeps two of
# each): rows of 1,024 at the published 32 x (128 + 64 | 128) in bfloat16
_STEP_BYTES = 5 * 1024 * 1024


def _tile_heads(nope, rope, v_dim):
    """The fewest heads whose q lanes and ``kv_b`` lanes are both whole
    128-lane tiles."""
    return math.lcm(_LANES // math.gcd(_LANES, nope + rope),
                    _LANES // math.gcd(_LANES, nope + v_dim))


def _heads_per_step(heads, nope, rope, v_dim):
    """:func:`_tile_heads`, or every head where that does not divide them
    (a block that is the whole axis needs no tile: interpret mode's)."""
    need = _tile_heads(nope, rope, v_dim)
    return need if heads % need == 0 else heads


def _pick_rows(seq, heads, nope, rope, v_dim, dtype):
    hp = _heads_per_step(heads, nope, rope, v_dim)
    lanes = 3 * (nope + rope) + 2 * (nope + v_dim)     # q, q', k; kv_b, k|v
    per_row = hp * lanes * jnp.dtype(dtype).itemsize
    for rows in (1024, 512, 256, 128, 64, 32, 16):
        if seq % rows == 0 and rows * per_row <= _STEP_BYTES:
            return rows
    return None


def supports(seq, heads, nope, rope, v_dim, dtype):
    """k's first lanes and v whole 128-lane tiles of ``kv_b`` (so a head's
    k and v leave as whole tiles and q's rotary lanes start on one), an
    even rotary width of at most a tile, heads that pair up into whole
    tiles, rows that tile by 16; float32 or bfloat16."""
    return (nope % _LANES == 0 and v_dim % _LANES == 0
            and rope % 2 == 0 and 0 < rope <= _LANES
            and heads % _tile_heads(nope, rope, v_dim) == 0
            and _pick_rows(seq, heads, nope, rope, v_dim, dtype) is not None
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _lane_maps(rope, interleave):
    """``P_d`` and ``P_s`` of the module's docstring, ``[2, rope, rope]``."""
    half = rope // 2
    at = np.arange(half)
    x1, x2 = (2 * at, 2 * at + 1) if interleave else (at, half + at)
    maps = np.zeros((2, rope, rope), np.float32)
    maps[0, x1, at] = maps[0, x2, half + at] = 1.0
    maps[1, x2, at], maps[1, x1, half + at] = -1.0, 1.0
    return maps


def _rotated(x, maps_ref, c_ref, s_ref):
    """``x [rows, rope]`` through the lane maps and the tables, float32
    (``_dot_for``: float32 accumulation, and float32 PRODUCTS for float32
    operands, which the MXU's default would round to bfloat16)."""
    dot = _dot_for(x.dtype)
    return dot(x, maps_ref[0].astype(x.dtype), _NN) * c_ref[...] \
        + dot(x, maps_ref[1].astype(x.dtype), _NN) * s_ref[...]


# ----------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, kv_ref, kr_ref, c_ref, s_ref, maps_ref, qo_ref, ko_ref,
                vo_ref, k_rot, *, nope, rope, v_dim):
    @pl.when(pl.program_id(2) == 0)
    def _():
        k_rot[...] = _rotated(kr_ref[0], maps_ref, c_ref, s_ref).astype(
            k_rot.dtype)

    d, e = nope + rope, nope + v_dim
    for j in range(qo_ref.shape[1]):
        qo_ref[0, j, :, :nope] = q_ref[0, :, j * d:j * d + nope]
        qo_ref[0, j, :, nope:] = _rotated(
            q_ref[0, :, j * d + nope:(j + 1) * d], maps_ref, c_ref,
            s_ref).astype(qo_ref.dtype)
        ko_ref[0, j, :, :nope] = kv_ref[0, :, j * e:j * e + nope]
        ko_ref[0, j, :, nope:] = k_rot[...]
        vo_ref[0, j] = kv_ref[0, :, j * e + nope:(j + 1) * e]


def _specs(rows, hp, nope, rope, v_dim):
    """The blocks both kernels share: the projections' side ``[B, T, N
    width]``, the flash calls' side ``[B, N, T, width]``, ``k_rope``, a
    table's rows and the lane maps."""
    d, e = nope + rope, nope + v_dim
    return dict(
        q=pl.BlockSpec((1, rows, hp * d), lambda b, i, p: (b, i, p)),
        kv=pl.BlockSpec((1, rows, hp * e), lambda b, i, p: (b, i, p)),
        kr=pl.BlockSpec((1, rows, rope), lambda b, i, p: (b, i, 0)),
        table=pl.BlockSpec((rows, rope), lambda b, i, p: (i, 0)),
        maps=pl.BlockSpec((2, rope, rope), lambda b, i, p: (0, 0, 0)),
        qk_heads=pl.BlockSpec((1, hp, rows, d), lambda b, i, p: (b, p, i, 0)),
        v_heads=pl.BlockSpec((1, hp, rows, v_dim),
                             lambda b, i, p: (b, p, i, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)

# jit(inline=True): a layer's call is traced once a step, not once a block
# (``ssd_scan_kernel._launch``)
_launch = functools.partial(jax.jit, inline=True,
                            static_argnames=("dims", "rows", "interpret"))


@_launch
def _expand_fwd(q, kv, kr, c, s, maps, dims, rows, interpret):
    heads, nope, rope, v_dim = dims
    batch, seq, _ = q.shape
    hp = _heads_per_step(*dims)
    sp = _specs(rows, hp, nope, rope, v_dim)
    qk = jax.ShapeDtypeStruct((batch, heads, seq, nope + rope), q.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nope=nope, rope=rope, v_dim=v_dim),
        name="mla_expand_fwd",
        grid=(batch, seq // rows, heads // hp),
        in_specs=[sp["q"], sp["kv"], sp["kr"], sp["table"], sp["table"],
                  sp["maps"]],
        out_specs=[sp["qk_heads"], sp["qk_heads"], sp["v_heads"]],
        out_shape=[qk, qk, jax.ShapeDtypeStruct(
            (batch, heads, seq, v_dim), q.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, rope), q.dtype)],
        interpret=interpret, compiler_params=_PARAMS,
    )(q, kv, kr, c, s, maps)


# ---------------------------------------------------------------- backward --

def _bwd_kernel(dq_ref, dk_ref, dv_ref, c_ref, s_ref, maps_ref, dqi_ref,
                dkv_ref, dkr_ref, k_sum, *, nope, rope, v_dim):
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        k_sum[...] = jnp.zeros(k_sum.shape, jnp.float32)

    d, e = nope + rope, nope + v_dim
    total = k_sum[...]
    for j in range(dq_ref.shape[1]):
        dqi_ref[0, :, j * d:j * d + nope] = dq_ref[0, j, :, :nope]
        dqi_ref[0, :, j * d + nope:(j + 1) * d] = _rotated(
            dq_ref[0, j, :, nope:], maps_ref, c_ref, s_ref).astype(
                dqi_ref.dtype)
        dkv_ref[0, :, j * e:j * e + nope] = dk_ref[0, j, :, :nope]
        dkv_ref[0, :, j * e + nope:(j + 1) * e] = dv_ref[0, j]
        total = total + dk_ref[0, j, :, nope:].astype(jnp.float32)
    k_sum[...] = total

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dkr_ref[0] = _rotated(total, maps_ref, c_ref, s_ref).astype(
            dkr_ref.dtype)


@_launch
def _expand_bwd(dq, dk, dv, c, s, maps, dims, rows, interpret):
    heads, nope, rope, v_dim = dims
    batch, _, seq, _ = dq.shape
    hp = _heads_per_step(*dims)
    sp = _specs(rows, hp, nope, rope, v_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nope=nope, rope=rope, v_dim=v_dim),
        name="mla_expand_bwd",
        grid=(batch, seq // rows, heads // hp),
        in_specs=[sp["qk_heads"], sp["qk_heads"], sp["v_heads"],
                  sp["table"], sp["table"], sp["maps"]],
        out_specs=[sp["q"], sp["kv"], sp["kr"]],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, heads * (nope + rope)),
                                 dq.dtype),
            jax.ShapeDtypeStruct((batch, seq, heads * (nope + v_dim)),
                                 dq.dtype),
            jax.ShapeDtypeStruct((batch, seq, rope), dq.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, rope), jnp.float32)],
        interpret=interpret, compiler_params=_PARAMS,
    )(dq, dk, dv, c, s, maps)


# ------------------------------------------------------------- public API --

def _tables(cos, sin, interleave, backward):
    """``cos``, ``sin`` ``[T, rope / 2]`` over a head's rotary lanes: in
    halves ``[c | c]`` where the forward's products are (its result's
    order), in the INPUT's lane order for the backward's (pair by pair
    under ``interleave``)."""
    if backward and interleave:
        return jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
    return jnp.concatenate([cos, cos], -1), jnp.concatenate([sin, sin], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _expand_kernels(q, kv, kr, cos, sin, dims, interleave, rows, interpret):
    return _fwd_rule(q, kv, kr, cos, sin, dims, interleave, rows,
                     interpret)[0]


def _fwd_rule(q, kv, kr, cos, sin, dims, interleave, rows, interpret):
    maps = jnp.asarray(_lane_maps(dims[2], interleave))
    out = _expand_fwd(q, kv, kr, *_tables(cos, sin, interleave, False), maps,
                      dims, rows, interpret)
    return tuple(out), (cos, sin)


def _bwd_rule(dims, interleave, rows, interpret, res, grads):
    cos, sin = res
    maps = jnp.asarray(_lane_maps(dims[2], interleave).transpose(0, 2, 1))
    dq, dkv, dkr = _expand_bwd(*grads, *_tables(cos, sin, interleave, True),
                               maps, dims, rows, interpret)
    return dq, dkv, dkr, jnp.zeros_like(cos), jnp.zeros_like(sin)


_expand_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/mla_moe.py``
    trains; the decode path is the absorbed form).  The lint sweeps one
    training-shaped case, forward and backward, at the published head
    (128 + 64 over 128, interleaved) in the engine's dtype."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    heads, nope, rope, v_dim, seq = 4, 128, 64, 128, 256

    def vjp(q, kv_b, k_rope, cos, sin):
        def loss(*o):
            return sum(jnp.sum(x.astype(f32)) for x in mla_expand_pallas(
                *o, cos, sin, nope=nope, interleave=True))
        # value AND gradients: the map is linear, so its gradients alone
        # would trace no forward kernel
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, kv_b, k_rope)

    yield registry.KernelCase(
        f"vjp[s{seq},h{heads}x({nope}+{rope}|{v_dim})]", vjp,
        (sds((1, seq, heads, nope + rope), engine.dtype),
         sds((1, seq, heads, nope + v_dim), engine.dtype),
         sds((1, seq, rope), engine.dtype),
         sds((seq, rope // 2), f32), sds((seq, rope // 2), f32)), None)


@registry.register_kernel(
    "mla_expand",
    fallback="paddle_tpu.ops.pallas:_xla_mla_expand_qkv",
    parity="tests/test_pallas_kernels.py::test_mla_expand_kernels_match_"
           "the_composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def mla_expand_pallas(q, kv_b, k_rope, cos, sin, *, nope, interleave,
                      interpret=False, block_rows=None):
    """``q [B, T, N, nope + rope]``, ``kv_b [B, T, N, nope + v]``, ``k_rope
    [B, T, rope]``, ``cos`` / ``sin`` ``[T, rope / 2]`` float32 -> q, k
    ``[B, T, N, nope + rope]`` and v ``[B, T, N, v]``, each the transposed
    view of a heads-first array; differentiable in the first three.
    ``block_rows`` is the tests' (a row block of their own)."""
    batch, seq, heads, width = q.shape
    rope, v_dim = width - nope, kv_b.shape[3] - nope
    rows = block_rows or _pick_rows(seq, heads, nope, rope, v_dim, q.dtype)
    if kv_b.dtype != q.dtype or k_rope.dtype != q.dtype or rope % 2 \
            or rows is None or seq % rows:
        raise ValueError(
            f"mla expand does not serve q{tuple(q.shape)} {q.dtype} "
            f"kv_b{tuple(kv_b.shape)} {kv_b.dtype} k_rope {k_rope.dtype} "
            f"nope={nope}: see mla_expand_kernel.supports")
    f32 = jnp.float32
    out = _expand_kernels(
        q.reshape(batch, seq, heads * width),
        kv_b.reshape(batch, seq, heads * (nope + v_dim)), k_rope,
        cos.astype(f32), sin.astype(f32), (heads, nope, rope, v_dim),
        bool(interleave), rows, interpret)
    return tuple(x.transpose(0, 2, 1, 3) for x in out)
