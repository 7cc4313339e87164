"""The Mamba mixer's gated group norm (``models/nemotron_h.py
_gated_norm``) as Pallas TPU kernels: ONE forward, ``gated_norm_fwd``, and
ONE backward, ``gated_norm_bwd``, under one ``jax.custom_vjp``.

The op, on ``y [..., C]`` and a gate ``z`` of the same rows, over ``G``
equal groups of ``C / G`` lanes, with a gain ``w [C]``:

    g   = y silu(z)                               the gate BEFORE the norm
    r   = rsqrt(mean_group(g^2) + eps)            one number a row and group
    out = g r w

everything in float32, rounded ONCE, to y's dtype: what the composition
computes.  XLA runs it at ``[4096, 8192]`` over 8 groups as float32 passes
round the group statistics that it fuses into neither projection, and forms
the view ``[..., G, C / G]`` as copies ``f32[512, 8, 8, 1024]``: 5.7 ms a
layer over a step's three passes for a need of 0.9 (``PERF.md`` section 6, PR
48).  A group's lanes are whole 128-lane tiles, so here no view is formed:
the grid runs over ``(row block, group)`` and a program holds ``[rows, C /
G]`` of y, of z and of the result, a group's statistics its own lanes'.  A
block crosses once each way in y's dtype; inside it the kernels work through
``rc`` rows at a time, a chunk's float32 values the compiler's to place.

``z`` may be WIDER than the norm: ``start`` lanes into it (whole groups'
widths) the block index maps read the ``C`` lanes where they lie, so that the
mixer's gate is read out of the projection's ``[T, 18560]`` result with no
slice before the call; z's gradient comes back at z's width, zero outside
the lanes read (``causal_conv_kernel``'s way with x).

The backward keeps y and z ALONE (no float32 ``g``, no statistics): from a
chunk of y, z and the result's gradient ``do`` it forms ``g``, ``r`` and ``gh
= g r`` again and, with ``d = do w``,

    dg = r (d - gh mean_group(d gh))
    dy = dg silu(z)        dz = dg y silu'(z)        dw = sum_rows do gh

the gain's gradient summed in float32, eight partial rows a group in
registers down a block and across row blocks in ONE output block ``[8, C /
G]`` revisited down the sequential row axis (its grid is ``(group, row
block)``; ``layernorm_kernel._bwd_kernel``'s way with ``dg``); the last
eight-to-one sum is the caller's, with the cast to w's dtype.

Constraints (else the dispatcher ``ops.pallas.gated_rms_norm`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, _block_rows, pick_block

# rows of the float32 block the gain's gradient is summed in: one tile
SIDE = 8
# a chunk's rows are whole tiles of a packed dtype's sublanes
TILE = 16


def _pick_block(rows, width, dtype):
    """``(block rows, chunk rows)``: whole tiles that divide the rows, 1 MiB
    of a group's lanes at most (the backward holds five such blocks, twice
    each) and 512 rows at most, worked through 64 rows at a time (on the
    chip at ``[4096, 8192]`` over 8 groups, forward | backward: chunks of 16
    rows 0.372 | 0.589 ms, 32 0.345 | 0.553, 64 0.335 | 0.537; ``PERF.md``
    section 6, PR 48); or None."""
    if width % _LANES:
        return None
    fit = (1 << 20) // (width * jnp.dtype(dtype).itemsize)
    br = pick_block(rows, min(512, fit)) if fit >= TILE else None
    if br is None or br % TILE:
        return None
    return br, pick_block(br, 64)


def supports(rows, channels, groups, dtype, start=0):
    """A group's width whole 128-lane tiles, ``start`` whole groups' widths
    into its operand, rows whole tiles of 16 sublanes; float32 or
    bfloat16."""
    if groups < 1 or channels % groups:
        return False
    width = channels // groups
    return (_pick_block(rows, width, dtype) is not None
            and start % width == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


# ----------------------------------------------------------- kernel bodies --

def _gate(y, z):
    """``(g, s)``: ``y silu(z)`` in the composition's order and the
    sigmoid, both float32."""
    s = jax.nn.sigmoid(z)
    return y * (z * s), s


def _scale(g, eps):
    """A group's ``rsqrt(mean(g^2) + eps)``, a column."""
    return jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)


def _fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, eps, rc):
    f32 = jnp.float32
    w = w_ref[...]

    def row_chunk(q, carry):
        at = _block_rows(q, rc)
        g, _ = _gate(y_ref[at, :].astype(f32), z_ref[at, :].astype(f32))
        o_ref[at, :] = (g * _scale(g, eps) * w).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // rc, row_chunk, 0)


def _fold(v):
    """``[rc, L]`` -> the eight partial sums ``[8, L]`` of its rows (adds
    of whole tiles)."""
    out = v[:SIDE]
    for r in range(SIDE, v.shape[0], SIDE):
        out = out + v[r:r + SIDE]
    return out


def _bwd_kernel(y_ref, z_ref, w_ref, do_ref, dy_ref, dz_ref, dw_ref, *, eps,
                rc):
    f32 = jnp.float32
    w = w_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def row_chunk(q, sums):
        at = _block_rows(q, rc)
        y, z = y_ref[at, :].astype(f32), z_ref[at, :].astype(f32)
        do = do_ref[at, :].astype(f32)
        g, s = _gate(y, z)
        r = _scale(g, eps)
        gh, d = g * r, do * w
        dg = r * (d - gh * jnp.mean(d * gh, axis=-1, keepdims=True))
        dy_ref[at, :] = (dg * (z * s)).astype(dy_ref.dtype)
        # d silu(z) = s (1 + z (1 - s))
        dz_ref[at, :] = (dg * y * (s * (1.0 + z * (1.0 - s)))).astype(
            dz_ref.dtype)
        return sums + _fold(do * gh)

    dw_ref[...] += jax.lax.fori_loop(
        0, y_ref.shape[0] // rc, row_chunk,
        jnp.zeros((SIDE, y_ref.shape[1]), f32))


# ------------------------------------------------------------ pallas calls --

# jit(inline=True): a layer's call is traced once a step, not once a block
# (``ssd_scan_kernel._launch``)
@functools.partial(jax.jit, inline=True, static_argnames=(
    "groups", "eps", "start", "block", "interpret"))
def _norm_fwd(y, z, w, groups, eps, start, block, interpret):
    rows, channels = y.shape
    width = channels // groups
    br, rc = block
    at = start // width
    here = pl.BlockSpec((br, width), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, rc=rc),
        name="gated_norm_fwd",
        grid=(rows // br, groups),
        # z where it lies, ``start`` lanes into a wider operand
        in_specs=[here,
                  pl.BlockSpec((br, width), lambda i, j: (i, at + j)),
                  pl.BlockSpec((1, width), lambda i, j: (0, j))],
        out_specs=here,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(y, z, w)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "groups", "eps", "start", "block", "interpret"))
def _norm_bwd(y, z, w, do, groups, eps, start, block, interpret):
    rows, channels = y.shape
    width = channels // groups
    br, rc = block
    at = start // width
    here = pl.BlockSpec((br, width), lambda j, i: (i, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, rc=rc),
        name="gated_norm_bwd",
        # the groups outermost: a group's sums are ONE output block,
        # revisited down its row blocks
        grid=(groups, rows // br),
        in_specs=[here,
                  pl.BlockSpec((br, width), lambda j, i: (i, at + j)),
                  pl.BlockSpec((1, width), lambda j, i: (0, j)),
                  here],
        out_specs=[here, here,
                   pl.BlockSpec((SIDE, width), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((SIDE, channels), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(y, z, w, do)


# ------------------------------------------------------------- public API --

def _rows(a):
    """``[..., W]`` -> ``[rows, W]``: a reshape of the leading axes."""
    return a.reshape(-1, a.shape[-1])


def _gain(weight):
    """The gain as the kernels read it: a float32 row."""
    return weight.astype(jnp.float32)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm_kernels(y, z, weight, groups, eps, start, block, interpret):
    return _fwd_rule(y, z, weight, groups, eps, start, block, interpret)[0]


def _fwd_rule(y, z, weight, groups, eps, start, block, interpret):
    out = _norm_fwd(_rows(y), _rows(z), _gain(weight), groups, eps, start,
                    block, interpret)
    return out.reshape(y.shape), (y, z, weight)


def _bwd_rule(groups, eps, start, block, interpret, res, do):
    y, z, weight = res
    dy, dz, dw = _norm_bwd(_rows(y), _rows(z), _gain(weight), _rows(do),
                           groups, eps, start, block, interpret)
    # the lanes of a wider operand that the norm did not read took no part:
    # the pad a slice's transpose would make (XLA adds it to the other
    # readers' gradients in one pass)
    dz = jnp.pad(dz.reshape(y.shape), ((0, 0),) * (y.ndim - 1) + (
        (start, z.shape[-1] - start - y.shape[-1]),))
    return (dy.reshape(y.shape), dz,
            jnp.sum(dw, axis=0).astype(weight.dtype))


_norm_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/nemotron_h.py``
    trains and has no decode path); the lint sweeps one training-shaped
    case, value and backward, in the engine's dtype: two groups of 256
    lanes, the gate read 512 lanes into a wider operand."""
    sds = jax.ShapeDtypeStruct
    y, z = sds((1, 512, 512), engine.dtype), sds((1, 512, 1280), engine.dtype)
    w = sds((512,), engine.dtype)
    norm = functools.partial(gated_norm_pallas, groups=2, epsilon=1e-5,
                             start=512)

    def vjp(y, z, w):
        def loss(*o):
            return jnp.sum(norm(*o).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(y, z, w)

    yield registry.KernelCase("value[s512,c512,g2]", norm, (y, z, w), None)
    yield registry.KernelCase("vjp[s512,c512,g2]", vjp, (y, z, w), None)


@registry.register_kernel(
    "gated_norm",
    fallback="paddle_tpu.models.nemotron_h:_gated_norm_composed",
    parity="tests/test_gated_norm_kernel.py::test_kernels_match_the_"
           "composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def gated_norm_pallas(y, z, weight, *, groups, epsilon, start=0,
                      interpret=False, block=None):
    """``y [..., C]``, ``z [..., W]`` of the same rows, ``weight [C]`` ->
    ``rms_groups(y * silu(z[..., start:start + C])) * weight`` ``[..., C]``
    in y's dtype, the statistics over each of ``groups`` equal parts of
    ``C``, the gate read where it lies in z (no slice is made);
    differentiable in all three.  ``block`` ``(block rows, chunk rows)`` is
    the tests' and the tuning's."""
    channels, wide = y.shape[-1], z.shape[-1]
    rows = math.prod(y.shape[:-1])
    if not supports(rows, channels, groups, y.dtype, start) \
            or z.dtype != y.dtype or z.shape[:-1] != y.shape[:-1] \
            or weight.shape != (channels,) \
            or not 0 <= start <= wide - channels:
        raise ValueError(
            f"gated norm does not serve y{tuple(y.shape)} {y.dtype} "
            f"z{tuple(z.shape)} {z.dtype} groups={groups} start={start}: "
            f"see gated_norm_kernel.supports")
    br, rc = block = block or _pick_block(rows, channels // groups, y.dtype)
    if rows % br or br % rc or rc % TILE:
        raise ValueError(f"gated norm: block {block} does not tile "
                         f"y{tuple(y.shape)}")
    return _norm_kernels(y, z, weight, int(groups), float(epsilon),
                         int(start), tuple(block), bool(interpret))
