"""Fused LayerNorm as a Pallas TPU kernel (forward + backward).

The reference fuses layernorm into residual/dropout chains with hand-written
CUDA (paddle/phi/kernels/fusion/gpu/fused_layernorm_*); XLA already fuses
most of this, so the Pallas kernel targets the remaining win: one pass over
HBM computing mean/rstd and the normalized output per row block, with a
recompute-free backward that reads the saved statistics.

Layout: input reshaped to [rows, C]; grid over row blocks; C (the feature
dim) must be lane-aligned (multiple of 128) for the kernel path, else the
caller falls back to the XLA composition.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry

_ROW_BLOCK = 256
# The backward keeps the x, dy and dx row blocks resident at once, each
# double-buffered: a [256, 4096] f32 block makes that 16.04 MiB and
# Mosaic refuses it (16 MiB scoped VMEM, v5e).  One block of at most
# 1 MiB of f32 keeps every width inside; up to C = 1024 that is still
# the full 256 rows.
_MAX_BLOCK_ELEMS = 256 * 1024


def _pick_rows(rows, channels):
    for b in (_ROW_BLOCK, 128, 64, 32, 16, 8):
        if rows % b == 0 and b * channels <= _MAX_BLOCK_ELEMS:
            return b
    return None


def supports(rows, channels):
    return channels % 128 == 0 and _pick_rows(rows, channels) is not None


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                   # [BR, C]
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mu) * rstd
    wdy = dy * g
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((wdy - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)
    # dg/db accumulate across the (sequential) TPU grid into one [1, C]
    # block — a [nb, C] partials array would need a block whose leading dim
    # is 1, which the TPU lowering rejects for nb not divisible by 8.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _ln_fwd(x2d, g, b, eps, block_rows, interpret):
    rows, c = x2d.shape
    grid = (rows // block_rows,)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layernorm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, c), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, g.reshape(1, c), b.reshape(1, c))
    return y, mu, rstd


def _ln_bwd(x2d, g, mu, rstd, dy, block_rows, interpret):
    rows, c = x2d.shape
    nb = rows // block_rows
    dx, dgp, dbp = pl.pallas_call(
        _bwd_kernel,
        name="layernorm_bwd",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, c), x2d.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, g.reshape(1, c), mu, rstd, dy)
    return dx, dgp[0], dbp[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layernorm2d(x2d, g, b, eps, interpret):
    y, _, _ = _ln_fwd(x2d, g, b, eps, _pick_rows(*x2d.shape), interpret)
    return y


def _layernorm2d_fwd(x2d, g, b, eps, interpret):
    y, mu, rstd = _ln_fwd(x2d, g, b, eps, _pick_rows(*x2d.shape), interpret)
    return y, (x2d, g, mu, rstd)


def _layernorm2d_bwd(eps, interpret, res, dy):
    x2d, g, mu, rstd = res
    dx, dg, db = _ln_bwd(x2d, g, mu, rstd, dy, _pick_rows(*x2d.shape),
                         interpret)
    return dx, dg.astype(g.dtype), db.astype(g.dtype)


_layernorm2d.defvjp(_layernorm2d_fwd, _layernorm2d_bwd)


def _engine_cases(engine):
    """Tiny test engines sit below the 128-lane channel minimum (the
    kernel is gated off there), so fall back to the smallest supported
    multi-block envelope — the sweep must always exercise the fwd AND
    bwd kernels, including the dg/db cross-grid accumulation K004
    deliberately admits."""
    rows, c = engine.token_budget, engine.hidden
    if not supports(rows, c):
        rows, c = 512, 128
    sds = jax.ShapeDtypeStruct
    x = sds((rows, c), jnp.float32)
    w = sds((c,), jnp.float32)

    def fwd(x, g, b):
        return layernorm_pallas(x, g, b)

    def vjp(x, g, b):
        def loss(*a):
            return jnp.sum(layernorm_pallas(*a).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(x, g, b)

    yield registry.KernelCase(f"fwd[{rows}x{c}]", fwd, (x, w, w), None)
    yield registry.KernelCase(f"vjp[{rows}x{c}]", vjp, (x, w, w), None)


@registry.register_kernel(
    "layernorm",
    fallback="paddle_tpu.nn.functional:layer_norm",
    parity="tests/test_pallas_kernels.py::test_layernorm_forward_and_grads",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def layernorm_pallas(x, gamma, beta, eps=1e-5, interpret=False):
    """LayerNorm over the last dim; x any rank, gamma/beta shape [C]."""
    c = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    y = _layernorm2d(x.reshape(rows, c), gamma, beta, float(eps), interpret)
    return y.reshape(x.shape)
