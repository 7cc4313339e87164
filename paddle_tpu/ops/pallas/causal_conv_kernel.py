"""The Mamba mixer's causal depthwise convolution with its bias and its
``silu`` (``nn/functional.py causal_conv1d(..., activation="silu")``) as
Pallas TPU kernels: ONE forward, ``causal_conv_fwd``, and ONE backward,
``causal_conv_bwd``, under one ``jax.custom_vjp``.

The op, on ``x [B, T, C]`` with ``K`` taps ``w [C, K]`` and a bias ``b [C]``:

    pre[t] = sum_k w[:, k] x[t - (K - 1) + k] + b      x before the row: 0
    y[t]   = pre[t] sigmoid(pre[t])

the sum and the ``silu`` in float32, rounded ONCE, to x's dtype.  That is
what the composition's fusion does on the TPU, where XLA keeps ``silu``'s
operand in the float32 it was summed in and divides a bfloat16 sigmoid by the
approximate reciprocal (:func:`_gate`; ``PERF.md`` section 6, PR 46: at
``[4096, 10240]`` bfloat16 the kernels' values are the composition's bit for
bit); off the TPU the composition rounds the sum and every step of the
sigmoid, and the kernels, in interpret mode, differ from it by those
roundings.  The backward likewise: the pre-activation's gradient stays float32
until x's gradient is rounded.

``x`` may be WIDER than the convolution: ``start`` lanes into it (whole
tiles) the block index maps read the ``C`` channels where they lie, so that
the Mamba mixer's x, B and C are each convolved out of the projection's
``[T, 18560]`` result with no slice before the call and none after it; x's
gradient comes back at x's width, zero outside the channels.

XLA runs it as a padded float32 copy of the row, ``K`` products sliced at
sublane offsets, and in the backward ``K + 1`` reductions over ``T`` and a
sum of ``K`` pads: 5.9 ms a layer at ``[4096, 10240]`` for a need of 0.8
(``PERF.md`` section 6, PR 46).  Here a block of rows ``[rows, lanes]``
crosses once each way in x's dtype and everything else lives in VMEM and in
registers.  Both kernels work through a block ``[rc, 128]`` lanes at a
time: the chunk and the ``HALO`` rows before it as one float32 value, the
taps' shifts as sublane rotations of it (what a rotation wraps round lands
in the halo's rows, which nothing reads: ``K - 1 <= HALO``).  The rows
before a block come through a second, ``HALO``-row view of the same
operand (zero for a row's first block: nothing leaks from one batch row
into the next).

- the forward's grid ``(batch, lane block, row block)`` has no order;
- the backward forms the pre-activation AGAIN from x, w and b (nothing
  ``[T, C]`` is saved but x) and walks the row blocks, and a block's
  chunks, from the LAST to the first: x's gradient at ``t`` needs the
  pre-activation's at ``t + 1 .. t + K - 1``, so the first ``HALO`` rows of
  the chunk done just before are carried (in registers inside a block, in a
  VMEM scratch from block to block).  The taps' and the bias's gradients
  accumulate in float32, eight partial rows a quantity in registers down a
  block, and across row blocks and batch rows in ONE output block ``[8,
  lanes]`` revisited down the two sequential axes (its grid is ``(lane
  block, batch, row block)``).  No reduction and no pad is left to XLA.

The taps and the bias cross as ONE float32 operand ``[SIDE, C]`` (a tap a
row, then the bias), and their gradients come back the same way.

Constraints (else the dispatcher ``ops.pallas.causal_conv1d`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, _block_rows, pick_block

# rows before a chunk that its taps may reach into (and, in the backward,
# rows behind it): a whole tile of a packed dtype's sublanes
HALO = 16
# rows of the float32 operand that carries the taps and the bias: one tile
SIDE = 8


def _pick_block(seq, channels, dtype, start=0):
    """``(block rows, block lanes, chunk rows, chunk lanes)``: a block of
    whole halos whose lanes divide the channels and the lanes before them,
    1 MiB of x at most (the backward holds three such blocks, twice each),
    worked through 128 rows by 128 lanes at a time; or None."""
    bt = pick_block(seq, 512)
    if bt is None or bt % HALO or channels % _LANES or start % _LANES:
        return None
    fit = (1 << 20) // (bt * jnp.dtype(dtype).itemsize)
    bc = next(b for b in (1024, 512, 256, _LANES)
              if b <= max(fit, _LANES) and channels % b == start % b == 0)
    return bt, bc, pick_block(bt, 128), _LANES


def supports(seq, channels, taps, dtype, start=0):
    """Channels whole 128-lane tiles, ``start`` whole tiles into their
    operand, rows whole tiles of 16 sublanes; the taps fit, with the bias,
    the one tile they cross in (at
    most 7 taps: ``SIDE - 1``, well inside the halo); float32 or bfloat16."""
    return (_pick_block(seq, channels, dtype, start) is not None
            and 1 <= taps < SIDE
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


# ----------------------------------------------------------- kernel bodies --

def _chunk_with_halo(x_ref, first, r0, rc, at):
    """Rows ``r0 - HALO .. r0 + rc`` of the block as float32; the block's
    first chunk takes ``first`` (the view of the rows before the block)."""
    before = pl.multiple_of(jnp.maximum(r0 - HALO, 0), HALO)
    head = jnp.where(r0 == 0, first, x_ref[0, pl.ds(before, HALO), at])
    return jnp.concatenate(
        [head, x_ref[0, pl.ds(r0, rc), at]]).astype(jnp.float32)


def _shifted(full, taps):
    """``full [HALO + rc, L]`` -> tap ``k``'s operand ``x[t - (K - 1) + k]``
    on the chunk's rows, ``k = 0 .. K - 1``."""
    return [(pltpu.roll(full, taps - 1 - k, 0) if k < taps - 1 else full)
            [HALO:] for k in range(taps)]


def _gate(xs, w, taps, dtype):
    """``(p, s)``: the pre-activation, the taps' float32 sum and the bias
    in the composition's order, and its sigmoid, both float32.  The sigmoid
    of a ``dtype`` value as XLA computes it on the TPU: a bfloat16 one
    divides by the EUP's approximate reciprocal (the fused composition's
    values at ``[4096, 10240]`` on the chip are these, bit for bit: ``PERF.md``
    section 6, PR 46), a float32 one divides exactly."""
    p = xs[0] * w[0:1]
    for k in range(1, taps):
        p = p + xs[k] * w[k:k + 1]
    p = p + w[taps:taps + 1]
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        return p, pl.reciprocal(1.0 + jnp.exp(-p), approx=True)
    return p, jax.nn.sigmoid(p)


def _first_rows(halo_ref, at, is_first):
    """The ``HALO`` rows before the block; zero before a row's first."""
    return jnp.where(is_first, jnp.zeros((), halo_ref.dtype),
                     halo_ref[0, :, at])


def _fwd_kernel(x_ref, halo_ref, w_ref, o_ref, *, taps, rc, lanes):
    bt, bc = x_ref.shape[1:]
    is_first = pl.program_id(2) == 0

    def lane_chunk(c, carry):
        at = _block_rows(c, lanes)
        w = w_ref[:, at]
        first = _first_rows(halo_ref, at, is_first)

        def row_chunk(q, carry):
            r0 = pl.multiple_of(q * rc, rc)
            p, s = _gate(
                _shifted(_chunk_with_halo(x_ref, first, r0, rc, at), taps),
                w, taps, o_ref.dtype)
            o_ref[0, pl.ds(r0, rc), at] = (p * s).astype(o_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, bt // rc, row_chunk, carry)

    jax.lax.fori_loop(0, bc // lanes, lane_chunk, 0)


def _fold(v):
    """``[rc, L]`` -> the eight partial sums ``[8, L]`` of its rows (adds
    of whole tiles; the last eight-to-one sum is taken once a block)."""
    out = v[:SIDE]
    for r in range(SIDE, v.shape[0], SIDE):
        out = out + v[r:r + SIDE]
    return out


def _bwd_kernel(x_ref, halo_ref, dy_ref, w_ref, dx_ref, dwb_ref, behind_ref,
                *, taps, rc, lanes):
    bt, bc = x_ref.shape[1:]
    f32 = jnp.float32
    batch_row, step = pl.program_id(1), pl.program_id(2)
    # the row blocks come from the last to the first
    is_first = step == pl.num_programs(2) - 1
    chunks = bt // rc

    @pl.when((batch_row == 0) & (step == 0))
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    @pl.when(step == 0)
    def _():
        behind_ref[...] = jnp.zeros_like(behind_ref)

    def lane_chunk(c, carry):
        at = _block_rows(c, lanes)
        w = w_ref[:, at]
        first = _first_rows(halo_ref, at, is_first)

        def row_chunk(q, carry):
            behind, sums = carry
            r0 = pl.multiple_of((chunks - 1 - q) * rc, rc)
            xs = _shifted(_chunk_with_halo(x_ref, first, r0, rc, at), taps)
            p, s = _gate(xs, w, taps, dx_ref.dtype)
            # d silu(p) = s (1 + p (1 - s))
            dpre = dy_ref[0, pl.ds(r0, rc), at].astype(f32) \
                * (s * (1.0 + p * (1.0 - s)))
            full = jnp.concatenate([dpre, behind])
            n = rc + HALO
            # dx[t] = sum_k w[k] dpre[t + (K - 1) - k]
            dx = dpre * w[taps - 1:taps]
            for k in range(taps - 1):
                dx = dx + pltpu.roll(full, n - (taps - 1 - k), 0)[:rc] \
                    * w[k:k + 1]
            dx_ref[0, pl.ds(r0, rc), at] = dx.astype(dx_ref.dtype)
            sums = tuple(a + _fold(v) for a, v in zip(
                sums, [dpre * v for v in xs] + [dpre]))
            return dpre[:HALO], sums

        zero = jnp.zeros((SIDE, lanes), f32)
        behind, sums = jax.lax.fori_loop(
            0, chunks, row_chunk, (behind_ref[:, at], (zero,) * (taps + 1)))
        behind_ref[:, at] = behind
        for k, partial in enumerate(sums):
            dwb_ref[k:k + 1, at] += jnp.sum(partial, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, bc // lanes, lane_chunk, 0)


# ------------------------------------------------------------ pallas calls --

def _halo_index(bt):
    """The row-block index, in ``HALO``-row blocks, of the rows before row
    block ``i`` (block 0 reads its own first rows and zeroes them)."""
    return lambda i: jnp.maximum(i * (bt // HALO) - 1, 0)


# jit(inline=True): a layer's call is traced once a step, not once a block
# (``ssd_scan_kernel._launch``)
@functools.partial(jax.jit, inline=True,
                   static_argnames=("taps", "start", "block", "interpret"))
def _conv_fwd(x, wb, taps, start, block, interpret):
    batch, seq, _ = x.shape
    channels = wb.shape[1]
    bt, bc, rc, lanes = block
    before, at = _halo_index(bt), start // bc
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, rc=rc, lanes=lanes),
        name="causal_conv_fwd",
        grid=(batch, channels // bc, seq // bt),
        # x where it lies, ``start`` lanes into a wider operand
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda b, j, i: (b, i, at + j)),
            pl.BlockSpec((1, HALO, bc),
                         lambda b, j, i: (b, before(i), at + j)),
            pl.BlockSpec((SIDE, bc), lambda b, j, i: (0, j))],
        out_specs=pl.BlockSpec((1, bt, bc), lambda b, j, i: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, seq, channels), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
    )(x, x, wb)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("taps", "start", "block", "interpret"))
def _conv_bwd(x, wb, dy, taps, start, block, interpret):
    batch, seq, channels = dy.shape
    bt, bc, rc, lanes = block
    last = seq // bt - 1
    before, at = _halo_index(bt), start // bc
    rows = pl.BlockSpec((1, bt, bc), lambda j, b, i: (b, last - i, j))
    side = pl.BlockSpec((SIDE, bc), lambda j, b, i: (0, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, rc=rc, lanes=lanes),
        name="causal_conv_bwd",
        # the lane blocks outermost: a lane block's sums are ONE output
        # block, revisited down its batch rows and row blocks
        grid=(channels // bc, batch, seq // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bc),
                         lambda j, b, i: (b, last - i, at + j)),
            pl.BlockSpec((1, HALO, bc),
                         lambda j, b, i: (b, before(last - i), at + j)),
            rows, side],
        out_specs=[rows, side],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, dy.dtype),
                   jax.ShapeDtypeStruct(wb.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HALO, bc), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(x, x, dy, wb)


# ------------------------------------------------------------- public API --

def _side(weight, bias):
    """The taps and the bias as the kernels read them: float32 ``[SIDE,
    C]``, tap ``k`` in row ``k``, the bias (or zeros) in row ``K``."""
    channels, taps = weight.shape
    rows = [weight.astype(jnp.float32).T,
            (jnp.zeros((channels,), jnp.float32) if bias is None
             else bias.astype(jnp.float32))[None]]
    return jnp.concatenate(
        rows + [jnp.zeros((SIDE - taps - 1, channels), jnp.float32)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernels(x, weight, bias, start, block, interpret):
    return _fwd_rule(x, weight, bias, start, block, interpret)[0]


def _fwd_rule(x, weight, bias, start, block, interpret):
    return _conv_fwd(x, _side(weight, bias), weight.shape[1], start, block,
                     interpret), (x, weight, bias)


def _bwd_rule(start, block, interpret, res, dy):
    x, weight, bias = res
    channels, taps = weight.shape
    dx, dwb = _conv_bwd(x, _side(weight, bias), dy, taps, start, block,
                        interpret)
    # the lanes of a wider operand that the convolution did not read took no
    # part: the pad a slice's transpose would make (XLA adds it to the other
    # readers' gradients in one pass)
    dx = jnp.pad(dx, ((0, 0), (0, 0),
                      (start, x.shape[2] - start - channels)))
    return (dx, dwb[:taps].T.astype(weight.dtype),
            None if bias is None else dwb[taps].astype(bias.dtype))


_conv_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/nemotron_h.py``
    trains and has no decode path); the lint sweeps one training-shaped
    case, value and backward, at the published 4 taps in the engine's
    dtype."""
    sds = jax.ShapeDtypeStruct
    x = sds((1, 512, 512), engine.dtype)
    w, b = sds((512, 4), engine.dtype), sds((512,), engine.dtype)

    def vjp(x, w, b):
        def loss(*o):
            return jnp.sum(causal_conv_pallas(*o).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

    yield registry.KernelCase("value[s512,c512,k4]", causal_conv_pallas,
                              (x, w, b), None)
    yield registry.KernelCase("vjp[s512,c512,k4]", vjp, (x, w, b), None)


@registry.register_kernel(
    "causal_conv",
    fallback="paddle_tpu.nn.functional:_causal_conv1d_silu",
    parity="tests/test_causal_conv_kernel.py::test_kernels_match_the_"
           "composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def causal_conv_pallas(x, weight, bias=None, *, start=0, interpret=False,
                       block=None):
    """``x [batch, T, W]``, ``weight [C, K]``, ``bias [C]`` or ``None`` ->
    ``silu(causal_conv1d(x[..., start:start + C], weight, bias))`` ``[batch,
    T, C]`` in x's dtype, the channels read where they lie in x (no slice
    is made); differentiable in all three.  ``block`` ``(block rows, block
    lanes, chunk rows, chunk lanes)`` is the tests' and the tuning's."""
    batch, seq, width = x.shape
    channels, taps = weight.shape
    if not supports(seq, channels, taps, x.dtype, start) \
            or not 0 <= start <= width - channels:
        raise ValueError(
            f"causal conv does not serve x{tuple(x.shape)} {x.dtype} "
            f"weight{tuple(weight.shape)} start={start}: see "
            f"causal_conv_kernel.supports")
    bt, bc, rc, lanes = block = block or _pick_block(seq, channels, x.dtype,
                                                     start)
    if seq % bt or channels % bc or start % bc or bt % rc or bc % lanes \
            or rc % HALO or lanes % _LANES:
        raise ValueError(f"causal conv: block {block} does not tile "
                         f"x{tuple(x.shape)} start={start}")
    return _conv_kernels(x, weight, bias, start, tuple(block),
                         bool(interpret))
