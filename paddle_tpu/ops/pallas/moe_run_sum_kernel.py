"""The expert layer's rows summed BY RUN as one Pallas TPU kernel,
``moe_run_sum``: the pass over ``[R, H]`` that takes a bucket's rows, already
in token order, back towards their tokens
(``incubate/distributed/models/moe/dropless.py _sum_by_runs``; the XLA
composition is ``dropless._run_sums``).

The op: rows ``[R, H]`` in token order, so the rows of one token are a RUN of
neighbours, at most ``max_run`` long (a token's experts are distinct);
``rem [R]`` int32, the rows of its own run that lie BEHIND row ``j``; a
float32 weight a row, or none.  The result holds at the FIRST row of every
run the float32 sum of the run's rows, each times its weight, rounded once;
what the other rows hold nobody reads.  Doubling, as the composition: after
the pass at ``step`` row ``j`` holds the rows ``j .. j + 2 step - 1`` of its
run,

    acc[j] += acc[j + step]   where rem[j] >= step

so a row takes in only rows of its own run, and what lies behind the last run
(rows of no token, NaN included) reaches no sum: a select, not a product.

XLA runs each pass as a shifted float32 copy of ``[R, H]`` through HBM, at a
quarter of the HBM bound (``PERF.md`` section 6, PR 31 and 45).  Here a block
of rows crosses once each way in the storage dtype, IN PLACE: grid ``(R /
rows,)`` from the last block up, a block's rows at their whole width, and
the ``HALO`` rows behind it (the head of the block done just before, as it
came) kept in a VMEM scratch from that step (the last block has nothing
behind it and, by ``rem``, reads nothing of what the scratch then holds); 256
lanes at a time the two side by side as one float32 value, the passes on it by
sublane rotations (what a rotation wraps round lands in the halo's last rows,
which no row of the block reads: ``max_run <= HALO``), and the block's own
rows written back.  ``rem`` and the weights cross lane-dense, a row a
quantity, and are turned into columns once a block (:func:`_columns`).

Constraints (else the dispatcher ``ops.pallas.moe_run_sum`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, pick_block

# rows behind a block that its runs may reach into: a whole tile of a packed
# dtype's sublanes, and the longest run the kernel serves
HALO = 16


def _pick_lanes(width):
    """The lanes a step of the kernel's inner loop works on."""
    return next((b for b in (256, _LANES) if width % b == 0), None)


# what a grid step's blocks may hold, in and out (Pallas keeps two of each):
# half of the 16 MiB of VMEM a kernel has without asking.  The kernel asks for
# no more: with 64 MiB set aside for it the compiler no longer kept the
# operands of the step's row gathers in VMEM beside it, and kanana's backward
# gathers ran at 33 ns a row for 6.4 (``PERF.md`` section 6, PR 45)
_STEP_BYTES = 8 * 1024 * 1024


def _pick_rows(rows, width, dtype):
    fit = _STEP_BYTES // (4 * width * jnp.dtype(dtype).itemsize)
    block = pick_block(rows, max(b for b in (512, 256, 128, 64, 32, HALO)
                                 if b <= max(fit, HALO)))
    return block if block and block >= HALO else None


def supports(rows, width, max_run, dtype):
    """Rows of whole 128-lane tiles, a row count that blocks of 16 rows at
    least divide, runs no longer than the halo; float32 or bfloat16."""
    return (_pick_lanes(width) is not None
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and _pick_rows(rows, width, dtype) is not None
            and 1 <= max_run <= HALO)


def _columns(side_ref, rows):
    """``side_ref [1, n, lanes]``, a quantity a row -> each quantity's first
    ``rows`` lanes as a column ``[rows, 1]``.  A value a ROW is what the
    passes want and a value a LANE is what HBM holds densely (a ``[R, 1]``
    array is padded to 128 lanes there: 16 MB a quantity at sdar's bucket,
    67 MB in its worst case): up to 128 lanes at a time go through the
    diagonal of a square tile and a lane reduction."""
    out = []
    for q in range(side_ref.shape[1]):
        cols = []
        for at in range(0, rows, _LANES):
            n = min(_LANES, rows - at)
            eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
                == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            tile = jnp.where(eye, jnp.broadcast_to(
                side_ref[0, q:q + 1, at:at + n], (n, n)), 0.0)
            cols.append(jnp.sum(tile, axis=1, keepdims=True))
        out.append(jnp.concatenate(cols) if len(cols) > 1 else cols[0])
    return out


def _kernel(x_ref, side_ref, o_ref, halo_rows, halo_side, *, passes, lanes):
    rows, n = x_ref.shape[0], x_ref.shape[0] + HALO
    own = _columns(side_ref, rows)
    rem, *weights = [jnp.concatenate([column, halo_side[q]])
                     for q, column in enumerate(own)]

    def chunk(c, carry):
        at = pl.ds(pl.multiple_of(c * lanes, lanes), lanes)
        head = x_ref[:HALO, at]
        acc = jnp.concatenate([x_ref[:, at], halo_rows[:, at]]).astype(
            jnp.float32)
        if weights:
            acc = acc * weights[0]
        for step in (1 << p for p in range(passes)):
            # behind[j] = acc[j + step]
            behind = pltpu.roll(acc, n - step, 0)
            acc = acc + jnp.where(rem >= step, behind, 0.0)
        o_ref[:, at] = acc[:rows].astype(o_ref.dtype)
        halo_rows[:, at] = head
        return carry

    # a loop, not its unrolling: a layer's step holds six of these kernels
    # (two branches, forward, recomputed and backward), and a block's whole
    # width unrolled was 1.7 MB of program each
    jax.lax.fori_loop(0, x_ref.shape[1] // lanes, chunk, 0)
    for q, column in enumerate(own):
        halo_side[q] = column[:HALO]


# jit(inline=True): a layer's call is traced once a step, not once a block
# (``ssd_scan_kernel._launch``)
@functools.partial(jax.jit, inline=True,
                   static_argnames=("passes", "block", "interpret"))
def _run_sum(rows, rem, weights, passes, block, interpret):
    n, width = rows.shape
    br, lanes = block
    last = n // br - 1
    # a row block's quantities lane-dense: [blocks, 1 or 2, block rows]
    # (float32: the run lengths are small integers)
    side = jnp.stack([rem.astype(jnp.float32)]
                     + ([] if weights is None else [weights]))
    side = side.reshape(side.shape[0], n // br, br).transpose(1, 0, 2)
    return pl.pallas_call(
        functools.partial(_kernel, passes=passes, lanes=lanes),
        name="moe_run_sum",
        # from the last block up: what a block's runs reach into is the
        # head of the block done just before
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, width), lambda i: (last - i, 0)),
            pl.BlockSpec((1,) + side.shape[1:], lambda i: (last - i, 0, 0))],
        out_specs=pl.BlockSpec((br, width), lambda i: (last - i, 0)),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        scratch_shapes=[pltpu.VMEM((HALO, width), rows.dtype),
                        pltpu.VMEM((side.shape[1], HALO, 1), jnp.float32)],
        # in place: a block is read whole before it is written, and nothing
        # else reads it (its head waits in VMEM for the block before it)
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(rows, side)


def _engine_cases(engine):
    """The serving engine launches none of this (the expert models train).
    The lint sweeps one training-shaped case, with and without weights, in
    the engine's dtype."""
    sds = jax.ShapeDtypeStruct
    rows, width = 256, 256
    for weighted in (True, False):
        args = (sds((rows, width), engine.dtype), sds((rows,), jnp.int32)) \
            + ((sds((rows,), jnp.float32),) if weighted else ())

        def fn(x, rem, w=None):
            return moe_run_sum_pallas(x, rem, w, max_run=8)

        yield registry.KernelCase(
            f"run_sum[r{rows},h{width},{'weighted' if weighted else 'plain'}]",
            fn, args, None)


@registry.register_kernel(
    "moe_run_sum",
    fallback="paddle_tpu.incubate.distributed.models.moe.dropless:_run_sums",
    parity="tests/test_moe_run_sum.py::test_the_kernel_matches_the_"
           "composition_bit_for_bit",
    engine_shapes=_engine_cases,
    supports=supports)
def moe_run_sum_pallas(rows, rem, weights=None, *, max_run, interpret=False,
                       block=None):
    """``rows [R, H]`` in token order, ``rem [R]`` int32 (the rows of its run
    behind each row), ``weights [R]`` float32 or ``None`` -> ``[R, H]``: at
    the first row of every run its rows' float32 sum, times the weights
    where given, rounded once.  ``block`` ``(rows, lanes)`` is the tests'
    and the tuning's."""
    n, width = rows.shape
    block = block or (_pick_rows(n, width, rows.dtype), _pick_lanes(width))
    if None in block or n % block[0] or width % block[1] \
            or block[0] % HALO or not 1 <= max_run <= HALO:
        raise ValueError(
            f"moe run sum does not serve rows{tuple(rows.shape)} {rows.dtype} "
            f"max_run={max_run} block={block}: see "
            f"moe_run_sum_kernel.supports")
    # doubling passes that cover a run of ``max_run`` rows
    return _run_sum(rows, rem,
                    None if weights is None else weights.astype(jnp.float32),
                    (max_run - 1).bit_length(), tuple(block), bool(interpret))
