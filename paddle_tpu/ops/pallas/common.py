"""What two or more files of this package share, and nothing else: the
block-size rule, the few lines of kernel body that several families run,
and the ONE log of the calls traced so far.

The package's arrows point one way: a kernel file imports ``jax``, this
file and ``registry``; the dispatcher (``__init__``) imports the kernel
files; nothing here or in a kernel file imports the dispatcher or another
kernel file (``tests/test_pallas_dispatch.py`` holds the files to it).
"""

import collections
import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANES = 128


def pick_block(size, preferred):
    """Largest of ``preferred``, 512, 256, ..., 8 that is <= ``preferred``
    and divides ``size`` (the kernels' shared block-size rule), or None."""
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and size % b == 0:
            return b
    return None


# --------------------------------------------------- lines of kernel body --

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dot_for(dtype):
    """The kernels' matmul on operands of ``dtype``, float32 accumulation.
    float32 operands ask for float32 products (``HIGHEST``): the MXU's
    default rounds them to bfloat16 inside, and the backward's pairs of
    sums (``ssd_scan_kernel._bwd_kernel``) cancel only where both sides see
    ONE rounding of the decay-weighted matrix."""
    precision = jax.lax.Precision.HIGHEST \
        if jnp.dtype(dtype) == jnp.dtype(jnp.float32) else None
    return functools.partial(jax.lax.dot_general, precision=precision,
                             preferred_element_type=jnp.float32)


def _block_rows(i, block):
    """The slice of rows [i*block, (i+1)*block).  Mosaic has to prove the
    alignment of a dynamic row slice of a packed dtype."""
    # imported where a kernel is traced: the dispatcher imports this file,
    # and ``import paddle_tpu`` does not pay for Pallas (0.7 s)
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(i * block, block), block)


def _rows(ref, i, block):
    """Rows [i*block, (i+1)*block) of a [1, seq, head] ref."""
    return ref[0, _block_rows(i, block), :]


def _mask_below_diagonal(s, row0, col0, row_axis, window=None):
    """Keep s where (row0 + row) >= (col0 + col), and with a ``window``
    where row - col < window besides; ``row_axis`` is the axis of ``s``
    that runs over queries.  A row wholly masked in a block it visits
    before its first visible key leaves m at -1e30 and p at 1; the first
    block with a visible key (every row sees itself) rescales that to
    nothing (alpha = exp(-1e30 - m) = 0)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, row_axis)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - row_axis)
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, _NEG_INF)


# ------------------------------------------------- the log of traced calls --

# The newest traced calls, a record each: ``kernel``, ``shapes``, ``path``
# (``kernel`` or ``composition``), ``reason`` (why the composition; None for
# the kernels) and what its writer adds: the dispatcher writes one for every
# call it places (a scan's with its ``chunk``), the flash kernels' entry
# point its own (below).  And the running sums, of which a compiled step
# takes what was traced while it compiled (``profiler.StepTrace.dispatch``).
traced_calls = collections.deque(maxlen=1024)
traced_sums = dict.fromkeys(
    ("flash_calls", "flash_operands_in_place", "flash_operands_copied",
     "ssd_calls", "ssd_calls_composed",
     "mla_expand_calls", "mla_expand_calls_composed",
     "moe_run_sum_calls", "moe_run_sum_calls_composed",
     "causal_conv_calls", "causal_conv_calls_composed",
     "gated_norm_calls", "gated_norm_calls_composed",
     "cca_mix_calls", "cca_mix_calls_composed"), 0)


def record_flash_layout(kernel, shapes, in_place, copied):
    """``flash_attention_pallas`` says of one traced call which of its
    eight operands (q, k, v, o and the backward's do, dq, dk, dv) the
    kernels read or write where XLA holds them, and which cross as copies
    ``[batch * heads, seq, width]`` and why (``{name: reason}``).  It alone
    knows them, and tests and the sharded launch call it directly."""
    traced_calls.append({"kernel": kernel, "shapes": shapes,
                         "path": "kernel", "reason": None,
                         "in_place": tuple(in_place), "copied": dict(copied)})
    traced_sums["flash_calls"] += 1
    traced_sums["flash_operands_in_place"] += len(in_place)
    traced_sums["flash_operands_copied"] += len(copied)
