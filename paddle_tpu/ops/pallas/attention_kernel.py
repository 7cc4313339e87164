"""Flash attention as a Pallas TPU kernel (forward + backward).

The reference ships FlashAttention as a dyn-loaded CUDA library
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, loader
paddle/phi/backends/dynload/flashattn.h).  Here the kernel is written
TPU-native in Pallas: online-softmax over key blocks (never materializes the
[T, T] score matrix), MXU operands in the dtype q, k, v are stored in with
float32 accumulation and a float32 softmax between the dots, and a
recompute-based backward that is ONE kernel (S, the mask, P and dP are
formed once a block pair and dQ, dK and dV all take their part from them),
wired up as a jax.custom_vjp.

Layouts: paddle's flash-attn API is [batch, seq, num_heads, head_dim]
(python/paddle/nn/functional/flash_attention.py:125), and a projection
leaves q, k or v in HBM as [batch, seq, heads * width].  The kernels have
TWO HBM layouts for an operand, chosen per operand by what the call shows
(``_in_place``; one kernel, two index maps, ``_head_spec``):

- in place, [batch, seq, heads * width] (a reshape that moves nothing; a
  block's index map picks the head on the lane axis; O, dQ, dK and dV come
  out the same way, so O goes on to the next projection as it lies): where
  the width is a whole number of 128 lanes AND the kv heads are grouped
  (laguna: every operand);
- the copy [batch * heads, seq, width], as before PR 35: any other width
  (64; latent attention's 192-wide q and k, whose heads no block rule can
  cut at lane 192 n) and every call with one kv head a q head (GPT,
  kanana), where in place measured slower (``_in_place`` says why).  XLA
  folds this layout's transposes into the producers where it can.

The kernels' blocks are the shapes they were in either layout, so the
bodies know nothing of it.  The row statistics (log-sum-exp, and the
backward's delta) cross lane-dense in every call, as rows [batch * heads,
seq / block_q, block_q] (a [seq, 1] column is lane-padded 128x in HBM as
in VMEM).  What XLA makes of either layout round the calls (it gives no
4-D [batch, seq, heads, width] intermediate the in-place layout's tiles):
PERF.md section 6, PR 35.

Head widths: q and k share one width, v and the output another.  Served
are one width <= 128 for all three (GPT, Llama), and latent attention's
expanded training form (MLA): q and k 192 wide (128 + a 64-wide rotary
part), v 128 wide.  The kernels see only the shapes; the second form is the
same two kernels with a wider score dot and a larger VMEM allowance.  The
192-wide score is ONE dot: split into a 128-wide and a 64-wide dot it ran
0.4% (forward) and 0.7% (backward) slower on the v5e ([64, 8192, 192 | 128]
bf16 causal, PR 26: 15.03 / 43.56 ms against 15.10 / 43.86; the MXU takes
two passes over the contraction either way).

Grouped KV heads are read in place: q may have ``group`` times the heads
of k and v, and q head ``h`` reads kv head ``h // group`` through the block
index (forward: K and V cross HBM once a kv head, since consecutive q
heads ask for the block that is already there; backward: the grid runs
``(kv head, q head of its group, k block)``, a q head's Q, dO and float32 dQ
stay in VMEM while its k blocks run, and the group's dK and dV parts are
summed in float32 VMEM scratch, so no caller expands K and V and no [q
heads, seq, head] dK or dV is ever written).

A sliding ``window`` (causal only): query ``t`` sees keys ``j`` with ``0 <=
t - j < window``.  A q block visits only the key blocks its rows can see
(``_key_blocks``; the backward's k block likewise only the q blocks that
see it, ``_query_blocks``), so the work follows the window and not the
sequence; the mask is applied in every visited block, as the causal one is.

A ``block_diffusion`` mask of block ``B`` (neither causal nor a window):
the row is ``[noised ; clean]``, two copies of ``L = seq / 2`` positions,
and with ``blk(i) = (i mod L) // B`` a noised query sees the noised keys of
its OWN block and the clean keys of EARLIER blocks, a clean query the clean
keys of its own block and of earlier ones, nobody a noised key of another
block (:func:`_mask_block_diffusion`).  A q block visits TWO ranges of key
blocks (noised rows: the noised blocks on their own diagonal, then the
clean blocks up to it; clean rows: one range), a k block two ranges of q
blocks (``_blockdiff_key_blocks``, ``_blockdiff_query_blocks``); the
staircase is applied in every visited block.

Names: ``flash_attention_fwd`` and ``flash_attention_bwd_dq_dkv``
(``flash_window<W>_attention_*`` under a window and
``flash_blockdiff<B>_attention_*`` under a block-diffusion mask, so that a
trace tells the three apart).  The benchmark counts a backward call as an
event whose name holds ``_bwd_dq``, a forward call as one that holds
``_fwd``, and charges the kernels the time of every event whose name holds
``flash_attention`` / ``flash_window`` / ``flash_blockdiff``: a call this
file adds keeps that true.

Without a window and with equal head counts the forward kernel is the
program it was: Mosaic is handed the same module, source locations aside
(``tests/test_tpu_lowering.py``).

Constraints (else the caller falls back to the XLA composition): seq divisible
by the block size, head widths and counts as :func:`supports` lists them.
Attention dropout and additive masks use the fallback path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import (_NEG_INF, _NN, _NT, _TN, _block_rows, _dot,
                     _mask_below_diagonal, _rows, pick_block,
                     record_flash_layout)


# q/k and v head widths that differ: the pairs Mosaic was shown to take
# (AOT for the v5e and on the chip, PR 26) -- MLA's 128 + 64 rotary over 128
_SPLIT_HEADS = {(192, 128)}


def supports(seq_q, seq_k, head_dim, v_head_dim=None, q_heads=None,
             kv_heads=None, window=None, causal=True, block_diffusion=None):
    """``head_dim``: q's and k's width; ``v_head_dim``: v's (else the same).
    ``q_heads`` over ``kv_heads``: any whole multiple.  ``window`` (SLIDING;
    a BLOCK window with summaries of the windows before it is asked of
    ``eva_attention_kernel.supports``): >= 1, causal, q and k of one length.
    ``block_diffusion`` ``B``: neither causal nor a window, q and k of one
    even length ``2 L``, ``L`` a whole number of q blocks, and ``B`` dividing
    the blocks (so a power of two).

    How long a sequence Mosaic takes is the forward's to say, which holds K
    and V whole: 8192 in bf16 and 4096 in float32 at one width on its default
    16 MB, 16,384 in bf16 with what it then asks (K and V's 16 MB and 16 MB
    more, ``_flash_fwd``), 32,768 at 192 | 128 (64 MB asked).  The
    backward, which holds a
    q head's Q, dO and float32 dQ whole, fits further: 32,768 in bf16
    (16,384 grouped, where whole-sequence dK and dV join them) and 16,384
    in float32 (ahead of time for the v5e, 512 x 512 blocks, PR 33)."""
    if v_head_dim is None or v_head_dim == head_dim:
        heads_ok = head_dim <= 128
    else:
        heads_ok = (head_dim, v_head_dim) in _SPLIT_HEADS
    if q_heads is not None and kv_heads is not None:
        heads_ok = heads_ok and kv_heads > 0 and q_heads % kv_heads == 0
    if window is not None:
        heads_ok = heads_ok and causal and window >= 1 and seq_q == seq_k
    blocks = _blocks(seq_q, seq_k)
    if block_diffusion is not None and None not in blocks:
        heads_ok = (heads_ok and not causal and window is None
                    and block_diffusion >= 1 and seq_q == seq_k
                    and seq_q % (2 * blocks[0]) == 0
                    and not any(b % block_diffusion for b in blocks))
    return heads_ok and None not in blocks


# K and V bytes held in VMEM from which the forward asks for its allowance
# (what is held and 16 MB more) and no longer lives on Mosaic's default 16 MB
_ASK_VMEM_FROM = 12 * 1024 * 1024


def _compiler_params(head_qk, head_v):
    """The forward's: nothing for one head width (the program Mosaic built
    before PR 26 is built again, to the byte).  K and V are held whole in
    VMEM, double-buffered: at seq 8192 a 192-wide bf16 operand is
    lane-padded to 256 and takes 4 MB a buffer, which passes the 16 MB
    Mosaic allows a kernel by default on a chip that has 128.  (The backward
    reckons its allowance from its blocks' bytes, ``_flash_bwd``.)"""
    if head_qk == head_v:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=64 * 1024 * 1024)}


# ----------------------------------------------------------- inner loops --
#
# What the MXU is handed: q, k, v and dO go into every dot in the dtype they
# are stored in, and the dot accumulates in float32
# (``preferred_element_type``).  bf16 x bf16 products are exact in a float32
# accumulator, so the dots whose operands both come from HBM lose nothing.
# The probabilities and dS are cast to the input dtype only where they
# become an operand of the second dot; the scores, the mask, exp, m, l, lse,
# delta and the accumulators stay float32, and the softmax scale is applied
# to the float32 scores (never to a bf16 q).  float32 inputs keep float32
# operands.
#
# This states the rounding; it does not buy time.  At Mosaic's default
# precision the v5e's MXU takes a float32 dot in ONE bf16 pass as well
# (measured, PR 25: the same kernels with every operand upcast run within
# 1%, at 90 TFLOP/s, above what three passes could reach), so an upcast
# costs converts and hides from interpret mode the rounding the chip
# applies anyway.  What bounds these kernels is the block shape
# (``_blocks``).


def _key_blocks(qi, block_q, block_k, seq_k, causal, window=None):
    """``(first, end)``: the key blocks q block ``qi`` attends to.  Causal,
    those past its last row are fully masked and skipped; with a window,
    those before the first key its FIRST row sees as well."""
    if not causal:
        return 0, seq_k // block_k
    end = ((qi + 1) * block_q + block_k - 1) // block_k
    if window is None:
        return 0, end
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k, end


def _query_blocks(ki, block_q, block_k, num_qb, causal, window=None):
    """``(first, end)``: the q blocks that see key block ``ki``.  Causal,
    those before its diagonal contribute nothing; with a window, those
    after the last row that sees its LAST key neither."""
    if not causal:
        return 0, num_qb
    first = (ki * block_k) // block_q
    if window is None:
        return first, num_qb
    last_row = (ki + 1) * block_k - 1 + (window - 1)
    return first, jnp.minimum(last_row // block_q + 1, num_qb)


def _blockdiff_key_blocks(qi, block_q, block_k, half, block):
    """``((a0, a1), (b0, b1))``: the two ranges of key blocks q block ``qi``
    attends to under the block-diffusion mask (``half`` = ``L``, a whole
    number of q blocks; ``block`` = ``B`` divides ``block_q``).  Noised rows
    ``[r0, r1)``: the noised keys ``[r0, r1)`` (their own diffusion blocks),
    then the clean keys ``L + [0, r1 - B)`` (the blocks before the last
    row's).  Clean rows: the clean keys up to their own block's end, ``L +
    [0, r1 - L)``, and an empty second range.  The ranges never share a
    block, also where a key block straddles ``L``."""
    r0 = qi * block_q
    r1 = r0 + block_q
    noised = r0 < half
    up = lambda x: (x + block_k - 1) // block_k             # noqa: E731
    a0 = jnp.where(noised, r0 // block_k, half // block_k)
    a1 = up(r1)
    b0 = jnp.where(noised, jnp.maximum(half // block_k, a1), a1)
    b1 = jnp.where(noised & (r1 > block),
                   jnp.maximum(up(half + r1 - block), b0), b0)
    return (a0, a1), (b0, b1)


def _blockdiff_query_blocks(ki, block_q, block_k, num_qb, half, block):
    """``((n0, n1), (m0, m1))``: the q blocks that see key block ``ki``,
    the transpose of :func:`_blockdiff_key_blocks`: among the noised q
    blocks those of the block's noised keys' own diffusion blocks and, for
    its clean keys ``L + [p, ...)``, those from the diffusion block after
    ``p``'s on; among the clean q blocks those from ``p``'s diffusion block
    on."""
    c0 = ki * block_k
    c1 = c0 + block_k
    has_noised, has_clean = c0 < half, c1 > half
    half_qb = half // block_q
    own0 = c0 // block_q
    own1 = (jnp.minimum(c1, half) + block_q - 1) // block_q
    p = (jnp.maximum(c0, half) - half) // block * block
    later0 = jnp.minimum((p + block) // block_q, half_qb)
    n0 = jnp.where(has_noised,
                   jnp.where(has_clean, jnp.minimum(own0, later0), own0),
                   later0)
    n1 = jnp.where(has_clean, half_qb, own1)
    m0 = jnp.where(has_clean, (half + p) // block_q, num_qb)
    return (n0, n1), (m0, num_qb)


def _over_two_ranges(ranges, body, init):
    """``fori_loop`` of ``body(block index, carry)`` over the first range,
    then the second."""
    (a0, a1), (b0, b1) = ranges
    first = a1 - a0

    def step(t, carry):
        return body(jnp.where(t < first, a0 + t, b0 + (t - first)), carry)

    return jax.lax.fori_loop(0, first + (b1 - b0), step, init)


def _mask_block_diffusion(s, row0, col0, row_axis, half, block):
    """Keep ``s`` where query ``i = row0 + row`` sees key ``j = col0 + col``
    under the block-diffusion mask: a CLEAN key (``j >= half``) of block
    ``blk(j)`` by the rows with ``blk(j) < blk(i) + [i is clean]``, a NOISED
    key by the noised rows with ``blk(i) == blk(j)``.  ``block`` is a power
    of two; the row and the column terms are formed on a column and a row
    vector and meet in two compares of the whole tile."""
    shift = block.bit_length() - 1

    def half_and_block(start, axis):
        shape = (s.shape[0], 1) if axis == 0 else (1, s.shape[1])
        pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        noised = pos < half
        return noised, jnp.where(noised, pos, pos - half) >> shift

    r_noised, rb = half_and_block(row0, row_axis)
    c_noised, cb = half_and_block(col0, 1 - row_axis)
    keep = (jnp.where(c_noised, jnp.int32(2 ** 30), cb)
            < rb + jnp.where(r_noised, 0, 1)) \
        | (jnp.where(c_noised, cb, -1) == jnp.where(r_noised, rb, -2))
    return jnp.where(keep, s, _NEG_INF)


def _kernel_name(kernel, window, block_diffusion=None):
    if block_diffusion is not None:
        return f"flash_blockdiff{block_diffusion}_attention_{kernel}"
    return (f"flash_attention_{kernel}" if window is None
            else f"flash_window{window}_attention_{kernel}")


# ------------------------------------------------------- HBM interface --

def _in_place(width, group):
    """An operand ``[batch, seq, heads, width]`` of a call whose q heads
    are ``group`` times its kv heads is read where it lies, as ``[batch,
    seq, heads * width]`` with the head picked on the lane axis, if

    - a head is a whole number of 128-lane tiles (no block rule cuts a lane
      axis anywhere else), and
    - the call's kv heads are grouped.  A kernel pays for the in-place
      layout: a head's rows are 4 KB tiles gathered at a stride, not one
      run (the kernels' roofline shares fell 1-3% on the v5e, PR 35).
      Grouped, K and V are fetched once for the ``group`` q heads that read
      them and XLA saves more round the call than the kernels lose (one
      laguna attention layer, forward + backward: 33.4 -> 32.5 and 39.4 ->
      37.6 ms).  One kv head a q head, every program fetches its own K and
      V and the layer LOSES: GPT's 27.8 -> 28.4 ms, kanana's (v-side only)
      71.7 -> 72.4, and the four-chip GPT step 1.2% (PERF.md section 6)."""
    return width % 128 == 0 and group > 1


def operand_layouts(head, head_v, group):
    """``(in place, copied)`` of a call's eight operands, its backward's
    among them: the names that cross where XLA holds them, and ``{name:
    why}`` of those that cross as copies.  A gradient has the width, and so
    the layout, of what it is the gradient of; O and dO have v's."""
    in_place, copied = (), {}
    for names, width in ((("q", "k", "dq", "dk"), head),
                         (("v", "o", "do", "dv"), head_v)):
        if _in_place(width, group):
            in_place += names
        else:
            copied.update(dict.fromkeys(
                names, f"width {width} % 128" if width % 128
                else "kv heads not grouped"))
    return in_place, copied


def _layout_shape(batch, seq, heads, width, group):
    """The shape in which a ``[batch, seq, heads, width]`` operand crosses
    between XLA and the kernels."""
    if _in_place(width, group):
        return batch, seq, heads * width
    return batch * heads, seq, width


def _to_kernel(x, group):
    """``[batch, seq, heads, width]`` in its kernel layout: a reshape that
    moves nothing, or the copy ``[batch * heads, seq, width]`` (which XLA
    folds into the producer where it can: a projection writes heads-first
    as readily as not)."""
    if not _in_place(x.shape[3], group):
        x = x.transpose(0, 2, 1, 3)
        return x.reshape(-1, *x.shape[2:])
    return x.reshape(*x.shape[:2], -1)


def _copied(x, batch, heads):
    """Whether kernel-layout operand ``x`` is the copy ``[batch * heads,
    seq, width]``.  With one head the two layouts are the same array."""
    return x.shape[0] == batch * heads


def _from_kernel(x, batch, heads):
    """A kernel-layout operand back as ``[batch, seq, heads, width]``."""
    if _copied(x, batch, heads):
        return x.reshape(batch, heads, x.shape[1], -1).transpose(0, 2, 1, 3)
    return x.reshape(batch, x.shape[1], heads, -1)


def _width(x, batch, heads):
    """The head width of kernel-layout operand ``x``."""
    return x.shape[2] if _copied(x, batch, heads) else x.shape[2] // heads


def _head_spec(x, batch, heads, rows, at):
    """The BlockSpec of ``rows`` rows of ONE head of kernel-layout operand
    ``x``, whichever of the two layouts it is in: ``at(*grid ids) -> (bn,
    row block)``, ``bn`` the head on the flattened ``[batch * heads]`` axis
    (heads innermost), which the copy has as its leading axis and the
    in-place form divides into ``(bn // heads, row block, bn % heads)``."""
    if _copied(x, batch, heads):
        return pl.BlockSpec((1, rows, x.shape[2]),
                            lambda *ids: (*at(*ids), 0))

    def on_the_lanes(*ids):
        bn, i = at(*ids)
        return bn // heads, i, bn % heads

    return pl.BlockSpec((1, rows, x.shape[2] // heads), on_the_lanes)


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                scale, window=None, block_diffusion=None):
    """One (batch*head, q-block) program: online softmax over key blocks."""
    q = q_ref[0]                                       # [Bq, H]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    half = k_ref.shape[1] // 2          # read under ``block_diffusion`` only

    def body(j, carry):
        o_acc, m, l = carry
        k = _rows(k_ref, j, block_k)
        v = _rows(v_ref, j, block_k)
        s = _dot(q, k, _NT) * scale                    # [Bq, Bk]
        if causal:
            s = _mask_below_diagonal(s, qi * block_q, j * block_k, 0, window)
        elif block_diffusion is not None:
            s = _mask_block_diffusion(s, qi * block_q, j * block_k, 0,
                                      half, block_diffusion)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        o_new = o_acc * alpha + _dot(p.astype(v.dtype), v, _NN)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    if block_diffusion is None:
        first, end = _key_blocks(qi, block_q, block_k, k_ref.shape[1],
                                 causal, window)
        o_acc, m, l = jax.lax.fori_loop(first, end, body, (o0, m0, l0))
    else:
        o_acc, m, l = _over_two_ranges(
            _blockdiff_key_blocks(qi, block_q, block_k, half,
                                  block_diffusion), body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o_acc / l).astype(o_ref.dtype)
    # lse leaves as the ROW [1, block_q] of the head's [seq / block_q,
    # block_q] block, which stays in VMEM while the head's q blocks run (a
    # [block_q, 1] column is lane-padded 128x in HBM): the column spread
    # over 128 lanes, that tile transposed, its first row
    lse = jnp.broadcast_to(m + jnp.log(l), (block_q, 128))
    lse_ref[0, pl.ds(qi, 1), :] = lse.T[:1]


def _flash_fwd(q, k, v, dims, causal, scale, block_q, block_k, interpret,
               window=None, block_diffusion=None):
    """q, k, v in their kernel layouts (``_to_kernel``), ``dims = (batch,
    q heads, kv heads)``.  Returns O in the layout of its width and the
    log-sum-exp as rows ``[batch * q heads, seq_q / block_q, block_q]``."""
    batch, heads, kv_heads = dims
    group = heads // kv_heads
    bn, seq_q, seq_k = batch * heads, q.shape[1], k.shape[1]
    head, head_v = _width(q, batch, heads), _width(v, batch, kv_heads)
    out_shape = jax.ShapeDtypeStruct(
        _layout_shape(batch, seq_q, heads, head_v, group), q.dtype)
    q_block = lambda b, i: (b, i)                            # noqa: E731
    # q head b reads kv head b // group of the flattened axis (heads are
    # innermost there, and q's are ``group`` times kv's), whole
    kv_head = lambda b, i: (b // group, 0)                   # noqa: E731
    num_qb = seq_q // block_q
    params = _compiler_params(head, head_v)
    # K and V are held whole, double-buffered: 16 MB at [16384, 128] bf16
    # (both halves of a block-diffusion row, or one causal row that long),
    # which is Mosaic's whole default allowance.  A call that holds less
    # than _ASK_VMEM_FROM asks nothing, as before (8 MB at 8,192)
    held = 2 * (_vmem_bytes((1, seq_k, head), k.dtype)
                + _vmem_bytes((1, seq_k, head_v), v.dtype))
    if block_diffusion is not None or (not params
                                       and held >= _ASK_VMEM_FROM):
        params = {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=held + 16 * 1024 * 1024)}
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                          scale=scale, window=window,
                          block_diffusion=block_diffusion),
        name=_kernel_name("fwd", window, block_diffusion),
        grid=(bn, num_qb),
        in_specs=[
            _head_spec(q, batch, heads, block_q, q_block),
            _head_spec(k, batch, kv_heads, seq_k, kv_head),
            _head_spec(v, batch, kv_heads, seq_k, kv_head),
        ],
        out_specs=[
            _head_spec(out_shape, batch, heads, block_q, q_block),
            pl.BlockSpec((1, num_qb, block_q), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((bn, num_qb, block_q), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward --

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc, block_q, causal,
                scale, window=None, group=1, block_diffusion=None):
    """One (kv head, q head of its group, k block) program over the q
    blocks that see the k block: S, the mask, P and dP are formed ONCE a
    block pair and all three gradients take their part from them.

    The scores are formed transposed, [Bk, Bq] = K Q^T, so that P^T dO and
    dS^T Q are plain matmuls; dQ's part, dS K, is then the one dot that
    contracts dimension 0 of both operands (Mosaic transposes the dS tile
    for it; it hangs off dS beside dK's dot and is not on the dot ->
    softmax -> dot chain).  lse and delta come as rows, one [1, Bq] row of a
    [num_qb, Bq] block per q block (a [seq_q, 1] block would be lane-padded
    128x in VMEM).

    dQ: the q head's whole-sequence float32 ``dq_acc`` stays in VMEM while
    the head's k blocks run (innermost axis), takes a q block's part at a
    time, in the order of the k blocks, and is written once, scaled and in
    the input dtype, at the head's last k block (the output block's index
    does not move before).

    dK, dV: with one q head a kv head the program owns its k block's rows
    and writes them.  Grouped KV heads: the q heads of the group run one
    after the other OUTSIDE the k blocks (Q and dO cross HBM once a q
    head), so a k block's parts are summed over the group in whole-sequence
    float32 scratch ``kv_acc`` and the whole-sequence output blocks, which
    stay where they are for the kv head, are written a k block at a time
    at the group's last head."""
    k = k_ref[0]                                               # [Bk, H]
    v = v_ref[0]
    block_k = k.shape[0]
    num_qb = q_ref.shape[1] // block_q
    g, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        def zero(i, _):
            dq_acc[_block_rows(i, block_q), :] = jnp.zeros(
                (block_q, dq_acc.shape[1]), jnp.float32)
        jax.lax.fori_loop(0, num_qb, zero, None)

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = _rows(q_ref, i, block_q)
        do = _rows(do_ref, i, block_q)
        lse = lse_ref[0, pl.ds(i, 1), :]                       # [1, Bq]
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = _dot(k, q, _NT) * scale                           # [Bk, Bq]
        if causal:
            st = _mask_below_diagonal(st, i * block_q, ki * block_k, 1,
                                      window)
        elif block_diffusion is not None:
            st = _mask_block_diffusion(st, i * block_q, ki * block_k, 1,
                                       q_ref.shape[1] // 2, block_diffusion)
        pt = jnp.exp(st - lse)
        dv_new = dv_acc + _dot(pt.astype(do.dtype), do, _NN)
        dst = (pt * (_dot(v, do, _NT) - delta)).astype(q.dtype)
        dk_new = dk_acc + _dot(dst, q, _NN)
        dq_acc[_block_rows(i, block_q), :] += _dot(dst, k, _TN)   # [Bq, H]
        return dk_new, dv_new

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = dk0 if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32)
    # q blocks before this k block's diagonal contribute nothing
    if block_diffusion is None:
        first, end = _query_blocks(ki, block_q, block_k, num_qb, causal,
                                   window)
        dk, dv = jax.lax.fori_loop(first, end, body, (dk0, dv0))
    else:
        dk, dv = _over_two_ranges(
            _blockdiff_query_blocks(ki, block_q, block_k, num_qb,
                                    q_ref.shape[1] // 2, block_diffusion),
            body, (dk0, dv0))

    # dS = P * (dP - delta) * scale: the scale goes on once, at the end
    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        def write(i, _):
            rows = _block_rows(i, block_q)
            dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(
                dq_ref.dtype)
        jax.lax.fori_loop(0, num_qb, write, None)

    if group == 1:
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    dk_sum, dv_sum = kv_acc
    k_rows = _block_rows(ki, block_k)

    @pl.when(g == 0)
    def _():
        dk_sum[k_rows, :] = dk
        dv_sum[k_rows, :] = dv

    @pl.when(g > 0)
    def _():
        dk_sum[k_rows, :] += dk
        dv_sum[k_rows, :] += dv

    @pl.when(g == group - 1)
    def _():
        dk_ref[0, k_rows, :] = (dk_sum[k_rows, :] * scale).astype(
            dk_ref.dtype)
        dv_ref[0, k_rows, :] = dv_sum[k_rows, :].astype(dv_ref.dtype)


def _vmem_bytes(shape, dtype):
    """Bytes of a block in VMEM: the last dimension padded to 128 lanes,
    the one before to the dtype's sublane tile."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    tile = 8 * 4 // itemsize
    n = (-(-rows // tile) * tile) * (-(-lanes // 128) * 128) * itemsize
    for d in lead:
        n *= d
    return n


def _bwd_blocks(group, seq_q, seq_k, head, head_v, block_q, block_k, dtype):
    """``(ins, outs, scratch)`` of the backward call, from the shapes
    alone: its input and output blocks as ``(shape, dtype, at)`` over the
    grid ``(kv head, q head of its group, k block)`` (``shape`` is ONE
    head's ``(1, rows, width)``, ``at`` as ``_head_spec`` takes it), its
    VMEM scratch as ``(shape, dtype)``."""
    f32 = jnp.float32
    num_qb = seq_q // block_q
    q_head = lambda b, g, j: (b * group + g, 0)             # noqa: E731
    k_block = lambda b, g, j: (b, j)                         # noqa: E731
    scratch = [((seq_q, head), f32)]                         # dQ
    if group == 1:
        kv_rows, kv_out = block_k, k_block
    else:
        kv_rows, kv_out = seq_k, lambda b, g, j: (b, 0)
        scratch += [((seq_k, head), f32), ((seq_k, head_v), f32)]
    ins = [((1, seq_q, head), dtype, q_head),
           ((1, block_k, head), dtype, k_block),
           ((1, block_k, head_v), dtype, k_block),
           ((1, seq_q, head_v), dtype, q_head),
           ((1, num_qb, block_q), f32, q_head),
           ((1, num_qb, block_q), f32, q_head)]
    outs = [((1, seq_q, head), dtype, q_head),
            ((1, kv_rows, head), dtype, kv_out),
            ((1, kv_rows, head_v), dtype, kv_out)]
    return ins, outs, scratch


def _flash_bwd(q, k, v, out, lse, do, dims, causal, scale, block_q, block_k,
               interpret, window=None, block_diffusion=None):
    """Operands in their kernel layouts (``out`` and ``do`` in the layout
    of v's width), ``lse`` as the forward left it, ``dims = (batch, q
    heads, kv heads)``; dQ, dK, dV come back in q's, k's and v's layouts."""
    batch, heads, kv_heads = dims
    group = heads // kv_heads
    seq_q, seq_k = q.shape[1], k.shape[1]
    head, head_v = _width(q, batch, heads), _width(v, batch, kv_heads)
    # delta = rowsum(dO * O) over a head's width -- cheap elementwise, left
    # to XLA fusion; formed as the rows the kernel reads, lse's
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    if _copied(do, batch, heads):
        delta = jnp.sum(prod, axis=-1)
    else:
        # [batch, seq, heads * width]: the view that keeps the eight rows of
        # an (8, 128) tile together is the array as it lies (a plain
        # [seq, heads, width] view costs a copy of the float32 product, 302
        # MB at [8192, 72 x 128]: the compiler, ahead of time, PR 35); what
        # is moved to heads-first is the [seq, heads] result
        delta = jnp.sum(prod.reshape(batch, seq_q // 8, 8, heads, head_v),
                        axis=-1).transpose(0, 3, 1, 2)
    delta = delta.reshape(lse.shape)
    ins, outs, scratch = _bwd_blocks(group, seq_q, seq_k, head, head_v,
                                     block_q, block_k, q.dtype)
    # the allowance, reckoned from the blocks' bytes: what is resident
    # (every block twice, Pallas double-buffers them; the scratch once:
    # 28 MB at [8192, 192 | 128] bf16, 32 MB grouped at [8192, 128]) and
    # Mosaic's own default of 16 MB on top, for the [Bk, Bq] float32 tiles
    # between the dots
    resident = (2 * sum(_vmem_bytes(s, dt) for s, dt, _ in ins + outs)
                + sum(_vmem_bytes(s, dt) for s, dt in scratch))
    # the outputs dQ, dK, dV are laid out as the first three inputs are
    operands = (q, k, v, do, lse, delta)
    of_heads = (heads, kv_heads, kv_heads, heads, heads, heads)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, causal=causal,
                          scale=scale, window=window, group=group,
                          block_diffusion=block_diffusion),
        name=_kernel_name("bwd_dq_dkv", window, block_diffusion),
        grid=(batch * kv_heads, group, seq_k // block_k),
        in_specs=[_head_spec(x, batch, n, s[1], at)
                  for x, n, (s, _, at) in zip(operands, of_heads, ins)],
        out_specs=[_head_spec(x, batch, n, s[1], at)
                   for x, n, (s, _, at) in zip(operands, of_heads, outs)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM(s, dt) for s, dt in scratch],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=resident + 16 * 1024 * 1024),
    )(*operands)
    return dq, dk, dv


# ------------------------------------------------------------- public API --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_kernels(q, k, v, dims, causal, scale, interpret,
                             window=None, block_diffusion=None):
    """q, k, v and the result in their kernel layouts (``_to_kernel``),
    which are the residuals too: an operand that had to be copied is
    copied once, as it always was."""
    out, _ = _fwd_rule(q, k, v, dims, causal, scale, interpret, window,
                       block_diffusion)
    return out


def _blocks(seq_q, seq_k):
    """``(block_q, block_k)``: on each axis the largest of 512, 256, 128, 64
    that divides the sequence (else of 32, 16, 8: a length :func:`supports`
    takes has one).  The rule is the tables below: nothing is searched at
    run time.

    Measured on the v5e (PR 25, bf16 causal, fwd + dq + dkv a call): a block
    step is bound by the latency of its dot -> softmax -> dot chain, not by
    the MXU or the vector unit, and a larger block gives the scheduler more
    independent rows to overlap: [128, 2048, 128] takes 19.8 ms at 128 x 128,
    9.9 at 256 x 256, 6.9 at 512 x 512; 1024 on either axis loses again (8.0
    to 8.2: the diagonal blocks compute more masked scores than the longer
    block saves), at seq 4096 too (11.1 against 10.9).  head_dim 64 at seq
    1024 orders the shapes the same way (4.8, 2.8, 2.3 ms), so the head
    size does not enter the choice.  Mosaic takes the forward at 512 x 512
    for head_dim 64 and 128 as far as it took it at 128 x 128: seq 8192 in
    bf16, 4096 in float32 (AOT for the v5e; beyond, K and V no longer fit
    VMEM whole).

    The ONE backward kernel (PR 33, the kernels alone on the v5e, bf16
    causal; forward | forward + backward of a call, ms; in brackets the two
    backward kernels it replaced, 512 x 512):

    ==========  ============  ============  ============  ===========
    blocks      [64, 8192,    [48 over 8,   the same, 72  [128, 2048,
    q x k       192 | 128]    8192, 128]    q heads,      128]
                                            window 512
    ==========  ============  ============  ============  ===========
    512 x 512   15.01 | 44.98 7.89 | 21.54  3.88 | 9.86   2.02 | 4.93
                [| 56.52]     [| 27.34]     [| 14.21]     [| 6.33]
    256 x 512   15.32 | 48.05 8.19 | 25.78  3.95 | 11.09  2.05 | 5.77
    512 x 256   20.32 | 51.10 11.79 | 27.68 5.14 | 11.88  2.81 | 6.12
    1024 x 512  16.19 | 45.85 8.43 | 21.94  5.16 | 12.59  2.35 | 5.60
    512 x 1024  14.87 | 45.38 7.86 | 21.84  4.75 | 12.25  2.22 | 5.61
    256 x 256   24.11 | 57.16 14.63 | 38.35 4.96 | 12.64  3.10 | 7.72
    ==========  ============  ============  ============  ===========

    512 x 512 stays.  The backward alone, 41.5 -> 30.0, 19.5 -> 13.6, 10.3
    -> 6.0 and 4.30 -> 2.91 ms, is now within a fifth of what the MXU takes
    for the five dots as it runs them (a 192-wide side costs it 256); the
    transposed dS tile of dQ's dot does not show beside that."""
    return pick_block(seq_q, 512), pick_block(seq_k, 512)


def blockdiff_pairs_needed(half, block):
    """The (query, key) pairs one row and head holds under the
    block-diffusion mask: a noised query its own block and the blocks
    before it, ``B + blk B``, a clean query its own and those before it,
    the same count: ``L (L + B)`` in all."""
    return half * (half + block)


@functools.lru_cache(maxsize=None)
def _blockdiff_tiles_visited(half, block, block_q, block_k):
    """The (q block, key block) pairs the forward's grid visits: asked once
    a layer while a step is traced, so reckoned once a shape."""
    with jax.ensure_compile_time_eval():
        return sum(
            int((a1 - a0) + (b1 - b0)) for (a0, a1), (b0, b1) in (
                _blockdiff_key_blocks(qi, block_q, block_k, half, block)
                for qi in range(2 * half // block_q)))


def blockdiff_pairs_scored(half, block):
    """The pairs the forward's grid forms scores for over one row and head
    (the backward's grid visits the same block pairs, transposed): the key
    blocks every q block visits, whole."""
    block_q, block_k = _blocks(2 * half, 2 * half)
    return _blockdiff_tiles_visited(half, block, block_q, block_k) \
        * block_q * block_k


# the forward's own results among the residuals, by the names a
# rematerialisation policy can keep (``jax.checkpoint_policies
# .save_only_these_names``): with both saved, the recomputed forward of a
# layer forms q, k and v again and never runs this kernel a second time
SAVED_BY_NAME = ("flash_attention_out", "flash_attention_lse")


def _fwd_rule(q, k, v, dims, causal, scale, interpret, window=None,
              block_diffusion=None):
    block_q, block_k = _blocks(q.shape[1], k.shape[1])
    out, lse = _flash_fwd(q, k, v, dims, causal, scale, block_q, block_k,
                          interpret, window, block_diffusion)
    out = checkpoint_name(out, SAVED_BY_NAME[0])
    lse = checkpoint_name(lse, SAVED_BY_NAME[1])
    return out, (q, k, v, out, lse)


def _bwd_rule(dims, causal, scale, interpret, window, block_diffusion, res,
              do):
    q, k, v, out, lse = res
    block_q, block_k = _blocks(q.shape[1], k.shape[1])
    return _flash_bwd(q, k, v, out, lse, do, dims, causal, scale, block_q,
                      block_k, interpret, window, block_diffusion)


_flash_attention_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """Sweep flash at the engine's full-context envelope with per-shard
    head counts; the vjp case traces jax.grad through the custom_vjp so
    the lint sees the backward kernel too."""
    n = max(engine.num_heads // engine.tp, 1)
    h = engine.head_dim
    seq = engine.max_model_len
    if not supports(seq, seq, h):
        return
    sds = jax.ShapeDtypeStruct
    x = sds((engine.max_batch, seq, n, h), engine.dtype)

    def fwd(q, k, v):
        return flash_attention_pallas(q, k, v, is_causal=True)

    def vjp(q, k, v):
        def loss(*a):
            return jnp.sum(fwd(*a).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    yield registry.KernelCase(f"fwd[s{seq}]", fwd, (x, x, x), None)
    yield registry.KernelCase(f"vjp[s{seq}]", vjp, (x, x, x), None)
    # a sliding window (an eighth of the context) over grouped KV heads
    # (every q head on one kv head): the block ranges that follow the
    # window, the kv block index, the backward's group axis and the
    # whole-sequence dK and dV it sums in scratch
    window = max(seq // 8, 1)
    one_kv = sds((engine.max_batch, seq, 1, h), engine.dtype)

    def vjp_window(q, k, v):
        def loss(*a):
            return jnp.sum(flash_attention_pallas(
                *a, is_causal=True, window=window).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    yield registry.KernelCase(f"vjp_window[s{seq},w{window},{n}over1]",
                              vjp_window, (x, one_kv, one_kv), None)
    # a block-diffusion mask of 4 over the same grouped heads, the row the
    # context's two halves: both loops' two ranges and the staircase
    if supports(seq, seq, h, h, n, 1, None, False, 4):
        def vjp_blockdiff(q, k, v):
            def loss(*a):
                return jnp.sum(flash_attention_pallas(
                    *a, block_diffusion=4).astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        yield registry.KernelCase(f"vjp_blockdiff[s{seq},b4,{n}over1]",
                                  vjp_blockdiff, (x, one_kv, one_kv), None)
    # latent attention's expanded form: q and k carry a rotary part half
    # as wide again as the head (128 + 64 over 128)
    wide = h + h // 2
    if supports(seq, seq, wide, h):
        xw = sds((engine.max_batch, seq, n, wide), engine.dtype)
        yield registry.KernelCase(f"fwd_mla[s{seq},{wide}|{h}]", fwd,
                                  (xw, xw, x), None)
        yield registry.KernelCase(f"vjp_mla[s{seq},{wide}|{h}]", vjp,
                                  (xw, xw, x), None)


@registry.register_kernel(
    "flash_attention",
    fallback="paddle_tpu.ops.pallas:_xla_attention",
    parity="tests/test_pallas_kernels.py::test_flash_attention_grads",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def flash_attention_pallas(q, k, v, is_causal=False, scale=None,
                           interpret=False, window=None,
                           block_diffusion=None):
    """q: [batch, seq, num_heads, head_dim]; k: [batch, seq, kv_heads,
    head_dim]; v: [batch, seq, kv_heads, v_head_dim] (paddle flash-attn
    layout; the two widths as :func:`supports` lists them).  ``kv_heads``
    divides ``num_heads``: q head ``h`` attends with kv head ``h //
    (num_heads / kv_heads)``, and K and V are never expanded.  ``window``
    (causal only): a query sees itself and the ``window - 1`` keys before
    it; one that covers the sequence is plain causal attention and runs as
    such.  ``block_diffusion`` ``B`` (neither causal nor a window): the row
    is ``[noised ; clean]`` and the mask the block-diffusion one of the
    module's docstring; the shapes as :func:`supports` says.

    What the kernels read and write in HBM (the module's docstring,
    "Layouts"): where the kv heads are grouped, an operand whose width is a
    whole number of 128 lanes as ``[batch, seq, heads * width]``, a reshape
    of what it is given that moves nothing, so that a projection's output
    goes in and ``out`` goes on to the next projection as it lies; any
    other operand as the copy ``[batch * heads, seq, width]``.  Which it
    was for each operand of each traced call, and why, is in
    ``ops.pallas.flash_layout_log()``.  A head-wise elementwise op between
    a projection and an in-place call works without a copy on the view
    ``[batch, seq / 8, 8, heads, width]`` (the (8, 128) tiles of ``[seq,
    heads * width]``: ``models/laguna.py _gate``).

    Returns [batch, seq, num_heads, v_head_dim]; differentiable.
    """
    b, sq, n, h = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    if n % nkv or (window is not None
                   and not (is_causal and window >= 1 and sq == sk)):
        raise ValueError(
            f"flash attention does not serve q{tuple(q.shape)} "
            f"k{tuple(k.shape)} causal={is_causal} window={window}: kv "
            f"heads must divide q heads, a window needs causal "
            f"self-attention")
    if block_diffusion is not None and not supports(
            sq, sk, h, v.shape[3], n, nkv, window, is_causal,
            block_diffusion):
        raise ValueError(
            f"flash attention does not serve q{tuple(q.shape)} "
            f"k{tuple(k.shape)} causal={is_causal} window={window} under a "
            f"block-diffusion mask of {block_diffusion}: see supports()")
    window = None if window is None or window >= sk else int(window)
    if scale is None:
        scale = 1.0 / (h ** 0.5)
    record_flash_layout(_kernel_name("fwd", window, block_diffusion)
                        .removesuffix("_fwd"),
                        f"q{tuple(q.shape)} k{tuple(k.shape)} "
                        f"v{tuple(v.shape)}",
                        *operand_layouts(h, v.shape[3], n // nkv))
    out = _flash_attention_kernels(
        *(_to_kernel(x, n // nkv) for x in (q, k, v)), (b, n, nkv),
        bool(is_causal), float(scale), interpret, window, block_diffusion)
    return _from_kernel(out, b, n)
