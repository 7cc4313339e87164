"""Compressed convolutional attention's mix (``models/zaya.py
CompressedConvAttention.mix``: everything between the q, k, v projections
and the flash call) as Pallas TPU kernels: ONE forward, ``cca_mix_fwd``, and
ONE backward, ``cca_mix_bwd``, under one ``jax.custom_vjp``.

The op, on q~ ``[B, T, n, D]`` and k~, v ``[B, T, kv, D]`` (``g = n / kv`` q
heads a kv head), with depthwise taps ``w0 [heads D, K0]``, per-head taps
``w1 [heads, K1, D, D]`` for q and for k, a temperature ``tau [kv]`` and the
rotary tables ``cos``, ``sin`` ``[T, rot / 2]``:

    a  = conv0(x)         a[t] = sum_k w0[:, k] x[t - (K0 - 1) + k]
    b  = conv1(a)         b[t, h] = sum_k a[t - (K1 - 1) + k, h] @ w1[h, k]
    uq[h] = bq[h] + (q~[h] + k~[h // g]) / 2
    uk[j] = bk[j] + (mean_{h // g = j} q~[h] + k~[j]) / 2
    nq = uq rsqrt(mean_D(uq^2) + eps)       nk = tau_j uk rsqrt(...)
    q^, k^ = rotate-half of nq, nk's leading ``rot`` dims, the rest as it is
    v' = v for kv heads 0 .. kv / 2 - 1, v one row LATER for the others

positions before a row's first read zero.  Rounded where the composition
rounds (``F.causal_conv1d``, ``F.causal_conv1d_heads``, ``_qk_mean_norm``,
``laguna._rope``): each convolution's float32 sum once, the norm's result
once, the rotation's once; conv1's matmuls take their operands in the stored
dtype and accumulate in float32; the q-k mean, the norms' statistics and the
rotation are float32.

XLA runs the chain at ``[1, 16384, 8 | 2, 128]`` as heads-first and
sequence-minor copies of the 4-D values, per-head ``multiply_reduce`` fusions
for the ``[128, 128]`` matmuls, pads and float32 passes: 21.7 ms a step over
a layer's three passes in six layers for a need of 3 (``PERF.md`` section 6,
PR 50).  A head's ``D`` lanes are whole 128-lane tiles, so here no 4-D view
is formed: both kernels run the grid ``(batch, row block)`` over the arrays
``[B, T, heads D]`` as the projections leave them, and write q^, k^ and v'
``[B, T, heads D]`` as the flash kernels read them in place.  A block of
rows crosses once each way in the stored dtype; inside it the kernels work
through ``rc`` rows at a time, a head's lanes at a time, a chunk with the
``HALO`` rows before it as one float32 value whose taps are sublane
rotations (``causal_conv_kernel``'s way: what a rotation wraps round lands
in the halo's first rows, which nothing reads: ``K0 + K1 - 2 <= HALO``).
The rows before a block come through a second, ``HALO``-row view of the
same operand (zero for a row's first block).  A group's four q heads and
its kv head are whole lane slices of one block, so the mean is a sum of
slices.  The rotation is two lane rotations and a select under the tables
laid over a head's lanes in VMEM (``cos | cos | 1``, ``-sin | sin | 0``).

The backward keeps q~ and k~ ALONE (and the parameters and tables): it forms
``a``, ``b``, ``u`` and the statistics again from a chunk with its halo and
walks the row blocks, and a block's chunks, from the LAST to the first: a
row's convolutions are read by the rows after it, so the first ``HALO`` rows
of ``db`` and ``da`` of the chunk done just before are carried in VMEM
scratch (and v's gradient's, for the shift).  The taps' and ``tau``'s
gradients accumulate in float32 output blocks that stay resident down the
whole grid: ``d w1[h, k]`` as ``[D, rows] x [rows, D]`` on the MXU (held
transposed: ``db`` is the operand both taps' dots turn), ``d w0`` and ``d
tau`` as eight partial rows a quantity, summed and rounded to the parameter's
dtype by the caller (``d tau`` stays float32).

Constraints (else the dispatcher ``ops.pallas.cca_mix`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, _NN, _NT, _TN, _dot_for, pick_block

# rows before a chunk that its taps may reach into (and, in the backward,
# rows behind it): a whole tile of a packed dtype's sublanes
HALO = 16
# rows of a float32 block of partial sums, and of the depthwise taps' operand
SIDE = 8


def _pick_block(seq, dtype):
    """``(block rows, chunk rows)``: a block of whole halos, 256 rows of
    bfloat16 (the backward holds eight such operands, twice each, beside
    1.6 MB of tap gradients, twice: half of the VMEM a kernel may take) and
    128 of float32, worked through as ONE chunk (the v5e compiler's own
    schedule of the two kernels, bundles a 128 rows forward | backward:
    chunks of 64 rows 2,634 | 7,032, 128 2,548 | 5,591, 256 2,011 | 4,461
    in blocks of 256 and 2,063 | 4,400 in blocks of 512; ``PERF.md`` section
    6, PR 50); or None."""
    bt = pick_block(seq, 512 // jnp.dtype(dtype).itemsize)
    if bt is None or bt % HALO:
        return None
    return bt, pick_block(bt, 256)


def supports(seq, heads, kv_heads, head_dim, taps, rot, dtype):
    """A head whole 128-lane tiles, q heads in whole groups over an EVEN
    number of kv heads (half of them shift), an even rotary width of at
    least two dims inside a head, both convolutions' reach inside the halo
    and a tap a row of the depthwise operand, rows whole tiles of 16;
    float32 or bfloat16."""
    return (head_dim % _LANES == 0
            and kv_heads > 0 and kv_heads % 2 == 0 and heads % kv_heads == 0
            and rot % 2 == 0 and 0 < rot <= head_dim
            and 1 <= taps[0] <= SIDE and taps[1] >= 1
            and taps[0] + taps[1] - 2 <= HALO
            and _pick_block(seq, dtype) is not None
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


# ----------------------------------------------------------- kernel bodies --

def _first_rows(halo_ref, is_first, at):
    """The ``HALO`` rows before the block; zero before a row's first."""
    return jnp.where(is_first, jnp.zeros((), halo_ref.dtype),
                     halo_ref[0, :, at])


def _with_halo(x_ref, first, r0, rc, at):
    """Rows ``r0 - HALO .. r0 + rc`` of lanes ``at`` as float32; the block's
    first chunk takes ``first`` (the view of the rows before the block)."""
    f32 = jnp.float32
    before = pl.multiple_of(jnp.maximum(r0 - HALO, 0), HALO)
    head = jnp.where(r0 == 0, first, x_ref[0, pl.ds(before, HALO), at])
    # joined as float32: whole tiles of eight rows (a packed dtype's pieces
    # would be unpacked and packed again to be joined)
    return jnp.concatenate(
        [head.astype(f32), x_ref[0, pl.ds(r0, rc), at].astype(f32)])


def _earlier(full, steps):
    """``full [HALO + rc, L]`` read ``steps`` rows earlier (the first
    ``steps`` rows wrap round)."""
    return pltpu.roll(full, steps, 0) if steps else full


def _later(full, steps):
    """``full [rc + HALO, L]`` read ``steps`` rows later (the last wrap)."""
    return pltpu.roll(full, full.shape[0] - steps, 0) if steps else full


def _depthwise(full, w, taps):
    """conv0 on a chunk with its halo: the taps' float32 sum in the
    composition's order, ``[HALO + rc, L]`` (its first ``taps - 1`` rows
    hold what the rotations wrapped)."""
    p = _earlier(full, taps - 1) * w[0:1]
    for k in range(1, taps):
        p = p + _earlier(full, taps - 1 - k) * w[k:k + 1]
    return p


def _tap_operands(a_full, taps, dtype):
    """conv1's left operands on the chunk's rows: tap ``k``'s is ``a[t -
    (K1 - 1) + k]``, rounded to the stored dtype (conv0's one rounding)."""
    return [_earlier(a_full, taps - 1 - k)[HALO:].astype(dtype)
            for k in range(taps)]


def _matrices(w1_ref):
    """A convolution's ``[heads][taps]`` matrices, read ONCE a block of
    rows, ahead of the loop over its chunks.  On a chip whose 16-bit tiles
    in VMEM are eight rows, a matmul's operand is formed from two of them
    (an unpack each and a pack: 24 vector ops a matrix); held as the 32-bit
    words of whole tiles, that is paid here and not a chunk and use."""
    def held(w):
        return pltpu.bitcast(w, jnp.uint32) if w.dtype.itemsize == 2 else w
    return [[held(w1_ref[h, k]) for k in range(w1_ref.shape[1])]
            for h in range(w1_ref.shape[0])]


def _matrix(held, dtype):
    """One of :func:`_matrices`' as a dot's operand."""
    return held if held.dtype == dtype else pltpu.bitcast(held, dtype)


def _per_head(a_ops, w1, dot):
    """conv1 of one head: a dot a tap, float32, summed in the composition's
    order."""
    dtype = a_ops[0].dtype
    b = dot(a_ops[0], _matrix(w1[0], dtype), _NN)
    for k in range(1, len(a_ops)):
        b = b + dot(a_ops[k], _matrix(w1[k], dtype), _NN)
    return b


def _unit(u, eps):
    """``(y, r)``: ``u rsqrt(mean(u^2) + eps)`` and the scale, a column."""
    r = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    return u * r, r


def _swapped(x, half, lane):
    """A head's rotary partner lanes: ``x2`` under ``x1``'s lanes and ``x1``
    under ``x2``'s; what lies under the other lanes meets a zero of the
    sine table."""
    d = x.shape[1]
    if 2 * half == d:
        return pltpu.roll(x, half, 1)
    return jnp.where(lane < half, pltpu.roll(x, d - half, 1),
                     pltpu.roll(x, half, 1))


def _over_a_head(cos_ref, sin_ref, rows, d):
    """A chunk's rows of the tables ``[T, rot / 2]`` laid over a head's
    ``d`` lanes: ``cos | cos | 1`` and ``-sin | sin | 0``."""
    c, s = cos_ref[rows, :], sin_ref[rows, :]
    rest = (c.shape[0], d - 2 * c.shape[1])
    if not rest[1]:
        return jnp.concatenate([c, c], axis=1), \
            jnp.concatenate([-s, s], axis=1)
    return (jnp.concatenate([c, c, jnp.ones(rest, c.dtype)], axis=1),
            jnp.concatenate([-s, s, jnp.zeros(rest, s.dtype)], axis=1))


def _rotated(x, cos, sin, half, lane):
    """Rotate-half under :func:`_over_a_head`'s tables: two products and
    their sum, float32."""
    return x * cos + _swapped(x, half, lane) * sin


def _rotated_back(g, cos, sin, half, lane):
    """:func:`_rotated`'s transpose (the partner map is its own inverse on
    the rotary lanes; off them the sine is zero and the partner must not
    leak in)."""
    back = _swapped(g * sin, half, lane)
    if 2 * half != g.shape[1]:
        back = jnp.where(lane < 2 * half, back, 0.0)
    return g * cos + back


def _latent(x_ref, halo_ref, w0_ref, w1, is_first, r0, rc, h, d, taps, dot):
    """One head's chunk through both convolutions: ``(x, x with its halo,
    conv1's operands, b)``, ``b`` rounded as the composition stores it."""
    at = slice(h * d, (h + 1) * d)
    dtype = x_ref.dtype
    full = _with_halo(x_ref, _first_rows(halo_ref, is_first, at), r0, rc,
                      at)
    a_ops = _tap_operands(_depthwise(full, w0_ref[:, at], taps[0]), taps[1],
                          dtype)
    b = _per_head(a_ops, w1[h], dot).astype(dtype).astype(jnp.float32)
    return full[HALO:], full, a_ops, b


def _group_sum(q_ref, rows, j, g, d):
    """The float32 sum of a group's q~ on the chunk's rows."""
    total = q_ref[0, rows, j * g * d:(j * g + 1) * d].astype(jnp.float32)
    for h in range(j * g + 1, (j + 1) * g):
        total = total + q_ref[0, rows, h * d:(h + 1) * d].astype(jnp.float32)
    return total


def _fwd_kernel(q_ref, qh_ref, k_ref, kh_ref, v_ref, vh_ref, qw0_ref, kw0_ref,
                qw1_ref, kw1_ref, tau_ref, cos_ref, sin_ref, qo_ref, ko_ref,
                vo_ref, *, dims, taps, half, eps, rc):
    n, kv, d = dims
    g = n // kv
    f32, dtype = jnp.float32, q_ref.dtype
    dot = _dot_for(dtype)
    is_first = pl.program_id(1) == 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (rc, d), 1)
    still = kv // 2 * d
    # the kv heads that read the position itself
    vo_ref[0, :, :still] = v_ref[0, :, :still]
    qw1, kw1 = _matrices(qw1_ref), _matrices(kw1_ref)

    def row_chunk(c, carry):
        r0 = pl.multiple_of(c * rc, rc)
        rows = pl.ds(r0, rc)
        cos, sin = _over_a_head(cos_ref, sin_ref, rows, d)
        for j in range(kv):
            at = slice(j * d, (j + 1) * d)
            kc, _, _, bk = _latent(k_ref, kh_ref, kw0_ref, kw1, is_first, r0,
                                   rc, j, d, taps, dot)
            # the group's q~ are summed as its heads pass
            total = None
            for h in range(j * g, (j + 1) * g):
                qc, _, _, bq = _latent(q_ref, qh_ref, qw0_ref, qw1, is_first,
                                       r0, rc, h, d, taps, dot)
                total = qc if total is None else total + qc
                y, _ = _unit(bq + 0.5 * (qc + kc), eps)
                qo_ref[0, rows, h * d:(h + 1) * d] = _rotated(
                    y.astype(dtype).astype(f32), cos, sin, half,
                    lane).astype(dtype)
            y, _ = _unit(bk + 0.5 * (total / g + kc), eps)
            ko_ref[0, rows, at] = _rotated(
                (y * tau_ref[:, at]).astype(dtype).astype(f32), cos, sin,
                half, lane).astype(dtype)
        # the other kv heads read the row before
        for s in range(still, 2 * still, d):
            # the halo's view holds the shifted half's lanes alone
            before = jnp.where(is_first, jnp.zeros((), dtype),
                               vh_ref[0, :, s - still:s - still + d])
            vo_ref[0, rows, s:s + d] = _earlier(_with_halo(
                v_ref, before, r0, rc, slice(s, s + d)), 1)[HALO:].astype(
                    dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[1] // rc, row_chunk, 0)


def _fold(v):
    """``[rc, L]`` -> the eight partial sums ``[8, L]`` of its rows (adds
    of whole tiles; the last eight-to-one sum is the caller's)."""
    out = v[:SIDE]
    for r in range(SIDE, v.shape[0], SIDE):
        out = out + v[r:r + SIDE]
    return out


def _unit_back(dy, y, r):
    """The gradient of ``u`` under ``y = u r``, ``r = rsqrt(mean(u^2) +
    eps)``."""
    return r * (dy - y * jnp.mean(dy * y, axis=-1, keepdims=True))


def _bwd_kernel(q_ref, qh_ref, k_ref, kh_ref, dqo_ref, dko_ref, dvo_ref,
                qw0_ref, kw0_ref, qw1_ref, kw1_ref, tau_ref, cos_ref, sin_ref,
                dq_ref, dk_ref, dv_ref, dqw0_ref, dkw0_ref, dqw1_ref,
                dkw1_ref, dtau_ref, qdb_ref, qda_ref, kdb_ref, kda_ref,
                vdo_ref, *, dims, taps, half, eps, rc):
    n, kv, d = dims
    g = n // kv
    f32, dtype = jnp.float32, q_ref.dtype
    dot = _dot_for(dtype)
    step = pl.program_id(1)
    # the row blocks come from the last to the first
    is_first = step == pl.num_programs(1) - 1
    chunks = q_ref.shape[1] // rc
    lane = jax.lax.broadcasted_iota(jnp.int32, (rc, d), 1)
    still = kv // 2 * d

    @pl.when((pl.program_id(0) == 0) & (step == 0))
    def _():
        for ref in (dqw0_ref, dkw0_ref, dqw1_ref, dkw1_ref, dtau_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(step == 0)
    def _():
        # nothing lies behind a row's last block
        for ref in (qdb_ref, qda_ref, kdb_ref, kda_ref, vdo_ref):
            ref[...] = jnp.zeros_like(ref)

    dv_ref[0, :, :still] = dvo_ref[0, :, :still]
    qw1, kw1 = _matrices(qw1_ref), _matrices(kw1_ref)

    def convolved_back(du, x_full, a_ops, w0_ref, w1, dw0_ref, dw1_ref,
                       db_ref, da_ref, h):
        """From ``du`` (b's gradient before its rounding) back through
        conv1 and conv0 of head ``h``: x's gradient by this path, float32;
        the taps' gradients and the carried rows as side effects."""
        at = slice(h * d, (h + 1) * d)
        db = du.astype(dtype)
        db_full = jnp.concatenate([db.astype(f32), db_ref[:, at]])
        # d w1[k] = sum_t a[t - (K1 - 1) + k]^T db[t], held TRANSPOSED (db
        # is the one operand both taps' dots turn); da[t] = sum_k db[t + (K1
        # - 1) - k] @ w1[k]^T
        da = None
        for k in range(taps[1]):
            dw1_ref[h, k] += dot(db, a_ops[k], _TN)
            db_k = db if k == taps[1] - 1 else _later(
                db_full, taps[1] - 1 - k)[:rc].astype(dtype)
            term = dot(db_k, _matrix(w1[h][k], dtype), _NT)
            da = term if da is None else da + term
        da = da.astype(dtype).astype(f32)
        da_full = jnp.concatenate([da, da_ref[:, at]])
        db_ref[:, at], da_ref[:, at] = db_full[:HALO], da[:HALO]
        # dx[t] = sum_k w0[k] da[t + (K0 - 1) - k]; d w0[k] = sum_t x[t -
        # (K0 - 1) + k] da[t]
        w0 = w0_ref[:, at]
        dx = None
        for k in range(taps[0]):
            term = _later(da_full, taps[0] - 1 - k)[:rc] * w0[k:k + 1]
            dx = term if dx is None else dx + term
            dw0_ref[k * SIDE:(k + 1) * SIDE, at] += _fold(
                _earlier(x_full, taps[0] - 1 - k)[HALO:] * da)
        return dx

    def row_chunk(c, carry):
        r0 = pl.multiple_of((chunks - 1 - c) * rc, rc)
        rows = pl.ds(r0, rc)
        cos, sin = _over_a_head(cos_ref, sin_ref, rows, d)

        def norm_back(ref, at, u, scale=None):
            """``(du, dn, y)``: the result's gradient back through the
            rotation (``dn``, rounded as the composition's cast hands it on)
            and through the norm of ``u`` (``y``, before ``scale``)."""
            dn = _rotated_back(ref[0, rows, at].astype(f32), cos, sin, half,
                               lane).astype(dtype).astype(f32)
            y, r = _unit(u, eps)
            return _unit_back(dn if scale is None else dn * scale, y, r), \
                dn, y

        for j in range(kv):
            at_k = slice(j * d, (j + 1) * d)
            kc, k_full, k_ops, bk = _latent(
                k_ref, kh_ref, kw0_ref, kw1, is_first, r0, rc, j, d, taps,
                dot)
            uk = bk + 0.5 * (_group_sum(q_ref, rows, j, g, d) / g + kc)
            duk, dn, y = norm_back(dko_ref, at_k, uk, tau_ref[:, at_k])
            dtau_ref[:, at_k] += _fold(dn * y)
            through = duk
            for h in range(j * g, (j + 1) * g):
                at = slice(h * d, (h + 1) * d)
                qc, q_full, q_ops, bq = _latent(
                    q_ref, qh_ref, qw0_ref, qw1, is_first, r0, rc, h, d,
                    taps, dot)
                duq = norm_back(dqo_ref, at, bq + 0.5 * (qc + kc))[0]
                through = through + duq
                dq_ref[0, rows, at] = (
                    convolved_back(duq, q_full, q_ops, qw0_ref, qw1,
                                   dqw0_ref, dqw1_ref, qdb_ref, qda_ref, h)
                    + (0.5 * duq + (0.5 / g) * duk)).astype(dtype)
            dk_ref[0, rows, at_k] = (
                convolved_back(duk, k_full, k_ops, kw0_ref, kw1, dkw0_ref,
                               dkw1_ref, kdb_ref, kda_ref, j)
                + 0.5 * through).astype(dtype)
        # v' read the row before: v's gradient is the row after's
        for s in range(0, still, d):
            at = slice(s, s + d)
            here = dvo_ref[0, rows, still + s:still + s + d].astype(f32)
            dv_ref[0, rows, still + s:still + s + d] = _later(
                jnp.concatenate([here, vdo_ref[:, at]]), 1)[:rc].astype(dtype)
            vdo_ref[:, at] = here[:HALO]
        return carry

    jax.lax.fori_loop(0, chunks, row_chunk, 0)


# ------------------------------------------------------------ pallas calls --

def _halo_index(bt):
    """The row-block index, in ``HALO``-row blocks, of the rows before row
    block ``i`` (block 0 reads its own first rows and zeroes them)."""
    return lambda i: jnp.maximum(i * (bt // HALO) - 1, 0)


def _whole(shape):
    """An operand every grid step holds whole (the taps, ``tau``, and in
    the backward their gradients: ONE block, resident down the grid)."""
    return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))


_launch = functools.partial(jax.jit, inline=True, static_argnames=(
    "dims", "taps", "half", "eps", "block", "interpret"))


# jit(inline=True): a layer's call is traced once a step, not once a block
# (``ssd_scan_kernel._launch``)
@_launch
def _mix_fwd(q, k, v, qw0, kw0, tau, qw1, kw1, cos, sin, dims, taps, half,
             eps, block, interpret):
    n, kv, d = dims
    batch, seq, _ = q.shape
    bt, rc = block
    before = _halo_index(bt)

    def rows(width, lane_block=0):
        return pl.BlockSpec((1, bt, width), lambda b, i: (b, i, lane_block))

    def halo(width, lane_block=0):
        return pl.BlockSpec((1, HALO, width),
                            lambda b, i: (b, before(i), lane_block))

    table = pl.BlockSpec((bt, half), lambda b, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dims=dims, taps=taps, half=half,
                          eps=eps, rc=rc),
        name="cca_mix_fwd",
        grid=(batch, seq // bt),
        in_specs=[rows(n * d), halo(n * d), rows(kv * d), halo(kv * d),
                  rows(kv * d),
                  # of v's rows before the block, the shifted half's alone
                  halo(kv * d // 2, 1),
                  _whole(qw0.shape), _whole(kw0.shape), _whole(qw1.shape),
                  _whole(kw1.shape), _whole(tau.shape), table, table],
        out_specs=[rows(n * d), rows(kv * d), rows(kv * d)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, q.dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, q, k, k, v, v, qw0, kw0, qw1, kw1, tau, cos, sin)


@_launch
def _mix_bwd(q, k, dqo, dko, dvo, qw0, kw0, tau, qw1, kw1, cos, sin, dims,
             taps, half, eps, block, interpret):
    n, kv, d = dims
    batch, seq, _ = q.shape
    bt, rc = block
    last = seq // bt - 1
    before = _halo_index(bt)
    f32 = jnp.float32

    def rows(width):
        return pl.BlockSpec((1, bt, width), lambda b, i: (b, last - i, 0))

    def halo(width):
        return pl.BlockSpec((1, HALO, width),
                            lambda b, i: (b, before(last - i), 0))

    table = pl.BlockSpec((bt, half), lambda b, i: (last - i, 0))
    sums = [jax.ShapeDtypeStruct((taps[0] * SIDE, n * d), f32),
            jax.ShapeDtypeStruct((taps[0] * SIDE, kv * d), f32),
            jax.ShapeDtypeStruct(qw1.shape, f32),
            jax.ShapeDtypeStruct(kw1.shape, f32),
            jax.ShapeDtypeStruct((SIDE, kv * d), f32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dims=dims, taps=taps, half=half,
                          eps=eps, rc=rc),
        name="cca_mix_bwd",
        grid=(batch, seq // bt),
        in_specs=[rows(n * d), halo(n * d), rows(kv * d), halo(kv * d),
                  rows(n * d), rows(kv * d), rows(kv * d),
                  _whole(qw0.shape), _whole(kw0.shape), _whole(qw1.shape),
                  _whole(kw1.shape), _whole(tau.shape), table, table],
        out_specs=[rows(n * d), rows(kv * d), rows(kv * d)]
        + [_whole(s.shape) for s in sums],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, q.dtype)] + sums,
        # the first rows of db and da of the chunk behind, q's and k's, and
        # of the shifted heads' dv'
        scratch_shapes=[pltpu.VMEM((HALO, n * d), f32)] * 2
        + [pltpu.VMEM((HALO, kv * d), f32)] * 2
        + [pltpu.VMEM((HALO, kv * d // 2), f32)],
        interpret=interpret,
        # the taps' sums are ONE block down both axes, and a row's blocks
        # hand rows on from the last to the first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(q, q, k, k, dqo, dko, dvo, qw0, kw0, qw1, kw1, tau, cos, sin)


# ------------------------------------------------------------- public API --

def _side(w0):
    """The depthwise taps as the kernels read them: float32 ``[SIDE, C]``,
    tap ``k`` in row ``k``."""
    channels, taps = w0.shape
    return jnp.concatenate([w0.astype(jnp.float32).T,
                            jnp.zeros((SIDE - taps, channels), jnp.float32)])


def _operands(qw0, kw0, tau, d):
    """The depthwise taps and ``tau`` as the kernels read them: float32
    rows, ``tau`` over its head's lanes."""
    return (_side(qw0), _side(kw0),
            jnp.repeat(tau.astype(jnp.float32), d)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13))
def _mix_kernels(q, k, v, qw0, kw0, qw1, kw1, tau, cos, sin, dims, eps, block,
                 interpret):
    return _fwd_rule(q, k, v, qw0, kw0, qw1, kw1, tau, cos, sin, dims, eps,
                     block, interpret)[0]


def _fwd_rule(q, k, v, qw0, kw0, qw1, kw1, tau, cos, sin, dims, eps, block,
              interpret):
    taps = (qw0.shape[1], qw1.shape[1])
    out = _mix_fwd(q, k, v, *_operands(qw0, kw0, tau, dims[2]), qw1, kw1,
                   cos, sin, dims, taps, cos.shape[1], eps, block, interpret)
    return tuple(out), (q, k, qw0, kw0, qw1, kw1, tau, cos, sin)


def _bwd_rule(dims, eps, block, interpret, res, grads):
    q, k, qw0, kw0, qw1, kw1, tau, cos, sin = res
    d = dims[2]
    taps = (qw0.shape[1], qw1.shape[1])
    dq, dk, dv, dqw0, dkw0, dqw1, dkw1, dtau = _mix_bwd(
        q, k, *grads, *_operands(qw0, kw0, tau, d), qw1, kw1, cos, sin, dims,
        taps, cos.shape[1], eps, block, interpret)

    def taps_of(sums, like):
        # eight partial rows a tap -> [C, K0]
        return jnp.sum(sums.reshape(taps[0], SIDE, -1), axis=1).T.astype(
            like.dtype)

    return (dq, dk, dv, taps_of(dqw0, qw0), taps_of(dkw0, kw0),
            dqw1.swapaxes(2, 3).astype(qw1.dtype),
            dkw1.swapaxes(2, 3).astype(kw1.dtype),
            jnp.sum(dtau.reshape(SIDE, -1, d), axis=(0, 2)).astype(tau.dtype),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


_mix_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/zaya.py`` trains
    and has no decode path); the lint sweeps one training-shaped case, value
    and backward, in the engine's dtype: four q heads over two kv heads of
    128, the published two taps each and half a head rotated."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    seq, n, kv, d, rot = 512, 4, 2, 128, 64
    args = (sds((1, seq, n, d), engine.dtype),
            sds((1, seq, kv, d), engine.dtype),
            sds((1, seq, kv, d), engine.dtype),
            sds((n * d, 2), engine.dtype), sds((n, 2, d, d), engine.dtype),
            sds((kv * d, 2), engine.dtype), sds((kv, 2, d, d), engine.dtype),
            sds((kv,), f32), sds((seq, rot // 2), f32),
            sds((seq, rot // 2), f32))
    mix = functools.partial(cca_mix_pallas, epsilon=1e-5)

    def vjp(*o):
        def loss(*o):
            return sum(jnp.sum(x.astype(f32)) for x in mix(*o))
        return jax.grad(loss, argnums=tuple(range(8)))(*o)

    label = f"[s{seq},h{n}|{kv}x{d},k2+2]"
    yield registry.KernelCase("value" + label, mix, args, None)
    yield registry.KernelCase("vjp" + label, vjp, args, None)


@registry.register_kernel(
    "cca_mix",
    fallback="paddle_tpu.models.zaya:_mix_composed",
    parity="tests/test_cca_mix_kernel.py::test_kernels_match_the_"
           "composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def cca_mix_pallas(q, k, v, q_conv0, q_conv1, k_conv0, k_conv1, tau, cos, sin,
                   *, epsilon, interpret=False, block=None):
    """``q [B, T, n, D]``, ``k``, ``v`` ``[B, T, kv, D]``, depthwise taps
    ``[heads D, K0]``, per-head taps ``[heads, K1, D, D]``, ``tau [kv]``,
    ``cos`` / ``sin`` ``[T, rot / 2]`` -> q^ ``[B, T, n, D]``, k^ and v'
    ``[B, T, kv, D]`` in q's dtype (reshapes of ``[B, T, heads D]`` arrays,
    which the flash kernels read in place); differentiable in the first
    eight.  ``block`` ``(block rows, chunk rows)`` is the tests' and the
    tuning's."""
    batch, seq, n, d = q.shape
    kv = k.shape[2]
    taps, rot = (q_conv0.shape[1], q_conv1.shape[1]), 2 * cos.shape[1]
    if not supports(seq, n, kv, d, taps, rot, q.dtype) \
            or k.shape != (batch, seq, kv, d) or v.shape != k.shape \
            or {k.dtype, v.dtype, q_conv1.dtype, k_conv1.dtype} != {q.dtype} \
            or q_conv0.shape != (n * d, taps[0]) \
            or k_conv0.shape != (kv * d, taps[0]) \
            or q_conv1.shape != (n, taps[1], d, d) \
            or k_conv1.shape != (kv, taps[1], d, d) \
            or tau.shape != (kv,) or cos.shape != sin.shape \
            or cos.shape[0] != seq:
        raise ValueError(
            f"cca mix does not serve q{tuple(q.shape)} {q.dtype} "
            f"k{tuple(k.shape)} {k.dtype} v{tuple(v.shape)} {v.dtype} taps "
            f"{tuple(q_conv0.shape)} {tuple(q_conv1.shape)} "
            f"{q_conv1.dtype} rot={rot}: see cca_mix_kernel.supports")
    bt, rc = block = block or _pick_block(seq, q.dtype)
    if seq % bt or bt % rc or rc % HALO:
        raise ValueError(f"cca mix: block {block} does not tile "
                         f"q{tuple(q.shape)}")
    out = _mix_kernels(
        q.reshape(batch, seq, n * d), k.reshape(batch, seq, kv * d),
        v.reshape(batch, seq, kv * d), q_conv0, k_conv0, q_conv1, k_conv1,
        tau, cos.astype(jnp.float32), sin.astype(jnp.float32), (n, kv, d),
        float(epsilon), tuple(block), bool(interpret))
    return tuple(x.reshape(batch, seq, -1, d) for x in out)
