"""The chunked Mamba-2 recurrence (``nn/functional.py ssd_scan``) as Pallas
TPU kernels: ONE forward, ``ssd_scan_fwd``, and ONE backward,
``ssd_scan_bwd``, under one ``jax.custom_vjp``.

With ``Q`` the chunk, a head ``h`` of width ``P`` on group ``g`` (``hg``
heads a group) at state ``N``, and inside one chunk ``a_i`` the running sum
of ``dt A`` up to position ``i``:

    G      = C B^T                                   [Q, Q], once a group
    M_h    = G * exp(a_i - a_j) * dt_j   (j <= i)    masked BEFORE the exp
    y_h    = M_h x_h + exp(a_i) C S_h^T + D_h x_h    S_h [P, N]: ENTERING state
    S_h'   = exp(a_Q) S_h + (x_h * w)^T B,   w_j = exp(a_Q - a_j) dt_j

Both kernels run the grid ``(batch, group, chunk)`` with the chunk axis last
and sequential.  A step holds ONE chunk of ONE group: x ``[Q, hg P]`` cut
straight out of ``[batch, T, heads P]`` by the block index map (whole
128-lane tiles: no ``[batch heads, ...]`` copy), B and C ``[Q, N]`` out of
``[batch, T, groups N]``, the step sizes as rows ``[hg, Q]`` out of
``[batch, heads, T]`` (2 MB a layer: XLA transposes them, nothing else).
``G``, every ``[Q, Q]`` decay matrix and the state live in VMEM only.  The
group's states ``[hg P, N]`` are a float32 scratch carried from one grid
step to the next:

- the forward zeroes it at chunk 0, writes y in x's dtype and each chunk's
  ENTERING state (float32, the backward's one residual beside the
  operands);
- the backward walks the chunks from the last to the first, carrying the
  gradient of the state a chunk leaves; it forms the chunk's matrices AGAIN
  from x, B, C and the step sizes, reads dy and the entering state, and
  writes dx, dB and dC (summed over the group's heads inside the step: the
  reason the grid is by group), the step sizes' and decay sums' gradients as
  rows, and ``D``'s gradient summed down the chunk axis in a revisited
  output block.  XLA is left ``A``'s gradient (a sum over 2 MB).

A head is 64 lanes, half a tile, and nothing here cuts a tile in VMEM: a
product that is a head's own (``M_h x_h``, ``M_h^T dy_h``, ``dy_h x_h^T``)
is formed on the whole tile of ``128 / P`` heads and the head's lanes are
selected (``lane // P == k``), or the other heads' lanes are zeroed before a
contraction over the lanes; a product the group's heads share an operand of
(``C S^T``, ``B dS^T``, ``(x w)^T B``, ``(dy exp a)^T C``) is one matmul
over all ``hg P`` lanes.  Sums over a head's ``P`` lanes (the decay sums'
gradients) are taken on the TRANSPOSED tile, as sums over sublanes that
come out as rows.

Precision (``nn/functional.py ssd_scan``'s, to the letter): float32 for the
step sizes, the decay sums (a log-step running sum on the VPU, exact in
float32), every ``exp``, the carried states and every accumulation; the
matmuls take the decay-weighted matrix, x, B, C, dy and the states in x's
dtype and accumulate in float32.

Constraints (else the dispatcher ``ops.pallas.ssd_scan`` takes the XLA
composition, aloud on the TPU): :func:`supports`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .common import _LANES, _NN, _NT, _TN, _dot_for


def supports(seq, heads, head_dim, groups, state, chunk, dtype):
    """The chunk and the state whole 128-lane tiles (chunks of 128 or 256);
    a head a whole divisor of a tile (32 lanes at least: a tile's heads
    each cost a tile's matmul) or a whole multiple, and a group's ``hg P``
    lanes whole tiles; a group's heads whole sublane tiles of 8 and at
    most 128 (they share one tile as columns); rows of whole chunks (the
    caller has padded with steps of size zero); float32 or bfloat16."""
    if heads % groups:
        return False
    hg = heads // groups
    return (chunk in (128, 256) and seq % chunk == 0
            and state % _LANES == 0
            and head_dim >= 32
            and (_LANES % head_dim == 0 or head_dim % _LANES == 0)
            and (hg * head_dim) % _LANES == 0
            and hg % 8 == 0 and hg <= _LANES
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


# ------------------------------------------------------------ a chunk's own --

def _running_sum(rows, reverse=False):
    """The inclusive running sum along the lanes of float32 ``[r, Q]`` (from
    the last lane down with ``reverse``), by log steps of roll and add on
    the VPU: float32-exact, where one bf16 pass of a triangular matmul
    would be a different result."""
    q = rows.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    shift = 1
    while shift < q:
        if reverse:
            rows = rows + jnp.where(lane < q - shift,
                                    pltpu.roll(rows, q - shift, 1), 0.0)
        else:
            rows = rows + jnp.where(lane >= shift,
                                    pltpu.roll(rows, shift, 1), 0.0)
        shift *= 2
    return rows


def _columns(rows):
    """``[hg, Q]`` rows as columns ``[Q, 128]`` (lane ``h`` is row ``h``):
    one transpose of a whole tile."""
    hg, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((_LANES - hg, q), rows.dtype)], axis=0).T


def _pick(parts, seg):
    """Lane ``l`` of ``parts[seg[l]]``."""
    out = parts[-1]
    for k in range(len(parts) - 2, -1, -1):
        out = jnp.where(seg == k, parts[k], out)
    return out


def _spread(cols, heads, seg):
    """``[Q, tile]`` whose lane ``l`` holds column ``heads[seg[l]]`` of
    ``cols``: a tile's heads' own factors, each over its head's lanes."""
    return _pick([cols[:, h:h + 1] for h in heads], seg)


def _set_row(rows, h, row):
    """``rows [hg, Q]`` with row ``h`` replaced by ``row [1, Q]``."""
    row_id = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(row_id == h, row, rows)


class _Chunk:
    """What both kernels form of a chunk before they touch x: the decay
    sums as rows and as columns, ``G``, and a head's decay matrix."""

    def __init__(self, b_ref, c_ref, dt_ref, dta_ref, keep_ref, head_dim):
        self.b, self.c = b_ref[0], c_ref[0]                    # [Q, N]
        self.dot = _dot_for(self.b.dtype)
        self.dt_rows = dt_ref[0]                               # [hg, Q]
        self.a_rows = _running_sum(dta_ref[0])
        self.a_cols = _columns(self.a_rows)                    # [Q, 128]
        q = self.b.shape[0]
        # exp(a_Q - a_j), what position j's step still weighs at the
        # chunk's end, and exp(a_i), what is left of the entering state
        self.to_end_cols = jnp.exp(self.a_cols[q - 1:q, :] - self.a_cols)
        self.w_cols = self.to_end_cols * _columns(self.dt_rows)
        self.ea_cols = jnp.exp(self.a_cols)
        # exp(a_Q), what the chunk keeps of the entering state; a head's,
        # ``keep_ref[h:h + 1, :]``, is read back as a row over the state's
        # lanes (Mosaic broadcasts along one axis at a time, and folds a
        # broadcast of a broadcast's slice into one along both)
        self.keep_rows = jnp.exp(self.a_rows[:, q - 1:q])      # [hg, 1]
        keep_ref[...] = jnp.broadcast_to(self.keep_rows, keep_ref.shape)
        self.g = self.dot(self.c, self.b, _NT)                 # [Q, Q]
        self.causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                       >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        tile = max(head_dim, _LANES)
        self.seg = jax.lax.broadcasted_iota(
            jnp.int32, (q, tile), 1) // head_dim

    def decay(self, h):
        """``exp(a_i - a_j)`` at and below the diagonal, 0 above: the mask
        goes on BEFORE the exp (above the diagonal the difference is
        positive and may overflow)."""
        return jnp.exp(jnp.where(
            self.causal,
            self.a_cols[:, h:h + 1] - self.a_rows[h:h + 1, :], -jnp.inf))


def _tiles(hg, head_dim):
    """``(lanes, heads)`` of each tile of a group's x: ``128 / P`` heads a
    128-lane tile, or one head of several tiles."""
    tile = max(head_dim, _LANES)
    per = tile // head_dim
    return [(slice(t * tile, (t + 1) * tile),
             list(range(t * per, (t + 1) * per)))
            for t in range(hg * head_dim // tile)]


# ----------------------------------------------------------------- forward --

def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, dta_ref, d_ref, y_ref, s_ref,
                state, keep, *, hg, head_dim):
    p = head_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    ch = _Chunk(b_ref, c_ref, dt_ref, dta_ref, keep, p)
    dtype, dot = x_ref.dtype, ch.dot
    s = state[...]                                             # [hg P, N]
    s_ref[0, 0] = s
    from_state = dot(ch.c, s.astype(dtype), _NT)               # [Q, hg P]
    for lanes, heads in _tiles(hg, p):
        xt = x_ref[0, :, lanes]                                # [Q, tile]
        x32 = xt.astype(jnp.float32)
        own = [dot((ch.g * ch.decay(h) * ch.dt_rows[h:h + 1, :])
                   .astype(dtype), xt, _NN) for h in heads]
        y = _pick(own, ch.seg) \
            + _spread(ch.ea_cols, heads, ch.seg) * from_state[:, lanes]
        y = y + d_ref[:, lanes] * x32
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        # what the chunk adds to its heads' states, a tile's heads at once
        xw = (x32 * _spread(ch.w_cols, heads, ch.seg)).astype(dtype)
        add = dot(xw, ch.b, _TN)                               # [tile, N]
        for k, h in enumerate(heads):
            rows = slice(h * p, (h + 1) * p)
            state[rows, :] = keep[h:h + 1, :] * s[rows, :] \
                + add[k * p:(k + 1) * p, :]


def _specs(hg, head_dim, state, chunk, at):
    """The blocks both kernels share, by ``at(c)``, the chunk a grid step
    along the last axis holds: x-shaped ``[B, T, heads P]``, B/C-shaped
    ``[B, T, groups N]``, the step sizes' rows ``[B, heads, T]``, ``D``
    over x's lanes ``[1, heads P]`` and the states ``[B, chunks, heads P,
    N]``."""
    wide = hg * head_dim
    return dict(
        x=pl.BlockSpec((1, chunk, wide), lambda b, g, c: (b, at(c), g)),
        bc=pl.BlockSpec((1, chunk, state), lambda b, g, c: (b, at(c), g)),
        rows=pl.BlockSpec((1, hg, chunk), lambda b, g, c: (b, g, at(c))),
        d=pl.BlockSpec((1, wide), lambda b, g, c: (0, g)),
        states=pl.BlockSpec((1, 1, wide, state),
                            lambda b, g, c: (b, at(c), g, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


# Both launches are ``jit(inline=True)``: a step's Mamba blocks call them
# with the same shapes, and jit's cache then traces the kernel body (a
# thousand ops, unrolled over a group's heads) ONCE a step and not once a
# block; inlined, each call still lowers under its own block's scopes.
_launch = functools.partial(jax.jit, inline=True,
                            static_argnames=("dims", "chunk", "interpret"))


@_launch
def _ssd_fwd(x, b, c, dt_rows, dta_rows, d_lanes, dims, chunk, interpret):
    heads, head_dim, groups, state = dims
    batch, seq, _ = x.shape
    hg, nc = heads // groups, seq // chunk
    sp = _specs(hg, head_dim, state, chunk, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hg=hg, head_dim=head_dim),
        name="ssd_scan_fwd",
        grid=(batch, groups, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["rows"], sp["rows"],
                  sp["d"]],
        out_specs=[sp["x"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, nc, heads * head_dim, state), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hg * head_dim, state), jnp.float32),
                        pltpu.VMEM((hg, state), jnp.float32)],
        interpret=interpret, compiler_params=_PARAMS,
    )(x, b, c, dt_rows, dta_rows, d_lanes)


# ---------------------------------------------------------------- backward --

def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, dta_ref, d_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, ddta_ref, dd_ref, dstate,
                keep, *, hg, head_dim):
    """One (batch, group, chunk) step, the chunks from the last to the
    first.  With dS' the gradient of the state the chunk leaves (carried),
    ``dM_h = dy_h x_h^T`` and ``K_h = dM_h * exp(a_i - a_j)`` (masked):

        dx_h  = M_h^T dy_h + w * (B dS_h'^T) + D_h dy_h
        dG    = sum_h K_h * dt_j
        dC    = dG B + (dy exp a) S;   dB = dG^T C + (x w) dS'
        dS_h  = exp(a_Q) dS_h' + (dy_h exp a)^T C
        r_j   = x_j . (B dS_h'^T)_j
        ddt_j = colsum(K_h * G)_j + r_j exp(a_Q - a_j)
        da_k  = dy_k . (y_k - D x_k) - colsum(dM_h * M_h)_k - r_k w_k
        da_Q += exp(a_Q) <dS_h', S_h> + sum_j r_j w_j

    (``rowsum(dM * M)_i`` is ``dy_i . (M x)_i``: the chunk's own output
    again, a matmul, and no sum along the lanes of a ``[Q, Q]`` matrix.)
    The gradient of ``dt A`` is the running sum of ``da`` from the chunk's
    end down."""
    p = head_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, jnp.float32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

    ch = _Chunk(b_ref, c_ref, dt_ref, dta_ref, keep, p)
    dtype, dot = x_ref.dtype, ch.dot
    f32 = jnp.float32
    q, n = ch.b.shape
    s, ds_out = s_ref[0, 0], dstate[...]                       # [hg P, N]
    s_low, ds_low = s.astype(dtype), ds_out.astype(dtype)
    from_state = dot(ch.c, s_low, _NT)                         # C S^T
    to_state = dot(ch.b, ds_low, _NT)                          # B dS'^T
    dg = jnp.zeros((q, q), f32)
    db, dc = jnp.zeros((q, n), f32), jnp.zeros((q, n), f32)
    # sums over a head's lanes, as rows [hg, Q]: colsum(K * G); colsum(dM *
    # M); dy . (y - D x); r exp(a_Q - a_j); and <dS', S> along the state's
    # lanes
    col_rows = jnp.zeros((hg, q), f32)
    pair_rows, out_rows, end_rows = col_rows, col_rows, col_rows
    through = jnp.zeros((hg, n), f32)
    for lanes, heads in _tiles(hg, p):
        xt, dyt = x_ref[0, :, lanes], dy_ref[0, :, lanes]      # [Q, tile]
        x32, dy32 = xt.astype(f32), dyt.astype(f32)
        own, dx = [], d_ref[:, lanes] * dy32
        for k, h in enumerate(heads):
            dy_h = dyt if len(heads) == 1 else \
                jnp.where(ch.seg == k, dyt, jnp.zeros_like(dyt))
            decay = ch.decay(h)
            dt_row = ch.dt_rows[h:h + 1, :]
            m = (ch.g * decay * dt_row).astype(dtype)
            own.append(dot(m, xt, _NN))
            dx = dx + dot(m, dy_h, _TN)
            dm = dot(dy_h, xt, _NT)                            # [Q, Q]
            kh = dm * decay
            dg = dg + kh * dt_row
            col_rows = _set_row(col_rows, h, jnp.sum(
                kh * ch.g, axis=0, keepdims=True))
            # colsum(dM * M) with M AS THE MATMULS TOOK IT (rounded to x's
            # dtype): rowsum(dM * M) is dy . (M x) below, and in the running
            # sum of da the two cancel pair by pair but for the pairs that
            # straddle a position; with one side rounded and the other not,
            # what is left of 8,000 pairs' rounding drowns the few that count
            pair_rows = _set_row(pair_rows, h, jnp.sum(
                dm * m.astype(f32), axis=0, keepdims=True))
        ea = _spread(ch.ea_cols, heads, ch.seg)
        w = _spread(ch.w_cols, heads, ch.seg)
        dx = dx + w * to_state[:, lanes]
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dd_ref[0, :, lanes] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
        dy_ea = (dy32 * ea).astype(dtype)
        dc = dc + dot(dy_ea, s_low[lanes, :], _NN)
        db = db + dot((x32 * w).astype(dtype), ds_low[lanes, :], _NN)
        grow = dot(dy_ea, ch.c, _TN)                           # [tile, N]
        out_t = (dy32 * (_pick(own, ch.seg) + ea * from_state[:, lanes])).T
        end_t = (x32 * to_state[:, lanes]
                 * _spread(ch.to_end_cols, heads, ch.seg)).T   # [tile, Q]
        for k, h in enumerate(heads):
            part, rows = slice(k * p, (k + 1) * p), slice(h * p, (h + 1) * p)
            out_rows = _set_row(out_rows, h, jnp.sum(
                out_t[part, :], axis=0, keepdims=True))
            end_rows = _set_row(end_rows, h, jnp.sum(
                end_t[part, :], axis=0, keepdims=True))
            through = _set_row(through, h, jnp.sum(
                ds_out[rows, :] * s[rows, :], axis=0, keepdims=True))
            dstate[rows, :] = keep[h:h + 1, :] * ds_out[rows, :] \
                + grow[part, :]
    dg = dg.astype(dtype)
    dc_ref[0] = (dc + dot(dg, ch.b, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (db + dot(dg, ch.c, _TN)).astype(db_ref.dtype)
    ddt_ref[0] = col_rows + end_rows
    weighed = end_rows * ch.dt_rows                            # r_j w_j
    da_last = ch.keep_rows * jnp.sum(through, axis=1, keepdims=True) \
        + jnp.sum(weighed, axis=1, keepdims=True)              # [hg, 1]
    ddta_ref[0] = _running_sum(
        out_rows - pair_rows - weighed, reverse=True) + da_last


@_launch
def _ssd_bwd(x, b, c, dt_rows, dta_rows, d_lanes, states, dy, dims, chunk,
             interpret):
    heads, head_dim, groups, state = dims
    batch, seq, _ = x.shape
    hg, nc = heads // groups, seq // chunk
    sp = _specs(hg, head_dim, state, chunk, lambda c: nc - 1 - c)
    d_sum = pl.BlockSpec((1, 1, hg * head_dim), lambda b, g, c: (b, 0, g))
    rows = jax.ShapeDtypeStruct(dt_rows.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hg=hg, head_dim=head_dim),
        name="ssd_scan_bwd",
        grid=(batch, groups, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["rows"], sp["rows"],
                  sp["d"], sp["states"], sp["x"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["rows"], sp["rows"],
                   d_sum],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), rows, rows,
                   jax.ShapeDtypeStruct((batch, 1, heads * head_dim),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hg * head_dim, state), jnp.float32),
                        pltpu.VMEM((hg, state), jnp.float32)],
        interpret=interpret, compiler_params=_PARAMS,
    )(x, b, c, dt_rows, dta_rows, d_lanes, states, dy)


# ------------------------------------------------------------- public API --

def _rows_and_lanes(dt, a_head, d_head, head_dim):
    """The small operands as the kernels read them: the step sizes and
    ``dt A`` as rows ``[B, heads, T]``, ``D`` over x's lanes."""
    dt_rows = dt.transpose(0, 2, 1)
    return dt_rows, dt_rows * a_head[:, None], \
        jnp.repeat(d_head, head_dim)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd_scan_kernels(x, dt, a_head, b, c, d_head, dims, chunk, interpret):
    return _fwd_rule(x, dt, a_head, b, c, d_head, dims, chunk, interpret)[0]


def _fwd_rule(x, dt, a_head, b, c, d_head, dims, chunk, interpret):
    y, states = _ssd_fwd(x, b, c, *_rows_and_lanes(dt, a_head, d_head,
                                                   dims[1]),
                         dims, chunk, interpret)
    return y, (x, dt, a_head, b, c, d_head, states)


def _bwd_rule(dims, chunk, interpret, res, dy):
    x, dt, a_head, b, c, d_head, states = res
    dt_rows, dta_rows, d_lanes = _rows_and_lanes(dt, a_head, d_head, dims[1])
    dx, db, dc, ddt_rows, ddta_rows, dd = _ssd_bwd(
        x, b, c, dt_rows, dta_rows, d_lanes, states, dy, dims, chunk,
        interpret)
    ddt = (ddt_rows + a_head[:, None] * ddta_rows).transpose(0, 2, 1)
    return (dx, ddt, jnp.sum(dt_rows * ddta_rows, axis=(0, 2)), db, dc,
            jnp.sum(dd.reshape(-1, dims[0], dims[1]), axis=(0, 2)))


_ssd_scan_kernels.defvjp(_fwd_rule, _bwd_rule)


def _engine_cases(engine):
    """The serving engine launches none of this (``models/nemotron_h.py``
    trains and has no decode path); the lint sweeps one training-shaped
    case, forward and backward, at the published heads (64 wide, 16 a
    group, state 128, chunk 128) in the engine's dtype."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    x = sds((1, 512, 32, 64), engine.dtype)
    bc = sds((1, 512, 2, 128), engine.dtype)

    def vjp(x, dt, a, b, c, d):
        def loss(*o):
            return jnp.sum(ssd_scan_pallas(*o, chunk=128).astype(f32))
        return jax.grad(loss, argnums=tuple(range(6)))(x, dt, a, b, c, d)

    yield registry.KernelCase(
        "vjp[s512,h32x64,g2,n128,c128]", vjp,
        (x, sds((1, 512, 32), f32), sds((32,), f32), bc, bc,
         sds((32,), f32)), None)


@registry.register_kernel(
    "ssd_scan",
    fallback="paddle_tpu.nn.functional:_ssd_scan_rows",
    parity="tests/test_ssd_scan_kernel.py::test_kernels_match_the_"
           "composition",
    engine_shapes=_engine_cases,
    supports=supports,
    grad=True)
def ssd_scan_pallas(x, dt, A, B, C, D, chunk=128, interpret=False):
    """``x [batch, T, heads, P]``, ``dt [batch, T, heads]``, ``A``, ``D``
    ``[heads]``, ``B``, ``C`` ``[batch, T, groups, N]``, ``T`` a whole
    number of chunks: ``nn/functional.py ssd_scan``'s function, ``y`` in
    x's dtype; differentiable in all six."""
    batch, seq, heads, head_dim = x.shape
    groups, state = B.shape[2:]
    if B.dtype != x.dtype or C.dtype != x.dtype or not supports(
            seq, heads, head_dim, groups, state, chunk, x.dtype):
        raise ValueError(
            f"ssd scan does not serve x{tuple(x.shape)} {x.dtype} "
            f"B{tuple(B.shape)} {B.dtype} chunk={chunk}: see "
            f"ssd_scan_kernel.supports")
    f32 = jnp.float32
    y = _ssd_scan_kernels(
        x.reshape(batch, seq, heads * head_dim), dt.astype(f32),
        A.astype(f32), B.reshape(batch, seq, groups * state),
        C.reshape(batch, seq, groups * state), D.astype(f32),
        (heads, head_dim, groups, state), chunk, interpret)
    return y.reshape(x.shape)
