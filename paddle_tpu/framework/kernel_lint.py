"""Static Pallas kernel verifier: tiling, VMEM, bounds, races, contract.

The jaxpr analyses next door (:mod:`paddle_tpu.framework.analysis`) and
the cost model (:mod:`paddle_tpu.framework.cost`) stop at the XLA graph
boundary: a ``pallas_call`` equation is opaque to them, yet it is where
the TPU-specific failure modes live — a block shape Mosaic cannot tile,
a per-step working set that overflows VMEM, an index map that DMAs past
the end of the array, an output revisited after the grid moved on.  All
of those surface only on real hardware, while the dev loop runs on CPU
in interpret mode where none of them reproduce.  This module closes the
gap: it traces a callable with ``jax.make_jaxpr`` over abstract
``ShapeDtypeStruct`` args (nothing executes, no cache warms), walks the
jaxpr for ``pallas_call`` equations, and verifies each kernel's grid,
block specs, index maps, and scratch shapes statically.

Rule catalog (Findings in the analysis.py style; docs/ANALYSIS.md):

- **K001 tiling** — for every rank>=2 input/output block: the lane
  (last) dim must be a multiple of 128 or the full array dim; the
  sublane (second-minor) dim must be 1, the full dim, or a multiple of
  the dtype minimum (f32/i32: 8, bf16: 16, int8: 32); every block dim
  must divide its array dim (the ``pick_block`` contract — the kernels
  here address partial work by masking inside full blocks, never by
  edge blocks); and, per output, the grid must cover every block of the
  array (enumerated over the index map when that is concretely
  evaluable).
- **K002 VMEM residency** — per grid step the kernel holds every
  input/output block twice (Pallas double-buffers the DMAs) plus its
  scratch once; the total is checked against the ``vmem_bytes`` entry
  of the device profiles in :mod:`paddle_tpu.framework.cost` (Mosaic's
  default allowance), or against the ``vmem_limit_bytes`` the call itself
  asks of Mosaic where that is more (the flash backward keeps a q head's
  Q, dO and float32 dQ whole and reckons its allowance from its blocks;
  past the chip's physical VMEM Mosaic refuses the compile itself), and
  the finding names the binding buffer.  :func:`estimate_residency` /
  :func:`vmem_fits` expose the same model to a caller that sizes its
  own blocks, so a block that overflows VMEM is seen before it is ever
  compiled.
- **K003 bounds** — interval analysis over each block's index map
  evaluated symbolically for all grid indices (grid axis ``i`` is the
  interval ``[0, grid[i] - 1]``; scalar-prefetch reads take their
  declared ``scalar_bounds``), proving the returned *block* index lies
  in ``[0, ceil(dim / block) - 1]`` per dim — the classic
  ``block_k * j`` overrun when the sequence is not divisible.  The same
  interval engine then walks the kernel body and checks every
  ``pl.ds``/indexed ref access whose offsets are affine in
  ``program_id`` against the block extents.  Unsupported arithmetic
  makes a spec *unverifiable*, never a false positive: the analysis
  silently skips what it cannot bound (loop-carried offsets, data
  -dependent gathers).
- **K004 write races** — an output index map that revisits a block
  after the (sequential, last-axis-fastest) TPU grid has left it:
  revisits within one contiguous run are the standard accumulate-in
  -place idiom (the block stays resident), but a non-contiguous revisit
  means the block was flushed and is silently overwritten —
  last-writer-wins on TPU, while interpret mode sees every write, so
  the bug hides exactly where tests run.
- **K005 registry contract** — every module under ``ops/pallas/`` that
  issues a ``pallas_call`` must register its entry point via
  ``@register_kernel`` (:mod:`paddle_tpu.ops.pallas.registry`), and
  every registered kernel must declare a resolvable XLA fallback and an
  interpret-mode parity test that actually exists in the named test
  file.  :func:`lint_registry` then sweeps every entry over the shapes
  the serving engine really launches (``engine_shapes`` built from the
  same ``_bucket_grid()`` warmup walks), which is what
  ``graph-lint kernels`` runs.

Nothing in here executes a kernel; ``analyze_kernel`` on an engine's
shapes leaves the engine's executable caches exactly as cold as it
found them (the same AOT discipline as ``analyze_engine`` — tested).
"""

import ast
import itertools
import os
import re

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax.extend import core as jcore

from .analysis import ERROR, WARNING, Finding, _raw, _subjaxprs, _want, \
    walk_jaxprs
from .cost import DEVICE_PROFILES

__all__ = [
    "BlockInfo", "KernelInfo", "introspect_kernels", "analyze_kernel",
    "check_registry", "lint_registry", "estimate_residency", "vmem_fits",
    "KERNEL_RULES",
]

KERNEL_RULES = ("K001", "K002", "K003", "K004", "K005")

_LANE = 128
# minimum sublane tile by dtype itemsize (pallas guide: f32 (8, 128),
# bf16 (16, 128), int8/fp8 (32, 128))
_MIN_SUBLANE = {4: 8, 2: 16, 1: 32}
# index-map enumeration cap: beyond this many grid steps the coverage
# and race checks are skipped (never reported) rather than estimated
_MAX_ENUM = 65536


# --------------------------------------------------------------------------
# introspection: pallas_call eqn -> KernelInfo
# --------------------------------------------------------------------------
class BlockInfo:
    """One BlockSpec as seen by the lowered ``pallas_call``."""

    __slots__ = ("origin", "block_shape", "array_shape", "dtype",
                 "index_map", "is_output")

    def __init__(self, origin, block_shape, array_shape, dtype, index_map,
                 is_output):
        self.origin = origin
        self.block_shape = block_shape
        self.array_shape = array_shape
        self.dtype = dtype
        self.index_map = index_map          # ClosedJaxpr or None
        self.is_output = is_output

    def __repr__(self):
        kind = "out" if self.is_output else "in"
        return (f"BlockInfo({self.origin} [{kind}] block="
                f"{self.block_shape} of {self.array_shape})")


class KernelInfo:
    """Everything the rules need about one ``pallas_call``."""

    __slots__ = ("name", "grid", "blocks", "scratch", "num_prefetch",
                 "body", "vmem_limit")

    def __init__(self, name, grid, blocks, scratch, num_prefetch, body,
                 vmem_limit=None):
        self.name = name
        self.grid = grid                    # tuple of ints
        self.blocks = blocks                # list[BlockInfo], ins then outs
        self.scratch = scratch              # list[(shape, dtype)]
        self.num_prefetch = num_prefetch
        self.body = body                    # raw kernel jaxpr
        self.vmem_limit = vmem_limit        # the call's own, or None

    def __repr__(self):
        return (f"KernelInfo({self.name} grid={self.grid} "
                f"{len(self.blocks)} blocks, {len(self.scratch)} scratch)")


def _ref_shape_dtype(aval):
    inner = getattr(aval, "inner_aval", aval)
    return tuple(inner.shape), inner.dtype


def _kernel_info(eqn):
    gm = eqn.params["grid_mapping"]
    try:
        grid = tuple(int(g) for g in gm.grid)
    except (TypeError, ValueError):
        return None                         # dynamic grid: out of scope
    num_in = gm.num_inputs
    num_prefetch = gm.num_index_operands
    body = _raw(eqn.params["jaxpr"])
    # the kernel's own parameter names (``x_ref``) are the origins the
    # findings quote; BlockMapping.origin is only positional (args[0])
    names = body.debug_info.arg_names
    blocks = []
    for idx, bm in enumerate(gm.block_mappings):
        sds = bm.array_aval
        # Blocked/Element dims carry block_size; a Squeezed dim is 1 wide
        bs = tuple(int(getattr(x, "block_size", 1))
                   for x in bm.block_shape)
        blocks.append(BlockInfo(
            str(names[num_prefetch + idx]), bs, tuple(sds.shape),
            sds.dtype, bm.index_map_jaxpr, idx >= num_in))
    scratch = []
    for v in body.invars[num_prefetch + len(blocks):]:
        scratch.append(_ref_shape_dtype(v.aval))
    name = eqn.params.get("name") or "pallas_call"
    mosaic = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    return KernelInfo(name, grid, blocks, scratch, num_prefetch, body,
                      getattr(mosaic, "vmem_limit_bytes", None))


def introspect_kernels(fn, *args):
    """Trace ``fn(*args)`` abstractly and return a :class:`KernelInfo`
    per ``pallas_call`` found anywhere in the jaxpr (custom_vjp
    backward kernels included when ``fn`` itself differentiates)."""
    closed = jax.make_jaxpr(fn)(*args)
    kernels = []
    for _path, j in walk_jaxprs(closed):
        for eqn in j.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            ki = _kernel_info(eqn)
            if ki is not None:
                kernels.append(ki)
    return kernels


# --------------------------------------------------------------------------
# interval arithmetic over index-map / body jaxprs
# --------------------------------------------------------------------------
class _Ival:
    """Closed integer interval [lo, hi]."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo, hi):
        self.lo = int(lo)
        self.hi = int(hi)

    @property
    def exact(self):
        return self.lo == self.hi

    def __repr__(self):
        return f"[{self.lo}, {self.hi}]"


def _binop(name, a, b):
    if a is None or b is None:
        return None
    if name == "add":
        return _Ival(a.lo + b.lo, a.hi + b.hi)
    if name == "sub":
        return _Ival(a.lo - b.hi, a.hi - b.lo)
    if name == "mul":
        c = (a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi)
        return _Ival(min(c), max(c))
    if name == "max":
        return _Ival(max(a.lo, b.lo), max(a.hi, b.hi))
    if name == "min":
        return _Ival(min(a.lo, b.lo), min(a.hi, b.hi))
    if name in ("div", "floor_divide"):
        # trunc == floor on the non-negative quadrant; anything signed
        # is left unverified rather than guessed
        if a.lo >= 0 and b.lo > 0:
            return _Ival(a.lo // b.hi, a.hi // b.lo)
        return None
    if name == "rem":
        if b.exact and b.lo > 0 and a.lo >= 0:
            if a.hi < b.lo:
                return _Ival(a.lo, a.hi)
            return _Ival(0, b.lo - 1)
        return None
    return None


_IDENTITY_PRIMS = frozenset((
    "convert_element_type", "squeeze", "reshape", "broadcast_in_dim",
    "copy", "stop_gradient",
))
_BIN_PRIMS = frozenset(("add", "sub", "mul", "max", "min", "div",
                        "floor_divide", "rem"))


class _IntervalEval:
    """Forward interval propagation for scalar integer arithmetic.

    ``env`` maps jaxpr Vars to :class:`_Ival` (absent = unknown);
    anything the table does not cover poisons its outputs to unknown,
    so the analysis is sound-but-incomplete by construction.
    """

    def __init__(self, grid=(), prefetch_bounds=None, prefetch_vars=()):
        self.grid = tuple(grid)
        self.bounds = prefetch_bounds or {}
        self.prefetch_pos = {v: i for i, v in enumerate(prefetch_vars)}
        self.env = {}

    def read(self, v):
        if isinstance(v, jcore.Literal):
            val = v.val
            try:
                val = val.item()
            except AttributeError:
                pass
            if isinstance(val, (bool, int)):
                return _Ival(int(val), int(val))
            return None
        return self.env.get(v)

    def _set(self, eqn, ival):
        for out in eqn.outvars:
            if ival is None:
                self.env.pop(out, None)
            else:
                self.env[out] = ival

    def eqn(self, eqn):
        name = eqn.primitive.name
        if name == "program_id":
            ax = eqn.params.get("axis", 0)
            hi = self.grid[ax] - 1 if ax < len(self.grid) else 0
            self._set(eqn, _Ival(0, max(hi, 0)))
        elif name == "num_programs":
            ax = eqn.params.get("axis", 0)
            n = self.grid[ax] if ax < len(self.grid) else 1
            self._set(eqn, _Ival(n, n))
        elif name == "get" and eqn.invars[0] in self.prefetch_pos:
            pos = self.prefetch_pos[eqn.invars[0]]
            b = self.bounds.get(pos)
            self._set(eqn, _Ival(*b) if b is not None else None)
        elif name in _BIN_PRIMS:
            self._set(eqn, _binop(name, self.read(eqn.invars[0]),
                                  self.read(eqn.invars[1])))
        elif name == "neg":
            a = self.read(eqn.invars[0])
            self._set(eqn, _Ival(-a.hi, -a.lo) if a else None)
        elif name == "clamp":
            lo, x, hi = (self.read(v) for v in eqn.invars)
            if x is None:
                self._set(eqn, None)
            else:
                clo = max(x.lo, lo.lo) if lo else x.lo
                chi = min(x.hi, hi.hi) if hi else x.hi
                self._set(eqn, _Ival(min(clo, chi), chi))
        elif name == "select_n":
            cases = [self.read(v) for v in eqn.invars[1:]]
            if all(c is not None for c in cases):
                self._set(eqn, _Ival(min(c.lo for c in cases),
                                     max(c.hi for c in cases)))
            else:
                self._set(eqn, None)
        elif name in _IDENTITY_PRIMS:
            self._set(eqn, self.read(eqn.invars[0]))
        else:
            self._set(eqn, None)


def _eval_index_map(block, grid_ivals, scalar_bounds):
    """Evaluate a block's index map over grid-index intervals.

    Returns a list with one :class:`_Ival` (or None = unverifiable) per
    output dim, or None when there is no index map to evaluate.
    """
    closed = block.index_map
    if closed is None:
        return None
    j = _raw(closed)
    ngrid = len(grid_ivals)
    ev = _IntervalEval(grid=[iv.hi + 1 for iv in grid_ivals],
                       prefetch_bounds=scalar_bounds,
                       prefetch_vars=j.invars[ngrid:])
    for v, iv in zip(j.invars[:ngrid], grid_ivals):
        ev.env[v] = iv
    consts = getattr(closed, "consts", ())
    for cv, cval in zip(getattr(j, "constvars", ()), consts):
        try:
            ev.env[cv] = _Ival(int(cval), int(cval))
        except (TypeError, ValueError):
            pass
    for eqn in j.eqns:
        ev.eqn(eqn)
    return [ev.read(v) for v in j.outvars]


def _enumerate_output_blocks(block, grid, scalar_bounds):
    """Concrete (step, block_tuple) walk of an output's index map over
    the sequential grid (row-major: last axis fastest, the TPU order).

    Returns None when the map depends on unverifiable values (prefetch
    reads without exact bounds, unsupported arithmetic) or the grid
    exceeds the enumeration cap.
    """
    total = 1
    for g in grid:
        total *= max(g, 1)
    if total > _MAX_ENUM:
        return None
    steps = []
    for t, point in enumerate(itertools.product(
            *(range(max(g, 1)) for g in grid))):
        ivals = _eval_index_map(
            block, [_Ival(p, p) for p in point], scalar_bounds)
        if ivals is None or any(iv is None or not iv.exact
                                for iv in ivals):
            return None
        steps.append((t, tuple(iv.lo for iv in ivals)))
    return steps


# --------------------------------------------------------------------------
# K001 — tiling / divisibility / coverage
# --------------------------------------------------------------------------
def _check_tiling(ki, loc, scalar_bounds, findings):
    for b in ki.blocks:
        bs, ash = b.block_shape, b.array_shape
        if len(bs) != len(ash):
            continue
        for d, (x, n) in enumerate(zip(bs, ash)):
            if x > 0 and n % x:
                findings.append(Finding(
                    "K001", ERROR, loc,
                    f"block dim {x} does not divide array dim {n} along "
                    f"axis {d} of {b.origin} {ash}: partial edge blocks "
                    f"are unsupported here (pick_block returns a "
                    f"dividing block or None — mask inside full blocks "
                    f"instead)", category="divisibility"))
        if len(bs) < 2:
            continue                        # rank-1 blocks (scalars rails)
        lane, n_lane = bs[-1], ash[-1]
        if lane % _LANE and lane != n_lane:
            findings.append(Finding(
                "K001", ERROR, loc,
                f"block {bs} on {b.origin} {ash}: lane dim {lane} is "
                f"neither a multiple of {_LANE} nor the full array dim "
                f"{n_lane} — Mosaic cannot tile it", category="lane"))
        sub, n_sub = bs[-2], ash[-2]
        ms = _MIN_SUBLANE.get(jnp.dtype(b.dtype).itemsize, 8)
        if sub not in (1, n_sub) and sub % ms:
            findings.append(Finding(
                "K001", ERROR, loc,
                f"block {bs} on {b.origin} {ash}: sublane dim {sub} is "
                f"not 1, not the full dim {n_sub}, and not a multiple "
                f"of the {jnp.dtype(b.dtype).name} minimum {ms}",
                category="sublane"))
    # coverage: the grid must write every block of every output
    for b in ki.blocks:
        if not b.is_output or len(b.block_shape) != len(b.array_shape):
            continue
        steps = _enumerate_output_blocks(b, ki.grid, scalar_bounds)
        if steps is None:
            continue
        expected = 1
        for x, n in zip(b.block_shape, b.array_shape):
            expected *= max(-(-n // x) if x else 1, 1)
        seen = {tpl for _t, tpl in steps}
        if len(seen) < expected:
            findings.append(Finding(
                "K001", ERROR, loc,
                f"grid {ki.grid} writes only {len(seen)} of the "
                f"{expected} blocks of output {b.origin} "
                f"{b.array_shape} (block {b.block_shape}) — uncovered "
                f"blocks keep uninitialized HBM", category="coverage"))


# --------------------------------------------------------------------------
# K002 — per-grid-step VMEM residency
# --------------------------------------------------------------------------
def _nbytes(shape, dtype):
    n = jnp.dtype(dtype).itemsize
    for d in shape:
        n *= max(int(d), 1)
    return n


def estimate_residency(blocks, scratch=()):
    """Per-grid-step VMEM bytes for ``blocks``/``scratch`` given as
    iterables of ``(shape, dtype)``: each in/out block counts twice
    (Pallas double-buffers the block DMAs), scratch lives once."""
    return (2 * sum(_nbytes(s, dt) for s, dt in blocks)
            + sum(_nbytes(s, dt) for s, dt in scratch))


def _vmem_limit(profile):
    p = DEVICE_PROFILES[profile] if isinstance(profile, str) else profile
    return p.get("vmem_bytes")


def vmem_fits(blocks, scratch=(), profile="tpu-v4"):
    """True when the residency model fits the profile's VMEM budget
    (profiles without a budget pass)."""
    limit = _vmem_limit(profile)
    return limit is None or estimate_residency(blocks, scratch) <= limit


def _check_vmem(ki, loc, profile, findings):
    limit = _vmem_limit(profile)
    if not limit:
        return
    limit = max(limit, ki.vmem_limit or 0)
    contributors = [(2 * _nbytes(b.block_shape, b.dtype),
                     f"{b.origin} block {b.block_shape} (x2 double-buffer)")
                    for b in ki.blocks]
    contributors += [(_nbytes(s, dt), f"scratch {s}")
                     for s, dt in ki.scratch]
    total = sum(nb for nb, _ in contributors)
    if total <= limit // 2:
        return
    bind_bytes, bind_desc = max(contributors, key=lambda c: c[0])
    sev = ERROR if total > limit else WARNING
    verb = "overflows" if sev == ERROR else "uses more than half of"
    findings.append(Finding(
        "K002", sev, loc,
        f"per-grid-step residency {total} B {verb} the "
        f"{limit} B VMEM budget; binding buffer: {bind_desc} = "
        f"{bind_bytes} B", category="residency"))


# --------------------------------------------------------------------------
# K003 — out-of-bounds proof (index maps + body pl.ds offsets)
# --------------------------------------------------------------------------
def _check_bounds(ki, loc, scalar_bounds, findings):
    grid_ivals = [_Ival(0, max(g - 1, 0)) for g in ki.grid]
    for b in ki.blocks:
        if len(b.block_shape) != len(b.array_shape):
            continue
        ivals = _eval_index_map(b, grid_ivals, scalar_bounds)
        if ivals is None:
            continue
        for d, iv in enumerate(ivals):
            if iv is None:
                continue                    # unverifiable dim: skip
            x, n = b.block_shape[d], b.array_shape[d]
            nb = max(-(-n // x) if x else 1, 1)
            if iv.lo < 0 or iv.hi > nb - 1:
                findings.append(Finding(
                    "K003", ERROR, loc,
                    f"index_map of {b.origin} reaches block index "
                    f"{iv} along dim {d}, valid range [0, {nb - 1}] "
                    f"(array {n} / block {x}) — out-of-bounds DMA "
                    f"(the block_k*j overrun class)",
                    category="index-map"))
    _check_body_ds(ki, loc, scalar_bounds, findings)


def _leaf_ival(leaf, ev):
    if isinstance(leaf, int):
        return _Ival(leaf, leaf)
    if isinstance(leaf, jcore.Literal):
        return ev.read(leaf)
    if isinstance(leaf, jcore.Var):
        if getattr(leaf.aval, "shape", None) != ():
            return None                     # array indexer: skip
        return ev.env.get(leaf)
    return None


def _check_indexer(eqn, ev, ref_shape, loc, findings):
    nskip = 1 if eqn.primitive.name == "get" else 2
    tree = eqn.params.get("tree")
    if tree is None:
        return
    try:
        indexers = jtu.tree_unflatten(tree, list(eqn.invars[nskip:]))
    except Exception:
        return
    for nd in indexers:
        indices = getattr(nd, "indices", None)
        if indices is None:
            continue
        shape = tuple(getattr(nd, "shape", ref_shape))
        for d, (ix, n) in enumerate(zip(indices, shape)):
            if hasattr(ix, "start"):        # pl.ds / pl.Slice
                size = ix.size
                stride = getattr(ix, "stride", 1) or 1
                if not isinstance(size, int):
                    continue
                iv = _leaf_ival(ix.start, ev)
                if iv is None:
                    continue
                last = iv.hi + (size - 1) * stride
                if iv.lo < 0 or last > n - 1:
                    findings.append(Finding(
                        "K003", ERROR, loc,
                        f"{eqn.primitive.name} slice "
                        f"ds(start={iv}, size={size}) along dim {d} "
                        f"reaches element {last} of a {n}-long ref dim "
                        f"— reads past the block", category="body-ds"))
            else:
                iv = _leaf_ival(ix, ev)
                if iv is None:
                    continue
                if iv.lo < 0 or iv.hi > n - 1:
                    findings.append(Finding(
                        "K003", ERROR, loc,
                        f"{eqn.primitive.name} index {iv} along dim "
                        f"{d} outside the {n}-long ref dim",
                        category="body-index"))


def _check_body_ds(ki, loc, scalar_bounds, findings):
    body = ki.body
    if body is None:
        return
    nblocks = len(ki.blocks)
    ev = _IntervalEval(grid=ki.grid, prefetch_bounds=scalar_bounds,
                       prefetch_vars=body.invars[:ki.num_prefetch])
    refshapes = {}
    for i, v in enumerate(body.invars[:ki.num_prefetch]):
        refshapes[v] = _ref_shape_dtype(v.aval)[0]
    for i, b in enumerate(ki.blocks):
        refshapes[body.invars[ki.num_prefetch + i]] = b.block_shape
    for i, (s, _dt) in enumerate(ki.scratch):
        refshapes[body.invars[ki.num_prefetch + nblocks + i]] = s

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("get", "swap", "addupdate") \
                    and eqn.invars[0] in refshapes:
                try:
                    _check_indexer(eqn, ev, refshapes[eqn.invars[0]],
                                   loc, findings)
                except Exception:
                    pass                    # unverifiable indexer shape
            if name == "cond":
                # pl.when lowers here; branch invars alias the cond's
                # trailing operands, so intervals and ref shapes flow
                # through — other higher-order prims (scan loop
                # carries) stay unknown by design
                ops = eqn.invars[1:]
                for br in eqn.params.get("branches", ()):
                    brj = _raw(br)
                    if len(brj.invars) == len(ops):
                        for bv, ov in zip(brj.invars, ops):
                            iv = ev.read(ov)
                            if iv is not None:
                                ev.env[bv] = iv
                            if ov in refshapes:
                                refshapes[bv] = refshapes[ov]
                    walk(brj)
                ev.eqn(eqn)
            else:
                ev.eqn(eqn)
                for sub in _subjaxprs(eqn):
                    walk(_raw(sub))

    walk(body)


# --------------------------------------------------------------------------
# K004 — output write races across the sequential grid
# --------------------------------------------------------------------------
def _check_races(ki, loc, scalar_bounds, findings):
    for b in ki.blocks:
        if not b.is_output or len(b.block_shape) != len(b.array_shape):
            continue
        steps = _enumerate_output_blocks(b, ki.grid, scalar_bounds)
        if steps is None:
            continue
        runs = {}                           # block tuple -> [first, last, n]
        for t, tpl in steps:
            if tpl in runs:
                runs[tpl][1] = t
                runs[tpl][2] += 1
            else:
                runs[tpl] = [t, t, 1]
        for tpl, (first, last, n) in sorted(runs.items()):
            if last - first + 1 != n:
                findings.append(Finding(
                    "K004", ERROR, loc,
                    f"output {b.origin} block {tpl} is written at grid "
                    f"steps {first}..{last} but only {n} of those "
                    f"{last - first + 1} steps — the block is "
                    f"revisited after the sequential grid left it: "
                    f"TPU silently keeps the last write while "
                    f"interpret mode sees every one (results differ "
                    f"exactly where tests do not run)",
                    category="revisit"))
                break                       # one finding per output


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------
def analyze_kernel(fn, *args, scalar_bounds=None, rules=None,
                   profile="tpu-v4", label=""):
    """Run K001-K004 over every ``pallas_call`` reached by tracing
    ``fn(*args)`` abstractly.  ``scalar_bounds`` maps scalar-prefetch
    operand positions to inclusive ``(lo, hi)`` value ranges."""
    findings = []
    for ki in introspect_kernels(fn, *args):
        loc = f"{label}/{ki.name}" if label else ki.name
        if _want(rules, "K001"):
            _check_tiling(ki, loc, scalar_bounds, findings)
        if _want(rules, "K002"):
            _check_vmem(ki, loc, profile, findings)
        if _want(rules, "K003"):
            _check_bounds(ki, loc, scalar_bounds, findings)
        if _want(rules, "K004"):
            _check_races(ki, loc, scalar_bounds, findings)
    return findings


def _module_issues_pallas_call(path):
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return False
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) \
                else getattr(fn, "id", "")
            if name == "pallas_call":
                return True
    return False


def _check_parity_ref(name, parity, root):
    where = f"kernels/{name}"
    if not parity or "::" not in parity:
        return [Finding(
            "K005", ERROR, where,
            f"kernel {name!r} declares no interpret-mode parity test "
            f"(expected a tests/file.py::test pytest node id)",
            category="parity")]
    path, _, rest = parity.partition("::")
    fpath = os.path.join(root, path)
    if not os.path.exists(fpath):
        return [Finding(
            "K005", ERROR, where,
            f"parity test file {path} does not exist",
            category="parity")]
    with open(fpath) as f:
        src = f.read()
    for part in rest.split("::"):
        if not re.search(rf"^\s*(?:def|class)\s+{re.escape(part)}\b",
                         src, re.M):
            return [Finding(
                "K005", ERROR, where,
                f"parity test {parity} not found: no def/class "
                f"{part!r} in {path}", category="parity")]
    return []


def check_registry(search_dir=None, entries=None):
    """K005: registry contract over ``ops/pallas/`` (or ``search_dir``).

    Checks (1) every module issuing a ``pallas_call`` has a registered
    entry point, (2) every entry's XLA fallback resolves to a callable,
    (3) every entry's parity test exists in the named test file.
    """
    import paddle_tpu
    from ..ops import pallas as _pkg
    from ..ops.pallas import registry as _registry

    findings = []
    if entries is None:
        entries = _registry.load_all()
    pkg_dir = search_dir or os.path.dirname(os.path.abspath(_pkg.__file__))
    registered = {e.fn.__module__.rsplit(".", 1)[-1]
                  for e in entries.values()}
    for fname in sorted(os.listdir(pkg_dir)):
        if not fname.endswith(".py"):
            continue
        stem = fname[:-3]
        if stem in registered:
            continue
        if _module_issues_pallas_call(os.path.join(pkg_dir, fname)):
            findings.append(Finding(
                "K005", ERROR, f"kernels/{fname}",
                f"module issues a pallas_call but registers no entry "
                f"point — add @register_kernel with an XLA fallback "
                f"and a parity test (ops/pallas/registry.py)",
                category="unregistered"))
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    for name in sorted(entries):
        e = entries[name]
        try:
            _registry.resolve_fallback(e)
        except Exception as ex:
            findings.append(Finding(
                "K005", ERROR, f"kernels/{name}",
                f"XLA fallback {e.fallback!r} is not resolvable "
                f"({type(ex).__name__}: {ex}) — every kernel must "
                f"keep a working everywhere-else path",
                category="fallback"))
        findings += _check_parity_ref(name, e.parity, root)
    return findings


def lint_registry(engine, rules=None, profile="tpu-v4"):
    """Sweep the whole kernel registry over ``engine``'s real launch
    shapes (built from the same ``_bucket_grid()`` walk as warmup) and
    run K001-K005.  Tracing is abstract: the engine's executable caches
    stay cold."""
    from ..ops.pallas import registry as _registry

    findings = []
    if _want(rules, "K005"):
        findings += check_registry()
    entries = _registry.load_all()
    for name in sorted(entries):
        e = entries[name]
        if e.engine_shapes is None:
            continue
        for case in e.engine_shapes(engine):
            findings += analyze_kernel(
                case.fn, *case.args, scalar_bounds=case.scalar_bounds,
                rules=rules, profile=profile,
                label=f"{name}[{case.label}]")
    return findings
