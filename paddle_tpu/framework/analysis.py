"""Jaxpr static-analysis suite: graph lint, donation/sharding/dtype
checkers, and a reusable recompile guard.

The reference Paddle tree front-loads correctness into compile-time
program checks — IR passes, op verifiers, ``infermeta`` shape
inference.  This module is the JAX-port analog: a set of analyses that
run over traced jaxprs (any jitted callable, the LLM engine's
chunk/decode executable grid, or programs loaded via
``static.program_import``) and return structured :class:`Finding`
records instead of failing at runtime, long after the damage is done.

Rule catalog (see docs/ANALYSIS.md):

- **D001 donation** — an argument marked donated (``donate_argnums``)
  must actually be consumed by the computation, and some output should
  be shape/dtype-compatible so XLA can alias the buffer.  A donated-
  but-unused pool means the caller gave up its buffer for nothing.
- **S001 sharding** — every ``shard_map`` mesh axis and every
  collective (``psum``/``all_gather``/…) axis must exist on the
  declared mesh; ``NamedSharding`` placements of live arrays must sit
  on that same mesh.  Validates the tensor-parallel layouts end to end.
- **T001 dtype** — no float64/complex128 value may appear anywhere in
  a jitted graph (default CPU jax silently promotes), and top-level
  outputs should not be weak-typed (a weak output means a bare python
  scalar leaked through the whole computation).
- **G001 dead code** — equations whose results are never used (and
  which carry no effects), plus — for imported static programs — ops
  whose outputs never reach a fetch target, reported with the
  program's real variable names.
- **H001 host-sync** — an AST lint over ``paddle_tpu/ops/`` and
  ``paddle_tpu/inference/llm/`` flagging ``.item()``/``.tolist()``,
  ``np.asarray``/``np.array``, and ``float()``/``int()``/``bool()``
  applied to tensor arguments: each is a device→host round-trip that
  breaks under ``jit`` and stalls the pipeline in eager.  Sites that
  are host-side by contract carry an inline ``# noqa: H001`` tag (or a
  module-wide ``# noqa-module: H001`` pragma for host-by-design
  modules — the scheduler, BlockManager, and n-gram drafter);
  everything untagged fails.

The cost layer lives next door in :mod:`paddle_tpu.framework.cost`:
static FLOPs/HBM/collective estimates, the donation-aware peak-memory
model, and the executable census with rules M001 (per-chip HBM budget),
C001 (collective placement), B001 (bucket-grid blowup).

``CompileWatcher`` is the dynamic companion: it snapshots the
executable caches of watched jitted callables (and optionally the
backend-compile monitoring stream) and raises :class:`RecompileError`
when anything compiles inside the guarded window — the generalized
form of the zero-new-compiles assertions the serving tests grew ad
hoc.

Traversal reuses the helpers in :mod:`paddle_tpu.framework.ir`
(`_producers` et al.) so both subsystems read jaxprs the same way.
"""

import argparse
import ast
import collections
import json
import logging
import os
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax.extend import core as jcore
from jax.sharding import NamedSharding, PartitionSpec as P

from .ir import _producers  # noqa: F401  (shared traversal idiom; re-export)

try:  # same a/b/c names as jax's own jaxpr printer (best-effort private)
    from jax._src import core as _pcore
except Exception:  # pragma: no cover - exercised only on jax upgrades
    _pcore = None

ERROR = "error"
WARNING = "warning"

__all__ = [
    "Finding", "CompileWatcher", "RecompileError",
    "analyze_jaxpr", "analyze_jitted", "analyze_engine",
    "analyze_program", "check_donation", "check_sharding",
    "check_dtypes", "check_dead_code", "check_host_sync",
    "check_placements", "collect_host_sync_sites", "main",
]


class Finding:
    """One structured analysis result.

    rule      -- "D001" | "S001" | "T001" | "G001" | "H001"
    severity  -- "error" | "warning"
    where     -- human-readable location: "chunk[8]/eqn 3 (scan)" or
                 "paddle_tpu/ops/misc_ops.py:452"
    message   -- what is wrong and why it matters
    category  -- optional sub-class (H001: item-call / np-asarray /
                 py-cast; others leave it empty)
    """

    __slots__ = ("rule", "severity", "where", "message", "category")

    def __init__(self, rule, severity, where, message, category=""):
        self.rule = rule
        self.severity = severity
        self.where = where
        self.message = message
        self.category = category

    def format(self):
        cat = f" [{self.category}]" if self.category else ""
        return f"{self.rule} {self.severity}{cat} {self.where}: " \
               f"{self.message}"

    def __repr__(self):
        return f"Finding({self.format()!r})"


_ALL_RULES = ("D001", "S001", "T001", "G001", "H001")


def _want(rules, rid):
    return rules is None or rid in rules


# --------------------------------------------------------------------------
# jaxpr traversal
# --------------------------------------------------------------------------
def _raw(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _subjaxprs(eqn):
    """Sub-jaxprs carried in an eqn's params (scan/cond/while/pjit/
    shard_map/custom_* all stash them under different keys — find them
    structurally rather than by name)."""
    for val in eqn.params.values():
        if isinstance(val, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    yield item


def walk_jaxprs(closed):
    """Yield ``(path, raw_jaxpr)`` for the jaxpr and every sub-jaxpr,
    where ``path`` is a tuple of "eqn <i> (<prim>)" strings."""
    stack = [((), _raw(closed))]
    while stack:
        path, j = stack.pop()
        yield path, j
        for i, eqn in enumerate(j.eqns):
            for sub in _subjaxprs(eqn):
                stack.append(
                    (path + (f"eqn {i} ({eqn.primitive.name})",),
                     _raw(sub)))


class _VarNames:
    """Display names for jaxpr vars, matching jax's printer (a, b, c…)
    when the private pretty-printer is importable, stable fallbacks
    otherwise."""

    def __init__(self):
        self._ctx = _pcore.JaxprPpContext() if _pcore else None
        self._fallback = {}

    def __call__(self, v):
        if isinstance(v, jcore.Literal):
            return repr(v.val)
        if self._ctx is not None:
            try:
                return str(_pcore.pp_var(v, self._ctx))
            except Exception:  # pragma: no cover
                pass
        return self._fallback.setdefault(v, f"v{len(self._fallback)}")


def _loc(label, path, tail=None):
    parts = [p for p in ((label,) + tuple(path)) if p]
    if tail:
        parts.append(tail)
    return "/".join(parts) if parts else "<jaxpr>"


# --------------------------------------------------------------------------
# D001 — donation
# --------------------------------------------------------------------------
def check_donation(fn, *args, label=""):
    """Donated args of a jitted callable must be consumed and aliasable.

    Traces (never executes) ``fn`` over ``args`` — arrays or
    ``jax.ShapeDtypeStruct`` stand-ins both work.
    """
    traced = fn.trace(*args)
    closed = traced.jaxpr
    infos = jtu.tree_leaves(traced.lower().args_info)
    return _check_donation_jaxpr(closed, infos, label=label)


def _check_donation_jaxpr(closed, args_info, label=""):
    findings = []
    j = _raw(closed)
    if len(args_info) != len(j.invars):  # pragma: no cover - defensive
        return [Finding("D001", WARNING, _loc(label, ()),
                        f"cannot align {len(args_info)} argument infos "
                        f"with {len(j.invars)} jaxpr inputs; donation "
                        "not checked")]
    used = {v for eqn in j.eqns for v in eqn.invars
            if isinstance(v, jcore.Var)}
    used |= {v for v in j.outvars if isinstance(v, jcore.Var)}
    out_sigs = [(tuple(v.aval.shape), jnp.dtype(v.aval.dtype))
                for v in j.outvars if hasattr(v, "aval")]
    for i, (info, iv) in enumerate(zip(args_info, j.invars)):
        if not getattr(info, "donated", False):
            continue
        sig = (tuple(iv.aval.shape), jnp.dtype(iv.aval.dtype))
        desc = f"{sig[1]}{list(sig[0])}"
        if iv not in used:
            findings.append(Finding(
                "D001", ERROR, _loc(label, (), f"arg {i}"),
                f"donated argument {i} ({desc}) is never consumed by "
                "the computation — the caller's buffer is destroyed "
                "for nothing"))
        elif sig not in out_sigs:
            findings.append(Finding(
                "D001", WARNING, _loc(label, (), f"arg {i}"),
                f"donated argument {i} ({desc}) has no shape/dtype-"
                "matching output, so XLA cannot alias the buffer and "
                "the donation saves no memory"))
    return findings


# --------------------------------------------------------------------------
# S001 — sharding / collectives
# --------------------------------------------------------------------------
_COLLECTIVES = {
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "reduce_scatter", "ppermute", "pshuffle", "axis_index", "pgather",
    "psum_scatter",
}


def _collective_axes(eqn):
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return [a for a in axes if isinstance(a, str)]


def check_sharding(closed, mesh=None, label=""):
    """Validate shard_map bodies and collectives against ``mesh``.

    With ``mesh=None`` only internal consistency is checked (collective
    axes must be bound by an enclosing shard_map); with a declared mesh
    every shard_map mesh axis must also exist on it.
    """
    findings = []
    declared = tuple(mesh.axis_names) if mesh is not None else None

    def rec(j, path, bound):
        for i, eqn in enumerate(j.eqns):
            name = eqn.primitive.name
            here = path + (f"eqn {i} ({name})",)
            if name == "shard_map":
                sm_mesh = eqn.params.get("mesh")
                sm_axes = tuple(getattr(sm_mesh, "axis_names", ()))
                if declared is not None:
                    for ax in sm_axes:
                        if ax not in declared:
                            findings.append(Finding(
                                "S001", ERROR, _loc(label, here),
                                f"shard_map mesh axis '{ax}' does not "
                                f"exist on the declared mesh (axes "
                                f"{declared})"))
                for key in ("in_names", "out_names"):
                    for entry in eqn.params.get(key, ()):
                        for ax_tuple in getattr(entry, "values",
                                                lambda: ())():
                            for ax in ax_tuple:
                                if ax not in sm_axes:
                                    findings.append(Finding(
                                        "S001", ERROR, _loc(label, here),
                                        f"shard_map {key} references "
                                        f"axis '{ax}' absent from its "
                                        f"mesh (axes {sm_axes})"))
                for sub in _subjaxprs(eqn):
                    rec(_raw(sub), here, bound | set(sm_axes))
                continue
            if name in _COLLECTIVES:
                for ax in _collective_axes(eqn):
                    if ax not in bound:
                        findings.append(Finding(
                            "S001", ERROR, _loc(label, here),
                            f"collective '{name}' names axis '{ax}' "
                            "which no enclosing shard_map binds"))
                    elif declared is not None and ax not in declared:
                        findings.append(Finding(
                            "S001", ERROR, _loc(label, here),
                            f"collective '{name}' axis '{ax}' does not "
                            f"exist on the declared mesh ({declared})"))
            for sub in _subjaxprs(eqn):
                rec(_raw(sub), here, bound)

    rec(_raw(closed), (), set())
    return findings


def check_placements(tree, mesh, label=""):
    """NamedSharding placements of live arrays must sit on ``mesh`` and
    only use axes it declares (S001 for data, not graphs)."""
    findings = []
    declared = tuple(mesh.axis_names)
    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        sh = getattr(leaf, "sharding", None)
        if not isinstance(sh, NamedSharding):
            continue
        where = _loc(label, (), jtu.keystr(path))
        if tuple(sh.mesh.axis_names) != declared or \
                sh.mesh.devices.tolist() != mesh.devices.tolist():
            findings.append(Finding(
                "S001", ERROR, where,
                f"array is placed on a different mesh (axes "
                f"{tuple(sh.mesh.axis_names)}) than the engine's "
                f"({declared}) — cross-mesh dispatch will reshard or "
                "fail"))
            continue
        for part in sh.spec:
            for ax in (part if isinstance(part, tuple) else (part,)):
                if ax is not None and ax not in declared:
                    findings.append(Finding(
                        "S001", ERROR, where,
                        f"PartitionSpec axis '{ax}' does not exist on "
                        f"the mesh (axes {declared})"))
    return findings


# --------------------------------------------------------------------------
# T001 — dtype hygiene
# --------------------------------------------------------------------------
_BAD_DTYPES = ("float64", "complex128")


def check_dtypes(closed, label=""):
    # int8 leaves are NOT findings: a weight-only-quantized or int8-KV
    # graph legitimately carries int8 params/pools beside bf16/f32
    # activations (the dequant multiply is the intent).  What T001 does
    # flag in a quantized graph is the classic dequant accident — a
    # convert_element_type that widens an int8 operand straight to
    # float64 (a python-float scale leaking through the multiply).
    findings = []
    for path, j in walk_jaxprs(closed):
        names = _VarNames()

        def bad(v, where, what):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) in _BAD_DTYPES:
                findings.append(Finding(
                    "T001", ERROR, where,
                    f"{what} '{names(v)}' is {dt} — double precision "
                    "leaked into the jitted graph (CPU jax promotes "
                    "silently; TPUs emulate f64 at ~100x cost)"))

        for v in j.invars:
            bad(v, _loc(label, path, "invars"), "input")
        for v in j.constvars:
            bad(v, _loc(label, path, "constvars"), "constant")
        for i, eqn in enumerate(j.eqns):
            for ov in eqn.outvars:
                bad(ov, _loc(label, path + (f"eqn {i} "
                                            f"({eqn.primitive.name})",)),
                    "result")
            if eqn.primitive.name == "convert_element_type" and \
                    str(getattr(eqn.invars[0].aval, "dtype", "")) \
                    == "int8" and \
                    str(eqn.params.get("new_dtype", "")) in _BAD_DTYPES:
                findings.append(Finding(
                    "T001", ERROR,
                    _loc(label, path + (f"eqn {i} (convert_element_"
                                        f"type)",)),
                    f"int8 '{names(eqn.invars[0])}' widens directly to "
                    f"{eqn.params['new_dtype']} — dequantize in the "
                    "activation dtype, not double precision"))
        if not path:  # weak-typed top-level outputs: a python scalar
            for k, ov in enumerate(j.outvars):  # flowed through to here
                aval = getattr(ov, "aval", None)
                if getattr(aval, "weak_type", False) and \
                        jnp.issubdtype(aval.dtype, jnp.inexact):
                    findings.append(Finding(
                        "T001", WARNING, _loc(label, (), f"output {k}"),
                        f"output {k} is weak-typed {aval.dtype} — a "
                        "bare python scalar reached the output; its "
                        "dtype will flip with the first strongly-typed "
                        "operand downstream"))
    return findings


# --------------------------------------------------------------------------
# G001 — dead code
# --------------------------------------------------------------------------
def check_dead_code(closed, label=""):
    """Equations whose outputs are never used and which carry no
    effects.  jax's tracer already marks locally-unused results as
    DropVar but keeps the eqn; this also catches chains feeding only
    dead eqns."""
    findings = []
    for path, j in walk_jaxprs(closed):
        names = _VarNames()
        live = {v for v in j.outvars if isinstance(v, jcore.Var)}
        for i in reversed(range(len(j.eqns))):
            eqn = j.eqns[i]
            if eqn.effects or any(ov in live for ov in eqn.outvars):
                live.update(v for v in eqn.invars
                            if isinstance(v, jcore.Var))
            else:
                outs = ", ".join(names(ov) for ov in eqn.outvars)
                findings.append(Finding(
                    "G001", WARNING,
                    _loc(label, path + (f"eqn {i} "
                                        f"({eqn.primitive.name})",)),
                    f"result(s) [{outs}] of '{eqn.primitive.name}' are "
                    "never used — dead computation compiled into the "
                    "executable"))
    findings.reverse()
    return findings


# --------------------------------------------------------------------------
# entry points: jitted callables / engines / imported programs
# --------------------------------------------------------------------------
def analyze_jaxpr(closed, *, mesh=None, rules=None, label=""):
    """Run the graph-level rules (S001/T001/G001) over a (Closed)Jaxpr."""
    findings = []
    if _want(rules, "S001"):
        findings += check_sharding(closed, mesh=mesh, label=label)
    if _want(rules, "T001"):
        findings += check_dtypes(closed, label=label)
    if _want(rules, "G001"):
        findings += check_dead_code(closed, label=label)
    return findings


def analyze_jitted(fn, *args, mesh=None, rules=None, label=""):
    """Trace a jitted callable over ``args`` (arrays or
    ``jax.ShapeDtypeStruct``) and run D001 + the graph rules.  Plain
    callables are jitted first (which disables D001 — nothing is
    donated)."""
    if not hasattr(fn, "trace"):
        fn = jax.jit(fn)
    traced = fn.trace(*args)
    closed = traced.jaxpr
    findings = []
    if _want(rules, "D001"):
        findings += _check_donation_jaxpr(
            closed, jtu.tree_leaves(traced.lower().args_info),
            label=label)
    findings += analyze_jaxpr(closed, mesh=mesh, rules=rules, label=label)
    return findings


def analyze_engine(engine, rules=None):
    """Run the jaxpr rules over every executable of an LLM engine's
    warmup bucket grid (chunk, decode, and — when the engine was built
    with ``speculative=`` — the verify family), plus S001 placement
    checks on the live params and K/V pools under tensor parallelism.

    Pure analysis: the engine's caches and executable caches are
    untouched (tracing uses abstract cache stand-ins and jax's AOT
    path, which does not populate the jit dispatch cache).
    """
    findings = []
    for kind, bucket, fn, args in engine.executable_grid():
        findings += analyze_jitted(
            fn, *args, mesh=engine.mesh, rules=rules,
            label=f"{kind}[{bucket}]")
    if engine.mesh is not None and _want(rules, "S001"):
        findings += check_placements(engine.params, engine.mesh,
                                     label="params")
        findings += check_placements(engine.kv_cache, engine.mesh,
                                     label="kv_pool")
    return findings


def analyze_program(program, rules=None, label=""):
    """G001 over an imported static program: top-level ops whose
    outputs never (transitively) reach a fetch target, and feed vars
    nothing reads — reported with the program's real variable names."""
    if not _want(rules, "G001"):
        return []
    findings = []
    blocks = getattr(program, "blocks", []) or []

    def op_reads(op, depth=0):
        reads = [a for args in op.inputs.values() for a in args]
        sub = op.attrs.get("sub_block")
        if sub is not None and depth < 16 and 0 <= sub < len(blocks):
            for sop in blocks[sub][0]:
                reads += op_reads(sop, depth + 1)
        return reads

    live = set(program.fetch_names)
    for idx in reversed(range(len(program.body))):
        op = program.body[idx]
        outs = [a for args in op.outputs.values() for a in args]
        # `while` mutates loop-carried vars in place; never prune it
        if op.type == "while" or any(o in live for o in outs):
            live.update(op_reads(op))
        else:
            findings.append(Finding(
                "G001", WARNING,
                _loc(label, (), f"op {idx} ({op.type})"),
                f"op '{op.type}' outputs {outs} never reach a fetch "
                "target — dead op in the imported program"))
    findings.reverse()
    for name in program.feed_names:
        if name not in live:
            findings.append(Finding(
                "G001", WARNING, _loc(label, (), f"feed '{name}'"),
                f"feed var '{name}' is never read by any live op"))
    return findings


# --------------------------------------------------------------------------
# H001 — host-sync AST lint over op kernels
# --------------------------------------------------------------------------
_METADATA_ATTRS = {"shape", "ndim", "size", "dtype", "name", "aval",
                   "sharding"}
_SYNC_METHODS = {"item": "item-call", "tolist": "item-call"}
_CAST_FUNCS = {"float": "py-cast", "int": "py-cast", "bool": "py-cast"}
# flagged UNCONDITIONALLY (no taint needed): these functions block the
# host on device work by definition, and the async-lookahead engine's
# pipelined step path must not hide one without an annotation
_EXPLICIT_SYNCS = ("device_get", "block_until_ready")
_NOQA = "noqa: H001"
_NOQA_MODULE = "noqa-module: H001"


def _data_names(node, acc=None):
    """Names contributing DATA (not metadata) to an expression: prunes
    ``.shape``/``.ndim``/``.dtype``-style attribute subtrees and
    ``len()`` calls, which read only metadata a tracer carries."""
    if acc is None:
        acc = set()
    if isinstance(node, ast.Attribute) and node.attr in _METADATA_ATTRS:
        return acc
    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Name) and node.func.id == "len":
        return acc
    if isinstance(node, ast.Name):
        acc.add(node.id)
    for child in ast.iter_child_nodes(node):
        _data_names(child, acc)
    return acc


class _Site:
    __slots__ = ("path", "line", "func", "category", "detail", "allowed")

    def __init__(self, path, line, func, category, detail, allowed):
        self.path, self.line, self.func = path, line, func
        self.category, self.detail, self.allowed = \
            category, detail, allowed


class _HostSyncLinter(ast.NodeVisitor):
    def __init__(self, path, lines, sites):
        self.path = path
        self.lines = lines
        self.sites = sites
        self._taint = []        # stack of tainted-name sets

    # ---- taint bookkeeping ----
    def _tensor_params(self, node):
        """Op-kernel convention: tensors are the leading no-default
        positional params; attrs always carry defaults."""
        args = node.args
        names = [a.arg for a in args.posonlyargs + args.args]
        n_def = len(args.defaults)
        tainted = names[:len(names) - n_def] if n_def else names
        return {n for n in tainted if n not in ("self", "cls", "name")}

    def visit_FunctionDef(self, node):
        inherited = self._taint[-1] if self._taint else set()
        self._taint.append(inherited | self._tensor_params(node))
        self.generic_visit(node)
        self._taint.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _is_tainted(self, expr):
        return bool(self._taint and
                    _data_names(expr) & self._taint[-1])

    def visit_Assign(self, node):
        self.generic_visit(node)
        if not self._taint:
            return
        tainted = self._is_tainted(node.value)
        for tgt in node.targets:
            for name in ([tgt] if isinstance(tgt, ast.Name) else
                         [e for e in ast.walk(tgt)
                          if isinstance(e, ast.Name)]):
                if isinstance(name.ctx, ast.Store):
                    (self._taint[-1].add if tainted else
                     self._taint[-1].discard)(name.id)

    def visit_For(self, node):
        if self._taint and self._is_tainted(node.iter):
            for name in ast.walk(node.target):
                if isinstance(name, ast.Name):
                    self._taint[-1].add(name.id)
        self.generic_visit(node)

    # ---- the flags ----
    def visit_Call(self, node):
        self.generic_visit(node)
        if not self._taint:
            return
        func = node.func
        if isinstance(func, ast.Attribute) and \
                func.attr in _SYNC_METHODS and \
                self._is_tainted(func.value):
            self._record(node, _SYNC_METHODS[func.attr],
                         f".{func.attr}() on a tensor value")
        elif isinstance(func, ast.Attribute) and \
                func.attr in ("asarray", "array") and \
                isinstance(func.value, ast.Name) and \
                func.value.id == "np" and node.args and \
                self._is_tainted(node.args[0]):
            self._record(node, "np-asarray",
                         f"np.{func.attr}() pulls a tensor to host")
        elif isinstance(func, ast.Name) and func.id in _CAST_FUNCS \
                and node.args and self._is_tainted(node.args[0]):
            self._record(node, _CAST_FUNCS[func.id],
                         f"{func.id}() on a tensor value")
        elif isinstance(func, ast.Attribute) and \
                func.attr in _EXPLICIT_SYNCS and \
                isinstance(func.value, ast.Name) and \
                func.value.id == "jax":
            # unconditional: jax.device_get / jax.block_until_ready
            # are host syncs BY DEFINITION, no taint analysis needed —
            # the name-taint pass cannot see them anyway (``self.…``
            # attributes carry the engine's device state, and ``self``
            # is excluded from the tensor-param taint).  One untagged
            # call inside the pipelined step path stalls the lookahead
            # window the engine works to keep full.
            self._record(node, "explicit-sync",
                         f"jax.{func.attr}() blocks the host on "
                         f"device work")

    def _record(self, node, category, detail):
        line = self.lines[node.lineno - 1] \
            if node.lineno - 1 < len(self.lines) else ""
        allowed = _NOQA in line
        self.sites.append(_Site(self.path, node.lineno, "", category,
                                detail, allowed))


def collect_host_sync_sites(paths=None):
    """All host-sync sites the AST lint matches, allowlisted or not —
    the classification view behind :func:`check_host_sync`."""
    if paths is None:
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = [os.path.join(pkg, "ops"),
                 os.path.join(pkg, "inference", "llm")]
    files = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                files += [os.path.join(root, n) for n in names
                          if n.endswith(".py")]
        else:
            files.append(p)
    sites = []
    for path in sorted(files):
        try:
            with open(path) as f:
                src = f.read()
            tree = ast.parse(src, filename=path)
        except (OSError, SyntaxError):  # pragma: no cover
            continue
        lines = src.splitlines()
        module_allowed = any(_NOQA_MODULE in ln for ln in lines[:40])
        file_sites = []
        _HostSyncLinter(path, lines, file_sites).visit(tree)
        if module_allowed:
            for s in file_sites:
                s.allowed = True
        sites += file_sites
    return sites


def check_host_sync(paths=None, label=""):
    """H001 findings: untagged host-sync sites in op kernels."""
    findings = []
    for s in collect_host_sync_sites(paths):
        if s.allowed:
            continue
        findings.append(Finding(
            "H001", ERROR, f"{os.path.relpath(s.path)}:{s.line}",
            f"{s.detail} — device->host sync in a jit-reachable op "
            "path (tag the line with '# noqa: H001 (<reason>)' only "
            "if it is host-side by contract)", category=s.category))
    return findings


# --------------------------------------------------------------------------
# CompileWatcher — the recompile guard
# --------------------------------------------------------------------------
class RecompileError(AssertionError):
    """A watched executable compiled inside a no-compile window."""


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileKeyLog(logging.Handler):
    """Captures the cache key of every executable build.

    jax has no public API for enumerating a pjit cache's keys, but the
    lowering path logs ``Compiling <fn> with global shapes and types
    (ShapedArray(...),). Argument mapping: ...`` for each new executable — at DEBUG even when
    ``jax_log_compiles`` is off, and including ``weak_type=True``
    (exactly the bit the classic python-scalar bucket leak flips).
    This handler parses those lines so :class:`RecompileError` can name
    the new cache keys, not just the growth count.

    Capture is reference-counted and WINDOW-scoped (armed by
    CompileWatcher, released at assert/exit): the pxla logger is only
    held at DEBUG while a guard window is open, because jax installs
    its own stderr handler on the parent 'jax' logger and a permanent
    DEBUG level would echo every later legitimate compile to stderr.
    """

    _RE = re.compile(
        r"Compiling (\S+) with global shapes and types (\(.*?\))"
        r"\. Argument mapping")
    _LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.seq = 0
        self.entries = collections.deque(maxlen=256)
        self._count = 0
        self._saved_level = None
        self._saved_propagate = None

    def emit(self, record):
        try:
            m = self._RE.search(record.getMessage())
        except Exception:  # pragma: no cover - malformed record
            return
        if m:
            self.seq += 1
            self.entries.append((self.seq, m.group(1), m.group(2)))

    def since(self, mark):
        """[(fn_name, avals_str)] for compiles after sequence ``mark``."""
        return [(name, key) for s, name, key in self.entries
                if s > mark]

    def acquire(self):
        if self._count == 0:
            lg = logging.getLogger(self._LOGGER)
            self._saved_level = lg.level
            self._saved_propagate = lg.propagate
            lg.addHandler(self)
            if lg.getEffectiveLevel() > logging.DEBUG:
                lg.setLevel(logging.DEBUG)
            # handlers attached here still fire; stop the records from
            # reaching the parent 'jax' stderr handler while the
            # window is open (the keys surface via RecompileError, not
            # the console)
            lg.propagate = False
        self._count += 1
        return self.seq

    def release(self):
        if self._count == 0:
            return
        self._count -= 1
        if self._count == 0:
            lg = logging.getLogger(self._LOGGER)
            lg.removeHandler(self)
            lg.setLevel(self._saved_level)
            lg.propagate = self._saved_propagate


_compile_key_log = _CompileKeyLog()


class CompileWatcher:
    """Guard a window of execution against unexpected recompiles.

    Snapshots the executable-cache sizes of the watched jitted
    callables at construction (and again at ``__enter__``); any growth
    observed by :meth:`assert_no_new_compiles` / ``__exit__`` raises
    :class:`RecompileError` naming the offender and the executable
    delta.  ``watch_backend=True`` additionally subscribes to jax's
    compile-monitoring stream for the window, catching compiles of
    executables that were not explicitly watched.

    Two idioms::

        with CompileWatcher(eng._ragged):
            serve_traffic()             # raises if anything compiled

        watcher = eng.warmup()          # armed at warmup exit
        serve_traffic()
        watcher.assert_no_new_compiles()
    """

    def __init__(self, *jitted, labels=None, strict=True,
                 watch_backend=False):
        self._fns = jitted
        self._labels = list(labels) if labels else \
            [getattr(f, "__name__", f"fn{i}")
             for i, f in enumerate(jitted)]
        self.strict = strict
        self._watch_backend = watch_backend
        self._listener = None
        self.backend_compiles = 0
        self._base = self._sizes()
        self._capturing = True
        self._key_mark = _compile_key_log.acquire()

    @staticmethod
    def _size(fn):
        try:
            return fn._cache_size()
        except Exception:  # pragma: no cover - non-pjit callables
            return 0

    def _sizes(self):
        return [self._size(f) for f in self._fns]

    def new_compiles(self):
        """[(label, executable_delta)] for every watched fn that grew."""
        deltas = [(lbl, now - was) for lbl, was, now in
                  zip(self._labels, self._base, self._sizes())
                  if now - was > 0]
        if self._watch_backend and self.backend_compiles:
            deltas.append(("<backend>", self.backend_compiles))
        return deltas

    def new_cache_keys(self):
        """[(fn_name, avals_str)] of every executable built inside the
        guard window — the actual cache keys behind the growth counts
        :meth:`new_compiles` reports (empty once the window closed)."""
        if not self._capturing:
            return []
        return _compile_key_log.since(self._key_mark)

    def _release_capture(self):
        if self._capturing:
            self._capturing = False
            _compile_key_log.release()

    def __del__(self):
        # a watcher that is never asserted (warmup()'s return value,
        # dropped) must not hold the capture window open forever
        try:
            self._release_capture()
        except Exception:  # pragma: no cover - interpreter teardown
            pass

    def assert_no_new_compiles(self):
        deltas = self.new_compiles()
        keys = self.new_cache_keys()
        self._release_capture()
        if deltas:
            detail = ", ".join(f"{lbl}: +{n}" for lbl, n in deltas)
            keydetail = "; ".join(f"{name} {key}" for name, key
                                  in keys[-8:])
            raise RecompileError(
                f"unexpected recompile(s) inside guarded window — "
                f"{detail}. A new executable signature appeared "
                "(shape/dtype/python-scalar leak past the bucket "
                "grid?)"
                + (f" New cache keys: {keydetail}" if keydetail else ""))

    def __enter__(self):
        self._base = self._sizes()
        self.backend_compiles = 0
        if not self._capturing:
            self._capturing = True
            self._key_mark = _compile_key_log.acquire()
        else:
            self._key_mark = _compile_key_log.seq
        if self._watch_backend:
            def _listener(event, _dur, **_kw):
                if event == _BACKEND_COMPILE_EVENT:
                    self.backend_compiles += 1
            self._listener = _listener
            jax.monitoring.register_event_duration_secs_listener(
                _listener)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._listener is not None:
            try:
                from jax._src import monitoring as _mon
                _mon._unregister_event_duration_listener_by_callback(
                    self._listener)
            except Exception:  # pragma: no cover
                pass
            self._listener = None
        if exc_type is None and self.strict:
            self.assert_no_new_compiles()
        else:
            self._release_capture()
        return False


# --------------------------------------------------------------------------
# CLI — tools/graph_lint.py and the `graph-lint` console script
# --------------------------------------------------------------------------
def _report(findings, out=None, json_out=False, strict=False,
            extra=None):
    """Print findings and return the exit code.

    Exit codes (documented in docs/ANALYSIS.md): 0 = clean (or
    warnings only), 1 = any error-severity finding — or any warning
    under ``strict`` — 2 = usage error (argparse's own).  ``json_out``
    emits one machine-readable JSON document instead of text;
    ``extra`` merges additional keys into it (the cost subcommand's
    census artifact)."""
    out = out or sys.stdout
    errors = sum(1 for f in findings if f.severity == ERROR)
    warnings = len(findings) - errors
    if json_out:
        doc = {
            "findings": [
                {"rule": f.rule, "severity": f.severity,
                 "category": f.category, "where": f.where,
                 "message": f.message} for f in findings],
            "errors": errors,
            "warnings": warnings,
        }
        if extra:
            doc.update(extra)
        print(json.dumps(doc, indent=2), file=out)
    else:
        for f in findings:
            print(f.format(), file=out)
        print(f"graph-lint: {errors} error(s), {warnings} warning(s)",
              file=out)
    return 1 if errors or (strict and warnings) else 0


def _parse_spec(spec):
    """'f32[2,3]' / 'int32[8]' / 'i32' -> ShapeDtypeStruct."""
    short = {"f32": "float32", "f16": "float16", "bf16": "bfloat16",
             "f64": "float64", "i32": "int32", "i64": "int64",
             "i8": "int8", "u8": "uint8", "b1": "bool"}
    name, _, dims = spec.partition("[")
    dt = jnp.dtype(short.get(name, name))
    shape = tuple(int(d) for d in dims.rstrip("]").split(",") if d) \
        if dims else ()
    return jax.ShapeDtypeStruct(shape, dt)


def _cli_build_engine(ns):
    from ..inference.llm import LLMEngine
    from ..models.gpt import gpt_tiny
    import paddle_tpu as paddle

    paddle.seed(0)
    model = gpt_tiny(num_layers=ns.layers)
    model.eval()
    return LLMEngine(model, block_size=ns.block_size,
                     max_batch=ns.max_batch,
                     max_model_len=ns.max_model_len,
                     token_budget=ns.token_budget,
                     tensor_parallel=ns.tp if ns.tp > 1 else None,
                     speculative=ns.spec if ns.spec > 0 else None,
                     quantize=getattr(ns, "quantize", None),
                     kv_tier=getattr(ns, "kv_tier", None),
                     # --lora N: N tenant adapters -> N+1 pool slots
                     # (slot 0 is the reserved base identity)
                     lora=(dict(rank=4,
                                max_adapters=getattr(ns, "lora", 0) + 1)
                           if getattr(ns, "lora", 0) else None))


def _cli_engine(ns):
    eng = _cli_build_engine(ns)
    findings = analyze_engine(eng, rules=ns.rules)
    if ns.rules is None or "H001" in ns.rules:
        findings += check_host_sync()
    return findings


def _cli_cost(ns):
    from .cost import run_census
    eng = _cli_build_engine(ns)
    census = run_census(eng, memory_budget=ns.memory_budget,
                        host_budget=getattr(ns, "host_budget", None),
                        profile=ns.profile,
                        max_executables=ns.max_executables)
    doc = census.to_dict()
    ns._extra = {"census": doc}
    if not ns.json:
        fams = ", ".join(f"{k}: {v}"
                         for k, v in sorted(census.families.items()))
        print(f"census: {census.compile_count} executable(s) — {fams}")
        for e in doc["entries"]:
            c = e["cost"]
            print(f"  {e['label']:<16} flops={c['flops']:<12} "
                  f"hbm={c['hbm_bytes']:<10} peak={c['peak_bytes']:<10} "
                  f"{e['roofline']}-bound")
        mem = doc["memory"]
        line = (f"memory/chip (tp={mem['tp']}): weights "
                f"{mem['weights_bytes']} + kv pool "
                f"{mem['kv_pool_bytes']} "
                f"({mem['num_blocks']} x {mem['page_bytes']}B pages)")
        if mem.get("lora_pool_bytes"):
            line += (f"; lora adapter pools "
                     f"{mem['lora_pool_bytes']} (counted in weights)")
        if mem.get("memory_budget") is not None:
            line += (f"; budget {mem['memory_budget']} admits "
                     f"max_batch <= {mem.get('derived_max_batch', 0)}")
        if mem.get("host_pool_bytes") or mem.get("prefix_store_bytes"):
            line += (f"; host tier {mem['host_pool_bytes']} pool + "
                     f"{mem['prefix_store_bytes']} store "
                     f"({mem['host_page_bytes']}B/page)")
            if mem.get("host_budget") is not None:
                line += (f" under host budget {mem['host_budget']} "
                         f"({mem.get('host_budget_pages', 0)} pages)")
        print(line)
    return census.findings


def _cli_kernels(ns):
    from .kernel_lint import lint_registry
    eng = _cli_build_engine(ns)
    return lint_registry(eng, rules=ns.rules, profile=ns.profile)


def _cli_program(ns):
    from ..static.program_import import load_reference_inference_model
    prog, _feeds, _fetches = load_reference_inference_model(ns.path_prefix)
    return analyze_program(prog, rules=ns.rules,
                           label=os.path.basename(ns.path_prefix))


def _cli_ops(ns):
    return check_host_sync(ns.paths or None)


def _cli_threads(ns):
    from .concurrency_lint import check_concurrency
    return check_concurrency(ns.paths or None, rules=ns.rules)


def _cli_fn(ns):
    import importlib
    mod_name, _, attr = ns.target.partition(":")
    fn = getattr(importlib.import_module(mod_name), attr)
    args = [_parse_spec(s) for s in ns.arg]
    if ns.donate:
        fn = jax.jit(fn, donate_argnums=tuple(
            int(i) for i in ns.donate.split(",")))
    return analyze_jitted(fn, *args, rules=ns.rules, label=ns.target)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="graph-lint",
        description="Static analysis over jitted graphs, the LLM "
                    "serving engine's executable grid, imported static "
                    "programs, the op-kernel sources, and the Pallas "
                    "kernel registry "
                    "(rules D001/S001/T001/G001/H001 + K001-K005 + "
                    "the R001-R005 concurrency rules — "
                    "see docs/ANALYSIS.md)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids (default: all)")
    # common output flags, valid after every subcommand; exit codes:
    # 0 clean, 1 errors (or warnings under --strict), 2 usage
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--json", action="store_true",
                        help="emit one machine-readable JSON document "
                             "instead of text findings")
    common.add_argument("--strict", action="store_true",
                        help="exit 1 on warnings too, not just errors")
    sub = ap.add_subparsers(dest="cmd", required=True)

    engine_args = argparse.ArgumentParser(add_help=False)
    engine_args.add_argument("--tp", type=int, default=1)
    engine_args.add_argument("--layers", type=int, default=2)
    engine_args.add_argument("--block-size", type=int, default=8)
    engine_args.add_argument("--max-batch", type=int, default=4)
    engine_args.add_argument("--max-model-len", type=int, default=64)
    engine_args.add_argument("--token-budget", type=int, default=16)
    engine_args.add_argument("--spec", type=int, default=0, metavar="K",
                             help="include the speculative verify "
                                  "family (K = max draft tokens; "
                                  "0 = off)")
    engine_args.add_argument("--quantize", default=None,
                             choices=["int8"],
                             help="lint the quantized serving profile "
                                  "(weight-only int8 GEMM + int8 "
                                  "paged KV pool)")
    engine_args.add_argument("--lora", type=int, default=0,
                             metavar="N",
                             help="lint the multi-LoRA serving profile "
                                  "with N adapter slots (rank 4; the "
                                  "ragged family must stay at its "
                                  "golden size)")

    eng = sub.add_parser("engine", parents=[common, engine_args],
                         help="lint the LLM engine's warmup "
                              "executable grid")
    eng.set_defaults(run=_cli_engine)

    cost = sub.add_parser(
        "cost", aliases=["census"], parents=[common, engine_args],
        help="static cost census over the engine's warmup grid: "
             "FLOPs/HBM/collectives per bucket, compile count, "
             "memory model, rules M001/C001/B001")
    cost.add_argument("--memory-budget", default=None,
                      help="per-chip HBM budget for M001, bytes or "
                           "'16GiB'")
    cost.add_argument("--host-budget", default=None,
                      help="host-RAM ceiling for the hierarchical-KV "
                           "tier (M001 names both budgets), bytes or "
                           "'64GiB'")
    cost.add_argument("--kv-tier", default=None,
                      help="configure the engine's hierarchical KV "
                           "tier: total byte budget ('128MiB'), split "
                           "evenly between host pool and prefix store")
    cost.add_argument("--profile", default="tpu-v4",
                      help="roofline device profile: "
                           "tpu-v4 | tpu-v5e | cpu")
    cost.add_argument("--max-executables", type=int, default=64,
                      help="B001 threshold on the census compile "
                           "count")
    cost.set_defaults(run=_cli_cost)

    kern = sub.add_parser(
        "kernels", parents=[common, engine_args],
        help="Pallas kernel verifier: sweep the kernel registry over "
             "the engine's executable-grid shapes "
             "(rules K001-K005, framework/kernel_lint.py)")
    kern.add_argument("--profile", default="tpu-v4",
                      help="device profile for the K002 VMEM budget: "
                           "tpu-v4 | tpu-v5e | cpu")
    kern.set_defaults(run=_cli_kernels)

    prog = sub.add_parser("program", parents=[common],
                          help="lint an exported inference "
                               "program (.pdmodel prefix)")
    prog.add_argument("path_prefix")
    prog.set_defaults(run=_cli_program)

    ops = sub.add_parser("ops", parents=[common],
                         help="H001 host-sync lint over op "
                              "kernel sources")
    ops.add_argument("paths", nargs="*")
    ops.set_defaults(run=_cli_ops)

    thr = sub.add_parser(
        "threads", parents=[common],
        help="concurrency lint over the serving tree: lock "
             "discipline, lock order, blocking-while-locked, "
             "lookahead epoch discipline, stale suppressions "
             "(rules R001-R005, framework/concurrency_lint.py)")
    thr.add_argument("paths", nargs="*",
                     help="files/dirs to sweep (default: "
                          "inference/llm, framework, sim)")
    thr.set_defaults(run=_cli_threads)

    fn = sub.add_parser("fn", parents=[common],
                        help="lint an importable (jitted) "
                             "callable: module.path:attr")
    fn.add_argument("target")
    fn.add_argument("--arg", action="append", default=[],
                    metavar="SPEC", help="abstract arg, e.g. f32[2,8]")
    fn.add_argument("--donate", default="",
                    help="comma-separated argnums to donate")
    fn.set_defaults(run=_cli_fn)

    ns = ap.parse_args(argv)
    ns.rules = tuple(r.strip() for r in ns.rules.split(",")) \
        if ns.rules else None
    ns._extra = None
    return _report(ns.run(ns), json_out=ns.json, strict=ns.strict,
                   extra=ns._extra)


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
