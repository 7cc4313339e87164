"""Graph cost engine: static FLOPs / HBM / collective analysis and the
serving-grid executable census.

:mod:`.analysis` answers yes/no lint questions over jaxprs; this module
answers *how much*: for any lowered jaxpr it computes

- **FLOPs** per launch, with two conventions: ``loop_aware`` (scan
  bodies multiplied by trip count — the true per-step cost) and
  ``xla_parity`` (loop bodies counted once, matching XLA's own
  ``compiled.cost_analysis()`` so the per-primitive rules can be
  cross-checked against the compiler's ground truth — the
  ``paddle.flops`` path proves that number is reachable);
- **HBM bytes** at two granularities: ``hbm_bytes`` is the executable-
  boundary traffic (arguments read + results written, donated aliases
  counted ONCE) — the roofline denominator — and ``access_bytes`` is
  the per-equation operand+result sum (the pre-fusion upper bound XLA's
  "bytes accessed" sits below);
- **peak live-buffer bytes** via backward liveness over the eqns (the
  same traversal G001 does, weighted by buffer sizes), donation-aware:
  a donated input with a shape/dtype-matching output shares its buffer,
  so the donated paged K/V pools are counted once, not twice;
- **collective bytes per mesh axis** (psum / all_gather / … payload
  under ``shard_map``, scan-multiplied), giving a static roofline
  estimate — compute-bound vs HBM-bound vs comms-bound — per bucket.

On top sits the **executable census** (:func:`run_census`): enumerate
the LLM engine's full warmup grid via ``executable_grid()`` (prefill
chunks x decode batches x verify (bb, kb) pairs, tp-aware), total the
compile count and aggregate cost, and emit three structured rules:

- **M001** — estimated peak HBM of any bucket exceeds the declared
  per-chip budget, reported with the pages+weights breakdown that also
  drives ``LLMEngine(memory_budget=)`` (the scheduler's admissible
  ``max_batch`` is pages + weights arithmetic, not guesswork);
- **C001** — a collective inside a scan/while body whose operand is
  loop-INVARIANT (hoistable: the same reduction runs every iteration),
  or redundant back-to-back collectives on the same axis
  (``psum(psum(x, 'mp'), 'mp')``);
- **B001** — bucket-grid blowup: the census compile count exceeds the
  declared threshold.  This is the standing measurement the
  ragged-attention refactor (ROADMAP item 1) must drive down — the
  census count is asserted equal to the compiles ``CompileWatcher``
  observes during ``warmup()``, so it is the authoritative baseline.

Everything here is AOT-only: tracing/lowering never executes, donates,
or populates a jit dispatch cache, so a census over a live engine
leaves its executable caches cold (tested).

Supersedes the measured-only ``paddle_tpu.cost_model`` package, which
now re-exports this module's static API next to its timing helpers.
"""

import json
import math

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax.extend import core as jcore
from jax.sharding import PartitionSpec as P

try:  # DropVar never left _src; degrade to counting dropped results
    from jax._src.core import DropVar as _DropVar
except Exception:  # pragma: no cover - exercised only on jax upgrades
    class _DropVar:
        pass

from .analysis import (
    ERROR,
    WARNING,
    Finding,
    _collective_axes,
    _COLLECTIVES,
    _raw,
    _subjaxprs,
)

__all__ = [
    "CostEstimate", "Census", "StepTimeModel", "estimate_jaxpr",
    "estimate_jitted", "xla_cost_analysis", "check_collectives",
    "run_census", "engine_memory_model", "derive_max_batch",
    "migration_estimate", "parse_bytes", "DEVICE_PROFILES",
]


# --------------------------------------------------------------------------
# device roofline profiles (peak rates, indicative public numbers)
# --------------------------------------------------------------------------
# flops_per_s is the dense-matmul peak for the wide dtype actually used
# by the serving engine (f32 on CPU hosts, bf16 on TPU); hbm / ici are
# per-chip memory and interconnect bandwidths in bytes/s.  These feed
# only the compute/hbm/comms CLASSIFICATION — the byte and flop counts
# themselves are hardware-independent.
# ``vmem_bytes`` is the per-core VMEM budget the Pallas kernel verifier
# (framework/kernel_lint.py, rule K002) checks per-grid-step
# block+scratch residency against (~16 MiB/core on current TPUs; the
# cpu profile keeps the same budget so interpret-mode lint matches what
# the chip will enforce).
DEVICE_PROFILES = {
    "tpu-v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1.2e12,
               "ici_bytes_per_s": 3.0e11, "vmem_bytes": 16 * 1024 * 1024},
    "tpu-v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 8.2e11,
                "ici_bytes_per_s": 1.6e11, "vmem_bytes": 16 * 1024 * 1024},
    "cpu": {"flops_per_s": 1.0e11, "hbm_bytes_per_s": 5.0e10,
            "ici_bytes_per_s": 2.0e10, "vmem_bytes": 16 * 1024 * 1024},
}

# ``device_kind`` as JAX reports it -> the profile that prices it
_PROFILE_BY_DEVICE_KIND = {"TPU v4": "tpu-v4", "TPU v5 lite": "tpu-v5e"}


def attached_profile():
    """DEVICE_PROFILES key of the device this process runs on: "cpu"
    off the TPU; on a TPU the profile of its ``device_kind``, and a
    kind with no profile raises — pricing one machine with another's
    rates is worse than refusing."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return "cpu"
    if dev.device_kind not in _PROFILE_BY_DEVICE_KIND:
        raise ValueError(
            f"no device profile for TPU device_kind {dev.device_kind!r} "
            f"(known: {sorted(_PROFILE_BY_DEVICE_KIND)}); add one to "
            f"DEVICE_PROFILES with its source")
    return _PROFILE_BY_DEVICE_KIND[dev.device_kind]


_BYTE_UNITS = {"b": 1, "kb": 1000, "mb": 1000**2, "gb": 1000**3,
               "tb": 1000**4, "kib": 1024, "mib": 1024**2,
               "gib": 1024**3, "tib": 1024**4}


def parse_bytes(value):
    """Byte counts from ints/floats or '16GiB' / '512MB' style strings
    (``LLMEngine(memory_budget=...)`` and ``graph-lint cost
    --memory-budget`` both accept either)."""
    if value is None:
        return None
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return int(value)
    s = str(value).strip().lower().replace(" ", "")
    try:
        for unit in sorted(_BYTE_UNITS, key=len, reverse=True):
            if s.endswith(unit):
                return int(float(s[: -len(unit)]) * _BYTE_UNITS[unit])
        return int(float(s))
    except ValueError:
        raise ValueError(
            f"can't parse memory size {value!r} — want an int byte "
            "count or a '<number><unit>' string like '16GiB' / "
            "'512MB'") from None


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.2f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0


# --------------------------------------------------------------------------
# per-primitive flop / transcendental rules
# --------------------------------------------------------------------------
def _elems(aval):
    return int(np.prod(aval.shape)) if aval.shape else 1


def _nbytes(aval):
    return _elems(aval) * jnp.dtype(aval.dtype).itemsize


# one flop per output element (XLA's HloCostAnalysis convention for
# elementwise arithmetic — including predicates, selects and dtype
# converts, which HloCostAnalysis also prices at one op per element;
# pure data movement like broadcast/reshape/slice counts zero)
_ELEMENTWISE_FLOP = {
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "floor", "ceil", "round", "sign", "nextafter", "add_any",
    "atan2", "complex", "real", "imag", "conj", "clamp", "square",
    "lt", "le", "gt", "ge", "eq", "ne", "select_n", "and", "or",
    "xor", "not", "is_finite", "convert_element_type",
}

# counted in the separate `transcendentals` bucket, NOT flops —
# matching XLA, which prices these per-element but reports them apart
_TRANSCENDENTAL = {
    "exp", "exp2", "expm1", "log", "log2", "log1p", "tanh", "sin",
    "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "asinh",
    "acosh", "atanh", "logistic", "erf", "erfc", "erf_inv", "rsqrt",
    "sqrt", "cbrt", "pow", "digamma", "lgamma",
}

# reductions: ~one op per input element folded away
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or",
}

# cumulative scans: XLA decomposes these into a logarithmic ladder of
# strided adds plus pad/select/convert bookkeeping; HloCostAnalysis on
# the optimized module prices the ladder at ~(13 + log2(L)/2) ops per
# element of the scanned array (L = scanned-axis length) — an
# empirical fit, exact for L in {128, 256} and within 2% down to L=16
_CUMULATIVE = {"cumsum", "cummax", "cummin", "cumprod", "cumlogsumexp"}

# call-like primitives whose cost is their sub-jaxpr's cost
_CALL_PRIMS = {
    "jit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "remat2",
    "checkpoint", "custom_lin", "shard_map", "named_call",
}


def _dot_flops(eqn):
    """2 * output-elements * contraction-size (one FMA = 2 flops)."""
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    k = 1
    for d in eqn.params["dimension_numbers"][0][0]:
        k *= lhs.shape[d]
    return 2 * _elems(out) * k


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval        # [spatial..., in_feat/g, out_feat]
    groups = int(eqn.params.get("feature_group_count", 1))
    kernel = _elems(rhs) // max(1, rhs.shape[-1])   # per output feature
    return 2 * _elems(out) * kernel // max(1, groups)


def _integer_pow_flops(eqn):
    # XLA expands x**n into O(log n) multiplies
    n = abs(int(eqn.params.get("y", 2)))
    return _elems(eqn.outvars[0].aval) * max(1, int(math.log2(max(n, 2))))


def _eqn_flops(eqn):
    """(flops, transcendentals) for one leaf equation."""
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_flops(eqn), 0
    if name == "conv_general_dilated":
        return _conv_flops(eqn), 0
    if name == "integer_pow":
        return _integer_pow_flops(eqn), 0
    if name in _ELEMENTWISE_FLOP:
        return sum(_elems(ov.aval) for ov in eqn.outvars), 0
    if name in _TRANSCENDENTAL:
        return 0, sum(_elems(ov.aval) for ov in eqn.outvars)
    if name in _REDUCTIONS:
        return sum(_elems(iv.aval) for iv in eqn.invars
                   if hasattr(iv, "aval")), 0
    if name in _CUMULATIVE:
        out = eqn.outvars[0].aval
        axis = eqn.params.get("axis", 0)
        length = max(2, out.shape[axis] if out.shape else 1)
        return int(_elems(out) * (13 + math.log2(length) / 2)), 0
    if name == "sort":
        # XLA's estimate: N log2 N comparisons over the whole array
        # (co-sorted operands ride the same comparisons for free)
        n = max(2, _elems(eqn.invars[0].aval))
        return int(n * math.ceil(math.log2(n))), 0
    if name in ("scatter-add", "scatter_add", "scatter-mul"):
        return _elems(eqn.invars[-1].aval), 0
    return 0, 0


def _collective_payload(eqn, mult):
    """{axis: bytes} one collective moves over the interconnect per
    device.  Ring all-reduce moves ~2x the payload, all_gather /
    reduce_scatter ~1x; the constant factors matter less than the axis
    attribution, so payload bytes x a small factor is reported."""
    name = eqn.primitive.name
    payload = sum(_nbytes(iv.aval) for iv in eqn.invars
                  if hasattr(iv, "aval"))
    factor = 2 if name in ("psum", "pmax", "pmin", "pmean",
                           "psum_scatter") else 1
    out = {}
    for ax in _collective_axes(eqn):
        out[ax] = out.get(ax, 0) + payload * factor * mult
    return out


# --------------------------------------------------------------------------
# the estimate
# --------------------------------------------------------------------------
class CostEstimate:
    """Static cost of one executable launch.

    flops            -- loop-aware float ops (scan bodies x trip count)
    flops_xla_parity -- same rules, loop bodies counted once (XLA's
                        cost_analysis convention, for cross-checking)
    transcendentals  -- exp/tanh/rsqrt/... element count (loop-aware)
    hbm_bytes        -- executable-boundary traffic: args + results,
                        donated aliases counted once
    access_bytes     -- per-eqn operand+result sum (pre-fusion bound)
    peak_bytes       -- donation-aware peak live-buffer bytes
    collective_bytes -- {mesh axis: interconnect bytes per device}
    dynamic_loops    -- number of `while` eqns whose trip count is
                        unknown statically (their bodies count once)
    """

    __slots__ = ("flops", "flops_xla_parity", "transcendentals",
                 "hbm_bytes", "access_bytes", "peak_bytes",
                 "collective_bytes", "dynamic_loops")

    def __init__(self):
        self.flops = 0
        self.flops_xla_parity = 0
        self.transcendentals = 0
        self.hbm_bytes = 0
        self.access_bytes = 0
        self.peak_bytes = 0
        self.collective_bytes = {}
        self.dynamic_loops = 0

    def arithmetic_intensity(self):
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    def roofline(self, profile="tpu-v4"):
        """Classify the launch as compute- / hbm- / comms-bound under a
        device profile (name from DEVICE_PROFILES or a dict)."""
        p = DEVICE_PROFILES[profile] if isinstance(profile, str) \
            else profile
        times = {
            "compute": self.flops / p["flops_per_s"],
            "hbm": self.hbm_bytes / p["hbm_bytes_per_s"],
            "comms": sum(self.collective_bytes.values())
            / p["ici_bytes_per_s"],
        }
        bound = max(times, key=times.get)
        return {"bound": bound, "times_s": times}

    def to_dict(self):
        return {
            "flops": int(self.flops),
            "flops_xla_parity": int(self.flops_xla_parity),
            "transcendentals": int(self.transcendentals),
            "hbm_bytes": int(self.hbm_bytes),
            "access_bytes": int(self.access_bytes),
            "peak_bytes": int(self.peak_bytes),
            "collective_bytes": {k: int(v) for k, v in
                                 sorted(self.collective_bytes.items())},
            "dynamic_loops": int(self.dynamic_loops),
            "arithmetic_intensity":
                round(self.arithmetic_intensity(), 3),
        }


def _walk_cost(j, est, mult):
    """Accumulate flops / transcendentals / access bytes / collective
    payload over ``j`` and its sub-jaxprs, multiplying by loop trip
    counts.  ``mult`` is (loop_aware_multiplier, xla_multiplier)."""
    m_loop, m_xla = mult
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            for sub in _subjaxprs(eqn):
                _walk_cost(_raw(sub), est, (m_loop * length, m_xla))
            continue
        if name == "while":
            est.dynamic_loops += 1
            for sub in _subjaxprs(eqn):
                _walk_cost(_raw(sub), est, mult)
            continue
        if name == "cond":
            # worst case across branches for flops would need a second
            # pass; branches in the serving graphs are tiny, so count
            # every branch (an upper bound) like XLA does
            for sub in _subjaxprs(eqn):
                _walk_cost(_raw(sub), est, mult)
            continue
        if name in _CALL_PRIMS:
            for sub in _subjaxprs(eqn):
                _walk_cost(_raw(sub), est, mult)
            continue
        if name in _COLLECTIVES:
            for ax, b in _collective_payload(eqn, m_loop).items():
                est.collective_bytes[ax] = \
                    est.collective_bytes.get(ax, 0) + b
        fl, tr = _eqn_flops(eqn)
        est.flops += fl * m_loop
        est.flops_xla_parity += fl * m_xla
        est.transcendentals += tr * m_loop
        eqn_bytes = sum(_nbytes(v.aval) for v in eqn.invars
                        if hasattr(v, "aval")) \
            + sum(_nbytes(v.aval) for v in eqn.outvars
                  if not isinstance(v, _DropVar))
        est.access_bytes += eqn_bytes * m_loop


# --------------------------------------------------------------------------
# peak live-buffer liveness
# --------------------------------------------------------------------------
def _call_excess(eqn):
    """Transient bytes a call-like eqn needs BEYOND its own operands and
    results (which the outer walk already accounts): the sub-jaxpr's
    internal peak minus its boundary buffers, clamped at zero."""
    excess = 0
    for sub in _subjaxprs(eqn):
        sj = _raw(sub)
        inner = _jaxpr_peak(sj)
        boundary = sum(_nbytes(v.aval)
                       for v in list(sj.invars) + list(sj.constvars)) \
            + sum(_nbytes(v.aval) for v in sj.outvars
                  if hasattr(v, "aval"))
        excess = max(excess, inner - boundary)
    return excess


def _jaxpr_peak(j):
    """Peak simultaneously-live buffer bytes of one (raw) jaxpr,
    donation-unaware (the caller subtracts aliased donations)."""
    n = len(j.eqns)
    last_use = {}
    for v in list(j.invars) + list(j.constvars):
        last_use[v] = -1            # live from entry ...
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = i
    for v in j.outvars:             # ... outputs live through the end
        if isinstance(v, jcore.Var):
            last_use[v] = n
    alive = sum(_nbytes(v.aval)
                for v in list(j.invars) + list(j.constvars))
    peak = alive
    for i, eqn in enumerate(j.eqns):
        out_b = sum(_nbytes(v.aval) for v in eqn.outvars
                    if not isinstance(v, _DropVar))
        peak = max(peak, alive + out_b + _call_excess(eqn))
        alive += out_b
        freed = set()
        for v in list(eqn.invars) + list(eqn.outvars):
            if isinstance(v, jcore.Var) and v not in freed \
                    and last_use.get(v, n) == i:
                alive -= _nbytes(v.aval)
                freed.add(v)
    return peak


def _boundary_bytes(j, donated_idx):
    """Args read + results written, with each donated input that has a
    shape/dtype-matching output counted ONCE (the pair shares one
    buffer after XLA aliases the donation)."""
    args = sum(_nbytes(v.aval)
               for v in list(j.invars) + list(j.constvars))
    outs = sum(_nbytes(v.aval) for v in j.outvars if hasattr(v, "aval"))
    return args + outs - _donated_alias_bytes(j, donated_idx)


def _donated_alias_bytes(j, donated_idx):
    """Total bytes of donated inputs that found a shape/dtype-matching
    output to alias (greedy matching, each output claimed once)."""
    out_sigs = {}
    for v in j.outvars:
        if hasattr(v, "aval"):
            sig = (tuple(v.aval.shape), jnp.dtype(v.aval.dtype))
            out_sigs[sig] = out_sigs.get(sig, 0) + 1
    saved = 0
    for i in donated_idx:
        if i >= len(j.invars):      # pragma: no cover - defensive
            continue
        v = j.invars[i]
        sig = (tuple(v.aval.shape), jnp.dtype(v.aval.dtype))
        if out_sigs.get(sig, 0) > 0:
            out_sigs[sig] -= 1
            saved += _nbytes(v.aval)
    return saved


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------
def estimate_jaxpr(closed, donated=(), loop_aware=True):
    """CostEstimate for a (Closed)Jaxpr.  ``donated`` is an iterable of
    flat input indices whose buffers the caller gives up."""
    j = _raw(closed)
    est = CostEstimate()
    _walk_cost(j, est, (1, 1))      # both conventions in one walk
    if not loop_aware:              # parity mode: report parity as flops
        est.flops = est.flops_xla_parity
    donated = tuple(donated)
    est.hbm_bytes = _boundary_bytes(j, donated)
    est.peak_bytes = _jaxpr_peak(j) - _donated_alias_bytes(j, donated)
    return est


def estimate_jitted(fn, *args, loop_aware=True):
    """Trace a jitted callable over ``args`` (arrays or
    ``jax.ShapeDtypeStruct`` stand-ins) and estimate its cost.  AOT
    tracing only: nothing executes and the dispatch cache stays cold."""
    if not hasattr(fn, "trace"):
        fn = jax.jit(fn)
    traced = fn.trace(*args)
    infos = jtu.tree_leaves(traced.lower().args_info)
    donated = tuple(i for i, info in enumerate(infos)
                    if getattr(info, "donated", False))
    return estimate_jaxpr(traced.jaxpr, donated=donated,
                          loop_aware=loop_aware)


def xla_cost_analysis(fn, *args):
    """XLA's own numbers for the same launch:
    ``trace().lower().compile().cost_analysis()`` — the cross-check for
    the static rules (AOT compile; the jit dispatch cache stays cold).
    Returns at least {"flops", "bytes accessed", "transcendentals"}."""
    if not hasattr(fn, "trace"):
        fn = jax.jit(fn)
    return dict(fn.trace(*args).lower().compile().cost_analysis())


# --------------------------------------------------------------------------
# C001 — collective placement
# --------------------------------------------------------------------------
def check_collectives(closed, label=""):
    """C001 findings over one jaxpr:

    - a collective inside a ``scan``/``while`` body whose operand is
      loop-INVARIANT (derives only from loop constants): the identical
      reduction runs every iteration and belongs outside the loop;
    - redundant back-to-back collectives: a psum/all_gather consuming
      the direct output of the same collective on the same axes.

    Collectives on loop-carried values (the engine's per-layer psums in
    the decoder scan) are the normal pattern and stay clean.
    """
    findings = []

    def loc(path):
        return "/".join((label,) + path) if label else \
            "/".join(path) or "<jaxpr>"

    def rec(j, path, in_loop, invariant):
        producers = {}
        for i, eqn in enumerate(j.eqns):
            name = eqn.primitive.name
            here = path + (f"eqn {i} ({name})",)
            if name in _COLLECTIVES:
                axes = tuple(_collective_axes(eqn))
                data_in = [v for v in eqn.invars
                           if isinstance(v, jcore.Var)]
                if in_loop and data_in and \
                        all(v in invariant for v in data_in):
                    findings.append(Finding(
                        "C001", ERROR, loc(here),
                        f"collective '{name}' over axes {axes} inside "
                        f"a {in_loop} body reduces a loop-invariant "
                        "value — the same result is recomputed every "
                        "iteration; hoist it out of the loop"))
                for v in data_in:
                    prev = producers.get(v)
                    if prev is not None and \
                            prev[0] == name and prev[1] == axes:
                        findings.append(Finding(
                            "C001", ERROR, loc(here),
                            f"'{name}' over axes {axes} consumes the "
                            f"output of an identical '{name}' on the "
                            "same axes — back-to-back collectives are "
                            "redundant (or a missing-scale bug)"))
            # outputs derived only from invariant inputs stay invariant
            ins = [v for v in eqn.invars if isinstance(v, jcore.Var)]
            if all(v in invariant for v in ins):
                for ov in eqn.outvars:
                    if not isinstance(ov, _DropVar):
                        invariant = invariant | {ov}
            if eqn.primitive.name in _COLLECTIVES:
                for ov in eqn.outvars:
                    if not isinstance(ov, _DropVar):
                        producers[ov] = (name,
                                         tuple(_collective_axes(eqn)))
            for sub in _subjaxprs(eqn):
                sj = _raw(sub)
                if name == "scan":
                    nc = int(eqn.params.get("num_consts", 0))
                    inv = set(sj.constvars) | set(sj.invars[:nc])
                    rec(sj, here, "scan", inv)
                elif name == "while":
                    # cond/body consts are the invariants
                    nc = int(eqn.params.get("body_nconsts",
                                            eqn.params.get("nconsts", 0)))
                    inv = set(sj.constvars) | set(sj.invars[:nc])
                    rec(sj, here, "while", inv)
                else:
                    # call-like: propagate invariance through the call
                    inv = set(sj.constvars)
                    for outer, inner in zip(eqn.invars, sj.invars):
                        if isinstance(outer, jcore.Var) and \
                                outer in invariant:
                            inv.add(inner)
                        elif isinstance(outer, jcore.Literal):
                            inv.add(inner)
                    rec(sj, here, in_loop, inv)

    rec(_raw(closed), (), "", set())
    return findings


# --------------------------------------------------------------------------
# engine memory model (pages + weights -> admissible batch)
# --------------------------------------------------------------------------
def engine_memory_model(engine, memory_budget=None, host_budget=None):
    """Per-chip HBM model of a live LLMEngine: weight bytes (sharding-
    aware — leaves whose PartitionSpec names 'mp' divide by tp), paged
    K/V pool bytes, per-page and per-sequence bytes, and — when a
    budget is declared — the admissible ``max_batch`` the budget
    supports (ROADMAP item 3's "pages + weights bound max_batch").

    The hierarchical-KV host tier (``kv_tier=``) is priced beside HBM:
    the host pool and prefix store budgets, the GLOBAL per-page
    payload they hold (``page_bytes * tp`` — a demoted chain carries
    every shard's pages), and — when ``host_budget`` is declared — how
    many tier pages that host-RAM budget admits."""
    tp = getattr(engine, "tp", 1)

    # params and _param_specs are dicts with the same key structure, so
    # their sorted-key leaf orders align; a leaf whose PartitionSpec
    # names 'mp' anywhere holds 1/tp of the global weight per chip
    def _sharded(spec):
        for part in tuple(spec):
            axes = part if isinstance(part, tuple) else (part,)
            if "mp" in axes:
                return True
        return False

    leaves = jtu.tree_leaves(engine.params)
    specs = jtu.tree_leaves(engine._param_specs,
                            is_leaf=lambda x: isinstance(x, P))
    weights = 0
    for leaf, spec in zip(leaves, specs):
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        weights += nbytes // tp if _sharded(spec) else nbytes

    # adapter residency (multi-LoRA): the lora.* pool leaves live in
    # params["blocks"] beside the base weights, so weights_bytes above
    # already counts them — this breaks them out so M001 (and any HBM
    # planner) can see what the adapter slots cost on their own
    lora = 0
    blocks = engine.params.get("blocks", {})
    for key in blocks:
        if not key.startswith("lora."):
            continue
        leaf = blocks[key]
        spec = engine._param_specs["blocks"][key]
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        lora += nbytes // tp if _sharded(spec) else nbytes

    # an int8-quantized pool stores 1 byte per element plus one f32
    # scale per (head, slot) — head_dim + 4 bytes per slot instead of
    # head_dim * itemsize, matching the engine's own page_bytes
    kv_quant = bool(getattr(engine, "_kv_quant", False))
    itemsize = jnp.dtype(engine.dtype).itemsize
    slot = (engine.head_dim + 4 if kv_quant
            else engine.head_dim * itemsize)
    nh_local = engine.num_heads // tp
    page = (2 * engine.num_layers * engine.block_size * nh_local
            * slot)                                # K + V, per chip
    pool = engine.num_blocks * page
    seq = engine.max_pages * page
    budget = parse_bytes(memory_budget
                         if memory_budget is not None
                         else getattr(engine, "memory_budget", None))
    model = {
        "tp": tp,
        "kv_quantized": kv_quant,
        "weights_bytes": int(weights),
        "lora_pool_bytes": int(lora),
        "page_bytes": int(page),
        "kv_pool_bytes": int(pool),
        "seq_bytes": int(seq),
        "max_pages": int(engine.max_pages),
        "num_blocks": int(engine.num_blocks),
        "memory_budget": budget,
    }
    # hierarchical KV (inference/llm/kv_tier.py): the host-RAM tier is
    # a SECOND memory budget beside HBM — report its configured pool/
    # store sizes in the same model so M001 (and any planner) sees
    # both, plus what one tier page costs (global payload: every
    # shard's slice of the page rides the demote)
    tier = getattr(engine, "kv_tier", None)
    host_page = int(page) * tp
    model["host_pool_bytes"] = int(tier.host_bytes) if tier else 0
    model["prefix_store_bytes"] = int(tier.store_bytes) if tier else 0
    model["host_page_bytes"] = host_page
    model["host_tier_pages"] = (
        (model["host_pool_bytes"] + model["prefix_store_bytes"])
        // host_page)
    hb = parse_bytes(host_budget)
    model["host_budget"] = hb
    if hb is not None:
        model["host_budget_pages"] = int(hb // host_page)
    if budget is not None:
        try:
            model["derived_max_batch"] = derive_max_batch(
                budget, weights, seq)
        except ValueError:
            # census reports the overrun as M001 instead of raising;
            # LLMEngine(memory_budget=) calls derive_max_batch directly
            # and keeps the fail-fast behaviour
            model["derived_max_batch"] = 0
    return model


def derive_max_batch(memory_budget, weights_bytes, seq_bytes):
    """pages + weights -> admissible batch: how many full-length
    sequences' pages fit beside the weights on one chip."""
    budget = parse_bytes(memory_budget)
    free = budget - int(weights_bytes)
    if free < seq_bytes:
        raise ValueError(
            f"memory_budget {_fmt_bytes(budget)} cannot hold the "
            f"weights ({_fmt_bytes(int(weights_bytes))}) plus one "
            f"max_model_len sequence ({_fmt_bytes(int(seq_bytes))} of "
            "pages) — raise the budget or shrink max_model_len")
    return int(free // int(seq_bytes))


def migration_estimate(engine, num_tokens, num_pages, profile="tpu-v4",
                       link_bytes_per_s=None):
    """Static migrate-vs-recompute estimate for one sequence's KV
    handoff (the fleet MigrationPolicy's decision inputs).

    Moving the sequence costs its GLOBAL K+V page payload
    (``num_pages`` pages at ``page_bytes * tp``) over the
    replica-to-replica link; recomputing it costs a fresh prefill of
    ``num_tokens`` tokens through the weights (2 flops per parameter
    per token — the standard dense-decoder estimate; attention flops
    are second-order at serving lengths).  Both counts are
    hardware-independent; ``profile`` (a DEVICE_PROFILES key) only
    converts them to seconds, with ``link_bytes_per_s`` overriding the
    profile's ICI rate for the transfer term.

    Returns {bytes_moved, migrate_s, recompute_flops, recompute_s,
    prefer} with ``prefer`` in ("migrate", "recompute")."""
    prof = DEVICE_PROFILES[profile]
    tp = getattr(engine, "tp", 1)
    model = engine_memory_model(engine)
    bytes_moved = int(num_pages) * model["page_bytes"] * tp
    n_params = sum(int(np.prod(leaf.shape)) if leaf.shape else 1
                   for leaf in jtu.tree_leaves(engine.params))
    flops = 2.0 * n_params * int(num_tokens)
    link = (float(link_bytes_per_s) if link_bytes_per_s
            else prof["ici_bytes_per_s"])
    migrate_s = bytes_moved / link
    recompute_s = flops / prof["flops_per_s"]
    return {"bytes_moved": int(bytes_moved),
            "migrate_s": migrate_s,
            "recompute_flops": int(flops),
            "recompute_s": recompute_s,
            "prefer": ("migrate" if migrate_s <= recompute_s
                       else "recompute")}


def speculative_draft_estimate(engine, profile="tpu-v4"):
    """Static per-step cost of the model-based draft phase.

    The draft model rides the SAME ragged executable family as the
    target (its padding layers are zeroed, not removed — a zero block
    still multiplies at full price on device), so one draft launch
    costs exactly one target launch of its bucket.  A K-deep greedy
    chain costs one catch-up launch plus K-1 single-token decode
    launches per step, all at the smallest decode bucket in the common
    case.  The estimate prices that against the dense 2-flops-per-
    param-per-token decode bound: worthwhile speculation needs the
    acceptance rate to beat ``flops_overhead_ratio / (1 + K)`` — the
    break-even line PERF.md rows quote.

    Returns {draft_launches_per_step, draft_flops_per_step,
    target_flops_per_token, flops_overhead_ratio, break_even_acceptance}
    or None when the engine has no model-based drafter."""
    spec = getattr(engine, "spec", None)
    if spec is None or not getattr(spec, "uses_draft_model", False):
        return None
    k = int(spec.num_tokens)
    n_params = sum(int(np.prod(leaf.shape)) if leaf.shape else 1
                   for leaf in jtu.tree_leaves(engine.params))
    per_tok = 2.0 * n_params
    launches = k                      # 1 catch-up + (K-1) chain steps
    draft_flops = per_tok * launches  # ~1 token per launch steady-state
    ratio = draft_flops / per_tok / (1 + k)
    return {"draft_launches_per_step": launches,
            "draft_flops_per_step": int(draft_flops),
            "target_flops_per_token": int(per_tok),
            "flops_overhead_ratio": draft_flops / per_tok,
            "break_even_acceptance": ratio}


def measured_host_overhead_s(engine):
    """Event-log-calibrated per-launch host overhead for
    :class:`StepTimeModel`: the engine's accumulated critical-path
    planning time (schedule + pack + staged-claim validation — the
    ``host_plan_s`` lifecycle gauge) divided by its launch count.
    Feed the result back as ``StepTimeModel(host_overhead_s=...)`` so
    the simulator's clock carries the measured scheduling cost of THIS
    workload — with ``lookahead=True``, staged-claimed steps
    contribute only their validation slice, so the calibrated value
    (and hence the sim) automatically credits the pipeline."""
    stats = engine.lifecycle_stats()
    n = getattr(engine, "_launch_count", 0)
    if not n:
        return 0.0
    return float(stats.get("host_plan_s") or 0.0) / n


# --------------------------------------------------------------------------
# per-launch step-time model (the discrete-event simulator's clock)
# --------------------------------------------------------------------------
class StepTimeModel:
    """Roofline step-time estimates per ``(kind, bucket)`` executable —
    what the discrete-event simulator (paddle_tpu/sim/) advances its
    virtual clock by in place of running the device.

    Built from an engine's own ``executable_grid()`` by AOT tracing
    (:func:`estimate_jitted` — nothing executes, dispatch caches stay
    cold), so the estimates are automatically tp- and quantize-aware:
    the sharded / int8 grid IS the grid that gets costed.  A launch's
    time is the roofline bound — ``max(compute, hbm, comms)`` seconds
    under the device ``profile`` (a DEVICE_PROFILES key or a dict) —
    plus a flat ``host_overhead_s`` covering scheduling, packing, and
    dispatch (calibrate it against a measured run; 0 by default).
    """

    def __init__(self, times_s, profile="tpu-v4", host_overhead_s=0.0):
        self.times_s = dict(times_s)      # (kind, bucket) -> seconds
        self.profile = profile
        self.host_overhead_s = float(host_overhead_s)

    @classmethod
    def from_engine(cls, engine, profile="tpu-v4", host_overhead_s=0.0,
                    loop_aware=True):
        times = {}
        for kind, bucket, fn, args in engine.executable_grid():
            est = estimate_jitted(fn, *args, loop_aware=loop_aware)
            rl = est.roofline(profile)
            times[(kind, bucket)] = max(rl["times_s"].values())
        return cls(times, profile=profile,
                   host_overhead_s=host_overhead_s)

    def step_seconds(self, kind, bucket):
        """Estimated seconds of one ``(kind, bucket)`` launch."""
        try:
            t = self.times_s[(kind, bucket)]
        except KeyError:
            raise KeyError(
                f"no step-time estimate for launch ({kind!r}, "
                f"{bucket!r}) — this model covers "
                f"{sorted(self.times_s)}; build it from an engine "
                f"configured like the one being simulated") from None
        return t + self.host_overhead_s

    def launches_seconds(self, launches):
        """Total estimated seconds of one step's launch list (the
        engine's ``last_launches``: [(kind, bucket), ...])."""
        return sum(self.step_seconds(k, b) for k, b in launches)

    def tier_seconds(self, nbytes, link_bytes_per_s=None):
        """Seconds to move ``nbytes`` of page payload over the
        host-HBM link — the hierarchical-KV traffic a step reports as
        ``last_tier_bytes`` (demotes, swap-ins, store promotes and
        adopts).  Priced at the profile's ICI rate by default — the
        same rate TierPolicy's swap-vs-recompute estimate uses, so the
        simulator's clock and the policy's break-even agree."""
        if not nbytes:
            return 0.0
        prof = (DEVICE_PROFILES[self.profile]
                if isinstance(self.profile, str) else self.profile)
        link = (float(link_bytes_per_s) if link_bytes_per_s
                else prof["ici_bytes_per_s"])
        return int(nbytes) / link

    def to_dict(self):
        return {
            "profile": (self.profile if isinstance(self.profile, str)
                        else "custom"),
            "host_overhead_s": self.host_overhead_s,
            "times_s": {f"{k}[{b}]": t
                        for (k, b), t in sorted(self.times_s.items())},
        }


# --------------------------------------------------------------------------
# the executable census
# --------------------------------------------------------------------------
class Census:
    """Cost census over an engine's full warmup grid.

    entries        -- [{kind, bucket, label, cost...}] per executable
    compile_count  -- total executables warmup() will compile (the B001
                      baseline; asserted == CompileWatcher-observed)
    families       -- {kind: count}
    totals         -- summed flops / bytes over the grid
    memory         -- engine_memory_model() breakdown
    findings       -- M001 / C001 / B001 Finding records
    """

    def __init__(self, entries, families, memory, findings, profile):
        self.entries = entries
        self.families = families
        self.memory = memory
        self.findings = findings
        self.profile = profile
        self.compile_count = len(entries)

    @property
    def totals(self):
        keys = ("flops", "flops_xla_parity", "transcendentals",
                "hbm_bytes", "access_bytes")
        tot = {k: sum(e["cost"][k] for e in self.entries) for k in keys}
        tot["max_peak_bytes"] = max(
            (e["cost"]["peak_bytes"] for e in self.entries), default=0)
        tot["collective_bytes"] = {}
        for e in self.entries:
            for ax, b in e["cost"]["collective_bytes"].items():
                tot["collective_bytes"][ax] = \
                    tot["collective_bytes"].get(ax, 0) + b
        return tot

    def to_dict(self):
        return {
            "compile_count": self.compile_count,
            "families": dict(self.families),
            "profile": self.profile,
            "entries": self.entries,
            "totals": self.totals,
            "memory": self.memory,
            "findings": [
                {"rule": f.rule, "severity": f.severity,
                 "where": f.where, "message": f.message}
                for f in self.findings],
        }

    def to_json(self, **kw):
        return json.dumps(self.to_dict(), **kw)


def run_census(engine, *, memory_budget=None, host_budget=None,
               profile="tpu-v4", max_executables=64, loop_aware=True):
    """Enumerate the engine's full warmup grid (chunk x decode x verify,
    tp-aware), cost every executable, and run M001/C001/B001.

    AOT-only: traces and lowers, never executes — the engine's
    executable caches stay cold (the caches-stay-cold test covers this
    path).  ``memory_budget`` (bytes or '16GiB') overrides the
    engine's own declared budget for the M001 check; with neither, the
    M001 rule is skipped and the memory model is still reported.
    ``host_budget`` declares the host-RAM ceiling the hierarchical-KV
    tier (``kv_tier=``) must fit under — tier budgets past it are an
    M001 too, and every M001 message names BOTH budgets when a host
    tier is configured (one census, two memories).
    """
    entries = []
    families = {}
    findings = []
    for kind, bucket, fn, args in engine.executable_grid():
        label = f"{kind}[{bucket}]"
        est = estimate_jitted(fn, *args, loop_aware=loop_aware)
        closed = fn.trace(*args).jaxpr
        findings += check_collectives(closed, label=label)
        families[kind] = families.get(kind, 0) + 1
        entries.append({
            "kind": kind,
            "bucket": bucket if not isinstance(bucket, tuple)
            else list(bucket),
            "label": label,
            "cost": est.to_dict(),
            "roofline": est.roofline(profile)["bound"],
        })

    memory = engine_memory_model(engine, memory_budget=memory_budget,
                                 host_budget=host_budget)
    budget = memory.get("memory_budget")
    host_bytes = (memory["host_pool_bytes"]
                  + memory["prefix_store_bytes"])
    tier_note = ""
    if host_bytes:
        tier_note = (
            f"; host tier holds {_fmt_bytes(host_bytes)} beside it "
            f"(pool {_fmt_bytes(memory['host_pool_bytes'])} + store "
            f"{_fmt_bytes(memory['prefix_store_bytes'])}"
            + (f" under host budget "
               f"{_fmt_bytes(memory['host_budget'])}"
               if memory.get("host_budget") is not None else "")
            + ")")
    if budget is not None:
        weights = memory["weights_bytes"]
        pool = memory["kv_pool_bytes"]
        for e in entries:
            # per-chip peak = resident weights + pool (exact, sharding-
            # aware) + the launch's transient excess over its boundary
            transient = max(0, e["cost"]["peak_bytes"]
                            - e["cost"]["hbm_bytes"])
            est_peak = weights + pool + transient
            e["est_chip_peak_bytes"] = int(est_peak)
            if est_peak > budget:
                seq = memory["seq_bytes"]
                admissible = ((budget - weights) // seq
                              if budget - weights >= seq else 0)
                lora_bytes = memory.get("lora_pool_bytes", 0)
                lora_note = (
                    f" (of which LoRA adapter pools "
                    f"{_fmt_bytes(lora_bytes)})" if lora_bytes else "")
                findings.append(Finding(
                    "M001", ERROR, e["label"],
                    f"estimated per-chip peak {_fmt_bytes(est_peak)} "
                    f"exceeds the declared budget {_fmt_bytes(budget)} "
                    f"— weights {_fmt_bytes(weights)}{lora_note} + "
                    f"KV pages "
                    f"{_fmt_bytes(pool)} ({memory['num_blocks']} "
                    f"blocks x {_fmt_bytes(memory['page_bytes'])}) + "
                    f"transients {_fmt_bytes(transient)}; at "
                    f"{_fmt_bytes(seq)}/sequence the budget supports "
                    f"max_batch <= {admissible}{tier_note}"))

    # host-tier residency check: the configured tier budgets must fit
    # the declared host-RAM ceiling — the host side of M001
    hb = memory.get("host_budget")
    if hb is not None and host_bytes > hb:
        host_page = memory["host_page_bytes"]
        findings.append(Finding(
            "M001", ERROR, "kv_tier",
            f"hierarchical-KV tier budgets total "
            f"{_fmt_bytes(host_bytes)} (host pool "
            f"{_fmt_bytes(memory['host_pool_bytes'])} + prefix store "
            f"{_fmt_bytes(memory['prefix_store_bytes'])}) — over the "
            f"declared host budget {_fmt_bytes(hb)}; at "
            f"{_fmt_bytes(host_page)}/page (global payload) the host "
            f"budget admits {memory['host_budget_pages']} tier pages"
            + (f"; HBM budget {_fmt_bytes(budget)} beside it"
               if budget is not None else "")))

    if max_executables is not None and len(entries) > max_executables:
        fam = ", ".join(f"{k}: {v}" for k, v in sorted(families.items()))
        findings.append(Finding(
            "B001", ERROR, "census",
            f"warmup grid compiles {len(entries)} executables "
            f"(threshold {max_executables}) — {fam}. The shipped grid "
            "is ONE ragged family, O(log token_budget) buckets; growth "
            "past the threshold means a new executable kind (or an "
            "unbucketed shape) leaked past the ragged collapse this "
            "census count is the regression baseline for"))

    return Census(entries, families, memory, findings, profile)
