"""Kernel registry — the contract surface for the K005 lint rule.

Every Pallas kernel entry point in this package registers itself with
:func:`register_kernel`, declaring the three things a kernel must never
ship without:

- **fallback** — a lazily-resolved ``"module.path:attr"`` string naming
  the XLA composition with identical semantics (lazy so registration
  never imports the serving stack and cannot create import cycles);
- **parity** — a pytest node id (``tests/file.py::Class::test``) for the
  interpret-mode parity test that pins kernel-vs-fallback numerics on
  CPU, where the dev loop actually runs;
- **engine_shapes** — a builder mapping a live ``LLMEngine`` to the
  concrete ``(label, traceable_fn, abstract_args, scalar_bounds)``
  cases the kernel is launched with across the engine's bucket grid, so
  ``graph-lint kernels`` sweeps the registry over the shapes serving
  really compiles, not a synthetic corpus.  ``scalar_bounds`` maps
  scalar-prefetch operand positions to inclusive ``(lo, hi)`` value
  ranges (e.g. block-table entries are page ids in
  ``[0, num_blocks - 1]``), which is what lets K003 prove index maps
  in-bounds through the prefetch indirection.

A kernel the TPU compiler does not accept yet also declares
**tpu_refusal** — the reason, which its TPU gates raise; every
other entry must lower for TPU wherever its ``supports()`` says yes
(tests/test_tpu_lowering.py holds both to it).

The decorator is a zero-overhead passthrough: it records the entry and
returns the function unchanged, so registration costs nothing on the
serving hot path.  :mod:`paddle_tpu.framework.kernel_lint` consumes the
registry; nothing here imports jax.
"""

import importlib
from collections import namedtuple

__all__ = [
    "KernelCase", "KernelEntry", "register_kernel", "kernel_registry",
    "load_all", "resolve_fallback", "KERNEL_MODULES",
]

# One lint/sweep case: ``fn(*args)`` must be traceable by jax.make_jaxpr
# (args are ShapeDtypeStructs) and reach the kernel's pallas_call —
# entries whose backward matters wrap fn in jax.grad so the sweep sees
# the bwd kernels too.
KernelCase = namedtuple("KernelCase",
                        ["label", "fn", "args", "scalar_bounds"])

# Modules that define kernels; ``load_all`` imports exactly these so a
# registry consumer sees every entry without importing the whole tree.
KERNEL_MODULES = (
    "attention_kernel",
    "eva_attention_kernel",
    "ssd_scan_kernel",
    "causal_conv_kernel",
    "mla_expand_kernel",
    "moe_run_sum_kernel",
    "decode_attention_kernel",
    "ragged_attention_kernel",
    "layernorm_kernel",
    "gated_norm_kernel",
    "cca_mix_kernel",
)

_REGISTRY = {}


class KernelEntry:
    """One registered kernel entry point (see module docstring)."""

    __slots__ = ("name", "fn", "fallback", "parity", "engine_shapes",
                 "supports", "grad", "tpu_refusal")

    def __init__(self, name, fn, fallback, parity, engine_shapes,
                 supports, grad, tpu_refusal):
        self.name = name
        self.fn = fn
        self.fallback = fallback
        self.parity = parity
        self.engine_shapes = engine_shapes
        self.supports = supports
        self.grad = grad
        self.tpu_refusal = tpu_refusal

    def __repr__(self):
        return f"KernelEntry({self.name!r} -> {self.fallback!r})"


def register_kernel(name, *, fallback, parity, engine_shapes,
                    supports=None, grad=False, tpu_refusal=None):
    """Decorator registering a kernel entry point under ``name``.

    ``supports`` is the module's hand-written shape gate (consulted by
    the supports-vs-lint consistency tests); ``grad=True`` declares that
    the entry differentiates through a custom_vjp and its
    ``engine_shapes`` cases include a grad-traced case covering the
    backward kernels.  ``tpu_refusal`` names why the kernel does not
    compile for TPU yet (interpret-mode only); None for all others.
    """
    def deco(fn):
        _REGISTRY[name] = KernelEntry(name, fn, fallback, parity,
                                      engine_shapes, supports, grad,
                                      tpu_refusal)
        return fn
    return deco


def unregister(name):
    """Remove an entry (test hook for seeded-contract-violation specs)."""
    return _REGISTRY.pop(name, None)


def load_all():
    """Import every kernel module, then return the full registry."""
    for mod in KERNEL_MODULES:
        importlib.import_module(f"{__package__}.{mod}")
    return dict(_REGISTRY)


def kernel_registry():
    return load_all()


def resolve_fallback(entry):
    """Resolve an entry's ``"module.path:attr"`` fallback to a callable.

    Raises (ImportError/AttributeError/ValueError) when the contract is
    broken — K005 converts that into a finding.
    """
    spec = entry.fallback if isinstance(entry, KernelEntry) else entry
    if not spec or ":" not in spec:
        raise ValueError(f"fallback spec {spec!r} is not 'module:attr'")
    mod_name, _, attr = spec.partition(":")
    fn = getattr(importlib.import_module(mod_name), attr)
    if not callable(fn):
        raise ValueError(f"fallback {spec!r} resolved to a non-callable")
    return fn
