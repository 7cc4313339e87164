"""Activation recompute as a user API.

Reference: python/paddle/distributed/fleet/recompute/recompute.py:332
(``recompute``), ``recompute_sequential`` (:456).  TPU-native design: the
function is wrapped in ``jax.checkpoint`` — its VJP recomputes the forward
from the inputs instead of saving intermediates.  That one primitive covers
both the eager tape (the recorded pullback holds only the inputs) and the
compiled paths (XLA rematerializes inside jit), replacing the reference's
hand-rolled RecomputeFunction/PyLayer machinery.

Policy knobs map to ``jax.checkpoint_policies``: ``checkpoint="full"``
saves nothing (default), ``"dots"`` saves matmul results
(dots_saveable), ``"nothing_saveable"``/``"everything_saveable"`` pass
through to jax; a list or tuple of names keeps only the values tagged with
them by ``jax.ad_checkpoint.checkpoint_name`` (``save_only_these_names``:
e.g. ``ops.pallas.attention_kernel.SAVED_BY_NAME``, the flash forward's
output and row statistics); ``True`` is the list :data:`KEPT_BY_BLOCK`.

``remat`` of the trainers (``jit.TrainStep``, ``parallel.SpmdTrainStep``:
one meaning, resolved here by :func:`_resolve_policy`).  Rematerialisation
is BY BLOCK: each of the model's repeated blocks runs under
``jax.checkpoint``, so its forward is run again in the backward pass and
one block's activations are live at a time.  ``False`` rematerialises
nothing.  ``True`` runs a block's forward again EXCEPT what the block
tagged as dear to make again (:data:`KEPT_BY_BLOCK`: the results of its
parallel layers, a row-parallel one's ``mp`` all-reduce with them, and of
its attention kernels): norms, activations, adds and reshapes are made
again, and of the matmuls only a product by heads over a weight held under
its ``mesh_view`` (the comment at the constant says why).  ``"full"`` keeps
nothing of a block (every op of its forward runs twice); any other policy
name above is jax's; a list of ``checkpoint_name`` tags keeps exactly what
it lists.  A kept value is the bits the re-run would make: no value changes
with ``remat``, only time and memory.  ``compile_account()["remat_kept"]``
says what the built step keeps.

The program's own tags (:data:`PROJECTIONS`, put on by :func:`tagged`) are
written only while a block is traced under :func:`checkpointed` with a
policy that keeps them, which is how both trainers and :func:`recompute`
rematerialise: a step that keeps nothing by name holds no tag, so its
lowered text is what it was before the tags existed.  A bare
``jax.checkpoint`` round a layer sees the kernels' tags only.
"""

import functools

import jax
from jax.ad_checkpoint import checkpoint_name

from ...core.tensor import Tensor
from ...ops.dispatch import apply_op
from ...ops.pallas.attention_kernel import SAVED_BY_NAME as _FLASH
from ...ops.pallas.eva_attention_kernel import SAVED_BY_NAME as _EVA

# The tags of the parallel projections' results (``meta_parallel/mp_layers.py``
# puts them on and exports them as its ``SAVED_BY_NAME``): a column-parallel
# layer's result (bias added); a row-parallel layer's LOGICAL result, i.e.
# after the ``mp`` reduction of the chips' partial products; and the
# column-parallel product of a weight held under its ``mesh_view``
# (``models/gpt.py _qkv_by_heads``: q, k and v by heads).
PROJECTIONS = ("column_parallel_out", "row_parallel_out",
               "column_parallel_by_heads_out")

# What ``True`` keeps of a rematerialised block: the kernels' results and the
# parallel layers' (a matmul, a kernel call or a collective each).  WHICH of
# the projections' tags are worth their memory was decided once, on the chip,
# from four compiled steps of the four-chip GPT cell (PERF.md section 6, PR
# 52): the fastest whose step the compiler's account keeps under 15.0 GB.
# The product by heads (that cell's ``qkv``) is the one left out: with it
# the step is 7 ms of 256 faster and reserves 15.63 GB of the 15.75 the
# chip's compiler takes; a list may name it.
KEPT_BY_BLOCK = _FLASH + _EVA + PROJECTIONS[:2]

_POLICIES = {
    None: None,
    "full": None,  # save nothing; recompute everything
    "dots": "dots_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims": "dots_with_no_batch_dims_saveable",
    "nothing_saveable": "nothing_saveable",
    "everything_saveable": "everything_saveable",
}


def _names(policy):
    """The tags a ``policy=`` / ``remat=`` value keeps by name, ``None``
    where it is a policy's name."""
    if policy is True:
        return KEPT_BY_BLOCK
    return tuple(policy) if isinstance(policy, (list, tuple)) else None


def _resolve_policy(name):
    """The ``jax.checkpoint`` policy of a ``policy=`` / ``remat=`` value
    (the module's header): ``None`` keeps nothing."""
    names = _names(name)
    if names is not None:
        return jax.checkpoint_policies.save_only_these_names(*names)
    key = _POLICIES.get(name, name)
    if key is None:
        return None
    pol = getattr(jax.checkpoint_policies, key, None)
    if pol is None:
        raise ValueError(
            f"unknown recompute policy {name!r}; use one of "
            f"{sorted(k for k in _POLICIES if isinstance(k, str))}")
    return pol


# the tags the block being traced keeps (``checkpointed`` sets it)
_kept = frozenset()


def tagged(x, name):
    """``x`` (an array or a Tensor) under the ``checkpoint_name`` tag
    ``name`` where the block being traced keeps it (:func:`checkpointed`),
    ``x`` itself elsewhere."""
    if name not in _kept:
        return x
    if isinstance(x, Tensor):
        return apply_op("checkpoint_name", checkpoint_name, (x, name), {})
    return checkpoint_name(x, name)


def checkpointed(function, policy):
    """``function`` under ``jax.checkpoint`` with what ``policy`` (a
    ``remat=`` value, the module's header) keeps; while it is traced,
    :func:`tagged` writes the tags the policy lists."""
    names = frozenset(_names(policy) or ())

    @functools.wraps(function)
    def body(*args, **kwargs):
        global _kept
        before, _kept = _kept, names
        try:
            return function(*args, **kwargs)
        finally:
            _kept = before

    return jax.checkpoint(body, policy=_resolve_policy(policy))


def remat_kept(remat):
    """What a step built with ``remat`` keeps of a block, for its record
    (``compile_account()["remat_kept"]``): ``None`` where nothing is
    rematerialised, the list of tags, or the policy's name.  Raises
    ``ValueError`` for a policy nobody knows."""
    if not remat:
        return None
    _resolve_policy(remat)
    names = _names(remat)
    return remat if names is None else list(names)


def _collect_param_tensors(function):
    """Trainable Tensors the function closes over (its Layer's parameters,
    bound-method self, closure cells).  These must become explicit
    differentiable inputs of the recorded recompute op — apply_op only
    differentiates Tensors it can SEE in args, so closed-over layer weights
    would otherwise silently stop training."""
    from ...nn.layer_base import Layer

    found, seen = [], set()

    def add(t):
        if isinstance(t, Tensor) and not t.stop_gradient and \
                id(t) not in seen:
            seen.add(id(t))
            found.append(t)

    def visit(obj, depth=0):
        if isinstance(obj, Layer):
            for p in obj.parameters():
                add(p)
        elif isinstance(obj, Tensor):
            add(obj)
        elif depth == 0 and isinstance(obj, (list, tuple)):
            for o in obj:
                visit(o, depth + 1)

    visit(function)
    self_obj = getattr(function, "__self__", None)
    if self_obj is not None:
        visit(self_obj)
    raw_fn = getattr(function, "__func__", function)
    for cell in getattr(raw_fn, "__closure__", None) or ():
        try:
            visit(cell.cell_contents)
        except ValueError:  # empty cell
            pass
    # globals referenced by name in the code object (a module-level layer
    # used inside the function is not a closure cell)
    code = getattr(raw_fn, "__code__", None)
    fglobals = getattr(raw_fn, "__globals__", None)
    if code is not None and fglobals is not None:
        for name in code.co_names:
            if name in fglobals:
                visit(fglobals[name])
    return found


def recompute(function, *args, use_reentrant=True, preserve_rng_state=True,
              policy=None, **kwargs):
    """Run ``function(*args, **kwargs)`` with activation rematerialization.

    The backward pass recomputes the forward instead of reading saved
    activations — the memory/computation trade the reference implements with
    RecomputeFunction (recompute.py:332).  ``use_reentrant`` and
    ``preserve_rng_state`` are accepted for API parity; rng state is always
    preserved (the dispatch key stream threads keys functionally, so replay
    is deterministic by construction).
    """
    params = _collect_param_tensors(function)
    return apply_op("recompute", _RecomputeFn(function, policy, params),
                    (tuple(args), kwargs, params), {})


class _RecomputeFn:
    """Pure callable so apply_op records one checkpointed node."""

    def __init__(self, function, policy, param_tensors):
        self._fn = function
        self._params = param_tensors
        self._ckpt = checkpointed(self._call, policy)

    def _call(self, args, kwargs, param_vals):
        # apply_op substituted raw arrays where Tensors were; hand the user
        # function Tensors again so arbitrary layer code works inside
        wrap = lambda a: Tensor(a) if isinstance(a, jax.Array) else a
        args = jax.tree_util.tree_map(wrap, args)
        kwargs = jax.tree_util.tree_map(wrap, kwargs)
        # bind traced values into the closed-over parameter Tensors for the
        # duration of the call (restored after; same pattern as QuantedLayer)
        from ...framework import mode
        originals = [p._data for p in self._params]
        try:
            for p, val in zip(self._params, param_vals):
                p._data = val._data if isinstance(val, Tensor) else val
            # grads flow through the enclosing jax trace, not the eager
            # tape — skip per-op vjp recording inside the checkpointed body
            with mode.grad_enabled(False):
                out = self._fn(*args, **kwargs)
        finally:
            for p, orig in zip(self._params, originals):
                p._data = orig
        return jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda v: isinstance(v, Tensor))

    def __call__(self, args, kwargs, param_vals):
        return self._ckpt(args, kwargs, param_vals)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Recompute a paddle.nn.Sequential in segments (reference
    recompute_sequential:456).  ``ctx`` carries {'segments': N}."""
    segments = (ctx or {}).get("segments", 1)
    layers = list(functions)
    seg_size = max(1, len(layers) // max(1, segments))
    out = args[0] if len(args) == 1 else args
    i = 0
    while i < len(layers):
        chunk = layers[i:i + seg_size]

        def seg_fn(x, _chunk=tuple(chunk)):
            for layer in _chunk:
                x = layer(x)
            return x

        out = recompute(seg_fn, out, **kwargs)
        i += seg_size
    return out
