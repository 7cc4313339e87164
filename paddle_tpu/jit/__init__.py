"""paddle_tpu.jit: the dygraph→compiled bridge.

Replaces the reference's dy2static stack (``paddle.jit.to_static`` at
python/paddle/jit/api.py:233: AST transformers → ProgramDesc →
PartialProgramLayer → InterpreterCore).  On TPU there is no program IR of our
own: ``to_static`` traces the eager code with jax tracers flowing through the
same op implementations and compiles via XLA.  ConcreteProgram analog = the
jaxpr cached inside jax.jit; StandaloneExecutor analog = PjRt executable cache.

Key pieces:
- ``functional_call(layer, values, *args)`` — run a Layer with its
  parameters/buffers substituted from a pytree (torch.func-style), the
  functionalization primitive everything else builds on.
- ``to_static(fn_or_layer)`` — compile forward.
- ``TrainStep(model, loss_fn, opt)`` — whole training step (fwd+bwd+optimizer)
  as ONE compiled XLA program: the performance path matching the reference's
  "everything under jit" north star.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..distributed.fleet.recompute import remat_kept
from ..framework import mode
from ..framework.random import get_rng_key, key_stream
from ..nn.layer_base import Layer, block_remat
from ..profiler import StepTrace

_is_tensor = lambda x: isinstance(x, Tensor)


def _bind(layer, values):
    """Swap state_dict tensors' storage to ``values``; return restore list."""
    sd = layer.state_dict()
    saved = []
    for name, arr in values.items():
        t = sd[name]
        saved.append((t, t._data))
        t._data = arr
    return saved, sd


def _restore(saved):
    for t, data in saved:
        t._data = data


def functional_call(layer, values, *args, return_buffers=False,
                    forward_fn=None, **kwargs):
    """Run ``layer(*args, **kwargs)`` with parameters/buffers from ``values``
    (dict name -> jax array).  Inputs may be Tensors or jax arrays.  Returns
    output (jax-array pytree); with ``return_buffers=True`` also returns the
    possibly-updated buffer values (BatchNorm running stats etc.).
    ``forward_fn`` overrides the callable (used by to_static to avoid
    re-entering its own compiled forward)."""
    saved, sd = _bind(layer, values)
    call = forward_fn if forward_fn is not None else layer
    try:
        targs = [Tensor(a) if not isinstance(a, Tensor) and
                 isinstance(a, (jax.Array, np.ndarray)) else a for a in args]
        with mode.grad_enabled(False):
            out = call(*targs, **kwargs)
        out_data = jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=_is_tensor)
        if return_buffers:
            buf = {name: t._data for name, t in sd.items() if name in values}
            return out_data, buf
        return out_data
    finally:
        _restore(saved)


def _split_state(layer):
    """Trainable params vs frozen state (non-trainable params + buffers)."""
    params, others = {}, {}
    for name, t in layer.state_dict().items():
        if isinstance(t, Tensor) and not t.stop_gradient:
            params[name] = t._data
        else:
            others[name] = t._data
    return params, others


class StaticFunction:
    """Compiled forward wrapper (ConcreteProgram/PartialProgramLayer analog,
    reference python/paddle/jit/dy2static/program_translator.py)."""

    def __init__(self, function, layer=None, ir_passes=None):
        self._function = function
        self._layer = layer
        self._cache = {}
        # jaxpr pattern-rewrite passes (framework/ir.py): None/False off,
        # True = all registered, or an explicit sequence of pass names
        self._ir_passes = ir_passes

    def __call__(self, *args, **kwargs):
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs),
                                                     is_leaf=_is_tensor)
        t_pos = tuple(i for i, l in enumerate(leaves) if isinstance(l, Tensor))
        datas = [leaves[i]._data for i in t_pos]
        static_leaves = tuple(
            None if i in t_pos else _hashable(leaves[i])
            for i in range(len(leaves)))
        training = self._layer.training if self._layer is not None else None
        cache_key = (treedef, t_pos, static_leaves, training)

        if cache_key not in self._cache:
            layer = self._layer
            function = self._function
            raw_leaves = list(leaves)

            if layer is not None:
                params, others = _split_state(layer)

                @jax.jit
                def compiled(params, others, key, *datas):
                    new_leaves = list(raw_leaves)
                    for i, d in zip(t_pos, datas):
                        new_leaves[i] = Tensor(d)
                    a, k = jax.tree_util.tree_unflatten(treedef, new_leaves)
                    with key_stream(key):
                        out, buf = functional_call(layer, {**params, **others},
                                                   *a, return_buffers=True,
                                                   forward_fn=function, **k)
                    return out, buf

                if self._ir_passes:
                    compiled = self._wrap_ir(compiled)
                self._cache[cache_key] = ("layer", compiled)
            else:
                @jax.jit
                def compiled(key, *datas):
                    new_leaves = list(raw_leaves)
                    for i, d in zip(t_pos, datas):
                        new_leaves[i] = Tensor(d)
                    a, k = jax.tree_util.tree_unflatten(treedef, new_leaves)
                    with key_stream(key), mode.grad_enabled(False):
                        out = function(*a, **k)
                    return jax.tree_util.tree_map(
                        lambda t: t._data if isinstance(t, Tensor) else t, out,
                        is_leaf=_is_tensor)

                if self._ir_passes:
                    compiled = self._wrap_ir(compiled)
                self._cache[cache_key] = ("fn", compiled)

        kind, compiled = self._cache[cache_key]
        key = get_rng_key()
        if kind == "layer":
            params, others = _split_state(self._layer)
            out, buf = compiled(params, others, key, *datas)
            sd = self._layer.state_dict()
            for name, val in buf.items():
                if name in sd and sd[name].stop_gradient and \
                        not isinstance(val, jax.core.Tracer):
                    sd[name]._data = val
        else:
            out = compiled(key, *datas)
        return jax.tree_util.tree_map(
            lambda d: Tensor(d) if isinstance(d, jax.Array) else d, out)

    def _wrap_ir(self, compiled):
        """Re-jit the cached callable with the IR passes applied to its
        pure inner function (reference build_strategy fuse passes)."""
        from ..framework import ir

        inner = compiled.__wrapped__  # the function under @jax.jit
        passes = None if self._ir_passes is True else list(self._ir_passes)
        return jax.jit(ir.optimize(inner, passes=passes))

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._function)


def _hashable(x):
    if isinstance(x, (list,)):
        return tuple(_hashable(i) for i in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in x.items()))
    if isinstance(x, np.ndarray):
        return (x.shape, str(x.dtype), x.tobytes())
    return x


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Compile a function or a Layer's forward (paddle.jit.to_static parity).

    Data-dependent python ``if``/``while`` on tensor values are converted
    by the dy2static AST pass (reference python/paddle/jit/dy2static/)
    into lax control flow; statements the pass can't convert keep the
    explicit trace-guard behavior, and any conversion failure falls back
    to plain tracing.

    ``ir_passes=True`` (or a sequence of pass names) runs the jaxpr
    pattern-rewrite passes (framework/ir.py) over the traced program —
    the reference's ``build_strategy`` fuse-pass role; a BuildStrategy
    object with any truthy ``fuse_*`` attribute enables them too.
    """
    import types

    from .dy2static import ast_transform

    ir_passes = kwargs.get("ir_passes")
    if ir_passes not in (None, True, False):
        # validate early: a bare string would iterate per-character and a
        # misspelled name would only KeyError deep inside the first trace
        from ..framework import ir as _ir
        if isinstance(ir_passes, str):
            raise TypeError(
                "ir_passes must be True/False or a SEQUENCE of pass "
                f"names, got the string {ir_passes!r} — did you mean "
                f"ir_passes=[{ir_passes!r}]?")
        unknown = [n for n in ir_passes if n not in _ir.PASSES]
        if unknown:
            raise ValueError(
                f"unknown ir pass(es) {unknown}; registered: "
                f"{list(_ir.PASSES)}")
    # explicit ir_passes=False is an OPT-OUT that build_strategy's fuse
    # flags must not override
    if "ir_passes" not in kwargs and build_strategy is not None:
        # only GRAPH-fusion BuildStrategy flags opt in — comm-fusion
        # flags (DistributedStrategy.fuse_all_reduce_ops etc.) are
        # semantically unrelated and default True
        _GRAPH_FUSE_FLAGS = ("fused_attention", "fuse_attention",
                             "fuse_elewise_add_act_ops",
                             "fuse_gemm_epilogue", "fuse_bn_act_ops",
                             "fuse_bn_add_act_ops",
                             "fuse_relu_depthwise_conv")
        ir_passes = any(bool(getattr(build_strategy, a, False))
                        for a in _GRAPH_FUSE_FLAGS)

    def decorate(fn):
        import inspect

        _gen_probe = fn.forward if isinstance(fn, Layer) else fn
        _gen_probe = getattr(_gen_probe, "__func__", _gen_probe)
        if inspect.isgeneratorfunction(_gen_probe) or \
                inspect.isasyncgenfunction(_gen_probe):
            # reference-quality decline: a compiled graph has one static
            # output structure; a generator's yields have none
            raise NotImplementedError(
                "to_static cannot compile a generator function: a "
                "jitted XLA program returns a fixed output structure, "
                "but `yield` produces values lazily. Restructure to "
                "accumulate results and return them (e.g. append to a "
                "list and return paddle.stack(outs)), or keep the "
                "generator outside the compiled region.")
        if isinstance(fn, Layer):
            raw = getattr(fn.forward, "__func__", fn.forward)
            conv = ast_transform(raw)
            fwd = types.MethodType(conv, fn) if conv is not None \
                else fn.forward
            static = StaticFunction(fwd, layer=fn, ir_passes=ir_passes)
            fn.forward = static
            return fn
        # a BOUND method must keep its binding through conversion: the
        # dy2static pass recompiles the underlying function, and calling
        # that unbound would swallow the first argument as self
        # (bug exposed by TranslatedLayer over Sequential, whose forward
        # has a convertible for-loop)
        self_obj = getattr(fn, "__self__", None)
        conv = ast_transform(getattr(fn, "__func__", fn))
        if conv is not None and self_obj is not None:
            conv = types.MethodType(conv, self_obj)
        return StaticFunction(conv if conv is not None else fn,
                              ir_passes=ir_passes)

    if function is not None:
        return decorate(function)
    return decorate


class TrainStep:
    """One whole training step compiled to a single XLA program.

    fwd + bwd (jax.grad over the functionalized model) + grad clip + optimizer
    update all fuse into one executable; parameters/optimizer state live on
    device across steps.  This is the TPU answer to the reference's fused
    optimizer kernels + CUDA-graph capture
    (paddle/phi/backends/gpu/cuda/cuda_graph.cc).

    Usage::
        step = TrainStep(model, loss_fn, opt)
        loss = step(batch_x, batch_y)      # Tensors in, loss Tensor out

    ``remat``: activation rematerialisation BY BLOCK, as
    ``parallel.SpmdTrainStep`` has it (``distributed/fleet/recompute.py``'s
    header is the one description).  Every layer that a ``LayerList`` of
    the model holds (its repeated blocks) runs under ``fleet.recompute``
    inside the step: its forward is run again in the backward pass, so one
    block's activations are live at a time.  ``True`` runs it again EXCEPT
    what the block tagged as dear to make again (``recompute.KEPT_BY_BLOCK``:
    its parallel projections' and its attention kernels' results);
    ``"full"`` keeps nothing of a block; a policy name of
    ``fleet.recompute`` or a list of ``checkpoint_name`` tags
    (``["flash_attention_out", "flash_attention_lse"]``: the flash kernel is
    not run twice) keeps what it names.  ``compile_account()["remat_kept"]``
    says which.  A block must hand on what it makes through its outputs,
    not through attributes.

    Counters: a model that defines ``step_counters()`` (a dict of small
    arrays its last forward made, e.g. the tokens each expert received)
    has them returned by the compiled step beside the loss; after a call
    they are ``step.counters``, device arrays nobody has waited for.  Keep
    them and read them when the timing is over (``docs/PROFILER.md``).
    """

    @StepTrace.init
    def __init__(self, model, loss_fn, optimizer, donate=True, remat=False,
                 scaler=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.remat = remat
        self._params, self._frozen = _split_state(model)
        self._opt_state = optimizer.init_state_pytree(self._params)
        self._step = 0
        self._compiled = None
        self._trace = StepTrace(remat_kept=remat_kept(remat))
        self._donate = donate
        self.counters = {}      # the last step's, see the class docstring
        # loss scaling composed INTO the compiled step (reference
        # fleet/scaler.py distributed_scaler + update_loss_scaling_ kernel)
        self.scaler = scaler if (scaler is not None and scaler.is_enable()) \
            else None
        if self.scaler is not None:
            from ..amp import scaler_init_state
            self._scaler_state = scaler_init_state(self.scaler)
            self.scaler._compiled_state = self._scaler_state
        else:
            self._scaler_state = None

    def _build(self):
        model = self.model
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        grad_clip = optimizer._grad_clip
        counters_of = getattr(model, "step_counters", None)
        # handed on to ``fleet.recompute`` round each block as it stands
        remat = self.remat or None

        def make_loss_f(frozen, key, inputs, labels):
            """``loss_f(params) -> (loss, counters)``."""
            def loss_f(p):
                with key_stream(key), block_remat(remat):
                    out = functional_call(model, {**p, **frozen}, *inputs)
                counters = counters_of() if counters_of is not None else {}
                out_t = jax.tree_util.tree_map(
                    lambda d: Tensor(d) if isinstance(d, jax.Array) else d, out)
                label_t = tuple(Tensor(l) if isinstance(l, jax.Array) else l
                                for l in labels)
                with mode.grad_enabled(False), jax.named_scope("loss"):
                    loss = loss_fn(out_t, *label_t)
                loss = loss._data if isinstance(loss, Tensor) else loss
                return loss, jax.tree_util.tree_map(
                    lambda c: c._data if isinstance(c, Tensor) else c,
                    counters, is_leaf=_is_tensor)

            return loss_f

        def train_step(params, frozen, opt_state, step, lr, key, inputs,
                       labels):
            loss_f = make_loss_f(frozen, key, inputs, labels)
            (loss, counters), grads = jax.value_and_grad(
                loss_f, has_aux=True)(params)
            if grad_clip is not None:
                grads = grad_clip.clip_pytree(grads)
            new_params, new_opt = optimizer.apply_gradients_pytree(
                params, grads, opt_state, step, lr=lr)
            return loss, new_params, new_opt, counters

        scaler = self.scaler

        def train_step_scaled(params, frozen, opt_state, step, lr, key,
                              inputs, labels, scaler_state):
            from ..amp import scaler_guarded_update
            loss_f = make_loss_f(frozen, key, inputs, labels)

            def scaled_f(p):
                l, counters = loss_f(p)
                return l * scaler_state["scale"].astype(l.dtype), \
                    (l, counters)

            (_, (loss, counters)), grads = jax.value_and_grad(
                scaled_f, has_aux=True)(params)
            new_params, new_opt, new_sstate = scaler_guarded_update(
                scaler, scaler_state, grads, grad_clip, optimizer,
                params, opt_state, step, lr)
            return loss, new_params, new_opt, counters, new_sstate

        donate = (0, 2) if self._donate else ()
        self._compiled = jax.jit(
            train_step_scaled if scaler is not None else train_step,
            donate_argnums=donate)

    def _operands(self, step, key, inputs, labels):
        """The compiled step's argument tuple for one call."""
        if self._compiled is None:
            self._build()
        if isinstance(inputs, Tensor):
            inputs = (inputs,)
        if isinstance(labels, Tensor):
            labels = (labels,)
        in_data = tuple(t._data if isinstance(t, Tensor) else t for t in inputs)
        lb_data = tuple(t._data if isinstance(t, Tensor) else t for t in labels)
        args = (self._params, self._frozen, self._opt_state,
                jnp.int32(step), jnp.float32(self.optimizer.get_lr()), key,
                in_data, lb_data)
        if self.scaler is not None:
            # the scaler object owns the live state (set_state_dict can
            # replace it between steps)
            args += (self.scaler._compiled_state,)
        return args

    def lower(self, inputs, labels=()):
        """jax's ``Lowered`` form of the step for these operands — what
        the compiler is handed (``.as_text()``, ``.compile()``).  Runs
        nothing: no step is counted and no RNG key is drawn."""
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = self._operands(self._step + 1, key, inputs, labels)
        return self._compiled.lower(*args)

    def __call__(self, inputs, labels=()):
        """inputs: Tensor or tuple for the model; labels: Tensor or tuple for
        loss_fn(output, *labels)."""
        self._step += 1
        step, trace = self._step, self._trace
        with trace.call(step):
            with trace.phase(trace.OPERANDS):
                args = self._operands(step, get_rng_key(), inputs, labels)
            out = trace.dispatch(self._compiled, args, step)
            loss, self._params, self._opt_state, self.counters = out[:4]
            if self.scaler is not None:
                self.scaler._compiled_state = out[4]
            with trace.phase(trace.SYNC):
                self.sync_to_model()
        return Tensor(loss)

    def stats(self):
        """``{"steps", "compiles", "long_steps"}``: calls so far, how many
        of them compiled (1 after the first; more means a shape or a dtype
        changed under way -- the trace marks which step,
        ``train_step::compiled``) and how many took over ``StepTrace.LONG``
        times the median of the steps before them (``profiler.step_log()`` says
        which, and who held each)."""
        return {"steps": self._step, "compiles": self._trace.compiles,
                "long_steps": self._trace.long_steps}

    def compile_account(self):
        """The compile log's record of the newest call that compiled
        (``profiler.compile_log``, docs/PROFILER.md): what building the
        step took, whether the persistent cache served it, and the
        compiler's memory account of the executable; ``None`` before the
        first call."""
        return self._trace.account

    def sync_to_model(self):
        """Rebind updated device arrays into the model's Parameters."""
        sd = self.model.state_dict()
        for name, arr in self._params.items():
            sd[name]._data = arr

    def state_dict(self):
        return {"params": self._params, "opt_state": self._opt_state,
                "step": self._step}


from .save_load import TranslatedLayer, load, save  # noqa: E402,F401
