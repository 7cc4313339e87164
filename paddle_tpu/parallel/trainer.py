"""SpmdTrainStep: the hybrid-parallel (dp × pp × mp [+sp]) training step.

One compiled XLA program per step over the fleet Mesh:
  embed (GSPMD dp/mp) → spmd_pipeline over 'pp' (shard_map+ppermute) →
  head+loss (GSPMD) → jax.grad → grad clip → optimizer update.
This is the TPU replacement for the reference's whole Fleet stack composition
(HybridParallelOptimizer + PipelineParallel + TensorParallel + sharding
wrappers — SURVEY §3.4): the strategy lives in shardings, the compiler owns
the collectives.

ZeRO/sharding stages map to optimizer-state sharding specs (stage 1), handled
here by sharding optimizer state over the 'sharding' axis when present —
stage 2/3 semantics (grad/param sharding) are with_sharding_constraint
choices, not separate machinery (reference group_sharded_stage{2,3}.py
dissolves into GSPMD).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..distributed.fleet.spmd import data_axes, use_mesh
from ..framework.random import get_rng_key, key_stream
from ..profiler import RecordEvent, StepTrace
from .pipeline import spmd_pipeline


def _spec_from_axes(mesh, axes, ndim):
    if axes is None:
        spec = [None] * ndim
    else:
        spec = [a if (a is None or a in mesh.axis_names) else None
                for a in axes]
        spec = spec + [None] * (ndim - len(spec))
    return P(*spec)


def _shard_opt_state_spec(mesh, param_spec, ndim, zero_axis="sharding"):
    """ZeRO stage-1: optimizer state sharded over ``zero_axis`` on the
    first dim not already sharded (falls back to the param's own spec).

    ``zero_axis="dp"`` folds sharding into the data-parallel axis — the
    reference's sharding group IS a subdivision of the dp replicas
    (group_sharded stage-1 semantics) — for meshes without a dedicated
    'sharding' axis."""
    if not zero_axis or zero_axis not in mesh.axis_names or \
            mesh.shape.get(zero_axis, 1) == 1:
        return param_spec
    spec = list(param_spec) + [None] * (ndim - len(param_spec))
    for i, s in enumerate(spec):
        if s is None:
            spec[i] = zero_axis
            return P(*spec)
    return param_spec


class SpmdTrainStep:
    """Compiled hybrid-parallel train step for models exposing
    ``functional_decompose()`` (see models/gpt.py).

    Usage::
        trainer = SpmdTrainStep(model, opt, mesh, n_microbatches=4)
        loss = trainer.step(input_ids, labels)
    """

    def __init__(self, model, optimizer, mesh, n_microbatches=1,
                 sequence_parallel=False, remat=False, zero_stage=1,
                 virtual_pp=1, scaler=None, zero_axis=None):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.n_microbatches = n_microbatches
        self.sequence_parallel = sequence_parallel
        self.remat = remat
        self.virtual_pp = virtual_pp
        # ZeRO axis: a dedicated 'sharding' mesh axis when present, else
        # opt-in folding into 'dp' (zero_axis="dp") — reference sharding
        # groups subdivide the data-parallel replicas
        if zero_axis is None:
            zero_axis = "sharding"
        self.zero_axis = zero_axis if zero_stage else None
        # loss scaling composed into the compiled hybrid step (the fleet
        # distributed_scaler role, fleet/scaler.py:28 — found-inf detection
        # is global automatically: grads are global arrays under GSPMD)
        self.scaler = scaler if (scaler is not None and scaler.is_enable()) \
            else None
        if self.scaler is not None:
            from ..amp import scaler_init_state
            self._scaler_state = scaler_init_state(self.scaler)
            self.scaler._compiled_state = self._scaler_state
        else:
            self._scaler_state = None

        d = model.functional_decompose()
        self.fns = d["fns"]
        self.num_layers = d["num_layers"]
        params = d["params"]
        specs = d["specs"]

        # Interleaved pipeline: permute the stacked layer dim ONCE here so
        # each stage's round-robin chunks land contiguously under the P('pp')
        # sharding — doing it inside the jitted step would re-gather half the
        # block weights across stages every step.
        self._layer_perm = None
        pp_deg = mesh.shape.get("pp", 1)
        if virtual_pp > 1 and pp_deg > 1:
            from .pipeline import interleave_permutation
            self._layer_perm = interleave_permutation(
                self.num_layers, pp_deg, virtual_pp)
            params = dict(params)
            params["blocks"] = jax.tree_util.tree_map(
                lambda leaf: leaf[self._layer_perm], params["blocks"])

        # build NamedShardings per leaf
        def shardings_for(p_tree, s_tree):
            out = {}
            for k, v in p_tree.items():
                spec = _spec_from_axes(mesh, s_tree.get(k), v.ndim)
                out[k] = NamedSharding(mesh, spec)
            return out

        self.param_shardings = {
            "embed": shardings_for(params["embed"], specs["embed"]),
            "blocks": shardings_for(params["blocks"], specs["blocks"]),
            "head": shardings_for(params["head"], specs["head"]),
        }
        # place params
        self.params = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), params, self.param_shardings)

        # optimizer state: mirror param sharding (+ ZeRO over 'sharding' axis)
        self.opt_state = optimizer.init_state_pytree(self.params)

        def opt_shard(path_sh, state):
            return jax.tree_util.tree_map(
                lambda sv: jax.device_put(
                    sv, NamedSharding(
                        mesh,
                        _shard_opt_state_spec(
                            mesh, path_sh.spec, sv.ndim, self.zero_axis)
                        if sv.ndim else P())),
                state)

        self.opt_state = jax.tree_util.tree_map(
            opt_shard, self.param_shardings, self.opt_state,
            is_leaf=lambda x: isinstance(x, NamedSharding))

        # batch parallelism rides dp AND a dedicated sharding axis — the
        # sharding group is extra data parallelism (reference group_sharded)
        self._batch_axes = data_axes(mesh) or None
        if self._batch_axes is not None and len(self._batch_axes) == 1:
            self._batch_axes = self._batch_axes[0]
        self.batch_sharding = NamedSharding(mesh, P(self._batch_axes))
        self._step_count = 0
        self._compiled = None
        self._trace = StepTrace()

    # ---- the step program ----
    def _build(self):
        embed_fn, block_fn, head_fn, loss_fn = self.fns
        mesh = self.mesh
        n_micro = self.n_microbatches
        optimizer = self.optimizer
        grad_clip = optimizer._grad_clip
        seq_spec = P(self._batch_axes, "mp", None) \
            if (self.sequence_parallel and "mp" in mesh.axis_names) \
            else P(self._batch_axes, None, None)
        blk = block_fn
        if self.remat:
            blk = jax.checkpoint(block_fn)

        def forward(params, input_ids, labels, key):
            key, pipe_key = jax.random.split(key)
            with key_stream(key):
                h = embed_fn(params["embed"], input_ids)
                h = jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh, seq_spec))
                h = spmd_pipeline(blk, params["blocks"], h, mesh=mesh,
                                  n_microbatches=n_micro, rng_key=pipe_key,
                                  activation_spec=seq_spec,
                                  virtual_pp=self.virtual_pp,
                                  prepermuted=True)
                h = jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh, seq_spec))
                logits = head_fn(params["head"], h, params["embed"])
                with jax.named_scope("loss"):
                    return loss_fn(logits, labels)

        def train_step(params, opt_state, step, lr, key, input_ids, labels):
            loss, grads = jax.value_and_grad(forward)(params, input_ids,
                                                      labels, key)
            if grad_clip is not None:
                grads = grad_clip.clip_pytree(grads)
            new_params, new_opt = optimizer.apply_gradients_pytree(
                params, grads, opt_state, step, lr=lr)
            return loss, new_params, new_opt

        scaler = self.scaler

        def train_step_scaled(params, opt_state, step, lr, key, input_ids,
                              labels, scaler_state):
            from ..amp import scaler_guarded_update

            def scaled(params, input_ids, labels, key):
                l = forward(params, input_ids, labels, key)
                return l * scaler_state["scale"].astype(l.dtype), l

            (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(
                params, input_ids, labels, key)
            new_params, new_opt, new_sstate = scaler_guarded_update(
                scaler, scaler_state, grads, grad_clip, optimizer,
                params, opt_state, step, lr)
            return loss, new_params, new_opt, new_sstate

        self._compiled = jax.jit(
            train_step_scaled if scaler is not None else train_step,
            donate_argnums=(0, 1))

    def step(self, input_ids, labels):
        if self._compiled is None:
            self._build()
        self._step_count += 1
        step, trace = self._step_count, self._trace
        with RecordEvent(trace.STEP, step=step):
            with RecordEvent(trace.OPERANDS, step=step):
                ids = input_ids._data if isinstance(input_ids, Tensor) \
                    else input_ids
                lbl = labels._data if isinstance(labels, Tensor) else labels
                args = (self.params, self.opt_state, jnp.int32(step),
                        jnp.float32(self.optimizer.get_lr()), get_rng_key(),
                        jax.device_put(ids, self.batch_sharding),
                        jax.device_put(lbl, self.batch_sharding))
                if self.scaler is not None:
                    args += (self.scaler._compiled_state,)
            # use_mesh, not a bare ``with mesh``: the kernel dispatchers read
            # fleet.spmd.current_mesh() to know GSPMD partitions this step
            with use_mesh(self.mesh):
                out = trace.dispatch(self._compiled, args, step)
            if self.scaler is not None:
                loss, self.params, self.opt_state, new_sstate = out
                self.scaler._compiled_state = new_sstate
            else:
                loss, self.params, self.opt_state = out
        return Tensor(loss)

    __call__ = step

    def stats(self):
        """``{"steps", "compiles"}``, as ``jit.TrainStep.stats``."""
        return {"steps": self._step_count, "compiles": self._trace.compiles}

    def _canonical_params(self):
        """Params with the stacked-layer dim in model order (the interleave
        permutation undone) — the layout checkpoints and the model use."""
        if self._layer_perm is None:
            return self.params
        inv = np.argsort(self._layer_perm)
        out = dict(self.params)
        out["blocks"] = jax.tree_util.tree_map(
            lambda leaf: leaf[inv], self.params["blocks"])
        return out

    def sync_to_model(self):
        self.model.load_stacked(self._canonical_params())

    def state_dict(self):
        return {"params": self._canonical_params(),
                "opt_state": self.opt_state,
                "step": self._step_count}
