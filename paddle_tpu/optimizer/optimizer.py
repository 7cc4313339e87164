"""Optimizers (reference python/paddle/optimizer/optimizer.py:91).

Each optimizer defines a **pure update rule** ``_update(p, g, state, lr, ctx)``
over jax arrays.  Eager ``step()`` applies it per-parameter on the tape's
``.grad``; the jit training path (paddle_tpu.jit.TrainStep) calls the same rule
inside a compiled function over the whole parameter pytree — the rule is
written once, matching the reference's single PHI kernel per optimizer
(e.g. adamw kernel paddle/phi/kernels/gpu/adamw_kernel.cu) consumed by both
dygraph and static executors.
"""

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..framework import no_grad
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._lr = learning_rate
        self._parameters = list(parameters) if parameters is not None else []
        if not self._parameters:
            raise ValueError("parameters is required in eager mode")
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._accumulators = {}  # id(param) -> state dict of jax arrays
        self._step_count = 0

    # ---- lr ----
    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    @property
    def _learning_rate(self):
        return self._lr

    # ---- state rules (override) ----
    def _init_state(self, p):
        """Return the initial state dict for one parameter (jax arrays)."""
        return {}

    def _update(self, p, g, state, lr, ctx):
        """Pure rule: (param, grad, state, lr, ctx) -> (new_param, new_state).

        ``ctx`` carries step count and shared scalars (all jax-friendly).
        """
        raise NotImplementedError

    def _decay_applied_in_rule(self):
        """AdamW-style decoupled decay handles weight_decay inside _update."""
        return False

    def _param_ctx(self, p, base_ctx):
        """Per-parameter ctx extension hook (AdamW decay masking)."""
        return base_ctx

    # ---- eager path ----
    @no_grad()
    def step(self):
        self._step_count += 1
        lr = self.get_lr()
        params = [p for p in self._parameters if p.grad is not None
                  and not p.stop_gradient]
        grads = [p.grad._data for p in params]
        if self._grad_clip is not None:
            grads = self._grad_clip._clip_jax(params, grads)
        ctx = {"step": self._step_count}
        for p, g in zip(params, grads):
            if (self._weight_decay and not self._decay_applied_in_rule()):
                g = g + float(self._weight_decay) * p._data
            state = self._accumulators.get(id(p))
            if state is None:
                state = self._init_state(p._data)
                self._accumulators[id(p)] = state
            new_p, new_state = self._update(p._data, g, state, lr,
                                            self._param_ctx(p, ctx))
            p._rebind(new_p)
            self._accumulators[id(p)] = new_state

    def clear_grad(self, set_to_zero=False):
        for p in self._parameters:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    # ---- functional path (used by jit.TrainStep) ----
    def init_state_pytree(self, params):
        """params: pytree of jax arrays -> pytree-of-state (same structure)."""
        return jax.tree_util.tree_map(self._init_state, params)

    @jax.named_scope("optimizer")
    def apply_gradients_pytree(self, params, grads, states, step, lr=None):
        """Pure whole-tree update for use inside jit. Returns (params, states)."""
        lr = self.get_lr() if lr is None else lr
        ctx = {"step": step}
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(states)
        new_p, new_s = [], []
        for p, g, s in zip(flat_p, flat_g, flat_s):
            if self._weight_decay and not self._decay_applied_in_rule():
                g = g + float(self._weight_decay) * p
            np_, ns = self._update(p, g, s, lr, ctx)
            # ``lr`` arrives as a float32 ARRAY here, so a rule that
            # multiplies it into a bf16 parameter (AdamW's decay, SGD)
            # promotes the result: without this cast amp-O2 params turn
            # float32 after the first step, which retraces the step and
            # trains the rest of the run in float32
            new_p.append(np_.astype(p.dtype))
            new_s.append(ns)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    # ---- checkpoint ----
    def state_dict(self):
        sd = {"step": self._step_count}
        for i, p in enumerate(self._parameters):
            state = self._accumulators.get(id(p))
            if state:
                for k, v in state.items():
                    sd[f"param{i}.{k}"] = Tensor(v)
        if isinstance(self._lr, LRScheduler):
            sd["LR_Scheduler"] = self._lr.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        self._step_count = int(state_dict.get("step", 0))
        for i, p in enumerate(self._parameters):
            state = {}
            prefix = f"param{i}."
            for k, v in state_dict.items():
                if isinstance(k, str) and k.startswith(prefix):
                    data = v._data if isinstance(v, Tensor) else jnp.asarray(v)
                    state[k[len(prefix):]] = data
            if state:
                self._accumulators[id(p)] = state
        if "LR_Scheduler" in state_dict and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _update(self, p, g, state, lr, ctx):
        return p - lr * g.astype(p.dtype), state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(p.dtype)
        v = self._momentum * state["velocity"] + g
        if self._nesterov:
            p = p - lr * (g + self._momentum * v)
        else:
            p = p - lr * v
        return p, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _init_state(self, p):
        return {"moment1": jnp.zeros_like(p, dtype=jnp.float32),
                "moment2": jnp.zeros_like(p, dtype=jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        upd = lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        return (p - upd.astype(p.dtype),
                {"moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p})


class AdamW(Adam):
    """Decoupled weight decay (reference python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._decay_names = None
        if apply_decay_param_fun is not None:
            self._decay_ids = {
                id(p) for p in self._parameters
                if p.name is None or apply_decay_param_fun(p.name)}
        else:
            self._decay_ids = None

    def _decay_applied_in_rule(self):
        return True

    def _param_ctx(self, p, base_ctx):
        decay = True if self._decay_ids is None else id(p) in self._decay_ids
        return {**base_ctx, "decay_mask": decay}

    def _update(self, p, g, state, lr, ctx):
        wd = float(self._weight_decay or 0.0)
        decay_mask = ctx.get("decay_mask", True)
        if wd and decay_mask:
            p = p - lr * wd * p
        return super()._update(p, g, state, lr, ctx)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, p):
        return {"moment": jnp.zeros_like(p, dtype=jnp.float32),
                "inf_norm": jnp.zeros_like(p, dtype=jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        m = self._beta1 * state["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(g))
        b1p = state["beta1_pow"] * self._beta1
        upd = lr * m / ((1 - b1p) * (u + self._epsilon))
        return (p - upd.astype(p.dtype),
                {"moment": m, "inf_norm": u, "beta1_pow": b1p})


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-06,
                 momentum=0.0, centered=False, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_state(self, p):
        s = {"mean_square": jnp.zeros_like(p, dtype=jnp.float32),
             "momentum": jnp.zeros_like(p, dtype=jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p, dtype=jnp.float32)
        return s

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            mg = None
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * state["momentum"] + lr * g / denom
        new_state = {"mean_square": ms, "momentum": mom}
        if mg is not None:
            new_state["mean_grad"] = mg
        return p - mom.astype(p.dtype), new_state


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_value = initial_accumulator_value

    def _init_state(self, p):
        return {"moment": jnp.full_like(p, self._init_value, dtype=jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        mom = state["moment"] + jnp.square(g)
        upd = lr * g / (jnp.sqrt(mom) + self._epsilon)
        return p - upd.astype(p.dtype), {"moment": mom}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon, self._rho = epsilon, rho

    def _init_state(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p, dtype=jnp.float32),
                "avg_squared_update": jnp.zeros_like(p, dtype=jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        asg = self._rho * state["avg_squared_grad"] + (1 - self._rho) * jnp.square(g)
        upd = g * jnp.sqrt(state["avg_squared_update"] + self._epsilon) / \
            jnp.sqrt(asg + self._epsilon)
        asu = self._rho * state["avg_squared_update"] + \
            (1 - self._rho) * jnp.square(upd)
        return (p - lr * upd.astype(p.dtype),
                {"avg_squared_grad": asg, "avg_squared_update": asu})


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, p):
        return {"moment1": jnp.zeros_like(p, dtype=jnp.float32),
                "moment2": jnp.zeros_like(p, dtype=jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + \
            self._lamb_wd * p.astype(jnp.float32)
        w_norm = jnp.linalg.norm(p.astype(jnp.float32))
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (p - (lr * trust * r).astype(p.dtype),
                {"moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p})


class Lars(Optimizer):
    """LARS momentum (reference fluid LarsMomentumOptimizer, used by the
    lars meta-optimizer): per-layer trust ratio ||w|| / (||g|| + wd*||w||)
    scales the learning rate so large-batch training keeps layer-wise
    update magnitudes balanced."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 parameters=None, grad_clip=None, epsilon=1e-9, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._wd = lars_weight_decay
        self._epsilon = epsilon

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p, dtype=jnp.float32)}

    def _update(self, p, g, state, lr, ctx):
        g = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        w_norm = jnp.linalg.norm(pf)
        g_norm = jnp.linalg.norm(g)
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._coeff * w_norm / (g_norm + self._wd * w_norm
                                    + self._epsilon),
            1.0)
        v = self._momentum * state["velocity"] + \
            lr * local_lr * (g + self._wd * pf)
        return (p - v.astype(p.dtype)), {"velocity": v}
