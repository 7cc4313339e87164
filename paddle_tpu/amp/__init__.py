"""AMP: auto_cast + GradScaler (reference python/paddle/amp/).

On TPU the mixed-precision story is bfloat16: same exponent range as float32,
so **loss scaling is unnecessary** — GradScaler keeps the reference API
(python/paddle/amp/grad_scaler.py:577) but defaults to an identity scale for
bf16 and real dynamic scaling for float16.  ``auto_cast`` sets a thread-local
policy consulted by op dispatch: white-list ops (matmul/conv family) cast
inputs down; black-list ops (softmax/norm/loss) compute in float32.
Reference lists: python/paddle/amp/amp_lists.py.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor

WHITE_LIST = {
    "matmul", "mm", "bmm", "mv", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "einsum", "addmm", "gpt_qkv_projection",
}

BLACK_LIST = {
    "softmax", "log_softmax", "cross_entropy", "nll_loss", "mse_loss",
    "l1_loss", "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "kl_div", "layer_norm", "rms_norm", "batch_norm", "group_norm",
    "instance_norm", "logsumexp", "mean", "sum", "exp", "log", "pow",
    "cumsum", "softmax_with_cross_entropy",
}


class _AmpState(threading.local):
    def __init__(self):
        self.enabled = False
        self.dtype = jnp.bfloat16
        self.level = "O1"
        self.custom_white = set()
        self.custom_black = set()


_state = _AmpState()


def amp_state():
    return _state


def amp_cast_inputs(op_name, datas):
    """Called by ops.dispatch: cast per AMP policy. Returns new datas list."""
    if not _state.enabled:
        return datas
    white = (WHITE_LIST | _state.custom_white) - _state.custom_black
    black = (BLACK_LIST | _state.custom_black) - _state.custom_white
    if _state.level == "O2":
        # cast everything float to target except black list
        if op_name in black:
            target = jnp.float32
        else:
            target = _state.dtype
    else:
        if op_name in white:
            target = _state.dtype
        elif op_name in black:
            target = jnp.float32
        else:
            return datas
    out = []
    for d in datas:
        if hasattr(d, "dtype") and jnp.issubdtype(d.dtype, jnp.floating) and \
                d.dtype != jnp.float64 and d.dtype != target:
            out.append(d.astype(target))
        else:
            out.append(d)
    return out


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast parity (reference amp/auto_cast.py:646)."""
    prev = (_state.enabled, _state.dtype, _state.level,
            _state.custom_white, _state.custom_black)
    _state.enabled = enable
    _state.dtype = jnp.bfloat16 if dtype in ("bfloat16", "bf16") else jnp.float16
    _state.level = level
    _state.custom_white = set(custom_white_list or ())
    _state.custom_black = set(custom_black_list or ())
    try:
        yield
    finally:
        (_state.enabled, _state.dtype, _state.level,
         _state.custom_white, _state.custom_black) = prev


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O1", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """O2 decoration: cast model params to the AMP dtype (master weights stay
    fp32 inside optimizer state — see Adam._init_state).  A parameter its
    layer marked ``amp_keep_float32`` stays as it is: small state that is
    read in float32 anyway and whose updates the AMP dtype's grid would
    swallow (a state-space layer's ``A_log``, ``D``, ``dt_bias``)."""
    if level == "O2":
        target = "bfloat16" if dtype in ("bfloat16", "bf16") else "float16"
        for m in models if isinstance(models, (list, tuple)) else [models]:
            kept = [(p, p._data) for p in m.parameters()
                    if getattr(p, "amp_keep_float32", False)]
            m.to(dtype=target)
            for p, data in kept:
                p._rebind(data)
    if optimizers is None:
        return models
    return models, optimizers


class GradScaler:
    """Loss scaler (reference python/paddle/amp/grad_scaler.py:577).

    For bf16 (TPU default) scaling is an identity; for fp16 implements the
    dynamic scale algorithm.
    """

    def __init__(self, enable=True, init_loss_scaling=65536.0,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = False

    def scale(self, loss):
        if not self._enable:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        if not self._enable or self._unscaled:
            return
        self._unscaled = True
        inv = 1.0 / self._scale
        found_inf = False
        for p in optimizer._parameters:
            if p.grad is not None:
                g = p.grad._data * inv
                if not bool(jnp.isfinite(g).all()):
                    found_inf = True
                p.grad = Tensor(g, stop_gradient=True)
        self._found_inf = found_inf

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self._unscaled = False

    def update(self):
        if not self._enable or not self._dynamic:
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def is_enable(self):
        return self._enable

    def get_scale(self):
        st = getattr(self, "_compiled_state", None)
        if st is not None:  # live state owned by a compiled TrainStep
            return float(st["scale"])
        return self._scale

    def state_dict(self):
        st = getattr(self, "_compiled_state", None)
        if st is not None:
            return {"scale": float(st["scale"]),
                    "good_steps": int(st["good"]),
                    "bad_steps": int(st["bad"])}
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps}

    def set_state_dict(self, sd):
        self._scale = sd.get("scale", self._scale)
        self._good_steps = sd.get("good_steps", self._good_steps)
        self._bad_steps = sd.get("bad_steps", self._bad_steps)
        if getattr(self, "_compiled_state", None) is not None:
            # write through: an attached compiled TrainStep reads this dict
            # as its live scaler state on the next step
            self._compiled_state = scaler_init_state(self)


# ---- compiled-path loss scaling (update_loss_scaling_ parity) ----

def scaler_init_state(scaler):
    """Device-array scaler state threaded through a compiled train step."""
    return {"scale": jnp.float32(scaler._scale),
            "good": jnp.int32(scaler._good_steps),
            "bad": jnp.int32(scaler._bad_steps)}


def scaler_apply(scaler, state, grads):
    """Pure: unscale grads, detect non-finite, run the dynamic-scale update.

    The in-jit form of GradScaler.unscale_/update (reference
    update_loss_scaling_ kernel + fleet distributed_scaler, fleet/scaler.py:28).
    Returns (unscaled_grads, found_inf, new_state).
    """
    inv = 1.0 / state["scale"]
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
    leaves = jax.tree_util.tree_leaves(grads)
    finite = jnp.all(jnp.stack([jnp.isfinite(l).all() for l in leaves]))
    found = jnp.logical_not(finite)
    if not scaler._dynamic:
        return grads, found, state
    bad1 = jnp.where(found, state["bad"] + 1, 0)
    good1 = jnp.where(found, 0, state["good"] + 1)
    dec = found & (bad1 >= scaler._decr_every)
    inc = (~found) & (good1 >= scaler._incr_every)
    scale1 = jnp.where(
        dec, jnp.maximum(state["scale"] * scaler._decr_ratio, 1.0),
        jnp.where(inc, state["scale"] * scaler._incr_ratio, state["scale"]))
    return grads, found, {"scale": scale1,
                          "good": jnp.where(inc, 0, good1),
                          "bad": jnp.where(dec, 0, bad1)}


def scaler_guarded_update(scaler, scaler_state, grads, grad_clip, optimizer,
                          params, opt_state, step, lr):
    """Shared compiled-step epilogue: unscale, clip, update, and keep the
    old params/opt-state when non-finite gradients were found."""
    # unscaling and the guarded merge are the update's own work: the same
    # trace scope as ``apply_gradients_pytree`` opens for itself
    with jax.named_scope("optimizer"):
        grads, found_inf, new_sstate = scaler_apply(scaler, scaler_state,
                                                    grads)
    if grad_clip is not None:
        grads = grad_clip.clip_pytree(grads)
    cand_params, cand_opt = optimizer.apply_gradients_pytree(
        params, grads, opt_state, step, lr=lr)

    def merge(old, new):
        return jax.tree_util.tree_map(
            lambda o, n: jnp.where(found_inf, o, n), old, new)

    with jax.named_scope("optimizer"):
        return (merge(params, cand_params), merge(opt_state, cand_opt),
                new_sstate)


from . import debugging  # noqa: E402,F401
