"""Normalization layers (reference python/paddle/nn/layer/norm.py)."""

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from . import functional as F
from .initializer import Constant
from .layer_base import Layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self.normalized_shape = list(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr,
                default_initializer=Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(self.normalized_shape,
                                              attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias,
                            self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={self.normalized_shape}, epsilon={self.epsilon}"


class RMSNorm(Layer):
    """RMSNorm — beyond the reference surface; llama-family requirement."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter((hidden_size,), attr=weight_attr,
                                            default_initializer=Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, epsilon=self.epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter((num_features,), attr=weight_attr,
                                                default_initializer=Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                              is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros(num_features)))
        self.register_buffer("_variance", Tensor(jnp.ones(num_features)))

    def forward(self, x):
        training = self.training and not self.use_global_stats
        out, batch_mean, batch_var = F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=training, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format,
            use_global_stats=self.use_global_stats)
        if training:
            m = self.momentum
            self._mean.set_value(m * self._mean._data +
                                 (1 - m) * batch_mean._data)
            self._variance.set_value(m * self._variance._data +
                                     (1 - m) * batch_var._data)
        return out


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class BatchNorm(_BatchNormBase):
    """Legacy fluid BatchNorm API shim."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05,
                 **kwargs):
        super().__init__(num_channels, momentum=momentum, epsilon=epsilon)
        self.act = act

    def forward(self, x):
        out = super().forward(x)
        if self.act == "relu":
            out = F.relu(out)
        return out


class SyncBatchNorm(_BatchNormBase):
    """On TPU, batch-norm stats sync falls out of SPMD (stats computed over the
    global batch under pjit); eager single-chip behaves like BatchNorm.
    Reference: python/paddle/nn/layer/norm.py SyncBatchNorm."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        # structural conversion kept for API parity
        for name, sub in list(layer._sub_layers.items()):
            layer.add_sublayer(name, cls.convert_sync_batchnorm(sub))
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, cls):
            new = cls(layer.num_features, layer.momentum, layer.epsilon,
                      data_format=layer.data_format)
            new.weight = layer.weight
            new.bias = layer.bias
            new._buffers = layer._buffers
            return new
        return layer


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.num_groups = num_groups
        self.epsilon = epsilon
        self.data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_channels,), attr=weight_attr, default_initializer=Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.weight, self.bias,
                            self.epsilon, self.data_format)


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight, self.bias = None, None
        else:
            self.weight = self.create_parameter((num_features,), attr=weight_attr,
                                                default_initializer=Constant(1.0))
            self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                              is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, self.weight, self.bias, self.epsilon)


InstanceNorm1D = InstanceNorm2D
InstanceNorm3D = InstanceNorm2D


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k)


class SpectralNorm(Layer):
    """Spectral normalization: divide a weight by its largest singular
    value, estimated by persistent power iteration
    (reference python/paddle/nn/layer/norm.py SpectralNorm /
    spectral_norm_hook.py; phi spectral_norm kernel).

    ``forward(weight)`` reshapes the weight so ``dim`` leads ([H, W],
    W = product of the rest), runs ``power_iters`` u/v updates against
    the persistent buffers, and returns ``weight / sigma``.
    """

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12):
        super().__init__()
        self.dim = int(dim)
        self.power_iters = int(power_iters)
        self.eps = float(eps)
        self._shape = list(weight_shape)
        h = int(weight_shape[self.dim])
        w = int(np.prod([d for i, d in enumerate(weight_shape)
                         if i != self.dim]))
        from ..framework.random import get_rng_key

        key = get_rng_key()
        ku, kv = jax.random.split(key)
        u = jax.random.normal(ku, (h,), jnp.float32)
        v = jax.random.normal(kv, (w,), jnp.float32)
        self.register_buffer(
            "weight_u", Tensor(u / jnp.maximum(jnp.linalg.norm(u),
                                               self.eps)))
        self.register_buffer(
            "weight_v", Tensor(v / jnp.maximum(jnp.linalg.norm(v),
                                               self.eps)))

    def forward(self, weight):
        x = weight._data if isinstance(weight, Tensor) else \
            jnp.asarray(weight)
        perm = [self.dim] + [i for i in range(x.ndim) if i != self.dim]
        mat = jnp.transpose(x, perm).reshape(x.shape[self.dim], -1)
        matf = mat.astype(jnp.float32)
        u = self._buffers["weight_u"]._data
        v = self._buffers["weight_v"]._data
        # power iteration runs OUTSIDE the autograd chain (the reference
        # marks u/v stop_gradient and treats sigma's u/v as constants)
        m_const = jax.lax.stop_gradient(matf)
        for _ in range(self.power_iters):
            v = m_const.T @ u
            v = v / jnp.maximum(jnp.linalg.norm(v), self.eps)
            u = m_const @ v
            u = u / jnp.maximum(jnp.linalg.norm(u), self.eps)
        self._buffers["weight_u"].set_value(u)
        self._buffers["weight_v"].set_value(v)
        from ..ops.dispatch import apply_op

        w_t = weight if isinstance(weight, Tensor) else Tensor(x)

        def fn(wd):
            md = jnp.transpose(wd, perm).reshape(
                wd.shape[self.dim], -1).astype(jnp.float32)
            sigma = u @ md @ v
            return (wd.astype(jnp.float32) /
                    jnp.maximum(sigma, self.eps)).astype(wd.dtype)

        return apply_op("spectral_norm", fn, (w_t,), {})
