"""nn.functional: stateless NN ops (reference python/paddle/nn/functional/).

Everything is a registered op (pure jax inside), so the same code runs eagerly
with tape autograd and traces under jit.  XLA fuses the elementwise chains;
attention has a Pallas fast path (ops/pallas/) selected on TPU.
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor
from ..framework.random import get_rng_key
from ..ops.registry import op

# ---------------- activations ----------------

@op()
def relu(x):
    return jax.nn.relu(x)

@op()
def relu6(x):
    return jax.nn.relu6(x)

@op()
def relu_(x):
    return jax.nn.relu(x)

@op()
def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)

@op()
def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope=negative_slope)

@op()
def prelu(x, weight, data_format="NCHW"):
    if weight.size == 1:
        w = weight.reshape(())
    else:
        shape = [1] * x.ndim
        ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
        shape[ch_axis] = weight.size
        w = weight.reshape(shape)
    return jnp.where(x >= 0, x, w * x)

@op()
def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha=alpha)

@op()
def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))

@op()
def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha=alpha)

@op()
def silu(x):
    return jax.nn.silu(x)

@op()
def swish(x):
    return jax.nn.silu(x)

@op()
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))

@op()
def softplus(x, beta=1.0, threshold=20.0):
    return jnp.where(x * beta > threshold, x,
                     (1.0 / beta) * jnp.log1p(jnp.exp(beta * x)))

@op()
def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))

@op()
def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)

@op()
def tanhshrink(x):
    return x - jnp.tanh(x)

@op()
def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)

@op()
def hardsigmoid(x, slope=0.1666667, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)

@op()
def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0

@op()
def softsign(x):
    return jax.nn.soft_sign(x)

@op()
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)

@op()
def softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.softmax(x, axis=axis)

@op()
def log_softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.log_softmax(x, axis=axis)

@op()
def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)

@op()
def maxout(x, groups, axis=1):
    shape = list(x.shape)
    ch = shape[axis]
    shape[axis] = ch // groups
    shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(shape), axis=axis + 1)

@op()
def normalize(x, p=2, axis=1, epsilon=1e-12):
    nrm = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(nrm, epsilon)

def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    key = get_rng_key()

    @op("gumbel_softmax")
    def _gs(x):
        g = jax.random.gumbel(key, x.shape, dtype=x.dtype)
        y = jax.nn.softmax((x + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis)
            onehot = jax.nn.one_hot(idx, y.shape[axis], axis=axis,
                                    dtype=y.dtype)
            y = onehot + y - lax.stop_gradient(y)  # straight-through
        return y
    return _gs(x)

# ---------------- linear / embedding ----------------

@op()
def linear(x, weight, bias=None):
    """y = x @ W + b; weight layout [in, out] (paddle convention)."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out

@op()
def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out

@op()
def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes)

@op()
def label_smooth(label, prior_dist=None, epsilon=0.1):
    k = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / k

@op()
def bilinear(x1, x2, weight, bias=None):
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out

# ---------------- conv / pool ----------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n

def _conv_padding(padding, nd):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nd
    padding = list(padding)
    if len(padding) == nd and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nd:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nd)]
    return [tuple(p) for p in padding]


@op()
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    """Conv via lax.conv_general_dilated (reference: phi conv kernels →
    cuDNN; here XLA convolution → MXU)."""
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    if data_format == "NHWC":
        weight = jnp.transpose(weight, (2, 3, 1, 0))
    out = lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride), padding=_conv_padding(padding, 2),
        rhs_dilation=_pair(dilation), dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


@op()
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    dn = ("NCH", "OIH", "NCH") if data_format == "NCL" else ("NHC", "HIO", "NHC")
    if data_format != "NCL":
        weight = jnp.transpose(weight, (2, 1, 0))
    out = lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride, 1),
        padding=_conv_padding(padding, 1), rhs_dilation=_pair(dilation, 1),
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        bshape = (1, -1, 1) if data_format == "NCL" else (1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


@op()
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    dn = ("NCDHW", "OIDHW", "NCDHW")
    out = lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride, 3),
        padding=_conv_padding(padding, 3), rhs_dilation=_pair(dilation, 3),
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        out = out + bias.reshape((1, -1, 1, 1, 1))
    return out


@op()
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW", output_size=None):
    stride = _pair(stride)
    dilation = _pair(dilation)
    pad = _conv_padding(padding, 2)
    if isinstance(pad, str):
        pad_pairs = [(0, 0), (0, 0)] if pad == "VALID" else None
    else:
        pad_pairs = pad
    opad = _pair(output_padding)
    kh = (weight.shape[2] - 1) * dilation[0] + 1
    kw = (weight.shape[3] - 1) * dilation[1] + 1
    if pad_pairs is None:  # SAME
        pad_pairs = [(kh // 2, kh // 2), (kw // 2, kw // 2)]
    # gradient-of-conv formulation: transpose padding
    lo_h = kh - 1 - pad_pairs[0][0]
    hi_h = kh - 1 - pad_pairs[0][1] + opad[0]
    lo_w = kw - 1 - pad_pairs[1][0]
    hi_w = kw - 1 - pad_pairs[1][1] + opad[1]
    # weight is [in, out/groups, kh, kw] in paddle transpose-conv convention
    w = jnp.flip(weight, axis=(2, 3))
    if groups > 1:
        ic, ocg = w.shape[0], w.shape[1]
        w = w.reshape(groups, ic // groups, ocg, *w.shape[2:])
        w = jnp.swapaxes(w, 1, 2).reshape(groups * ocg, ic // groups, *w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(lo_h, hi_h), (lo_w, hi_w)],
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=groups)
    if bias is not None:
        out = out + bias.reshape((1, -1, 1, 1))
    return out


def _ceil_extra(size, k, s, p_lo, p_hi):
    """Extra high-side padding so reduce_window matches ceil_mode output."""
    import math as _m
    floor_out = (size + p_lo + p_hi - k) // s + 1
    ceil_out = _m.ceil((size + p_lo + p_hi - k) / s) + 1
    return (ceil_out - floor_out) * s


@op()
def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW"):
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    pd = _conv_padding(padding, 2)
    if data_format != "NCHW" and return_mask:
        raise NotImplementedError("return_mask requires NCHW")
    if isinstance(pd, str):
        pad = pd
        pd_pairs = [(0, 0), (0, 0)]
    else:
        pd_pairs = [list(p) for p in pd]
        if ceil_mode:
            h, w = (x.shape[2], x.shape[3]) if data_format == "NCHW" else \
                (x.shape[1], x.shape[2])
            pd_pairs[0][1] += _ceil_extra(h, ks[0], st[0], *pd_pairs[0])
            pd_pairs[1][1] += _ceil_extra(w, ks[1], st[1], *pd_pairs[1])
        pd_pairs = [tuple(p) for p in pd_pairs]
        pad = [(0, 0), (0, 0)] + pd_pairs if data_format == "NCHW" else \
            [(0, 0)] + pd_pairs + [(0, 0)]
    dims = (1, 1) + ks if data_format == "NCHW" else (1,) + ks + (1,)
    strides = (1, 1) + st if data_format == "NCHW" else (1,) + st + (1,)
    if jnp.issubdtype(x.dtype, jnp.inexact):
        # -inf (not finfo.min): lax.reduce_window's max VJP only linearizes
        # with the identity element as the init value
        neg = -jnp.inf
    else:
        neg = jnp.iinfo(x.dtype).min
    out = lax.reduce_window(x, neg, lax.max, dims, strides, pad)
    if not return_mask:
        return out
    # mask: flattened input position (h*W + w) of each window max, paddle-style
    n, c, h, w = x.shape
    hw = jnp.arange(h * w, dtype=jnp.float32).reshape(1, 1, h, w)
    hw = jnp.broadcast_to(hw, x.shape)
    # pad explicitly (x with -inf so padded cells never win; hw with -1)
    full_pad = [(0, 0), (0, 0)] + pd_pairs
    xp = jnp.pad(x, full_pad, constant_values=neg)
    hp = jnp.pad(hw, full_pad, constant_values=-1.0)
    zero_pad = [(0, 0), (0, 0)]
    patches_x = lax.conv_general_dilated_patches(
        xp, ks, st, zero_pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    patches_i = lax.conv_general_dilated_patches(
        hp, ks, st, zero_pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    oh, ow = patches_x.shape[2], patches_x.shape[3]
    px = patches_x.reshape(n, c, ks[0] * ks[1], oh, ow)
    pi = patches_i.reshape(n, c, ks[0] * ks[1], oh, ow)
    arg = jnp.argmax(px, axis=2)
    mask = jnp.take_along_axis(pi, arg[:, :, None], axis=2)[:, :, 0]
    return out, mask.astype(jnp.int32)


@op()
def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, data_format="NCHW"):
    ks = _pair(kernel_size)
    st = _pair(stride if stride is not None else kernel_size)
    pd = _conv_padding(padding, 2)
    if isinstance(pd, str):
        pad = pd
    else:
        pad = [(0, 0), (0, 0)] + pd if data_format == "NCHW" else \
            [(0, 0)] + pd + [(0, 0)]
    dims = (1, 1) + ks if data_format == "NCHW" else (1,) + ks + (1,)
    strides = (1, 1) + st if data_format == "NCHW" else (1,) + st + (1,)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
    if exclusive and not isinstance(pad, str):
        counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, dims,
                                   strides, pad)
        return summed / counts
    return summed / float(np.prod(ks))


@op()
def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False):
    ks = _pair(kernel_size, 1)
    st = _pair(stride if stride is not None else kernel_size, 1)
    pd = _conv_padding(padding, 1)
    pad = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
    neg = jnp.finfo(x.dtype).min
    return lax.reduce_window(x, neg, lax.max, (1, 1) + ks, (1, 1) + st, pad)


@op()
def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True):
    ks = _pair(kernel_size, 1)
    st = _pair(stride if stride is not None else kernel_size, 1)
    pd = _conv_padding(padding, 1)
    pad = pd if isinstance(pd, str) else [(0, 0), (0, 0)] + pd
    summed = lax.reduce_window(x, 0.0, lax.add, (1, 1) + ks, (1, 1) + st, pad)
    return summed / float(ks[0])


@op()
def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out_h, out_w = _pair(output_size)
    h, w = (x.shape[2], x.shape[3]) if data_format == "NCHW" else (x.shape[1], x.shape[2])
    if h % out_h == 0 and w % out_w == 0:
        kh, kw = h // out_h, w // out_w
        dims = (1, 1, kh, kw) if data_format == "NCHW" else (1, kh, kw, 1)
        summed = lax.reduce_window(x, 0.0, lax.add, dims, dims, "VALID")
        return summed / (kh * kw)
    # general case: mean over index buckets
    def pool_axis(arr, axis, out_sz):
        idx = [(int(math.floor(i * arr.shape[axis] / out_sz)),
                int(math.ceil((i + 1) * arr.shape[axis] / out_sz)))
               for i in range(out_sz)]
        pieces = [jnp.mean(lax.slice_in_dim(arr, a, b, axis=axis), axis=axis,
                           keepdims=True) for a, b in idx]
        return jnp.concatenate(pieces, axis=axis)
    ax_h, ax_w = (2, 3) if data_format == "NCHW" else (1, 2)
    return pool_axis(pool_axis(x, ax_h, out_h), ax_w, out_w)


@op()
def adaptive_max_pool2d(x, output_size, return_mask=False):
    out_h, out_w = _pair(output_size)
    h, w = x.shape[2], x.shape[3]
    if h % out_h == 0 and w % out_w == 0:
        kh, kw = h // out_h, w // out_w
        neg = jnp.finfo(x.dtype).min
        return lax.reduce_window(x, neg, lax.max, (1, 1, kh, kw),
                                 (1, 1, kh, kw), "VALID")

    def pool_axis(arr, axis, out_sz):
        idx = [(int(math.floor(i * arr.shape[axis] / out_sz)),
                int(math.ceil((i + 1) * arr.shape[axis] / out_sz)))
               for i in range(out_sz)]
        pieces = [jnp.max(lax.slice_in_dim(arr, a, b, axis=axis), axis=axis,
                          keepdims=True) for a, b in idx]
        return jnp.concatenate(pieces, axis=axis)

    return pool_axis(pool_axis(x, 2, out_h), 3, out_w)


@op()
def adaptive_avg_pool1d(x, output_size):
    l = x.shape[2]
    if l % output_size == 0:
        k = l // output_size
        summed = lax.reduce_window(x, 0.0, lax.add, (1, 1, k), (1, 1, k),
                                   "VALID")
        return summed / k
    idx = [(int(math.floor(i * l / output_size)),
            int(math.ceil((i + 1) * l / output_size)))
           for i in range(output_size)]
    pieces = [jnp.mean(lax.slice_in_dim(x, a, b, axis=2), axis=2,
                       keepdims=True) for a, b in idx]
    return jnp.concatenate(pieces, axis=2)

# ---------------- normalization ----------------

@op()
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    from ..ops import pallas as _pallas
    if (len(normalized_shape) == 1 and weight is not None
            and bias is not None and _pallas._use_pallas()):
        from ..ops.pallas.layernorm_kernel import layernorm_pallas, supports
        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        if supports(rows, x.shape[-1]):
            if not _pallas._partitioned_by_gspmd():
                return layernorm_pallas(x, weight, bias, eps=epsilon)
            _pallas.warn_fallback("layernorm", f"x{tuple(x.shape)}",
                                  _pallas.GSPMD_REASON)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@op()
def rms_norm(x, weight=None, epsilon=1e-06, axis=-1):
    """RMSNorm — exceeds the reference surface (needed for llama-family)."""
    var = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    out = x * lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    return out


@op()
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05, data_format="NCHW",
               use_global_stats=None):
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(a for a in range(x.ndim) if a != ch_axis)
    if training and not use_global_stats:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
    else:
        mean, var = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    out = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    # new running stats are returned; the BatchNorm layer updates its buffers
    return out, mean, var


@op()
def instance_norm(x, weight=None, bias=None, epsilon=1e-05):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape)
        if bias is not None:
            out = out + bias.reshape(shape)
    return out


@op()
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-05,
               data_format="NCHW"):
    if data_format != "NCHW":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    out = ((g - mean) * lax.rsqrt(var + epsilon)).reshape(n, c, *spatial)
    shape = [1, -1] + [1] * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if data_format != "NCHW":
        out = jnp.moveaxis(out, 1, -1)
    return out


@op()
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    pad = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2)
    padded = jnp.pad(sq, pad)
    window = sum(lax.slice_in_dim(padded, i, i + x.shape[1], axis=1)
                 for i in range(size))
    return x / jnp.power(k + alpha * window / size, beta)

# ---------------- dropout ----------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None, rng_key=None):
    if not training:
        # downscale_in_infer: unscaled mask at train time, x*(1-p) at infer
        if mode == "downscale_in_infer" and p > 0.0:
            from ..ops.registry import OPS
            return OPS["scale"].user_fn(x, scale=1.0 - p)
        return x if isinstance(x, Tensor) else Tensor(x)
    if p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    key = rng_key if rng_key is not None else get_rng_key()

    @op("dropout")
    def _dropout(x):
        shape = list(x.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, x / (1.0 - p), 0.0)
        return jnp.where(keep, x, 0.0)
    return _dropout(x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else Tensor(x)
    key = get_rng_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    @op("alpha_dropout")
    def _ad(x):
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
        a = (1.0 / math.sqrt((1.0 - p) * (1.0 + p * alpha_p ** 2))) \
            if p < 1.0 else 0.0
        b = -a * alpha_p * p
        return a * jnp.where(keep, x, alpha_p) + b
    return _ad(x)

# ---------------- padding / misc ----------------

@op()
def pad(x, pad, mode="constant", value=0.0, data_format="NCHW"):
    if isinstance(pad, (list, tuple)) and len(pad) == x.ndim * 2:
        pairs = [(pad[2 * i], pad[2 * i + 1]) for i in range(x.ndim)]
    else:
        # paddle convention: pad pairs apply starting from the LAST dim
        # backward ([w_left, w_right, h_top, h_bottom] for NCHW)
        pairs = [(0, 0)] * x.ndim
        np_ = len(pad) // 2
        if data_format.startswith("NC"):
            dims = list(range(x.ndim - 1, x.ndim - 1 - np_, -1))
        else:
            dims = list(range(x.ndim - 2, x.ndim - 2 - np_, -1))
        for i, d in enumerate(dims):
            pairs[d] = (pad[2 * i], pad[2 * i + 1])
    jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge",
             "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(x, pairs, mode="constant", constant_values=value)
    return jnp.pad(x, pairs, mode=jmode)


@op()
def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)], rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches.reshape(n, c * kh * kw, -1)


@op()
def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


@op()
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    n, c, h, w = x.shape
    if size is None:
        sf = _pair(scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    out_h, out_w = int(size[0]), int(size[1])
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic",
              "linear": "linear", "area": "linear"}[mode]
    moved = jnp.moveaxis(x, 1, -1)
    out = jax.image.resize(moved, (n, out_h, out_w, c), method=method)
    return jnp.moveaxis(out, -1, 1)

upsample = interpolate


@op()
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot_ = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot_ / jnp.maximum(n1 * n2, eps)


@op()
def sequence_mask(x, maxlen=None, dtype="int64"):
    if maxlen is None:
        maxlen = int(jnp.max(x))
    from ..framework.dtype import convert_dtype
    steps = jnp.arange(maxlen)
    return (steps[None, :] < x[..., None]).astype(convert_dtype(dtype))


@op()
def temporal_shift(x, seg_num, shift_ratio=0.25):
    nt, c, h, w = x.shape
    n = nt // seg_num
    x = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([x[:, 1:, :fold], jnp.zeros_like(x[:, :1, :fold])], 1)
    right = jnp.concatenate([jnp.zeros_like(x[:, :1, fold:2 * fold]),
                             x[:, :-1, fold:2 * fold]], 1)
    rest = x[:, :, 2 * fold:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)

# ---------------- losses ----------------

@op()
def mse_loss(input, label, reduction="mean"):
    loss = jnp.square(input - label)
    return _reduce(loss, reduction)


@op()
def l1_loss(input, label, reduction="mean"):
    return _reduce(jnp.abs(input - label), reduction)


@op()
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = jnp.abs(input - label)
    loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce(loss, reduction)


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


_XENT_CHUNK = 256


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _chunked_softmax_xent(logits2d, labels1d):
    """Per-row softmax cross-entropy without materializing f32 [N, V].

    The naive path (`input.astype(f32)` + `log_softmax`) allocates two full
    f32 copies of the logits — for a GPT LM head that is the largest tensor
    in the whole training step (f32[B*T, vocab], the round-1 OOM at batch
    64) and several ms of pure HBM traffic.  Here both passes stream over
    row chunks inside a `lax.map`, keeping only [chunk, V] f32 transient in
    VMEM; the backward recomputes softmax from the saved per-row lse.
    """
    loss, _ = _chunked_softmax_xent_fwd(logits2d, labels1d)
    return loss


def _xent_rows(x_c, y_c):
    x32 = x_c.astype(jnp.float32)
    m = jnp.max(x32, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x32 - m[:, None]), axis=-1))
    picked = jnp.take_along_axis(
        x32, y_c[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return lse - picked, lse


def _chunked_softmax_xent_fwd(logits2d, labels1d):
    n, v = logits2d.shape
    c = _XENT_CHUNK
    if n % c != 0:
        loss, lse = _xent_rows(logits2d, labels1d)
        return loss, (logits2d, labels1d, lse)
    xs = logits2d.reshape(n // c, c, v)
    ys = labels1d.reshape(n // c, c)
    loss, lse = jax.lax.map(lambda args: _xent_rows(*args), (xs, ys))
    return loss.reshape(n), (logits2d, labels1d, lse.reshape(n))


def _chunked_softmax_xent_bwd(res, g):
    logits2d, labels1d, lse = res
    n, v = logits2d.shape
    c = _XENT_CHUNK

    def rows(x_c, y_c, lse_c, g_c):
        p = jnp.exp(x_c.astype(jnp.float32) - lse_c[:, None])
        onehot = jax.nn.one_hot(y_c, v, dtype=jnp.float32)
        return ((p - onehot) * g_c[:, None]).astype(logits2d.dtype)

    if n % c != 0:
        return rows(logits2d, labels1d, lse, g), None
    d = jax.lax.map(
        lambda args: rows(*args),
        (logits2d.reshape(n // c, c, v), labels1d.reshape(n // c, c),
         lse.reshape(n // c, c), g.reshape(n // c, c)))
    return d.reshape(n, v), None


_chunked_softmax_xent.defvjp(_chunked_softmax_xent_fwd,
                             _chunked_softmax_xent_bwd)


@op()
def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True,
                  label_smoothing=0.0):
    """Softmax cross-entropy (reference python/paddle/nn/functional/loss.py).

    Computed in float32 with logsumexp for stability regardless of input dtype
    (bf16-safe on TPU).  The hard-label/no-smoothing hot path streams over
    row chunks (see ``_chunked_softmax_xent``) instead of materializing f32
    logits.
    """
    ax = axis if axis >= 0 else input.ndim + axis
    if (use_softmax and not soft_label and label_smoothing == 0.0
            and weight is None and ax == input.ndim - 1 and input.ndim >= 1):
        lbl = label
        if lbl.ndim == input.ndim and lbl.shape[ax] == 1:
            lbl = jnp.squeeze(lbl, axis=ax)
        v = input.shape[-1]
        flat = input.reshape(-1, v)
        lbl_flat = lbl.reshape(-1)
        valid = lbl_flat != ignore_index
        safe = jnp.where(valid, lbl_flat, 0)
        loss = _chunked_softmax_xent(flat, safe)
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            return jnp.sum(loss) / denom
        if reduction == "sum":
            return jnp.sum(loss)
        return loss.reshape(lbl.shape)
    logits = input.astype(jnp.float32)
    if use_softmax:
        logp = jax.nn.log_softmax(logits, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(logits, 1e-30))
    n_classes = logits.shape[axis]
    if soft_label:
        soft = label.astype(jnp.float32)
        if label_smoothing > 0:
            soft = soft * (1 - label_smoothing) + label_smoothing / n_classes
        loss = -jnp.sum(soft * logp, axis=axis)
        valid = None
    else:
        lbl = label
        if lbl.ndim == logp.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        valid = lbl != ignore_index
        safe_lbl = jnp.where(valid, lbl, 0)
        onehot_logp = jnp.take_along_axis(
            logp, safe_lbl[..., None].astype(jnp.int32), axis=axis)[..., 0]
        if label_smoothing > 0:
            smooth_loss = -jnp.mean(logp, axis=axis)
            loss = (1 - label_smoothing) * (-onehot_logp) + \
                label_smoothing * smooth_loss
        else:
            loss = -onehot_logp
        if weight is not None:
            loss = loss * jnp.take(weight, safe_lbl, axis=0)
        loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        if valid is not None:
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            if weight is not None:
                denom = jnp.maximum(jnp.sum(
                    jnp.where(valid, jnp.take(weight, safe_lbl, axis=0), 0.0)),
                    1e-9)
            return jnp.sum(loss) / denom
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    loss = cross_entropy(logits, label, reduction="none", soft_label=soft_label,
                         ignore_index=ignore_index, axis=axis)
    from ..ops.registry import OPS
    loss = OPS["unsqueeze"].user_fn(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


@op()
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    picked = -jnp.take_along_axis(input, safe[..., None].astype(jnp.int32),
                                  axis=-1)[..., 0]
    if weight is not None:
        w = jnp.take(weight, safe, axis=0)
        picked = picked * w
    picked = jnp.where(valid, picked, 0.0)
    if reduction == "mean":
        denom = jnp.sum(jnp.take(weight, safe, axis=0) * valid) if weight is not None \
            else jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return jnp.sum(picked) / denom
    if reduction == "sum":
        return jnp.sum(picked)
    return picked


@op()
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.maximum(input, eps)) +
             (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


@op()
def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None):
    neg_abs = -jnp.abs(logit)
    loss = jnp.maximum(logit, 0.0) - logit * label + jnp.log1p(jnp.exp(neg_abs))
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = loss * log_w
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


@op()
def kl_div(input, label, reduction="mean"):
    loss = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


@op()
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    loss = jnp.maximum(-label * (input - other) + margin, 0.0)
    return _reduce(loss, reduction)


@op()
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1, input, jnp.maximum(0.0, margin - input))
    return _reduce(loss, reduction)


@op()
def square_error_cost(input, label):
    return jnp.square(input - label)


@op()
def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = jnp.maximum(logit, 0.0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * ((1 - p_t) ** gamma)
    if alpha >= 0:
        a_t = alpha * label + (1 - alpha) * (1 - label)
        loss = a_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


@op()
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a - b) + epsilon, p), -1),
                         1.0 / p)
    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_neg = jnp.minimum(d_neg, dist(positive, negative))
    return _reduce(jnp.maximum(d_pos - d_neg + margin, 0.0), reduction)


@op()
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    cos = jnp.sum(input1 * input2, -1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1), 1e-12)
    loss = jnp.where(label == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce(loss, reduction)

def ctc_loss(log_probs, labels, input_lengths=None, label_lengths=None,
             blank=0, reduction="mean", norm_by_times=False):
    """CTC loss (reference paddle.nn.functional.ctc_loss over the warpctc
    kernel).  log_probs: [T, B, C] time-major logits."""
    from ..ops.seq_ops import warpctc

    loss = warpctc(log_probs, labels, logits_length=input_lengths,
                   labels_length=label_lengths, blank=blank,
                   norm_by_times=norm_by_times)
    # loss is a Tensor (warpctc is a registered op): reduce at Tensor level
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


# ---------------- state-space (Mamba-2) ----------------

def _causal_conv1d_taps(x, weight, bias=None, start=0):
    """:func:`causal_conv1d` as the XLA composition: over the channels
    ``start .. start + C`` of ``x`` a sum of ``K`` shifted products in
    float32, rounded once to ``x``'s dtype."""
    channels, taps = weight.shape
    x = x[..., start:start + channels]
    x32 = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    t = x.shape[1]
    out = sum(x32[:, k:k + t] * w32[:, k] for k in range(taps))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _causal_conv1d_silu(x, weight, bias=None, start=0):
    """``silu`` of :func:`_causal_conv1d_taps`: the composition the
    ``causal_conv_*`` kernels stand in for."""
    return jax.nn.silu(_causal_conv1d_taps(x, weight, bias, start))


@op()
def causal_conv1d(x, weight, bias=None, activation=None, start=0):
    """Causal depthwise convolution over time: ``x [B, T, W]``, ``weight [C,
    K]`` (tap ``k`` reads position ``t - (K - 1) + k``: the last tap is the
    position itself), ``bias [C]``, over the channels ``start .. start + C``
    of ``x`` (all of them where ``W`` is ``C``); positions before the row's
    first read zero.  A sum of ``K`` shifted products in float32, rounded
    once to ``x``'s dtype: one fused pass over the row, no convolution op, at
    the Mamba mixer's ``K`` = 4 as at the ``K`` = 2 of a compressed
    attention's first convolution (``models/zaya.py``: no bias, no
    activation).

    ``activation="silu"`` applies :func:`silu` to that (the Mamba mixer's
    use) and lets ``ops.pallas.causal_conv1d`` place the two as ONE call:
    on the TPU, where ``causal_conv_kernel.supports`` takes the shapes
    (``C`` and ``start`` whole 128-lane tiles, rows whole tiles of 16), the
    ``causal_conv_fwd`` / ``causal_conv_bwd`` kernels, which read the
    channels where they lie in ``x`` and whose backward keeps ``x`` alone;
    elsewhere (off the TPU; aloud on it, ``KernelFallbackWarning``, for
    other shapes or under a GSPMD mesh) this composition, differentiated as
    it stands.  ``None`` is the composition alone, never a kernel."""
    if activation is None:
        return _causal_conv1d_taps(x, weight, bias, start)
    if activation != "silu":
        raise ValueError(f"causal_conv1d: activation {activation!r} is "
                         f"neither None nor 'silu'")
    from ..ops import pallas

    return pallas.causal_conv1d(x, weight, bias, start)


def _later(x, steps):
    """``x [B, T, ...]`` ``steps`` later in time, zeros before the row's
    first."""
    if not steps:
        return x
    pad = ((0, 0), (steps, 0)) + ((0, 0),) * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


@op()
def time_shift(x, steps=1):
    """``x [B, T, ...]`` one step (``steps``) later in time: position ``t``
    reads position ``t - steps``, positions before the row's first read
    zero."""
    return _later(x, steps)


@op()
def causal_conv1d_heads(x, weight):
    """Causal convolution over time that is FULL over a head's channels and
    none across heads: ``x [B, T, N, D]``, ``weight [N, K, D, E]`` (tap ``k``
    reads position ``t - (K - 1) + k``, as :func:`causal_conv1d`) ->
    ``[B, T, N, E]``, ``out[t, n] = sum_k x[t - (K - 1) + k, n] @ weight[n,
    k]``; positions before the row's first read zero.  ``K`` batched matmuls
    over the heads on ``x`` as it lies, each result shifted to its tap's
    place in time (the shift commutes with a matmul a position), summed in
    float32 and rounded once to ``x``'s dtype.  The operands are cast up
    and multiplied at the default precision (one bfloat16 pass on the TPU,
    which drops the casts; the CPU's batched dot has no bfloat16 x bfloat16
    -> float32)."""
    taps = weight.shape[1]
    x32, w32 = x.astype(jnp.float32), weight.astype(jnp.float32)
    out = sum(_later(jnp.einsum("btnd,nde->btne", x32, w32[:, k],
                                precision=lax.Precision.DEFAULT),
                     taps - 1 - k) for k in range(taps))
    return out.astype(x.dtype)


def _ssd_scan_row(x, dt, a_head, b, c, d_head, chunk):
    """One row of :func:`ssd_scan`: ``x [T, nh, P]``, ``dt [T, nh]``, ``b``,
    ``c`` ``[T, G, N]``, ``T`` a whole number of chunks."""
    f32 = jnp.float32
    t, nh, p = x.shape
    g, n = b.shape[1:]
    hg, nc = nh // g, t // chunk
    dt = dt.astype(f32).reshape(nc, chunk, g, hg)
    alpha = jnp.cumsum(dt * a_head.astype(f32).reshape(g, hg), axis=1)
    xc = x.reshape(nc, chunk, g, hg, p)
    bc, cc = b.reshape(nc, chunk, g, n), c.reshape(nc, chunk, g, n)
    # inside a chunk: (C_i . B_j) exp(alpha_i - alpha_j) dt_j over j <= i;
    # the difference is masked BEFORE the exp (above the diagonal it is
    # positive and may overflow)
    cb = jnp.einsum("cign,cjgn->cgij", cc, bc, preferred_element_type=f32)
    by_head = alpha.transpose(0, 2, 3, 1)                   # [c, g, h, q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    m = cb[:, :, None] * decay * dt.transpose(0, 2, 3, 1)[..., None, :]
    y = jnp.einsum("cghij,cjghp->cighp", m.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # what a chunk adds to the state at its end, and the state each chunk
    # starts from: the one recurrence left, over T / chunk steps
    to_end = jnp.exp(alpha[:, -1:] - alpha) * dt            # [c, q, g, h]
    local = jnp.einsum(
        "cqghp,cqgn->cghpn",
        (xc.astype(f32) * to_end[..., None]).astype(x.dtype), bc,
        preferred_element_type=f32)

    def carry(state, chunk_in):
        keep, add = chunk_in
        return keep[..., None, None] * state + add, state

    _, entering = lax.scan(carry, jnp.zeros_like(local[0]),
                           (jnp.exp(alpha[:, -1]), local))
    y = y + jnp.exp(alpha)[..., None] * jnp.einsum(
        "cqgn,cghpn->cqghp", cc, entering.astype(x.dtype),
        preferred_element_type=f32)
    y = y + d_head.astype(f32).reshape(g, hg, 1) * xc.astype(f32)
    return y.astype(x.dtype).reshape(t, nh, p)


def _ssd_scan_rows(x, dt, A, B, C, D, chunk=128):
    """:func:`ssd_scan` on rows of whole chunks as the XLA composition:
    :func:`_ssd_scan_row` over the batch."""
    return jax.vmap(lambda x_, dt_, b_, c_: _ssd_scan_row(
        x_, dt_, A, b_, c_, D, chunk))(x, dt, B, C)


@op()
def ssd_scan(x, dt, A, B, C, D, chunk=128):
    """The selective state-space recurrence of Mamba-2 (arXiv:2405.21060)
    in its CHUNKED form.  ``x [B, T, nh, P]``, ``dt [B, T, nh]`` (the step
    sizes, already positive), ``A [nh]`` (negative), ``B``, ``C`` ``[B, T,
    G, N]`` (head ``h`` reads group ``h // (nh / G)``), ``D [nh]``:

        S[t] = exp(dt[t] A) S[t-1] + dt[t] x[t] (outer) B[t];  S[-1] = 0
        y[t] = S[t] C[t] + D x[t]

    computed a chunk of ``chunk`` positions at a time, with ``alpha_i`` the
    running sum of ``dt A`` inside the chunk: inside it ``sum_{j <= i} (C_i
    . B_j) exp(alpha_i - alpha_j) dt_j x_j``; one ``[P, N]`` state a head
    carried from chunk to chunk, read by ``exp(alpha_i) S C_i``.  Never a
    loop over positions, never a ``[T, T]`` matrix.  The decay sums, every
    ``exp`` and the carried states are float32; the matmuls take their
    operands in ``x``'s dtype and accumulate in float32; ``y`` comes back
    in ``x``'s dtype.  A ``T`` that is no whole number of chunks is padded
    with steps of size zero, which leave the state as it is.

    What runs (``ops.pallas.ssd_scan`` decides, by the shapes):

    - on the TPU, where ``ssd_scan_kernel.supports`` takes the shapes
      (chunk and state whole 128-lane tiles, a group's heads whole tiles):
      the ``ssd_scan_fwd`` / ``ssd_scan_bwd`` kernels.  The chunk's decay
      matrices, ``C B^T`` and the carried state live in VMEM; the backward
      keeps the operands and the chunks' ENTERING states ``[T / chunk, nh,
      P, N]`` (float32) and forms every matrix again;
    - elsewhere (off the TPU; aloud on it, ``KernelFallbackWarning``, for
      other shapes or under a GSPMD mesh): :func:`_ssd_scan_row`, three
      batched matmuls and a ``lax.scan`` over ``T / chunk`` steps,
      differentiated as it stands: its backward keeps, per row, the masked
      decay matrix and ``C B^T`` ``[T / chunk, nh, chunk, chunk]``, the
      chunks' local and entering states and the operands.

    Either way under ``jit.TrainStep(remat=...)`` only while its own block
    is differentiated."""
    from ..ops import pallas

    t = x.shape[1]
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    y = pallas.ssd_scan(x, dt, A, B, C, D, chunk)
    return y[:, :t] if pad else y


# ---------------- attention ----------------

def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, window=None,
                                 block_diffusion=None):
    """SDPA on [batch, seq, heads, dim] (paddle layout,
    python/paddle/nn/functional/flash_attention.py:125).  Uses the Pallas
    flash kernel on TPU when available, else XLA attention.  Attention
    dropout draws from the active key stream.  ``key`` and ``value`` may
    have fewer heads than ``query`` (a whole divisor: grouped KV heads,
    never expanded); ``window`` (with ``is_causal``): a query sees itself
    and the ``window - 1`` keys before it; ``block_diffusion`` (without
    it): the row is ``[noised ; clean]`` under the block-diffusion mask of
    that block (``ops.pallas.block_diffusion_mask``)."""
    from ..ops import pallas
    use_drop = dropout_p > 0.0 and training
    drop_key = get_rng_key() if use_drop else None

    @op("scaled_dot_product_attention")
    def _sdpa(query, key, value, attn_mask):
        return pallas.flash_attention(
            query, key, value, attn_mask=attn_mask, is_causal=is_causal,
            dropout_p=dropout_p if use_drop else 0.0, dropout_key=drop_key,
            window=window, block_diffusion=block_diffusion)

    return _sdpa(query, key, value, attn_mask)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True):
    out = scaled_dot_product_attention(query, key, value, is_causal=causal,
                                       training=training)
    if return_softmax:
        return out, None
    return out, None
