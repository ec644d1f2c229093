"""nn functional ops.

Analog of python/paddle/nn/functional/ — activations, linear/conv/pool,
normalization, embedding, attention, losses. Convs lower to
``lax.conv_general_dilated`` (XLA tiles them onto the MXU); attention routes to
the Pallas flash kernel when enabled (FLAGS_use_fused_attention), mirroring the
reference's fused-op dispatch (paddle/phi/kernels/gpu/flash_attn_kernel.cu,
python/paddle/nn/functional/flash_attention.py).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.flags import flags
from paddle_tpu.framework import random as rnd
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.registry import register_op

__all__ = [
    # activations
    "relu", "relu6", "leaky_relu", "elu", "selu", "celu", "gelu", "silu",
    "swish", "mish", "softplus", "softsign", "softshrink", "hardshrink",
    "tanhshrink", "hardtanh", "hardsigmoid", "hardswish", "sigmoid", "tanh",
    "softmax", "log_softmax", "gumbel_softmax", "prelu", "rrelu", "glu",
    "maxout", "log_sigmoid",
    # linear & conv & pool
    "linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
    "conv2d_transpose", "conv3d_transpose", "max_pool1d", "max_pool2d",
    "max_pool3d", "avg_pool1d", "avg_pool2d", "avg_pool3d",
    "adaptive_avg_pool1d", "adaptive_avg_pool2d", "adaptive_max_pool2d",
    "unfold", "interpolate", "upsample", "pixel_shuffle", "pixel_unshuffle",
    # norm / dropout / embedding
    "layer_norm", "rms_norm", "batch_norm", "instance_norm", "group_norm",
    "local_response_norm", "normalize", "dropout", "dropout2d", "dropout3d",
    "alpha_dropout", "embedding", "one_hot",
    # attention
    "scaled_dot_product_attention", "flash_attention", "softmax_mask_fuse",
    # losses
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "kl_div", "smooth_l1_loss", "margin_ranking_loss", "cosine_similarity",
    "cosine_embedding_loss", "ctc_loss", "hinge_embedding_loss",
    "label_smooth", "square_error_cost", "sigmoid_focal_loss",
    "triplet_margin_loss", "pairwise_distance",
    # misc
    "pad", "sequence_mask", "temporal_shift", "class_center_sample",
    "margin_cross_entropy", "flash_attn_varlen",
]

from paddle_tpu.ops.manipulation import pad, one_hot  # noqa: E402  (re-export)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def _unary(name, fn):
    @register_op(name)
    def _op(x, *args, **kwargs):
        return fn(x, *args, **kwargs)
    _op.__name__ = name
    globals()[name] = _op
    return _op


_unary("relu", jax.nn.relu)
_unary("relu6", jax.nn.relu6)
_unary("silu", jax.nn.silu)
_unary("log_sigmoid", jax.nn.log_sigmoid)
_unary("softsign", jax.nn.soft_sign)
_unary("tanhshrink", lambda x: x - jnp.tanh(x))

from paddle_tpu.ops.math import sigmoid, tanh  # noqa: E402  (re-export)


@register_op("leaky_relu")
def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


@register_op("elu")
def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


@register_op("selu")
def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


@register_op("celu")
def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


@register_op("gelu")
def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


@register_op("swish")
def swish(x):
    return jax.nn.silu(x)


@register_op("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@register_op("softplus")
def softplus(x, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jax.nn.softplus(scaled) / beta)


@register_op("softshrink")
def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@register_op("hardshrink")
def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@register_op("hardtanh")
def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


@register_op("hardsigmoid")
def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


@register_op("hardswish")
def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


@register_op("softmax")
def softmax(x, axis=-1, dtype=None):
    out = jax.nn.softmax(x.astype(dtype) if dtype else x, axis=axis)
    return out


@register_op("log_softmax")
def log_softmax(x, axis=-1, dtype=None):
    return jax.nn.log_softmax(x.astype(dtype) if dtype else x, axis=axis)


@register_op("prelu")
def prelu(x, weight, data_format="NCHW"):
    if weight.size == 1:
        w = weight.reshape(())
    else:
        nd = x.ndim
        c_axis = 1 if data_format.startswith("NC") else nd - 1
        shape = [1] * nd
        shape[c_axis] = weight.size
        w = weight.reshape(shape)
    return jnp.where(x >= 0, x, w * x)


@register_op("glu")
def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


@register_op("maxout")
def maxout(x, groups, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(jnp.reshape(x, new_shape), axis=axis + 1)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    if isinstance(x, Tensor):
        key = rnd.split_key()
        return _gumbel_softmax_op(x, key, temperature=temperature, hard=hard, axis=axis)
    raise TypeError("gumbel_softmax expects a Tensor")


@register_op("gumbel_softmax_impl")
def _gumbel_softmax_op(x, key, temperature=1.0, hard=False, axis=-1):
    g = jax.random.gumbel(key, x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False)
        y = y_hard + (y - lax.stop_gradient(y))  # straight-through estimator
    return y


def rrelu(x, lower=0.125, upper=1.0 / 3.0, training=True):
    if not training:
        return leaky_relu(x, (lower + upper) / 2)
    key = rnd.split_key()
    return _rrelu_op(x, key, lower=lower, upper=upper)


@register_op("rrelu_impl")
def _rrelu_op(x, key, lower, upper):
    a = jax.random.uniform(key, x.shape, x.dtype, lower, upper)
    return jnp.where(x >= 0, x, a * x)


# ---------------------------------------------------------------------------
# linear / conv / pool
# ---------------------------------------------------------------------------

@register_op("linear", ref="python/paddle/nn/functional/common.py:linear")
def linear(x, weight, bias=None):
    # paddle weight layout: (in_features, out_features)
    pet = jnp.float32 if jnp.dtype(x.dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)) else None
    out = jnp.matmul(x, weight, preferred_element_type=pet)
    if pet is not None:
        out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_padding(padding, n, kernel, dilation):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * n:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(n)]
    return [tuple(p) for p in padding]


def _conv(x, weight, bias, stride, padding, dilation, groups, n, data_format):
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad_arg = _conv_padding(padding, n, weight.shape[2:], dilation)
    if data_format in ("NCHW", "NCL", "NCDHW"):
        lhs_spec = "NC" + "DHW"[3 - n:]
    else:
        lhs_spec = "N" + "DHW"[3 - n:] + "C"
    rhs_spec = "OI" + "DHW"[3 - n:]
    out_spec = lhs_spec
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, (lhs_spec, rhs_spec, out_spec))
    # No preferred_element_type here: jax's conv transpose rule (unlike
    # dot_general's) can't differentiate through a widened output dtype —
    # the f32 cotangent meets the bf16 weight and conv rejects mixed
    # dtypes. The TPU MXU accumulates bf16 convs in f32 internally anyway.
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad_arg,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        c_axis = lhs_spec.index("C")
        shape = [1] * out.ndim
        shape[c_axis] = bias.shape[0]
        out = out + jnp.reshape(bias, shape)
    return out


@register_op("conv1d")
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1, data_format)


@register_op("conv2d", ref="paddle/phi/kernels/gpudnn/conv_kernel.cu (cuDNN path) -> lax.conv_general_dilated")
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2, data_format)


@register_op("conv3d")
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3, data_format)


def _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                    groups, n, data_format):
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    if data_format in ("NCHW", "NCL", "NCDHW"):
        lhs_spec = "NC" + "DHW"[3 - n:]
    else:
        lhs_spec = "N" + "DHW"[3 - n:] + "C"
    # paddle transpose-conv weight layout: (in_c, out_c//groups, *k)
    rhs_spec = "IO" + "DHW"[3 - n:]
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, (lhs_spec, rhs_spec, lhs_spec))
    op_ = _norm_tuple(output_padding, n) if output_padding else (0,) * n
    if isinstance(padding, str):
        if any(op_):
            raise ValueError("conv_transpose: output_padding requires "
                             "explicit (numeric) padding, got "
                             f"padding={padding!r}")
        pad_arg = padding.upper()
    else:
        p = _conv_padding(padding, n, weight.shape[2:], dilation)
        # conv_transpose padding semantics: invert forward-conv padding.
        # output_padding extends the high side of the dilated-input conv, so
        # the extra rows/cols hold real gradient-of-conv values (matching
        # paddle/torch), not zeros.
        k = weight.shape[2:]
        pad_arg = [
            (dilation[i] * (k[i] - 1) - p[i][0],
             dilation[i] * (k[i] - 1) - p[i][1] + op_[i])
            for i in range(n)
        ]
    # transposed conv = gradient-of-conv: dilate the input by `stride` and
    # convolve with the spatially-flipped kernel (weight layout (I, O, *k)
    # already has x's channels as the contracting dim)
    spatial = tuple(range(2, 2 + n))
    weight = jnp.flip(weight, axis=spatial)
    out = lax.conv_general_dilated(
        x, weight, window_strides=(1,) * n, padding=pad_arg,
        lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        c_axis = lhs_spec.index("C")
        shape = [1] * out.ndim
        shape[c_axis] = bias.shape[0]
        out = out + jnp.reshape(bias, shape)
    return out


@register_op("conv1d_transpose")
def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCL"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 1, data_format)


@register_op("conv2d_transpose")
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCHW"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 2, data_format)


@register_op("conv3d_transpose")
def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCDHW"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 3, data_format)


def _pool(x, kernel, stride, padding, n, reducer, init, data_format, ceil_mode=False,
          count_include_pad=True):
    kernel = _norm_tuple(kernel, n)
    stride = _norm_tuple(stride if stride is not None else kernel, n)
    if data_format in ("NCHW", "NCL", "NCDHW"):
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        spatial0 = 2
    else:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        spatial0 = 1
    if isinstance(padding, str):
        pad_cfg = padding.upper()
    else:
        p = _conv_padding(padding, n, kernel, (1,) * n)
        pads = [(0, 0)] * x.ndim
        for i in range(n):
            lo, hi = p[i]
            if ceil_mode:
                size = x.shape[spatial0 + i]
                rem = (size + lo + hi - kernel[i]) % stride[i]
                if rem:
                    hi += stride[i] - rem  # cover the tail window
            pads[spatial0 + i] = (lo, hi)
        pad_cfg = pads
    # reduce_window pads with `init` (-inf for max, 0 for sum), so avg counts
    # stay exclusive of padding automatically
    return lax.reduce_window(x, init, reducer, window, strides, pad_cfg)


def _pool_with_index(x, kernel_size, stride, padding, nd, ceil_mode,
                     data_format):
    """return_mask branch shared by max_pool1/2/3d: channel-last input is
    transposed to channel-first for the index kernel (and back), ceil_mode
    is rejected rather than silently ignored."""
    if ceil_mode:
        raise NotImplementedError(
            "max_pool(return_mask=True) does not support ceil_mode=True")
    from paddle_tpu.nn.functional_extra import max_pool_with_index
    channel_last = data_format in ("NLC", "NHWC", "NDHWC")
    if channel_last:
        fwd = (0, nd + 1) + tuple(range(1, nd + 1))      # to channel-first
        bwd = (0,) + tuple(range(2, nd + 2)) + (1,)      # back
        x = jnp.transpose(x, fwd)
    out, idx = max_pool_with_index(x, kernel_size, stride, padding, nd=nd)
    if channel_last:
        out = jnp.transpose(out, bwd)
        idx = jnp.transpose(idx, bwd)
    return out, idx


@register_op("max_pool1d")
def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCL"):
    if return_mask:
        return _pool_with_index(x, kernel_size, stride, padding, 1,
                                ceil_mode, data_format)
    return _pool(x, kernel_size, stride, padding, 1, lax.max, -jnp.inf,
                 data_format, ceil_mode)


@register_op("max_pool2d")
def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW"):
    if return_mask:
        return _pool_with_index(x, kernel_size, stride, padding, 2,
                                ceil_mode, data_format)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return _pool(x, kernel_size, stride, padding, 2, lax.max, init,
                 data_format, ceil_mode)


@register_op("max_pool3d")
def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW"):
    if return_mask:
        return _pool_with_index(x, kernel_size, stride, padding, 3,
                                ceil_mode, data_format)
    return _pool(x, kernel_size, stride, padding, 3, lax.max, -jnp.inf,
                 data_format, ceil_mode)


def _avg_pool(x, kernel_size, stride, padding, n, data_format, ceil_mode=False,
              exclusive=True, divisor_override=None):
    s = _pool(x, kernel_size, stride, padding, n, lax.add, 0.0, data_format,
              ceil_mode)
    if divisor_override is not None:
        return s / divisor_override
    if exclusive:
        ones = jnp.ones_like(x)
        cnt = _pool(ones, kernel_size, stride, padding, n, lax.add, 0.0,
                    data_format, ceil_mode)
        return s / cnt
    kernel = _norm_tuple(kernel_size, n)
    import numpy as _np
    return s / float(_np.prod(kernel))


@register_op("avg_pool1d")
def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL"):
    return _avg_pool(x, kernel_size, stride, padding, 1, data_format,
                     ceil_mode, exclusive)


@register_op("avg_pool2d")
def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW"):
    return _avg_pool(x, kernel_size, stride, padding, 2, data_format,
                     ceil_mode, exclusive, divisor_override)


@register_op("avg_pool3d")
def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW"):
    return _avg_pool(x, kernel_size, stride, padding, 3, data_format,
                     ceil_mode, exclusive, divisor_override)


@register_op("adaptive_avg_pool1d")
def adaptive_avg_pool1d(x, output_size):
    n = x.shape[-1]
    out = int(output_size) if not isinstance(output_size, (list, tuple)) else int(output_size[0])
    assert n % out == 0, "adaptive pool requires divisible sizes"
    return jnp.mean(jnp.reshape(x, x.shape[:-1] + (out, n // out)), axis=-1)


def _adaptive_pool_matrix(n_in: int, n_out: int, dtype):
    """(n_out, n_in) averaging matrix with torch/paddle adaptive windows
    (start = floor(i*n/o), end = ceil((i+1)*n/o)); pooling becomes a small
    matmul, which is the MXU-friendly general (non-divisible) form."""
    import numpy as np
    m = np.zeros((n_out, n_in), dtype=np.float32)
    for i in range(n_out):
        s = (i * n_in) // n_out
        e = -(-((i + 1) * n_in) // n_out)  # ceil
        m[i, s:e] = 1.0 / (e - s)
    return jnp.asarray(m, dtype=dtype)


@register_op("adaptive_avg_pool2d")
def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    oh, ow = output_size
    if data_format == "NCHW":
        n_, c, h, w = x.shape
        if h % oh == 0 and w % ow == 0:  # fast path: plain reshape-mean
            r = jnp.reshape(x, (n_, c, oh, h // oh, ow, w // ow))
            return jnp.mean(r, axis=(3, 5))
        cdt = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
        mh = _adaptive_pool_matrix(h, oh, cdt)
        mw = _adaptive_pool_matrix(w, ow, cdt)
        # highest precision: default TPU matmul quantizes to bf16, which
        # would put ~3e-3 error into a pooling average
        out = jnp.einsum("nchw,oh,pw->ncop", x.astype(cdt), mh, mw,
                         precision="highest")
        return out.astype(x.dtype)
    n_, h, w, c = x.shape
    if h % oh == 0 and w % ow == 0:
        r = jnp.reshape(x, (n_, oh, h // oh, ow, w // ow, c))
        return jnp.mean(r, axis=(2, 4))
    cdt = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
    mh = _adaptive_pool_matrix(h, oh, cdt)
    mw = _adaptive_pool_matrix(w, ow, cdt)
    return jnp.einsum("nhwc,oh,pw->nopc", x.astype(cdt), mh, mw,
                      precision="highest").astype(x.dtype)


@register_op("adaptive_max_pool2d")
def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    oh, ow = output_size
    n_, c, h, w = x.shape
    r = jnp.reshape(x, (n_, c, oh, h // oh, ow, w // ow))
    return jnp.max(r, axis=(3, 5))


@register_op("unfold")
def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    p = _conv_padding(paddings, 2, k, d)
    n_, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), p[0], p[1]])
    patches = lax.conv_general_dilated_patches(
        xp, filter_shape=k, window_strides=s, padding="VALID", rhs_dilation=d,
        dimension_numbers=lax.conv_dimension_numbers(xp.shape, (1, 1) + k, ("NCHW", "OIHW", "NCHW")))
    # patches: (N, C*kh*kw, oh, ow) -> (N, C*kh*kw, L)
    return jnp.reshape(patches, (n_, patches.shape[1], -1))


@register_op("interpolate")
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    if data_format == "NCHW":
        n_, c, h, w = x.shape
        if size is None:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor,) * 2
            size = (int(h * sf[0]), int(w * sf[1]))
        xs = jnp.transpose(x, (0, 2, 3, 1))
        method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic",
                  "area": "linear", "linear": "linear"}[mode]
        out = jax.image.resize(xs, (n_, size[0], size[1], c), method=method)
        return jnp.transpose(out, (0, 3, 1, 2)).astype(x.dtype)
    n_, h, w, c = x.shape
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor,) * 2
        size = (int(h * sf[0]), int(w * sf[1]))
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]
    return jax.image.resize(x, (n_, size[0], size[1], c), method=method).astype(x.dtype)


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             data_format="NCHW"):
    return interpolate(x, size=size, scale_factor=scale_factor, mode=mode,
                       align_corners=align_corners, data_format=data_format)


@register_op("pixel_shuffle")
def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    n_, c, h, w = x.shape
    oc = c // (r * r)
    out = jnp.reshape(x, (n_, oc, r, r, h, w))
    out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
    return jnp.reshape(out, (n_, oc, h * r, w * r))


@register_op("pixel_unshuffle")
def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    n_, c, h, w = x.shape
    out = jnp.reshape(x, (n_, c, h // r, r, w // r, r))
    out = jnp.transpose(out, (0, 1, 3, 5, 2, 4))
    return jnp.reshape(out, (n_, c * r * r, h // r, w // r))


# ---------------------------------------------------------------------------
# normalization / dropout / embedding
# ---------------------------------------------------------------------------

@register_op("layer_norm", ref="paddle/phi/kernels/gpu/layer_norm_kernel.cu; spmd rule paddle/phi/infermeta/spmd_rules/layer_norm.cc")
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    # f32 statistics for bf16 inputs (numerics parity with fused kernels)
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@register_op("rms_norm")
def rms_norm(x, weight=None, epsilon=1e-6):
    """RMSNorm (fused analog: paddle.incubate.nn.functional.fused_rms_norm,
    paddle/phi/kernels/fusion/gpu/fused_rms_norm). Routes to the Pallas
    kernel (ops/pallas/rms_norm.py) when shapes/flags allow."""
    from paddle_tpu.ops.fused_norm import _pallas_ok, rms_lax, rms_norm_fused
    if weight is not None and _pallas_ok(x, weight, epsilon):
        return rms_norm_fused(x, weight, epsilon)
    return rms_lax(x, weight, epsilon)


@register_op("batch_norm_infer")
def _batch_norm_infer(x, running_mean, running_var, weight, bias, epsilon, c_axis):
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    rm = jnp.reshape(running_mean, shape)
    rv = jnp.reshape(running_var, shape)
    out = (x - rm) * lax.rsqrt(rv + epsilon)
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    return out


@register_op("batch_norm_train", n_outputs=3)
def _batch_norm_train(x, weight, bias, epsilon, c_axis):
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    out = (xf - jnp.reshape(mean, shape)) * lax.rsqrt(jnp.reshape(var, shape) + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    return out, mean, var


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None):
    """Stateful BN entry: updates running stats eagerly in training mode
    (python/paddle/nn/functional/norm.py batch_norm analog)."""
    c_axis = 1 if data_format.startswith("NC") or x.ndim <= 2 else x.ndim - 1
    if x.ndim <= 2:
        c_axis = x.ndim - 1
    if not training or use_global_stats:
        return _batch_norm_infer(x, running_mean, running_var, weight, bias,
                                 epsilon, c_axis)
    out, mean, var = _batch_norm_train(x, weight, bias, epsilon, c_axis)
    if isinstance(running_mean, Tensor):
        m = momentum
        running_mean._set_value(running_mean.value * m + mean.value * (1 - m))
        running_var._set_value(running_var.value * m + var.value * (1 - m))
    return out


@register_op("instance_norm")
def instance_norm(x, weight=None, bias=None, epsilon=1e-5, data_format="NCHW"):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out + jnp.reshape(bias, shape)
    return out


@register_op("group_norm")
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    """GroupNorm (fused analog: paddle/phi/kernels/fusion add_group_norm_*).
    Routes to the Pallas kernel (ops/pallas/group_norm.py) when
    shapes/flags allow; missing affine params become constants whose
    grads jax drops (zero cotangents on literals)."""
    from paddle_tpu.ops.fused_norm import _gn_pallas_ok, group_norm_fused
    if data_format == "NCHW" and x.ndim >= 3 \
            and _gn_pallas_ok(x, num_groups, epsilon):
        w = weight if weight is not None else jnp.ones(x.shape[1], x.dtype)
        b = bias if bias is not None else jnp.zeros(x.shape[1], x.dtype)
        return group_norm_fused(x, w, b, num_groups, epsilon, None)
    n_, c = x.shape[0], x.shape[1]
    g = num_groups
    r = jnp.reshape(x, (n_, g, c // g) + x.shape[2:])
    axes = tuple(range(2, r.ndim))
    mean = jnp.mean(r, axis=axes, keepdims=True)
    var = jnp.var(r, axis=axes, keepdims=True)
    out = (r - mean) * lax.rsqrt(var + epsilon)
    out = jnp.reshape(out, x.shape)
    shape = [1, c] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        out = out + jnp.reshape(bias, shape)
    return out


@register_op("local_response_norm")
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
    sq = jnp.square(x)
    c = x.shape[1]
    half = size // 2
    padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2))
    acc = sum(padded[:, i:i + c] for i in range(size))
    return x / jnp.power(k + alpha * acc, beta)


@register_op("normalize")
def normalize(x, p=2, axis=1, epsilon=1e-12):
    n = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(n, epsilon)


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1 - p) if isinstance(x, Tensor) else x * (1 - p)
        return x
    key = rnd.split_key()
    return _dropout_op(x, key, p=p, mode=mode, axis=axis)


@register_op("dropout_impl")
def _dropout_op(x, key, p, mode, axis=None):
    shape = x.shape
    if axis is not None:
        axes = [axis] if isinstance(axis, int) else list(axis)
        shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    if not training or p == 0.0:
        return x
    key = rnd.split_key()
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return _dropout_op(x, key, p=p, mode="upscale_in_train", axis=axis)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    if not training or p == 0.0:
        return x
    key = rnd.split_key()
    axis = (0, 1) if data_format == "NCDHW" else (0, 4)
    return _dropout_op(x, key, p=p, mode="upscale_in_train", axis=axis)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    key = rnd.split_key()
    return _alpha_dropout_op(x, key, p=p)


@register_op("alpha_dropout_impl")
def _alpha_dropout_op(x, key, p):
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / (scale * ((1 - p) * (1 + p * alpha_p ** 2)) ** 0.5))
    b = -a * alpha_p * p
    return (a * jnp.where(keep, x, alpha_p) + b).astype(x.dtype)


@register_op("embedding", ref="paddle/phi/kernels embedding; spmd rule paddle/phi/infermeta/spmd_rules/embedding.cc")
def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@register_op("sdpa_ref")
def _sdpa_ref(q, k, v, attn_mask=None, dropout_key=None, dropout_p=0.0,
              causal=False, scale=None):
    """Reference attention in pure XLA ops (flash path in ops/pallas).

    q/k/v: (batch, seq, heads, head_dim) — paddle flash_attention layout.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qT = jnp.swapaxes(q, 1, 2)  # (b,h,s,d)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_key is not None and dropout_p > 0.0:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True):
    """python/paddle/nn/functional/flash_attention.py:scaled_dot_product_attention
    analog. Layout (batch, seq, heads, head_dim)."""
    if (flags.use_fused_attention and attn_mask is None and dropout_p == 0.0
            and key.shape[1] >= flags.flash_attention_min_seq):
        from paddle_tpu.ops.pallas import flash_attention as fa
        if fa.supported(query.shape, key.shape, is_causal):
            return fa.flash_attention_op(query, key, value, causal=is_causal)
    dk = rnd.split_key() if (dropout_p > 0.0 and training) else None
    return _sdpa_ref(query, key, value, attn_mask=attn_mask, dropout_key=dk,
                     dropout_p=dropout_p if training else 0.0, causal=is_causal)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True):
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    if return_softmax:
        return out, None
    return out


@register_op("softmax_mask_fuse")
def softmax_mask_fuse(x, mask):
    return jax.nn.softmax(x + mask, axis=-1)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@register_op("cross_entropy", ref="paddle/phi/infermeta/spmd_rules/cross_entropy_with_softmax.cc; python/paddle/nn/functional/loss.py")
def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0):
    if use_softmax:
        logp = jax.nn.log_softmax(input.astype(jnp.float32), axis=axis)
    else:
        logp = jnp.log(jnp.maximum(input.astype(jnp.float32), 1e-30))
    n_classes = input.shape[axis]
    if soft_label:
        target = label.astype(jnp.float32)
    else:
        lbl = label
        if lbl.ndim == logp.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        target = jax.nn.one_hot(lbl, n_classes, axis=axis, dtype=jnp.float32)
    if label_smoothing > 0.0:
        target = target * (1 - label_smoothing) + label_smoothing / n_classes
    loss = -jnp.sum(target * logp, axis=axis)
    applied_weight = None
    if weight is not None and not soft_label:
        lbl = label
        if lbl.ndim == logp.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        applied_weight = jnp.take(weight, lbl)
        loss = loss * applied_weight
    if not soft_label:
        # ignore_index masking applies for ANY sentinel value, including the
        # default -100 (paddle semantics: ignored tokens contribute no loss
        # and do not count in the mean denominator). one_hot already zeroes
        # out-of-range labels; the denominator is the real divergence risk.
        lbl = label
        if lbl.ndim == logp.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        valid = (lbl != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            if applied_weight is not None:
                denom = jnp.maximum(jnp.sum(applied_weight * valid), 1e-12)
            else:
                denom = jnp.maximum(jnp.sum(valid), 1)
            return jnp.sum(loss) / denom
    if reduction == "mean" and applied_weight is not None:
        # paddle: weighted mean divides by the sum of applied weights
        return jnp.sum(loss) / jnp.maximum(jnp.sum(applied_weight), 1e-12)
    return _reduce_loss(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False, axis=-1,
                               ignore_index=-100, return_softmax=False):
    loss = cross_entropy(logits, label, soft_label=soft_label, axis=axis,
                         ignore_index=ignore_index, reduction="none")
    if isinstance(loss, Tensor):
        loss = loss.unsqueeze(axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


@register_op("binary_cross_entropy")
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.maximum(input, eps)) +
             (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


@register_op("binary_cross_entropy_with_logits")
def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None):
    softplus_neg_abs = jnp.log1p(jnp.exp(-jnp.abs(logit)))
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        loss = (1 - label) * logit + log_w * (softplus_neg_abs + jnp.maximum(-logit, 0))
    else:
        loss = jnp.maximum(logit, 0) - logit * label + softplus_neg_abs
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


@register_op("mse_loss")
def mse_loss(input, label, reduction="mean"):
    return _reduce_loss(jnp.square(input - label), reduction)


@register_op("l1_loss")
def l1_loss(input, label, reduction="mean"):
    return _reduce_loss(jnp.abs(input - label), reduction)


@register_op("nll_loss")
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    picked = -jnp.take_along_axis(input, label[..., None], axis=-1)[..., 0]
    if weight is not None:
        w = jnp.take(weight, label)
        picked = picked * w
    if ignore_index >= 0:
        valid = label != ignore_index
        picked = jnp.where(valid, picked, 0.0)
        if reduction == "mean":
            denom = jnp.sum(jnp.take(weight, label) * valid) if weight is not None else jnp.maximum(jnp.sum(valid), 1)
            return jnp.sum(picked) / denom
    if reduction == "mean" and weight is not None:
        return jnp.sum(picked) / jnp.sum(jnp.take(weight, label))
    return _reduce_loss(picked, reduction)


@register_op("kl_div")
def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        loss = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    return _reduce_loss(loss, reduction)


@register_op("smooth_l1_loss")
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    return _reduce_loss(loss, reduction)


@register_op("margin_ranking_loss")
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    loss = jnp.maximum(-label * (input - other) + margin, 0.0)
    return _reduce_loss(loss, reduction)


@register_op("cosine_similarity")
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot_ = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot_ / jnp.maximum(n1 * n2, eps)


@register_op("cosine_embedding_loss")
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    cos = jnp.sum(input1 * input2, axis=-1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1), 1e-12)
    loss = jnp.where(label == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
    return _reduce_loss(loss, reduction)


@register_op("hinge_embedding_loss")
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1, input, jnp.maximum(margin - input, 0.0))
    return _reduce_loss(loss, reduction)


@register_op("triplet_margin_loss")
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2,
                        epsilon=1e-6, swap=False, reduction="mean"):
    dp = jnp.linalg.norm(input - positive + epsilon, ord=p, axis=-1)
    dn = jnp.linalg.norm(input - negative + epsilon, ord=p, axis=-1)
    if swap:
        dn2 = jnp.linalg.norm(positive - negative + epsilon, ord=p, axis=-1)
        dn = jnp.minimum(dn, dn2)
    return _reduce_loss(jnp.maximum(dp - dn + margin, 0.0), reduction)


@register_op("pairwise_distance")
def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    return jnp.linalg.norm(x - y + epsilon, ord=p, axis=-1, keepdims=keepdim)


@register_op("label_smooth")
def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / n


@register_op("square_error_cost")
def square_error_cost(input, label):
    return jnp.square(input - label)


@register_op("sigmoid_focal_loss")
def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * jnp.power(1 - p_t, gamma)
    if alpha >= 0:
        a_t = alpha * label + (1 - alpha) * (1 - label)
        loss = a_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce_loss(loss, reduction)


@register_op("ctc_loss",
             ref="paddle/phi/kernels/impl/warpctc_kernel_impl.h (warpctc) "
                 "-> alpha-recursion lax.scan")
def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss via the standard alpha (forward) recursion, batched and
    scanned over time — differentiable through jax autodiff (no separate
    beta/gradient kernel needed, unlike warpctc).

    log_probs: (T, B, C) log-softmax outputs; labels: (B, L) int padded;
    input_lengths/label_lengths: (B,).
    """
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    neg_inf = jnp.float32(-1e30)
    lp = log_probs.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    input_lengths = jnp.asarray(input_lengths, jnp.int32)
    label_lengths = jnp.asarray(label_lengths, jnp.int32)

    # extended sequence: blank, l1, blank, l2, ..., blank  (B, S)
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    pos = jnp.arange(S)[None, :]
    # skip-transition allowed where ext[s] != ext[s-2] and ext[s] != blank
    ext_m2 = jnp.concatenate([jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], 1)
    can_skip = (ext != blank) & (ext != ext_m2)
    valid = pos < (2 * label_lengths[:, None] + 1)

    def emit(t_lp, s_idx):
        # log prob of emitting ext symbol at each position: (B, S)
        return jnp.take_along_axis(t_lp, s_idx, axis=1)

    a0 = jnp.full((B, S), neg_inf)
    a0 = a0.at[:, 0].set(lp[0, :, blank])
    first_lab = jnp.where(label_lengths > 0,
                          jnp.take_along_axis(
                              lp[0], ext[:, 1:2], axis=1)[:, 0], neg_inf)
    a0 = a0.at[:, 1].set(first_lab)
    a0 = jnp.where(valid, a0, neg_inf)

    def step(alpha, t_lp):
        shift1 = jnp.concatenate(
            [jnp.full((B, 1), neg_inf), alpha[:, :-1]], 1)
        shift2 = jnp.concatenate(
            [jnp.full((B, 2), neg_inf), alpha[:, :-2]], 1)
        shift2 = jnp.where(can_skip, shift2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
        new = merged + emit(t_lp, ext)
        return jnp.where(valid, new, neg_inf), new

    _, alphas = lax.scan(step, a0, lp[1:])          # (T-1, B, S)
    alphas = jnp.concatenate([a0[None], alphas], 0)  # (T, B, S)

    # per-sample loss: -logadd(alpha[T_b-1, last], alpha[T_b-1, last-1])
    t_idx = jnp.clip(input_lengths - 1, 0, T - 1)
    last = jnp.take_along_axis(
        alphas, t_idx[None, :, None], axis=0)[0]     # (B, S)
    end = 2 * label_lengths                          # blank after last label
    a_end = jnp.take_along_axis(last, end[:, None], axis=1)[:, 0]
    a_end1 = jnp.where(
        label_lengths > 0,
        jnp.take_along_axis(last, jnp.maximum(end - 1, 0)[:, None],
                            axis=1)[:, 0], neg_inf)
    nll = -jnp.logaddexp(a_end, a_end1)
    if norm_by_times:
        nll = nll / jnp.maximum(input_lengths.astype(jnp.float32), 1.0)
    if reduction == "mean":
        # paddle semantics: per-sample loss / label_length, then mean
        return jnp.mean(nll / jnp.maximum(
            label_lengths.astype(jnp.float32), 1.0))
    if reduction == "sum":
        return jnp.sum(nll)
    return nll


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

@register_op("sequence_mask", differentiable=False)
def sequence_mask(x, maxlen=None, dtype="int64"):
    maxlen = int(maxlen) if maxlen is not None else None
    if maxlen is None:
        raise ValueError("sequence_mask requires static maxlen under TPU tracing")
    r = jnp.arange(maxlen)
    return (r[None, :] < x[..., None]).astype(jnp.dtype(dtype))


@register_op("temporal_shift")
def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    nt, c, h, w = x.shape
    n = nt // seg_num
    r = jnp.reshape(x, (n, seg_num, c, h, w))
    fold = int(c * shift_ratio)
    left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, -1:, :fold])], axis=1)
    right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]), r[:, :-1, fold:2 * fold]], axis=1)
    rest = r[:, :, 2 * fold:]
    out = jnp.concatenate([left, right, rest], axis=2)
    return jnp.reshape(out, (nt, c, h, w))


# schema-codegen'd losses + vision ops re-exported on the functional surface
# (defined once in ops/schema_defs.py; see ops/schema.py for the fan-out)
from paddle_tpu.ops.schema_defs import (  # noqa: E402
    affine_grid, channel_shuffle, dice_loss, grid_sample, huber_loss,
    log_loss, multi_label_soft_margin_loss, npair_loss, pdist,
    soft_margin_loss)

__all__ += [
    "affine_grid", "channel_shuffle", "dice_loss", "grid_sample",
    "huber_loss", "log_loss", "multi_label_soft_margin_loss", "npair_loss",
    "pdist", "soft_margin_loss",
]


# functional surface round-out (see nn/functional_extra.py)
from paddle_tpu.nn.functional_extra import (  # noqa: E402
    adaptive_avg_pool3d, adaptive_max_pool1d, adaptive_max_pool3d, bilinear,
    fold, fractional_max_pool2d, fractional_max_pool3d, gaussian_nll_loss,
    hsigmoid_loss, max_unpool1d, max_unpool2d, max_unpool3d,
    multi_margin_loss, poisson_nll_loss, rnnt_loss, spectral_norm,
    thresholded_relu, triplet_margin_with_distance_loss)

__all__ += [
    "adaptive_avg_pool3d", "adaptive_max_pool1d", "adaptive_max_pool3d",
    "bilinear", "fold", "fractional_max_pool2d", "fractional_max_pool3d",
    "gaussian_nll_loss", "hsigmoid_loss", "max_unpool1d", "max_unpool2d",
    "max_unpool3d", "multi_margin_loss", "poisson_nll_loss", "rnnt_loss",
    "spectral_norm", "thresholded_relu",
    "triplet_margin_with_distance_loss",
]


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample class centers for margin-based softmax training
    (python/paddle/nn/functional/common.py::class_center_sample,
    phi class_center_sample kernel). All POSITIVE classes in ``label``
    are kept; negative classes fill up to ``num_samples``. Returns
    (remapped_label, sampled_class_index). Sampling is data-dependent
    (unique counts), so this op is eager-only — inside jit, sample on
    the host per step and feed the result. ``group``: restrict to a
    model-parallel shard's class range [group.rank*num_classes_local, ...)
    is handled by callers; here num_classes is THIS shard's count."""
    import numpy as _np

    lab = _np.asarray(label.numpy() if isinstance(label, Tensor)
                      else label).astype(_np.int64)
    pos = _np.unique(lab)
    if len(pos) >= num_samples:
        sampled = pos
    else:
        rng_key = rnd.split_key()
        seed = int(_np.asarray(jax.random.randint(
            rng_key, (), 0, 2 ** 31 - 1)))
        g = _np.random.default_rng(seed)
        neg_pool = _np.setdiff1d(_np.arange(num_classes, dtype=_np.int64),
                                 pos, assume_unique=True)
        extra = g.choice(neg_pool, size=num_samples - len(pos),
                         replace=False)
        sampled = _np.concatenate([pos, extra])
    remap = _np.full((num_classes,), -1, _np.int64)
    remap[sampled] = _np.arange(len(sampled))
    return (Tensor(jnp.asarray(remap[lab])),
            Tensor(jnp.asarray(sampled)))


@register_op("margin_cross_entropy",
             ref="python/paddle/nn/functional/loss.py:margin_cross_entropy "
                 "(ArcFace-family margin softmax)")
def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    """ArcFace/CosFace margin softmax: the target-class cosine theta gets
    cos(m1*theta + m2) - m3 before scaling. ``logits`` are normalized
    cosines (N, C). The reference's model-parallel variant shards C
    across ranks with a custom comm kernel; here class-sharded logits
    are GSPMD shardings — jit the call with logits sharded on the class
    axis and XLA inserts the softmax collectives."""
    lbl = label.reshape((-1,)).astype(jnp.int32)
    C = logits.shape[-1]
    onehot = jax.nn.one_hot(lbl, C, dtype=logits.dtype)
    target = jnp.sum(logits * onehot, axis=-1)
    theta = jnp.arccos(jnp.clip(target, -1.0 + 1e-7, 1.0 - 1e-7))
    new_target = jnp.cos(margin1 * theta + margin2) - margin3
    adjusted = logits + onehot * (new_target - target)[:, None]
    adjusted = adjusted * scale
    logp = jax.nn.log_softmax(adjusted.astype(jnp.float32), axis=-1)
    loss = -jnp.take_along_axis(logp, lbl[:, None], axis=-1)
    loss = _reduce_loss(loss, reduction)
    if return_softmax:
        return loss, jnp.exp(logp).astype(logits.dtype)
    return loss


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k,
                      max_seqlen_q=None, max_seqlen_k=None, scale=None,
                      dropout=0.0, causal=False, training=True, name=None):
    """Varlen (packed/unpadded) attention: q/k/v are (total_tokens, H, D)
    with ``cu_seqlens_*`` the (B+1,) cumulative sequence starts
    (reference flash_attn_unpadded, phi flash_attn kernels). TPU-native
    form: static shapes are the deployment contract, so the packed batch
    runs as ONE dense attention with a segment mask (tokens attend only
    within their own sequence, optionally causally) — correct for any
    ragged batch, with the dense kernel's compute cost. Pair with
    bucketed padding when the total length varies across steps."""
    key = rnd.split_key() if (dropout > 0.0 and training) else None
    return _flash_attn_varlen_op(q, k, v, cu_seqlens_q, cu_seqlens_k,
                                 key, scale=scale, dropout=dropout,
                                 causal=causal, training=training)


@register_op("flash_attn_varlen",
             ref="python/paddle/nn/functional/flash_attention.py:"
                 "flash_attn_unpadded (segment-masked dense form)")
def _flash_attn_varlen_op(q, k, v, cu_seqlens_q, cu_seqlens_k, key=None,
                          scale=None, dropout=0.0, causal=False,
                          training=True):
    cq = jnp.asarray(cu_seqlens_q).astype(jnp.int32)
    ck = jnp.asarray(cu_seqlens_k).astype(jnp.int32)
    tq, h, d = q.shape
    tk = k.shape[0]
    seg_q = jnp.searchsorted(cq, jnp.arange(tq), side="right")
    seg_k = jnp.searchsorted(ck, jnp.arange(tk), side="right")
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        pos_q = jnp.arange(tq) - jnp.take(cq, seg_q - 1)
        pos_k = jnp.arange(tk) - jnp.take(ck, seg_k - 1)
        mask = mask & (pos_q[:, None] >= pos_k[None, :])
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (float(scale) if scale is not None else 1.0 / math.sqrt(d))
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if dropout > 0.0 and training and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
