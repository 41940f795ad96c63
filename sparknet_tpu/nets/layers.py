"""Caffe layer semantics on XLA — the TPU-native layer library.

The reference executes layers inside native Caffe (SURVEY.md §1-2: Caffe
vendored as native engine; mount empty so semantics follow the published
Caffe layer catalogue, not file:line cites). We re-implement the layer
*contract* — shapes, math, fillers, phase behavior — as pure functions
on ``jax.numpy``, designed for the TPU:

- **NHWC layout** (channels-last) everywhere, the layout XLA tiles best
  onto the MXU; Caffe's NCHW axis arguments are remapped (axis 1 ->
  last). Flatten order therefore differs from Caffe NCHW flatten; this
  matters only for bit-compat weight import, not for training parity.
- Convolution weights are stored **HWIO**, matmul weights **(in, out)**
  — both directly MXU-friendly, no transposes in the hot path.
- All shape arithmetic (ceil-mode pooling, Caffe's average-pool divisor
  that counts padding) is precomputed with numpy at trace time, so the
  compiled graph contains only static-shaped ``lax`` ops.

Each layer type registers three pure functions:
``infer`` (shape inference), ``init`` (param fillers), ``apply``.
BatchNorm additionally carries running stats through the ``state``
pytree (Caffe keeps them in blobs; a functional state pytree is the JAX
equivalent).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..proto.caffe_pb import Filler, LayerParameter
from ..ops.lrn import lrn_nhwc, uses_lrn_kernel
from ..ops.matmul import mxu_dot

Shape = Tuple[int, ...]

# Layer types that declare net inputs rather than computing anything.
DATA_LAYER_TYPES = {
    "Data",
    "Input",
    "MemoryData",
    "ImageData",
    "HDF5Data",
    "DummyData",
    "AnnotatedData",
    "WindowData",
}

LOSS_LAYER_TYPES = {
    "SoftmaxWithLoss",
    "SigmoidCrossEntropyLoss",
    "EuclideanLoss",
    "HingeLoss",
    "ContrastiveLoss",
    "MultinomialLogisticLoss",
    "InfogainLoss",
}


@dataclass
class ApplyCtx:
    train: bool
    rng: Optional[jax.Array]
    compute_dtype: Any = jnp.float32


# ---------------------------------------------------------------------------
# helpers


def _ints(param, name: str, default: int, count: int = 2) -> Tuple[int, ...]:
    """Caffe repeated-or-scalar spatial params (kernel_size/stride/pad)."""
    if param is None:
        return (default,) * count
    vals = [int(v) for v in param.get_all(name)]
    h = param.get(name + "_h")
    w = param.get(name + "_w")
    if h is not None or w is not None:
        return (int(h if h is not None else default), int(w if w is not None else default))
    if not vals:
        return (default,) * count
    if len(vals) == 1:
        return (vals[0],) * count
    return tuple(vals[:count])


def caffe_axis(axis: int, ndim: int) -> int:
    """Map a Caffe NCHW-axis argument onto our NHWC layout."""
    if axis < 0:
        axis += ndim
    if ndim == 4:
        return {0: 0, 1: 3, 2: 1, 3: 2}[axis]
    return axis


def fill(filler: Filler, rng: jax.Array, shape: Shape, fan_in: int, fan_out: int) -> jax.Array:
    t = filler.type
    if t == "constant":
        return jnp.full(shape, filler.value, jnp.float32)
    if t == "gaussian":
        return filler.mean + filler.std * jax.random.normal(rng, shape, jnp.float32)
    if t == "uniform":
        return jax.random.uniform(rng, shape, jnp.float32, filler.min, filler.max)
    if t in ("xavier", "msra"):
        if filler.variance_norm == "FAN_OUT":
            fan = fan_out
        elif filler.variance_norm == "AVERAGE":
            fan = (fan_in + fan_out) / 2.0
        else:
            fan = fan_in
        if t == "xavier":
            scale = math.sqrt(3.0 / fan)
            return jax.random.uniform(rng, shape, jnp.float32, -scale, scale)
        std = math.sqrt(2.0 / fan)
        return std * jax.random.normal(rng, shape, jnp.float32)
    if t == "bilinear":
        # upsampling deconv init; rarely used — approximate with msra
        std = math.sqrt(2.0 / fan_in)
        return std * jax.random.normal(rng, shape, jnp.float32)
    raise NotImplementedError(f"filler type {t!r}")


def nchw_view(shape) -> List[int]:
    """The NCHW view of an NHWC 4D shape; non-4D shapes already carry
    NCHW-order axes (see the Reshape policy below)."""
    if len(shape) == 4:
        n, h, w, c = shape
        return [n, c, h, w]
    return list(shape)


def _spatial_geom(p):
    """convolution_param's kernel/stride/pad/dilation (shared by
    Convolution/Deconvolution via _conv_geom and by Im2col, which has
    no num_output)."""
    return (
        _ints(p, "kernel_size", 0), _ints(p, "stride", 1),
        _ints(p, "pad", 0), _ints(p, "dilation", 1),
    )


def _conv_geom(lp: LayerParameter):
    p = lp.convolution_param
    if p is None:
        raise ValueError(f"layer {lp.name}: missing convolution_param")
    (kh, kw), (sh, sw), (ph, pw), (dh, dw) = _spatial_geom(p)
    group = int(p.get("group", 1))
    cout = int(p.get("num_output"))
    bias = bool(p.get("bias_term", True))
    return (kh, kw), (sh, sw), (ph, pw), (dh, dw), group, cout, bias


def _conv_out(h: int, k: int, s: int, p: int, d: int) -> int:
    keff = d * (k - 1) + 1
    return (h + 2 * p - keff) // s + 1


def _pool_out(h: int, k: int, s: int, p: int) -> int:
    """Caffe ceil-mode pooling output size with the start-inside clamp."""
    out = int(math.ceil((h + 2 * p - k) / s)) + 1
    if p > 0 and (out - 1) * s >= h + p:
        out -= 1
    return out


# ---------------------------------------------------------------------------
# layer implementations. Each is a namespace of pure functions.


class Convolution:
    @staticmethod
    def infer(lp: LayerParameter, in_shapes: List[Shape]) -> List[Shape]:
        (kh, kw), (sh, sw), (ph, pw), (dh, dw), group, cout, _ = _conv_geom(lp)
        n, h, w, c = in_shapes[0]
        return [(n, _conv_out(h, kh, sh, ph, dh), _conv_out(w, kw, sw, pw, dw), cout)]

    @staticmethod
    def init(lp: LayerParameter, rng: jax.Array, in_shapes: List[Shape]) -> Dict[str, jax.Array]:
        (kh, kw), _, _, _, group, cout, bias = _conv_geom(lp)
        cin = in_shapes[0][3]
        assert cin % group == 0 and cout % group == 0, (
            f"{lp.name}: group={group} must divide cin={cin}, cout={cout}"
        )
        p = lp.convolution_param
        wf = Filler.from_message(p.get("weight_filler"))
        k1, k2 = jax.random.split(rng)
        fan_in = kh * kw * (cin // group)
        fan_out = kh * kw * (cout // group)
        params = {"weight": fill(wf, k1, (kh, kw, cin // group, cout), fan_in, fan_out)}
        if bias:
            bf = Filler.from_message(p.get("bias_filler"))
            params["bias"] = fill(bf, k2, (cout,), fan_in, fan_out)
        return params

    @staticmethod
    def apply(lp, params, state, inputs, ctx: ApplyCtx):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw), group, cout, bias = _conv_geom(lp)
        x = inputs[0].astype(ctx.compute_dtype)
        w = params["weight"].astype(ctx.compute_dtype)
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=(sh, sw),
            padding=((ph, ph), (pw, pw)),
            rhs_dilation=(dh, dw),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=group,
            # no preferred_element_type: the MXU already accumulates
            # bf16 products in f32 internally, and an explicit f32
            # output breaks the conv transpose rule under mixed dtypes.
        )
        if bias and "bias" in params:
            y = y + params["bias"].astype(y.dtype)
        return [y], None


class Deconvolution:
    @staticmethod
    def infer(lp, in_shapes):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw), group, cout, _ = _conv_geom(lp)
        n, h, w, c = in_shapes[0]
        oh = sh * (h - 1) + (dh * (kh - 1) + 1) - 2 * ph
        ow = sw * (w - 1) + (dw * (kw - 1) + 1) - 2 * pw
        return [(n, oh, ow, cout)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return Convolution.init(lp, rng, in_shapes)

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        # Transposed conv as an lhs-dilated conv (supports groups, which
        # lax.conv_transpose does not expose): dilate the input by the
        # stride, spatially flip the kernel, pad by keff-1-p.
        (kh, kw), (sh, sw), (ph, pw), (dh, dw), group, cout, bias = _conv_geom(lp)
        x = inputs[0].astype(ctx.compute_dtype)
        w = params["weight"].astype(ctx.compute_dtype)
        w = jnp.flip(w, (0, 1))
        keff_h = dh * (kh - 1) + 1
        keff_w = dw * (kw - 1) + 1
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=(1, 1),
            padding=((keff_h - 1 - ph, keff_h - 1 - ph), (keff_w - 1 - pw, keff_w - 1 - pw)),
            lhs_dilation=(sh, sw),
            rhs_dilation=(dh, dw),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=group,
        )
        if bias and "bias" in params:
            y = y + params["bias"].astype(y.dtype)
        return [y], None


class Pooling:
    @staticmethod
    def _geom(lp, in_shape):
        p = lp.pooling_param
        n, h, w, c = in_shape
        if p is not None and bool(p.get("global_pooling", False)):
            kh, kw = h, w
            sh = sw = 1
            ph = pw = 0
        else:
            kh, kw = _ints(p, "kernel_size", 0)
            sh, sw = _ints(p, "stride", 1)
            ph, pw = _ints(p, "pad", 0)
        mode = str(p.get("pool", "MAX")) if p is not None else "MAX"
        return (kh, kw), (sh, sw), (ph, pw), mode

    @staticmethod
    def infer(lp, in_shapes):
        (kh, kw), (sh, sw), (ph, pw), _ = Pooling._geom(lp, in_shapes[0])
        n, h, w, c = in_shapes[0]
        return [(n, _pool_out(h, kh, sh, ph), _pool_out(w, kw, sw, pw), c)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        n, h, w, c = x.shape
        (kh, kw), (sh, sw), (ph, pw), mode = Pooling._geom(lp, x.shape)
        oh = _pool_out(h, kh, sh, ph)
        ow = _pool_out(w, kw, sw, pw)
        # ceil mode may need extra low-side... no: extra high-side padding
        extra_h = max(0, (oh - 1) * sh + kh - (h + 2 * ph))
        extra_w = max(0, (ow - 1) * sw + kw - (w + 2 * pw))
        pad_h = (ph, ph + extra_h)
        pad_w = (pw, pw + extra_w)
        if mode == "MAX":
            y = lax.reduce_window(
                x,
                -jnp.inf,
                lax.max,
                (1, kh, kw, 1),
                (1, sh, sw, 1),
                ((0, 0), pad_h, pad_w, (0, 0)),
            )
            return [y.astype(x.dtype)], None
        if mode == "AVE":
            s = lax.reduce_window(
                x.astype(jnp.float32),
                0.0,
                lax.add,
                (1, kh, kw, 1),
                (1, sh, sw, 1),
                ((0, 0), pad_h, pad_w, (0, 0)),
            )
            # Caffe divisor: window clipped to the *padded* region — padding
            # counts toward the denominator. Static per-position constant.
            hs = np.arange(oh) * sh - ph
            he = np.minimum(hs + kh, h + ph)
            hs = np.maximum(hs, -ph)
            ws_ = np.arange(ow) * sw - pw
            we = np.minimum(ws_ + kw, w + pw)
            ws_ = np.maximum(ws_, -pw)
            div = (he - hs)[:, None] * (we - ws_)[None, :]
            y = s / jnp.asarray(div[None, :, :, None], jnp.float32)
            return [y.astype(x.dtype)], None
        raise NotImplementedError(f"pool mode {mode}")


class SPP:
    """Spatial pyramid pooling (He et al.): pyramid level i pools into
    a 2^i x 2^i grid (Caffe geometry: kernel = ceil(dim/bins), pad
    centers the remainder), each level flattens in NCHW order and the
    levels concatenate — a fixed-length descriptor from any input
    resolution."""

    @staticmethod
    def _geom(lp):
        p = lp.sub("spp_param")
        if p is None or p.get("pyramid_height") is None:
            raise ValueError(
                f"layer {lp.name!r}: SPP requires "
                f"spp_param {{ pyramid_height: N }}"
            )
        return int(p.get("pyramid_height")), str(p.get("pool", "MAX"))

    @staticmethod
    def _level(dim: int, bins: int):
        k = -(-dim // bins)  # ceil
        remainder = k * bins - dim
        pad = (remainder + 1) // 2
        return k, pad

    @staticmethod
    def infer(lp, in_shapes):
        height, _ = SPP._geom(lp)
        n, h, w, c = in_shapes[0]
        top_bins = 2 ** (height - 1)
        if top_bins > min(h, w):
            # Caffe CHECKs this at setup; without it the padded MAX
            # windows cover only -inf and the loss goes NaN silently
            raise ValueError(
                f"layer {lp.name!r}: pyramid level {height - 1} needs "
                f"{top_bins} bins per side but the input is {h}x{w}"
            )
        total = sum((2 ** i) ** 2 for i in range(height))
        return [(n, c * total)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        height, mode = SPP._geom(lp)
        x = inputs[0]
        n, h, w, c = x.shape
        SPP.infer(lp, [x.shape])  # re-check bins vs dims (direct callers)
        pieces = []
        for i in range(height):
            bins = 2 ** i
            kh, ph = SPP._level(h, bins)
            kw, pw = SPP._level(w, bins)
            if mode == "MAX":
                init_v = -jnp.inf
                op = lax.max
            elif mode == "AVE":
                init_v = 0.0
                op = lax.add
            else:
                raise NotImplementedError(f"spp pool mode {mode}")
            y = lax.reduce_window(
                x.astype(jnp.float32), init_v, op,
                window_dimensions=(1, kh, kw, 1),
                window_strides=(1, kh, kw, 1),
                padding=((0, 0), (ph, kh * bins - h - ph),
                         (pw, kw * bins - w - pw), (0, 0)),
            )
            if mode == "AVE":
                y = y / (kh * kw)  # Caffe divides by the full window
            # flatten in NCHW order so the descriptor layout matches
            pieces.append(
                jnp.transpose(y, (0, 3, 1, 2)).reshape(n, -1)
            )
        out = jnp.concatenate(pieces, axis=1)
        return [out.astype(x.dtype)], None


class InnerProduct:
    @staticmethod
    def _geom(lp):
        p = lp.inner_product_param
        return int(p.get("num_output")), bool(p.get("bias_term", True)), int(p.get("axis", 1))

    @staticmethod
    def _axis(lp, ndim: int) -> int:
        # Caffe semantics: dims before `axis` are preserved (batch-like),
        # dims from `axis` on are flattened into the contraction
        ax = InnerProduct._geom(lp)[2]
        ax = ax if ax >= 0 else ndim + ax
        if not 1 <= ax < ndim:
            raise ValueError(
                f"layer {lp.name!r}: inner_product axis={ax} out of "
                f"range for a {ndim}-d bottom"
            )
        return ax

    @staticmethod
    def infer(lp, in_shapes):
        cout, _, _ = InnerProduct._geom(lp)
        ax = InnerProduct._axis(lp, len(in_shapes[0]))
        return [tuple(in_shapes[0][:ax]) + (cout,)]

    @staticmethod
    def init(lp, rng, in_shapes):
        cout, bias, _ = InnerProduct._geom(lp)
        ax = InnerProduct._axis(lp, len(in_shapes[0]))
        cin = int(np.prod(in_shapes[0][ax:]))
        p = lp.inner_product_param
        wf = Filler.from_message(p.get("weight_filler"))
        bf = Filler.from_message(p.get("bias_filler"))
        k1, k2 = jax.random.split(rng)
        params = {"weight": fill(wf, k1, (cin, cout), cin, cout)}
        if bias:
            params["bias"] = fill(bf, k2, (cout,), cin, cout)
        return params

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        cout, bias, _ = InnerProduct._geom(lp)
        x = inputs[0]
        ax = InnerProduct._axis(lp, x.ndim)
        lead = x.shape[:ax]
        x2 = x.reshape(int(np.prod(lead)), -1).astype(ctx.compute_dtype)
        w = params["weight"].astype(ctx.compute_dtype)
        # mxu_dot: f32 accumulation forward AND compute-dtype
        # backward dots (the default transpose rule would run the
        # backward at f32 MXU rate — see ops/matmul.py)
        y = mxu_dot(x2, w)
        if bias and "bias" in params:
            y = y + params["bias"]
        return [y.astype(ctx.compute_dtype).reshape(lead + (cout,))], None


class ReLU:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        p = lp.sub("relu_param")
        slope = float(p.get("negative_slope", 0.0)) if p else 0.0
        if slope:
            return [jnp.where(x > 0, x, slope * x)], None
        return [jax.nn.relu(x)], None


class _Elementwise:
    fn = staticmethod(lambda x: x)

    @classmethod
    def infer(cls, lp, in_shapes):
        return [in_shapes[0]]

    @classmethod
    def init(cls, lp, rng, in_shapes):
        return {}

    @classmethod
    def apply(cls, lp, params, state, inputs, ctx):
        return [cls.fn(inputs[0])], None


class Sigmoid(_Elementwise):
    fn = staticmethod(jax.nn.sigmoid)


class TanH(_Elementwise):
    fn = staticmethod(jnp.tanh)


class AbsVal(_Elementwise):
    fn = staticmethod(jnp.abs)


class BNLL(_Elementwise):
    # log(1 + exp(x)), computed stably
    fn = staticmethod(jax.nn.softplus)


class ELU:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.sub("elu_param")
        alpha = float(p.get("alpha", 1.0)) if p else 1.0
        return [jax.nn.elu(inputs[0], alpha)], None


class Power:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.sub("power_param")
        power = float(p.get("power", 1.0)) if p else 1.0
        scale = float(p.get("scale", 1.0)) if p else 1.0
        shift = float(p.get("shift", 0.0)) if p else 0.0
        y = scale * inputs[0] + shift
        if power != 1.0:
            y = jnp.power(y, power)
        return [y], None


class Exp:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.sub("exp_param")
        base = float(p.get("base", -1.0)) if p else -1.0
        scale = float(p.get("scale", 1.0)) if p else 1.0
        shift = float(p.get("shift", 0.0)) if p else 0.0
        y = scale * inputs[0] + shift
        return [jnp.exp(y) if base <= 0 else jnp.power(base, y)], None


class Log:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.sub("log_param")
        base = float(p.get("base", -1.0)) if p else -1.0
        scale = float(p.get("scale", 1.0)) if p else 1.0
        shift = float(p.get("shift", 0.0)) if p else 0.0
        y = jnp.log(scale * inputs[0] + shift)
        if base > 0:
            y = y / math.log(base)
        return [y], None


class LRN:
    """Local response normalization (AlexNet/GoogLeNet). ACROSS_CHANNELS
    runs the window over the channel axis, last in NHWC; WITHIN_CHANNEL
    averages over a size x size spatial window."""

    @staticmethod
    def _geom(lp):
        p = lp.lrn_param
        size = int(p.get("local_size", 5)) if p else 5
        alpha = float(p.get("alpha", 1.0)) if p else 1.0
        beta = float(p.get("beta", 0.75)) if p else 0.75
        k = float(p.get("k", 1.0)) if p else 1.0
        region = str(p.get("norm_region", "ACROSS_CHANNELS")) if p else "ACROSS_CHANNELS"
        return size, alpha, beta, k, region

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        size, alpha, beta, k, region = LRN._geom(lp)
        x = inputs[0]
        if uses_lrn_kernel(x.shape, region):
            # one Pallas pass each way (ops/lrn.py), in the orientation
            # whose row-major form is the layout XLA gives the tensor
            return [lrn_nhwc(x, size=size, alpha=alpha, beta=beta, k=k)], None
        # The jax.numpy form: off a TPU, WITHIN_CHANNEL, and shapes that
        # fit neither of the kernels' orientations; the kernels' oracle.
        # Its temps follow the input's dtype.
        sq = jnp.square(x)
        half = size // 2
        if region == "ACROSS_CHANNELS":
            window = (1, 1, 1, size)
            padding = ((0, 0), (0, 0), (0, 0), (half, size - 1 - half))
            scale = alpha / size
        else:  # WITHIN_CHANNEL: avg over the size*size spatial window, k fixed 1
            window = (1, size, size, 1)
            padding = ((0, 0), (half, size - 1 - half), (half, size - 1 - half), (0, 0))
            scale = alpha / (size * size)
            k = 1.0
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1), padding)
        d = k + scale * ssum
        return [(x * jnp.power(d, -beta)).astype(x.dtype)], None


class Dropout:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        p = lp.dropout_param
        ratio = float(p.get("dropout_ratio", 0.5)) if p else 0.5
        if not ctx.train or ratio <= 0.0:
            return [x], None
        keep = 1.0 - ratio
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)], None


class BatchNorm:
    """Caffe BatchNorm: normalization only (pair with Scale for affine).

    Caffe stores unnormalized sums + a scale factor in blobs; we keep
    normalized running mean/var in the state pytree with EMA updates
    (equivalent fixed point; SURVEY.md notes no file:line available).
    """

    @staticmethod
    def _geom(lp):
        p = lp.batch_norm_param
        use_global = p.get("use_global_stats") if p else None
        mavf = float(p.get("moving_average_fraction", 0.999)) if p else 0.999
        eps = float(p.get("eps", 1e-5)) if p else 1e-5
        return use_global, mavf, eps

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def init_state(lp, in_shapes):
        c = in_shapes[0][-1]
        return {
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        use_global, mavf, eps = BatchNorm._geom(lp)
        x = inputs[0]
        xf = x.astype(jnp.float32)
        axes = tuple(range(x.ndim - 1))  # all but channel
        if use_global is None:
            use_global = not ctx.train
        if use_global:
            mean, var = state["mean"], state["var"]
            new_state = state
        else:
            mean = jnp.mean(xf, axes)
            var = jnp.var(xf, axes)
            new_state = {
                "mean": mavf * state["mean"] + (1 - mavf) * mean,
                "var": mavf * state["var"] + (1 - mavf) * var,
            }
        # note: a compute-dtype normalize pass was probed on-chip in
        # round 5 and measured no faster (141 vs 143 ms ResNet-50
        # bs256 step) — unlike LRN's temp chain, XLA already fuses
        # these converts, so the f32 math here is free
        y = (xf - mean) * lax.rsqrt(var + eps)
        return [y.astype(x.dtype)], new_state


class Scale:
    """Per-channel (axis) scale, optional bias: the affine half of BN."""

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        if len(in_shapes) == 2:  # scale comes from the second bottom
            return {}
        p = lp.scale_param
        bias = bool(p.get("bias_term", False)) if p else False
        c = in_shapes[0][-1]
        wf = Filler.from_message(p.get("filler")) if p and p.get("filler") else Filler(type="constant", value=1.0)
        bf = Filler.from_message(p.get("bias_filler")) if p and p.get("bias_filler") else Filler(type="constant", value=0.0)
        k1, k2 = jax.random.split(rng)
        params = {"weight": fill(wf, k1, (c,), c, c)}
        if bias:
            params["bias"] = fill(bf, k2, (c,), c, c)
        return params

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        if len(inputs) == 2:  # two-bottom form: second input is the scale
            y = inputs[0] * inputs[1]
        else:
            y = inputs[0] * params["weight"]
        if "bias" in params:
            y = y + params["bias"]
        return [y], None


class Bias:
    PARAM_ORDER = ("bias",)  # single learned blob

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        if len(in_shapes) == 2:
            return {}
        c = in_shapes[0][-1]
        return {"bias": jnp.zeros((c,), jnp.float32)}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        b = inputs[1] if len(inputs) == 2 else params["bias"]
        return [inputs[0] + b], None


class Eltwise:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.eltwise_param
        op = str(p.get("operation", "SUM")) if p else "SUM"
        if op == "SUM":
            coeffs = [float(c) for c in p.get_all("coeff")] if p else []
            if coeffs:
                if len(coeffs) != len(inputs):
                    raise ValueError(
                        f"layer {lp.name!r}: {len(coeffs)} eltwise coeffs "
                        f"for {len(inputs)} bottoms"
                    )
                y = sum(c * x for c, x in zip(coeffs, inputs))
            else:
                y = sum(inputs[1:], inputs[0])
        elif op == "PROD":
            y = inputs[0]
            for x in inputs[1:]:
                y = y * x
        elif op == "MAX":
            y = inputs[0]
            for x in inputs[1:]:
                y = jnp.maximum(y, x)
        else:
            raise NotImplementedError(f"eltwise op {op}")
        return [y], None


class Concat:
    @staticmethod
    def _axis(lp, ndim):
        p = lp.concat_param
        ax = int(p.get("axis", p.get("concat_dim", 1))) if p else 1
        return caffe_axis(ax, ndim)

    @staticmethod
    def infer(lp, in_shapes):
        ax = Concat._axis(lp, len(in_shapes[0]))
        out = list(in_shapes[0])
        out[ax] = sum(s[ax] for s in in_shapes)
        return [tuple(out)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        return [jnp.concatenate(inputs, Concat._axis(lp, inputs[0].ndim))], None


class Slice:
    @staticmethod
    def _geom(lp, in_shape):
        p = lp.sub("slice_param")
        ax = int(p.get("axis", p.get("slice_dim", 1))) if p else 1
        ax = caffe_axis(ax, len(in_shape))
        points = [int(x) for x in p.get_all("slice_point")] if p else []
        return ax, points

    @staticmethod
    def infer(lp, in_shapes):
        ax, points = Slice._geom(lp, in_shapes[0])
        total = in_shapes[0][ax]
        if not points:
            n = len(lp.top)
            points = [total // n * i for i in range(1, n)]
        bounds = [0] + points + [total]
        outs = []
        for i in range(len(bounds) - 1):
            s = list(in_shapes[0])
            s[ax] = bounds[i + 1] - bounds[i]
            outs.append(tuple(s))
        return outs

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        ax, points = Slice._geom(lp, x.shape)
        if not points:
            n = len(lp.top)
            points = [x.shape[ax] // n * i for i in range(1, n)]
        return list(jnp.split(x, points, axis=ax)), None


class Split:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]] * max(1, len(lp.top))

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        return [inputs[0]] * max(1, len(lp.top)), None


class Flatten:
    @staticmethod
    def infer(lp, in_shapes):
        s = in_shapes[0]
        return [(s[0], int(np.prod(s[1:])))]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        return [x.reshape(x.shape[0], -1)], None


class Reshape:
    """Caffe reshape semantics operate on the NCHW view; we transpose a
    4D NHWC input to NCHW, reshape, and transpose back when the result
    is again 4D (non-4D results keep NCHW-order axes, like Caffe)."""

    @staticmethod
    def _nchw_shape(lp, in_shape_nchw):
        p = lp.sub("reshape_param")
        dims = [int(d) for d in p.get("shape").get_all("dim")]
        out = []
        for i, d in enumerate(dims):
            if d == 0:
                out.append(in_shape_nchw[i])
            else:
                out.append(d)
        if -1 in out:
            known = int(np.prod([d for d in out if d != -1]))
            total = int(np.prod(in_shape_nchw))
            out[out.index(-1)] = total // known
        return tuple(out)

    @staticmethod
    def _shapes(lp, in_shape):
        if len(in_shape) == 4:
            n, h, w, c = in_shape
            nchw_in = (n, c, h, w)
        else:
            nchw_in = tuple(in_shape)
        nchw_out = Reshape._nchw_shape(lp, nchw_in)
        if len(nchw_out) == 4:
            n, c, h, w = nchw_out
            return nchw_out, (n, h, w, c)
        return nchw_out, nchw_out

    @staticmethod
    def infer(lp, in_shapes):
        return [Reshape._shapes(lp, in_shapes[0])[1]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        nchw_out, out = Reshape._shapes(lp, x.shape)
        if x.ndim == 4:
            x = jnp.transpose(x, (0, 3, 1, 2))
        y = x.reshape(nchw_out)
        if len(nchw_out) == 4:
            y = jnp.transpose(y, (0, 2, 3, 1))
        return [y], None


class Softmax:
    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        p = lp.sub("softmax_param")
        ax = caffe_axis(int(p.get("axis", 1)) if p else 1, x.ndim)
        return [jax.nn.softmax(x.astype(jnp.float32), axis=ax).astype(x.dtype)], None


class SoftmaxWithLoss:
    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        logits, labels = inputs[0], inputs[1]
        logits = logits.astype(jnp.float32)
        if logits.ndim > 2:
            ax = caffe_axis(1, logits.ndim)
            logits = jnp.moveaxis(logits, ax, -1).reshape(-1, logits.shape[ax])
            labels = labels.reshape(-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels.astype(jnp.int32)[:, None], axis=-1
        )[:, 0]
        p = lp.sub("loss_param")
        ignore = p.get("ignore_label") if p else None
        if ignore is not None:
            valid = labels != int(ignore)
            loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
                jnp.sum(valid), 1
            )
        else:
            loss = jnp.mean(nll)
        return [loss], None


class SigmoidCrossEntropyLoss:
    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x, t = inputs[0].astype(jnp.float32), inputs[1].astype(jnp.float32)
        # stable: max(x,0) - x*t + log(1+exp(-|x|)); Caffe normalizes by N
        loss = jnp.sum(
            jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
        ) / x.shape[0]
        return [loss], None


class EuclideanLoss:
    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        a, b = inputs[0].astype(jnp.float32), inputs[1].astype(jnp.float32)
        return [jnp.sum(jnp.square(a - b)) / (2.0 * a.shape[0])], None


class Accuracy:
    @staticmethod
    def infer(lp, in_shapes):
        return [()] * max(1, len(lp.top))

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        logits, labels = inputs[0], inputs[1].astype(jnp.int32)
        p = lp.sub("accuracy_param")
        top_k = int(p.get("top_k", 1)) if p else 1
        ignore = p.get("ignore_label") if p else None
        if top_k == 1:
            correct = jnp.argmax(logits, -1) == labels
        else:
            _, idx = lax.top_k(logits, top_k)
            correct = jnp.any(idx == labels[:, None], axis=-1)
        if ignore is not None:
            valid = labels != int(ignore)
            acc = jnp.sum(
                jnp.where(valid, correct, False).astype(jnp.float32)
            ) / jnp.maximum(jnp.sum(valid), 1)
        else:
            acc = jnp.mean(correct.astype(jnp.float32))
        outs = [acc] * max(1, len(lp.top))
        return outs, None


class PReLU:
    """Learnable leaky slope, per channel (Caffe NCHW channel -> our
    trailing axis) or shared (``channel_shared``); filler default 0.25."""

    PARAM_ORDER = ("slope",)  # prototxt param{} spec 0 is the slope blob

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        p = lp.sub("prelu_param")
        shared = bool(p.get("channel_shared", False)) if p else False
        c = 1 if shared else int(in_shapes[0][-1])
        fm = p.get("filler") if p else None
        filler = (
            Filler.from_message(fm)
            if fm is not None
            else Filler(type="constant", value=0.25)
        )
        return {"slope": fill(filler, rng, (c,), c, c)}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        a = params["slope"].astype(x.dtype)
        return [jnp.where(x > 0, x, a * x)], None


class Threshold(_Elementwise):
    @classmethod
    def apply(cls, lp, params, state, inputs, ctx):
        p = lp.sub("threshold_param")
        t = float(p.get("threshold", 0.0)) if p else 0.0
        x = inputs[0]
        return [(x > t).astype(x.dtype)], None


class Tile:
    @staticmethod
    def _geom(lp, ndim):
        p = lp.sub("tile_param")
        axis = caffe_axis(int(p.get("axis", 1)) if p else 1, ndim)
        tiles = int(p.get("tiles")) if p else 1
        return axis, tiles

    @staticmethod
    def infer(lp, in_shapes):
        s = list(in_shapes[0])
        axis, tiles = Tile._geom(lp, len(s))
        s[axis] *= tiles
        return [tuple(s)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        axis, tiles = Tile._geom(lp, x.ndim)
        reps = [1] * x.ndim
        reps[axis] = tiles
        return [jnp.tile(x, reps)], None


class MVN:
    """Mean-variance normalization per sample: over H,W per channel, or
    over C,H,W when ``across_channels``."""

    @staticmethod
    def infer(lp, in_shapes):
        return [in_shapes[0]]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        p = lp.sub("mvn_param")
        across = bool(p.get("across_channels", False)) if p else False
        norm_var = bool(p.get("normalize_variance", True)) if p else True
        eps = float(p.get("eps", 1e-9)) if p else 1e-9
        x = inputs[0].astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if across else tuple(range(1, x.ndim - 1))
        mu = jnp.mean(x, axes, keepdims=True)
        y = x - mu
        if norm_var:
            # Caffe divides by sqrt(E[(x-mu)^2]) + eps (eps OUTSIDE)
            y = y / (jnp.sqrt(jnp.mean(jnp.square(y), axes, keepdims=True)) + eps)
        return [y.astype(inputs[0].dtype)], None


class ArgMax:
    """Per-sample top-k indices (float blob, like Caffe); ``axis`` keeps
    dims and disallows out_max_val pairs, axis-less flattens the sample."""

    @staticmethod
    def _geom(lp):
        p = lp.sub("argmax_param")
        top_k = int(p.get("top_k", 1)) if p else 1
        out_max = bool(p.get("out_max_val", False)) if p else False
        axis = p.get("axis") if p else None
        return top_k, out_max, (None if axis is None else int(axis))

    @staticmethod
    def infer(lp, in_shapes):
        top_k, out_max, axis = ArgMax._geom(lp)
        s = in_shapes[0]
        if axis is not None:
            out = list(s)
            out[caffe_axis(axis, len(s))] = top_k
            return [tuple(out)]
        return [(s[0], 2 if out_max else 1, top_k)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        top_k, out_max, axis = ArgMax._geom(lp)
        x = inputs[0].astype(jnp.float32)
        if axis is not None:
            ax = caffe_axis(axis, x.ndim)
            xm = jnp.moveaxis(x, ax, -1)
            vals, idx = lax.top_k(xm, top_k)
            # with an axis, Caffe emits the top-k VALUES when
            # out_max_val is set (indices otherwise) — never pairs
            y = vals if out_max else idx.astype(jnp.float32)
            return [jnp.moveaxis(y, -1, ax)], None
        flat = x.reshape(x.shape[0], -1)
        vals, idx = lax.top_k(flat, top_k)
        idx = idx.astype(jnp.float32)[:, None, :]
        if out_max:
            return [jnp.concatenate([idx, vals[:, None, :]], axis=1)], None
        return [idx], None


class Embed:
    """Lookup table: integer indices -> (…, num_output) rows."""

    @staticmethod
    def _geom(lp):
        p = lp.sub("embed_param")
        return (
            int(p.get("num_output")),
            int(p.get("input_dim")),
            # caffe.proto EmbedParameter: bias_term [default = true]
            bool(p.get("bias_term", True)),
        )

    @staticmethod
    def infer(lp, in_shapes):
        cout, _, _ = Embed._geom(lp)
        return [tuple(in_shapes[0]) + (cout,)]

    @staticmethod
    def init(lp, rng, in_shapes):
        cout, vocab, bias = Embed._geom(lp)
        p = lp.sub("embed_param")
        wf = Filler.from_message(p.get("weight_filler"))
        k1, k2 = jax.random.split(rng)
        params = {"weight": fill(wf, k1, (vocab, cout), vocab, cout)}
        if bias:
            bf = Filler.from_message(p.get("bias_filler"))
            params["bias"] = fill(bf, k2, (cout,), vocab, cout)
        return params

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        idx = inputs[0].astype(jnp.int32)
        y = params["weight"][idx]
        if "bias" in params:
            y = y + params["bias"]
        return [y.astype(ctx.compute_dtype)], None


class Reduction:
    """Reduce every axis from ``axis`` to the end of the NCHW view
    (Caffe flattens the tail); non-4D outputs keep NCHW-order axes,
    matching the Reshape policy above."""

    @staticmethod
    def _geom(lp):
        p = lp.sub("reduction_param")
        op = str(p.get("operation", "SUM")) if p else "SUM"
        axis = int(p.get("axis", 0)) if p else 0
        coeff = float(p.get("coeff", 1.0)) if p else 1.0
        return op, axis, coeff

    @staticmethod
    def infer(lp, in_shapes):
        _, axis, _ = Reduction._geom(lp)
        nchw = nchw_view(in_shapes[0])
        axis = axis % len(nchw) if axis else 0
        return [tuple(nchw[:axis])]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        op, axis, coeff = Reduction._geom(lp)
        x = inputs[0].astype(jnp.float32)
        if x.ndim == 4:
            x = jnp.transpose(x, (0, 3, 1, 2))
        axis = axis % x.ndim if axis else 0
        axes = tuple(range(axis, x.ndim))
        if op == "SUM":
            y = jnp.sum(x, axes)
        elif op == "ASUM":
            y = jnp.sum(jnp.abs(x), axes)
        elif op == "SUMSQ":
            y = jnp.sum(jnp.square(x), axes)
        elif op == "MEAN":
            y = jnp.mean(x, axes)
        else:
            raise NotImplementedError(f"reduction op {op}")
        return [(coeff * y).astype(inputs[0].dtype)], None


class Crop:
    """Crop bottom[0] to bottom[1]'s size from ``axis`` (NCHW view)
    onward at the given offsets, like the FCN skip-connection crops."""

    @staticmethod
    def _geom(lp, ndim):
        p = lp.sub("crop_param")
        axis = int(p.get("axis", 2)) if p else 2
        offsets = [int(o) for o in p.get_all("offset")] if p else []
        return axis % ndim, offsets

    @staticmethod
    def infer(lp, in_shapes):
        a = nchw_view(in_shapes[0])
        b = nchw_view(in_shapes[1])
        if len(a) != len(b):
            # Caffe's CropLayer CHECKs num_axes equality
            raise ValueError(
                f"layer {lp.name!r}: crop bottoms must have equal rank, "
                f"got {len(a)} vs {len(b)}"
            )
        axis, _ = Crop._geom(lp, len(a))
        out = a[:axis] + b[axis:]
        if len(out) == 4:
            n, c, h, w = out
            return [(n, h, w, c)]
        return [tuple(out)]

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0]
        ref_nchw = nchw_view(inputs[1].shape)
        if len(ref_nchw) != x.ndim:
            raise ValueError(
                f"layer {lp.name!r}: crop bottoms must have equal rank, "
                f"got {x.ndim} vs {len(ref_nchw)}"
            )
        x_nchw4 = x.ndim == 4
        if x_nchw4:
            x = jnp.transpose(x, (0, 3, 1, 2))
        axis, offsets = Crop._geom(lp, x.ndim)
        n_cropped = x.ndim - axis
        if len(offsets) not in (0, 1, n_cropped):
            # Caffe's CropLayer CHECKs exactly 1 or n offsets
            raise ValueError(
                f"layer {lp.name!r}: crop needs 1 or {n_cropped} offsets, "
                f"got {len(offsets)}"
            )
        starts = [0] * x.ndim
        sizes = list(x.shape)
        for i in range(axis, x.ndim):
            j = i - axis
            off = offsets[j] if len(offsets) == n_cropped else (
                offsets[0] if offsets else 0
            )
            starts[i] = off
            sizes[i] = ref_nchw[i]
        y = lax.slice(
            x, starts, [s + z for s, z in zip(starts, sizes)]
        )
        if x_nchw4:
            y = jnp.transpose(y, (0, 2, 3, 1))
        return [y], None

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}


class Silence:
    """Consumes its bottoms, produces nothing (suppresses unused-blob
    plumbing in prototxts)."""

    @staticmethod
    def infer(lp, in_shapes):
        return []

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        return [], None


class LSTM:
    """Caffe's LSTMLayer: time-major input x (T, N, ...) plus sequence
    -continuation markers cont (T, N) (0 at sequence starts resets the
    state, so packed batches of variable-length sequences train
    correctly). One ``lax.scan`` over T — the TPU-native unrolling;
    gate order i, f, o, g matches Caffe's blob layout, and the blobs
    are [W_xc (in,4H), b (4H), W_hc (H,4H)] via PARAM_ORDER."""

    PARAM_ORDER = ("weight", "bias", "hidden_weight")

    @staticmethod
    def _geom(lp):
        p = lp.sub("recurrent_param")
        h = int(p.get("num_output"))
        if p.get("expose_hidden"):
            raise NotImplementedError(
                f"layer {lp.name!r}: recurrent expose_hidden unsupported"
            )
        return h, p

    @staticmethod
    def infer(lp, in_shapes):
        h, _ = LSTM._geom(lp)
        t, n = in_shapes[0][:2]
        return [(t, n, h)]

    @staticmethod
    def init(lp, rng, in_shapes):
        h, p = LSTM._geom(lp)
        cin = int(np.prod(in_shapes[0][2:]))
        wf = Filler.from_message(p.get("weight_filler"))
        bf = Filler.from_message(p.get("bias_filler"))
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "weight": fill(wf, k1, (cin, 4 * h), cin, 4 * h),
            "bias": fill(bf, k2, (4 * h,), cin, 4 * h),
            "hidden_weight": fill(wf, k3, (h, 4 * h), h, 4 * h),
        }

    @staticmethod
    def _cont(inputs, t, n, dtype):
        if len(inputs) > 1:
            return inputs[1].astype(dtype).reshape(t, n)
        # no cont bottom: one unbroken sequence per batch row (first
        # step still starts from the zero state)
        return jnp.ones((t, n), dtype).at[0].set(0.0)

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        hs, _ = LSTM._geom(lp)
        x = inputs[0]
        t, n = x.shape[:2]
        cdt = ctx.compute_dtype
        x = x.reshape(t, n, -1).astype(cdt)
        cont = LSTM._cont(inputs, t, n, jnp.float32)
        wx = params["weight"].astype(cdt)
        wh = params["hidden_weight"].astype(cdt)
        b = params["bias"]
        # input contribution for every step in one batched matmul
        gx = mxu_dot(x, wx) + b  # (T, N, 4H) f32

        def step(carry, inp):
            h_prev, c_prev = carry
            gxt, ct = inp
            h_in = (h_prev * ct[:, None]).astype(cdt)
            gates = gxt + mxu_dot(h_in, wh)
            i, f, o, g = jnp.split(gates, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            o = jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c = ct[:, None] * (f * c_prev) + i * g
            h = o * jnp.tanh(c)
            return (h, c), h

        zeros = jnp.zeros((n, hs), jnp.float32)
        _, hseq = lax.scan(step, (zeros, zeros), (gx, cont))
        return [hseq.astype(cdt)], None


class RNN(LSTM):
    """Caffe's RNNLayer: h_t = tanh(W_xh x_t + b_h + W_hh h_{t-1}),
    o_t = tanh(W_ho h_t + b_o); blobs [W_xh, b_h, W_hh, W_ho, b_o]."""

    PARAM_ORDER = (
        "weight", "bias", "hidden_weight", "out_weight", "out_bias"
    )

    @staticmethod
    def init(lp, rng, in_shapes):
        h, p = LSTM._geom(lp)
        cin = int(np.prod(in_shapes[0][2:]))
        wf = Filler.from_message(p.get("weight_filler"))
        bf = Filler.from_message(p.get("bias_filler"))
        ks = jax.random.split(rng, 5)
        return {
            "weight": fill(wf, ks[0], (cin, h), cin, h),
            "bias": fill(bf, ks[1], (h,), cin, h),
            "hidden_weight": fill(wf, ks[2], (h, h), h, h),
            "out_weight": fill(wf, ks[3], (h, h), h, h),
            "out_bias": fill(bf, ks[4], (h,), h, h),
        }

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        hs, _ = LSTM._geom(lp)
        x = inputs[0]
        t, n = x.shape[:2]
        cdt = ctx.compute_dtype
        x = x.reshape(t, n, -1).astype(cdt)
        cont = LSTM._cont(inputs, t, n, jnp.float32)
        wx = params["weight"].astype(cdt)
        wh = params["hidden_weight"].astype(cdt)
        wo = params["out_weight"].astype(cdt)
        gx = mxu_dot(x, wx) + params["bias"]

        def step(h_prev, inp):
            gxt, ct = inp
            h_in = (h_prev * ct[:, None]).astype(cdt)
            h = jnp.tanh(gxt + mxu_dot(h_in, wh))
            o = jnp.tanh(mxu_dot(h.astype(cdt), wo) + params["out_bias"])
            return h, o

        zeros = jnp.zeros((n, hs), jnp.float32)
        _, oseq = lax.scan(step, zeros, (gx, cont))
        return [oseq.astype(cdt)], None


class MultinomialLogisticLoss:
    """NLL over already-softmaxed probabilities (Caffe pairs it with an
    explicit Softmax layer; SoftmaxWithLoss is the fused form)."""

    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        probs = inputs[0].astype(jnp.float32)
        labels = inputs[1].astype(jnp.int32).reshape(-1)
        p = jnp.take_along_axis(
            probs.reshape(labels.shape[0], -1), labels[:, None], axis=-1
        )[:, 0]
        # Caffe clamps at kLOG_THRESHOLD=1e-20
        return [-jnp.mean(jnp.log(jnp.maximum(p, 1e-20)))], None


class InfogainLoss:
    """NLL weighted by an infogain matrix H (bottom[2] or
    ``infogain_loss_param.source`` .binaryproto); H=I reduces to
    MultinomialLogisticLoss."""

    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def _matrix(lp, inputs, n_classes):
        if len(inputs) == 3:
            return inputs[2].astype(jnp.float32).reshape(n_classes, n_classes)
        p = lp.sub("infogain_loss_param")
        src = str(p.get("source")) if p and p.get("source") else None
        if src is None:
            raise ValueError(
                f"layer {lp.name!r}: InfogainLoss needs a third bottom or "
                f"infogain_loss_param.source"
            )
        from ..proto.caffemodel import load_binaryproto_mean

        h = load_binaryproto_mean(src)
        return jnp.asarray(h, jnp.float32).reshape(n_classes, n_classes)

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        probs = inputs[0].astype(jnp.float32)
        labels = inputs[1].astype(jnp.int32).reshape(-1)
        probs = probs.reshape(labels.shape[0], -1)
        h = InfogainLoss._matrix(lp, inputs, probs.shape[-1])
        logp = jnp.log(jnp.maximum(probs, 1e-20))
        # loss_i = -sum_j H[label_i, j] * log p_ij
        rows = h[labels]  # (N, C)
        return [-jnp.mean(jnp.sum(rows * logp, axis=-1))], None


class HingeLoss:
    """One-vs-all hinge over (N, C) scores: t=+1 at the label, -1
    elsewhere; L1 or squared (L2) norm, averaged over N."""

    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        x = inputs[0].astype(jnp.float32)
        labels = inputs[1].astype(jnp.int32).reshape(-1)
        t = 2.0 * jax.nn.one_hot(labels, x.shape[-1]) - 1.0
        m = jnp.maximum(0.0, 1.0 - t * x)
        p = lp.sub("hinge_loss_param")
        norm = str(p.get("norm", "L1")) if p else "L1"
        if norm == "L2":
            m = jnp.square(m)
        return [jnp.sum(m) / x.shape[0]], None


class ContrastiveLoss:
    """Siamese pairs: y=1 similar pulls d^2, y=0 dissimilar pushes to
    ``margin``; legacy_version uses Caffe's original margin-d^2 form."""

    @staticmethod
    def infer(lp, in_shapes):
        return [()]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        a = inputs[0].astype(jnp.float32).reshape(inputs[0].shape[0], -1)
        b = inputs[1].astype(jnp.float32).reshape(inputs[1].shape[0], -1)
        y = inputs[2].astype(jnp.float32).reshape(-1)
        p = lp.sub("contrastive_loss_param")
        margin = float(p.get("margin", 1.0)) if p else 1.0
        legacy = bool(p.get("legacy_version", False)) if p else False
        d2 = jnp.sum(jnp.square(a - b), -1)
        if legacy:
            dissim = jnp.maximum(margin - d2, 0.0)
        else:
            dissim = jnp.square(jnp.maximum(margin - jnp.sqrt(d2 + 1e-12), 0.0))
        loss = jnp.sum(y * d2 + (1.0 - y) * dissim) / (2.0 * a.shape[0])
        return [loss], None


class BatchReindex:
    """Caffe BatchReindexLayer: top = bottom[0][bottom[1]] along the
    batch axis (gather; autodiff gives the scatter-add backward)."""

    @staticmethod
    def infer(lp, in_shapes):
        if len(in_shapes[1]) != 1:
            raise ValueError(
                f"layer {lp.name!r}: BatchReindex wants a rank-1 index "
                f"blob (Caffe's contract), got shape {in_shapes[1]}"
            )
        return [(in_shapes[1][0],) + tuple(in_shapes[0][1:])]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        idx = inputs[1].reshape(-1).astype(jnp.int32)
        # mode="clip": an out-of-range index (Caffe CHECK-fails at
        # runtime; untraceable under jit) clamps to the batch edge
        # instead of jnp.take's default fill-with-NaN, which would
        # silently poison training
        return [jnp.take(inputs[0], idx, axis=0, mode="clip")], None


class Parameter:
    """Caffe ParameterLayer: exposes a learnable blob as a top.
    ``parameter_param { shape { dim ... } }``; Caffe initialises the
    blob to zeros (values normally arrive via .caffemodel loading),
    and so do we."""

    @staticmethod
    def _shape(lp) -> Shape:
        p = lp.sub("parameter_param")
        shp = p.get("shape") if p else None
        if shp is None:
            raise ValueError(
                f"layer {lp.name!r}: Parameter needs parameter_param.shape"
            )
        return tuple(int(d) for d in shp.get_all("dim"))

    @staticmethod
    def infer(lp, in_shapes):
        return [Parameter._shape(lp)]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {"weight": jnp.zeros(Parameter._shape(lp), jnp.float32)}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        return [params["weight"].astype(ctx.compute_dtype)], None


class Im2col:
    """Caffe Im2colLayer: explicit patch extraction. NCHW Caffe emits
    (N, C*kh*kw, Ho, Wo) with c-major column order; the NHWC twin emits
    (N, Ho, Wo, C*kh*kw) with the SAME c-major feature order, so
    column contents match Caffe's exactly (only the axis placement
    follows this library's NHWC policy)."""

    @staticmethod
    def _geom(lp):
        p = lp.convolution_param
        if p is None:
            raise ValueError(f"layer {lp.name}: missing convolution_param")
        return _spatial_geom(p)

    @staticmethod
    def infer(lp, in_shapes):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = Im2col._geom(lp)
        n, h, w, c = in_shapes[0]
        return [(
            n, _conv_out(h, kh, sh, ph, dh), _conv_out(w, kw, sw, pw, dw),
            c * kh * kw,
        )]

    @staticmethod
    def init(lp, rng, in_shapes):
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        (kh, kw), (sh, sw), (ph, pw), (dh, dw) = Im2col._geom(lp)
        x = inputs[0]
        # conv_general_dilated_patches orders the output features
        # c-major (source channel, then filter h, then filter w) — the
        # exact Caffe column order
        out = jax.lax.conv_general_dilated_patches(
            x.astype(ctx.compute_dtype),
            filter_shape=(kh, kw),
            window_strides=(sh, sw),
            padding=((ph, ph), (pw, pw)),
            rhs_dilation=(dh, dw),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return [out], None


# ---------------------------------------------------------------------------
# Caffe `Python` layer escape hatch.
#
# Caffe's Python layer loads a user class (python_param.module/.layer)
# and calls its setup/forward/backward on host tensors. A host callback
# per layer would serialize the TPU pipeline, so the TPU-native contract
# is a *traceable* callable registry instead: the user registers a pure
# JAX function (or a full infer/init/apply impl) under "module.layer",
# and it is traced and fused into the jitted step like any built-in
# layer — autodiff replaces the hand-written backward.

PYTHON_LAYER_REGISTRY: Dict[str, Any] = {}


def register_python_layer(name: str, impl: Any = None):
    """Register a ``Python``-layer implementation (also a decorator).

    ``impl`` is either a bare traceable callable
    ``fn(inputs: list[Array], param_str: str) -> list[Array]`` —
    stateless, shapes inferred with ``jax.eval_shape`` over *float32*
    avals (a callable that demands integer inputs, e.g. index-taking
    on a label bottom, will fail at net-build time; give it the full
    protocol with an explicit ``infer`` instead) — or an object with
    the full built-in layer protocol (``infer(lp, in_shapes)``,
    ``init(lp, rng, in_shapes)``, ``apply(lp, params, state, inputs,
    ctx)``) for layers that need params, state, integer-typed inputs,
    or phase behavior.
    ``name`` should match the prototxt's ``python_param`` as
    ``"<module>.<layer>"``; a bare ``"<layer>"`` key acts as a
    module-agnostic fallback.
    """
    if impl is None:
        return lambda f: register_python_layer(name, f)
    PYTHON_LAYER_REGISTRY[name] = impl
    return impl


class PythonLayer:
    """Dispatch for Caffe ``Python`` layers via the callable registry."""

    @staticmethod
    def _impl(lp) -> Tuple[Any, str]:
        p = lp.sub("python_param")
        module = str(p.get("module", "")) if p else ""
        layer = str(p.get("layer", "")) if p else ""
        param_str = str(p.get("param_str", "")) if p else ""
        for key in ((f"{module}.{layer}",) if module else ()) + (layer,):
            if key in PYTHON_LAYER_REGISTRY:
                return PYTHON_LAYER_REGISTRY[key], param_str
        raise KeyError(
            f"Python layer {lp.name!r} wants {module + '.' if module else ''}"
            f"{layer} but nothing is registered under that name — call "
            f"sparknet_tpu.register_python_layer({(module + '.' + layer) if module else layer!r}, fn) "
            f"with a traceable callable before building the net"
        )

    @staticmethod
    def infer(lp, in_shapes):
        impl, param_str = PythonLayer._impl(lp)
        if hasattr(impl, "infer"):
            return impl.infer(lp, in_shapes)
        outs = jax.eval_shape(
            lambda *xs: impl(list(xs), param_str),
            *[jax.ShapeDtypeStruct(s, jnp.float32) for s in in_shapes],
        )
        return [tuple(o.shape) for o in outs]

    @staticmethod
    def init(lp, rng, in_shapes):
        impl, _ = PythonLayer._impl(lp)
        if hasattr(impl, "init"):
            return impl.init(lp, rng, in_shapes)
        return {}

    @staticmethod
    def apply(lp, params, state, inputs, ctx):
        impl, param_str = PythonLayer._impl(lp)
        if hasattr(impl, "apply"):
            return impl.apply(lp, params, state, inputs, ctx)
        return list(impl(list(inputs), param_str)), None


LAYER_IMPLS = {
    "Convolution": Convolution,
    "Deconvolution": Deconvolution,
    "Pooling": Pooling,
    "InnerProduct": InnerProduct,
    "ReLU": ReLU,
    "Sigmoid": Sigmoid,
    "TanH": TanH,
    "AbsVal": AbsVal,
    "BNLL": BNLL,
    "ELU": ELU,
    "Power": Power,
    "Exp": Exp,
    "Log": Log,
    "LRN": LRN,
    "Dropout": Dropout,
    "BatchNorm": BatchNorm,
    "Scale": Scale,
    "Bias": Bias,
    "Eltwise": Eltwise,
    "Concat": Concat,
    "Slice": Slice,
    "Split": Split,
    "Flatten": Flatten,
    "Reshape": Reshape,
    "Softmax": Softmax,
    "SoftmaxWithLoss": SoftmaxWithLoss,
    "SigmoidCrossEntropyLoss": SigmoidCrossEntropyLoss,
    "EuclideanLoss": EuclideanLoss,
    "Accuracy": Accuracy,
    "PReLU": PReLU,
    "Threshold": Threshold,
    "Tile": Tile,
    "MVN": MVN,
    "ArgMax": ArgMax,
    "Embed": Embed,
    "Reduction": Reduction,
    "Crop": Crop,
    "Silence": Silence,
    "HingeLoss": HingeLoss,
    "ContrastiveLoss": ContrastiveLoss,
    "MultinomialLogisticLoss": MultinomialLogisticLoss,
    "InfogainLoss": InfogainLoss,
    "LSTM": LSTM,
    "RNN": RNN,
    "SPP": SPP,
    "Python": PythonLayer,
    "BatchReindex": BatchReindex,
    "Parameter": Parameter,
    "Im2col": Im2col,
}
