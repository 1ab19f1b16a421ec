"""Remaining layer confs completing the reference's ~60-layer surface.

Reference: ``org.deeplearning4j.nn.conf.layers.*`` — Convolution3D,
Subsampling3DLayer, Subsampling1DLayer, Upsampling1D/3D, Cropping1D/3D,
ZeroPadding1DLayer/ZeroPadding3DLayer, DepthwiseConvolution2D,
LocallyConnected1D/2D, PReLULayer, ElementWiseMultiplicationLayer,
RepeatVector, MaskLayer, GravesBidirectionalLSTM.

Layouts: 3D volumes are NDHWC (TPU-native; reference NCDHW), 1D sequences
``[batch, time, channels]`` (see layers_rnn.py).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.layers import BaseLayer, Layer
from deeplearning4j_tpu.conf.layers_cnn import ConvolutionMode, PoolingType
from deeplearning4j_tpu.conf.layers_rnn import (
    Bidirectional,
    BidirectionalMode,
    GravesLSTM,
)


def _triple(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v, v)


def _pair(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


def _out3d(size, k, s, mode):
    if mode is ConvolutionMode.SAME:
        return -(-size // s)
    return (size - k) // s + 1


# ---------------------------------------------------------------------------
# 3D convolutions / pooling / resizing
# ---------------------------------------------------------------------------

def _pool(x, pooling_type, window, strides, pad, pnorm=2):
    """Shared reduce_window pooling (semantics of the 2D SubsamplingLayer)."""
    if pooling_type is PoolingType.MAX:
        return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pad)
    if pooling_type is PoolingType.SUM:
        return lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
    if pooling_type is PoolingType.AVG:
        tot = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
        cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window,
                                strides, pad)
        return tot / cnt
    if pooling_type is PoolingType.PNORM:
        p = float(pnorm)
        tot = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window,
                                strides, pad)
        return tot ** (1.0 / p)
    raise ValueError(f"unknown pooling type {pooling_type}")




@serde.register
@dataclasses.dataclass
class Convolution3D(BaseLayer):
    """Reference ``Convolution3D`` — NDHWC x DHWIO (reference NCDHW)."""

    n_out: int = 0
    kernel_size: Tuple[int, int, int] = (2, 2, 2)
    stride: Tuple[int, int, int] = (1, 1, 1)
    convolution_mode: ConvolutionMode = ConvolutionMode.SAME
    has_bias: bool = True

    def output_type(self, input_type):
        assert isinstance(input_type, it.Convolutional3D), input_type
        k, s = _triple(self.kernel_size), _triple(self.stride)
        m = self.convolution_mode
        return it.Convolutional3D(
            depth=_out3d(input_type.depth, k[0], s[0], m),
            height=_out3d(input_type.height, k[1], s[1], m),
            width=_out3d(input_type.width, k[2], s[2], m),
            channels=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        kd, kh, kw = _triple(self.kernel_size)
        in_c = input_type.channels
        fan_in = kd * kh * kw * in_c
        w = self.weight_init.init(key, (kd, kh, kw, in_c, self.n_out),
                                  fan_in, kd * kh * kw * self.n_out, dtype,
                                  self.distribution)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        pad = ("SAME" if self.convolution_mode is ConvolutionMode.SAME
               else "VALID")
        y = lax.conv_general_dilated(
            x, params["W"], window_strides=_triple(self.stride), padding=pad,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class Cnn3DToFeedForwardPreProcessor(Layer):
    """Reference ``Cnn3DToFeedForwardPreProcessor``: flatten NDHWC volumes
    into [batch, d*h*w*c] for dense layers."""

    depth: int = 0
    height: int = 0
    width: int = 0
    channels: int = 0

    def output_type(self, input_type):
        return it.FeedForward(size=input_type.arity())

    def forward(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@serde.register
@dataclasses.dataclass
class Subsampling3DLayer(Layer):
    """Reference ``Subsampling3DLayer``."""

    pooling_type: PoolingType = PoolingType.MAX
    kernel_size: Tuple[int, int, int] = (2, 2, 2)
    stride: Tuple[int, int, int] = (2, 2, 2)
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE

    def output_type(self, input_type):
        k, s = _triple(self.kernel_size), _triple(self.stride)
        m = self.convolution_mode
        return it.Convolutional3D(
            depth=_out3d(input_type.depth, k[0], s[0], m),
            height=_out3d(input_type.height, k[1], s[1], m),
            width=_out3d(input_type.width, k[2], s[2], m),
            channels=input_type.channels)

    pnorm: int = 2

    def forward(self, params, state, x, train=False, rng=None):
        k = (1, *_triple(self.kernel_size), 1)
        s = (1, *_triple(self.stride), 1)
        pad = ("SAME" if self.convolution_mode is ConvolutionMode.SAME
               else "VALID")
        return _pool(x, self.pooling_type, k, s, pad, self.pnorm), state


@serde.register
@dataclasses.dataclass
class Subsampling1DLayer(Layer):
    """Reference ``Subsampling1DLayer`` over [batch, time, channels]."""

    def streaming_safe(self) -> bool:
        # windows/offsets span rnn_time_step call boundaries -> inexact
        return False

    pooling_type: PoolingType = PoolingType.MAX
    kernel_size: int = 2
    stride: int = 2
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE

    def output_type(self, input_type):
        ts = input_type.timesteps
        if ts and ts > 0:
            ts = _out3d(ts, self.kernel_size, self.stride,
                        self.convolution_mode)
        return it.Recurrent(size=input_type.size, timesteps=ts)

    pnorm: int = 2

    def forward(self, params, state, x, train=False, rng=None):
        k = (1, self.kernel_size, 1)
        s = (1, self.stride, 1)
        pad = ("SAME" if self.convolution_mode is ConvolutionMode.SAME
               else "VALID")
        return _pool(x, self.pooling_type, k, s, pad, self.pnorm), state

    def resize_mask(self, mask):
        """[batch, time] mask through the pooling time geometry (reference
        ``feedForwardMaskArray``: masks are max-pooled)."""
        pad = ("SAME" if self.convolution_mode is ConvolutionMode.SAME
               else "VALID")
        return lax.reduce_window(mask, 0.0, lax.max, (1, self.kernel_size),
                                 (1, self.stride), pad)


@serde.register
@dataclasses.dataclass
class Upsampling1D(Layer):
    """Reference ``Upsampling1D``: repeat along time."""

    def streaming_safe(self) -> bool:
        # windows/offsets span rnn_time_step call boundaries -> inexact
        return False

    size: int = 2

    def output_type(self, input_type):
        ts = input_type.timesteps
        return it.Recurrent(size=input_type.size,
                            timesteps=ts * self.size if ts and ts > 0 else ts)

    def forward(self, params, state, x, train=False, rng=None):
        return jnp.repeat(x, self.size, axis=1), state

    def resize_mask(self, mask):
        return jnp.repeat(mask, self.size, axis=1)


@serde.register
@dataclasses.dataclass
class Upsampling3D(Layer):
    """Reference ``Upsampling3D``."""

    size: Tuple[int, int, int] = (2, 2, 2)

    def output_type(self, input_type):
        sd, sh, sw = _triple(self.size)
        return it.Convolutional3D(
            depth=input_type.depth * sd, height=input_type.height * sh,
            width=input_type.width * sw, channels=input_type.channels)

    def forward(self, params, state, x, train=False, rng=None):
        sd, sh, sw = _triple(self.size)
        x = jnp.repeat(x, sd, axis=1)
        x = jnp.repeat(x, sh, axis=2)
        return jnp.repeat(x, sw, axis=3), state


@serde.register
@dataclasses.dataclass
class Cropping1D(Layer):
    """Reference ``Cropping1D``: crop [top, bottom] timesteps."""

    def streaming_safe(self) -> bool:
        # windows/offsets span rnn_time_step call boundaries -> inexact
        return False

    cropping: Tuple[int, int] = (0, 0)

    def output_type(self, input_type):
        a, b = _pair(self.cropping)
        ts = input_type.timesteps
        return it.Recurrent(size=input_type.size,
                            timesteps=ts - a - b if ts and ts > 0 else ts)

    def forward(self, params, state, x, train=False, rng=None):
        a, b = _pair(self.cropping)
        return x[:, a:x.shape[1] - b, :], state

    def resize_mask(self, mask):
        a, b = _pair(self.cropping)
        return mask[:, a:mask.shape[1] - b]


@serde.register
@dataclasses.dataclass
class Cropping3D(Layer):
    """Reference ``Cropping3D``."""

    cropping: Tuple[int, int, int, int, int, int] = (0, 0, 0, 0, 0, 0)

    def output_type(self, input_type):
        c = self.cropping
        return it.Convolutional3D(
            depth=input_type.depth - c[0] - c[1],
            height=input_type.height - c[2] - c[3],
            width=input_type.width - c[4] - c[5],
            channels=input_type.channels)

    def forward(self, params, state, x, train=False, rng=None):
        c = self.cropping
        return x[:, c[0]:x.shape[1] - c[1], c[2]:x.shape[2] - c[3],
                 c[4]:x.shape[3] - c[5], :], state


@serde.register
@dataclasses.dataclass
class ZeroPadding1DLayer(Layer):
    """Reference ``ZeroPadding1DLayer``."""

    def streaming_safe(self) -> bool:
        # windows/offsets span rnn_time_step call boundaries -> inexact
        return False

    padding: Tuple[int, int] = (0, 0)

    def output_type(self, input_type):
        a, b = _pair(self.padding)
        ts = input_type.timesteps
        return it.Recurrent(size=input_type.size,
                            timesteps=ts + a + b if ts and ts > 0 else ts)

    def forward(self, params, state, x, train=False, rng=None):
        a, b = _pair(self.padding)
        return jnp.pad(x, ((0, 0), (a, b), (0, 0))), state

    def resize_mask(self, mask):
        # padded timesteps are synthetic -> invalid (0) in the mask
        a, b = _pair(self.padding)
        return jnp.pad(mask, ((0, 0), (a, b)))


@serde.register
@dataclasses.dataclass
class ZeroPadding3DLayer(Layer):
    """Reference ``ZeroPadding3DLayer``."""

    padding: Tuple[int, int, int, int, int, int] = (0, 0, 0, 0, 0, 0)

    def output_type(self, input_type):
        p = self.padding
        return it.Convolutional3D(
            depth=input_type.depth + p[0] + p[1],
            height=input_type.height + p[2] + p[3],
            width=input_type.width + p[4] + p[5],
            channels=input_type.channels)

    def forward(self, params, state, x, train=False, rng=None):
        p = self.padding
        return jnp.pad(x, ((0, 0), (p[0], p[1]), (p[2], p[3]),
                           (p[4], p[5]), (0, 0))), state


# ---------------------------------------------------------------------------
# 2D extras
# ---------------------------------------------------------------------------

@serde.register
@dataclasses.dataclass
class DepthwiseConvolution2D(BaseLayer):
    """Reference ``DepthwiseConvolution2D``: per-channel conv with a
    ``depth_multiplier`` (nOut = nIn * depth_multiplier)."""

    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    depth_multiplier: int = 1
    convolution_mode: ConvolutionMode = ConvolutionMode.SAME
    has_bias: bool = True

    def output_type(self, input_type):
        k, s = _pair(self.kernel_size), _pair(self.stride)
        m = self.convolution_mode
        return it.Convolutional(
            height=_out3d(input_type.height, k[0], s[0], m),
            width=_out3d(input_type.width, k[1], s[1], m),
            channels=input_type.channels * self.depth_multiplier)

    def init(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c = input_type.channels
        n_out = c * self.depth_multiplier
        fan_in = kh * kw
        w = self.weight_init.init(key, (kh, kw, 1, n_out), fan_in,
                                  kh * kw * self.depth_multiplier, dtype,
                                  self.distribution)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((n_out,), self.bias_init, dtype)
        return p

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        pad = ("SAME" if self.convolution_mode is ConvolutionMode.SAME
               else "VALID")
        y = lax.conv_general_dilated(
            x, params["W"], window_strides=_pair(self.stride), padding=pad,
            feature_group_count=x.shape[-1],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class LocallyConnected2D(BaseLayer):
    """Reference ``LocallyConnected2D``: convolution with UNSHARED weights
    per output position. Weights [outH, outW, kh*kw*inC, nOut]; the patch
    extraction + per-position contraction is one einsum on the MXU."""

    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    has_bias: bool = True

    def _out_hw(self, input_type):
        k, s = _pair(self.kernel_size), _pair(self.stride)
        return ((input_type.height - k[0]) // s[0] + 1,
                (input_type.width - k[1]) // s[1] + 1)

    def output_type(self, input_type):
        oh, ow = self._out_hw(input_type)
        return it.Convolutional(height=oh, width=ow, channels=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        oh, ow = self._out_hw(input_type)
        c = input_type.channels
        fan_in = kh * kw * c
        w = self.weight_init.init(key, (oh, ow, fan_in, self.n_out), fan_in,
                                  self.n_out, dtype, self.distribution)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((oh, ow, self.n_out), self.bias_init, dtype)
        return p

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        kh, kw = _pair(self.kernel_size)
        patches = lax.conv_general_dilated_patches(
            x, (kh, kw), _pair(self.stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # conv_general_dilated_patches emits channel-major patches
        # [C*kh*kw]; weights were initialized against that flat order
        y = jnp.einsum("bhwk,hwko->bhwo", patches, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class LocallyConnected1D(BaseLayer):
    """Reference ``LocallyConnected1D`` over [batch, time, channels]."""

    def streaming_safe(self) -> bool:
        # per-position kernels window the time axis across call boundaries
        return False

    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    has_bias: bool = True

    def _out_t(self, input_type):
        return (input_type.timesteps - self.kernel_size) // self.stride + 1

    def output_type(self, input_type):
        return it.Recurrent(size=self.n_out, timesteps=self._out_t(input_type))

    def init(self, key, input_type, dtype=jnp.float32):
        ot = self._out_t(input_type)
        fan_in = self.kernel_size * input_type.size
        w = self.weight_init.init(key, (ot, fan_in, self.n_out), fan_in,
                                  self.n_out, dtype, self.distribution)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((ot, self.n_out), self.bias_init, dtype)
        return p

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        patches = lax.conv_general_dilated_patches(
            x[:, :, None, :], (self.kernel_size, 1), (self.stride, 1),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))[:, :, 0, :]
        y = jnp.einsum("btk,tko->bto", patches, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class PReLULayer(BaseLayer):
    """Reference ``PReLULayer``: y = max(0,x) + alpha*min(0,x) with
    learnable per-channel alpha."""

    def output_type(self, input_type):
        return input_type

    def _alpha_shape(self, input_type):
        if isinstance(input_type, (it.Convolutional, it.Convolutional3D)):
            return (input_type.channels,)
        if isinstance(input_type, it.ConvolutionalFlat):
            return (input_type.arity(),)
        return (input_type.size,)

    def init(self, key, input_type, dtype=jnp.float32):
        return {"alpha": jnp.full(self._alpha_shape(input_type), 0.25,
                                  dtype)}

    def param_order(self):
        return ["alpha"]

    def regularized_param_keys(self):
        return []

    def forward(self, params, state, x, train=False, rng=None):
        a = params["alpha"]
        return jnp.maximum(x, 0) + a * jnp.minimum(x, 0), state


@serde.register
@dataclasses.dataclass
class ElementWiseMultiplicationLayer(BaseLayer):
    """Reference ``ElementWiseMultiplicationLayer``: out = act(x ⊙ w + b),
    learnable per-feature scale + shift."""

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        n = input_type.size
        return {"W": jnp.ones((n,), dtype),
                "b": jnp.full((n,), self.bias_init, dtype)}

    def param_order(self):
        return ["W", "b"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        return self.activation.apply(x * params["W"] + params["b"]), state


@serde.register
@dataclasses.dataclass
class Permute(Layer):
    """Permute the non-batch axes (Keras ``Permute``; 1-indexed dims over
    the non-batch axes, Keras convention). Recurrent input [b, t, f] with
    dims (2, 1) becomes [b, f, t]; Convolutional input permutes any of
    (h, w, c). The reference's Keras importer lowers this onto a permute
    preprocessor; here it is a plain stateless layer."""

    dims: Tuple[int, ...] = ()

    def _perm(self, rank: int) -> Tuple[int, ...]:
        if sorted(self.dims) != list(range(1, rank)):
            raise ValueError(
                f"Permute dims {self.dims} must be a permutation of "
                f"1..{rank - 1} (1-indexed non-batch axes)")
        return (0,) + tuple(self.dims)

    def output_type(self, input_type):
        if isinstance(input_type, it.Recurrent):
            sizes = [input_type.timesteps, input_type.size]
            self._perm(3)
            out = [sizes[d - 1] for d in self.dims]
            if out[1] is not None and out[1] < 0:
                raise ValueError(
                    f"Permute {self.dims}: the variable-length time axis "
                    "(timesteps=-1) cannot become the feature axis — "
                    "downstream layers need a static feature size")
            return it.Recurrent(size=out[1], timesteps=out[0])
        if isinstance(input_type, it.Convolutional):
            sizes = [input_type.height, input_type.width,
                     input_type.channels]
            self._perm(4)
            out = [sizes[d - 1] for d in self.dims]
            return it.Convolutional(height=out[0], width=out[1],
                                    channels=out[2])
        raise ValueError(
            f"Permute supports Recurrent/Convolutional input, got "
            f"{input_type}")

    def forward(self, params, state, x, train=False, rng=None):
        return jnp.transpose(x, self._perm(x.ndim)), state


@serde.register
@dataclasses.dataclass
class RepeatVector(Layer):
    """Reference ``RepeatVector``: [batch, size] -> [batch, n, size]."""

    repetition_factor: int = 1

    def output_type(self, input_type):
        return it.Recurrent(size=input_type.size,
                            timesteps=self.repetition_factor)

    def forward(self, params, state, x, train=False, rng=None):
        return jnp.repeat(x[:, None, :], self.repetition_factor, axis=1), \
            state


@serde.register
@dataclasses.dataclass
class MaskLayer(Layer):
    """Reference ``util.MaskLayer``: zero out masked timesteps."""

    uses_mask = True

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        if mask is None:
            return x, state
        return x * jnp.asarray(mask, x.dtype)[:, :, None], state


@serde.register
@dataclasses.dataclass
class GravesBidirectionalLSTM(Bidirectional):
    """Reference ``GravesBidirectionalLSTM`` = bidirectional Graves LSTM
    with CONCAT combining (kept as its own conf class for parity; the
    modern reference deprecates it in favor of Bidirectional(GravesLSTM))."""

    n_out: int = 0
    forget_gate_bias_init: float = 1.0

    def __post_init__(self):
        if self.layer is None:
            self.layer = GravesLSTM(
                n_out=self.n_out,
                forget_gate_bias_init=self.forget_gate_bias_init)
        self.mode = BidirectionalMode.CONCAT


@serde.register
@dataclasses.dataclass
class LayerNormalization(BaseLayer):
    """Layer normalization over the feature axis with learnable gain/bias
    (the reference exposes layer norm as ``DenseLayer.hasLayerNorm`` and
    ``sd.nn.layerNorm``; a standalone conf layer makes Transformer blocks
    composable in the graph DSL)."""

    scope_class = "norm"

    eps: float = 1e-5

    def output_type(self, input_type):
        return input_type

    def _n(self, input_type):
        if isinstance(input_type, it.Recurrent):
            return input_type.size
        if isinstance(input_type, (it.Convolutional, it.Convolutional3D)):
            return input_type.channels
        return input_type.size

    def init(self, key, input_type, dtype=jnp.float32):
        n = self._n(input_type)
        return {"gain": jnp.ones((n,), dtype),
                "b": jnp.zeros((n,), dtype)}

    def param_order(self):
        return ["gain", "b"]

    def regularized_param_keys(self):
        return []

    def forward(self, params, state, x, train=False, rng=None):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * lax.rsqrt(var + self.eps)
        return y * params["gain"] + params["b"], state


@serde.register
@dataclasses.dataclass
class PositionEmbeddingLayer(BaseLayer):
    """Learned absolute position embeddings added to a sequence (no direct
    reference layer — the reference reaches Transformers only through
    SameDiff; kept here so TransformerEncoder is order-aware). Params
    ``P: [max_len, size]``; sequences longer than ``max_len`` are
    rejected at trace time."""

    scope_class = "pos"

    max_len: int = 512

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        n = input_type.size
        w = self.weight_init.init(key, (self.max_len, n), self.max_len, n,
                                  dtype, self.distribution)
        return {"P": w * 0.02}

    def param_order(self):
        return ["P"]

    def regularized_param_keys(self):
        return []

    def forward(self, params, state, x, train=False, rng=None):
        t = x.shape[1]
        if t > self.max_len:
            raise ValueError(f"sequence length {t} exceeds "
                             f"max_len={self.max_len}")
        return x + params["P"][None, :t, :], state
