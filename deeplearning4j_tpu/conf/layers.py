"""Layer configurations + their functional forward passes.

Reference: config classes in ``org.deeplearning4j.nn.conf.layers`` (~60
layer confs) and the runtime impls in ``org.deeplearning4j.nn.layers``.
The reference splits conf (builder data) from runtime (stateful ``Layer``
objects issuing per-op JNI calls); here the conf dataclass *is* the layer —
its ``forward`` is a pure jax function that XLA fuses into the whole-program
compile, so there is no separate runtime class hierarchy.

Contract:
- ``output_type(input_type)``: shape inference (reference
  ``Layer#getOutputType`` driven by ``InputType``).
- ``init(key, input_type, dtype) -> params dict`` (e.g. ``{"W":…, "b":…}``).
- ``init_state(input_type, dtype) -> state dict`` (e.g. BN running stats).
- ``forward(params, state, x, train, rng) -> (y, new_state)``.
- ``param_order()``: canonical flat-vector ordering for serializer parity
  (reference: one contiguous params vector, ``MultiLayerNetwork#params``).

Arrays are NHWC for CNN (TPU-native; reference defaults NCHW — see
``conf.inputs`` docstring), ``[batch, time, features]`` for RNN (reference
uses [batch, features, time]; converters transpose at the boundary).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.activations import Activation
from deeplearning4j_tpu.conf.losses import ILossFunction, LossMCXENT
from deeplearning4j_tpu.conf.regularization import Regularization
from deeplearning4j_tpu.conf.updaters import IUpdater
from deeplearning4j_tpu.conf.weights import Distribution, WeightInit


@serde.register_enum
class GradientNormalization(enum.Enum):
    """Reference: ``org.deeplearning4j.nn.conf.GradientNormalization``."""

    NONE = "none"
    RENORMALIZE_L2_PER_LAYER = "l2_per_layer"
    RENORMALIZE_L2_PER_PARAM_TYPE = "l2_per_param"
    CLIP_ELEMENTWISE_ABSOLUTE_VALUE = "clip_elementwise"
    CLIP_L2_PER_LAYER = "clip_l2_per_layer"
    CLIP_L2_PER_PARAM_TYPE = "clip_l2_per_param"


@dataclasses.dataclass
class Layer:
    """Base layer conf (reference: ``org.deeplearning4j.nn.conf.layers.Layer``)."""

    name: Optional[str] = None

    # what ``jax.named_scope`` the decoder's walk puts a vertex of this
    # layer under (``telemetry.device_time.SCOPE_CLASSES``): a class
    # attribute, no field, so no conf's repr or JSON holds it
    scope_class = "ffn"

    # --- shape inference ---------------------------------------------------
    def output_type(self, input_type):
        return input_type

    # --- params/state ------------------------------------------------------
    def init(self, key, input_type, dtype=jnp.float32) -> dict:
        return {}

    def init_state(self, input_type, dtype=jnp.float32) -> dict:
        return {}

    def param_order(self) -> List[str]:
        return []

    def regularized_param_keys(self) -> List[str]:
        return ["W"]

    # --- execution ---------------------------------------------------------
    def forward(self, params, state, x, train: bool = False, rng=None):
        return x, state

    def has_params(self) -> bool:
        return bool(self.param_order())


@dataclasses.dataclass
class BaseLayer(Layer):
    """Layers with weights (reference ``BaseLayer``): common hyperparams.

    ``dropout`` follows the REFERENCE convention: the value is the RETAIN
    probability applied to the layer *input* during training (``dropOut(0.5)``
    keeps half the activations, scaled by 1/p — inverted dropout); 0 disables.
    """

    activation: Activation = Activation.IDENTITY
    weight_init: WeightInit = WeightInit.XAVIER
    bias_init: float = 0.0
    distribution: Optional[Distribution] = None
    updater: Optional[IUpdater] = None
    regularization: Tuple[Regularization, ...] = ()
    regularization_bias: Tuple[Regularization, ...] = ()
    dropout: float = 0.0
    gradient_normalization: GradientNormalization = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0

    def _dropout_input(self, x, train, rng):
        if train and 0.0 < self.dropout < 1.0 and rng is not None:
            keep = self.dropout
            mask = jax.random.bernoulli(rng, keep, x.shape)
            return jnp.where(mask, x / keep, 0.0)
        return x


def _as_ff_size(input_type) -> int:
    if isinstance(input_type, it.FeedForward):
        return input_type.size
    if isinstance(input_type, (it.Convolutional, it.ConvolutionalFlat)):
        return input_type.arity()
    if isinstance(input_type, it.Recurrent):
        return input_type.size
    raise ValueError(f"cannot treat {input_type} as feed-forward input")


@serde.register
@dataclasses.dataclass
class DenseLayer(BaseLayer):
    """Fully connected (reference ``DenseLayer`` /
    ``org.deeplearning4j.nn.layers.feedforward.dense.DenseLayer``).
    W: [nIn, nOut] (reference layout), b: [nOut]."""

    n_out: int = 0
    has_bias: bool = True

    def output_type(self, input_type):
        if isinstance(input_type, it.Recurrent):
            # time-distributed dense over [batch, time, features]
            return it.Recurrent(size=self.n_out, timesteps=input_type.timesteps)
        return it.FeedForward(size=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = _as_ff_size(input_type)
        w = self.weight_init.init(key, (n_in, self.n_out), n_in, self.n_out,
                                  dtype, self.distribution)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state

    def pre_output(self, params, x):
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def fold_scale_shift(self, params, scale, shift):
        """Inference fold hook (``nn.inference_opt``): absorb a following
        per-output-channel affine ``y*scale + shift`` (an eval-mode batch
        norm) into W/b. Valid only when this layer's activation is
        IDENTITY — the caller checks. Returns ``(new_layer, new_params)``;
        a bias appears if the layer had none."""
        dt = params["W"].dtype
        scale = jnp.asarray(scale, jnp.float32)
        shift = jnp.asarray(shift, jnp.float32)
        w = (params["W"].astype(jnp.float32) * scale).astype(dt)
        b = params["b"].astype(jnp.float32) if self.has_bias else 0.0
        b = (b * scale + shift).astype(dt)
        return dataclasses.replace(self, has_bias=True), {"W": w, "b": b}


@serde.register
@dataclasses.dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference ``OutputLayer`` — a ``BaseOutputLayer``).
    The network computes score via ``score()`` on pre-activations so fused
    stable softmax/sigmoid CE forms apply."""

    scope_class = "head"

    loss_fn: ILossFunction = dataclasses.field(default_factory=LossMCXENT)
    activation: Activation = Activation.SOFTMAX

    def score(self, params, x, labels, mask=None):
        z = self.pre_output(params, x)
        return self.loss_fn.score(labels, z, self.activation, mask)


@serde.register
@dataclasses.dataclass
class LossLayer(BaseLayer):
    """Loss without params (reference ``LossLayer``): input size == label
    size; applies activation + loss only."""

    loss_fn: ILossFunction = dataclasses.field(default_factory=LossMCXENT)

    def forward(self, params, state, x, train=False, rng=None):
        return self.activation.apply(x), state

    def score(self, params, x, labels, mask=None):
        return self.loss_fn.score(labels, x, self.activation, mask)

    def regularized_param_keys(self):
        return []


@serde.register
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Reference ``ActivationLayer``: applies an activation, no params."""

    activation: Activation = Activation.RELU

    def forward(self, params, state, x, train=False, rng=None):
        return self.activation.apply(x), state


@serde.register
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Reference ``DropoutLayer``; ``dropout`` = retain probability."""

    dropout: float = 0.5

    def forward(self, params, state, x, train=False, rng=None):
        if train and 0.0 < self.dropout < 1.0 and rng is not None:
            mask = jax.random.bernoulli(rng, self.dropout, x.shape)
            return jnp.where(mask, x / self.dropout, 0.0), state
        return x, state


@serde.register
@dataclasses.dataclass
class EmbeddingLayer(BaseLayer):
    """Reference ``EmbeddingLayer``: int index [batch] or [batch, 1] ->
    [batch, nOut] lookup (mathematically one-hot matmul; lowered by XLA to a
    gather, which is what the reference implements by hand)."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = False

    def output_type(self, input_type):
        return it.FeedForward(size=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        w = self.weight_init.init(key, (self.n_in, self.n_out), self.n_in,
                                  self.n_out, dtype, self.distribution)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params

    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class EmbeddingSequenceLayer(BaseLayer):
    """Reference ``EmbeddingSequenceLayer``: [batch, time] int ->
    [batch, time, nOut]."""

    scope_class = "embed"

    n_in: int = 0
    n_out: int = 0

    def output_type(self, input_type):
        ts = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(size=self.n_out, timesteps=ts)

    def init(self, key, input_type, dtype=jnp.float32):
        w = self.weight_init.init(key, (self.n_in, self.n_out), self.n_in,
                                  self.n_out, dtype, self.distribution)
        return {"W": w}

    def param_order(self):
        return ["W"]

    def forward(self, params, state, x, train=False, rng=None):
        y = params["W"][x.astype(jnp.int32)]
        return self.activation.apply(y), state


# --- preprocessors (auto-inserted by shape inference) ----------------------


@serde.register
@dataclasses.dataclass
class CnnToFeedForwardPreProcessor(Layer):
    """Reference ``CnnToFeedForwardPreProcessor``: NHWC -> flat [batch, hwc]."""

    height: int = 0
    width: int = 0
    channels: int = 0

    def output_type(self, input_type):
        return it.FeedForward(size=self.height * self.width * self.channels)

    def forward(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@serde.register
@dataclasses.dataclass
class FeedForwardToCnnPreProcessor(Layer):
    """Reference ``FeedForwardToCnnPreProcessor``: flat -> NHWC."""

    height: int = 0
    width: int = 0
    channels: int = 0

    def output_type(self, input_type):
        return it.Convolutional(self.height, self.width, self.channels)

    def forward(self, params, state, x, train=False, rng=None):
        return x.reshape(x.shape[0], self.height, self.width, self.channels), state


@serde.register
@dataclasses.dataclass
class RnnToFeedForwardPreProcessor(Layer):
    """Reference ``RnnToFeedForwardPreProcessor``: [b, t, f] kept as-is —
    downstream dense layers are applied time-distributed (the reference
    reshapes to [b*t, f]; XLA treats batched matmul identically)."""

    def forward(self, params, state, x, train=False, rng=None):
        return x, state


@serde.register
@dataclasses.dataclass
class FeedForwardToRnnPreProcessor(Layer):
    def forward(self, params, state, x, train=False, rng=None):
        return x, state
