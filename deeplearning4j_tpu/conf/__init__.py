"""Configuration DSL (reference: ``deeplearning4j-nn/.../nn/conf/`` +
``org.nd4j.linalg.learning.config`` + ``org.nd4j.linalg.lossfunctions``).

Configs are plain dataclasses that serialize to JSON with full round-trip
fidelity (see :mod:`deeplearning4j_tpu.serde`); they are *data*, the durable
API-parity surface. Execution lowers them to jitted XLA programs.
"""

from deeplearning4j_tpu.conf.activations import Activation
from deeplearning4j_tpu.conf.inputs import InputType
from deeplearning4j_tpu.conf.weights import WeightInit

# import layer/loss/updater modules for their serde tag registrations, so
# from_json works regardless of which entry point the user imported first
from deeplearning4j_tpu.conf import (  # noqa: E402,F401
    layers, layers_attention, layers_cnn, layers_extra, layers_hybrid,
    layers_objdetect,
    layers_quant, layers_rnn, losses, regularization, schedules, updaters,
)
