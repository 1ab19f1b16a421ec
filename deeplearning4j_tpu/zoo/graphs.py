"""Model zoo — ComputationGraph models.

Reference: ``org.deeplearning4j.zoo.model.{VGG16,VGG19,ResNet50,SqueezeNet,
Darknet19,UNet}`` — each ``init()`` builds a ComputationGraphConfiguration;
topologies follow the reference's graph builders (conv/bn orderings, residual
wiring via ``ElementWiseVertex(Add)``, fire-module concat via
``MergeVertex``). Layouts are NHWC (TPU-native) instead of the reference's
NCHW; shapes/channel counts match.
"""

from __future__ import annotations

from typing import Tuple

from deeplearning4j_tpu.conf import Activation, InputType, WeightInit
from deeplearning4j_tpu.conf.graph import (
    ComputationGraphConfiguration,
    ElementWiseOp,
    ElementWiseVertex,
    LayerVertex,
    MergeVertex,
)
from deeplearning4j_tpu.conf.layers import (ActivationLayer, DenseLayer,
    LossLayer, OutputLayer)
from deeplearning4j_tpu.conf.layers_cnn import (
    BatchNormalization,
    CnnLossLayer,
    ConvolutionLayer,
    ConvolutionMode,
    GlobalPoolingLayer,
    PoolingType,
    SubsamplingLayer,
    Upsampling2D,
)
from deeplearning4j_tpu.conf.losses import LossBinaryXENT, LossMCXENT
from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
from deeplearning4j_tpu.conf.updaters import Adam, IUpdater, Nesterovs
from deeplearning4j_tpu.zoo.models import ZooModel


def _conv(n_out, k, s=(1, 1), act=Activation.RELU, mode=ConvolutionMode.SAME,
          bias=True):
    return ConvolutionLayer(n_out=n_out, kernel_size=k, stride=s,
                            activation=act, convolution_mode=mode,
                            has_bias=bias)


def _maxpool(k=(2, 2), s=(2, 2), mode=ConvolutionMode.TRUNCATE):
    return SubsamplingLayer(pooling_type=PoolingType.MAX, kernel_size=k,
                            stride=s, convolution_mode=mode)


class GraphZooModel(ZooModel):
    def init(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        return ComputationGraph(self.conf()).init()


class VGG16(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.VGG16``: 13 conv3x3 SAME +
    5 maxpools + FC 4096/4096/classes."""

    BLOCKS: Tuple[Tuple[int, int], ...] = (
        (64, 2), (128, 2), (256, 3), (512, 3), (512, 3))

    def __init__(self, num_classes: int = 1000, height: int = 224,
                 width: int = 224, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Nesterovs(learning_rate=0.01, momentum=0.9)

    def conf(self) -> ComputationGraphConfiguration:
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        prev = "input"
        for bi, (ch, reps) in enumerate(self.BLOCKS):
            for ri in range(reps):
                name = f"conv{bi + 1}_{ri + 1}"
                g.add_layer(name, _conv(ch, (3, 3)), prev)
                prev = name
            g.add_layer(f"pool{bi + 1}", _maxpool(), prev)
            prev = f"pool{bi + 1}"
        g.add_layer("fc1", DenseLayer(n_out=4096, activation=Activation.RELU),
                    prev)
        g.add_layer("fc2", DenseLayer(n_out=4096, activation=Activation.RELU),
                    "fc1")
        g.add_layer("output", OutputLayer(n_out=self.num_classes,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "fc2")
        g.set_outputs("output")
        return g.build()


class VGG19(VGG16):
    """Reference ``VGG19``: VGG16 with 4-deep conv blocks 3..5."""

    BLOCKS = ((64, 2), (128, 2), (256, 4), (512, 4), (512, 4))


class ResNet50(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.ResNet50``: conv7x7/2 + BN +
    maxpool3x3/2, 4 stages of bottleneck blocks [3,4,6,3] with channel
    triples (64,64,256)x, residual add via ``ElementWiseVertex(Add)``,
    global avg pool + softmax."""

    def __init__(self, num_classes: int = 1000, height: int = 224,
                 width: int = 224, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)

    stem_space_to_depth: bool = False
    """EXACT rewrite of the 7x7/s2 stem conv as space-to-depth(2) +
    zero-pad(1,2) + 4x4/s1 conv (the MLPerf TPU ResNet trick):
    out[i,j] = sum_{di,dj<7} x[2i+di-2, 2j+dj-2]*W regroups over 2x2
    input blocks into a stride-1 conv whose input has 4x the channels —
    3 -> 12 fills the 128-wide MXU 4x better, which matters most in the
    stem's dW backward (measured ~30 ms of the 113 ms batch-256 fwd+bwd,
    bench_resnet_profile.py). Same function class, weights map 1:1
    (tests pin the equivalence); default off keeps the reference's exact
    topology. Set via attribute after construction."""

    @staticmethod
    def stem_weights_to_s2d(w7):
        """Exact weight remap for ``stem_space_to_depth``: the reference
        stem's [7, 7, 3, C] kernel -> the rewrite's [4, 4, 12, C] kernel
        (w'[m, n, (a*2+b)*3 + ch] = w[2m+a, 2n+b, ch]; taps with
        2m+a >= 7 are zero). Transfer-learning/pretrained weights load
        through this."""
        import numpy as _np

        k7 = _np.asarray(w7)
        cin = k7.shape[2]
        out = _np.zeros((4, 4, 4 * cin, k7.shape[-1]), k7.dtype)
        for m in range(4):
            for a in range(2):
                if 2 * m + a >= 7:
                    continue
                for n in range(4):
                    for b in range(2):
                        if 2 * n + b >= 7:
                            continue
                        f = (a * 2 + b) * cin
                        out[m, n, f:f + cin] = k7[2 * m + a, 2 * n + b]
        return out

    fused_conv_bn: bool = False
    """Route every 1x1-conv + BN pair through ``FusedConvBN1x1`` (same
    math, BN statistics fused into the conv's output pass by a Pallas
    kernel — see ``ops/conv_fused.py``). ResNet-50 has 36 such pairs
    (bottleneck a/c convs + projections); the unfused schedule re-reads
    each conv output once for the statistics. Weights map 1:1 from the
    unfused graph via :meth:`fused_param_remap`; parity pinned by
    ``tests/test_zoo.py``. Default off keeps the reference's exact
    layer-pair topology. Set via attribute after construction."""

    def _conv_bn(self, g, name, n_out, k, s, inp, act=True,
                 mode=ConvolutionMode.SAME):
        if self.fused_conv_bn and tuple(k) == (1, 1):
            from deeplearning4j_tpu.conf.layers_cnn import FusedConvBN1x1

            g.add_layer(f"{name}_cb", FusedConvBN1x1(
                n_out=n_out, stride=s,
                activation=Activation.RELU if act else Activation.IDENTITY),
                inp)
            return f"{name}_cb"
        g.add_layer(f"{name}_conv",
                    _conv(n_out, k, s, Activation.IDENTITY, mode,
                          bias=False), inp)
        g.add_layer(f"{name}_bn", BatchNormalization(
            activation=Activation.RELU if act else Activation.IDENTITY),
            f"{name}_conv")
        return f"{name}_bn"

    @staticmethod
    def fused_param_remap(params, state):
        """Map an unfused ResNet-50's params/state onto the
        ``fused_conv_bn=True`` graph: every ``{n}_conv`` (W) + ``{n}_bn``
        (gamma/beta, running mean/var) pair collapses into ``{n}_cb``
        holding all five; non-1x1 layers pass through unchanged.
        Transfer-learning/pretrained weights load through this."""
        new_p, new_s = {}, {}
        for k, v in params.items():
            if k.endswith("_conv") and v.get("W") is not None \
                    and v["W"].shape[:2] == (1, 1) \
                    and f"{k[:-5]}_bn" in params and "b" not in v:
                n = k[:-5]
                new_p[f"{n}_cb"] = {"W": v["W"],
                                    "gamma": params[f"{n}_bn"]["gamma"],
                                    "beta": params[f"{n}_bn"]["beta"]}
                new_s[f"{n}_cb"] = dict(state.get(f"{n}_bn", {}))
            elif k.endswith("_bn") and f"{k[:-3]}_conv" in params \
                    and params[f"{k[:-3]}_conv"].get("W") is not None \
                    and params[f"{k[:-3]}_conv"]["W"].shape[:2] == (1, 1) \
                    and "b" not in params[f"{k[:-3]}_conv"]:
                continue  # folded into the _cb entry above
            else:
                new_p[k] = v
                if k in state:
                    new_s[k] = state[k]
        for k, v in state.items():
            if k not in new_s and k in new_p:
                new_s[k] = v
        return new_p, new_s

    def _bottleneck(self, g, name, inp, filters, stride, project):
        f1, f2, f3 = filters
        x = self._conv_bn(g, f"{name}_a", f1, (1, 1), stride, inp)
        x = self._conv_bn(g, f"{name}_b", f2, (3, 3), (1, 1), x)
        x = self._conv_bn(g, f"{name}_c", f3, (1, 1), (1, 1), x, act=False)
        if project:
            sc = self._conv_bn(g, f"{name}_sc", f3, (1, 1), stride, inp,
                               act=False)
        else:
            sc = inp
        g.add_vertex(f"{name}_add", ElementWiseVertex(op=ElementWiseOp.ADD),
                     x, sc)
        g.add_layer(f"{name}_relu", ActivationLayer(activation=Activation.RELU),
                    f"{name}_add")
        return f"{name}_relu"

    def conf(self) -> ComputationGraphConfiguration:
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.RELU)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        if self.stem_space_to_depth:
            from deeplearning4j_tpu.conf.layers_cnn import (
                SpaceToDepthLayer,
                ZeroPaddingLayer,
            )

            g.add_vertex("stem_s2d", LayerVertex(
                layer=SpaceToDepthLayer(block_size=2)), "input")
            g.add_vertex("stem_pad", LayerVertex(
                layer=ZeroPaddingLayer(padding=(1, 2, 1, 2))), "stem_s2d")
            x = self._conv_bn(g, "stem", 64, (4, 4), (1, 1), "stem_pad",
                              mode=ConvolutionMode.TRUNCATE)
        else:
            x = self._conv_bn(g, "stem", 64, (7, 7), (2, 2), "input")
        g.add_layer("stem_pool", _maxpool((3, 3), (2, 2),
                                          ConvolutionMode.SAME), x)
        x = "stem_pool"
        stages = ((64, 64, 256, 3), (128, 128, 512, 4),
                  (256, 256, 1024, 6), (512, 512, 2048, 3))
        for si, (f1, f2, f3, reps) in enumerate(stages):
            for ri in range(reps):
                stride = (1, 1) if (si == 0 or ri > 0) else (2, 2)
                x = self._bottleneck(g, f"res{si + 2}{chr(97 + ri)}", x,
                                     (f1, f2, f3), stride, project=(ri == 0))
        g.add_layer("avgpool",
                    GlobalPoolingLayer(pooling_type=PoolingType.AVG), x)
        g.add_layer("output", OutputLayer(n_out=self.num_classes,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "avgpool")
        g.set_outputs("output")
        return g.build()


class SqueezeNet(GraphZooModel):
    """Reference ``SqueezeNet`` (v1.1): conv3x3/2 + fire modules with
    squeeze(1x1) -> expand(1x1 || 3x3) -> MergeVertex concat, conv1x1 head +
    global avg pool."""

    def __init__(self, num_classes: int = 1000, height: int = 224,
                 width: int = 224, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)

    def _fire(self, g, name, inp, squeeze, expand):
        g.add_layer(f"{name}_sq", _conv(squeeze, (1, 1)), inp)
        g.add_layer(f"{name}_e1", _conv(expand, (1, 1)), f"{name}_sq")
        g.add_layer(f"{name}_e3", _conv(expand, (3, 3)), f"{name}_sq")
        g.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_e1", f"{name}_e3")
        return f"{name}_cat"

    def conf(self) -> ComputationGraphConfiguration:
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.RELU)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        g.add_layer("conv1", _conv(64, (3, 3), (2, 2)), "input")
        g.add_layer("pool1", _maxpool((3, 3), (2, 2)), "conv1")
        x = self._fire(g, "fire2", "pool1", 16, 64)
        x = self._fire(g, "fire3", x, 16, 64)
        g.add_layer("pool3", _maxpool((3, 3), (2, 2)), x)
        x = self._fire(g, "fire4", "pool3", 32, 128)
        x = self._fire(g, "fire5", x, 32, 128)
        g.add_layer("pool5", _maxpool((3, 3), (2, 2)), x)
        x = self._fire(g, "fire6", "pool5", 48, 192)
        x = self._fire(g, "fire7", x, 48, 192)
        x = self._fire(g, "fire8", x, 64, 256)
        x = self._fire(g, "fire9", x, 64, 256)
        g.add_layer("conv10", _conv(self.num_classes, (1, 1)), x)
        g.add_layer("avgpool",
                    GlobalPoolingLayer(pooling_type=PoolingType.AVG), "conv10")
        # avgpool already yields num_classes features: a parameter-free
        # LossLayer head, matching the reference topology (no extra dense)
        g.add_layer("output", LossLayer(
            activation=Activation.SOFTMAX, loss_fn=LossMCXENT()), "avgpool")
        g.set_outputs("output")
        return g.build()


class Darknet19(GraphZooModel):
    """Reference ``Darknet19`` (YOLO9000 backbone): 19 convs (3x3/1x1
    alternation) + BN + LeakyReLU, 5 maxpools, conv1x1 head + global
    avg pool."""

    def __init__(self, num_classes: int = 1000, height: int = 224,
                 width: int = 224, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)

    def _conv_bn_leaky(self, g, i, n_out, k, inp):
        name = f"conv{i}"
        g.add_layer(name, _conv(n_out, k, (1, 1), Activation.IDENTITY,
                                bias=False), inp)
        g.add_layer(f"{name}_bn",
                    BatchNormalization(activation=Activation.LEAKYRELU), name)
        return f"{name}_bn"

    def conf(self) -> ComputationGraphConfiguration:
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.RELU)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        spec = [(32, 3), "M", (64, 3), "M", (128, 3), (64, 1), (128, 3), "M",
                (256, 3), (128, 1), (256, 3), "M",
                (512, 3), (256, 1), (512, 3), (256, 1), (512, 3), "M",
                (1024, 3), (512, 1), (1024, 3), (512, 1), (1024, 3)]
        x, ci, pi = "input", 0, 0
        for s in spec:
            if s == "M":
                pi += 1
                g.add_layer(f"pool{pi}", _maxpool(), x)
                x = f"pool{pi}"
            else:
                ci += 1
                n_out, k = s
                x = self._conv_bn_leaky(g, ci, n_out, (k, k), x)
        g.add_layer("head", _conv(self.num_classes, (1, 1),
                                  act=Activation.IDENTITY), x)
        g.add_layer("avgpool",
                    GlobalPoolingLayer(pooling_type=PoolingType.AVG), "head")
        # avgpool already yields num_classes features: a parameter-free
        # LossLayer head, matching the reference topology (no extra dense)
        g.add_layer("output", LossLayer(
            activation=Activation.SOFTMAX, loss_fn=LossMCXENT()), "avgpool")
        g.set_outputs("output")
        return g.build()


class UNet(GraphZooModel):
    """Reference ``UNet``: 4-down/4-up encoder-decoder, skip connections via
    ``MergeVertex``, nearest-neighbour ``Upsampling2D`` + conv on the way up,
    sigmoid ``CnnLossLayer`` head (binary segmentation)."""

    def __init__(self, height: int = 128, width: int = 128, channels: int = 1,
                 base: int = 64, seed: int = 123,
                 updater: IUpdater | None = None):
        self.height, self.width, self.channels = height, width, channels
        self.base = base
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-4)

    def _double_conv(self, g, name, n_out, inp):
        g.add_layer(f"{name}_1", _conv(n_out, (3, 3)), inp)
        g.add_layer(f"{name}_2", _conv(n_out, (3, 3)), f"{name}_1")
        return f"{name}_2"

    def conf(self) -> ComputationGraphConfiguration:
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.RELU)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        b = self.base
        skips = []
        x = "input"
        for i, ch in enumerate([b, b * 2, b * 4, b * 8]):
            x = self._double_conv(g, f"down{i + 1}", ch, x)
            skips.append(x)
            g.add_layer(f"dpool{i + 1}", _maxpool(), x)
            x = f"dpool{i + 1}"
        x = self._double_conv(g, "bottom", b * 16, x)
        for i, ch in enumerate([b * 8, b * 4, b * 2, b]):
            g.add_layer(f"up{i + 1}_us", Upsampling2D(size=(2, 2)), x)
            g.add_layer(f"up{i + 1}_conv", _conv(ch, (2, 2)), f"up{i + 1}_us")
            g.add_vertex(f"up{i + 1}_cat", MergeVertex(),
                         skips[3 - i], f"up{i + 1}_conv")
            x = self._double_conv(g, f"up{i + 1}", ch, f"up{i + 1}_cat")
        g.add_layer("head", _conv(1, (1, 1), act=Activation.IDENTITY), x)
        g.add_layer("output", CnnLossLayer(activation=Activation.SIGMOID,
                                           loss_fn=LossBinaryXENT()), "head")
        g.set_outputs("output")
        return g.build()


class Xception(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.Xception``: entry flow
    (conv32/2, conv64, separable-conv residual blocks 128/256/728), middle
    flow (8 x three separable-conv-728 residual blocks), exit flow
    (728->1024 residual, sepconv 1536, 2048, global average pool)."""

    def __init__(self, num_classes: int = 1000, height: int = 299,
                 width: int = 299, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None,
                 middle_flow_repeats: int = 8):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Nesterovs(learning_rate=1e-2, momentum=0.9)
        self.middle_flow_repeats = middle_flow_repeats

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers_cnn import SeparableConvolution2D

        def sep(n):
            return SeparableConvolution2D(
                n_out=n, kernel_size=(3, 3), stride=(1, 1),
                activation=Activation.IDENTITY,
                convolution_mode=ConvolutionMode.SAME)

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))
        g.add_layer("c1", _conv(32, (3, 3), (2, 2),
                                mode=ConvolutionMode.TRUNCATE,
                                act=Activation.IDENTITY), "input")
        g.add_layer("c1bn", BatchNormalization(activation=Activation.RELU),
                    "c1")
        g.add_layer("c2", _conv(64, (3, 3), act=Activation.IDENTITY), "c1bn")
        g.add_layer("c2bn", BatchNormalization(activation=Activation.RELU),
                    "c2")
        prev = "c2bn"
        # entry-flow residual blocks
        for i, ch in enumerate((128, 256, 728)):
            rname = f"e{i}_res"
            g.add_layer(rname, _conv(ch, (1, 1), (2, 2),
                                     act=Activation.IDENTITY,
                                     mode=ConvolutionMode.SAME), prev)
            g.add_layer(f"e{i}_s1", sep(ch), prev)
            g.add_layer(f"e{i}_b1",
                        BatchNormalization(activation=Activation.RELU),
                        f"e{i}_s1")
            g.add_layer(f"e{i}_s2", sep(ch), f"e{i}_b1")
            g.add_layer(f"e{i}_b2", BatchNormalization(), f"e{i}_s2")
            g.add_layer(f"e{i}_pool", _maxpool((3, 3), (2, 2),
                                               ConvolutionMode.SAME),
                        f"e{i}_b2")
            g.add_vertex(f"e{i}_add",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         f"e{i}_pool", rname)
            prev = f"e{i}_add"
        # middle flow
        for r in range(self.middle_flow_repeats):
            inp = prev
            last = inp
            for j in range(3):
                g.add_layer(f"m{r}_a{j}",
                            ActivationLayer(activation=Activation.RELU),
                            last)
                g.add_layer(f"m{r}_s{j}", sep(728), f"m{r}_a{j}")
                g.add_layer(f"m{r}_b{j}", BatchNormalization(),
                            f"m{r}_s{j}")
                last = f"m{r}_b{j}"
            g.add_vertex(f"m{r}_add",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         last, inp)
            prev = f"m{r}_add"
        # exit flow
        g.add_layer("x_res", _conv(1024, (1, 1), (2, 2),
                                   act=Activation.IDENTITY,
                                   mode=ConvolutionMode.SAME), prev)
        g.add_layer("x_s1", sep(728), prev)
        g.add_layer("x_b1", BatchNormalization(activation=Activation.RELU),
                    "x_s1")
        g.add_layer("x_s2", sep(1024), "x_b1")
        g.add_layer("x_b2", BatchNormalization(), "x_s2")
        g.add_layer("x_pool", _maxpool((3, 3), (2, 2), ConvolutionMode.SAME),
                    "x_b2")
        g.add_vertex("x_add", ElementWiseVertex(op=ElementWiseOp.ADD),
                     "x_pool", "x_res")
        g.add_layer("x_s3", sep(1536), "x_add")
        g.add_layer("x_b3", BatchNormalization(activation=Activation.RELU),
                    "x_s3")
        g.add_layer("x_s4", sep(2048), "x_b3")
        g.add_layer("x_b4", BatchNormalization(activation=Activation.RELU),
                    "x_s4")
        g.add_layer("gap", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                    "x_b4")
        g.add_layer("output", OutputLayer(n_out=self.num_classes,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "gap")
        g.set_outputs("output")
        return g.build()


class InceptionResNetV1(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.InceptionResNetV1`` (the
    FaceNet variant): stem, 5 x Inception-ResNet-A (scale 0.17), reduction-A,
    10 x Inception-ResNet-B (scale 0.10), reduction-B, 5 x Inception-ResNet-C
    (scale 0.20), average pool, embedding + softmax head. Residual scaling
    uses ``ScaleVertex`` + ``ElementWiseVertex(Add)`` as in the reference."""

    def __init__(self, num_classes: int = 1001, height: int = 160,
                 width: int = 160, channels: int = 3,
                 embedding_size: int = 128, seed: int = 123,
                 updater: IUpdater | None = None,
                 blocks_a: int = 5, blocks_b: int = 10, blocks_c: int = 5):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.embedding_size = embedding_size
        self.seed = seed
        self.updater = updater or Adam(learning_rate=0.1)
        self.blocks_a, self.blocks_b, self.blocks_c = blocks_a, blocks_b, blocks_c

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.graph import ScaleVertex

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def cbr(name, n, k, s, inp, mode=ConvolutionMode.SAME):
            g.add_layer(name, _conv(n, k, s, act=Activation.IDENTITY,
                                    mode=mode), inp)
            g.add_layer(name + "_bn",
                        BatchNormalization(activation=Activation.RELU), name)
            return name + "_bn"

        # stem
        p = cbr("s1", 32, (3, 3), (2, 2), "input",
                ConvolutionMode.TRUNCATE)
        p = cbr("s2", 32, (3, 3), (1, 1), p)
        p = cbr("s3", 64, (3, 3), (1, 1), p)
        g.add_layer("s4", _maxpool((3, 3), (2, 2)), p)
        p = cbr("s5", 80, (1, 1), (1, 1), "s4")
        p = cbr("s6", 192, (3, 3), (1, 1), p)
        p = cbr("s7", 256, (3, 3), (2, 2), p, ConvolutionMode.SAME)

        def block_a(i, inp):
            b1 = cbr(f"a{i}_b1", 32, (1, 1), (1, 1), inp)
            b2 = cbr(f"a{i}_b2b", 32, (3, 3), (1, 1),
                     cbr(f"a{i}_b2a", 32, (1, 1), (1, 1), inp))
            b3 = cbr(f"a{i}_b3c", 32, (3, 3), (1, 1),
                     cbr(f"a{i}_b3b", 32, (3, 3), (1, 1),
                         cbr(f"a{i}_b3a", 32, (1, 1), (1, 1), inp)))
            g.add_vertex(f"a{i}_cat", MergeVertex(), b1, b2, b3)
            g.add_layer(f"a{i}_up", _conv(256, (1, 1),
                                          act=Activation.IDENTITY),
                        f"a{i}_cat")
            g.add_vertex(f"a{i}_scale", ScaleVertex(scale_factor=0.17),
                         f"a{i}_up")
            g.add_vertex(f"a{i}_add",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         inp, f"a{i}_scale")
            g.add_layer(f"a{i}_relu",
                        ActivationLayer(activation=Activation.RELU),
                        f"a{i}_add")
            return f"a{i}_relu"

        for i in range(self.blocks_a):
            p = block_a(i, p)

        # reduction-A -> 896 channels
        g.add_layer("ra_pool", _maxpool((3, 3), (2, 2),
                                        ConvolutionMode.SAME), p)
        ra1 = cbr("ra_c1", 384, (3, 3), (2, 2), p, ConvolutionMode.SAME)
        ra2 = cbr("ra_c2c", 256, (3, 3), (2, 2),
                  cbr("ra_c2b", 192, (3, 3), (1, 1),
                      cbr("ra_c2a", 192, (1, 1), (1, 1), p)),
                  ConvolutionMode.SAME)
        g.add_vertex("ra_cat", MergeVertex(), "ra_pool", ra1, ra2)
        p = "ra_cat"  # 256+384+256 = 896

        def block_b(i, inp):
            b1 = cbr(f"b{i}_b1", 128, (1, 1), (1, 1), inp)
            b2 = cbr(f"b{i}_b2c", 128, (7, 1), (1, 1),
                     cbr(f"b{i}_b2b", 128, (1, 7), (1, 1),
                         cbr(f"b{i}_b2a", 128, (1, 1), (1, 1), inp)))
            g.add_vertex(f"b{i}_cat", MergeVertex(), b1, b2)
            g.add_layer(f"b{i}_up", _conv(896, (1, 1),
                                          act=Activation.IDENTITY),
                        f"b{i}_cat")
            g.add_vertex(f"b{i}_scale", ScaleVertex(scale_factor=0.10),
                         f"b{i}_up")
            g.add_vertex(f"b{i}_add",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         inp, f"b{i}_scale")
            g.add_layer(f"b{i}_relu",
                        ActivationLayer(activation=Activation.RELU),
                        f"b{i}_add")
            return f"b{i}_relu"

        for i in range(self.blocks_b):
            p = block_b(i, p)

        # reduction-B -> 1792 channels
        g.add_layer("rb_pool", _maxpool((3, 3), (2, 2),
                                        ConvolutionMode.SAME), p)
        rb1 = cbr("rb_c1b", 384, (3, 3), (2, 2),
                  cbr("rb_c1a", 256, (1, 1), (1, 1), p),
                  ConvolutionMode.SAME)
        rb2 = cbr("rb_c2b", 256, (3, 3), (2, 2),
                  cbr("rb_c2a", 256, (1, 1), (1, 1), p),
                  ConvolutionMode.SAME)
        rb3 = cbr("rb_c3c", 256, (3, 3), (2, 2),
                  cbr("rb_c3b", 256, (3, 3), (1, 1),
                      cbr("rb_c3a", 256, (1, 1), (1, 1), p)),
                  ConvolutionMode.SAME)
        g.add_vertex("rb_cat", MergeVertex(), "rb_pool", rb1, rb2, rb3)
        p = "rb_cat"  # 896+384+256+256 = 1792

        def block_c(i, inp):
            b1 = cbr(f"c{i}_b1", 192, (1, 1), (1, 1), inp)
            b2 = cbr(f"c{i}_b2c", 192, (3, 1), (1, 1),
                     cbr(f"c{i}_b2b", 192, (1, 3), (1, 1),
                         cbr(f"c{i}_b2a", 192, (1, 1), (1, 1), inp)))
            g.add_vertex(f"c{i}_cat", MergeVertex(), b1, b2)
            g.add_layer(f"c{i}_up", _conv(1792, (1, 1),
                                          act=Activation.IDENTITY),
                        f"c{i}_cat")
            g.add_vertex(f"c{i}_scale", ScaleVertex(scale_factor=0.20),
                         f"c{i}_up")
            g.add_vertex(f"c{i}_add",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         inp, f"c{i}_scale")
            g.add_layer(f"c{i}_relu",
                        ActivationLayer(activation=Activation.RELU),
                        f"c{i}_add")
            return f"c{i}_relu"

        for i in range(self.blocks_c):
            p = block_c(i, p)

        g.add_layer("gap", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                    p)
        g.add_layer("embedding", DenseLayer(
            n_out=self.embedding_size, activation=Activation.IDENTITY), "gap")
        g.add_layer("output", OutputLayer(n_out=self.num_classes,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "embedding")
        g.set_outputs("output")
        return g.build()


class TinyYOLO(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.TinyYOLO``: Darknet-tiny
    backbone (conv3x3 16..1024 with leaky-relu BN and maxpools) + 1x1
    detection conv + ``Yolo2OutputLayer``; input 416x416 -> 13x13 grid,
    5 anchor priors."""

    PRIORS = ((1.08, 1.19), (3.42, 4.41), (6.63, 11.38), (9.42, 5.11),
              (16.62, 10.52))

    def __init__(self, num_classes: int = 20, height: int = 416,
                 width: int = 416, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None,
                 boxes: Tuple[Tuple[float, float], ...] | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)
        self.boxes = boxes or self.PRIORS

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers_objdetect import Yolo2OutputLayer

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def cbl(name, n, inp):  # conv + BN + leaky relu
            g.add_layer(name, _conv(n, (3, 3), act=Activation.IDENTITY,
                                    bias=False), inp)
            g.add_layer(name + "_bn", BatchNormalization(
                activation=Activation.LEAKYRELU), name)
            return name + "_bn"

        p = cbl("c1", 16, "input")
        for i, n in enumerate((32, 64, 128, 256, 512)):
            g.add_layer(f"p{i + 1}", _maxpool((2, 2), (2, 2)), p)
            p = cbl(f"c{i + 2}", n, f"p{i + 1}")
        # final pool is stride-1 SAME in tiny-yolo (keeps 13x13)
        g.add_layer("p6", _maxpool((2, 2), (1, 1), ConvolutionMode.SAME), p)
        p = cbl("c7", 1024, "p6")
        p = cbl("c8", 1024, p)
        nb = len(self.boxes)
        g.add_layer("detect", _conv(nb * (5 + self.num_classes), (1, 1),
                                    act=Activation.IDENTITY), p)
        g.add_layer("yolo", Yolo2OutputLayer(boxes=tuple(self.boxes)),
                    "detect")
        g.set_outputs("yolo")
        return g.build()


class YOLO2(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.YOLO2``: Darknet-19 backbone
    with the passthrough route — the 26x26x512 feature map goes through a
    1x1x64 conv and ``SpaceToDepth(2)`` then concats with the 13x13x1024
    head before the detection conv (reference wiring via the same
    vertices)."""

    PRIORS = ((0.57273, 0.677385), (1.87446, 2.06253), (3.33843, 5.47434),
              (7.88282, 3.52778), (9.77052, 9.16828))

    def __init__(self, num_classes: int = 80, height: int = 416,
                 width: int = 416, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None,
                 boxes: Tuple[Tuple[float, float], ...] | None = None):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)
        self.boxes = boxes or self.PRIORS

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers_cnn import SpaceToDepthLayer
        from deeplearning4j_tpu.conf.layers_objdetect import Yolo2OutputLayer

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def cbl(name, n, k, inp):
            g.add_layer(name, _conv(n, k, act=Activation.IDENTITY,
                                    bias=False), inp)
            g.add_layer(name + "_bn", BatchNormalization(
                activation=Activation.LEAKYRELU), name)
            return name + "_bn"

        # darknet-19 trunk
        p = cbl("c1", 32, (3, 3), "input")
        g.add_layer("p1", _maxpool((2, 2), (2, 2)), p)
        p = cbl("c2", 64, (3, 3), "p1")
        g.add_layer("p2", _maxpool((2, 2), (2, 2)), p)
        p = cbl("c3", 128, (3, 3), "p2")
        p = cbl("c4", 64, (1, 1), p)
        p = cbl("c5", 128, (3, 3), p)
        g.add_layer("p3", _maxpool((2, 2), (2, 2)), p)
        p = cbl("c6", 256, (3, 3), "p3")
        p = cbl("c7", 128, (1, 1), p)
        p = cbl("c8", 256, (3, 3), p)
        g.add_layer("p4", _maxpool((2, 2), (2, 2)), p)
        p = cbl("c9", 512, (3, 3), "p4")
        p = cbl("c10", 256, (1, 1), p)
        p = cbl("c11", 512, (3, 3), p)
        p = cbl("c12", 256, (1, 1), p)
        route = cbl("c13", 512, (3, 3), p)  # 26x26x512 passthrough source
        g.add_layer("p5", _maxpool((2, 2), (2, 2)), route)
        p = cbl("c14", 1024, (3, 3), "p5")
        p = cbl("c15", 512, (1, 1), p)
        p = cbl("c16", 1024, (3, 3), p)
        p = cbl("c17", 512, (1, 1), p)
        p = cbl("c18", 1024, (3, 3), p)
        p = cbl("c19", 1024, (3, 3), p)
        p = cbl("c20", 1024, (3, 3), p)
        # passthrough: 26x26x512 -> 1x1x64 -> space-to-depth -> 13x13x256
        r = cbl("route_conv", 64, (1, 1), route)
        g.add_layer("route_s2d", SpaceToDepthLayer(block_size=2), r)
        g.add_vertex("concat", MergeVertex(), "route_s2d", p)
        p = cbl("c21", 1024, (3, 3), "concat")
        nb = len(self.boxes)
        g.add_layer("detect", _conv(nb * (5 + self.num_classes), (1, 1),
                                    act=Activation.IDENTITY), p)
        g.add_layer("yolo", Yolo2OutputLayer(boxes=tuple(self.boxes)),
                    "detect")
        g.set_outputs("yolo")
        return g.build()


class NASNet(GraphZooModel):
    """Reference ``org.deeplearning4j.zoo.model.NASNet`` (NASNet-A mobile
    schema): stem conv, alternating stacks of NORMAL cells separated by
    REDUCTION cells, each cell the NASNet-A 5-block DAG over (h, h_prev)
    with separable convs / average pools / identities, 1x1 squeeze
    adjustments on both inputs, block outputs concatenated."""

    def __init__(self, num_classes: int = 1000, height: int = 224,
                 width: int = 224, channels: int = 3, seed: int = 123,
                 updater: IUpdater | None = None,
                 penultimate_filters: int = 1056, num_cells: int = 4):
        self.num_classes = num_classes
        self.height, self.width, self.channels = height, width, channels
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)
        # NASNet-A (N @ P): filters per normal cell = P / 24 * 4
        self.filters = max(penultimate_filters // 24, 8)
        self.num_cells = num_cells

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers_cnn import SeparableConvolution2D

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.convolutional(
                 self.height, self.width, self.channels)))

        def sep(name, n, k, s, inp):
            g.add_layer(name + "_r",
                        ActivationLayer(activation=Activation.RELU), inp)
            g.add_layer(name, SeparableConvolution2D(
                n_out=n, kernel_size=k, stride=s,
                activation=Activation.IDENTITY,
                convolution_mode=ConvolutionMode.SAME), name + "_r")
            g.add_layer(name + "_bn", BatchNormalization(), name)
            return name + "_bn"

        def squeeze(name, n, s, inp):
            g.add_layer(name, _conv(n, (1, 1), s, act=Activation.IDENTITY,
                                    mode=ConvolutionMode.SAME), inp)
            g.add_layer(name + "_bn", BatchNormalization(), name)
            return name + "_bn"

        def avg3(name, s, inp):
            g.add_layer(name, SubsamplingLayer(
                pooling_type=PoolingType.AVG, kernel_size=(3, 3), stride=s,
                convolution_mode=ConvolutionMode.SAME), inp)
            return name

        def add(name, a, b):
            g.add_vertex(name, ElementWiseVertex(op=ElementWiseOp.ADD), a, b)
            return name

        def normal_cell(cid, h, h_prev, f, prev_stride=(1, 1)):
            # adjust both inputs to f channels (reference squeeze/adjust);
            # right after a reduction cell h_prev still has the pre-reduction
            # spatial size, so its adjust runs at stride 2 (the reference's
            # factorized-reduction adjust block)
            h = squeeze(f"{cid}_adj", f, (1, 1), h)
            hp = squeeze(f"{cid}_adjp", f, prev_stride, h_prev)
            b1 = add(f"{cid}_b1", sep(f"{cid}_b1s", f, (3, 3), (1, 1), h), h)
            b2 = add(f"{cid}_b2",
                     sep(f"{cid}_b2a", f, (3, 3), (1, 1), hp),
                     sep(f"{cid}_b2b", f, (5, 5), (1, 1), h))
            b3 = add(f"{cid}_b3", avg3(f"{cid}_b3p", (1, 1), h), hp)
            b4 = add(f"{cid}_b4", avg3(f"{cid}_b4a", (1, 1), hp),
                     avg3(f"{cid}_b4b", (1, 1), hp))
            b5 = add(f"{cid}_b5",
                     sep(f"{cid}_b5a", f, (5, 5), (1, 1), hp),
                     sep(f"{cid}_b5b", f, (3, 3), (1, 1), hp))
            g.add_vertex(f"{cid}_out", MergeVertex(), b1, b2, b3, b4, b5)
            return f"{cid}_out"

        def reduction_cell(cid, h, h_prev, f):
            h = squeeze(f"{cid}_adj", f, (1, 1), h)
            hp = squeeze(f"{cid}_adjp", f, (1, 1), h_prev)
            b1 = add(f"{cid}_b1",
                     sep(f"{cid}_b1a", f, (5, 5), (2, 2), hp),
                     sep(f"{cid}_b1b", f, (7, 7), (2, 2), h))
            g.add_layer(f"{cid}_b2m", _maxpool((3, 3), (2, 2),
                                               ConvolutionMode.SAME), h)
            b2 = add(f"{cid}_b2", f"{cid}_b2m",
                     sep(f"{cid}_b2s", f, (7, 7), (2, 2), hp))
            b3 = add(f"{cid}_b3", avg3(f"{cid}_b3a", (2, 2), h),
                     sep(f"{cid}_b3s", f, (5, 5), (2, 2), hp))
            b4 = add(f"{cid}_b4", avg3(f"{cid}_b4a", (1, 1), b1),
                     f"{cid}_b2m")
            b5 = add(f"{cid}_b5", sep(f"{cid}_b5s", f, (3, 3), (1, 1), b1),
                     avg3(f"{cid}_b5a", (2, 2), h))
            g.add_vertex(f"{cid}_out", MergeVertex(), b2, b3, b4, b5)
            return f"{cid}_out"

        f = self.filters
        g.add_layer("stem", _conv(f, (3, 3), (2, 2),
                                  act=Activation.IDENTITY,
                                  mode=ConvolutionMode.SAME), "input")
        g.add_layer("stem_bn", BatchNormalization(), "stem")
        h_prev, h = "stem_bn", "stem_bn"
        cid = 0
        for stack in range(3):
            for ci in range(self.num_cells):
                stride_prev = (2, 2) if stack > 0 and ci == 0 else (1, 1)
                out = normal_cell(f"n{cid}", h, h_prev, f,
                                  prev_stride=stride_prev)
                h_prev, h = h, out
                cid += 1
            if stack < 2:
                f *= 2
                out = reduction_cell(f"r{stack}", h, h_prev, f)
                h_prev, h = h, out
        g.add_layer("final_relu", ActivationLayer(
            activation=Activation.RELU), h)
        g.add_layer("gap", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                    "final_relu")
        g.add_layer("output", OutputLayer(n_out=self.num_classes,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "gap")
        g.set_outputs("output")
        return g.build()


class TransformerEncoder(GraphZooModel):
    """Transformer encoder classifier (no direct reference zoo model — the
    reference reaches Transformers only through SameDiff
    ``multiHeadDotProductAttention`` / TF import, SURVEY.md §5.7; this makes
    the same architecture a first-class graph config). Learned positional
    embeddings, then pre-LN blocks: x + MHA(LN(x)), x + FFN(LN(x)). The
    attention core goes through ``ops.dot_product_attention`` (``auto``
    dispatches by measured crossover — bench_attention.py — to full
    materialization, XLA blockwise, or the Pallas flash kernel;
    ``attention_impl='flash'`` forces the strictly-O(T)-VMEM kernel)."""

    def __init__(self, num_classes: int = 2, vocab_size: int = 0,
                 embed_dim: int = 64, n_heads: int = 4, n_layers: int = 2,
                 ffn_dim: int = 0, max_len: int = 128, seed: int = 123,
                 updater: IUpdater | None = None,
                 attention_impl: str = "auto", causal: bool = False,
                 moe_experts: int = 0, moe_top_k: int = 2,
                 moe_capacity_factor: float = 1.25,
                 lm_head: bool = False, use_kernels: bool = False):
        """``vocab_size``>0: token-id inputs through an embedding;
        0: continuous ``[batch, time, embed_dim]`` inputs.

        ``moe_experts`` > 0 replaces every block's dense FFN with a
        GShard-style ``MoELayer`` (round-4 productization): the same
        config then trains data+expert-parallel under
        ``ParallelWrapper(expert_parallel=True)`` with no hand-written
        shard_map.

        ``lm_head=True`` makes this a causal language model instead of a
        classifier: the pooling layer is dropped and the output head is a
        time-distributed ``[batch, time, vocab_size]`` softmax over the
        vocabulary (requires ``vocab_size > 0`` and ``causal=True``).
        This is the configuration :meth:`decoder` serves with a KV cache
        (``nn.decoding.TransformerDecoder`` /
        ``parallel.generation.GenerationEngine``).

        ``use_kernels=True`` opts the conf into registry kernel routing
        (tuned flash-attention prefill / paged decode attention plus the
        matmul-class fusions); untuned envelopes stay stock XLA."""
        self.num_classes = num_classes
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.ffn_dim = ffn_dim or 4 * embed_dim
        self.max_len = max_len
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)
        self.attention_impl = attention_impl
        self.causal = causal
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.lm_head = lm_head
        self.use_kernels = use_kernels
        if lm_head and not (vocab_size and causal):
            raise ValueError("lm_head=True requires vocab_size > 0 and "
                             "causal=True (a language model decodes token "
                             "ids left to right)")

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers import EmbeddingSequenceLayer
        from deeplearning4j_tpu.conf.layers_attention import (
            SelfAttentionLayer,
        )
        from deeplearning4j_tpu.conf.layers_extra import (
            LayerNormalization,
            PositionEmbeddingLayer,
        )

        e = self.embed_dim
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .use_kernels(self.use_kernels)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.recurrent(
                 e if not self.vocab_size else 1, timesteps=self.max_len)))
        prev = "input"
        if self.vocab_size:
            g.add_layer("embed", EmbeddingSequenceLayer(
                n_in=self.vocab_size, n_out=e), prev)
            prev = "embed"
        g.add_layer("pos", PositionEmbeddingLayer(max_len=self.max_len),
                    prev)
        prev = "pos"
        for i in range(self.n_layers):
            g.add_layer(f"b{i}_ln1", LayerNormalization(), prev)
            g.add_layer(f"b{i}_attn", SelfAttentionLayer(
                n_out=e, n_heads=self.n_heads, causal=self.causal,
                attention_impl=self.attention_impl), f"b{i}_ln1")
            g.add_vertex(f"b{i}_res1",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         prev, f"b{i}_attn")
            g.add_layer(f"b{i}_ln2", LayerNormalization(), f"b{i}_res1")
            if self.moe_experts:
                from deeplearning4j_tpu.conf.layers_moe import MoELayer

                g.add_layer(f"b{i}_moe", MoELayer(
                    n_experts=self.moe_experts, d_hidden=self.ffn_dim,
                    top_k=self.moe_top_k,
                    capacity_factor=self.moe_capacity_factor,
                    residual=False), f"b{i}_ln2")
                ff_out = f"b{i}_moe"
            else:
                g.add_layer(f"b{i}_ff1", DenseLayer(
                    n_out=self.ffn_dim, activation=Activation.GELU),
                    f"b{i}_ln2")
                g.add_layer(f"b{i}_ff2", DenseLayer(
                    n_out=e, activation=Activation.IDENTITY), f"b{i}_ff1")
                ff_out = f"b{i}_ff2"
            g.add_vertex(f"b{i}_res2",
                         ElementWiseVertex(op=ElementWiseOp.ADD),
                         f"b{i}_res1", ff_out)
            prev = f"b{i}_res2"
        g.add_layer("final_ln", LayerNormalization(), prev)
        if self.lm_head:
            # language-model head: time-distributed vocab logits — no
            # pooling, every position predicts its next token
            g.add_layer("output", OutputLayer(
                n_out=self.vocab_size, activation=Activation.SOFTMAX,
                loss_fn=LossMCXENT()), "final_ln")
        else:
            g.add_layer("pool", GlobalPoolingLayer(
                pooling_type=PoolingType.AVG), "final_ln")
            g.add_layer("output", OutputLayer(
                n_out=self.num_classes, activation=Activation.SOFTMAX,
                loss_fn=LossMCXENT()), "pool")
        g.set_outputs("output")
        return g.build()

    def decoder(self, net=None, **kw):
        """KV-cached generation front for this configuration: a
        ``nn.decoding.TransformerDecoder`` with ``prefill`` (one-launch
        prompt ingestion) and ``decode_step`` (fused multi-token
        autoregressive decode) executables, AOT-cached per KV
        length-bucket. ``net``: an already-initialized/trained
        ComputationGraph of this conf (default: a fresh ``init()``).
        Remaining kwargs go to ``TransformerDecoder`` (``max_batch``,
        ``fused_steps``, bucket knobs)."""
        if not self.lm_head:
            raise ValueError(
                "decoder() requires lm_head=True (the classifier head "
                "pools over time and cannot emit next-token logits)")
        from deeplearning4j_tpu.nn.decoding import TransformerDecoder

        return TransformerDecoder(net if net is not None else self.init(),
                                  max_len=self.max_len, **kw)


class HybridDecoderLM(GraphZooModel):
    """A causal language model whose mixer differs by layer:
    ``mixer_types[i]`` is ``"lightning-attn"`` (linear attention with a
    recurrent state, ``conf.layers_hybrid.LightningAttentionLayer``),
    ``"minicpm4"`` (block-sparse attention with grouped KV heads,
    ``BlockSparseAttentionLayer``), ``"window-attn"`` (gated grouped-query
    attention that rotates and sees its last ``window`` positions: a ring
    cache, ``GatedAttentionLayer``) or ``"full-attn"`` (the same layer,
    neither rotating nor bounded: the two kinds pair the layer's two
    switches, ``window`` and ``rope_theta``, as the one family that uses
    them does), ``"rope-attn"`` (grouped-query attention with the q/k
    norm, rotating at ``rope_theta``, neither gated nor bounded,
    ``NormedAttentionLayer``), ``"plain-attn"`` (grouped-query
    attention with neither q/k norm, gate, rotation nor window,
    ``GroupedAttentionLayer``), ``"mamba"`` (a state-space mixer: a
    selective scan behind a causal convolution, two kinds of per-row
    state, ``conf.layers_ssm.MambaMixerLayer``, its sizes in ``mamba``),
    ``"short-conv"`` (a gated short convolution, one kind of per-row
    state, ``conf.layers_ssm.ShortConvLayer``, its sizes in
    ``shortconv``),
    ``"gated-deltanet"`` (gated delta-rule linear attention behind a
    causal convolution, two kinds of per-row state,
    ``conf.layers_delta.GatedDeltaNetLayer``, its sizes in ``delta``) or
    ``"mla"`` (latent attention: one latent vector a position cached,
    ``conf.layers_delta.LatentAttentionLayer``, its sizes in ``mla``).
    Scaled token embedding, then per
    layer ``h = x + c Mixer(RMSNorm(x))``, ``x' = h + c FFN(RMSNorm(h))``
    with a gated feed-forward and ``c = scale_depth / sqrt(depth_for_scale)``,
    a final RMS norm and a head whose logits are divided by
    ``hidden / dim_model_base``, untied unless ``tie_head`` (the head is
    then the embedding's matrix). No position embedding: the lightning and
    window layers rotate, the others take order from causality alone or
    from the state-space layers around them.

    ``ffn_types[i]`` is ``"dense"`` (default) or ``"moe"``: dropless routed
    experts beside a shared one (``conf.layers_moe.RoutedExpertsLayer``,
    its sizes in ``moe``). ``post_norms`` puts an RMS norm on each
    branch's OUTPUT as well: ``h = x + c Norm(Mixer(Norm(x)))``, four
    norms a layer. ``zero_centred_norms``: every norm's gain is ``1 + w``
    (``RMSNormLayer(zero_centred=True)``); ``swiglu_limit``: every SwiGLU
    clamped (``ops.routed_experts.swiglu``).

    ``layer_indices`` gives each built layer its index among
    ``n_layers_total`` (a served slice of a deeper model keeps its
    layers' own decays); ``sparse`` holds ``BlockSparseAttentionLayer``'s
    selection sizes. ``weight_dtype`` / ``cache_dtype`` are the matrices'
    and the KV caches' types; the recurrent state is float32."""

    MIXERS = ("lightning-attn", "minicpm4", "window-attn", "full-attn",
              "plain-attn", "mamba", "gated-deltanet", "mla", "rope-attn",
              "short-conv")

    def __init__(self, vocab_size: int, hidden: int, ffn_dim: int,
                 mixer_types, n_heads: int, head_dim: int,
                 n_kv_heads: int, lightning_heads: int = 0,
                 lightning_head_dim: int = 0, layer_indices=None,
                 n_layers_total: int = 0, depth_for_scale: int = 0,
                 scale_emb: float = 1.0, scale_depth: float = 1.0,
                 dim_model_base: int = 0, rope_theta: float = 10000.0,
                 eps: float = 1e-6, sparse: dict | None = None,
                 max_len: int = 4096, weight_dtype: str = "",
                 cache_dtype: str = "", seed: int = 123,
                 updater: IUpdater | None = None, window: int = 0,
                 ffn_types=None, moe: dict | None = None,
                 post_norms: bool = False, mamba: dict | None = None,
                 tie_head: bool = False, delta: dict | None = None,
                 mla: dict | None = None, zero_centred_norms: bool = False,
                 swiglu_limit: float = 0.0, shortconv: dict | None = None):
        self.mixer_types = list(mixer_types)
        unknown = sorted(set(self.mixer_types) - set(self.MIXERS))
        if unknown:
            raise ValueError(f"unknown mixer types {unknown}; have "
                             f"{list(self.MIXERS)}")
        n = len(self.mixer_types)
        self.vocab_size, self.hidden, self.ffn_dim = vocab_size, hidden, ffn_dim
        self.n_heads, self.head_dim, self.n_kv_heads = (n_heads, head_dim,
                                                        n_kv_heads)
        self.lightning_heads = lightning_heads or n_heads
        self.lightning_head_dim = lightning_head_dim or head_dim
        self.layer_indices = list(layer_indices or range(n))
        if len(self.layer_indices) != n:
            raise ValueError("layer_indices needs one index a layer")
        self.n_layers_total = n_layers_total or n
        self.residual_scale = scale_depth / (depth_for_scale
                                             or self.n_layers_total) ** 0.5
        self.scale_emb = scale_emb
        self.logit_scale = (dim_model_base / hidden if dim_model_base
                            else 1.0)
        self.rope_theta, self.eps = rope_theta, eps
        self.sparse = dict(sparse or {})
        self.window = window
        self.ffn_types = list(ffn_types or ["dense"] * n)
        if len(self.ffn_types) != n or set(self.ffn_types) - {"dense", "moe"}:
            raise ValueError("ffn_types needs 'dense' or 'moe' for each "
                             "layer")
        self.moe = dict(moe or {})
        self.post_norms = post_norms
        self.mamba = dict(mamba or {})
        self.delta, self.mla = dict(delta or {}), dict(mla or {})
        self.shortconv = dict(shortconv or {})
        self.zero_centred_norms = zero_centred_norms
        self.swiglu_limit = swiglu_limit
        self.tie_head = tie_head
        self.max_len = max_len
        self.weight_dtype, self.cache_dtype = weight_dtype, cache_dtype
        self.seed = seed
        self.updater = updater or Adam(learning_rate=1e-3)

    def conf(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.layers_hybrid import (
            BlockSparseAttentionLayer,
            GatedAttentionLayer,
            GatedFeedForwardLayer,
            GroupedAttentionLayer,
            LightningAttentionLayer,
            LMHeadLayer,
            NormedAttentionLayer,
            ResidualAddVertex,
            RMSNormLayer,
            ScaledEmbeddingLayer,
        )
        from deeplearning4j_tpu.conf.layers_delta import (
            GatedDeltaNetLayer,
            LatentAttentionLayer,
        )
        from deeplearning4j_tpu.conf.layers_moe import RoutedExpertsLayer
        from deeplearning4j_tpu.conf.layers_ssm import (
            MambaMixerLayer,
            ShortConvLayer,
        )

        e, wd, c = self.hidden, self.weight_dtype, self.residual_scale
        # the clamp and the zero-centred gain only where asked for: every
        # other model's layers keep their defaults
        limit = {"swiglu_limit": self.swiglu_limit} if self.swiglu_limit \
            else {}

        def norm():
            if self.zero_centred_norms:
                return RMSNormLayer(eps=self.eps, zero_centred=True)
            return RMSNormLayer(eps=self.eps)

        def branch(name):
            """The vertex a residual sum takes: the branch's own output,
            or its RMS norm."""
            if not self.post_norms:
                return name
            g.add_layer(f"{name}_norm", norm(), name)
            return f"{name}_norm"

        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(self.updater)
             .weight_init(WeightInit.XAVIER)
             .graph_builder()
             .add_inputs("input")
             .set_input_types(InputType.recurrent(1, timesteps=self.max_len)))
        g.add_layer("embed", ScaledEmbeddingLayer(
            n_in=self.vocab_size, n_out=e, scale=self.scale_emb,
            weight_dtype=wd), "input")
        prev = "embed"
        for i, kind in enumerate(self.mixer_types):
            g.add_layer(f"b{i}_norm1", norm(), prev)
            if kind == "lightning-attn":
                mixer = LightningAttentionLayer(
                    n_out=e, n_heads=self.lightning_heads,
                    head_size=self.lightning_head_dim,
                    layer_index=self.layer_indices[i],
                    n_layers_total=self.n_layers_total,
                    rope_theta=self.rope_theta, eps=self.eps, out_scale=c,
                    weight_dtype=wd)
            elif kind == "minicpm4":
                mixer = BlockSparseAttentionLayer(
                    n_out=e, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_size=self.head_dim,
                    eps=self.eps, out_scale=c, weight_dtype=wd,
                    cache_dtype=self.cache_dtype, **self.sparse)
            elif kind == "mamba":
                mixer = MambaMixerLayer(n_out=e, eps=self.eps, out_scale=c,
                                        weight_dtype=wd, **self.mamba)
            elif kind == "gated-deltanet":
                mixer = GatedDeltaNetLayer(n_out=e, eps=self.eps, out_scale=c,
                                           weight_dtype=wd, **self.delta)
            elif kind == "mla":
                mixer = LatentAttentionLayer(
                    n_out=e, eps=self.eps, out_scale=c, weight_dtype=wd,
                    cache_dtype=self.cache_dtype, **self.mla)
            elif kind == "short-conv":
                mixer = ShortConvLayer(n_out=e, out_scale=c, weight_dtype=wd,
                                       **self.shortconv)
            elif kind == "rope-attn":
                mixer = NormedAttentionLayer(
                    n_out=e, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_size=self.head_dim,
                    rope_theta=self.rope_theta, eps=self.eps, out_scale=c,
                    weight_dtype=wd, cache_dtype=self.cache_dtype)
            elif kind == "plain-attn":
                mixer = GroupedAttentionLayer(
                    n_out=e, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_size=self.head_dim,
                    eps=self.eps, out_scale=c, weight_dtype=wd,
                    cache_dtype=self.cache_dtype)
            else:
                windowed = kind == "window-attn"
                mixer = GatedAttentionLayer(
                    n_out=e, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_size=self.head_dim,
                    window=self.window if windowed else 0,
                    rope_theta=self.rope_theta if windowed else 0.0,
                    eps=self.eps, out_scale=c, weight_dtype=wd,
                    cache_dtype=self.cache_dtype)
            g.add_layer(f"b{i}_mix", mixer, f"b{i}_norm1")
            g.add_vertex(f"b{i}_res1", ResidualAddVertex(),
                         prev, branch(f"b{i}_mix"))
            g.add_layer(f"b{i}_norm2", norm(), f"b{i}_res1")
            if self.ffn_types[i] == "moe":
                ffn = RoutedExpertsLayer(n_out=e, out_scale=c,
                                         weight_dtype=wd, **self.moe, **limit)
            else:
                ffn = GatedFeedForwardLayer(n_out=e, n_hidden=self.ffn_dim,
                                            out_scale=c, weight_dtype=wd,
                                            **limit)
            g.add_layer(f"b{i}_ffn", ffn, f"b{i}_norm2")
            g.add_vertex(f"b{i}_res2", ResidualAddVertex(),
                         f"b{i}_res1", branch(f"b{i}_ffn"))
            prev = f"b{i}_res2"
        g.add_layer("final_norm", norm(), prev)
        g.add_layer("output", LMHeadLayer(
            n_out=self.vocab_size, activation=Activation.SOFTMAX,
            loss_fn=LossMCXENT(), logit_scale=self.logit_scale,
            weight_dtype=wd, tied_to="embed" if self.tie_head else ""),
            "final_norm")
        g.set_outputs("output")
        return g.build()

    def decoder(self, net=None, **kw):
        """The serving front (``nn.decoding.TransformerDecoder``): each
        layer's kind of per-row state in one donated pytree. ``cache_dtype``
        defaults to this model's."""
        from deeplearning4j_tpu.nn.decoding import TransformerDecoder

        kw.setdefault("max_len", self.max_len)
        if self.cache_dtype:
            kw.setdefault("cache_dtype", self.cache_dtype)
        return TransformerDecoder(net if net is not None else self.init(),
                                  **kw)
