"""ComputationGraph configuration: DAG of vertices + GraphBuilder DSL.

Reference: ``org.deeplearning4j.nn.conf.ComputationGraphConfiguration``
(+ ``#graphBuilder`` fluent DSL) and the vertex confs in
``org.deeplearning4j.nn.conf.graph`` (``MergeVertex``, ``ElementWiseVertex``,
``SubsetVertex``, ``ScaleVertex``, ``ShiftVertex``, ``L2NormalizeVertex``,
``StackVertex``, ``UnstackVertex``, ``ReshapeVertex``,
``PreprocessorVertex``, ``LayerVertex``).

TPU-native inversion (SURVEY.md §3.2): the reference walks the topological
order at *runtime*, calling ``GraphVertex#doForward`` per vertex with per-op
JNI dispatch underneath. Here the topological order is walked once at trace
time — every vertex's ``forward`` is a pure jax function, so the whole DAG
(forward + backward + updaters) fuses into ONE compiled XLA program.

Vertex contract (multi-input generalization of ``conf.layers.Layer``):
- ``output_type(input_types: list) -> InputType``
- ``init(key, input_types, dtype) -> params dict``
- ``init_state(input_types, dtype) -> state dict``
- ``forward(params, state, inputs: list, train, rng) -> (y, new_state)``
- ``param_order()`` — canonical flat-params ordering (serializer parity).
"""

from __future__ import annotations

import dataclasses
import enum

import jax
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.layers import (
    CnnToFeedForwardPreProcessor,
    DenseLayer,
    Layer,
)
from deeplearning4j_tpu.conf.multilayer import BackpropType
from deeplearning4j_tpu.conf.updaters import IUpdater, Sgd


@dataclasses.dataclass
class GraphVertex:
    """Base vertex conf (reference ``org.deeplearning4j.nn.conf.graph
    .GraphVertex``)."""

    name: Optional[str] = None

    # the decoder walk's scope for a vertex without a layer
    # (``Layer.scope_class``): such a vertex combines activations
    scope_class = "residual"

    def output_type(self, input_types: List[object]):
        return input_types[0]

    def init(self, key, input_types, dtype=jnp.float32) -> dict:
        return {}

    def init_state(self, input_types, dtype=jnp.float32) -> dict:
        return {}

    def param_order(self) -> List[str]:
        return []

    def regularized_param_keys(self) -> List[str]:
        return []

    def forward(self, params, state, inputs: List, train: bool = False,
                rng=None):
        raise NotImplementedError

    def has_params(self) -> bool:
        return bool(self.param_order())


@serde.register
@dataclasses.dataclass
class LayerVertex(GraphVertex):
    """Wraps a layer conf as a single-input vertex (reference
    ``LayerVertex`` = layer + optional InputPreProcessor)."""

    scope_class = property(lambda self: self.layer.scope_class)

    layer: Optional[Layer] = None
    preprocessor: Optional[Layer] = None

    def _pre(self, input_types):
        t = input_types[0]
        return self.preprocessor.output_type(t) if self.preprocessor else t

    def output_type(self, input_types):
        return self.layer.output_type(self._pre(input_types))

    def init(self, key, input_types, dtype=jnp.float32):
        return self.layer.init(key, self._pre(input_types), dtype)

    def init_state(self, input_types, dtype=jnp.float32):
        return self.layer.init_state(self._pre(input_types), dtype)

    def param_order(self):
        return self.layer.param_order()

    def regularized_param_keys(self):
        return self.layer.regularized_param_keys()

    def forward(self, params, state, inputs, train=False, rng=None,
                mask=None):
        x = inputs[0]
        if self.preprocessor is not None:
            x, _ = self.preprocessor.forward({}, {}, x, train=train, rng=None)
        kw = ({"mask": mask} if mask is not None
              and getattr(self.layer, "uses_mask", False) else {})
        return self.layer.forward(params, state, x, train=train, rng=rng,
                                  **kw)

    # recurrent carry pass-through (tBPTT / stateful inference): a
    # LayerVertex is carry-bearing iff its wrapped layer is — the graph
    # runtime threads {vertex name: carry} across tBPTT segments exactly
    # as MultiLayerNetwork threads {layer idx: carry} (reference:
    # ComputationGraph#rnnUpdateStateWithTBPTTState)
    @property
    def has_carry(self) -> bool:
        return getattr(self.layer, "has_carry", False)

    def zero_carry(self, batch: int, dtype=jnp.float32):
        return self.layer.zero_carry(batch, dtype)

    def forward_with_carry(self, params, carry, inputs, train=False,
                           rng=None, mask=None):
        x = inputs[0]
        if self.preprocessor is not None:
            x, _ = self.preprocessor.forward({}, {}, x, train=train, rng=None)
        kw = ({"mask": mask} if mask is not None
              and getattr(self.layer, "uses_mask", False) else {})
        return self.layer.forward_with_carry(params, carry, x, train=train,
                                             rng=rng, **kw)

    # score hook when wrapping an output layer (reference: output vertices
    # must be LayerVertex over an IOutputLayer)
    def score(self, params, x, labels, mask=None):
        if self.preprocessor is not None:
            x, _ = self.preprocessor.forward({}, {}, x, train=False, rng=None)
        return self.layer.score(params, x, labels, mask)

    def is_output(self) -> bool:
        return hasattr(self.layer, "score")


@serde.register
@dataclasses.dataclass
class AttentionVertex(GraphVertex):
    """Multi-head dot-product attention vertex (reference
    ``org.deeplearning4j.nn.conf.graph.AttentionVertex`` over
    ``sd.nn.multiHeadDotProductAttention``). Inputs: ``[queries, keys,
    values]`` or ``[queries, keys, values, key_mask]`` — all sequences
    ``[batch, time, size]``, mask ``[batch, time_k]``. Projections
    ``Wq/Wk/Wv: [nIn*, nHeads*headSize]``, ``Wo: [nHeads*headSize, nOut]``.
    The attention core dispatches to the Pallas flash kernel on TPU
    (:mod:`deeplearning4j_tpu.ops`)."""

    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    project_input: bool = True
    weight_init: "WeightInit" = None  # set in __post_init__
    attention_impl: str = "auto"
    causal: bool = False
    streaming_window: int = 0
    """> 0 (requires ``causal``): the vertex streams through
    ``rnn_time_step`` — and threads across tBPTT segments — with a
    key/value cache of the most recent ``streaming_window`` steps.
    EXACT causal attention while the streamed history fits the window;
    sliding-window attention beyond it (the round-3 'attention-vertex
    streaming' refusal, closed where the window allows). 0 = whole-
    sequence attention only (streaming refuses, as before)."""

    def __post_init__(self):
        from deeplearning4j_tpu.conf.weights import WeightInit
        if self.weight_init is None:
            self.weight_init = WeightInit.XAVIER
        if self.streaming_window and not self.causal:
            raise ValueError(
                "AttentionVertex: streaming_window requires causal=True "
                "(non-causal attention reads future keys and cannot "
                "stream)")

    def _head_size(self, nq):
        return self.head_size or (self.n_out // self.n_heads)

    def streaming_safe(self) -> bool:
        # whole-sequence attention cannot stream; a causal KV-cache
        # window can (exact while history <= streaming_window)
        return bool(self.causal and self.streaming_window > 0)

    @property
    def has_carry(self):
        return self.streaming_safe()

    def zero_carry(self, batch, dtype=jnp.float32):
        w = int(self.streaming_window)
        e = self.n_heads * (self.head_size or self.n_out // self.n_heads)
        return {"k": jnp.zeros((batch, w, e), dtype),
                "v": jnp.zeros((batch, w, e), dtype),
                "m": jnp.zeros((batch, w), dtype)}

    def forward_with_carry(self, params, carry, inputs, train=False,
                           rng=None):
        """Chunked causal attention over cached + current keys/values:
        query i of the chunk sees every valid cached step plus chunk
        steps <= i; the cache keeps the last ``streaming_window`` steps
        (scores materialize [B, H, Tc, W+Tc] — streaming chunks are
        small by construction)."""
        from deeplearning4j_tpu.conf.layers_attention import (
            _split_heads, _merge_heads)

        q_in, k_in, v_in = inputs[0], inputs[1], inputs[2]
        mask = inputs[3] if len(inputs) > 3 else None
        if mask is not None and mask.ndim == 3:
            mask = mask[:, :, 0]
        if self.project_input:
            q = q_in @ params["Wq"] + params["bq"]
            k = k_in @ params["Wk"] + params["bk"]
            v = v_in @ params["Wv"] + params["bv"]
        else:
            q, k, v = q_in, k_in, v_in
        b, tc, _ = q.shape
        w = int(self.streaming_window)
        cm = carry["m"].astype(q.dtype)
        chunk_m = (jnp.ones((b, tc), q.dtype) if mask is None
                   else mask.astype(q.dtype))
        kcat = jnp.concatenate([carry["k"].astype(k.dtype), k], axis=1)
        vcat = jnp.concatenate([carry["v"].astype(v.dtype), v], axis=1)
        mcat = jnp.concatenate([cm, chunk_m], axis=1)      # [B, W+Tc]
        qh = _split_heads(q, self.n_heads)                 # [B, H, Tc, hs]
        kh = _split_heads(kcat, self.n_heads)
        vh = _split_heads(vcat, self.n_heads)
        hs = qh.shape[-1]
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / jnp.sqrt(
            jnp.asarray(hs, qh.dtype))
        # band: chunk query i sees cached keys (j < W) + chunk j <= i
        j = jnp.arange(w + tc)[None, :]
        i = jnp.arange(tc)[:, None]
        band = (j <= w + i).astype(qh.dtype)               # [Tc, W+Tc]
        vis = band[None, None] * mcat[:, None, None, :]
        scores = jnp.where(vis > 0, scores, -1e30)
        # fully-masked rows (cold cache, masked query) -> zero output
        any_vis = jnp.max(vis, axis=-1, keepdims=True)
        att = jax.nn.softmax(scores, axis=-1) * any_vis
        o = jnp.einsum("bhqk,bhkd->bhqd", att, vh)
        y = _merge_heads(o)
        if self.project_input:
            y = y @ params["Wo"] + params["bo"]
        new_carry = {"k": kcat[:, -w:].astype(carry["k"].dtype),
                     "v": vcat[:, -w:].astype(carry["v"].dtype),
                     "m": mcat[:, -w:].astype(carry["m"].dtype)}
        return y, new_carry

    def output_type(self, input_types):
        tq = input_types[0]
        ts = tq.timesteps if isinstance(tq, it.Recurrent) else -1
        # unprojected attention emits a weighted sum of the VALUES, so the
        # output feature size is the values' size, not the queries'
        n = self.n_out if self.project_input else input_types[2].size
        return it.Recurrent(size=n, timesteps=ts)

    def init(self, key, input_types, dtype=jnp.float32):
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError("project_input=False requires n_heads == 1")
            return {}
        nq, nk, nv = (t.size for t in input_types[:3])
        hs = self._head_size(nq)
        e = self.n_heads * hs
        import jax as _jax
        ks = _jax.random.split(key, 4)
        wi = self.weight_init
        return {
            "Wq": wi.init(ks[0], (nq, e), nq, e, dtype),
            "Wk": wi.init(ks[1], (nk, e), nk, e, dtype),
            "Wv": wi.init(ks[2], (nv, e), nv, e, dtype),
            "Wo": wi.init(ks[3], (e, self.n_out), e, self.n_out, dtype),
            "bq": jnp.zeros((e,), dtype), "bk": jnp.zeros((e,), dtype),
            "bv": jnp.zeros((e,), dtype), "bo": jnp.zeros((self.n_out,), dtype),
        }

    def param_order(self):
        if not self.project_input:
            return []
        return ["Wq", "bq", "Wk", "bk", "Wv", "bv", "Wo", "bo"]

    def regularized_param_keys(self):
        return ["Wq", "Wk", "Wv", "Wo"] if self.project_input else []

    def forward(self, params, state, inputs, train=False, rng=None):
        from deeplearning4j_tpu.conf.layers_attention import (
            _split_heads, _merge_heads)
        from deeplearning4j_tpu.ops import dot_product_attention
        q_in, k_in, v_in = inputs[0], inputs[1], inputs[2]
        mask = inputs[3] if len(inputs) > 3 else None
        if mask is not None and mask.ndim == 3:
            mask = mask[:, :, 0]
        if self.project_input:
            q = q_in @ params["Wq"] + params["bq"]
            k = k_in @ params["Wk"] + params["bk"]
            v = v_in @ params["Wv"] + params["bv"]
        else:
            q, k, v = q_in, k_in, v_in
        o = dot_product_attention(
            _split_heads(q, self.n_heads), _split_heads(k, self.n_heads),
            _split_heads(v, self.n_heads), key_mask=mask,
            causal=self.causal, impl=self.attention_impl, train=train)
        y = _merge_heads(o)
        if self.project_input:
            y = y @ params["Wo"] + params["bo"]
        return y, state


@serde.register_enum
class ElementWiseOp(enum.Enum):
    """Reference ``ElementWiseVertex.Op``."""

    ADD = "add"
    SUBTRACT = "subtract"
    PRODUCT = "product"
    AVERAGE = "average"
    MAX = "max"


@serde.register
@dataclasses.dataclass
class ElementWiseVertex(GraphVertex):
    """Reference ``ElementWiseVertex``: pointwise combine of same-shaped
    inputs (the residual-connection workhorse in ResNet50)."""

    op: ElementWiseOp = ElementWiseOp.ADD

    def forward(self, params, state, inputs, train=False, rng=None):
        y = inputs[0]
        if self.op is ElementWiseOp.ADD:
            for x in inputs[1:]:
                y = y + x
        elif self.op is ElementWiseOp.SUBTRACT:
            if len(inputs) != 2:
                raise ValueError("SUBTRACT requires exactly 2 inputs")
            y = inputs[0] - inputs[1]
        elif self.op is ElementWiseOp.PRODUCT:
            for x in inputs[1:]:
                y = y * x
        elif self.op is ElementWiseOp.AVERAGE:
            y = sum(inputs) / float(len(inputs))
        elif self.op is ElementWiseOp.MAX:
            for x in inputs[1:]:
                y = jnp.maximum(y, x)
        return y, state


@serde.register
@dataclasses.dataclass
class MergeVertex(GraphVertex):
    """Reference ``MergeVertex``: concat along the feature dimension —
    channels for CNN (last axis in NHWC), features for FF/RNN (last axis)."""

    def output_type(self, input_types):
        t0 = input_types[0]
        if isinstance(t0, it.Convolutional):
            return it.Convolutional(t0.height, t0.width,
                                    sum(t.channels for t in input_types))
        if isinstance(t0, it.Recurrent):
            return it.Recurrent(size=sum(t.size for t in input_types),
                                timesteps=t0.timesteps)
        return it.FeedForward(size=sum(t.arity() for t in input_types))

    def forward(self, params, state, inputs, train=False, rng=None):
        return jnp.concatenate(inputs, axis=-1), state


@serde.register
@dataclasses.dataclass
class SubsetVertex(GraphVertex):
    """Reference ``SubsetVertex``: features[from..to] INCLUSIVE (the
    reference's interval convention) along the feature (last) axis."""

    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        t0 = input_types[0]
        if isinstance(t0, it.Convolutional):
            return it.Convolutional(t0.height, t0.width, n)
        if isinstance(t0, it.Recurrent):
            return it.Recurrent(size=n, timesteps=t0.timesteps)
        return it.FeedForward(size=n)

    def forward(self, params, state, inputs, train=False, rng=None):
        return inputs[0][..., self.from_idx:self.to_idx + 1], state


@serde.register
@dataclasses.dataclass
class ScaleVertex(GraphVertex):
    """Reference ``ScaleVertex``: y = scale * x."""

    scale_factor: float = 1.0

    def forward(self, params, state, inputs, train=False, rng=None):
        return inputs[0] * self.scale_factor, state


@serde.register
@dataclasses.dataclass
class ShiftVertex(GraphVertex):
    """Reference ``ShiftVertex``: y = x + shift."""

    shift_factor: float = 0.0

    def forward(self, params, state, inputs, train=False, rng=None):
        return inputs[0] + self.shift_factor, state


@serde.register
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertex):
    """Reference ``L2NormalizeVertex``: x / max(||x||_2, eps) over all
    non-batch dims."""

    eps: float = 1e-8

    def forward(self, params, state, inputs, train=False, rng=None):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))
        return x / jnp.maximum(norm, self.eps), state


@serde.register
@dataclasses.dataclass
class StackVertex(GraphVertex):
    """Reference ``StackVertex``: concat inputs along the BATCH (0) axis —
    the dual of UnstackVertex, used for weight-shared towers."""

    def forward(self, params, state, inputs, train=False, rng=None):
        return jnp.concatenate(inputs, axis=0), state


@serde.register
@dataclasses.dataclass
class UnstackVertex(GraphVertex):
    """Reference ``UnstackVertex``: take slice ``from_idx`` of ``stack_size``
    equal chunks along the batch axis."""

    from_idx: int = 0
    stack_size: int = 1

    def forward(self, params, state, inputs, train=False, rng=None):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_idx * step:(self.from_idx + 1) * step], state


@serde.register
@dataclasses.dataclass
class ReshapeVertex(GraphVertex):
    """Reference ``ReshapeVertex``: reshape non-batch dims (first entry of
    ``new_shape`` is the batch placeholder -1)."""

    new_shape: Tuple[int, ...] = ()

    def output_type(self, input_types):
        s = self.new_shape
        if len(s) == 2:
            return it.FeedForward(size=s[1])
        if len(s) == 3:
            return it.Recurrent(size=s[2], timesteps=s[1])
        if len(s) == 4:
            return it.Convolutional(height=s[1], width=s[2], channels=s[3])
        raise ValueError(f"cannot infer InputType for reshape to {s}")

    def forward(self, params, state, inputs, train=False, rng=None):
        return inputs[0].reshape(self.new_shape), state


@serde.register
@dataclasses.dataclass
class PreprocessorVertex(GraphVertex):
    """Reference ``PreprocessorVertex``: a standalone InputPreProcessor."""

    preprocessor: Optional[Layer] = None

    def output_type(self, input_types):
        return self.preprocessor.output_type(input_types[0])

    def forward(self, params, state, inputs, train=False, rng=None):
        return self.preprocessor.forward({}, {}, inputs[0], train=train,
                                         rng=rng)


@serde.register
@dataclasses.dataclass
class VertexSpec:
    """One named node in the DAG: vertex conf + its input vertex names."""

    name: str = ""
    vertex: Optional[GraphVertex] = None
    inputs: Tuple[str, ...] = ()


@serde.register
@dataclasses.dataclass
class ComputationGraphConfiguration:
    """The serializable DAG definition (reference
    ``ComputationGraphConfiguration``)."""

    network_inputs: Tuple[str, ...] = ()
    network_outputs: Tuple[str, ...] = ()
    vertices: Tuple[VertexSpec, ...] = ()
    input_types: Tuple[object, ...] = ()
    seed: int = 12345
    updater: IUpdater = dataclasses.field(default_factory=Sgd)
    backprop_type: BackpropType = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    dtype: str = "float32"
    # mixed-precision compute dtype (see MultiLayerConfiguration.compute_dtype)
    compute_dtype: Optional[str] = None
    # Pallas kernel-registry routing (see
    # MultiLayerConfiguration.use_kernels; default OFF = unchanged)
    use_kernels: bool = False

    def to_json(self) -> str:
        return serde.to_json(self)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        obj = serde.from_json(s)
        if not isinstance(obj, ComputationGraphConfiguration):
            raise TypeError(f"JSON is a {type(obj).__name__}, "
                            "not ComputationGraphConfiguration")
        return obj

    # --- structure ---------------------------------------------------------
    def vertex_map(self) -> Dict[str, VertexSpec]:
        return {v.name: v for v in self.vertices}

    def topo_order(self) -> List[str]:
        """Topological vertex order (reference
        ``ComputationGraph#topologicalSortOrder``), deterministic: repeated
        scans emitting ready vertices in declaration order."""
        vmap = self.vertex_map()
        for v in self.vertices:
            for src in v.inputs:
                if src not in vmap and src not in self.network_inputs:
                    raise ValueError(
                        f"vertex {v.name!r} references unknown input {src!r}")
        order, done = [], set(self.network_inputs)
        pending = list(self.vertices)
        while pending:
            progressed = False
            remaining = []
            for v in pending:
                if all(src in done for src in v.inputs):
                    order.append(v.name)
                    done.add(v.name)
                    progressed = True
                else:
                    remaining.append(v)
            if not progressed:
                cyc = [v.name for v in remaining]
                raise ValueError(f"graph has a cycle involving {cyc}")
            pending = remaining
        return order

    def vertex_output_types(self) -> Dict[str, object]:
        """Shape-inference pass over the DAG (reference: InputType
        propagation in ``ComputationGraphConfiguration#addPreProcessors``)."""
        if len(self.input_types) != len(self.network_inputs):
            raise ValueError(
                f"{len(self.network_inputs)} network inputs but "
                f"{len(self.input_types)} input types (setInputTypes)")
        types: Dict[str, object] = dict(zip(self.network_inputs,
                                            self.input_types))
        vmap = self.vertex_map()
        for name in self.topo_order():
            spec = vmap[name]
            in_types = [types[src] for src in spec.inputs]
            types[name] = spec.vertex.output_type(in_types)
        return types

    # --- flat-params protocol (util.params duck-typing) --------------------
    def ordered_param_keys(self) -> List[str]:
        return self.topo_order()

    def layer_for_key(self, key: str):
        return self.vertex_map()[key].vertex

    def output_vertices(self) -> List[VertexSpec]:
        vmap = self.vertex_map()
        return [vmap[n] for n in self.network_outputs]


class GraphBuilder:
    """Reference ``ComputationGraphConfiguration.GraphBuilder`` (obtained
    via ``NeuralNetConfiguration.Builder#graphBuilder``)."""

    def __init__(self, base):
        self._base = base  # conf.multilayer.Builder (global defaults)
        self._inputs: List[str] = []
        self._input_types: List[object] = []
        self._specs: List[VertexSpec] = []
        self._outputs: List[str] = []
        self._backprop_type = BackpropType.STANDARD
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_input_types(self, *types) -> "GraphBuilder":
        self._input_types.extend(types)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        self._specs.append(VertexSpec(name=name, vertex=LayerVertex(layer=layer),
                                      inputs=tuple(inputs)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex,
                   *inputs: str) -> "GraphBuilder":
        self._specs.append(VertexSpec(name=name, vertex=vertex,
                                      inputs=tuple(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def backprop_type(self, bp: BackpropType, fwd: int = 20,
                      back: int = 20) -> "GraphBuilder":
        self._backprop_type = bp
        self._tbptt_fwd = fwd
        self._tbptt_back = back
        return self

    def build(self) -> ComputationGraphConfiguration:
        from deeplearning4j_tpu.conf.multilayer import ListBuilder

        specs = []
        for s in self._specs:
            v = s.vertex
            if isinstance(v, LayerVertex):
                layer = ListBuilder._apply_defaults_static(self._base, v.layer)
                v = LayerVertex(layer=layer, preprocessor=v.preprocessor)
            else:
                v = dataclasses.replace(v)
            v.name = s.name
            specs.append(VertexSpec(name=s.name, vertex=v, inputs=s.inputs))
        conf = ComputationGraphConfiguration(
            network_inputs=tuple(self._inputs),
            network_outputs=tuple(self._outputs),
            vertices=tuple(specs),
            input_types=tuple(self._input_types),
            seed=self._base._seed,
            updater=self._base._updater,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            dtype=self._base._dtype,
            compute_dtype=self._base._compute_dtype,
            use_kernels=self._base._use_kernels,
        )
        if self._input_types:
            _insert_graph_preprocessors(conf)
            conf.vertex_output_types()  # validate shape inference end-to-end
        return conf


def _insert_graph_preprocessors(conf: ComputationGraphConfiguration) -> None:
    """Auto-insert CNN->FF flatten preprocessors into LayerVertex where the
    incoming type is Convolutional but the layer is dense-like (reference:
    ``ComputationGraphConfiguration#addPreProcessors``). Mutates vertex
    confs in place (pre-serialization, during build only)."""
    types: Dict[str, object] = dict(zip(conf.network_inputs, conf.input_types))
    vmap = conf.vertex_map()
    for name in conf.topo_order():
        spec = vmap[name]
        v = spec.vertex
        in_types = [types[src] for src in spec.inputs]
        if (isinstance(v, LayerVertex) and v.preprocessor is None
                and in_types and isinstance(in_types[0], it.Convolutional)
                and isinstance(v.layer, DenseLayer)):
            t = in_types[0]
            v.preprocessor = CnnToFeedForwardPreProcessor(
                height=t.height, width=t.width, channels=t.channels)
        types[name] = v.output_type(in_types)
