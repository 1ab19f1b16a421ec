"""Layer-to-kernel routing + envelope planning.

``maybe_forward(layer, ...)`` is the single dispatch point the model
forward passes call when ``conf.use_kernels`` is on: it inspects the
layer (exact forward, not a subclass override), derives the concrete
:class:`registry.MatmulEnvelope` from the traced shapes, and asks the
registry for a TUNED selection. Anything short of a tuned, envelope-
covered, elementwise-activation match returns ``None`` — the caller
runs the stock layer forward, bit-identical to ``use_kernels=False``.

Routed classes:

- ``DenseLayer`` (2-D input, elementwise activation) and 1x1
  ``ConvolutionLayer`` (a 1x1 conv IS a matmul over [B*H*W, Cin]) →
  ``matmul_bias_act``;
- ``FusedConvBN1x1`` in train mode → ``conv_bn_act`` (matmul + fused
  per-channel statistics; normalize/activation stay in XLA), sharing
  ``_bn_running_update`` / ``_bn_normalize`` with the layer so the
  semantics cannot diverge;
- ``SelfAttentionLayer`` → ``flash_attention``: the route re-enters the
  layer's OWN forward with ``use_kernels=True`` so only the
  softmax(QK^T)V core is swapped (dropout / projections / activation /
  mask-zeroing stay single-sourced in the layer).

The serving prefill routes through the functional twin
:func:`maybe_flash_attention`, called from inside
``SelfAttentionLayer.cache_prefill`` when the decoder passes
``use_kernels=True``. The decode step does NOT come through here: it
runs ``ops.attention.bounded_decode_attention``, which puts the paged
kernel on the path wherever the program is lowered for a TPU, at a page
worked out from the shape (the registry keeps
``PagedDecodeAttentionKernel`` for the smoke's Mosaic check).
:func:`decoder_envelopes` / :func:`autotune_decoder` plan and tune the
prompt-bucket flash envelopes.

Selection happens at TRACE time (shapes are static under jit), so a
routed executable bakes exactly one tuned layout — which is why the
step keys carry the registry's tuning digest: a retune means a new
trace, never a silently stale kernel.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from deeplearning4j_tpu.kernels.registry import (
    REGISTRY,
    AttentionEnvelope,
    MatmulEnvelope,
)


def backend() -> str:
    """The Pallas execution mode for this process, decided from
    ``jax.default_backend()`` alone: ``"tpu"`` (real Mosaic lowering) or
    ``"interpret"`` (the Pallas interpreter — CPU containers). On a TPU
    backend a kernel Mosaic refuses is a compile error carrying the
    compiler's message, never a quiet run in the interpreter."""
    import jax

    return "tpu" if jax.default_backend() == "tpu" else "interpret"


# every Activation is elementwise except softmax (normalizes over the
# feature axis — cannot run per-tile in the epilogue)
_NON_ELEMENTWISE = frozenset({"softmax"})


def _elementwise(act) -> bool:
    return act.value not in _NON_ELEMENTWISE


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return int(v[0]), int(v[1])
    return int(v), int(v)


def _env(m: int, k: int, n: int, dtype, act: str = "identity",
         mode: Optional[str] = None) -> MatmulEnvelope:
    return MatmulEnvelope(m=int(m), k=int(k), n=int(n), dtype=str(dtype),
                          backend=mode or backend(), act=act)


def _attn_env(b: int, h: int, tq: int, tk: int, d: int, dtype,
              causal: bool, masked: bool,
              mode: Optional[str] = None) -> AttentionEnvelope:
    return AttentionEnvelope(b=int(b), h=int(h), tq=int(tq), tk=int(tk),
                             d=int(d), dtype=str(dtype),
                             backend=mode or backend(),
                             causal=bool(causal), masked=bool(masked))


# --------------------------------------------------------------------------
# per-layer routes (each returns (y, new_state) or None = stock XLA)
# --------------------------------------------------------------------------

def _record_selected(kernel_id: str, env) -> None:
    from deeplearning4j_tpu import telemetry

    telemetry.record_kernel_selected(kernel_id, env.shape_bucket)
    telemetry.record_tuning_cache(REGISTRY.tuning.hits,
                                  REGISTRY.tuning.entries())


def _route_dense(layer, params, state, x, train, rng):
    from deeplearning4j_tpu.conf.layers import DenseLayer

    if type(layer).forward is not DenseLayer.forward:
        return None  # a subclass with its own forward: never reroute it
    if x.ndim != 2 or not _elementwise(layer.activation):
        return None
    m, k = x.shape
    sel = REGISTRY.select("matmul_bias_act",
                          _env(m, k, layer.n_out, x.dtype,
                               act=layer.activation.value))
    if sel is None:
        return None
    import jax.numpy as jnp

    x = layer._dropout_input(x, train, rng)
    w = params["W"]
    b = params["b"] if layer.has_bias else jnp.zeros((layer.n_out,),
                                                     x.dtype)
    y = sel.kernel.build(sel.env, sel.tiling)(x, w, b)
    _record_selected("matmul_bias_act", sel.env)
    return y, state


def _route_conv1x1(layer, params, state, x, train, rng):
    from deeplearning4j_tpu.conf.layers_cnn import (
        ConvolutionLayer,
        ConvolutionMode,
    )

    if type(layer).forward is not ConvolutionLayer.forward:
        return None
    if x.ndim != 4 or not _elementwise(layer.activation):
        return None
    if _pair(layer.kernel_size) != (1, 1) or _pair(layer.dilation) != (1, 1):
        return None
    # a 1x1 conv reads no neighborhood, so explicit padding changes the
    # output (zero-rows appear) — only pad-free geometries are a pure
    # matmul. SAME/stride s samples positions 0, s, 2s, ... exactly.
    if (layer.convolution_mode is not ConvolutionMode.SAME
            and _pair(layer.padding) != (0, 0)):
        return None
    sh, sw = _pair(layer.stride)
    b_, h, wd, cin = x.shape
    h_o, w_o = -(-h // sh), -(-wd // sw)
    m = b_ * h_o * w_o
    sel = REGISTRY.select("matmul_bias_act",
                          _env(m, cin, layer.n_out, x.dtype,
                               act=layer.activation.value))
    if sel is None:
        return None
    import jax.numpy as jnp

    # dropout BEFORE the stride subsample — the stock forward masks the
    # FULL input, so the bernoulli draw must see the same shape (a
    # post-slice mask would be a different stream for the same rng)
    x = layer._dropout_input(x, train, rng)
    xs = x[:, ::sh, ::sw, :] if (sh, sw) != (1, 1) else x
    w2 = params["W"].reshape(cin, layer.n_out)
    b = params["b"] if layer.has_bias else jnp.zeros((layer.n_out,),
                                                     x.dtype)
    y2 = sel.kernel.build(sel.env, sel.tiling)(xs.reshape(m, cin), w2, b)
    _record_selected("matmul_bias_act", sel.env)
    return y2.reshape(b_, h_o, w_o, layer.n_out), state


def _route_quant_dense(layer, params, state, x, train, rng):
    from deeplearning4j_tpu.conf.layers_quant import (
        QuantizedDenseLayer,
        quantize_input,
    )

    if type(layer).forward is not QuantizedDenseLayer.forward:
        return None
    if x.ndim != 2 or not _elementwise(layer.activation):
        return None
    m, k = x.shape
    sel = REGISTRY.select("matmul_bias_act_int8",
                          _env(m, k, layer.n_out, "int8",
                               act=layer.activation.value))
    if sel is None:
        return None
    # the round/clip/cast stays in XLA (it fuses into the surrounding
    # program); the kernel receives the already-int8 activations
    xq = quantize_input(x, params["xs"], params["xz"])
    y = sel.kernel.build(sel.env, sel.tiling)(xq, params["Wq"],
                                              params["scale"], params["b"])
    _record_selected("matmul_bias_act_int8", sel.env)
    return y.astype(x.dtype), state


def _route_quant_conv1x1(layer, params, state, x, train, rng):
    from deeplearning4j_tpu.conf.layers_quant import (
        QuantizedConv1x1Layer,
        quantize_input,
    )

    if type(layer).forward is not QuantizedConv1x1Layer.forward:
        return None
    if x.ndim != 4 or not _elementwise(layer.activation):
        return None
    sh, sw = _pair(layer.stride)
    b_, h, wd, cin = x.shape
    h_o, w_o = -(-h // sh), -(-wd // sw)
    m = b_ * h_o * w_o
    sel = REGISTRY.select("matmul_bias_act_int8",
                          _env(m, cin, layer.n_out, "int8",
                               act=layer.activation.value))
    if sel is None:
        return None
    xs = x[:, ::sh, ::sw, :] if (sh, sw) != (1, 1) else x
    xq = quantize_input(xs.reshape(m, cin), params["xs"], params["xz"])
    y2 = sel.kernel.build(sel.env, sel.tiling)(xq, params["Wq"],
                                               params["scale"], params["b"])
    _record_selected("matmul_bias_act_int8", sel.env)
    return y2.reshape(b_, h_o, w_o, layer.n_out).astype(x.dtype), state


def _route_fused_conv_bn(layer, params, state, x, train, rng):
    from deeplearning4j_tpu.conf.layers_cnn import (
        FusedConvBN1x1,
        _bn_normalize,
        _bn_running_update,
    )

    if type(layer).forward is not FusedConvBN1x1.forward:
        return None
    if not train or x.ndim != 4:
        return None  # eval mode reads running stats: no statistics pass
    sh, sw = _pair(layer.stride)
    b_, h, wd, cin = (x[:, ::sh, ::sw, :].shape if (sh, sw) != (1, 1)
                      else x.shape)
    m = b_ * h * wd
    sel = REGISTRY.select("conv_bn_act", _env(m, cin, layer.n_out, x.dtype))
    if sel is None:
        return None
    import jax.numpy as jnp

    # EXACTLY the layer's train-mode kernel path, with the registry's
    # tuned tiling instead of ops/conv_fused's fixed one; the BN pieces
    # are the layer module's own helpers so semantics cannot diverge
    x = layer._dropout_input(x, train, rng)
    xs = x[:, ::sh, ::sw, :] if (sh, sw) != (1, 1) else x
    sdt = state["mean"].dtype
    y2, s, q = sel.kernel.build(sel.env, sel.tiling)(
        xs.reshape(m, cin), params["W"].reshape(cin, layer.n_out))
    y = y2.reshape(b_, h, wd, layer.n_out)
    mean = (s / m).astype(sdt)
    var = jnp.maximum((q / m).astype(sdt) - mean * mean, 0.0)
    new_state = _bn_running_update(state, mean, var, layer.decay)
    xhat = _bn_normalize(y.astype(sdt), mean, var, layer.eps,
                         params["gamma"].astype(sdt),
                         params["beta"].astype(sdt))
    _record_selected("conv_bn_act", sel.env)
    return layer.activation.apply(xhat).astype(x.dtype), new_state


def maybe_flash_attention(q, k, v, key_mask=None, causal=False):
    """Route head-split ``[B, H, T, D]`` attention through the tuned
    flash kernel, or return ``None`` for the stock tier (untuned
    envelope, unsupported shape). Selection happens
    at trace time, so the caller's executable bakes one tuned
    ``(block_q, block_k)`` layout."""
    b, h, tq, d = q.shape
    env = _attn_env(b, h, tq, k.shape[2], d, q.dtype, causal=causal,
                    masked=key_mask is not None)
    sel = REGISTRY.select("flash_attention", env)
    if sel is None:
        return None
    out = sel.kernel.build(sel.env, sel.tiling)(q, k, v, key_mask)
    _record_selected("flash_attention", sel.env)
    return out


def _route_self_attention(layer, params, state, x, train, rng, mask):
    from deeplearning4j_tpu.conf.layers_attention import SelfAttentionLayer

    if type(layer).forward is not SelfAttentionLayer.forward:
        return None
    if x.ndim != 3 or layer.attention_impl not in ("auto", "flash"):
        return None
    b, t, e = x.shape
    h = layer.n_heads if layer.project_input else 1
    env = _attn_env(b, h, t, t, layer._head_size(e), x.dtype,
                    causal=layer.causal, masked=mask is not None)
    if REGISTRY.select("flash_attention", env) is None:
        return None
    # the layer's OWN forward with the kernel core swapped in — the
    # dropout / projection / activation / mask-zeroing semantics stay
    # single-sourced (the inner route re-derives this same envelope)
    return layer.forward(params, state, x, train=train, rng=rng,
                         mask=mask, use_kernels=True)


def maybe_forward(layer, params, state, x, train=False, rng=None, **kw):
    """Run ``layer`` through a tuned registry kernel, or return ``None``
    for the stock path. ``kw`` beyond SelfAttentionLayer's ``mask``
    never routes."""
    from deeplearning4j_tpu.conf.layers import DenseLayer
    from deeplearning4j_tpu.conf.layers_attention import SelfAttentionLayer
    from deeplearning4j_tpu.conf.layers_cnn import (
        ConvolutionLayer,
        FusedConvBN1x1,
    )
    from deeplearning4j_tpu.conf.layers_quant import (
        QuantizedConv1x1Layer,
        QuantizedDenseLayer,
    )

    if isinstance(layer, SelfAttentionLayer):
        mask = kw.pop("mask", None)
        if kw:
            return None
        return _route_self_attention(layer, params, state, x, train, rng,
                                     mask)
    if kw:
        return None
    if isinstance(layer, QuantizedDenseLayer):
        return _route_quant_dense(layer, params, state, x, train, rng)
    if isinstance(layer, QuantizedConv1x1Layer):
        return _route_quant_conv1x1(layer, params, state, x, train, rng)
    if isinstance(layer, FusedConvBN1x1):
        return _route_fused_conv_bn(layer, params, state, x, train, rng)
    if isinstance(layer, ConvolutionLayer):
        return _route_conv1x1(layer, params, state, x, train, rng)
    if isinstance(layer, DenseLayer):
        return _route_dense(layer, params, state, x, train, rng)
    return None


def maybe_vertex_forward(vertex, params, state, xs, train=False, rng=None,
                         **kw):
    """Graph-side dispatch: route a single-input ``LayerVertex``'s
    wrapped layer (applying its preprocessor first, exactly as
    ``LayerVertex.forward`` does). None = run the stock vertex forward
    (an unrouted preprocessor application here is dead code XLA
    eliminates). A feature ``mask`` rides through only for
    SelfAttentionLayer (the one routed class that consumes it)."""
    mask = kw.pop("mask", None)
    if kw:
        return None
    layer = getattr(vertex, "layer", None)
    if layer is None or len(xs) != 1:
        return None
    if mask is not None:
        from deeplearning4j_tpu.conf.layers_attention import (
            SelfAttentionLayer,
        )

        if not isinstance(layer, SelfAttentionLayer):
            return None
    x = xs[0]
    pre = getattr(vertex, "preprocessor", None)
    if pre is not None:
        x, _ = pre.forward({}, {}, x, train=train, rng=None)
    mkw = {"mask": mask} if mask is not None else {}
    return maybe_forward(layer, params, state, x, train=train, rng=rng,
                         **mkw)


# --------------------------------------------------------------------------
# envelope planning + whole-model autotune
# --------------------------------------------------------------------------

def _layer_envelope(layer, itype, batch: int, dtype,
                    mode: Optional[str]) -> Optional[Tuple[str, object]]:
    """The ``(kernel_id, envelope)`` a routable layer at this input
    type/batch would select against, or None — the static-shape twin of
    the ``_route_*`` checks (same qualifiers, conf-derived geometry)."""
    from deeplearning4j_tpu.conf import inputs as it
    from deeplearning4j_tpu.conf.layers import DenseLayer
    from deeplearning4j_tpu.conf.layers_attention import SelfAttentionLayer
    from deeplearning4j_tpu.conf.layers_cnn import (
        ConvolutionLayer,
        ConvolutionMode,
        FusedConvBN1x1,
    )
    from deeplearning4j_tpu.conf.layers_quant import (
        QuantizedConv1x1Layer,
        QuantizedDenseLayer,
    )

    if isinstance(layer, QuantizedDenseLayer) \
            and type(layer).forward is QuantizedDenseLayer.forward \
            and _elementwise(layer.activation):
        try:
            from deeplearning4j_tpu.conf.layers import _as_ff_size

            k = _as_ff_size(itype)
        except ValueError:
            return None
        return ("matmul_bias_act_int8",
                _env(batch, k, layer.n_out, "int8",
                     act=layer.activation.value, mode=mode))
    if isinstance(layer, QuantizedConv1x1Layer) \
            and type(layer).forward is QuantizedConv1x1Layer.forward \
            and isinstance(itype, it.Convolutional) \
            and _elementwise(layer.activation):
        sh, sw = _pair(layer.stride)
        m = batch * (-(-itype.height // sh)) * (-(-itype.width // sw))
        return ("matmul_bias_act_int8",
                _env(m, itype.channels, layer.n_out, "int8",
                     act=layer.activation.value, mode=mode))
    if isinstance(layer, SelfAttentionLayer) \
            and type(layer).forward is SelfAttentionLayer.forward \
            and isinstance(itype, it.Recurrent) \
            and itype.timesteps and itype.timesteps > 0 \
            and layer.attention_impl in ("auto", "flash"):
        h = layer.n_heads if layer.project_input else 1
        t = itype.timesteps
        # masked=False: the planned fit envelope is the no-feature-mask
        # path; a masked fit derives its own envelope at trace time
        return ("flash_attention",
                _attn_env(batch, h, t, t, layer._head_size(itype.size),
                          dtype, causal=layer.causal, masked=False,
                          mode=mode))
    if isinstance(layer, FusedConvBN1x1) \
            and type(layer).forward is FusedConvBN1x1.forward \
            and isinstance(itype, it.Convolutional):
        sh, sw = _pair(layer.stride)
        m = batch * (-(-itype.height // sh)) * (-(-itype.width // sw))
        return ("conv_bn_act",
                _env(m, itype.channels, layer.n_out, dtype, mode=mode))
    if isinstance(layer, ConvolutionLayer) \
            and type(layer).forward is ConvolutionLayer.forward \
            and isinstance(itype, it.Convolutional) \
            and _pair(layer.kernel_size) == (1, 1) \
            and _pair(layer.dilation) == (1, 1) \
            and (layer.convolution_mode is ConvolutionMode.SAME
                 or _pair(layer.padding) == (0, 0)) \
            and _elementwise(layer.activation):
        sh, sw = _pair(layer.stride)
        m = batch * (-(-itype.height // sh)) * (-(-itype.width // sw))
        return ("matmul_bias_act",
                _env(m, itype.channels, layer.n_out, dtype,
                     act=layer.activation.value, mode=mode))
    if isinstance(layer, DenseLayer) \
            and type(layer).forward is DenseLayer.forward \
            and not isinstance(itype, it.Recurrent) \
            and _elementwise(layer.activation):
        try:
            from deeplearning4j_tpu.conf.layers import _as_ff_size

            k = _as_ff_size(itype)
        except ValueError:
            return None
        return ("matmul_bias_act",
                _env(batch, k, layer.n_out, dtype,
                     act=layer.activation.value, mode=mode))
    return None


def plan_envelopes(conf, batch: int,
                   mode: Optional[str] = None) -> List[Tuple[str, object]]:
    """The ``(kernel_id, envelope)`` list a ``use_kernels`` fit of this
    conf at ``batch`` would try to route — what :func:`autotune_model`
    tunes. Derived from the conf's static shape chain, so it needs no
    params or data. Accepts a MultiLayerConfiguration (layer chain) or
    a ComputationGraphConfiguration (DAG walk over its LayerVertex
    specs, preprocessors applied)."""
    dtype = getattr(conf, "compute_dtype", None) or conf.dtype
    out: List[Tuple[str, object]] = []
    seen = set()

    def add(pair):
        if pair is None:
            return
        kid, env = pair
        if (kid, env.key) not in seen:
            seen.add((kid, env.key))
            out.append((kid, env))

    if hasattr(conf, "vertices"):  # ComputationGraphConfiguration
        types = conf.vertex_output_types()
        vmap = conf.vertex_map()
        inputs_t = dict(zip(conf.network_inputs, conf.input_types))
        for name in conf.topo_order():
            spec = vmap[name]
            layer = getattr(spec.vertex, "layer", None)
            if layer is None or len(spec.inputs) != 1:
                continue
            src = spec.inputs[0]
            itype = inputs_t.get(src, types.get(src))
            pre = getattr(spec.vertex, "preprocessor", None)
            if pre is not None and itype is not None:
                itype = pre.output_type(itype)
            if itype is not None:
                add(_layer_envelope(layer, itype, batch, dtype, mode))
    else:
        for layer, itype in zip(conf.layers, conf.input_types()):
            add(_layer_envelope(layer, itype, batch, dtype, mode))
    return out


def autotune_model(conf, batch: int, retune: bool = False,
                   **autotune_kw) -> List[object]:
    """Autotune every routable envelope of a model conf (MLN chain or
    graph DAG) at one batch size (already-tuned envelopes are skipped
    unless ``retune``). Returns the :class:`tuner.AutotuneResult` list;
    after this, a ``use_kernels`` fit at ``batch`` routes every planned
    layer."""
    from deeplearning4j_tpu.kernels import tuner as tuner_mod

    results = []
    for kid, env in plan_envelopes(conf, batch):
        kernel = REGISTRY.get(kid)
        if kernel is None or not kernel.supports(env):
            continue
        if not retune \
                and REGISTRY.tuning.winner(kid, env.key) is not None:
            continue
        results.append(tuner_mod.autotune(kernel, env, **autotune_kw))
    return results


def decoder_envelopes(decoder,
                      mode: Optional[str] = None
                      ) -> List[Tuple[str, object]]:
    """The attention ``(kernel_id, envelope)`` list a ``use_kernels``
    :class:`nn.decoding.TransformerDecoder` routes: one flash envelope
    per (prompt bucket, join width) — cold prefill always attends under
    the prompt-length key mask, so those envelopes are ``masked=True``.
    (The decode step chooses its kernel by platform and shape:
    ``ops.attention.bounded_decode_attention``.) Derived from the
    decoder's ladders and attention geometry; needs no params or
    traffic."""
    out: List[Tuple[str, object]] = []
    seen = set()

    def add(kid, env):
        if (kid, env.key) not in seen:
            seen.add((kid, env.key))
            out.append((kid, env))

    dtype = decoder._dtype
    geoms = set()
    for name, n_in in decoder._attn.items():
        layer = decoder._layer(name)
        geoms.add((layer.n_heads, layer._head_size(n_in)))
    for h, d in sorted(geoms):
        for tp in decoder.prompt_ladder:
            for bp in decoder.join_ladder:
                add("flash_attention",
                    _attn_env(bp, h, tp, tp, d, dtype, causal=True,
                              masked=True, mode=mode))
    return out


def autotune_decoder(decoder, retune: bool = False,
                     **autotune_kw) -> List[object]:
    """Autotune every attention envelope a ``use_kernels`` decoder would
    route (flash prefill per prompt/join bucket pair). Run BEFORE
    ``warm_all``: selection is baked at trace time, so executables
    compiled before tuning keep the stock core until their key's digest
    token changes."""
    from deeplearning4j_tpu.kernels import tuner as tuner_mod

    results = []
    for kid, env in decoder_envelopes(decoder):
        kernel = REGISTRY.get(kid)
        if kernel is None or not kernel.supports(env):
            continue
        if not retune \
                and REGISTRY.tuning.winner(kid, env.key) is not None:
            continue
        results.append(tuner_mod.autotune(kernel, env, **autotune_kw))
    return results
