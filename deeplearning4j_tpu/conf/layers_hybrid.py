"""Layers of a sparse/linear hybrid decoder: RMS norm, a scaled token
embedding, a gated (SwiGLU) feed-forward, a scaled language-model head,
and the two mixers: lightning linear attention (a fixed-size recurrent
state a row) and block-sparse attention with grouped KV heads and a
compressed-key cache.

No reference counterpart (the reference reaches Transformers only through
SameDiff); DSL-visible like every other layer, and built into a stack by
``zoo.graphs.HybridDecoderLM``.

Types. A layer's matrices are created in its ``weight_dtype`` (the net's
dtype when empty); norm gains are float32. Every matrix product rounds
its left operand to the matrix's type and accumulates in float32; norms,
softmax, the rotation, the decay and the recurrent state are float32;
what a layer hands on is float32.

The per-layer cache interface (``nn.decoding`` walks it; ``docs/serving.md``):

- ``cache_init(batch, length, n_in, dtype)``: the layer's per-row state
  for ``batch`` rows and a bucket of ``length`` positions, zeros.
  ``dtype`` is the decoder's default; a layer's own ``cache_dtype`` /
  ``state_dtype`` wins.
- ``cache_prefill(params, x, key_mask, dtype)``: a whole prompt bucket
  from an empty cache -> ``(y, block)``, ``block`` shaped like ``cache_init`` at
  the prompt bucket's length.
- ``cache_join(cache, block, rows, length)``: write prefilled rows WHOLE
  (nothing of a row's last tenant is left).
- ``cache_step(params, x, cache, positions, active)``: one token ->
  ``(y, cache, counts)``, ``counts`` a dict of per-row int32 the decoder
  sums over the active rows.
- ``cache_grow(cache, length)``, ``cache_release(cache, keep)``.
- ``cache_kinds``: cache leaf -> the kind of state it is (``kv``,
  ``kv_ring``, ``compressed_keys``, ``recurrent``, ``conv_window``: a
  state-space or delta-rule mixer's two, a short convolution's one,
  ``conf/layers_ssm.py``, ``conf/layers_delta.py``; ``latent``: one latent
  vector a position, ``conf/layers_delta.py``); ``cache_counters``:
  the names of the counts ``cache_step`` returns.

A layer WITHOUT per-row state that still couples the rows of a batch (the
routed experts of ``conf/layers_moe.py`` group tokens by expert) has
``forward_live(params, x, live) -> (y, counts)`` and ``live_counters``
instead: ``nn.decoding`` tells it which tokens are live (the active rows
of a decode step, the prompt's real positions) and sums its ``counts``,
one scalar a call, into the decode window's counters.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.graph import GraphVertex
from deeplearning4j_tpu.conf.layers import (
    BaseLayer,
    EmbeddingSequenceLayer,
    OutputLayer,
    _as_ff_size,
)
from deeplearning4j_tpu.ops import block_sparse, cache_update
from deeplearning4j_tpu.ops.attention import (
    bounded_decode_attention,
    grouped_causal_attention,
    window_ring_attention,
    window_ring_block,
    window_ring_update,
)
from deeplearning4j_tpu.ops.block_sparse import SparseSpec
from deeplearning4j_tpu.ops.linear_attention import (
    decay_slopes,
    linear_attention_chunked,
    linear_attention_step,
)
from deeplearning4j_tpu.ops.routed_experts import swiglu


def _wdtype(name: str, default):
    return jnp.dtype(name) if name else jnp.dtype(default)


def _dot(x, w):
    """``x @ w``: ``x`` rounded to the matrix's type, float32 out."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def rotate(x, positions, theta: float):
    """Rotary positions, the half-split form: ``x: [..., heads, d]``,
    ``positions: [...]``; pair ``i`` is ``(x[i], x[i + d/2])``, its angle
    ``position * theta^(-2i/d)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    angle = positions[..., None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _join_rows(cache, block, rows, length=None):
    """``block``'s rows written whole into ``cache`` at ``rows`` (a row
    index past the cache is padding and dropped); a block shorter than
    the cache along axis 1 is zero-padded to ``length`` first."""
    if length is not None and block.shape[1] != length:
        block = jnp.pad(block, ((0, 0), (0, length - block.shape[1]))
                        + ((0, 0),) * (block.ndim - 2))
    return cache.at[rows].set(block.astype(cache.dtype), mode="drop")


GATED_TOKEN_SPAN = 2048     # positions GatedAttentionLayer projects at a time


def _token_spans(t: int, span: int):
    """``(count, length)``: a sequence of ``t`` positions goes through a
    mixer ``span`` at a time, so that a long prompt's float32 projections
    are never all alive at once; whole when it is short or no multiple."""
    return (t // span, span) if t > span and t % span == 0 else (1, t)


def _split_spans(a, n: int, span: int):
    """``[batch, n * span, ...]`` -> ``[n, batch, span, ...]``: what a scan
    over the spans takes; :func:`_merge_spans` undoes it."""
    return jnp.swapaxes(a.reshape((a.shape[0], n, span) + a.shape[2:]), 0, 1)


def _merge_spans(a):
    a = jnp.swapaxes(a, 0, 1)
    return a.reshape((a.shape[0], a.shape[1] * a.shape[2]) + a.shape[3:])


def _matrix(layer, key, shape, dtype):
    return layer.weight_init.init(key, shape, shape[0], shape[1], dtype,
                                  layer.distribution)


@serde.register
@dataclasses.dataclass
class ResidualAddVertex(GraphVertex):
    """``x + y`` of a residual stream, MATERIALIZED: an optimization
    barrier keeps the compiler from folding the sum into its consumers.
    Without it XLA re-derives the stream in every consumer from the
    embedding and every earlier layer's output, which keeps all of them
    alive to the end of a prefill: 0.94 GB a layer at a 16 k prompt, 13 GB
    of temporaries for twelve layers against 2 GB with the barrier (the
    prefill compiled for the v5e, PERF.md section 6, PR 29)."""

    def forward(self, params, state, inputs, train=False, rng=None):
        return jax.lax.optimization_barrier(inputs[0] + inputs[1]), state


@serde.register
@dataclasses.dataclass
class RMSNormLayer(BaseLayer):
    """``x / rms(x) * gain`` over the feature axis, float32.
    ``zero_centred``: the parameter is ``w`` and the gain ``1 + w``."""

    scope_class = "norm"

    eps: float = 1e-6
    zero_centred: bool = False

    def init(self, key, input_type, dtype=jnp.float32):
        n = _as_ff_size(input_type)
        return {"gain": jnp.zeros((n,), jnp.float32) if self.zero_centred
                else jnp.ones((n,), jnp.float32)}

    def param_order(self):
        return ["gain"]

    def regularized_param_keys(self):
        return []

    def forward(self, params, state, x, train=False, rng=None):
        gain = params["gain"]
        return rms_norm(x, 1.0 + gain if self.zero_centred else gain,
                        self.eps), state


@serde.register
@dataclasses.dataclass
class ScaledEmbeddingLayer(EmbeddingSequenceLayer):
    """Token embedding times ``scale`` (muP's ``scale_emb``), float32 out
    of a table in ``weight_dtype``."""

    scale: float = 1.0
    weight_dtype: str = ""

    def init(self, key, input_type, dtype=jnp.float32):
        return super().init(key, input_type,
                            _wdtype(self.weight_dtype, dtype))

    def forward(self, params, state, x, train=False, rng=None):
        y = params["W"][x.astype(jnp.int32)].astype(jnp.float32)
        return self.activation.apply(y * self.scale), state


@serde.register
@dataclasses.dataclass
class GatedFeedForwardLayer(BaseLayer):
    """``out_scale * Wd (silu(Wg x) * Wu x)`` (:func:`swiglu`, clamped
    where ``swiglu_limit``). More than ``rows_max`` tokens go through in
    slices of that many, so that a long prompt's hidden activations are
    never all alive at once."""

    n_out: int = 0
    n_hidden: int = 0
    out_scale: float = 1.0
    rows_max: int = 2048
    weight_dtype: str = ""
    swiglu_limit: float = 0.0

    def output_type(self, input_type):
        if isinstance(input_type, it.Recurrent):
            return it.Recurrent(size=self.n_out,
                                timesteps=input_type.timesteps)
        return it.FeedForward(size=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = _as_ff_size(input_type)
        wd = _wdtype(self.weight_dtype, dtype)
        kg, ku, kd = jax.random.split(key, 3)
        return {"Wg": _matrix(self, kg, (n_in, self.n_hidden), wd),
                "Wu": _matrix(self, ku, (n_in, self.n_hidden), wd),
                "Wd": _matrix(self, kd, (self.n_hidden, self.n_out), wd)}

    def param_order(self):
        return ["Wg", "Wu", "Wd"]

    def regularized_param_keys(self):
        return ["Wg", "Wu", "Wd"]

    def forward(self, params, state, x, train=False, rng=None):
        x = self._dropout_input(x, train, rng)

        def ff(u):
            hidden = swiglu(_dot(u, params["Wg"]),
                            lambda: _dot(u, params["Wu"]), self.swiglu_limit)
            return _dot(hidden, params["Wd"]) * self.out_scale

        flat = x.reshape(-1, x.shape[-1])
        n = flat.shape[0]
        if n > self.rows_max and n % self.rows_max == 0:
            y = jax.lax.map(ff, flat.reshape(n // self.rows_max,
                                             self.rows_max, -1))
        else:
            y = ff(flat)
        return y.reshape(x.shape[:-1] + (self.n_out,)), state


@serde.register
@dataclasses.dataclass
class LMHeadLayer(OutputLayer):
    """Vocabulary logits ``logit_scale * (x W)`` (muP's
    ``dim_model_base / hidden``), no bias by default. ``tied_to`` names
    the embedding vertex whose table ``[vocabulary, features]`` IS the
    head (``x E^T``): the layer then has no parameters of its own, the
    graph and the decoder's walk hand it that vertex's
    (``ComputationGraph._params_of``), and a gradient step updates the
    one matrix through both uses."""

    has_bias: bool = False
    logit_scale: float = 1.0
    weight_dtype: str = ""
    tied_to: str = ""

    def init(self, key, input_type, dtype=jnp.float32):
        if self.tied_to:
            return {}
        return super().init(key, input_type,
                            _wdtype(self.weight_dtype, dtype))

    def param_order(self):
        return [] if self.tied_to else super().param_order()

    def regularized_param_keys(self):
        return [] if self.tied_to else super().regularized_param_keys()

    def pre_output(self, params, x):
        w = params["W"]
        if self.tied_to:
            y = jax.lax.dot_general(
                x.astype(w.dtype), w, (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            y = _dot(x, w)
        if self.has_bias:
            y = y + params["b"].astype(jnp.float32)
        return y * self.logit_scale

    def forward(self, params, state, x, train=False, rng=None):
        return self.activation.apply(self.pre_output(params, x)), state


class _GatedMixer(BaseLayer):
    """What the two mixers share: q/k RMS norm with a gain over each
    head's width, the sigmoid output gate, ``out_scale``."""

    def output_type(self, input_type):
        ts = (input_type.timesteps if isinstance(input_type, it.Recurrent)
              else -1)
        return it.Recurrent(size=self.n_out, timesteps=ts)

    def streaming_safe(self) -> bool:
        return False

    def regularized_param_keys(self):
        return ["Wq", "Wk", "Wv", "Wg", "Wo"]

    def _init_matrices(self, key, n_in, kv_width, dtype):
        wd = _wdtype(self.weight_dtype, dtype)
        e = self.n_heads * self.head_size
        ks = jax.random.split(key, 5)
        gain = jnp.ones((self.head_size,), jnp.float32)
        return {"Wq": _matrix(self, ks[0], (n_in, e), wd),
                "Wk": _matrix(self, ks[1], (n_in, kv_width), wd),
                "Wv": _matrix(self, ks[2], (n_in, kv_width), wd),
                "Wg": _matrix(self, ks[3], (n_in, e), wd),
                "Wo": _matrix(self, ks[4], (e, self.n_out), wd),
                "q_norm": gain, "k_norm": gain}

    def _heads(self, params, u, name, n):
        y = _dot(u, params["W" + name]).reshape(
            u.shape[:-1] + (n, self.head_size))
        return (rms_norm(y, params[name + "_norm"], self.eps)
                if name in ("q", "k") else y)

    def _finish(self, params, u, o):
        """``o: [..., heads, d]`` float32 -> the layer's output."""
        gate = jax.nn.sigmoid(_dot(u, params["Wg"]))
        o = o.reshape(o.shape[:-2] + (-1,)) * gate
        return self.activation.apply(_dot(o, params["Wo"]) * self.out_scale)


@serde.register
@dataclasses.dataclass
class LightningAttentionLayer(_GatedMixer):
    """Linear attention with a per-head decay (``ops/linear_attention``):
    q/k RMS norm, rotary positions, ``S_t = lambda S_{t-1} + k_t^T v_t``,
    ``o_t = (q_t / sqrt(d)) S_t``, an RMS norm of each head's output, the
    sigmoid gate, ``Wo``. Its cache is the state alone, ``[rows, heads,
    d, d]`` in ``state_dtype``, whatever the bucket."""

    scope_class = "attn.lightning"

    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    layer_index: int = 0        # this layer's index among n_layers_total:
    n_layers_total: int = 1     # together they set the decay's slopes
    rope_theta: float = 10000.0     # 0: no rotation
    eps: float = 1e-6
    out_scale: float = 1.0
    chunk: int = 256            # the chunked recurrence's chunk
    token_span: int = 2048      # positions projected at a time
    weight_dtype: str = ""
    state_dtype: str = "float32"

    uses_mask = True
    has_carry = True
    cache_kinds = {"state": "recurrent"}
    cache_counters = ("recurrent_state_updates",)

    def init(self, key, input_type, dtype=jnp.float32):
        p = self._init_matrices(key, _as_ff_size(input_type),
                                self.n_heads * self.head_size, dtype)
        p["o_norm"] = jnp.ones((self.head_size,), jnp.float32)
        return p

    def param_order(self):
        return ["Wq", "Wk", "Wv", "Wg", "Wo", "q_norm", "k_norm", "o_norm"]

    def _log_decay(self):
        return -jnp.asarray(decay_slopes(self.n_heads, self.layer_index,
                                         self.n_layers_total))

    def _qkv(self, params, u, positions):
        q = self._heads(params, u, "q", self.n_heads)
        k = self._heads(params, u, "k", self.n_heads)
        v = self._heads(params, u, "v", self.n_heads)
        if self.rope_theta:
            q = rotate(q, positions, self.rope_theta)
            k = rotate(k, positions, self.rope_theta)
        return q / math.sqrt(self.head_size), k, v

    def _out(self, params, u, o):
        return self._finish(params, u, rms_norm(o, params["o_norm"],
                                                self.eps))

    def _sequence(self, params, x, mask, state, offset):
        """``x: [batch, time, features]`` from ``state`` (zeros when
        ``None``) at positions ``offset + 0..``: a scan over spans of
        ``token_span`` positions, the state its carry; inside a span the
        chunked recurrence."""
        b, t, _ = x.shape
        n, span = _token_spans(t, self.token_span)
        mask = (jnp.ones((b, t), jnp.float32) if mask is None
                else jnp.asarray(mask, jnp.float32))
        if state is None:
            state = jnp.zeros((b, self.n_heads, self.head_size,
                               self.head_size), jnp.float32)

        def body(s, xs):
            xc, mc, start = xs
            positions = offset[:, None] + start + jnp.arange(span)
            q, k, v = self._qkv(params, xc, positions)
            o, s = linear_attention_chunked(q, k, v, self._log_decay(), mc,
                                            s, self.chunk)
            return s, self._out(params, xc, o) * mc[:, :, None]

        state, y = jax.lax.scan(body, state, (
            _split_spans(x, n, span), _split_spans(mask, n, span),
            jnp.arange(n) * span))
        return _merge_spans(y), state

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _ = self._sequence(params, x, mask, None,
                              jnp.zeros((x.shape[0],), jnp.int32))
        return y, state

    # --- recurrent carry (tBPTT segments, rnn_time_step) -------------------
    def zero_carry(self, batch, dtype=jnp.float32):
        return {"state": jnp.zeros((batch, self.n_heads, self.head_size,
                                    self.head_size), jnp.float32),
                "seen": jnp.zeros((batch,), jnp.int32)}

    def forward_with_carry(self, params, carry, x, mask=None, train=False,
                           rng=None):
        x = self._dropout_input(x, train, rng)
        y, s = self._sequence(params, x, mask,
                              carry["state"].astype(jnp.float32),
                              carry["seen"])
        seen = carry["seen"] + (x.shape[1] if mask is None else
                                jnp.sum(mask > 0, axis=1).astype(jnp.int32))
        return y, {"state": s, "seen": seen}

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        return {"state": jnp.zeros(
            (batch, self.n_heads, self.head_size, self.head_size),
            _wdtype(self.state_dtype, dtype))}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        y, s = self._sequence(params, x, key_mask, None,
                              jnp.zeros((x.shape[0],), jnp.int32))
        return y, {"state": s.astype(_wdtype(self.state_dtype, dtype))}

    def cache_join(self, cache, block, rows, length):
        return {"state": _join_rows(cache["state"], block["state"], rows)}

    def cache_step(self, params, x, cache, positions, active=None):
        q, k, v = self._qkv(params, x, positions)
        o, s = linear_attention_step(
            q, k, v, cache["state"].astype(jnp.float32), self._log_decay())
        counts = {"recurrent_state_updates": jnp.ones_like(positions)}
        return (self._out(params, x, o),
                {"state": s.astype(cache["state"].dtype)}, counts)

    def cache_grow(self, cache, length):
        return cache

    def cache_release(self, cache, keep):
        return {"state": jnp.where(keep[:, None, None, None],
                                   cache["state"], 0)}


@serde.register
@dataclasses.dataclass
class BlockSparseAttentionLayer(_GatedMixer):
    """Causal softmax attention with grouped KV heads, q/k RMS norm, no
    rotation and a sigmoid output gate; beyond ``dense_len`` positions of
    context each query attends the first blocks, the last ``window``
    positions and the ``topk`` blocks its compressed-key scores choose
    (``ops/block_sparse``). Its cache: keys and values ``[rows, bucket,
    kv_heads * d]`` in ``cache_dtype`` and the compressed keys ``[rows,
    bucket / stride, kv_heads * d]`` in float32 (they feed a discrete
    choice: ``ops/block_sparse``). How many compressed keys of a row are live follows
    from the row's position, so a row's next tenant inherits none."""

    scope_class = "attn.sparse"

    n_out: int = 0
    n_heads: int = 1
    n_kv_heads: int = 1
    head_size: int = 0
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    topk: int = 64
    dense_len: int = 8192
    eps: float = 1e-6
    out_scale: float = 1.0
    q_chunk: int = 128          # queries that select and attend together
    token_span: int = 2048      # positions whose queries are projected at a time
    weight_dtype: str = ""
    cache_dtype: str = ""

    uses_mask = True
    cache_kinds = {"k": "kv", "v": "kv", "ck": "compressed_keys"}
    cache_counters = ("sparse_attended_positions", "sparse_context_positions",
                      "sparse_dense_fallback_queries",
                      "sparse_read_positions")

    def _spec(self) -> SparseSpec:
        return SparseSpec(kernel=self.kernel_size, stride=self.kernel_stride,
                          block=self.block_size, window=self.window_size,
                          init_blocks=self.init_blocks, topk=self.topk,
                          dense_len=self.dense_len)

    def _kv_width(self):
        return self.n_kv_heads * self.head_size

    def init(self, key, input_type, dtype=jnp.float32):
        return self._init_matrices(key, _as_ff_size(input_type),
                                   self._kv_width(), dtype)

    def param_order(self):
        return ["Wq", "Wk", "Wv", "Wg", "Wo", "q_norm", "k_norm"]

    def _kv(self, params, u, dtype):
        """Keys (normed) and values in cache layout ``[..., kv_heads *
        d]`` and the cache's type."""
        flat = u.shape[:-1] + (self._kv_width(),)
        return tuple(self._heads(params, u, name, self.n_kv_heads)
                     .reshape(flat).astype(dtype) for name in ("k", "v"))

    def _sequence(self, params, x, mask, dtype):
        """Keys, values and compressed keys of the whole sequence first,
        then the queries a span of ``token_span`` positions at a time."""
        k, v = self._kv(params, x, dtype)
        spec = self._spec()
        ck = block_sparse.compress_keys(k, spec)
        n, span = _token_spans(x.shape[1], self.token_span)

        def body(xs):
            xc, start = xs
            o = block_sparse.sparse_prefill_attention(
                self._heads(params, xc, "q", self.n_heads), k, v, ck, spec,
                self.n_kv_heads, self.q_chunk, offset=start)
            return self._finish(params, xc, o)

        y = _merge_spans(jax.lax.map(body, (_split_spans(x, n, span),
                                            jnp.arange(n) * span)))
        if mask is not None:
            y = y * jnp.asarray(mask, y.dtype)[:, :, None]
        return y, {"k": k, "v": v, "ck": ck}

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _ = self._sequence(params, x, mask,
                              _wdtype(self.cache_dtype, jnp.float32))
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        dt = _wdtype(self.cache_dtype, dtype)
        kv = (batch, length, self._kv_width())
        ck = (batch, -(-length // self.kernel_stride), self._kv_width())
        return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
                "ck": jnp.zeros(ck, jnp.float32)}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        return self._sequence(params, x, key_mask,
                              _wdtype(self.cache_dtype, dtype))

    def cache_join(self, cache, block, rows, length):
        n_ck = cache["ck"].shape[1]
        return {"k": _join_rows(cache["k"], block["k"], rows, length),
                "v": _join_rows(cache["v"], block["v"], rows, length),
                "ck": _join_rows(cache["ck"], block["ck"], rows, n_ck)}

    def cache_step(self, params, x, cache, positions, active=None):
        spec = self._spec()
        g = self.n_kv_heads
        q = self._heads(params, x, "q", self.n_heads)
        k, v = self._kv(params, x, cache["k"].dtype)
        k_cache = cache_update(cache["k"], k[:, None], positions)
        v_cache = cache_update(cache["v"], v[:, None], positions)
        # the compressed key whose window this token completes, if any
        done = positions + 1 - spec.kernel
        j = jnp.maximum(done, 0) // spec.stride
        window = block_sparse.rows_slice(k_cache, j * spec.stride,
                                         spec.kernel)
        old = block_sparse.rows_slice(cache["ck"], j, 1)
        new = window.astype(jnp.float32).mean(axis=1, keepdims=True)
        complete = (done >= 0) & (done % spec.stride == 0)
        ck_cache = cache_update(
            cache["ck"], jnp.where(complete[:, None, None], new, old), j)
        s_len = k_cache.shape[1]
        dense = positions < spec.dense_len
        context = g * (positions + 1)
        if s_len <= spec.dense_len:
            o = block_sparse.dense_decode_attention(q, k_cache, v_cache,
                                                    positions, g)
            attended = context
            read = jnp.full_like(context, g * s_len)
        else:
            o, attended, read = block_sparse.sparse_decode_attention(
                q, k_cache, v_cache, ck_cache, positions, spec, g)
            wanted = dense if active is None else dense & active
            o_dense = jax.lax.cond(
                jnp.any(wanted),
                lambda: block_sparse.dense_decode_attention(
                    q, k_cache[:, :spec.dense_len],
                    v_cache[:, :spec.dense_len], positions, g),
                lambda: jnp.zeros_like(o))
            o = jnp.where(dense[:, None, None], o_dense, o)
            attended = jnp.where(dense, context, attended)
            # the dense branch, where a row wants it, streams every row's
            # first dense_len positions beside the selection's
            read = read + jnp.where(jnp.any(wanted), g * spec.dense_len, 0)
        counts = {"sparse_attended_positions": attended,
                  "sparse_context_positions": context,
                  "sparse_dense_fallback_queries": dense.astype(jnp.int32),
                  "sparse_read_positions": read}
        return (self._finish(params, x, o),
                {"k": k_cache, "v": v_cache, "ck": ck_cache}, counts)

    def cache_grow(self, cache, length):
        def pad(a, n):
            return jnp.pad(a, ((0, 0), (0, n - a.shape[1]), (0, 0)))

        return {"k": pad(cache["k"], length), "v": pad(cache["v"], length),
                "ck": pad(cache["ck"], -(-length // self.kernel_stride))}

    def cache_release(self, cache, keep):
        return cache


@serde.register
@dataclasses.dataclass
class GatedAttentionLayer(_GatedMixer):
    """Causal softmax attention with grouped KV heads, q/k RMS norm and a
    sigmoid output gate, with two switches a model sets per layer:
    ``rope_theta`` (0: no rotation; order from causality alone) and
    ``window`` (0: every earlier position; ``w``: query ``t`` sees keys
    ``t - w < j <= t``). A full layer's cache is keys and values
    ``[rows, bucket, kv_heads * d]``; a window layer's is a RING of
    ``window`` slots a row whatever the bucket (``ops/attention.py``):
    ``cache_join`` writes the last ``window`` positions of a prompt, each
    in its slot, ``cache_grow`` has nothing to grow, and ``state_bytes``
    reports it under the kind ``kv_ring``."""

    scope_class = property(
        lambda self: "attn.window" if self.window else "attn.full")

    n_out: int = 0
    n_heads: int = 1
    n_kv_heads: int = 1
    head_size: int = 0
    window: int = 0
    rope_theta: float = 0.0
    eps: float = 1e-6
    out_scale: float = 1.0
    weight_dtype: str = ""
    cache_dtype: str = ""

    uses_mask = True
    cache_counters = ("decode_kv_read_positions",
                      "decode_kv_bucket_positions")

    @property
    def cache_kinds(self):
        kind = "kv_ring" if self.window else "kv"
        return {"k": kind, "v": kind}

    def _kv_width(self):
        return self.n_kv_heads * self.head_size

    def init(self, key, input_type, dtype=jnp.float32):
        return self._init_matrices(key, _as_ff_size(input_type),
                                   self._kv_width(), dtype)

    def param_order(self):
        return ["Wq", "Wk", "Wv", "Wg", "Wo", "q_norm", "k_norm"]

    def _q(self, params, u, positions):
        q = self._heads(params, u, "q", self.n_heads)
        return rotate(q, positions, self.rope_theta) if self.rope_theta else q

    def _kv(self, params, u, positions, dtype):
        """Keys (normed, rotated at their own position) and values in
        cache layout ``[..., kv_heads * d]`` and the cache's type."""
        k = self._heads(params, u, "k", self.n_kv_heads)
        if self.rope_theta:
            k = rotate(k, positions, self.rope_theta)
        v = self._heads(params, u, "v", self.n_kv_heads)
        flat = u.shape[:-1] + (self._kv_width(),)
        return k.reshape(flat).astype(dtype), v.reshape(flat).astype(dtype)

    def _sequence(self, params, x, dtype):
        """Keys and values of the whole sequence first, then the queries a
        span of ``GATED_TOKEN_SPAN`` positions at a time."""
        t = x.shape[1]
        k, v = self._kv(params, x, jnp.arange(t), dtype)
        n, span = _token_spans(t, GATED_TOKEN_SPAN)

        def body(xs):
            xc, start = xs
            with jax.named_scope("attn.window" if self.window
                                 else "attn.full"):
                o = grouped_causal_attention(
                    self._q(params, xc, start + jnp.arange(span)), k, v,
                    self.n_kv_heads, self.window, offset=start)
            return self._finish(params, xc, o)

        y = _merge_spans(jax.lax.map(body, (_split_spans(x, n, span),
                                            jnp.arange(n) * span)))
        return y, k, v

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _, _ = self._sequence(params, x,
                                 _wdtype(self.cache_dtype, jnp.float32))
        if mask is not None:
            y = y * jnp.asarray(mask, y.dtype)[:, :, None]
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        shape = (batch, self.window or length, self._kv_width())
        dt = _wdtype(self.cache_dtype, dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        y, k, v = self._sequence(params, x, _wdtype(self.cache_dtype, dtype))
        lengths = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        if key_mask is not None:
            y = y * jnp.asarray(key_mask, y.dtype)[:, :, None]
            lengths = jnp.sum(key_mask > 0, axis=1).astype(jnp.int32)
        if self.window:
            k = window_ring_block(k, lengths, self.window)
            v = window_ring_block(v, lengths, self.window)
        return y, {"k": k, "v": v}

    def cache_join(self, cache, block, rows, length):
        length = None if self.window else length
        return {n: _join_rows(cache[n], block[n], rows, length)
                for n in ("k", "v")}

    def cache_step(self, params, x, cache, positions, active=None):
        """A full layer's read is bounded per row by ``positions``
        (:func:`bounded_decode_attention`: the paged kernel where the
        program is lowered for a TPU, the masked read of the bucket
        elsewhere); a window layer's ring is read whole and masked.
        ``counts``: per row, the cached positions the step streamed and,
        for a full layer, the positions the bucket holds, for a window
        layer the positions the row's context holds (that ratio is under
        1 only where the window bounds the read)."""
        g = self.n_kv_heads
        q = self._q(params, x, positions)
        k, v = self._kv(params, x, positions, cache["k"].dtype)
        slots = jnp.full_like(positions, cache["k"].shape[1])
        if self.window:
            k_cache = window_ring_update(cache["k"], k[:, None], positions)
            v_cache = window_ring_update(cache["v"], v[:, None], positions)
            with jax.named_scope("attn.window"):
                o = window_ring_attention(q, k_cache, v_cache, positions, g)
            read, held = slots, positions + 1
        else:
            k_cache = cache_update(cache["k"], k[:, None], positions)
            v_cache = cache_update(cache["v"], v[:, None], positions)
            with jax.named_scope("attn.full"):
                o, read = bounded_decode_attention(q, k_cache, v_cache,
                                                   positions, groups=g)
            held = slots
        counts = {"decode_kv_read_positions": read,
                  "decode_kv_bucket_positions": held}
        return (self._finish(params, x, o), {"k": k_cache, "v": v_cache},
                counts)

    def cache_grow(self, cache, length):
        if self.window:
            return cache                    # a ring has nothing to grow
        pad = ((0, 0), (0, length - cache["k"].shape[1]), (0, 0))
        return {n: jnp.pad(cache[n], pad) for n in ("k", "v")}

    def cache_release(self, cache, keep):
        return cache


@serde.register
@dataclasses.dataclass
class GroupedAttentionLayer(GatedAttentionLayer):
    """:class:`GatedAttentionLayer` with neither the q/k norm nor the
    output gate: ``q = Wq u``, ``k = Wk u``, ``v = Wv u``, causal softmax
    attention over grouped KV heads, ``Wo``. The switches (``window``,
    ``rope_theta``) and every cache method are the parent's."""

    def init(self, key, input_type, dtype=jnp.float32):
        wd = _wdtype(self.weight_dtype, dtype)
        n_in, e = _as_ff_size(input_type), self.n_heads * self.head_size
        ks = jax.random.split(key, 4)
        return {"Wq": _matrix(self, ks[0], (n_in, e), wd),
                "Wk": _matrix(self, ks[1], (n_in, self._kv_width()), wd),
                "Wv": _matrix(self, ks[2], (n_in, self._kv_width()), wd),
                "Wo": _matrix(self, ks[3], (e, self.n_out), wd)}

    def param_order(self):
        return ["Wq", "Wk", "Wv", "Wo"]

    def regularized_param_keys(self):
        return ["Wq", "Wk", "Wv", "Wo"]

    def _heads(self, params, u, name, n):
        return _dot(u, params["W" + name]).reshape(
            u.shape[:-1] + (n, self.head_size))

    def _finish(self, params, u, o):
        o = o.reshape(o.shape[:-2] + (-1,))
        return self.activation.apply(_dot(o, params["Wo"]) * self.out_scale)


@serde.register
@dataclasses.dataclass
class NormedAttentionLayer(GroupedAttentionLayer):
    """:class:`GroupedAttentionLayer` with the q/k RMS norm of
    :class:`GatedAttentionLayer` (a gain over each head's width) and no
    output gate: ``q = RMSNorm(Wq u)``, ``k = RMSNorm(Wk u)``, rotated at
    ``rope_theta`` where it is set, ``v = Wv u``, causal softmax attention
    over grouped KV heads, ``Wo``."""

    _heads = _GatedMixer._heads

    def init(self, key, input_type, dtype=jnp.float32):
        gain = jnp.ones((self.head_size,), jnp.float32)
        return {**super().init(key, input_type, dtype), "q_norm": gain,
                "k_norm": gain}

    def param_order(self):
        return super().param_order() + ["q_norm", "k_norm"]
