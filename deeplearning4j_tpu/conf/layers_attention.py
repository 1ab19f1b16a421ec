"""Attention layers.

Reference: ``org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer, RecurrentAttentionLayer}`` and
``org.deeplearning4j.nn.conf.graph.AttentionVertex`` — all built on
``sd.nn.multiHeadDotProductAttention`` (the reference materializes the full
attention matrix per head). TPU-native design: the projections are single
large matmuls on the MXU and the softmax·V core goes through
:func:`deeplearning4j_tpu.ops.dot_product_attention` (``auto``, from the
committed ``bench_attention.py`` measurement: full materialization to
T=1024, the XLA blockwise scan in the moderate band, the Pallas flash
kernel from T=4096 up — the fastest long-T path and the only one that
compiles backward at T=16k; ``attention_impl`` forces a tier).

Weight layout (locked by serializer round-trip tests): ``Wq/Wk/Wv:
[nIn, nHeads*headSize]``, ``Wo: [nHeads*headSize, nOut]``, biases per
projection. With ``project_input=False`` the layer requires ``nHeads == 1``
and applies attention directly (no params), as the reference does.

Sequence data layout is ``[batch, time, features]`` (see layers_rnn.py);
``key_mask`` is the per-timestep features mask ``[batch, time]``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.activations import Activation
from deeplearning4j_tpu.conf.layers import BaseLayer
from deeplearning4j_tpu.ops import (
    bounded_decode_attention,
    cache_update,
    dot_product_attention,
)


def _split_heads(x, nheads):
    b, t, e = x.shape
    return jnp.transpose(x.reshape(b, t, nheads, e // nheads), (0, 2, 1, 3))


def _merge_heads(x):
    b, h, t, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, t, h * d)


def _attn_core(q, k, v, key_mask, causal, impl, train, use_kernels):
    """The softmax(QK^T)V core over head-split ``[B, H, T, D]`` inputs:
    the tuned Pallas flash kernel when ``use_kernels`` finds a registry
    winner for this envelope, else the stock
    :func:`dot_product_attention` tier — an untuned or unsupported
    shape is bit-identical to ``use_kernels=False``."""
    if use_kernels and impl in ("auto", "flash"):
        from deeplearning4j_tpu.kernels import routing as _routing

        o = _routing.maybe_flash_attention(q, k, v, key_mask=key_mask,
                                           causal=causal)
        if o is not None:
            return o
    return dot_product_attention(q, k, v, key_mask=key_mask, causal=causal,
                                 impl=impl, train=train)


def _mha(params, q_in, kv_in, nheads, key_mask, causal=False, impl="auto",
         train=True, use_kernels=False):
    """Projected multi-head attention over [B, T, E] inputs."""
    q = q_in @ params["Wq"] + params["bq"]
    k = kv_in @ params["Wk"] + params["bk"]
    v = kv_in @ params["Wv"] + params["bv"]
    o = _attn_core(_split_heads(q, nheads), _split_heads(k, nheads),
                   _split_heads(v, nheads), key_mask, causal, impl, train,
                   use_kernels)
    return _merge_heads(o) @ params["Wo"] + params["bo"]


def _rnn_size(input_type) -> int:
    if isinstance(input_type, it.Recurrent):
        return input_type.size
    raise ValueError(f"attention layer needs Recurrent input, got {input_type}")


@serde.register
@dataclasses.dataclass
class SelfAttentionLayer(BaseLayer):
    """Self-attention over the sequence (reference ``SelfAttentionLayer``)."""

    scope_class = "attn.mha"

    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0  # 0 → nOut // nHeads
    project_input: bool = True
    causal: bool = False  # TPU extension (reference is always bidirectional)
    attention_impl: str = "auto"  # auto|flash|blockwise|reference

    uses_mask = True

    def streaming_safe(self) -> bool:
        # attention needs the WHOLE sequence; per-segment rnn_time_step
        # calls would attend only within each call's window
        return False

    def _head_size(self, n_in):
        if not self.project_input:
            return n_in
        return self.head_size or (self.n_out // self.n_heads)

    def output_type(self, input_type):
        ts = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        n = self.n_out if self.project_input else _rnn_size_static(input_type)
        return it.Recurrent(size=n, timesteps=ts)

    def init(self, key, input_type, dtype=jnp.float32):
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError("project_input=False requires n_heads == 1 "
                                 "(reference SelfAttentionLayer semantics)")
            return {}
        n_in = _rnn_size(input_type)
        hs = self._head_size(n_in)
        e = self.n_heads * hs
        ks = jax.random.split(key, 4)
        wi = self.weight_init
        return {
            "Wq": wi.init(ks[0], (n_in, e), n_in, e, dtype, self.distribution),
            "Wk": wi.init(ks[1], (n_in, e), n_in, e, dtype, self.distribution),
            "Wv": wi.init(ks[2], (n_in, e), n_in, e, dtype, self.distribution),
            "Wo": wi.init(ks[3], (e, self.n_out), e, self.n_out, dtype,
                          self.distribution),
            "bq": jnp.zeros((e,), dtype), "bk": jnp.zeros((e,), dtype),
            "bv": jnp.zeros((e,), dtype),
            "bo": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def param_order(self):
        if not self.project_input:
            return []
        return ["Wq", "bq", "Wk", "bk", "Wv", "bv", "Wo", "bo"]

    def regularized_param_keys(self):
        return ["Wq", "Wk", "Wv", "Wo"]

    def forward(self, params, state, x, train=False, rng=None, mask=None,
                use_kernels=False):
        x = self._dropout_input(x, train, rng)
        if not self.project_input:
            q = _split_heads(x, 1)
            o = _attn_core(q, q, q, mask, self.causal, self.attention_impl,
                           train, use_kernels)
            y = _merge_heads(o)
        else:
            y = _mha(params, x, x, self.n_heads, mask, self.causal,
                     self.attention_impl, train=train,
                     use_kernels=use_kernels)
        y = self.activation.apply(y)
        if mask is not None:  # masked-out steps emit zeros, as the reference
            y = y * jnp.asarray(mask, y.dtype)[:, :, None]
        return y, state

    # --- KV-cached autoregressive decode (nn.decoding / generation) -------
    #
    # The serving decode path splits the forward into two phases sharing
    # one cache layout — ``k/v: [max_batch, max_len, n_heads * head_size]``,
    # a position's projection as ``x @ Wk`` produces it (why, in
    # ``ops/attention.py``), plus a per-sequence slot count — so a
    # sequence's keys/values are projected exactly once and every later
    # token attends them from the cache instead of re-running the
    # whole-prompt projection.

    def _decode_check(self):
        if not self.project_input:
            raise ValueError("KV-cached decode requires project_input=True")
        if not self.causal:
            raise ValueError("KV-cached decode requires causal=True "
                             "(bidirectional attention cannot stream)")

    def kv_cache_shape(self, batch, length, n_in):
        """Shape of one K or V buffer in cache layout — a cache at
        ``length = max_len``, a prefill or prefix-page block at a prompt
        bucket: ``(batch, length, n_heads * head_size)``."""
        self._decode_check()
        return (batch, length, self.n_heads * self._head_size(n_in))

    # --- the per-layer cache interface (nn.decoding walks it; the other
    # layer kinds and the contract: conf/layers_hybrid.py) ----------------
    cache_kinds = {"k": "kv", "v": "kv"}
    cache_counters = ("decode_kv_read_positions",
                      "decode_kv_bucket_positions")

    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        """Preallocated per-sequence KV buffers for this layer:
        ``{"k","v"}: [batch, length, n_heads * head_size]`` zeros."""
        shape = self.kv_cache_shape(batch, length, n_in)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def cache_prefill(self, params, x, key_mask=None, dtype=None,
                      use_kernels=False):
        """Whole-prompt forward that ALSO returns the projected keys and
        values so the caller can seed a KV cache in one launch.
        ``x: [batch, time, features]``; returns ``(y, {"k", "v"})`` with
        ``k/v: [batch, time, n_heads * head_size]`` (cache layout) and
        ``y`` identical to :meth:`forward` in eval mode (activation and
        mask-zeroing applied). ``use_kernels`` swaps the attention core
        for the tuned flash kernel when this envelope has a winner."""
        self._decode_check()
        q = x @ params["Wq"] + params["bq"]
        k = x @ params["Wk"] + params["bk"]
        v = x @ params["Wv"] + params["bv"]
        o = _attn_core(
            _split_heads(q, self.n_heads), _split_heads(k, self.n_heads),
            _split_heads(v, self.n_heads), key_mask, True,
            self.attention_impl, False, use_kernels)
        y = self.activation.apply(_merge_heads(o) @ params["Wo"]
                                  + params["bo"])
        if key_mask is not None:
            y = y * jnp.asarray(key_mask, y.dtype)[:, :, None]
        return y, {"k": k, "v": v}

    def cache_join(self, cache, block, rows, length):
        """Prefilled rows written whole: the block padded to the bucket,
        rows past the cache dropped."""
        pad = ((0, 0), (0, length - block["k"].shape[1]), (0, 0))
        return {n: cache[n].at[rows].set(jnp.pad(block[n], pad), mode="drop")
                for n in ("k", "v")}

    def cache_step(self, params, x, cache, positions, active=None):
        """One token of causal attention against the KV cache, beside
        what it read. ``x: [batch, features]`` is the new token's
        representation, ``positions: [batch]`` the cache slot it occupies
        (== number of tokens already cached for that row). Projects q/k/v
        for the token, writes k/v into the cache at ``positions`` via
        :func:`cache_update`, attends slots ``0..positions`` inclusive,
        and returns ``(y [batch, features_out], new_cache, counts)``. The
        caller donates the cache buffers into the compiled step so the
        write is in-place (PRG201 audits this). The read is bounded per
        row by ``positions`` (:func:`bounded_decode_attention`: the paged
        kernel where the program is lowered for a TPU and the shape fills
        its tiles, the masked read of the bucket elsewhere). ``counts``:
        per row, the cached positions the attention streamed and the
        positions the bucket holds (their ratio over a window is the
        share of the bucket the bound left; 1 = the bound is off)."""
        self._decode_check()
        b = x.shape[0]
        nh = self.n_heads
        hs = params["Wk"].shape[1] // nh
        q = (x @ params["Wq"] + params["bq"]).reshape(b, nh, hs)
        k_new = (x @ params["Wk"] + params["bk"])[:, None]
        v_new = (x @ params["Wv"] + params["bv"])[:, None]
        k_cache = cache_update(cache["k"], k_new, positions)
        v_cache = cache_update(cache["v"], v_new, positions)
        o, read = bounded_decode_attention(q, k_cache, v_cache, positions)
        y = o.reshape(b, nh * hs) @ params["Wo"] + params["bo"]
        counts = {"decode_kv_read_positions": read,
                  "decode_kv_bucket_positions": jnp.full_like(
                      read, k_cache.shape[1])}
        return (self.activation.apply(y), {"k": k_cache, "v": v_cache},
                counts)

    def cache_grow(self, cache, length):
        pad = ((0, 0), (0, length - cache["k"].shape[1]), (0, 0))
        return {n: jnp.pad(cache[n], pad) for n in ("k", "v")}

    def cache_release(self, cache, keep):
        return cache

    def prefill_suffix(self, params, x, prefix_k, prefix_v, prefix_mask,
                       key_mask=None, use_kernels=False):
        """Prompt-suffix prefill against an already-projected prefix —
        the prefix-cache-hit twin of :meth:`cache_prefill`. ``x: [batch,
        t_suffix, features]`` holds the suffix tokens' representations;
        ``prefix_k/prefix_v: [batch, t_prefix, n_heads * head_size]`` are
        the shared prefix pages in cache layout (padding masked by
        ``prefix_mask: [batch, t_prefix]``). The suffix queries attend
        the concatenation ``[prefix ; suffix]``: with ``Tk = t_prefix +
        t_suffix`` and ``Tq = t_suffix``, the reference causal rule
        ``j <= i + (Tk - Tq)`` makes the whole prefix visible to every
        suffix query while the suffix stays causal within itself —
        exactly the cold-prefill semantics, minus re-projecting the
        prefix. Returns ``(y, k, v)`` with ``k/v`` the SUFFIX blocks only
        (cache layout), ready for the dynamic-offset join scatter."""
        self._decode_check()
        b, t, _ = x.shape
        nh = self.n_heads
        q = x @ params["Wq"] + params["bq"]
        k = x @ params["Wk"] + params["bk"]
        v = x @ params["Wv"] + params["bv"]
        k_full = jnp.concatenate([prefix_k, k], axis=1)
        v_full = jnp.concatenate([prefix_v, v], axis=1)
        if key_mask is None:
            key_mask = jnp.ones((b, t), x.dtype)
        mask = jnp.concatenate(
            [jnp.asarray(prefix_mask, x.dtype),
             jnp.asarray(key_mask, x.dtype)], axis=1)
        # flash handles Tq != Tk via the same off = Tk - Tq causal rule
        o = _attn_core(_split_heads(q, nh), _split_heads(k_full, nh),
                       _split_heads(v_full, nh), mask, True,
                       self.attention_impl, False, use_kernels)
        y = self.activation.apply(_merge_heads(o) @ params["Wo"]
                                  + params["bo"])
        y = y * jnp.asarray(key_mask, y.dtype)[:, :, None]
        return y, k, v


def _rnn_size_static(input_type):
    return input_type.size if isinstance(input_type, it.Recurrent) else 0


@serde.register
@dataclasses.dataclass
class LearnedSelfAttentionLayer(BaseLayer):
    """Attention with ``n_queries`` LEARNED query vectors (reference
    ``LearnedSelfAttentionLayer``) — output is a fixed-length
    ``[batch, n_queries, n_out]`` sequence regardless of input length, so it
    doubles as a sequence-pooling layer. Param ``Q: [n_queries,
    n_heads*head_size]`` holds the queries directly in projected space."""

    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    n_queries: int = 1
    project_input: bool = True
    attention_impl: str = "auto"

    uses_mask = True

    def streaming_safe(self) -> bool:
        # attention needs the WHOLE sequence; per-segment rnn_time_step
        # calls would attend only within each call's window
        return False

    def _dims(self, n_in):
        hs = self.head_size or ((self.n_out if self.project_input else n_in)
                                // self.n_heads)
        return hs, self.n_heads * hs

    def output_type(self, input_type):
        n = self.n_out if self.project_input else _rnn_size_static(input_type)
        return it.Recurrent(size=n, timesteps=self.n_queries)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = _rnn_size(input_type)
        hs, e = self._dims(n_in)
        ks = jax.random.split(key, 4)
        wi = self.weight_init
        p = {"Q": wi.init(ks[3], (self.n_queries, e), e, e, dtype,
                          self.distribution)}
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError("project_input=False requires n_heads == 1")
            if self.head_size and self.head_size != n_in:
                raise ValueError(
                    f"project_input=False: learned queries attend directly "
                    f"over the {n_in}-wide input, so head_size must be "
                    f"{n_in} (or 0 for automatic), got {self.head_size}")
            return p
        p.update({
            "Wk": wi.init(ks[0], (n_in, e), n_in, e, dtype, self.distribution),
            "Wv": wi.init(ks[1], (n_in, e), n_in, e, dtype, self.distribution),
            "Wo": wi.init(ks[2], (e, self.n_out), e, self.n_out, dtype,
                          self.distribution),
            "bk": jnp.zeros((e,), dtype), "bv": jnp.zeros((e,), dtype),
            "bo": jnp.full((self.n_out,), self.bias_init, dtype),
        })
        return p

    def param_order(self):
        if not self.project_input:
            return ["Q"]
        return ["Q", "Wk", "bk", "Wv", "bv", "Wo", "bo"]

    def regularized_param_keys(self):
        return ["Q", "Wk", "Wv", "Wo"] if self.project_input else ["Q"]

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        b = x.shape[0]
        q = jnp.broadcast_to(params["Q"][None], (b,) + params["Q"].shape)
        if self.project_input:
            k = x @ params["Wk"] + params["bk"]
            v = x @ params["Wv"] + params["bv"]
        else:
            k = v = x
        o = dot_product_attention(
            _split_heads(q, self.n_heads), _split_heads(k, self.n_heads),
            _split_heads(v, self.n_heads), key_mask=mask,
            impl=self.attention_impl, train=train)
        y = _merge_heads(o)
        if self.project_input:
            y = y @ params["Wo"] + params["bo"]
        return self.activation.apply(y), state


@serde.register
@dataclasses.dataclass
class RecurrentAttentionLayer(BaseLayer):
    """Recurrent cell with attention over the full input sequence at every
    timestep, query = previous hidden state (reference
    ``RecurrentAttentionLayer``):

        ctx_t = MHA(q = h_{t-1}·Wq, K = x·Wk, V = x·Wv)
        h_t   = act(x_t·W + h_{t-1}·RW + ctx_t·Wc + b)

    Keys/values are projected ONCE outside the scan (one big MXU matmul);
    only the per-step query projection and the [1, T] attention row run
    inside ``lax.scan``."""

    n_out: int = 0
    n_heads: int = 1
    head_size: int = 0
    activation: Activation = Activation.TANH

    uses_mask = True
    has_carry = True

    def streaming_safe(self) -> bool:
        # attention needs the WHOLE sequence; per-segment rnn_time_step
        # calls would attend only within each call's window
        return False

    def _dims(self):
        hs = self.head_size or (self.n_out // self.n_heads)
        return hs, self.n_heads * hs

    def output_type(self, input_type):
        ts = input_type.timesteps if isinstance(input_type, it.Recurrent) else -1
        return it.Recurrent(size=self.n_out, timesteps=ts)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = _rnn_size(input_type)
        hs, e = self._dims()
        ks = jax.random.split(key, 6)
        wi = self.weight_init
        return {
            "W": wi.init(ks[0], (n_in, self.n_out), n_in, self.n_out, dtype,
                         self.distribution),
            "RW": wi.init(ks[1], (self.n_out, self.n_out), self.n_out,
                          self.n_out, dtype, self.distribution),
            "Wq": wi.init(ks[2], (self.n_out, e), self.n_out, e, dtype,
                          self.distribution),
            "Wk": wi.init(ks[3], (n_in, e), n_in, e, dtype, self.distribution),
            "Wv": wi.init(ks[4], (n_in, e), n_in, e, dtype, self.distribution),
            "Wc": wi.init(ks[5], (e, self.n_out), e, self.n_out, dtype,
                          self.distribution),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def param_order(self):
        return ["W", "RW", "Wq", "Wk", "Wv", "Wc", "b"]

    def regularized_param_keys(self):
        return ["W", "RW", "Wq", "Wk", "Wv", "Wc"]

    def zero_carry(self, batch, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def forward_with_carry(self, params, carry, x, mask=None, train=False,
                           rng=None):
        x = self._dropout_input(x, train, rng)
        b, t, _ = x.shape
        hs, e = self._dims()
        nh = self.n_heads
        k = (x @ params["Wk"]).reshape(b, t, nh, hs)
        v = (x @ params["Wv"]).reshape(b, t, nh, hs)
        m = jnp.ones((b, t), x.dtype) if mask is None \
            else jnp.asarray(mask, x.dtype)
        xw = jnp.einsum("btf,fh->bth", x, params["W"]) + params["b"]
        scale = 1.0 / jnp.sqrt(jnp.asarray(hs, x.dtype))

        def step(h, inp):
            xw_t, m_t = inp  # [b, nOut], [b]
            q = (h @ params["Wq"]).reshape(b, nh, hs)
            s = jnp.einsum("bnd,btnd->bnt", q, k) * scale
            s = jnp.where(m[:, None, :] > 0, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bnt,btnd->bnd", p, v).reshape(b, e)
            h_new = self.activation.apply(
                xw_t + h @ params["RW"] + ctx @ params["Wc"])
            h = m_t[:, None] * h_new + (1.0 - m_t[:, None]) * h
            return h, m_t[:, None] * h_new

        h_final, ys = jax.lax.scan(
            step, carry["h"], (jnp.swapaxes(xw, 0, 1), jnp.swapaxes(m, 0, 1)))
        return jnp.swapaxes(ys, 0, 1), {"h": h_final}

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        carry = self.zero_carry(x.shape[0], x.dtype)
        y, _ = self.forward_with_carry(params, carry, x, mask=mask,
                                       train=train, rng=rng)
        return y, state
