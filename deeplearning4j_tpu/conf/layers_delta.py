"""Two mixers on the per-layer cache interface of ``conf/layers_hybrid.py``:

- :class:`GatedDeltaNetLayer`, gated delta-rule linear attention
  (``ops/delta_rule.py``): projections into queries, keys, values and an
  output gate, a depthwise causal convolution over the queries', keys' and
  values' channels that keeps its last ``d_conv - 1`` inputs, L2-normed
  queries and keys, a per-head write strength and an input-dependent
  decay, a zero-centred RMS norm of each head's output under a scaled
  sigmoid gate. TWO kinds of per-row state, both float32 and neither
  depending on the bucket: the delta rule's ``[rows, value_heads, d_k,
  d_v]`` (kind ``recurrent``) and the convolution's RING of its last
  ``d_conv - 1`` inputs, ``[rows, (d_conv - 1) * channels]`` (kind
  ``conv_window``; ``conf/layers_ssm.py`` says why a ring, and flat).
- :class:`LatentAttentionLayer`, multi-head latent attention: each position
  is cached as ONE latent vector beside one shared rotated key (kind
  ``latent``, ``[rows, bucket, kv_rank + rope_dim]``). A prompt expands the
  latent into every head's keys and values; a decode step is ABSORBED:
  every head's query is carried into the latent space and read against
  the latent cache as one KV head whose values are its first ``kv_rank``
  columns (``ops.attention.latent_decode_attention``). Rotary positions
  pair neighbouring elements under YaRN's frequencies (:func:`yarn_inverse_frequencies`).

What ``cache_prefill`` owes a RIGHT-padded row (``docs/serving.md``): the
delta rule's state after the row's last REAL token (a padded position
writes nothing and decays nothing), the row's last ``d_conv - 1`` REAL
inputs each in its position's slot, the latent vectors of every position
(a padded one is never attended).

Types as ``conf/layers_hybrid.py``: matrices in ``weight_dtype``, every
product rounds its left operand to the matrix's type and accumulates in
float32; norms, gates, decays, the convolution's taps, softmax and both
delta-rule states float32; the latent cache in ``cache_dtype``. Norm gains
are zero-centred: the parameter is ``w`` and the gain ``1 + w``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf.layers import _as_ff_size
from deeplearning4j_tpu.conf.layers_hybrid import (
    _dot,
    _join_rows,
    _matrix,
    _merge_spans,
    _split_spans,
    _token_spans,
    _wdtype,
    rms_norm,
)
from deeplearning4j_tpu.conf.layers_ssm import (
    _SequenceMixer,
    conv_ring_step,
    conv_span,
    tail_to_ring,
)
from deeplearning4j_tpu.ops import cache_update
from deeplearning4j_tpu.ops.attention import NEG_INF, latent_decode_attention
from deeplearning4j_tpu.ops.delta_rule import (
    delta_rule_chunked,
    delta_rule_step,
    l2_normalize,
)

DELTA_TOKEN_SPAN = 2048     # positions GatedDeltaNetLayer projects at a time
LATENT_QUERY_CHUNK = 128    # a prompt's queries LatentAttentionLayer attends at a time


@serde.register
@dataclasses.dataclass
class GatedDeltaNetLayer(_SequenceMixer):
    """``[q | k | v | z] = W_qkvz u`` (q, k: ``key_heads x d_k``; v, z:
    ``value_heads x d_v``), ``[a | b] = W_ab u``; ``[q | k | v] <-
    silu(causal depthwise convolution of d_conv taps, no bias)``; ``q^ =
    l2norm(q) / sqrt(d_k)``, ``k^ = l2norm(k)``, value head ``h`` reads key
    head ``h / (value_heads / key_heads)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) softplus(a + dt_bias)``; the delta rule; ``y = W_o
    [ZCRMSNorm(o_h) * gate_scale * sigmoid(z_h)]``. The mask of a sequence
    is taken to be a RIGHT padding."""

    scope_class = "attn.delta"

    n_out: int = 0
    key_heads: int = 1
    value_heads: int = 1
    key_dim: int = 0
    value_dim: int = 0
    d_conv: int = 4
    gate_scale: float = 1.0
    eps: float = 1e-6
    out_scale: float = 1.0
    chunk: int = 64             # the chunked (WY) form's chunk
    weight_dtype: str = ""

    uses_mask = True
    cache_kinds = {"state": "recurrent", "conv": "conv_window"}
    cache_counters = ("delta_state_updates",)

    def _channels(self):
        """The convolved channels: q, k, v."""
        return (2 * self.key_heads * self.key_dim
                + self.value_heads * self.value_dim)

    def init(self, key, input_type, dtype=jnp.float32):
        """Matrices by the layer's initializer; the decay's constants as
        the published gated delta rule initialises them: ``A`` uniform in
        [1, 16], ``dt_bias`` the inverse softplus of steps log-uniform in
        [1e-3, 1e-1]; taps uniform in +-1/sqrt(d_conv)."""
        n_in, hv = _as_ff_size(input_type), self.value_heads
        wd = _wdtype(self.weight_dtype, dtype)
        c, z = self._channels(), hv * self.value_dim
        ks = jax.random.split(key, 6)
        step = jnp.exp(jax.random.uniform(
            ks[3], (hv,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return {"W_qkvz": _matrix(self, ks[0], (n_in, c + z), wd),
                "W_ab": _matrix(self, ks[1], (n_in, 2 * hv), wd),
                "conv_w": jax.random.uniform(
                    ks[2], (self.d_conv, c), jnp.float32, -1.0, 1.0)
                / self.d_conv ** 0.5,
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (hv,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "o_norm": jnp.zeros((self.value_dim,), jnp.float32),
                "W_o": _matrix(self, ks[5], (z, self.n_out), wd)}

    def param_order(self):
        return ["W_qkvz", "W_ab", "conv_w", "A_log", "dt_bias", "o_norm",
                "W_o"]

    def regularized_param_keys(self):
        return ["W_qkvz", "W_ab", "W_o"]

    # --- the mathematics -------------------------------------------------------
    def _project(self, params, u):
        """``u`` -> ``(x, z, ab)``: the convolution's input, the gate, the
        write strength's and the decay's pre-activations."""
        with jax.named_scope("delta.in_proj"):
            qkvz = _dot(u, params["W_qkvz"])
            ab = _dot(u, params["W_ab"])
        c = self._channels()
        return qkvz[..., :c], qkvz[..., c:], ab

    def _heads(self, xc):
        """The convolved channels -> ``(q^, k^, v)`` by value head."""
        hk, hv, dk = self.key_heads, self.value_heads, self.key_dim
        lead = xc.shape[:-1]
        q = xc[..., :hk * dk].reshape(lead + (hk, dk))
        k = xc[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
        v = xc[..., 2 * hk * dk:].reshape(lead + (hv, self.value_dim))
        rep = hv // hk
        q = jnp.repeat(l2_normalize(q) / math.sqrt(dk), rep, axis=-2)
        return q, jnp.repeat(l2_normalize(k), rep, axis=-2), v

    def _gates(self, params, ab):
        """``(g, beta)`` a value head, float32."""
        hv = self.value_heads
        g = -jnp.exp(params["A_log"]) * jax.nn.softplus(
            ab[..., :hv] + params["dt_bias"])
        return g, jax.nn.sigmoid(ab[..., hv:])

    def _finish(self, params, o, z):
        """``o: [..., value_heads, d_v]`` float32 -> the layer's output."""
        with jax.named_scope("delta.out_proj"):
            z = z.reshape(o.shape)
            y = (rms_norm(o, 1.0 + params["o_norm"], self.eps)
                 * (self.gate_scale * jax.nn.sigmoid(z)))
            return self.activation.apply(
                _dot(y.reshape(o.shape[:-2] + (-1,)), params["W_o"])
                * self.out_scale)

    def _sequence(self, params, u, mask, state, conv):
        """``u: [batch, time, features]`` from the delta rule's ``state``
        and the convolution's last inputs ``conv: [batch, d_conv - 1,
        channels]``, oldest first: a ``lax.scan`` over spans of
        ``DELTA_TOKEN_SPAN`` positions, both states its carry; inside a
        span the chunked form. Returns ``(y, state, conv)``."""
        b, t, _ = u.shape
        n, span = _token_spans(t, DELTA_TOKEN_SPAN)
        mask = (jnp.ones((b, t), jnp.float32) if mask is None
                else (jnp.asarray(mask) > 0).astype(jnp.float32))

        def body(carry, xs):
            s, tail = carry
            uc, mc = xs
            x, z, ab = self._project(params, uc)
            with jax.named_scope("delta.conv"):
                xc, tail = conv_span(tail, x, params["conv_w"], mc)
            q, kk, v = self._heads(xc)
            g, beta = self._gates(params, ab)
            with jax.named_scope("delta.chunked"):
                o, s = delta_rule_chunked(q, kk, v, g, beta, mc, s,
                                          self.chunk)
            return (s, tail), self._finish(params, o, z) * mc[:, :, None]

        (state, conv), y = jax.lax.scan(
            body, (state, conv),
            (_split_spans(u, n, span), _split_spans(mask, n, span)))
        return _merge_spans(y), state, conv

    def _zeros(self, batch):
        return (jnp.zeros((batch, self.value_heads, self.key_dim,
                           self.value_dim), jnp.float32),
                jnp.zeros((batch, self.d_conv - 1, self._channels()),
                          jnp.float32))

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _, _ = self._sequence(params, x, mask, *self._zeros(x.shape[0]))
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        state, conv = self._zeros(batch)
        return {"state": state, "conv": conv.reshape(batch, -1)}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        b = x.shape[0]
        y, state, tail = self._sequence(params, x, key_mask, *self._zeros(b))
        lengths = (jnp.full((b,), x.shape[1], jnp.int32) if key_mask is None
                   else jnp.sum(key_mask > 0, axis=1).astype(jnp.int32))
        return y, {"state": state, "conv": tail_to_ring(tail, lengths)}

    def cache_join(self, cache, block, rows, length):
        return {n: _join_rows(cache[n], block[n], rows)
                for n in ("state", "conv")}

    def cache_step(self, params, x, cache, positions, active=None):
        with jax.named_scope("delta.step"):
            xi, z, ab = self._project(params, x)
            xc, ring = conv_ring_step(cache["conv"], xi, params["conv_w"],
                                      positions)
            q, kk, v = self._heads(xc)
            g, beta = self._gates(params, ab)
            o, state = delta_rule_step(q, kk, v, jnp.exp(g), beta,
                                       cache["state"])
        counts = {"delta_state_updates": jnp.ones_like(positions)}
        return (self._finish(params, o, z),
                {"state": state, "conv": ring}, counts)

    def cache_grow(self, cache, length):
        return cache

    def cache_release(self, cache, keep):
        return {"state": jnp.where(keep[:, None, None, None],
                                   cache["state"], 0),
                "conv": jnp.where(keep[:, None], cache["conv"], 0)}


def yarn_inverse_frequencies(dim: int, theta: float, factor: float = 1.0,
                             original: int = 0, beta_fast: float = 32.0,
                             beta_slow: float = 1.0) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of YaRN: pairs that turn more
    than ``beta_fast`` times over the ``original`` context keep
    ``theta^(-2i/dim)``, pairs that turn fewer than ``beta_slow`` times are
    divided by ``factor``, and a linear ramp joins the two (``factor`` 1:
    plain rotary frequencies)."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1.0:
        return base.astype(np.float32)

    def turns(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns(beta_fast)), 0)
    hi = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (base / factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def rotate_pairs(x, positions, inv_freq):
    """Rotary positions over NEIGHBOURING pairs ``(x[2i], x[2i + 1])``,
    the angle ``position * inv_freq[i]``: ``x: [..., heads, d]``,
    ``positions: [...]``. The result lists the pairs' first elements, then
    their second ones (a fixed order of the lanes, the same for queries and
    keys, so no product changes)."""
    angle = (positions[..., None, None].astype(jnp.float32)
             * jnp.asarray(inv_freq))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@serde.register
@dataclasses.dataclass
class LatentAttentionLayer(_SequenceMixer):
    """``c_q = ZCRMSNorm(W_dq u)``, ``[q_nope | q_rope] = W_uq c_q`` a head;
    ``[c_kv | k_rope] = W_dkv u``, ``c_kv <- ZCRMSNorm(c_kv)``; ``q_rope``
    and ``k_rope`` (one, shared by the heads) rotated by :func:`rotate_pairs`
    at YaRN's frequencies; ``[k_nope | v] = W_ukv c_kv`` a head; causal
    softmax attention of ``[q_nope | q_rope]`` over ``[k_nope | k_rope]``
    scaled by ``mscale^2 / sqrt(nope_dim + rope_dim)`` (``mscale = 0.1
    mscale_all_dim ln(factor) + 1``, 1 without YaRN); the output times
    ``sigmoid(W_g u)``; ``W_o``. The cache holds ``[c_kv |
    k_rope]`` in ``cache_dtype``, one vector a position (zeros after it to
    whole 128-lane tiles: ``LANES``)."""

    scope_class = "attn.latent"
    # a cache row's lanes: the latent and the rotated key, zeros after them
    # to whole 128-lane tiles. At 576 the compiler kept the decode loop's
    # cache in another layout than the program's argument and copied all
    # 1.2 GB of it in and out of every decode window (compiled for the
    # v5e); the padded row costs no more memory than the tiles it fills
    LANES = 128

    n_out: int = 0
    n_heads: int = 1
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    value_dim: int = 0
    rope_theta: float = 10000.0
    yarn_factor: float = 1.0
    yarn_original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    eps: float = 1e-6
    out_scale: float = 1.0
    weight_dtype: str = ""
    cache_dtype: str = ""

    uses_mask = True
    cache_kinds = {"latent": "latent"}
    cache_counters = ("decode_kv_read_positions",
                      "decode_kv_bucket_positions")

    def _width(self):
        """The cache row: ``kv_rank + rope_dim`` in whole 128-lane tiles."""
        w = self.kv_rank + self.rope_dim
        return -(-w // self.LANES) * self.LANES

    def _scale(self):
        m = (0.1 * self.mscale_all_dim * math.log(self.yarn_factor) + 1.0
             if self.yarn_factor > 1.0 else 1.0)
        return m * m / math.sqrt(self.nope_dim + self.rope_dim)

    def _inv_freq(self):
        return yarn_inverse_frequencies(
            self.rope_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original, self.beta_fast, self.beta_slow)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in, h = _as_ff_size(input_type), self.n_heads
        wd = _wdtype(self.weight_dtype, dtype)
        ks = jax.random.split(key, 6)
        p = {"W_dq": _matrix(self, ks[0], (n_in, self.q_rank), wd),
             "q_norm": jnp.zeros((self.q_rank,), jnp.float32),
             "W_uq": _matrix(self, ks[1], (
                 self.q_rank, h * (self.nope_dim + self.rope_dim)), wd),
             "W_dkv": _matrix(self, ks[2],
                              (n_in, self.kv_rank + self.rope_dim), wd),
             "kv_norm": jnp.zeros((self.kv_rank,), jnp.float32),
             "W_ukv": _matrix(self, ks[3], (
                 self.kv_rank, h * (self.nope_dim + self.value_dim)), wd),
             "W_o": _matrix(self, ks[4], (h * self.value_dim, self.n_out),
                            wd),
             "W_g": _matrix(self, ks[5], (n_in, h * self.value_dim), wd)}
        return p

    def param_order(self):
        return ["W_dq", "q_norm", "W_uq", "W_dkv", "kv_norm", "W_ukv", "W_o",
                "W_g"]

    def regularized_param_keys(self):
        return [k for k in self.param_order() if not k.endswith("_norm")]

    # --- the mathematics -------------------------------------------------------
    def _queries(self, params, u, positions):
        """``(q_nope, q_rope)``, ``[..., heads, nope_dim | rope_dim]``, the
        second rotated."""
        c = rms_norm(_dot(u, params["W_dq"]), 1.0 + params["q_norm"],
                     self.eps)
        q = _dot(c, params["W_uq"]).reshape(
            u.shape[:-1] + (self.n_heads, self.nope_dim + self.rope_dim))
        return (q[..., :self.nope_dim],
                rotate_pairs(q[..., self.nope_dim:], positions,
                             self._inv_freq()))

    def _latent(self, params, u, positions, dtype):
        """``[c_kv | k_rope | 0]`` of each position in the cache's type."""
        kv = _dot(u, params["W_dkv"])
        c = rms_norm(kv[..., :self.kv_rank], 1.0 + params["kv_norm"],
                     self.eps)
        r = rotate_pairs(kv[..., None, self.kv_rank:], positions,
                         self._inv_freq())[..., 0, :]
        pad = jnp.zeros(r.shape[:-1] + (self._width() - self.kv_rank
                                        - self.rope_dim,), r.dtype)
        return jnp.concatenate([c, r, pad], axis=-1).astype(dtype)

    def _up(self, params):
        """``W_ukv`` as ``(W_uk [kv_rank, heads, nope], W_uv [kv_rank,
        heads, value])``."""
        w = params["W_ukv"].reshape(self.kv_rank, self.n_heads,
                                    self.nope_dim + self.value_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def _finish(self, params, u, o):
        """``o: [..., heads, value_dim]`` float32 -> the layer's output."""
        o = o.reshape(o.shape[:-2] + (-1,)) * jax.nn.sigmoid(
            _dot(u, params["W_g"]))
        return self.activation.apply(_dot(o, params["W_o"]) * self.out_scale)

    def _sequence(self, params, x, dtype):
        """A whole prompt, EXPANDED: every head's keys and values from the
        latent vectors (rounded to the cache's type first, as a decode step
        reads them), then causal attention ``LATENT_QUERY_CHUNK`` queries at
        a time. Returns ``(y, latent)``."""
        b, t, _ = x.shape
        h, nope = self.n_heads, self.nope_dim
        pos = jnp.arange(t)
        latent = self._latent(params, x, pos, dtype)             # [b, t, w]
        kv = _dot(latent[..., :self.kv_rank], params["W_ukv"]).reshape(
            b, t, h, nope + self.value_dim)
        rope = jnp.broadcast_to(
            latent[:, :, None, self.kv_rank:self.kv_rank + self.rope_dim],
            (b, t, h, self.rope_dim))
        k = jnp.concatenate([kv[..., :nope].astype(dtype), rope], axis=-1)
        v = kv[..., nope:].astype(dtype)
        q_nope, q_rope = self._queries(params, x, pos)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        c = min(LATENT_QUERY_CHUNK, t)
        if t % c:
            raise ValueError(f"{t} positions are no multiple of the query "
                             f"chunk {c}")
        scale = self._scale()

        def body(_, xs):
            qc, start = xs                                   # [b, c, h, d]
            s = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(k.dtype), k,
                           preferred_element_type=jnp.float32) * scale
            seen = pos[None, :] <= (start + jnp.arange(c))[:, None]
            p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
            return None, jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)

        n = t // c
        _, o = jax.lax.scan(body, None, (
            jnp.swapaxes(q.reshape(b, n, c, h, -1), 0, 1), jnp.arange(n) * c))
        o = jnp.swapaxes(o, 0, 1).reshape(b, t, h, self.value_dim)
        return self._finish(params, x, o), latent

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _ = self._sequence(params, x,
                              _wdtype(self.cache_dtype, jnp.float32))
        if mask is not None:
            y = y * jnp.asarray(mask, y.dtype)[:, :, None]
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        return {"latent": jnp.zeros((batch, length, self._width()),
                                    _wdtype(self.cache_dtype, dtype))}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        y, latent = self._sequence(params, x, _wdtype(self.cache_dtype, dtype))
        if key_mask is not None:
            y = y * jnp.asarray(key_mask, y.dtype)[:, :, None]
        return y, {"latent": latent}

    def cache_join(self, cache, block, rows, length):
        return {"latent": _join_rows(cache["latent"], block["latent"], rows,
                                     length)}

    def cache_step(self, params, x, cache, positions, active=None):
        """ABSORBED: ``q_lat = q_nope W_uk^T`` a head, the scores ``q_lat .
        c_kv + q_rope . k_rope`` and the values ``c_kv`` read from the one
        latent cache (``ops.attention.latent_decode_attention``), ``o =
        (softmax . c_kv) W_uv``. ``counts`` as a full attention layer's:
        the cached positions the step streamed, the positions the bucket
        holds."""
        w_uk, w_uv = self._up(params)
        q_nope, q_rope = self._queries(params, x, positions)
        latent = cache_update(
            cache["latent"],
            self._latent(params, x, positions, cache["latent"].dtype)[:, None],
            positions)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope.astype(w_uk.dtype), w_uk,
                           preferred_element_type=jnp.float32)
        pad = jnp.zeros(q_rope.shape[:-1] + (latent.shape[-1] - self.kv_rank
                                             - self.rope_dim,), jnp.float32)
        o_lat, read = latent_decode_attention(
            jnp.concatenate([q_lat, q_rope, pad], axis=-1), latent,
            positions, self.kv_rank, self._scale())
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(w_uv.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        counts = {"decode_kv_read_positions": read,
                  "decode_kv_bucket_positions": jnp.full_like(
                      positions, latent.shape[1])}
        return self._finish(params, x, o), {"latent": latent}, counts

    def cache_grow(self, cache, length):
        pad = ((0, 0), (0, length - cache["latent"].shape[1]), (0, 0))
        return {"latent": jnp.pad(cache["latent"], pad)}

    def cache_release(self, cache, keep):
        return cache
