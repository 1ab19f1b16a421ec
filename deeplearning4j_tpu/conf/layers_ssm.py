"""Two convolution mixers on the per-layer cache interface of
``conf/layers_hybrid.py``:

- :class:`MambaMixerLayer`, a state-space ("Mamba") mixer: an input
  projection into ``x`` and a gate ``z``, a depthwise causal convolution
  over ``x`` that keeps its last ``d_conv - 1`` inputs, a selective scan
  (``ops/selective_scan``) whose step, ``B`` and ``C`` come from the
  convolved input, the gate, an output projection;
- :class:`ShortConvLayer`, a gated short convolution: an input projection
  into two gates ``B``, ``C`` and ``x``, a depthwise causal convolution of
  a few taps over ``B * x`` with no activation, ``C`` on its output, an
  output projection. ONE kind of per-row state, the convolution's last
  ``d_conv - 1`` inputs (kind ``conv_window``, the same ring as below).

The Mamba mixer keeps TWO kinds of per-row state, both float32 and
neither depending on the bucket: the scan's state ``[rows, d_state,
d_inner]`` (kind ``recurrent``) and the convolution's last ``d_conv - 1``
inputs (kind ``conv_window``), a RING ``[rows, (d_conv - 1) * d_inner]``:
the input of position ``p`` lies in slot ``p mod (d_conv - 1)``, so a
decode step overwrites the oldest input where it lies and moves nothing
(a window kept oldest-first is shifted every step: the compiler copied
every layer's whole window a step to do it; and flat, so that no
dimension of 3 meets the TPU's tiles of 8 x 128). What ``cache_prefill``
owes a RIGHT-padded row (``docs/serving.md``): the scan's state after the
row's last REAL token (a padded position has a step of 0: the state
stands still) and the row's last ``d_conv - 1`` REAL inputs, each in its
position's slot (zeros where the prompt is shorter), not the bucket's
tail.

Types as ``conf/layers_hybrid.py``: matrices in ``weight_dtype``, every
product rounds its left operand to the matrix's type and accumulates in
float32; the convolution's taps, the inner norms, the step's bias, ``A``,
``D``, the scan and both states float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.layers import BaseLayer, _as_ff_size
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
from deeplearning4j_tpu.ops.selective_scan import (
    selective_scan,
    selective_scan_loop,
    selective_scan_step,
)


SSM_TOKEN_SPAN = 2048      # positions MambaMixerLayer projects and scans at a time
SHORTCONV_TOKEN_SPAN = 2048     # positions ShortConvLayer projects at a time


class _SequenceMixer(BaseLayer):
    """What a sequence mixer of the cache interface shares: a recurrent
    output of ``n_out`` features, and no streaming."""

    def output_type(self, input_type):
        ts = (input_type.timesteps if isinstance(input_type, it.Recurrent)
              else -1)
        return it.Recurrent(size=self.n_out, timesteps=ts)

    def streaming_safe(self) -> bool:
        return False


# --- a causal depthwise convolution that keeps its last inputs in a ring ------
# (the state-space mixer's, the delta-rule mixer's, conf/layers_delta.py,
# and the short convolution's, which takes it without the activation)

def conv_span(tail, x, taps, mask, bias=None, silu=True):
    """A span ``x: [batch, span, d]`` through ``K = taps.shape[0]`` causal
    taps after the ``K - 1`` earlier inputs ``tail: [batch, K - 1, d]``,
    oldest first. Returns ``(y, tail')``: ``y_t = silu((bias +) sum_j
    taps[j] x_{t-K+1+j})`` (``silu`` False: the sum alone), and the last
    ``K - 1`` REAL inputs (a right-padded row's real positions are the
    span's first ``sum(mask)``)."""
    k, span = taps.shape[0] - 1, x.shape[1]
    padded = jnp.concatenate([tail, x], axis=1)          # [b, k + span, d]
    y = sum(taps[j] * padded[:, j:j + span] for j in range(k + 1))
    if bias is not None:
        y = bias + y
    if silu:
        y = jax.nn.silu(y)
    real = jnp.sum(mask, axis=1).astype(jnp.int32)
    return y, jnp.take_along_axis(
        padded, (real[:, None] + jnp.arange(k))[:, :, None], axis=1)


def tail_to_ring(tail, lengths):
    """The last ``k`` inputs oldest first ``[batch, k, d]`` of rows of
    ``lengths`` positions as the RING a decode step keeps, ``[batch, k *
    d]``: slot ``s`` holds the input of the position ``p = s (mod k)`` among
    the last ``k``, the tail's entry ``(s - length) mod k``."""
    b, k, d = tail.shape
    entry = (jnp.arange(k)[None, :] - lengths[:, None]) % k
    return jnp.take_along_axis(tail, entry[:, :, None], axis=1).reshape(
        b, k * d)


def conv_ring_step(ring, x, taps, positions, bias=None, silu=True):
    """One token ``x: [batch, d]`` at ``positions`` through the taps, its
    ``k = K - 1`` earlier inputs in ``ring: [batch, k * d]`` (the input of
    position ``p`` in slot ``p mod k``): the taps weigh the ring where it
    lies and the token's input overwrites the oldest slot, so nothing is
    shifted. Returns ``(silu(y), ring')`` (``silu`` False: ``(y,
    ring')``), ``y`` as :func:`conv_span`'s."""
    k, d = taps.shape[0] - 1, x.shape[-1]
    # the ring's slot of each lane, and how many positions back from the
    # oldest input (slot positions mod k) it lies
    slot = jax.lax.broadcasted_iota(jnp.int32, ring.shape, 1) // d
    age = (slot - (positions % k)[:, None]) % k
    weighed = sum(jnp.where(age == j, jnp.tile(taps[j], k), 0.0)
                  for j in range(k)) * ring
    y = taps[k] * x
    if bias is not None:
        y = bias + y
    y = y + sum(weighed[:, j * d:(j + 1) * d] for j in range(k))
    if silu:
        y = jax.nn.silu(y)
    with jax.named_scope("cache.write"):
        ring = jnp.where(age == 0, jnp.tile(x, (1, k)), ring)
    return y, ring


@serde.register
@dataclasses.dataclass
class MambaMixerLayer(_SequenceMixer):
    """``[x ; z] = W_in u``; ``x~_t = silu(b_c + sum_j w_c[j] x_{t-K+1+j})``
    over ``K = d_conv`` taps; ``[delta ; B ; C] = W_x x~``, each RMS-normed
    with a gain; ``dt = softplus(W_dt delta +
    b_dt)``; ``A = -exp(A_log)``; the selective scan; ``W_out (y *
    silu(z))``. The mask of a sequence is taken to be a RIGHT padding
    (each row's real positions first)."""

    scope_class = "ssm"

    n_out: int = 0
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    eps: float = 1e-6
    out_scale: float = 1.0
    weight_dtype: str = ""

    uses_mask = True
    cache_kinds = {"state": "recurrent", "conv": "conv_window"}
    cache_counters = ("ssm_state_updates",)

    def init(self, key, input_type, dtype=jnp.float32):
        """Matrices by the layer's initializer; what decides how long the
        state remembers as the published initialisation has it: ``A_log =
        log(1..d_state)`` a channel, ``D = 1``, ``b_dt`` the inverse
        softplus of steps log-uniform in [1e-3, 1e-1]."""
        n_in, d, n, r = (_as_ff_size(input_type), self.d_inner,
                         self.d_state, self.dt_rank)
        wd = _wdtype(self.weight_dtype, dtype)
        ks = jax.random.split(key, 6)
        step = jnp.exp(jax.random.uniform(
            ks[5], (d,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        taps = jax.random.uniform(ks[1], (self.d_conv + 1, d), jnp.float32,
                                  -1.0, 1.0) / self.d_conv ** 0.5
        return {"W_in": _matrix(self, ks[0], (n_in, 2 * d), wd),
                "conv_w": taps[:-1], "conv_b": taps[-1],
                "W_x": _matrix(self, ks[2], (d, r + 2 * n), wd),
                "W_dt": _matrix(self, ks[3], (r, d), wd),
                "b_dt": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
                "D": jnp.ones((d,), jnp.float32),
                "W_out": _matrix(self, ks[4], (d, self.n_out), wd),
                "dt_norm": jnp.ones((r,), jnp.float32),
                "b_norm": jnp.ones((n,), jnp.float32),
                "c_norm": jnp.ones((n,), jnp.float32)}

    def param_order(self):
        return ["W_in", "conv_w", "conv_b", "W_x", "W_dt", "b_dt", "A_log",
                "D", "W_out", "dt_norm", "b_norm", "c_norm"]

    def regularized_param_keys(self):
        return ["W_in", "W_x", "W_dt", "W_out"]

    # --- the mathematics -------------------------------------------------------
    def _project(self, params, u):
        with jax.named_scope("ssm.in_proj"):
            xz = _dot(u, params["W_in"])
        return xz[..., :self.d_inner], xz[..., self.d_inner:]

    def _scan_inputs(self, params, xc):
        """The convolved input ``xc`` -> ``(dt, B, C)`` of the scan."""
        r, n = self.dt_rank, self.d_state
        dbc = _dot(xc, params["W_x"])
        delta, b, c = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
        delta = rms_norm(delta, params["dt_norm"], self.eps)
        b = rms_norm(b, params["b_norm"], self.eps)
        c = rms_norm(c, params["c_norm"], self.eps)
        dt = jax.nn.softplus(_dot(delta, params["W_dt"]) + params["b_dt"])
        return dt, b, c

    def _finish(self, params, y, z):
        with jax.named_scope("ssm.out_proj"):
            return self.activation.apply(
                _dot(y * jax.nn.silu(z), params["W_out"]) * self.out_scale)

    def _sequence(self, params, u, mask, state, conv, scan):
        """``u: [batch, time, features]`` from the scan's ``state`` and the
        convolution's last inputs ``conv: [batch, d_conv - 1, d_inner]``,
        oldest first: a ``lax.scan`` over spans of ``SSM_TOKEN_SPAN``
        positions, both states its carry. Returns ``(y, state, conv)``."""
        b, t, _ = u.shape
        n, span = _token_spans(t, SSM_TOKEN_SPAN)
        mask = (jnp.ones((b, t), jnp.float32) if mask is None
                else (jnp.asarray(mask) > 0).astype(jnp.float32))
        a = -jnp.exp(params["A_log"])

        def body(carry, xs):
            h, tail = carry
            uc, mc = xs
            x, z = self._project(params, uc)
            with jax.named_scope("ssm.conv"):
                xc, tail = conv_span(tail, x, params["conv_w"], mc,
                                     params["conv_b"])
            dt, bm, cm = self._scan_inputs(params, xc)
            with jax.named_scope("ssm.scan"):
                y, h = scan(xc, dt, a, bm, cm, params["D"], mc, h)
            return (h, tail), self._finish(params, y, z) * mc[:, :, None]

        (state, conv), y = jax.lax.scan(
            body, (state, conv),
            (_split_spans(u, n, span), _split_spans(mask, n, span)))
        return _merge_spans(y), state, conv

    def _zeros(self, batch):
        return (jnp.zeros((batch, self.d_state, self.d_inner), jnp.float32),
                jnp.zeros((batch, self.d_conv - 1, self.d_inner),
                          jnp.float32))

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        """The differentiable walk (``selective_scan_loop``): the kernel
        has no backward."""
        x = self._dropout_input(x, train, rng)
        y, _, _ = self._sequence(params, x, mask, *self._zeros(x.shape[0]),
                                 selective_scan_loop)
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        state, conv = self._zeros(batch)
        return {"state": state, "conv": conv.reshape(batch, -1)}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        b = x.shape[0]
        y, state, tail = self._sequence(
            params, x, key_mask, *self._zeros(b), selective_scan)
        lengths = (jnp.full((b,), x.shape[1], jnp.int32) if key_mask is None
                   else jnp.sum(key_mask > 0, axis=1).astype(jnp.int32))
        return y, {"state": state, "conv": tail_to_ring(tail, lengths)}

    def cache_join(self, cache, block, rows, length):
        return {n: _join_rows(cache[n], block[n], rows)
                for n in ("state", "conv")}

    def cache_step(self, params, x, cache, positions, active=None):
        with jax.named_scope("ssm.step"):
            xi, z = self._project(params, x)
            xc, ring = conv_ring_step(cache["conv"], xi, params["conv_w"],
                                      positions, params["conv_b"])
            dt, b, c = self._scan_inputs(params, xc)
            y, state = selective_scan_step(
                xc, dt, -jnp.exp(params["A_log"]), b, c, params["D"],
                cache["state"])
        counts = {"ssm_state_updates": jnp.ones_like(positions)}
        return (self._finish(params, y, z),
                {"state": state, "conv": ring}, counts)

    def cache_grow(self, cache, length):
        return cache

    def cache_release(self, cache, keep):
        return {"state": jnp.where(keep[:, None, None], cache["state"], 0),
                "conv": jnp.where(keep[:, None], cache["conv"], 0)}


@serde.register
@dataclasses.dataclass
class ShortConvLayer(_SequenceMixer):
    """A gated short convolution: ``[B | C | x] = W_in u`` (three
    ``n_out``-wide chunks in that order), ``v = B * x``, ``z_t = sum_j w[j]
    v_{t-K+1+j}`` over ``K = d_conv`` depthwise causal taps with no bias
    and no activation, ``W_out (C * z)``. Its per-row state is the last
    ``d_conv - 1`` values of ``v``, float32, a RING ``[rows, (d_conv - 1) *
    n_out]`` whatever the bucket (kind ``conv_window``): what
    ``cache_prefill`` owes a right-padded row is its last ``d_conv - 1``
    REAL values, each in its position's slot. The mask of a sequence is
    taken to be a RIGHT padding."""

    scope_class = "mixer.shortconv"

    n_out: int = 0
    d_conv: int = 3
    out_scale: float = 1.0
    weight_dtype: str = ""

    uses_mask = True
    cache_kinds = {"conv": "conv_window"}
    cache_counters = ("shortconv_state_updates",)

    def init(self, key, input_type, dtype=jnp.float32):
        """Matrices by the layer's initializer; the taps uniform in
        +-1/sqrt(d_conv), float32."""
        n_in, e = _as_ff_size(input_type), self.n_out
        wd = _wdtype(self.weight_dtype, dtype)
        ks = jax.random.split(key, 3)
        return {"W_in": _matrix(self, ks[0], (n_in, 3 * e), wd),
                "conv_w": jax.random.uniform(
                    ks[1], (self.d_conv, e), jnp.float32, -1.0, 1.0)
                / self.d_conv ** 0.5,
                "W_out": _matrix(self, ks[2], (e, self.n_out), wd)}

    def param_order(self):
        return ["W_in", "conv_w", "W_out"]

    def regularized_param_keys(self):
        return ["W_in", "W_out"]

    # --- the mathematics ----------------------------------------------------
    def _project(self, params, u):
        """``u`` -> ``(v, C)``: the convolution's input ``B * x`` and the
        output gate."""
        e = self.n_out
        with jax.named_scope("shortconv.in_proj"):
            bcx = _dot(u, params["W_in"])
            return bcx[..., :e] * bcx[..., 2 * e:], bcx[..., e:2 * e]

    def _finish(self, params, z, c):
        with jax.named_scope("shortconv.out_proj"):
            return self.activation.apply(
                _dot(c * z, params["W_out"]) * self.out_scale)

    def _sequence(self, params, u, mask, conv):
        """``u: [batch, time, features]`` after the convolution's last
        inputs ``conv: [batch, d_conv - 1, n_out]``, oldest first: a
        ``lax.scan`` over spans of ``SHORTCONV_TOKEN_SPAN`` positions, the
        inputs its carry. Returns ``(y, conv)``."""
        b, t, _ = u.shape
        n, span = _token_spans(t, SHORTCONV_TOKEN_SPAN)
        mask = (jnp.ones((b, t), jnp.float32) if mask is None
                else (jnp.asarray(mask) > 0).astype(jnp.float32))

        def body(tail, xs):
            uc, mc = xs
            v, c = self._project(params, uc)
            with jax.named_scope("shortconv.conv"):
                z, tail = conv_span(tail, v, params["conv_w"], mc,
                                    silu=False)
            return tail, self._finish(params, z, c) * mc[:, :, None]

        conv, y = jax.lax.scan(
            body, conv,
            (_split_spans(u, n, span), _split_spans(mask, n, span)))
        return _merge_spans(y), conv

    def _zeros(self, batch):
        return jnp.zeros((batch, self.d_conv - 1, self.n_out), jnp.float32)

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        y, _ = self._sequence(params, x, mask, self._zeros(x.shape[0]))
        return y, state

    # --- the cache interface ------------------------------------------------
    def cache_init(self, batch, length, n_in, dtype=jnp.float32):
        return {"conv": self._zeros(batch).reshape(batch, -1)}

    def cache_prefill(self, params, x, key_mask=None, dtype=jnp.float32,
                      use_kernels=False):
        b = x.shape[0]
        y, tail = self._sequence(params, x, key_mask, self._zeros(b))
        lengths = (jnp.full((b,), x.shape[1], jnp.int32) if key_mask is None
                   else jnp.sum(key_mask > 0, axis=1).astype(jnp.int32))
        return y, {"conv": tail_to_ring(tail, lengths)}

    def cache_join(self, cache, block, rows, length):
        return {"conv": _join_rows(cache["conv"], block["conv"], rows)}

    def cache_step(self, params, x, cache, positions, active=None):
        with jax.named_scope("shortconv.step"):
            v, c = self._project(params, x)
            z, ring = conv_ring_step(cache["conv"], v, params["conv_w"],
                                     positions, silu=False)
        counts = {"shortconv_state_updates": jnp.ones_like(positions)}
        return self._finish(params, z, c), {"conv": ring}, counts

    def cache_grow(self, cache, length):
        return cache

    def cache_release(self, cache, keep):
        return {"conv": jnp.where(keep[:, None], cache["conv"], 0)}
