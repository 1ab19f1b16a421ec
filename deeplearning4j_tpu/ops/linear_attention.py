"""Linear attention with a per-head decay ("lightning" attention): the
recurrence, its one-token step and its chunked prefill form.

Per head, in float32::

    S_t = lambda * S_{t-1} + k_t^T v_t        (S: [head_dim, head_dim])
    o_t = q_t S_t

``lambda = exp(log_decay)`` is a constant of the head (``log_decay < 0``).
A sequence's whole past is the fixed-size state ``S``: a decode step reads
and writes it once, whatever the context length.

The prefill form cuts the sequence into chunks of ``chunk`` positions:
quadratic inside a chunk (a decayed, causally masked ``Q K^T``), the state
across chunks. With ``n_i`` the count of VALID positions of the chunk up
to and including ``i`` and ``S_in`` the state the chunk starts from::

    o_i   = lambda^n_i q_i S_in + sum_{j<=i} lambda^(n_i - n_j) (q_i.k_j) v_j
    S_out = lambda^n_C S_in + sum_j lambda^(n_C - n_j) k_j^T v_j

Every exponent is a non-negative count times ``log_decay``: nothing is
divided by a decay, so nothing overflows however long the chunk. A
padded position (``valid`` 0) contributes no ``k^T v`` and costs no
decay, so the state after a padded prompt bucket is the state after the
prompt's last real token.

Every product here runs at ``Precision.HIGHEST``: the state is float32 and
is read by every later token, and the FLOPs are a thousandth of the
projections beside them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def decay_slopes(n_heads: int, layer_index: int, n_layers: int) -> np.ndarray:
    """``s_h = 2^(-8h/H) * (1 - l/(L-1) + 1e-5)`` for head ``h = 1..H`` of
    layer ``l`` of ``L`` (Lightning Attention-2's slopes); the decay is
    ``exp(-s_h)``."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    layer = 1.0 - layer_index / max(n_layers - 1, 1) + 1e-5
    return (2.0 ** (-8.0 * h / n_heads) * layer).astype(np.float32)


def linear_attention_step(q, k, v, state, log_decay):
    """One token: ``q, k, v: [batch, heads, head_dim]``, ``state: [batch,
    heads, head_dim, head_dim]`` float32, ``log_decay: [heads]``. Returns
    ``(o [batch, heads, head_dim], state')``; elementwise, so the state is
    read once and written once."""
    lam = jnp.exp(log_decay)[None, :, None, None]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    state = lam * state + k[..., :, None] * v[..., None, :]
    return jnp.sum(q[..., :, None] * state, axis=-2), state


def linear_attention_chunked(q, k, v, log_decay, valid=None, state=None,
                             chunk: int = 256):
    """A whole sequence: ``q, k, v: [batch, time, heads, head_dim]``,
    ``valid: [batch, time]`` (1 = a real position), ``state`` the state to
    start from (zeros when ``None``). Returns ``(o [batch, time, heads,
    head_dim] float32, state [batch, heads, head_dim, head_dim])``. A
    ``time`` that is no multiple of ``chunk`` is padded with invalid
    positions."""
    b, t, h, d = q.shape
    c = min(int(chunk), t)
    n = -(-t // c)
    pad = n * c - t
    if valid is None:
        valid = jnp.ones((b, t), jnp.float32)
    valid = jnp.asarray(valid, jnp.float32)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    if state is None:
        state = jnp.zeros((b, h, d, d), jnp.float32)

    def chunks(a):      # [b, n*c, ...] -> [n, b, c, ...]
        return jnp.swapaxes(a.reshape((b, n, c) + a.shape[2:]), 0, 1)

    causal = jnp.tril(jnp.ones((c, c), bool))
    ld = jnp.asarray(log_decay, jnp.float32)

    def body(s_in, xs):
        qc, kc, vc, m = xs                       # [b, c, h, d], m [b, c]
        kc = kc * m[:, :, None, None]
        count = jnp.cumsum(m, axis=1)            # n_i  [b, c]
        steps = count[:, :, None] - count[:, None, :]        # n_i - n_j
        a = jnp.exp(ld[None, :, None, None] * steps[:, None])
        a = jnp.where(causal[None, None], a, 0.0)            # [b, h, c, c]
        qk = jnp.einsum("bihd,bjhd->bhij", qc, kc, precision=_HI)
        o = jnp.einsum("bhij,bjhd->bihd", qk * a, vc, precision=_HI)
        into = jnp.exp(ld[None, None, :] * count[:, :, None])  # [b, c, h]
        o = o + jnp.einsum("bihd,bhde->bihe", qc * into[..., None], s_in,
                           precision=_HI)
        left = count[:, -1:, None] - count[:, :, None]       # n_C - n_j
        w = jnp.exp(ld[None, None, :] * left)                # [b, c, h]
        s_out = (jnp.exp(ld[None, :] * count[:, -1:])[:, :, None, None]
                 * s_in
                 + jnp.einsum("bjhd,bjhe->bhde", kc * w[..., None], vc,
                              precision=_HI))
        return s_out, o

    state, o = jax.lax.scan(body, state.astype(jnp.float32),
                            (chunks(q), chunks(k), chunks(v), chunks(valid)))
    o = jnp.swapaxes(o, 0, 1).reshape(b, n * c, h, d)
    return o[:, :t], state
