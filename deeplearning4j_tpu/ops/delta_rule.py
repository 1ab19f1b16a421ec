"""The gated delta rule: linear attention whose state is corrected toward
each new value rather than summed into, under a decay that depends on the
input. Its token recurrence, its one-token step (a Pallas kernel where the
program is lowered for a TPU) and its chunked prefill form.

Per value head, in float32, with ``S: [d_k, d_v]``, ``q`` already scaled
and ``q``, ``k`` L2-normed::

    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` and ``0 <= beta_t <= 1`` come from the token. A sequence's
whole past is the fixed-size state: a decode step reads and writes it once,
whatever the context length.

The prefill form (the "WY" form of the delta rule) cuts the sequence into
chunks of ``chunk`` positions. Inside a chunk, with ``G_i`` the cumulative
sum of ``g`` up to and including ``i`` and ``A`` the strictly lower
``A_ij = beta_i (k_i . k_j) e^{G_i - G_j}``, the corrections the chunk's
positions write are ``T (beta v) - T (beta e^G k) S_in`` with ``T = (I +
A)^-1`` (a unit triangular solve); outputs and the outgoing state follow
from them by products. Every exponent is a difference ``G_i - G_j`` with
``i >= j`` or ``G_i`` itself, never positive: nothing is divided by a
decay. A padded position (``valid`` 0) writes nothing and decays nothing,
so the state after a right-padded prompt bucket is the state after the
prompt's last real token.

Every product of the state runs at ``Precision.HIGHEST``: the state is
float32 and is read by every later token.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
STEP_HEADS = 16     # value heads a grid step of the decode kernel updates


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule_loop(q, k, v, g, beta, state=None):
    """The token recurrence over a whole sequence, one position at a time
    (the test's and the reference's form): ``q, k: [batch, time, heads,
    d_k]``, ``v: [batch, time, heads, d_v]``, ``g, beta: [batch, time,
    heads]``. Returns ``(o [batch, time, heads, d_v], state [batch, heads,
    d_k, d_v])``."""
    b, _, h, dk = k.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def body(s, xs):
        o, s = delta_rule_step_xla(*xs, s)
        return s, o

    xs = tuple(jnp.swapaxes(a.astype(jnp.float32), 0, 1)
               for a in (q, k, v, jnp.exp(g), beta))
    state, o = jax.lax.scan(body, state.astype(jnp.float32), xs)
    return jnp.swapaxes(o, 0, 1), state


def delta_rule_step_xla(q, k, v, decay, beta, state):
    """One token: ``q, k: [batch, heads, d_k]``, ``v: [batch, heads, d_v]``,
    ``decay = e^g``, ``beta: [batch, heads]``, ``state: [batch, heads, d_k,
    d_v]`` float32. Returns ``(o [batch, heads, d_v], state')``."""
    s = state * decay[..., None, None]
    delta = beta[..., None] * (v - jnp.sum(s * k[..., :, None], axis=-2))
    s = s + k[..., :, None] * delta[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def _delta_step_kernel(s_ref, q_ref, k_ref, v_ref, d_ref, b_ref, o_ref,
                       s_out):
    """One grid step: ``STEP_HEADS`` heads of one row, their states
    ``[heads, d_k, d_v]`` read once and written once. ``q_ref``, ``k_ref``,
    ``v_ref: [1, heads, d]``; ``d_ref`` and ``b_ref`` hold each head's
    decay and write strength in every lane of its row. A head's ``k`` and
    ``q`` are wanted along the state's sublanes (``d_k``): both blocks are
    transposed by one product with the identity (exact at HIGHEST), and a
    head's column is a lane of the result."""
    heads, dk = k_ref.shape[1], k_ref.shape[2]
    f32 = jnp.float32
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)).astype(f32)

    def columns(x):             # [heads, d_k] -> [d_k, heads]
        return jax.lax.dot_general(eye, x, (((1,), (1,)), ((), ())),
                                   precision=_HI, preferred_element_type=f32)

    k_cols, q_cols = columns(k_ref[0]), columns(q_ref[0])
    for i in range(heads):
        s = s_ref[0, i] * d_ref[0, i:i + 1, :]                  # [d_k, d_v]
        kc = k_cols[:, i:i + 1]                                  # [d_k, 1]
        delta = b_ref[0, i:i + 1, :] * (
            v_ref[0, i:i + 1, :] - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * delta
        s_out[0, i] = s
        o_ref[0, i:i + 1, :] = jnp.sum(s * q_cols[:, i:i + 1], axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_step_kernel(q, k, v, decay, beta, state,
                           interpret: Optional[bool] = None):
    """:func:`delta_rule_step_xla` as one Pallas kernel: a grid step a row
    and ``STEP_HEADS`` heads, each state read once and written once in
    place (the state is aliased from input to output). ``interpret=None``
    runs the Pallas interpreter off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, dk, dv = state.shape
    hb = min(STEP_HEADS, h)
    if h % hb:
        raise ValueError(f"{h} heads are no multiple of {hb}")
    lanes = lambda a: jnp.broadcast_to(  # noqa: E731
        a.astype(jnp.float32)[..., None], (b, h, dv))
    vec = lambda d: pl.BlockSpec((1, hb, d), lambda r, j: (r, j, 0))  # noqa: E731
    state_spec = pl.BlockSpec((1, hb, dk, dv), lambda r, j: (r, j, 0, 0))
    params = None
    if not interpret:
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    o, state = pl.pallas_call(
        _delta_step_kernel,
        grid=(b, h // hb),
        in_specs=[state_spec, vec(dk), vec(dk), vec(dv), vec(dv), vec(dv)],
        out_specs=[vec(dv), state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={0: 1},
        compiler_params=params, interpret=interpret,
    )(state.astype(jnp.float32), q.astype(jnp.float32),
      k.astype(jnp.float32), v.astype(jnp.float32), lanes(decay),
      lanes(beta))
    return o, state


def delta_rule_step_applies(state_shape) -> bool:
    """Whether the TPU takes a ``[batch, heads, d_k, d_v]`` state by the
    kernel, from the shape alone: whole 128-lane rows, whole sublane tiles,
    whole steps of heads."""
    _, h, dk, dv = state_shape
    return dk % 128 == 0 and dv % 128 == 0 and h % min(STEP_HEADS, h) == 0


def delta_rule_step(q, k, v, decay, beta, state):
    """One decode step: the Pallas kernel where the program is LOWERED for
    a TPU and the shape allows (``lax.platform_dependent``, as
    ``ops.attention.bounded_decode_attention``), :func:`delta_rule_step_xla`
    elsewhere. Same arguments and result."""
    if not delta_rule_step_applies(state.shape):
        return delta_rule_step_xla(q, k, v, decay, beta, state)
    return jax.lax.platform_dependent(
        q, k, v, decay, beta, state,
        tpu=functools.partial(delta_rule_step_kernel, interpret=False),
        default=delta_rule_step_xla)


def delta_rule_chunked(q, k, v, g, beta, valid=None, state=None,
                       chunk: int = 64):
    """A whole sequence in the chunked (WY) form: ``q, k: [batch, time,
    heads, d_k]``, ``v: [batch, time, heads, d_v]``, ``g, beta: [batch,
    time, heads]``, ``valid: [batch, time]`` (1 = a real position),
    ``state`` the state to start from (zeros when ``None``). Returns ``(o
    [batch, time, heads, d_v] float32, state [batch, heads, d_k, d_v])``;
    the token recurrence's up to rounding. A ``time`` that is no multiple
    of ``chunk`` is padded with invalid positions."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    n = -(-t // c)
    pad = n * c - t
    f32 = jnp.float32
    valid = (jnp.ones((b, t), f32) if valid is None
             else jnp.asarray(valid, f32))
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if pad:
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)  # noqa: E731
        q, k, v, g, beta, valid = (jnp.pad(a, widths(a))
                                   for a in (q, k, v, g, beta, valid))
    # a padded position writes nothing and decays nothing
    g = g * valid[..., None]
    beta = beta * valid[..., None]
    if state is None:
        state = jnp.zeros((b, h, dk, dv), f32)

    def chunks(a):      # [b, n*c, h, ...] -> [n, b, h, c, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((c, c), bool), -1)
    causal = jnp.tril(jnp.ones((c, c), bool))
    eye = jnp.eye(c, dtype=f32)

    def body(s_in, xs):
        qc, kc, vc, gc, bc = xs          # [b, h, c, d], gates [b, h, c]
        big = jnp.cumsum(gc, axis=-1)                        # G_i
        diff = big[..., :, None] - big[..., None, :]         # G_i - G_j
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
        kk = jnp.einsum("bhid,bhjd->bhij", kc, kc, precision=_HI)
        a = jnp.where(lower, bc[..., :, None] * kk * decay, 0.0)
        rhs = jnp.concatenate([vc * bc[..., None],
                               kc * (bc * jnp.exp(big))[..., None]], axis=-1)
        wu = jax.lax.linalg.triangular_solve(
            a + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
        new = wu[..., :dv] - jnp.einsum("bhik,bhkv->bhiv", wu[..., dv:],
                                        s_in, precision=_HI)
        qk = jnp.einsum("bhid,bhjd->bhij", qc, kc, precision=_HI) * decay
        o = (jnp.einsum("bhik,bhkv->bhiv", qc * jnp.exp(big)[..., None],
                        s_in, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk, new, precision=_HI))
        last = big[..., -1:]
        s_out = (jnp.exp(last)[..., None] * s_in
                 + jnp.einsum("bhjk,bhjv->bhkv",
                              kc * jnp.exp(last - big)[..., None], new,
                              precision=_HI))
        return s_out, o

    state, o = jax.lax.scan(body, state.astype(f32),
                            tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, dv)
    return o[:, :t], state
