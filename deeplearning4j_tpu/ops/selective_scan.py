"""The selective scan of a state-space ("Mamba") mixer: the recurrence over
a whole prompt and its one-token step.

Per channel ``c`` of ``d`` and state index ``i`` of ``n``, in float32::

    h_t[i, c] = exp(dt_t[c] * A[i, c]) * h_{t-1}[i, c] + dt_t[c] * x_t[c] * B_t[i]
    y_t[c]    = sum_i h_t[i, c] * C_t[i] + D[c] * x_t[c]

``A < 0``; the step ``dt_t``, ``B_t`` and ``C_t`` depend on the input, so
the decay is a different number for each of the ``n * d`` state elements
at each position: there is no ``[chunk, chunk]`` product to batch on the
matrix unit (``ops/linear_attention``'s chunked form does not carry
over). A sequence's whole past is the state ``h: [n, d]``, whatever the
context length. The state lies ``[rows, n, d]``, the channels minor: on
the TPU the minor dimension fills whole 128-lane tiles (``[rows, d, n]``
with ``n = 16`` would be padded to eight times its bytes).

A masked position has ``dt = 0``: ``exp(0) = 1`` and nothing is added, so
the state after a right-padded prompt bucket is the state after the
prompt's last real token.

Where the program is LOWERED for a TPU (``lax.platform_dependent``, as
``ops.attention.bounded_decode_attention``), :func:`selective_scan` walks
a prompt with the Pallas kernel :func:`selective_scan_kernel`; on every
other platform, so in every CPU run, with :func:`selective_scan_loop`, a
``lax.scan`` over positions, which is also the kernel's reference and the
form that can be differentiated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHANNEL_BLOCK = 8 * LANES   # channels a grid step holds: one vreg a state index
TIME_CHUNK = 256            # positions streamed into VMEM at a time
# positions the kernel's loop body holds: at 2,048 positions of 5,120
# channels on the v5e 965 us at 1, 917 at 2, 883 at 4, 870 at 8 (PERF.md §6)
POSITION_UNROLL = 4
# positions a body of the ``lax.scan`` holds: one layer at 2,048 positions
# on the v5e 3,545 us at 1, 1,443 at 4, 1,377 at 16 (PERF.md §6)
LOOP_UNROLL = 16


def selective_scan_step(x, dt, a, b, c, d, h):
    """One token: ``x, dt: [rows, d]``, ``a: [n, d]``, ``b, c: [rows, n]``,
    ``d: [d]``, ``h: [rows, n, d]`` float32. Returns ``(y [rows, d], h')``;
    elementwise, so the state is read once and written once."""
    h = (jnp.exp(dt[:, None, :] * a) * h
         + (dt * x)[:, None, :] * b[:, :, None])
    return jnp.sum(h * c[:, :, None], axis=1) + d * x, h


def _masked(dt, mask):
    return dt if mask is None else dt * jnp.asarray(mask, dt.dtype)[:, :, None]


def selective_scan_loop(x, dt, a, b, c, d, mask=None, h0=None):
    """A whole sequence, position by position (``lax.scan``): ``x, dt:
    [rows, t, d]``, ``b, c: [rows, t, n]``, ``mask: [rows, t]`` (above 0 =
    a real position), ``h0: [rows, n, d]`` (zeros when ``None``). Returns
    ``(y [rows, t, d], h_t [rows, n, d])``, float32. No state history is
    kept: the carry is the state alone."""
    dt = _masked(dt, mask)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]), jnp.float32)

    def body(h, xs):
        y, h = selective_scan_step(*xs[:2], a, *xs[2:], d, h)
        return h, y

    h, y = jax.lax.scan(
        body, h0.astype(jnp.float32),
        tuple(jnp.swapaxes(v.astype(jnp.float32), 0, 1)
              for v in (x, dt, b, c)), unroll=LOOP_UNROLL)
    return jnp.swapaxes(y, 0, 1), h


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref,
                 h_ref, *, n, chunk):
    """One (row, block of channels, time chunk): the block's state, ``n``
    tiles of ``[8, 128]`` channels, stays in ``h_ref`` (the output block,
    resident while the innermost grid axis walks the chunks); a position
    is ``n`` independent multiply-adds of whole tiles, ``B_t[i]`` and
    ``C_t[i]`` scalars read from SMEM (a chunk's ``chunk * n`` of them
    laid ``[8, chunk * n / 8]``: SMEM pads no row), and ``y_t`` their
    sum: nothing is reduced across sublanes or lanes."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    a = [a_ref[i] for i in range(n)]
    skip = d_ref[...]
    per_row = chunk // 8        # positions a row of the scalars holds

    def position(t, h):
        x, dt = x_ref[t], dt_ref[t]
        row, col = t // per_row, (t % per_row) * n
        dx = dt * x
        y = skip * x
        out = []
        for i in range(n):
            hi = jnp.exp(dt * a[i]) * h[i] + dx * b_ref[row, col + i]
            y = y + hi * c_ref[row, col + i]
            out.append(hi)
        y_ref[t] = y
        return tuple(out)

    def positions(k, h):
        for u in range(POSITION_UNROLL):
            h = position(k * POSITION_UNROLL + u, h)
        return h

    h = jax.lax.fori_loop(0, chunk // POSITION_UNROLL, positions,
                          tuple(h_ref[i] for i in range(n)))
    for i in range(n):
        h_ref[i] = h[i]


def selective_scan_applies(t: int, d: int) -> bool:
    """Whether the kernel walks a prompt of ``t`` positions over ``d``
    channels: whole channel blocks, whole time chunks of whole eights
    (the scalars' rows, and :data:`POSITION_UNROLL` divides eight)."""
    chunk = min(t, TIME_CHUNK)
    return d % CHANNEL_BLOCK == 0 and t % chunk == 0 and chunk % 8 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(x, dt, a, b, c, d, h0, interpret: bool = False):
    """:func:`selective_scan_loop` (``dt`` already masked) as a Pallas
    kernel: a grid over (row, block of 1,024 channels, time chunk), the
    chunks innermost and sequential, the block's state resident in VMEM
    from chunk to chunk, ``x`` and ``dt`` streamed a chunk at a time,
    ``B`` and ``C`` a chunk at a time into SMEM, ``y`` written a chunk at
    a time. Jitted so that a decoder's layers share one trace."""
    rows, t, width = x.shape
    n = a.shape[0]
    chunk = min(t, TIME_CHUNK)
    blocks, chunks = width // CHANNEL_BLOCK, t // chunk
    f32 = jnp.float32

    def tiles(v):       # [..., width] -> [..., width / 128, 128]
        return v.astype(f32).reshape(v.shape[:-1] + (width // LANES, LANES))

    def scalars(v):     # [rows, t, n] -> [rows, chunks, 8, chunk * n / 8]
        return v.astype(f32).reshape(rows, chunks, 8, chunk * n // 8)

    stream = pl.BlockSpec((None, chunk, 8, LANES), lambda r, j, k: (r, k, j, 0))
    smem = pl.BlockSpec((None, None, 8, chunk * n // 8),
                        lambda r, j, k: (r, k, 0, 0), memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, n, 8, LANES), lambda r, j, k: (r, 0, j, 0))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n=n, chunk=chunk),
        grid=(rows, blocks, chunks),
        in_specs=[smem, smem, stream, stream,
                  pl.BlockSpec((n, 8, LANES), lambda r, j, k: (0, j, 0)),
                  pl.BlockSpec((8, LANES), lambda r, j, k: (j, 0)),
                  state],
        out_specs=[stream, state],
        out_shape=[jax.ShapeDtypeStruct((rows, t, width // LANES, LANES), f32),
                   jax.ShapeDtypeStruct((rows, n, width // LANES, LANES),
                                        f32)],
        compiler_params=params, interpret=interpret,
        name="selective_scan",
    )(scalars(b), scalars(c), tiles(x), tiles(dt), tiles(a), tiles(d),
      tiles(h0))
    return y.reshape(rows, t, width), h.reshape(rows, n, width)


def selective_scan(x, dt, a, b, c, d, mask=None, h0=None):
    """A whole prompt bucket: as :func:`selective_scan_loop`. The kernel
    where the program is lowered for a TPU and the shape allows
    (:func:`selective_scan_applies`), the loop elsewhere."""
    dt = _masked(dt, mask)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]), jnp.float32)
    if not selective_scan_applies(x.shape[1], x.shape[2]):
        return selective_scan_loop(x, dt, a, b, c, d, None, h0)
    return jax.lax.platform_dependent(
        x, dt, a, b, c, d, h0,
        tpu=selective_scan_kernel,
        default=lambda *v: selective_scan_loop(*v[:6], None, v[6]))
