"""The routed experts' product for a FEW rows: one Pallas kernel over the
matrices of the experts the rows chose, read where they lie.

A dropless routed feed-forward (``conf.layers_moe.RoutedExpertsLayer``)
adds, for row ``r``, ``sum_e w[r, e] * Wd_e (silu(Wg_e x_r) * Wu_e x_r)``
over the experts ``e`` it holds, ``w[r, e]`` zero where the row did not
choose ``e``. A decode step has tens of rows and hundreds of experts: the
time is the reading of the matrices (three of ``d x h`` an expert), and an
expert no row chose has nothing to add. The batched product over every
expert reads them all; the grouped product (``jax.lax.ragged_dot``) skips
the unchosen ones and streams the rest at half the memory's rate
(``tools/chip/moe_crossover.py``).

:func:`touched_experts_ffn` walks a compact list of the TOUCHED experts
(``sizes > 0``), one expert a grid step, the grid as long as the list (a
traced length, as ``ops.attention.paged_decode_attention``'s list of live
pages). The list is scalar-prefetched and the index maps of the three
stacks read the expert's id from it: an expert nobody chose is never
fetched and costs no step, and the stacks are taken as they lie
(``[held, d, h]``, ``[held, d, h]``, ``[held, h, n_out]``), no copy or
gather in front. The BlockSpec pipeline fetches the next expert's
matrices while the matrix unit works through this one's: ALL the rows go
through an expert at once, so its time is the loading of the weight tiles
whatever the rows are, and it hides behind the copies. The ``[rows,
n_out]`` float32 sum stays in VMEM over the whole list and is written
once.

The arithmetic is the batched product's: operands in the weights' type,
float32 accumulation, ``silu`` and the product of the two halves in
float32, the hidden activations rounded to the weights' type before
``Wd``, the rows' weights and the sum over experts in float32; less the
terms ``0 * finite`` of the experts that are not read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# bytes of matrices a grid step may hold (the pipeline keeps two steps'
# worth in VMEM): an expert whose three matrices are larger goes through
# in blocks of its hidden width, a block a step
STEP_BYTES_MAX = 16 << 20


def touched_experts_applies(stack_shape, n_out: int) -> bool:
    """Whether the TPU takes a ``[held, d, h]`` stack of experts by
    :func:`touched_experts_ffn`, from the shapes alone: the three widths
    fill whole 128-lane tiles."""
    _, d, h = stack_shape
    return d % LANES == 0 and h % LANES == 0 and n_out % LANES == 0


def hidden_blocks(d: int, h: int, n_out: int, itemsize: int) -> int:
    """Blocks an expert's hidden width goes through in: the fewest (a
    power of two) that keep a step's matrices within ``STEP_BYTES_MAX``
    in whole 128-lane tiles."""
    blocks = 1
    while ((2 * d + n_out) * (h // blocks) * itemsize > STEP_BYTES_MAX
           and (h // blocks) % (2 * LANES) == 0):
        blocks *= 2
    return blocks


def touched_list(sizes):
    """``(ids [held] int32, count)``: the experts with ``sizes > 0`` in
    order, then the last of them again (those steps never run), and how
    many they are. A comparison of places, no sort and no scatter."""
    held = sizes.shape[0]
    touched = sizes > 0
    place = jnp.cumsum(touched, dtype=jnp.int32) - 1
    count = place[-1] + 1
    slot = jnp.arange(held, dtype=jnp.int32)
    at = jnp.minimum(slot, jnp.maximum(count - 1, 0))
    ids = jnp.sum(jnp.where(touched[None, :] & (place[None, :] == at[:, None]),
                            slot[None, :], 0), axis=1, dtype=jnp.int32)
    return ids, count


def _touched_experts_kernel(ids_ref, count_ref, x_ref, w_ref, wg_ref, wu_ref,
                            wd_ref, o_ref, limit=0.0):
    """Grid step ``(t, j)``: block ``j`` of the hidden width of expert
    ``ids_ref[t]``. ``x_ref: [rows, d]``, ``w_ref: [rows, held]`` float32,
    ``wg_ref, wu_ref: [1, d, hb]``, ``wd_ref: [1, hb, n_out]``, ``o_ref:
    [rows, n_out]`` float32, the same block at every step. The grid has
    one step even where the list is empty: that step zeroes the sum and
    reads no matrix into it. ``limit``: the SwiGLU's clamp
    (``conf.layers_hybrid.swiglu``)."""
    t, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when((t == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < count_ref[0])
    def _expert():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=f32)
        if limit:
            g = jnp.minimum(g, limit)
        up = jax.nn.silu(g)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=f32)
        hidden = up * (jnp.clip(u, -limit, limit) if limit else u)
        y = jnp.dot(hidden.astype(x.dtype), wd_ref[0],
                    preferred_element_type=f32)
        lane = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)
        w = jnp.sum(jnp.where(lane == ids_ref[t], w_ref[...], 0.0), axis=1,
                    keepdims=True)                      # [rows, 1]
        o_ref[...] += y * w


# jitted so that a decoder's expert layers share one trace and one lowered
# body of the kernel (``ops.attention.paged_decode_attention``)
@functools.partial(jax.jit, static_argnames=("interpret", "limit"))
def touched_experts_ffn(x, Wg, Wu, Wd, w, sizes,
                        interpret: Optional[bool] = None, limit: float = 0.0):
    """``sum_e w[:, e, None] * (silu(x Wg_e) * (x Wu_e)) Wd_e`` over the
    experts with ``sizes[e] > 0`` alone: ``x: [rows, d]`` in the stacks'
    type, ``Wg, Wu: [held, d, h]``, ``Wd: [held, h, n_out]``, ``w: [rows,
    held]`` float32 (zero where a row did not choose an expert),
    ``sizes: [held]`` the slots an expert got. Returns ``[rows, n_out]``
    float32; zeros where ``sizes`` is zero everywhere. The matrices of an
    expert with no slot are never read: they may hold anything.
    ``interpret=None`` runs the Pallas interpreter off the TPU; a TPU
    needs :func:`touched_experts_applies` besides. ``limit``: every
    SwiGLU clamped (``conf.layers_hybrid.swiglu``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = x.shape
    held, _, h = Wg.shape
    n_out = Wd.shape[-1]
    dt = Wg.dtype
    ids, count = touched_list(sizes)
    # whole sublane tiles of the stacks' type (a row more costs nothing:
    # an expert's time is the loading of its weight tiles)
    rows = -(-n // 16) * 16
    x = jnp.pad(x.astype(dt), ((0, rows - n), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, rows - n), (0, 0)))
    blocks = hidden_blocks(d, h, n_out, dt.itemsize)
    hb = h // blocks
    whole = lambda t, j, ids, count: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(count, 1), blocks),
        in_specs=[pl.BlockSpec((rows, d), whole),
                  pl.BlockSpec((rows, held), whole),
                  pl.BlockSpec((1, d, hb),
                               lambda t, j, ids, count: (ids[t], 0, j)),
                  pl.BlockSpec((1, d, hb),
                               lambda t, j, ids, count: (ids[t], 0, j)),
                  pl.BlockSpec((1, hb, n_out),
                               lambda t, j, ids, count: (ids[t], j, 0))],
        out_specs=pl.BlockSpec((rows, n_out), whole),
    )
    params = None
    if not interpret:
        # two steps' matrices, the rows' blocks twice over (the pipeline's
        # two buffers), the body's float32 intermediates, and room
        step = (2 * d + n_out) * hb * dt.itemsize
        rows_in = rows * (d * dt.itemsize + 4 * held + 4 * n_out)
        body = rows * (hb * (8 + dt.itemsize) + 4 * n_out)
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(2 * step + 2 * rows_in + body + (8 << 20)))
    kernel = _touched_experts_kernel
    if limit:
        kernel = functools.partial(_touched_experts_kernel, limit=limit)
    y = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n_out), jnp.float32),
        compiler_params=params, interpret=interpret,
    )(ids, count.reshape(1), x, w, Wg, Wu, Wd)
    return y[:n]
