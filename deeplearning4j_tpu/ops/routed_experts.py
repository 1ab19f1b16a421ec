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

A PROMPT's thousands of tokens give every expert hundreds of slots (token
x chosen expert): the time is the matrix unit's. :func:`grouped_experts_ffn`
is the same kernel with its rows tiled: the slots sorted by expert
(:func:`grouped_experts_ragged`'s order), each expert's slots laid at the
start of whole tiles of ``ROW_TILE`` rows (no tile holds two experts,
an expert nobody chose holds none), and one grid step a tile, the grid as
long as the list of tiles. Consecutive tiles of one expert name the same
matrices, so the pipeline fetches an expert's matrices once. Its arithmetic
is :func:`grouped_experts_ragged`'s (``jax.lax.ragged_dot``, what every
other platform runs), and so is its gradient.
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
    (:func:`swiglu`)."""
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
    SwiGLU clamped (:func:`swiglu`)."""
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


# --- a prompt's product: the slots sorted by expert, in tiles of rows -------

# rows a grid step of :func:`grouped_experts_ffn` takes: the matrix unit's
# 128-row pass. A longer tile saves a little inside the kernel and pads
# more rows into the gathers around it. Read on the v5e, the whole product
# of one layer in ms (tools/chip/moe_crossover.py prompt; PERF.md section
# 6), ``jax.lax.ragged_dot`` | tiles of 128 | 256 | 512 rows:
#   Trinity-Mini (128 of 128 experts)   512 tokens   5.55 |  3.75 |  5.04 |  8.31
#                                      4096 tokens  12.29 |  9.17 |  9.69 | 11.73
#   GigaChat3.5 (16 of 256 experts)    1024 tokens   7.49 |  4.05 |  4.37 |  6.43
#                                      4096 tokens  16.23 | 10.14 |  9.92 | 11.86
ROW_TILE = 128


def swiglu(g, up, limit: float = 0.0):
    """``silu(g) * up()``; with ``limit`` the gate's input is clamped from
    above and the other half into ``[-limit, limit]`` first. ``up`` is
    called after the gate's activation, in the order the unclamped
    expression has always been traced in. Every SwiGLU of the package
    (``conf.layers_hybrid``, ``conf.layers_moe``, the kernels here)."""
    if limit:
        return (jax.nn.silu(jnp.minimum(g, limit))
                * jnp.clip(up(), -limit, limit))
    return jax.nn.silu(g) * up()


def grouped_experts_ragged(x, Wg, Wu, Wd, group, w, sizes, top_k: int,
                           limit: float = 0.0):
    """The grouped product by ``jax.lax.ragged_dot``: ``x: [n, d]`` in the
    stacks' type, each of its ``n * top_k`` slots (token ``s // top_k``)
    with its expert ``group[s]`` (``held``: not this holder's; it sorts
    behind every group) and weight ``w[s]`` float32; ``sizes: [held]`` the
    slots an expert got. Returns ``[n, n_out]`` float32: each token's sum
    over its slots held here. An expert nobody chose is never read."""
    f32 = jnp.float32
    order = jnp.argsort(group)
    xs = x[order // top_k]
    hidden = swiglu(
        jax.lax.ragged_dot(xs, Wg, sizes, preferred_element_type=f32),
        lambda: jax.lax.ragged_dot(xs, Wu, sizes, preferred_element_type=f32),
        limit)
    ys = jax.lax.ragged_dot(hidden.astype(x.dtype), Wd, sizes,
                            preferred_element_type=f32)
    # rows behind the last group are whatever the product left
    ys = jnp.where((jnp.arange(order.size) < jnp.sum(sizes))[:, None],
                   ys * w[order][:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
    return ys[back].reshape(x.shape[0], top_k, -1).sum(axis=1)


def tile_layout(group, sizes, tm: int):
    """Where each slot goes in whole tiles of ``tm`` rows: the slots sorted
    by expert (as :func:`grouped_experts_ragged` sorts them), each expert's
    at the start of tiles of its own. ``group: [slots]`` (``held``: not
    this holder's, no tile), ``sizes: [held]``. Returns ``(ids [tiles]
    int32, count, slot [tiles * tm], valid [tiles * tm], row [slots])``:
    tile ``t < count``'s expert (past the list, a held expert's id: those
    steps never run); a row's slot and whether it holds one (a padding
    row's slot is 0); a slot's row (0 where not held here). ``tiles``
    is the static bound ``slots // tm + min(held, slots)``: an expert's
    slots fill whole tiles but its last. Gathers and cumulative sums over
    ``ceil(sizes / tm)``, no scatter into the rows."""
    held, slots = sizes.shape[0], group.shape[0]
    n_tiles = slots // tm + min(held, slots)
    tiles = (sizes + tm - 1) // tm
    end = jnp.cumsum(tiles, dtype=jnp.int32)
    start = end - tiles
    count = end[-1]
    first = jnp.cumsum(sizes, dtype=jnp.int32) - sizes   # an expert's 1st
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    ids = jnp.minimum(jnp.sum(end[None, :] <= t[:, None], axis=1,
                              dtype=jnp.int32), held - 1)
    r = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    e = jnp.repeat(ids, tm)
    rank = r - start[e] * tm                    # the row's place in its expert
    valid = (r < count * tm) & (rank < sizes[e])
    order = jnp.argsort(group)
    slot = order[jnp.where(valid, first[e] + rank, 0)]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(slots,
                                                          dtype=order.dtype))
    g = jnp.minimum(group, held - 1)
    row = jnp.where(group < held, start[g] * tm + back - first[g], 0)
    return ids, count, slot, valid, row


def _grouped_experts_kernel(ids_ref, count_ref, x_ref, w_ref, wg_ref, wu_ref,
                            wd_ref, o_ref, blocks=1, limit=0.0):
    """Grid step ``(t, j)``: block ``j`` of ``blocks`` of the hidden width
    of expert ``ids_ref[t]`` over row tile ``t``. ``x_ref: [tm, d]``,
    ``w_ref: [tm, 1]`` float32 (zero in a padding row), ``wg_ref, wu_ref:
    [1, d, hb]``, ``wd_ref: [1, hb, n_out]``, ``o_ref: [tm, n_out]``
    float32, the tile's own, the same block at every ``j``: the blocks'
    products are summed there and the sum multiplied by the rows' weights
    at the last. The grid has one step where the list is empty: that step
    writes zeros."""
    t, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(t < count_ref[0])
    def _tile():
        x = x_ref[...]
        hidden = swiglu(jnp.dot(x, wg_ref[0], preferred_element_type=f32),
                        lambda: jnp.dot(x, wu_ref[0],
                                        preferred_element_type=f32), limit)
        y = jnp.dot(hidden.astype(x.dtype), wd_ref[0],
                    preferred_element_type=f32)
        if blocks == 1:
            o_ref[...] = y * w_ref[...]
            return

        @pl.when(j == 0)
        def _first():
            o_ref[...] = y

        @pl.when((j > 0) & (j < blocks - 1))
        def _more():
            o_ref[...] = o_ref[...] + y

        @pl.when(j == blocks - 1)
        def _last():
            o_ref[...] = (o_ref[...] + y) * w_ref[...]

    @pl.when(t >= count_ref[0])
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


def tiles_ffn(xp, wp, Wg, Wu, Wd, ids, count, limit: float, interpret: bool):
    """The kernel over the rows laid out by :func:`tile_layout`: ``xp:
    [tiles * tm, d]`` in the stacks' type, ``wp: [tiles * tm, 1]`` float32,
    tile ``t < count`` of expert ``ids[t]``. Returns ``[tiles * tm,
    n_out]`` float32, each row its product times its weight; the rows of
    tiles past ``count`` are not written."""
    rows, d = xp.shape
    tm = rows // ids.shape[0]
    _, _, h = Wg.shape
    n_out = Wd.shape[-1]
    dt = Wg.dtype
    blocks = hidden_blocks(d, h, n_out, dt.itemsize)
    hb = h // blocks
    tile = lambda t, j, ids, count: (t, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(count, 1), blocks),
        in_specs=[pl.BlockSpec((tm, d), tile),
                  pl.BlockSpec((tm, 1), tile),
                  pl.BlockSpec((1, d, hb),
                               lambda t, j, ids, count: (ids[t], 0, j)),
                  pl.BlockSpec((1, d, hb),
                               lambda t, j, ids, count: (ids[t], 0, j)),
                  pl.BlockSpec((1, hb, n_out),
                               lambda t, j, ids, count: (ids[t], j, 0))],
        out_specs=pl.BlockSpec((tm, n_out), tile),
    )
    params = None
    if not interpret:
        # two steps' matrices and tiles (the pipeline's two buffers; the
        # weights' column pads to 128 lanes), the body's float32
        # intermediates, and room
        step = (2 * d + n_out) * hb * dt.itemsize
        rows_io = tm * (d * dt.itemsize + 4 * LANES + 4 * n_out)
        body = tm * (hb * (8 + dt.itemsize) + 4 * n_out)
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(2 * step + 2 * rows_io + body + (8 << 20)))
    kernel = functools.partial(_grouped_experts_kernel, blocks=blocks,
                               limit=limit)
    with jax.named_scope("moe.grouped"):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, n_out), jnp.float32),
            compiler_params=params, interpret=interpret,
            name="grouped_experts_ffn",
        )(ids, count.reshape(1), xp, wp, Wg, Wu, Wd)


@functools.partial(jax.jit,
                   static_argnames=("top_k", "tm", "limit", "interpret"))
def _grouped_tiles(x, Wg, Wu, Wd, group, w, sizes, top_k, tm, limit,
                   interpret):
    n = x.shape[0]
    held = Wg.shape[0]
    ids, count, slot, valid, row = tile_layout(group, sizes, tm)
    xp = x.astype(Wg.dtype)[slot // top_k]
    wp = jnp.where(valid, w.astype(jnp.float32)[slot], 0.0)[:, None]
    y = tiles_ffn(xp, wp, Wg, Wu, Wd, ids, count, limit, interpret)
    ys = jnp.where((group < held)[:, None], y[row], 0.0)
    return ys.reshape(n, top_k, -1).sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def grouped_experts_ffn(x, Wg, Wu, Wd, group, w, sizes, top_k: int,
                        limit: float = 0.0, interpret: Optional[bool] = None):
    """:func:`grouped_experts_ragged`'s sum by one Pallas kernel over the
    slots sorted by expert, a tile of ``ROW_TILE`` rows of one expert
    a grid step (:func:`tile_layout`), the three stacks read where they
    lie; the same operands, the same result to float32 rounding. The
    matrices of an expert with no slot are never read: they may hold
    anything. The gradient is :func:`grouped_experts_ragged`'s.
    ``interpret=None`` runs the Pallas interpreter off the TPU; a TPU
    needs :func:`touched_experts_applies` besides. ``limit``: every
    SwiGLU clamped (:func:`swiglu`)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped_tiles(x, Wg, Wu, Wd, group, w, sizes, top_k=top_k,
                          tm=ROW_TILE, limit=limit, interpret=interpret)


def _grouped_fwd(x, Wg, Wu, Wd, group, w, sizes, top_k, limit, interpret):
    return (grouped_experts_ffn(x, Wg, Wu, Wd, group, w, sizes, top_k, limit,
                                interpret),
            (x, Wg, Wu, Wd, group, w, sizes))


def _grouped_bwd(top_k, limit, interpret, res, dy):
    x, Wg, Wu, Wd, group, w, sizes = res
    _, vjp = jax.vjp(lambda x, Wg, Wu, Wd, w: grouped_experts_ragged(
        x, Wg, Wu, Wd, group, w, sizes, top_k, limit), x, Wg, Wu, Wd, w)
    dx, dWg, dWu, dWd, dw = vjp(dy)
    return dx, dWg, dWu, dWd, None, dw, None


grouped_experts_ffn.defvjp(_grouped_fwd, _grouped_bwd)
