"""Scaled dot-product attention: reference, blockwise (XLA), and Pallas flash.

Reference counterparts: ``sd.nn.multiHeadDotProductAttention`` /
``org.nd4j.linalg.api.ops.impl.transforms.custom.MultiHeadDotProductAttention``
and the attention layers in ``org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer}`` — the reference materializes the full [Tq, Tk]
attention matrix per head on-device. TPU-native design: three tiers sharing
one semantics,

- ``reference_attention``: plain jnp, full materialization (oracle for tests).
- ``blockwise_attention``: online-softmax ``lax.scan`` over key blocks —
  O(T) memory at the XLA level, differentiable, runs on any backend. This is
  FlashAttention's math without a hand kernel; used as the CPU path and as the
  local compute inside ring attention (ops/ring.py).
- ``flash_attention``: Pallas TPU kernel (fwd + custom-VJP bwd), blocks
  streamed HBM→VMEM by the pipeline, f32 accumulators in VMEM scratch,
  softmax max/denominator saved lane-replicated for the backward. Grid
  iterates key blocks in the innermost (sequential) dimension so scratch
  persists across them; on the v5e this is the fastest trainable path at
  long T (BASELINE.md round-2 table) and the only one at T=16k.

All take ``q, k, v: [batch, heads, time, head_dim]``, optional
``key_mask: [batch, time_k]`` (1.0 = valid, 0.0 = padding) and ``causal``.
``dot_product_attention`` dispatches by backend/size.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _scale(q, scale):
    return (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale


# ---------------------------------------------------------------------------
# Tier 0: reference (oracle)
# ---------------------------------------------------------------------------

def reference_attention(q, k, v, key_mask=None, causal=False, scale=None):
    """Full-materialization attention; the test oracle."""
    sm = _scale(q, scale)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None] + (tk - tq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ---------------------------------------------------------------------------
# KV-cached single-token decode (autoregressive serving)
# ---------------------------------------------------------------------------
#
# THE cache layout, stated once: keys and values are stored
# ``[batch, max_len, heads * head_dim]`` — a position's whole projection
# (what ``x @ Wk`` produces, before any split into heads) in the minor
# (lane) dimension, positions in the second-minor (sublane) one. Why:
#
# - The TPU tiles an array's two minor dimensions by (8, 128). Bucket
#   lengths are powers of two and model widths multiples of 128, so the
#   tiled cache is exactly its logical size; with ``[.., heads,
#   head_dim]`` minor, 20 heads of 64 filled 64 of 128 lanes and 20 of
#   24 sublanes and attention streamed 2.4 times the bytes.
# - A token's write is one full-width sublane row, in place, and it is
#   the layout XLA keeps the array in at rest, so a donated cache aliases
#   from the executable's arguments through the fused decode loop to its
#   results with no relayout copy on either side.
# - The per-head products cannot reshape the lane dimension into
#   ``[heads, head_dim]`` (that IS a relayout of the whole cache), so
#   they run on the matrix unit against a block-diagonal operand built
#   from the small side: scores ``K[s, :] @ Qb`` with ``Qb[(g, d), g'] =
#   q[g, d]`` where ``g == g'`` and 0 elsewhere, output ``P^T @ V`` of
#   which each head keeps its own ``head_dim`` columns. The zeros add
#   exact zeros. ``Precision.HIGHEST`` keeps float32 operands float32
#   (the matrix unit's default rounds them to bfloat16). A bfloat16
#   cache's paged read takes the same numbers in one bfloat16 pass
#   (:func:`exact_parts_dot`): at ONE KV head, six float32 passes of 20
#   rows were a third of the read's time (PERF.md §6, PR 38).

def _block_diagonal(q):
    """``q: [batch, time, heads, head_dim]`` as the right-hand side of the
    score product: ``[batch, heads * head_dim, time * heads]``, column
    ``(t, g)`` holding query ``t``'s head ``g`` in rows
    ``g * head_dim .. (g + 1) * head_dim`` and zeros in every other
    head's rows."""
    b, t, h, d = q.shape
    eye = jnp.eye(h, dtype=q.dtype)
    blocks = q[:, :, :, :, None] * eye[:, None, :]       # [b, t, h, d, g]
    return jnp.transpose(blocks, (0, 2, 3, 1, 4)).reshape(b, h * d, t * h)


def chunk_decode_attention(q, k_cache, v_cache, positions, scale=None):
    """A ``Tq``-token window of causal attention against a preallocated
    KV cache. ``q: [batch, time, heads, head_dim]`` holds the window's
    queries, ``k_cache/v_cache: [batch, max_len, heads * head_dim]``
    (cache layout, above) every previously-written key/value, including
    the window's own (written by the caller via :func:`cache_update`
    before this call). Query ``i`` of row ``b`` sits at cache slot
    ``positions[b] + i``, so it may attend slots ``0 .. positions[b] + i``
    inclusive; everything beyond is masked to ``NEG_INF`` exactly like
    the padding mask in :func:`reference_attention` (exp underflows to
    0.0, so garbage in unwritten slots can never leak into the output as
    long as it is finite — zeros or stale keys from a retired sequence
    both qualify). One softmax over the whole row of scores, no online
    softmax: the row is small and one fused pass is its fastest shape.

    ``Tq = 1`` is an ordinary decode step (:func:`decode_attention`);
    a wider window is a chunk of tokens in one dispatch. Returns
    ``[batch, time, heads, head_dim]``."""
    b, t, h, d = q.shape
    s = k_cache.shape[1]
    sm = _scale(q, scale)
    scores = jnp.einsum("bse,bek->bsk", k_cache, _block_diagonal(q),
                        precision=jax.lax.Precision.HIGHEST) * sm
    slot = jnp.arange(s)[None, :, None]
    qpos = positions[:, None, None] + jnp.arange(t)[None, None, :]
    scores = jnp.where((slot <= qpos)[..., None],
                       scores.reshape(b, s, t, h), NEG_INF)
    p = jax.nn.softmax(scores, axis=1)
    # [t * heads, heads * head_dim]: head g's output is columns
    # g * head_dim .. of row (t, g); the other blocks are discarded
    out = jnp.einsum("bsk,bse->bke", p.reshape(b, s, t * h), v_cache,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("btggd->btgd", out.reshape(b, t, h, h, d))


def decode_attention(q, k_cache, v_cache, positions, scale=None):
    """One decode step of causal attention against the KV cache:
    :func:`chunk_decode_attention` at ``Tq = 1``. ``q: [batch, heads,
    head_dim]`` is the new token's query, ``positions: [batch]`` the
    cache slot it occupies (its own k/v already written there); returns
    ``[batch, heads, head_dim]``."""
    return chunk_decode_attention(q[:, None], k_cache, v_cache, positions,
                                  scale)[:, 0]


def exact_parts_dot(x, y, y_contract: int):
    """``x @ y`` (``x``'s axis 1 against ``y``'s axis ``y_contract``) at
    ``Precision.HIGHEST`` for a float32 ``x: [rows, k]`` and a bfloat16
    ``y``, in ONE bfloat16 pass of the matrix unit. ``y`` is exact in one
    bfloat16 part, so the float32 product has three partial products that
    are not zero: each of ``x``'s three exact bfloat16 parts (``hi =
    bf16(x)``, ``mid = bf16(x - hi)``, ``lo = bf16(x - hi - mid)``) times
    ``y``. Stacked along the rows they are one product of ``3 * rows``
    rows against ``y`` (which the matrix unit loads once), accumulated in
    float32; the three row blocks summed are the HIGHEST product to float32
    rounding. Nothing is rounded to one bfloat16 part. On the TPU ``rows``
    is a multiple of 16, so that the parts stack on whole bfloat16 tiles.
    Returns ``[rows, n]`` float32."""
    n = x.shape[0]
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    z = jax.lax.dot_general(
        jnp.concatenate([hi, mid, lo], axis=0), y,
        (((1,), (y_contract,)), ((), ())),
        preferred_element_type=jnp.float32)
    return z[:n] + z[n:2 * n] + z[2 * n:]


def _paged_decode_kernel(pos_ref, row_ref, page_ref, q_ref, own_ref, k_ref,
                         v_ref, o_ref, m_sc, l_sc, acc_sc, *, sm, page,
                         grouped=False, split=False, value_width=0):
    """Online-softmax decode over the LIVE KV pages of every row. The grid
    is one flat list of (row, page) pairs, a row's pages in order and the
    rows one after another, as long as the rows' positions make it (its
    length is a traced value): no step is spent on a page wholly past
    ``positions[b]``, and the pipeline fetches a row's first page while
    the row before it finishes. ``pos_ref``, ``row_ref`` and ``page_ref``
    are scalar-prefetched: the index maps read them before the body runs.
    The scratch accumulates over a row's pages; it is reset at a row's
    first page and the row's output written at its last. The two
    products are the block-diagonal matrix products of
    :func:`chunk_decode_attention` with the heads along the SUBLANES of
    every intermediate (scores ``[heads, page]``, accumulator ``[heads,
    heads * head_dim]``), which makes them the two plain forms of the
    matrix unit, ``Q K^T`` and ``P V``, with no transpose of a page:
    ``q_ref[g]`` holds head ``g``'s query in its own ``head_dim`` columns
    and zeros elsewhere, ``own_ref[g, (g', d)]`` is 1 where ``g == g'``.
    ``grouped`` (several query heads a KV head): a head's query sits in
    its KV GROUP's columns, the heads of a group share them, and the
    store keeps every head's row apart (``[heads, e]``, zeros outside the
    head's own group). ``split`` (a bfloat16 cache): the page stays
    bfloat16 and both products are :func:`exact_parts_dot`, the float32
    operand's three exact parts in one pass, in place of a float32 page
    at HIGHEST. ``v_ref`` None: the values are the first ``value_width``
    columns of the keys' own page (a latent cache: one array, one DMA)."""
    w = pl.program_id(0)
    j = page_ref[w]
    pos = pos_ref[row_ref[w]]
    highest = jax.lax.Precision.HIGHEST

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def values():                                  # [page, e or value_width]
        return v_ref[0] if v_ref is not None else k_ref[0][:, :value_width]

    if split:
        s = exact_parts_dot(q_ref[0], k_ref[0], 1) * sm           # [h, page]
    else:
        k = k_ref[0].astype(jnp.float32)           # [page, e]
        v = values().astype(jnp.float32)
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            precision=highest, preferred_element_type=jnp.float32) * sm
    # boundary page: slots past positions[b] masked exactly like the
    # masked full-cache read (exp underflows to 0.0 — garbage in
    # unwritten slots can never leak)
    slot = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(slot <= pos, s, NEG_INF)
    m_prev, l_prev = m_sc[...], l_sc[...]      # [h, 1]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_next)
    alpha = jnp.exp(m_prev - m_next)
    m_sc[...] = m_next
    l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + (                           # [h, e]
        exact_parts_dot(p, values(), 0) if split else jnp.dot(
            p, v, precision=highest, preferred_element_type=jnp.float32))

    @pl.when(j == pos // page)
    def _store():
        l_inv = 1.0 / l_sc[...]     # slot 0 is live in every row: l > 0
        if grouped:
            o_ref[0] = (acc_sc[...] * l_inv * own_ref[...]).astype(
                o_ref.dtype)                                    # [h, e]
        else:
            o_ref[0] = jnp.sum(acc_sc[...] * l_inv * own_ref[...], axis=0,
                               keepdims=True).astype(o_ref.dtype)   # [1, e]


def _latent_page_kernel(kernel, value_width, pos_ref, row_ref, page_ref,
                        q_ref, own_ref, kv_ref, *rest):
    """:func:`_paged_decode_kernel` over a latent cache: one page operand,
    the values its first ``value_width`` columns."""
    kernel(pos_ref, row_ref, page_ref, q_ref, own_ref, kv_ref, None, *rest,
           value_width=value_width)


# jitted so that a decoder's layers share ONE trace and one lowered body of
# the kernel: 36 layers each tracing and lowering their own cost the decode
# window 1.7 s more at every start, warm or cold, and a third more StableHLO
@functools.partial(jax.jit, static_argnames=("scale", "page", "interpret",
                                             "groups", "value_width"))
def paged_decode_attention(q, k_cache, v_cache, positions, scale=None,
                           page: int = 64,
                           interpret: Optional[bool] = None,
                           groups: int = 0, value_width: int = 0):
    """:func:`decode_attention` as a Pallas kernel gathering KV **pages**
    in-kernel: ``page``-slot blocks of the cache stream HBM→VMEM one DMA
    per page, and only the pages that hold a position up to
    ``positions[b]`` are ever visited (the grid is the list of live
    pages, built from the positions; the boundary page masks per-slot).
    Same signature and semantics as the masked full-cache read — ``q:
    [batch, heads, head_dim]``, ``k_cache/v_cache: [batch, max_len,
    heads * head_dim]``, ``positions: [batch]`` (clamped into the cache,
    as :func:`cache_update` clamps them) — and bitwise the same masking
    rule, so the parity tests pin it directly against
    :func:`decode_attention`.

    ``page`` must divide ``max_len`` (the pow2 bucket ladder guarantees
    a divisor exists). ``interpret=None`` auto-enables the Pallas
    interpreter off-TPU. ``groups`` (0: one query head a KV head): the
    caches hold ``groups`` KV heads, ``heads / groups`` query heads
    each. A bfloat16 cache is read as it lies and multiplied by
    :func:`exact_parts_dot` (the heads padded to whole bfloat16 tiles); a
    float32 one at HIGHEST.

    ``v_cache`` None (a LATENT cache, ``groups`` 1): the values are the
    first ``value_width`` columns of the keys themselves, read from the
    same page; the key width ``d`` is the cache's and the result is
    ``[batch, heads, value_width]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    s, e = k_cache.shape[1:]
    page = min(int(page), s)
    if s % page:
        raise ValueError(f"page {page} must divide cache length {s}")
    if groups and (h % groups or e != groups * d):
        raise ValueError(f"{h} heads of {d} must share {groups} KV heads of "
                         f"a {e}-wide cache")
    sm = _scale(q, scale)
    latent = v_cache is None
    if latent and (groups != 1 or not 0 < value_width <= e):
        raise ValueError("a latent cache takes groups=1 and 0 < value_width "
                         f"<= {e}")
    ev = value_width if latent else e           # the values' width
    split = k_cache.dtype == (k_cache if latent else v_cache).dtype \
        == jnp.bfloat16
    rows = -(-h // 16) * 16 if split else h

    def pad(x):     # [.., h, e] -> [.., rows, e], zeros below
        if rows == h:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, rows - h), (0, 0)])

    pos = jnp.clip(positions.astype(jnp.int32), 0, s - 1)
    if groups:      # a head's query and output sit in its GROUP's columns
        from deeplearning4j_tpu.ops.block_sparse import (
            _grouped_block_diagonal,
        )
        own = jnp.repeat((jnp.arange(h) // (h // groups))[:, None]
                         == jnp.arange(groups), d, axis=1).astype(jnp.float32)
        q_rows = jnp.swapaxes(_grouped_block_diagonal(
            q[:, None].astype(jnp.float32), groups), 1, 2)
    else:
        own = jnp.repeat(jnp.eye(h, dtype=jnp.float32), d, axis=1)  # [h, e]
    if latent:
        own = own[:, :ev]
    # the flat list of live pages: step w reads page page_of[w] of row
    # row_of[w]; steps past the list's end (never run) name the last pair
    pages = pos // page + 1
    ends = jnp.cumsum(pages)
    steps = jnp.minimum(jnp.arange(b * (s // page), dtype=jnp.int32),
                        ends[-1] - 1)
    row_of = jnp.sum(steps[:, None] >= ends[None, :], axis=1,
                     dtype=jnp.int32)
    page_of = steps - (ends - pages)[row_of]

    def row_map(w, p, r, j):
        return (r[w], 0, 0)

    def kv_map(w, p, r, j):
        return (r[w], j[w], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, rows, e), row_map),
                  pl.BlockSpec((rows, ev), lambda w, p, r, j: (0, 0)),
                  pl.BlockSpec((1, page, e), kv_map)]
        + ([] if latent else [pl.BlockSpec((1, page, e), kv_map)]),
        out_specs=pl.BlockSpec((1, rows if groups else 1, ev), row_map),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, ev), jnp.float32)],
    )
    params = None
    if not interpret:
        params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    kernel = functools.partial(_paged_decode_kernel, sm=sm, page=page,
                               grouped=bool(groups), split=split)
    if latent:
        kernel = functools.partial(_latent_page_kernel, kernel, ev)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows if groups else 1, ev),
                                       q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(pos, row_of, page_of,
      pad(q_rows if groups else jnp.swapaxes(_block_diagonal(q[:, None]), 1,
                                             2)),
      pad(own), k_cache, *([] if latent else [v_cache]))
    if latent:
        return out[:, :h]
    if groups:      # the other groups' columns of a head's row are zeros
        return out[:, :h].reshape(b, h, groups, d).sum(axis=2)
    return out.reshape(b, h, d)


# The decode step's page. What a row reads is rounded up to it, and a
# grid step costs a fixed third of a microsecond beside its DMA: at
# [8, 1024, 1280] float32 on the v5e, 128 reads a fifth-full bucket in
# 40 us a layer (64: 42, 256: 45) and a full one in 117 (64: 145, 256:
# 111) against the masked read's 113 (PERF.md §5). A narrow cache's page
# is lengthened until its keys fill DECODE_PAGE_BYTES: a grid step costs
# some 0.45 us beside its DMA whatever the page holds, and ONE bfloat16 KV
# head of 128 holds 32 KB a page of 128. One such layer at 128 rows and
# about 1,070 live positions a row read in 599 us at 128 a page, 360 at
# 256, 231 at 512, 195 at 1,024; four KV heads at 32 rows and about 2,800
# in 465, 332, 273, 278 (PERF.md §6, PR 38).
DECODE_PAGE = 128
DECODE_PAGE_BYTES = 1 << 18


def decode_page(max_len: int, width: int, itemsize: int) -> Optional[int]:
    """The page :func:`bounded_decode_attention` reads a ``[batch,
    max_len, width]`` cache of ``itemsize``-byte elements by on the TPU,
    from the shape alone, or ``None`` where the kernel does not apply:
    the width must fill whole 128-lane tiles and the bucket must hold at
    least two pages (with one there is nothing to skip). The page is
    ``DECODE_PAGE`` times the largest power of two that keeps a page of
    keys within ``DECODE_PAGE_BYTES`` and still divides the bucket into
    two pages or more."""
    if width % 128 or max_len % DECODE_PAGE or max_len < 2 * DECODE_PAGE:
        return None
    page = DECODE_PAGE
    while (2 * page * width * itemsize <= DECODE_PAGE_BYTES
           and max_len % (2 * page) == 0 and max_len >= 4 * page):
        page *= 2
    return page


def bounded_decode_attention(q, k_cache, v_cache, positions, scale=None,
                             groups: int = 0):
    """:func:`decode_attention` bounded PER ROW by ``positions``, which
    is traced: a row that holds 150 positions costs 150 (rounded up to a
    page), a row that holds 1,000 costs 1,000, in one executable. On the
    TPU this is :func:`paged_decode_attention` at :func:`decode_page`;
    on any other platform, and for a shape the kernel does not take, the
    masked read of the whole bucket. The platform is the one the program
    is LOWERED for (``lax.platform_dependent``), not the host's default
    backend: a program compiled for a TPU from a CPU host gets the
    kernel, and a CPU run never pays the Pallas interpreter.

    ``groups`` (0: one query head a KV head): grouped KV heads, caches
    ``[batch, max_len, groups * head_dim]``; float32 out, and the scale
    is ``1 / sqrt(head_dim)`` (the masked grouped read knows no other).

    Returns ``(out [batch, heads, head_dim], read [batch] int32)``:
    ``read[b]`` is the number of cached positions the step streamed for
    row ``b`` (whole pages; the whole bucket where the bound is off)."""
    if groups and scale is not None:
        raise ValueError("bounded_decode_attention: grouped KV heads take "
                         "the default scale")
    s, e = k_cache.shape[1:]
    page = decode_page(s, e, k_cache.dtype.itemsize)

    def masked(q, k_cache, v_cache, positions):
        if groups:
            from deeplearning4j_tpu.ops.block_sparse import (
                dense_decode_attention,
            )
            out = dense_decode_attention(q, k_cache, v_cache, positions,
                                         groups)
        else:
            out = decode_attention(q, k_cache, v_cache, positions, scale)
        return out, jnp.full(positions.shape, s, jnp.int32)

    def paged(q, k_cache, v_cache, positions):
        out = paged_decode_attention(q, k_cache, v_cache, positions, scale,
                                     page=page, interpret=False,
                                     groups=groups)
        return out, (jnp.clip(positions, 0, s - 1) // page + 1) * page

    if page is None:
        return masked(q, k_cache, v_cache, positions)
    return jax.lax.platform_dependent(q, k_cache, v_cache, positions,
                                      tpu=paged, default=masked)


def latent_decode_attention(q, cache, positions, value_width: int,
                            scale: float):
    """One token of ABSORBED latent attention: every query head ``q:
    [batch, heads, e]`` against ONE latent head ``cache: [batch, max_len,
    e]`` whose first ``value_width`` columns are also its values (a latent
    vector beside a shared rotated key). Bounded per row as
    :func:`bounded_decode_attention`: on the TPU the paged kernel reads a
    page once for both products (:func:`paged_decode_attention` with no
    ``v_cache``) at :func:`decode_page` of the cache's width in whole
    128-lane tiles; elsewhere the masked read of the bucket. Returns ``(out
    [batch, heads, value_width] float32, read [batch] int32)``."""
    s, e = cache.shape[1:]
    page = decode_page(s, -(-e // 128) * 128, cache.dtype.itemsize)

    def masked(q, cache, positions):
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        c = cache.astype(f32)
        scores = jnp.einsum("bhe,bse->bhs", q.astype(f32), c,
                            precision=hi) * scale
        live = jnp.arange(s)[None, None, :] <= positions[:, None, None]
        p = jax.nn.softmax(jnp.where(live, scores, NEG_INF), axis=-1)
        out = jnp.einsum("bhs,bsv->bhv", p, c[..., :value_width],
                         precision=hi)
        return out, jnp.full(positions.shape, s, jnp.int32)

    def paged(q, cache, positions):
        out = paged_decode_attention(q, cache, None, positions, scale,
                                     page=page, interpret=False, groups=1,
                                     value_width=value_width)
        return out, (jnp.clip(positions, 0, s - 1) // page + 1) * page

    if page is None:
        return masked(q, cache, positions)
    return jax.lax.platform_dependent(q, cache, positions, tpu=paged,
                                      default=masked)


def cache_update(cache, new, positions):
    """Write a token block ``new: [batch, t, heads * head_dim]`` (t = 1
    for ordinary decode, more for a chunk of tokens) into
    ``cache: [batch, max_len, heads * head_dim]`` at per-sequence slot
    ``positions: [batch]``: one ``dynamic_update_slice`` a row (the slot
    index is traced, so one executable serves every position), ``t``
    whole sublane rows each, in place. Written out row by row and NOT as
    one vmapped update: that lowers to a scatter, which XLA expands into
    a ``while`` over the rows and, with the cache at its logical size,
    stages each whole cache through VMEM and back every step to run it.
    Out-of-range positions clamp to the last slot (``dynamic_update_slice``
    semantics) — harmless by construction: only retired rows ever sit at
    a position that high, and their slots are never attended. The scope
    ``cache.write`` is what ``telemetry.device_time`` files the write
    under, beneath the layer's own."""
    with jax.named_scope("cache.write"):
        for i in range(cache.shape[0]):
            cache = jax.lax.dynamic_update_slice(cache, new[i:i + 1],
                                                 (i, positions[i], 0))
    return cache


# ---------------------------------------------------------------------------
# Grouped KV heads, a sliding window, a ring cache (stock XLA)
# ---------------------------------------------------------------------------
#
# A WINDOW layer's query at position ``t`` sees keys ``j`` with ``t -
# window < j <= t``. Its cache is a RING of ``window`` slots a row,
# whatever the bucket: position ``p`` lives in slot ``p % window``, so a
# token overwrites the key that has just left its window, and the slots a
# row has written are always the prefix ``0 .. min(t, window - 1)``. Keys
# are cached as they are attended (normed, rotated at their own
# position), so the order of the slots does not matter to the softmax.

def grouped_causal_attention(q, k, v, groups: int, window: int = 0,
                             q_chunk: int = 128, offset=0):
    """Causal attention of a prompt's queries over its keys, grouped KV
    heads: ``q: [batch, tq, heads, d]`` at positions ``offset + 0..tq-1``
    (``offset`` may be traced), ``k, v: [batch, t, groups * d]`` in cache
    layout. ``window > 0`` bounds what a query sees to its last
    ``window`` positions, itself included. Queries go ``q_chunk`` at a
    time; a chunk is multiplied with the keys it can see and no others:
    with a window a slice of ``window + q_chunk`` keys, so a long prompt
    costs ``O(t * window)``. Padding needs no key mask: it lies past
    every real query, and causality hides it. Returns ``[batch, tq,
    heads, d]`` float32."""
    from deeplearning4j_tpu.ops.block_sparse import _grouped_attend

    b, tq, h, d = q.shape
    t = k.shape[1]
    c = min(int(q_chunk), tq)
    if tq % c:
        raise ValueError(f"{tq} queries are no multiple of the query "
                         f"chunk {c}")
    span = min(t, window + c) if window else t

    def body(_, xs):
        qc, start = xs                                   # [b, c, h, d]
        pos = offset + start + jnp.arange(c)
        lo = jnp.clip(pos[-1] + 1 - span, 0, t - span)
        kc = jax.lax.dynamic_slice_in_dim(k, lo, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, lo, span, axis=1)
        kpos = lo + jnp.arange(span)
        seen = kpos[None, :] <= pos[:, None]
        if window:
            seen &= kpos[None, :] > pos[:, None] - window
        mask = jnp.broadcast_to(seen[None, :, None, :], (b, c, groups, span))
        return None, _grouped_attend(qc, kc, vc, mask, groups)

    n = tq // c
    qs = jnp.swapaxes(q.reshape(b, n, c, h, d), 0, 1)
    _, o = jax.lax.scan(body, None, (qs, jnp.arange(n) * c))
    return jnp.swapaxes(o, 0, 1).reshape(b, tq, h, d)


def window_ring_block(k, lengths, window: int):
    """A prompt's keys (or values) ``k: [batch, t, e]`` as the ring a
    window layer joins: ``[batch, window, e]``, slot ``j`` holding the
    LAST position ``p < lengths[b]`` with ``p % window == j`` (the last
    ``window`` positions of a prompt longer than the window, each in its
    own slot) and zeros where the row has no such position yet."""
    t = k.shape[1]
    j = jnp.arange(window)[None, :]
    last = lengths[:, None] - 1
    p = j + window * ((last - j) // window)              # floor: < 0 if none
    held = (j <= last)[:, :, None]
    rows = jnp.take_along_axis(k, jnp.clip(p, 0, t - 1)[:, :, None], axis=1)
    return jnp.where(held, rows, jnp.zeros((), k.dtype))


def window_ring_update(ring, new, positions):
    """Write one token's ``new: [batch, 1, e]`` into its slot of ``ring:
    [batch, window, e]``: ``positions % window``, in place
    (:func:`cache_update`)."""
    return cache_update(ring, new, positions % ring.shape[1])


def window_ring_attention(q, k_ring, v_ring, positions, groups: int):
    """One token against its window, the ring read whole and masked: ``q:
    [batch, heads, d]``, rings ``[batch, window, groups * d]``,
    ``positions: [batch]`` the token's own position (its key and value
    already in their slot). The slots written are ``0 .. min(position,
    window - 1)``; once a row has wrapped, every slot is inside its
    window. Returns ``[batch, heads, d]`` float32."""
    from deeplearning4j_tpu.ops.block_sparse import dense_decode_attention

    return dense_decode_attention(
        q, k_ring, v_ring, jnp.minimum(positions, k_ring.shape[1] - 1),
        groups)


# ---------------------------------------------------------------------------
# Tier 1: blockwise online-softmax (pure XLA, any backend)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, key_mask=None, causal=False, scale=None,
                        block_k: int = 128):
    """Online-softmax over key blocks via ``lax.scan`` — never materializes
    the [Tq, Tk] matrix. Differentiable (scan has a transpose rule);
    ``jax.checkpoint`` on the block body keeps backward memory O(T)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    sm = _scale(q, scale)
    bk = min(block_k, tk)
    nk = -(-tk // bk)
    pad = nk * bk - tk

    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    km = jnp.ones((b, tk), q.dtype) if key_mask is None \
        else jnp.asarray(key_mask, q.dtype)
    km = jnp.pad(km, ((0, 0), (0, pad)))

    # [nk, b, h, bk, d] blocks scanned over axis 0
    kb = jnp.moveaxis(kp.reshape(b, h, nk, bk, d), 2, 0)
    vb = jnp.moveaxis(vp.reshape(b, h, nk, bk, d), 2, 0)
    mb = jnp.moveaxis(km.reshape(b, nk, bk), 1, 0)

    q32 = q.astype(jnp.float32)
    qpos = jnp.arange(tq)[:, None] + (tk - tq)  # global query positions

    @jax.checkpoint
    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, mblk, j = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kblk.astype(jnp.float32)) * sm
        s = jnp.where(mblk[:, None, None, :] > 0, s, NEG_INF)
        if causal:
            kpos = j * bk + jnp.arange(bk)[None, :]
            s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, tq, d), jnp.float32)
    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, mb, jnp.arange(nk)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Tier 2: Pallas flash kernel
# ---------------------------------------------------------------------------
#
# Mosaic-friendly structure (the round-1 kernel lost 9-14x to XLA; these
# are the fixes, each a measured TPU layout/pipeline rule):
# - every ref keeps >= 128 lanes: running max/denominator live as
#   [block_q, 128] lane-replicated tiles (a [bq, 1] ref forces degenerate
#   1-lane layouts), and the key-padding mask is laid out lane-major as
#   [batch, 8, Tk] instead of [.., Tk, 1];
# - 4D grid (batch, heads, q blocks, k blocks) over the native
#   [B, H, T, D] arrays — no host-side reshape to [B*H, T, D];
# - causal skipping redirects the kv index map to block 0 for skipped
#   blocks, so the pipeline never DMAs data the kernel won't read
#   (a pl.when gate alone still pays the HBM traffic);
# - the accumulator is kept pre-normalized (rescaled by 1/l every step),
#   so the final store is a cast, and softmax residuals are saved as
#   l and m (lane-replicated) rather than one packed lse.

_LANES = 128
_SUBLANES = 8


def _below_diag(i, bq, j, bk, off):
    """True when key block j intersects the causal lower triangle of query
    block i (``off = tk - tq`` aligns the diagonal for cross-attention)."""
    return (i + 1) * bq - 1 + off >= j * bk


def _rep(x, n):
    """[bq, 128] lane-replicated tile -> [bq, n] (n % 128 == 0 on TPU;
    n < 128 happens only with the small blocks interpret-mode tests use)."""
    return jnp.tile(x, (1, n // _LANES)) if n >= _LANES else x[:, :n]


_lane_fit = _rep  # accumulator width d follows the same rule


def _block_mask(km_ref, causal, i, j, bq, bk, off):
    """Combined padding+causal mask for the current [bq, bk] tile, or None."""
    mask = None
    if km_ref is not None:
        mask = km_ref[0, :1, :] > 0  # [1, bk], broadcasts over rows
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq + off
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        cm = cols <= rows
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return mask


def _scores(q_ref, k_ref, km_ref, sm, causal, i, j, off):
    """Masked, scaled [bq, bk] logits tile in f32."""
    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if sm != 1.0:
        s = s * sm
    bq, bk = s.shape
    mask = _block_mask(km_ref, causal, i, j, bq, bk, off)
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, l_ref, m_ref,
                m_sc, l_sc, acc_sc, *, sm, causal, nk, off):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    i = pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]
    run = True if not causal else _below_diag(i, bq, j, bk, off)

    @pl.when(run)
    def _compute():
        s = _scores(q_ref, k_ref, km_ref, sm, causal, i, j, off)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])  # [bq,128]
        p = jnp.exp(s - _rep(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_corr = alpha * l_prev
        l_next = jnp.sum(p, axis=1)[:, None] + l_corr
        m_sc[...] = m_next
        l_sc[...] = l_next
        l_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
        acc_sc[...] *= _lane_fit(l_corr * l_inv, d)
        pv = jax.lax.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                         preferred_element_type=jnp.float32)
        acc_sc[...] += pv * _lane_fit(l_inv, d)

    @pl.when(j == nk - 1)
    def _store():
        o_ref[0, 0] = acc_sc[...].astype(o_ref.dtype)
        l_ref[0, 0] = l_sc[...]
        m_ref[0, 0] = m_sc[...]


def _p_tile(q_ref, k_ref, km_ref, l_ref, m_ref, sm, causal, i, j, off):
    """Recompute the normalized probability tile p = exp(s - m) / l."""
    s = _scores(q_ref, k_ref, km_ref, sm, causal, i, j, off)
    bk = s.shape[1]
    l = l_ref[0, 0]
    l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
    return jnp.exp(s - _rep(m_ref[0, 0], bk)) * _rep(l_inv, bk)


def _di_tile(do, o_ref):
    """di = rowsum(do * o) recomputed in-kernel from the fwd output block:
    [bq, 1], broadcasts against the [bq, bk] dp tile. Passing o (bf16,
    d lanes) instead of a lane-replicated di operand saves a
    [B, H, Tq, 128] f32 HBM materialization per backward."""
    return jnp.sum(do.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
                   axis=1)[:, None]


def _dq_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, o_ref, l_ref, m_ref,
               dq_ref, dq_sc, *, sm, causal, nk, off):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    i = pl.program_id(2)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    run = True if not causal else _below_diag(i, bq, j, bk, off)

    @pl.when(run)
    def _compute():
        p = _p_tile(q_ref, k_ref, km_ref, l_ref, m_ref, sm, causal, i, j, off)
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _di_tile(do, o_ref))
        if sm != 1.0:
            ds = ds * sm
        dq_sc[...] += jax.lax.dot(ds.astype(k_ref.dtype), k_ref[0, 0],
                                  preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _store():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, o_ref, l_ref, m_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, sm, causal, nq, off):
    i = pl.program_id(3)  # query block (innermost, sequential)
    j = pl.program_id(2)  # key block

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    run = True if not causal else _below_diag(i, bq, j, bk, off)

    @pl.when(run)
    def _compute():
        p = _p_tile(q_ref, k_ref, km_ref, l_ref, m_ref, sm, causal, i, j, off)
        do = do_ref[0, 0]
        dv_sc[...] += jax.lax.dot(p.astype(do.dtype).T, do,
                                  preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _di_tile(do, o_ref))
        if sm != 1.0:
            ds = ds * sm
        dk_sc[...] += jax.lax.dot(ds.astype(q_ref.dtype).T, q_ref[0, 0],
                                  preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _store():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _tpu_compiler_params(interpret: bool):
    """Batch/head/query grid dims are parallel; the innermost streamed
    (scratch-accumulating) dim is sequential."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _cost(b, h, tq, tk, d, causal, bwd: bool):
    """Rough CostEstimate so Mosaic schedules the pipeline sensibly."""
    frac = 0.5 if causal else 1.0
    matmuls = 5 if bwd else 2  # s, pv fwd; s, dp, dq, dk, dv bwd
    return pl.CostEstimate(
        flops=int(matmuls * 2 * b * h * tq * tk * d * frac),
        transcendentals=int(b * h * tq * tk * frac),
        bytes_accessed=int((4 if bwd else 2) * b * h * (tq + tk) * d * 2),
    )


def _pad_t(x, blk):
    t = x.shape[2]
    pad = (-t) % blk
    return (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))), t + pad) \
        if pad else (x, t)


def _mask_operand(km, b, tk0, tk):
    """Lane-major mask operand [batch, 8, tk] (sublane-tiled), or None
    when no mask is needed. Padding keys forced to 0 even without a user
    mask (the padded tail must not attend). Always f32: Mosaic's VPU has
    no bf16 compare, and the kernel tests ``> 0`` directly."""
    if km is None and tk == tk0:
        return None
    if km is None:
        km = jnp.ones((b, tk0), jnp.float32)
    km = jnp.pad(jnp.asarray(km, jnp.float32), ((0, 0), (0, tk - tk0)))
    return jnp.broadcast_to(km[:, None, :], (b, _SUBLANES, km.shape[1]))


def _blk(requested, t):
    """Effective block size: >= one lane tile, a multiple of the lane
    width (the lane-replication math requires it), padded-t divides it.
    When t sits just above a block multiple, shrink to the largest
    128-multiple keeping the padding waste <= t/8 — T=640 with 512-blocks
    would otherwise pad to 1024 and silently burn ~60% of the compute/HBM
    on masked rows (round-2 advisor finding)."""
    if requested > _LANES:
        requested -= requested % _LANES
    b = min(requested, max(_LANES, 1 << (t - 1).bit_length()))
    while b > _LANES and (-(-t // b)) * b - t > t // 8:
        b -= _LANES
    return b


def _index_maps(causal, bq, bk, off):
    """(q, kv, mask) BlockSpec index maps for grid (b, h, i_q, j_kv). The
    causal redirect points skipped kv blocks at block 0 so the pipeline
    never DMAs data the kernel won't read — shared by fwd and dq so the
    skip logic cannot diverge between them."""

    def q_map(b_, h_, i, j):
        return (b_, h_, i, 0)

    def kv_map(b_, h_, i, j):
        if causal:
            j = jax.lax.select(_below_diag(i, bq, j, bk, off), j, 0)
        return (b_, h_, j, 0)

    def km_map(b_, h_, i, j):
        if causal:
            j = jax.lax.select(_below_diag(i, bq, j, bk, off), j, 0)
        return (b_, 0, j)

    return q_map, kv_map, km_map


def _flash_fwd_impl(q, k, v, km, causal, scale, block_q, block_k, interpret):
    b, h, tq0, d = q.shape
    tk0 = k.shape[2]
    if d > _LANES and d % _LANES:
        raise NotImplementedError(
            f"head_dim {d} > {_LANES} must be a multiple of {_LANES}")
    sm = _scale(q, scale)
    bq = _blk(block_q, tq0)
    bk = _blk(block_k, tk0)
    q, tq = _pad_t(q, bq)
    k, tk = _pad_t(k, bk)
    v, _ = _pad_t(v, bk)
    kmo = _mask_operand(km, b, tk0, tk)
    nq, nk = tq // bq, tk // bk
    off = tk0 - tq0
    q_map, kv_map, km_map = _index_maps(causal, bq, bk, off)

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        None if kmo is None else pl.BlockSpec((1, _SUBLANES, bk), km_map),
    ]
    out, l, m = pl.pallas_call(
        functools.partial(_fwd_kernel, sm=sm, causal=causal, nk=nk, off=off),
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq, _LANES), q_map),
            pl.BlockSpec((1, 1, bq, _LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, tq, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_tpu_compiler_params(interpret),
        cost_estimate=_cost(b, h, tq, tk, d, causal, bwd=False),
        interpret=interpret,
    )(q, k, v, kmo)  # a None operand pairs with its None spec
    # residuals packed to one lane: the kernel writes them lane-replicated
    # (layout), but only [b, h, tq0] of information is worth keeping
    # around between forward and backward (536MB -> 4MB at T=16k B4/H8)
    return out[:, :, :tq0], l[:, :, :tq0, 0], m[:, :, :tq0, 0]


def _flash_bwd_impl(q, k, v, km, out, l, m, g, causal, scale, block_q,
                    block_k, interpret):
    b, h, tq0, d = q.shape
    tk0 = k.shape[2]
    sm = _scale(q, scale)
    bq = _blk(block_q, tq0)
    bk = _blk(block_k, tk0)
    qp, tq = _pad_t(q, bq)
    kp, tk = _pad_t(k, bk)
    vp, _ = _pad_t(v, bk)
    gp, _ = _pad_t(g, bq)
    kmo = _mask_operand(km, b, tk0, tk)
    nq, nk = tq // bq, tk // bk
    off = tk0 - tq0
    q_map, kv_map, km_map = _index_maps(causal, bq, bk, off)

    # per-row residuals arrive packed [b, h, tq0]; rebuild the
    # lane-replicated [.., tq, 128] operands the kernels read (padded q
    # rows: do = 0 zeroes their dk/dv contribution; l pads to 1.0 so the
    # recomputed p stays finite). These two transients (l, m) are the
    # only lane-replicated HBM operands — di is recomputed in-kernel
    # from the (bf16, d-lane) fwd output instead.
    def lanes(x, pad_value=0.0):
        x = jnp.broadcast_to(x[..., None], (b, h, tq0, _LANES))
        return jnp.pad(x, ((0, 0), (0, 0), (0, tq - tq0), (0, 0)),
                       constant_values=pad_value)

    lp = lanes(l, pad_value=1.0)
    mp = lanes(m)
    op, _ = _pad_t(out, bq)

    q_spec = pl.BlockSpec((1, 1, bq, d), q_map)
    kv_spec = pl.BlockSpec((1, 1, bk, d), kv_map)
    km_spec = None if kmo is None else pl.BlockSpec((1, _SUBLANES, bk), km_map)
    lm_spec = pl.BlockSpec((1, 1, bq, _LANES), q_map)
    operands = (qp, kp, vp, kmo, gp, op, lp, mp)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm=sm, causal=causal, nk=nk, off=off),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, km_spec, q_spec, q_spec,
                  lm_spec, lm_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_tpu_compiler_params(interpret),
        cost_estimate=_cost(b, h, tq, tk, d, causal, bwd=True),
        interpret=interpret,
    )(*operands)

    # dkv grid: kv blocks outer, q blocks inner (scratch accumulates over
    # q); skipped q blocks redirect their DMAs to the last q block, which
    # is always live under the causal gate
    def q_map_t(b_, h_, j, i):
        if causal:
            i = jax.lax.select(_below_diag(i, bq, j, bk, off), i, nq - 1)
        return (b_, h_, i, 0)

    def kv_map_t(b_, h_, j, i):
        return (b_, h_, j, 0)

    def km_map_t(b_, h_, j, i):
        return (b_, 0, j)

    q_spec_t = pl.BlockSpec((1, 1, bq, d), q_map_t)
    kv_spec_t = pl.BlockSpec((1, 1, bk, d), kv_map_t)
    km_spec_t = (None if kmo is None
                 else pl.BlockSpec((1, _SUBLANES, bk), km_map_t))
    lm_spec_t = pl.BlockSpec((1, 1, bq, _LANES), q_map_t)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm=sm, causal=causal, nq=nq, off=off),
        grid=(b, h, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, km_spec_t, q_spec_t,
                  q_spec_t, lm_spec_t, lm_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_tpu_compiler_params(interpret),
        cost_estimate=_cost(b, h, tq, tk, d, causal, bwd=True),
        interpret=interpret,
    )(*operands)

    return (dq[:, :, :tq0], dk[:, :, :tk0], dv[:, :, :tk0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, km, causal, scale, block_q, block_k, interpret):
    out, _, _ = _flash_fwd_impl(q, k, v, km, causal, scale, block_q, block_k,
                                interpret)
    return out


def _flash_fwd(q, k, v, km, causal, scale, block_q, block_k, interpret):
    out, l, m = _flash_fwd_impl(q, k, v, km, causal, scale, block_q, block_k,
                                interpret)
    return out, (q, k, v, km, out, l, m)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, km, out, l, m = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, km, out, l, m, g, causal, scale,
                                 block_q, block_k, interpret)
    dkm = None if km is None else jnp.zeros_like(km)
    return dq, dk, dv, dkm


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, key_mask=None, causal=False, scale=None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """FlashAttention as a Pallas TPU kernel with a custom-VJP backward.

    ``interpret=None`` auto-enables the Pallas interpreter off-TPU so the
    same kernel code is exercised in CPU CI (SURVEY.md §4 backend-parity
    oracle)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    km = None if key_mask is None else jnp.asarray(key_mask)
    if km is not None and not jnp.issubdtype(km.dtype, jnp.floating):
        km = km.astype(jnp.float32)  # bool/int masks: keep the vjp float
    return _flash(q, k, v, km, causal, scale, block_q, block_k, interpret)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

# Measured crossover (committed bench_attention.py, v5e, B4/H8/D64 bf16
# causal, N=20 queue-timed + value-forced sync — two confirming runs):
#   T=2048: blockwise 4.4-7.7ms fwd / 6.3-6.9ms fwd+bwd vs flash
#           7.0-8.5 / 7.1-8.3 — blockwise wins or ties both modes;
#   T=4096: flash 7.1-7.6ms fwd / 10.2-12.0ms fwd+bwd vs blockwise
#           8.4 / 32.5-36.2 — flash wins both modes;
#   T=8192: flash 11.6 / 28.8 vs blockwise 42.2 / 178.4 — no contest,
#           and blockwise fwd+bwd cannot compile at all by T=16384 (the
#           scan saves one O(B*H*T*D) residual per key block > HBM).
# The crossover is the same for training and inference, so `train` does
# not change the choice today; it stays in the signature because the
# layers pass their mode and a future re-measurement may split the rule
# again (the round-2 dispatcher was wrong precisely because fwd-only was
# never measured separately).
_FLASH_MIN_T = 4096


def dot_product_attention(q, k, v, key_mask=None, causal=False, scale=None,
                          impl: str = "auto", train: bool = True):
    """Pick the right tier, from measurement (regenerate with
    ``python bench_attention.py`` on-chip; the table above and
    BASELINE.md's copy come from that script): full materialization for
    short sequences (one fused kernel), the XLA blockwise scan in the
    moderate band, the Pallas flash kernel from T=4096 up — and blockwise
    everywhere the kernel can't run (non-TPU backends, exotic head
    dims)."""
    d = q.shape[-1]
    flash_ok = (jax.default_backend() == "tpu"
                and (d <= _LANES or d % _LANES == 0))
    if impl == "auto":
        if q.shape[2] <= 1024:
            impl = "reference"
        elif flash_ok and q.shape[2] >= _FLASH_MIN_T:
            impl = "flash"
        else:
            impl = "blockwise"
    if impl == "flash":
        return flash_attention(q, k, v, key_mask, causal, scale)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, key_mask, causal, scale)
    return reference_attention(q, k, v, key_mask, causal, scale)
