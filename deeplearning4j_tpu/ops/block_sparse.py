"""Block-sparse causal attention with a learned-free block selection over
compressed keys (the InfLLM-v2 shape: score, reduce to blocks, top-k,
gather, attend), for grouped KV heads.

Cache layout as ``ops/attention.py`` states it: keys and values
``[batch, positions, kv_heads * head_dim]``; beside the keys a cache of
COMPRESSED keys ``[batch, positions / stride, kv_heads * head_dim]``,
entry ``j`` the mean of keys ``stride * j .. stride * j + kernel - 1``.

For the query at position ``t`` (context ``t + 1`` positions) and KV head
``g``, with ``hpg`` query heads a KV head:

1. the compressed keys whose window ends at or before ``t`` are scored:
   ``p = softmax_j(q . c_j / sqrt(d))`` per query head, summed over the
   ``hpg`` heads of ``g``;
2. a block of ``block`` positions scores the maximum of ``p`` over the
   compressed keys that overlap it;
3. always attended: the first ``init_blocks`` blocks and the last
   ``window`` positions; of the blocks that start at or before
   ``t - window`` (so that they reach outside the window) and are not
   initial, the ``topk`` with the highest score;
4. the output is softmax attention over exactly those positions.

A query at a position under ``dense_len`` attends every position up to
its own. :class:`SparseSpec` carries the sizes; nothing here knows a
model.

A prompt's queries choose by ``lax.top_k`` and attend all keys under the
mask of the chosen positions (:func:`sparse_prefill_attention`). The
decode step (:func:`sparse_decode_attention`) needs only WHICH blocks: in
a program lowered for a TPU it ranks them without a sort
(:func:`rank_blocks`) and reads them where they lie in the caches, in one
Pallas kernel (:func:`sparse_read_attention`); on every other platform it
gathers them out and attends the copy (:func:`gathered_decode_attention`,
which is also the kernel's reference).

The choice of step 3 is discrete, so steps 1 to 3 run in float32 at
``Precision.HIGHEST`` over float32 compressed keys, whatever type the
keys and values are cached in: with bfloat16 scores a served model's
chosen set differs from the exact one wherever two blocks' scores lie
within the rounding, and a block swapped at the 64th place moves a
peaked softmax's output by more than all the other rounding of a layer
(one sparse layer's logits off by 0.27 of their standard deviation at
the worst of 400 steps against 0.016 at the median; my chip run, PR 29).
The compressed keys are a sixteenth of the keys: float32 costs 33 MB a
layer at 16 rows of 32 k positions.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    kernel: int = 32        # positions a compressed key averages
    stride: int = 16        # positions between compressed keys
    block: int = 64         # positions a selected block holds
    window: int = 2048      # last positions, always attended
    init_blocks: int = 1    # first blocks, always attended
    topk: int = 64          # blocks chosen beside the fixed ones
    dense_len: int = 8192   # contexts up to here attend everything

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError("kernel and block must be multiples of stride")
        if self.kernel // self.stride - 1 > self.block // self.stride:
            raise ValueError("a compressed key may overlap two blocks at most")

    def complete(self, pos):
        """How many compressed keys are complete for a query at ``pos``."""
        return jnp.maximum((pos + 1 - self.kernel) // self.stride + 1, 0)


def compress_keys(k, spec: SparseSpec):
    """``k: [batch, time, e]`` -> ``[batch, ceil(time / stride), e]``
    float32, entry ``j`` the mean of ``k[stride * j : stride * j +
    kernel]``. Float32 whatever ``k``'s type: the compressed keys feed a
    DISCRETE choice, and a choice made from rounded scores differs from
    the exact one at every near-tie (module docstring). Entries whose
    window runs past ``time`` are zero-padded means: a reader takes entry
    ``j`` only once position ``stride * j + kernel - 1`` is in its
    context."""
    b, t, e = k.shape
    n = -(-t // spec.stride)
    k32 = jnp.pad(k.astype(jnp.float32),
                  ((0, 0), (0, n * spec.stride - t), (0, 0)))
    sums = k32.reshape(b, n, spec.stride, e).sum(axis=2)
    total = sums
    for i in range(1, spec.kernel // spec.stride):
        total = total + jnp.pad(sums[:, i:], ((0, 0), (0, i), (0, 0)))
    return total / spec.kernel


def _grouped_block_diagonal(q, groups: int):
    """``q: [batch, time, heads, d]`` as the right-hand side of a product
    with a ``[.., groups * d]`` cache: ``[batch, groups * d, time *
    heads]``, column ``(t, h)`` holding ``q[t, h]`` in the rows of head
    ``h``'s KV group and zeros in the others (``ops/attention.py`` says
    why the lanes are not reshaped into heads)."""
    b, t, h, d = q.shape
    own = (jnp.arange(h) // (h // groups))[:, None] == jnp.arange(groups)
    blocks = q[:, :, :, None, :] * own.astype(q.dtype)[None, None, :, :, None]
    return jnp.transpose(blocks, (0, 3, 4, 1, 2)).reshape(b, groups * d,
                                                          t * h)


def block_scores(q, ck, positions, spec: SparseSpec, groups: int):
    """Every block's score for each query: ``q: [batch, time, heads, d]``,
    ``ck: [batch, n_compressed, groups * d]``, ``positions: [batch,
    time]`` each query's own position. Returns ``[batch, time, groups,
    blocks]`` float32: a candidate block's score (0 or more), -1 for a
    block that is no candidate (initial, or not yet outside the
    window)."""
    b, t, h, d = q.shape
    nc = ck.shape[1]
    ratio = spec.block // spec.stride
    if nc % ratio:
        raise ValueError(f"{nc} compressed keys do not fill whole blocks")
    nb = nc // ratio
    hpg = h // groups
    qb = _grouped_block_diagonal(q.astype(jnp.float32), groups)
    s = jnp.einsum("bne,bek->bnk", ck.astype(jnp.float32), qb,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    s = s.reshape(b, nc, t, h)
    valid = (jnp.arange(nc)[None, :, None]
             < spec.complete(positions)[:, None, :])        # [b, nc, t]
    p = jax.nn.softmax(jnp.where(valid[..., None], s, NEG_INF), axis=1)
    p = p.reshape(b, nc, t, groups, hpg).sum(axis=-1)
    p = jnp.where(valid[..., None], p, -1.0)                # [b, nc, t, g]
    by_block = p.reshape(b, nb, ratio, t, groups)
    score = by_block.max(axis=2)
    for i in range(1, spec.kernel // spec.stride):
        # compressed key ratio*b - i starts in block b - 1 and ends in b
        before = jnp.pad(by_block[:, :-1, ratio - i],
                         ((0, 0), (1, 0), (0, 0), (0, 0)),
                         constant_values=-1.0)
        score = jnp.maximum(score, before)
    start = jnp.arange(nb) * spec.block
    candidate = ((start[None, :, None] <= positions[:, None, :] - spec.window)
                 & (jnp.arange(nb) >= spec.init_blocks)[None, :, None])
    score = jnp.where(candidate[..., None], score, -1.0)
    return jnp.transpose(score, (0, 2, 3, 1))


def select_blocks(q, ck, positions, spec: SparseSpec, groups: int):
    """The chosen blocks of each query (:func:`block_scores`' arguments).
    Returns ``(idx, ok)``, both ``[batch, time, groups, k]``: block
    numbers by falling score and whether each is a real choice (a query
    with fewer candidate blocks than ``topk`` has fewer)."""
    score = block_scores(q, ck, positions, spec, groups)
    vals, idx = jax.lax.top_k(score, min(spec.topk, score.shape[-1]))
    return idx.astype(jnp.int32), vals >= 0.0


def rank_blocks(score, k: int):
    """:func:`jax.lax.top_k`'s ``(idx, vals >= 0)`` of ``score: [...,
    blocks]`` without a sort: a block's place is the number of blocks that
    beat it (a greater score, or an equal score and a lower number:
    ``top_k``'s own order), and place ``r`` names the one block whose
    count is ``r``. ``blocks ** 2`` comparisons, which the vector unit
    makes faster than it sorts: on the TPU ``top_k`` of 512 scores is a
    full sort of them (PERF.md §6)."""
    nb = score.shape[-1]
    i = jnp.arange(nb, dtype=jnp.int32)
    first, second = score[..., :, None], score[..., None, :]
    beats = (first > second) | ((first == second) & (i[:, None] < i))
    place = beats.sum(axis=-2, dtype=jnp.int32)              # [..., nb]
    hit = place[..., None] == jnp.arange(k, dtype=jnp.int32)  # [..., nb, k]
    idx = jnp.sum(jnp.where(hit, i[:, None], 0), axis=-2)
    ok = jnp.any(hit & (score[..., None] >= 0.0), axis=-2)
    return idx, ok


def allowed_positions(idx, ok, positions, n_positions: int,
                      spec: SparseSpec):
    """The positions each query attends, as a mask ``[batch, time,
    groups, n_positions]``: what :func:`select_blocks` chose, the window,
    the initial blocks, everything for a query under ``dense_len``; never
    past the query itself."""
    nb = n_positions // spec.block
    chosen = ((idx[..., None] == jnp.arange(nb)) & ok[..., None]).any(axis=-2)
    chosen = jnp.repeat(chosen, spec.block, axis=-1)     # [b, t, g, n]
    p = jnp.arange(n_positions)
    t = positions[:, :, None, None]
    outside = p <= t - spec.window
    allowed = ((chosen & outside) | ~outside
               | (p < spec.init_blocks * spec.block)
               | (t < spec.dense_len))
    return allowed & (p <= t)


def _grouped_attend(q, k, v, mask, groups: int):
    """``q: [b, t, h, d]`` against ``k, v: [b, n, groups * d]`` under
    ``mask: [b, t, groups, n]``; float32 accumulation and softmax."""
    b, t, h, d = q.shape
    hpg = h // groups
    outs = []
    for g in range(groups):
        kg, vg = k[..., g * d:(g + 1) * d], v[..., g * d:(g + 1) * d]
        qg = q[:, :, g * hpg:(g + 1) * hpg].astype(k.dtype)
        s = jnp.einsum("btmd,bnd->btmn", qg, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[:, :, g, None], s, NEG_INF),
                           axis=-1)
        outs.append(jnp.einsum("btmn,bnd->btmd", p.astype(v.dtype), vg,
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=2)


def sparse_prefill_attention(q, k, v, ck, spec: SparseSpec, groups: int,
                             q_chunk: int = 128, offset=0):
    """Causal attention of a prompt's queries over its keys: ``q: [batch,
    tq, heads, d]`` the queries at positions ``offset + 0..tq-1``, ``k, v:
    [batch, time, groups * d]`` the whole prompt's, ``ck`` their
    compressed keys. Queries go in chunks of ``q_chunk``; each chunk
    selects its blocks and attends all keys under the mask of the chosen
    positions. A prompt no longer than ``dense_len`` selects nothing.
    Returns ``[batch, tq, heads, d]`` float32."""
    b, tq, h, d = q.shape
    t = k.shape[1]
    c = min(int(q_chunk), tq)
    if tq % c:
        raise ValueError(f"{tq} queries are no multiple of the query "
                         f"chunk {c}")
    sparse = t > spec.dense_len
    if sparse and t % spec.block:
        raise ValueError(f"a prompt bucket beyond dense_len must hold "
                         f"whole blocks of {spec.block}, got {t}")

    def body(_, xs):
        qc, start = xs                                   # [b, c, h, d]
        pos = jnp.broadcast_to(offset + start + jnp.arange(c), (b, c))
        if sparse:
            idx, ok = select_blocks(qc, ck, pos, spec, groups)
            mask = allowed_positions(idx, ok, pos, t, spec)
        else:
            mask = jnp.broadcast_to(
                (jnp.arange(t) <= pos[:, :, None])[:, :, None],
                (b, c, groups, t))
        return None, _grouped_attend(qc, k, v, mask, groups)

    n = tq // c
    qs = jnp.swapaxes(q.reshape(b, n, c, h, d), 0, 1)
    _, o = jax.lax.scan(body, None, (qs, jnp.arange(n) * c))
    return jnp.swapaxes(o, 0, 1).reshape(b, tq, h, d)


def rows_slice(cache, starts, length: int):
    """``cache[i, starts[i] : starts[i] + length]`` for every row ``i``:
    ``[batch, length, e]``. One ``dynamic_slice`` a row, written out row
    by row like ``ops.attention.cache_update``: vmapped it lowers to a
    gather, for which XLA relays the WHOLE cache out first (a 268 MB copy
    a sparse layer a step, 0.82 ms each; my chip run, PR 29)."""
    return jnp.concatenate([
        jax.lax.dynamic_slice(cache, (i, starts[i], 0),
                              (1, length, cache.shape[2]))
        for i in range(cache.shape[0])], axis=0)


def dense_decode_attention(q, k_cache, v_cache, positions, groups: int):
    """One token against the first positions of the cache, grouped KV
    heads: ``q: [batch, heads, d]``, caches ``[batch, n, groups * d]``,
    every slot up to ``positions`` attended. The per-head products run
    against a block-diagonal operand, lanes never reshaped."""
    b, h, d = q.shape
    n = k_cache.shape[1]
    qb = _grouped_block_diagonal(q[:, None].astype(k_cache.dtype), groups)
    s = jnp.einsum("bne,bek->bnk", k_cache, qb,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    live = jnp.arange(n)[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(live[..., None], s, NEG_INF), axis=1)
    out = jnp.einsum("bnk,bne->bke", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    hpg = h // groups
    return jnp.einsum("bgmgd->bgmd",
                      out.reshape(b, groups, hpg, groups, d)).reshape(b, h, d)


def sparse_decode_attention(q, k_cache, v_cache, ck_cache, positions,
                            spec: SparseSpec, groups: int):
    """One token of the selection: score the compressed keys, choose the
    blocks, attend them with the window and the initial blocks. ``q:
    [batch, heads, d]``, ``positions: [batch]`` the slot of the token (its
    own key and value already written). Returns ``(o [batch, heads, d]
    float32, attended [batch], read [batch])``: the count of (KV head,
    position) pairs attended, and of those whose keys and values the step
    streamed to attend them.

    In a program LOWERED for a TPU (``lax.platform_dependent``, as
    ``ops.attention.bounded_decode_attention``) the blocks are chosen by
    :func:`rank_blocks` and read where they lie by
    :func:`sparse_read_attention`, where the caches' shape allows
    (:func:`sparse_read_applies`); on every other platform, so in every
    CPU run, by ``top_k`` and :func:`gathered_decode_attention`, which is
    also the kernel's reference."""
    score = block_scores(q[:, None], ck_cache, positions[:, None], spec,
                         groups)[:, 0]                   # [b, g, nb]
    k = min(spec.topk, score.shape[-1])

    def gathered(score, q, k_cache, v_cache, positions):
        vals, idx = jax.lax.top_k(score, k)
        return gathered_decode_attention(
            q, k_cache, v_cache, idx.astype(jnp.int32), vals >= 0.0,
            positions, spec, groups)

    def in_place(score, q, k_cache, v_cache, positions):
        idx, ok = rank_blocks(score, k)
        return sparse_read_attention(q, k_cache, v_cache, idx, ok, positions,
                                     spec, groups, interpret=False)

    if not sparse_read_applies(k_cache.shape, k_cache.dtype, q.shape[-1],
                               spec):
        return gathered(score, q, k_cache, v_cache, positions)
    return jax.lax.platform_dependent(score, q, k_cache, v_cache, positions,
                                      tpu=in_place, default=gathered)


def gathered_decode_attention(q, k_cache, v_cache, idx, ok, positions,
                              spec: SparseSpec, groups: int):
    """The chosen blocks ``idx, ok: [batch, groups, k]`` gathered out of
    the caches, concatenated with the window and the initial blocks and
    attended by one softmax (stock XLA). Returns as
    :func:`sparse_decode_attention`; what it streams: the window and the
    initial blocks once (both KV heads' lanes), each KV head's chosen
    blocks with the other head's lanes beside them."""
    b, h, d = q.shape
    s_len = k_cache.shape[1]
    hpg = h // groups
    nb = s_len // spec.block
    window = min(spec.window, s_len)
    n_init = min(spec.init_blocks * spec.block, s_len)
    pos = positions[:, None]
    # the window, one slice a row, and the initial blocks
    w0 = jnp.clip(positions - window + 1, 0, s_len - window)
    kw, vw = rows_slice(k_cache, w0, window), rows_slice(v_cache, w0, window)
    pw = w0[:, None] + jnp.arange(window)
    mw = (pw <= pos) & (pw > pos - spec.window)
    pi = jnp.arange(n_init)[None]
    mi = (pi <= pos - spec.window) & (pi <= pos)
    k_blocks = k_cache.reshape(b, nb, spec.block, groups * d)
    v_blocks = v_cache.reshape(b, nb, spec.block, groups * d)
    pb = (idx[..., None] * spec.block
          + jnp.arange(spec.block)).reshape(b, groups, -1)
    mb = (jnp.repeat(ok, spec.block, axis=-1)
          & (pb <= pos[:, None] - spec.window))
    outs, attended = [], 0
    for g in range(groups):
        lanes = slice(g * d, (g + 1) * d)

        def gather(blocks):
            # the chosen blocks first (both KV heads' lanes), THEN this
            # head's lanes: the other order splits the whole cache by
            # head before the gather (0.8 ms a cache a step, PR 29)
            return jax.vmap(lambda c, i: c[i])(
                blocks, idx[:, g])[..., lanes].reshape(b, -1, d)

        keys = (gather(k_blocks), kw[..., lanes], k_cache[:, :n_init, lanes])
        vals = (gather(v_blocks), vw[..., lanes], v_cache[:, :n_init, lanes])
        mask = jnp.concatenate([mb[:, g], mw, jnp.broadcast_to(
            mi, (b, n_init))], axis=1)                   # [b, n]
        qg = q[:, g * hpg:(g + 1) * hpg].astype(k_cache.dtype)
        s = jnp.concatenate(
            [jnp.einsum("bmd,bnd->bmn", qg, part,
                        preferred_element_type=jnp.float32)
             for part in keys], axis=-1) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
        p = p.astype(v_cache.dtype)
        o, at = 0.0, 0
        for part in vals:
            n = part.shape[1]
            o = o + jnp.einsum("bmn,bnd->bmd", p[..., at:at + n], part,
                               preferred_element_type=jnp.float32)
            at += n
        outs.append(o)
        attended = attended + mask.sum(axis=-1)
    read = groups * (window + n_init + groups * idx.shape[-1] * spec.block)
    return (jnp.concatenate(outs, axis=1), attended.astype(jnp.int32),
            jnp.full((b,), read, jnp.int32))


# ---------------------------------------------------------------------------
# The decode step's read as one Pallas kernel: the caches stay in HBM
# ---------------------------------------------------------------------------
#
# What decides its shape (PERF.md §6, PR 34): a row attends 64 + 33 + 1
# blocks a KV head, and a grid step of the Pallas pipeline costs a third of
# a microsecond beside its DMA (``ops.attention.DECODE_PAGE``), so one
# block a grid step would be slower than the gathers it replaces. The grid
# is the ROWS; a step starts the NEXT row's copies by hand (the window and
# the initial blocks, both KV heads' lanes in one run each; every chosen
# block of every KV head) into the other half of a VMEM slab, waits for its
# own, and attends them while the next row's arrive. A chosen block is
# copied with its own KV head's ``d`` lanes alone: with both heads' (whole
# contiguous rows, twice the chosen bytes) the kernel took 236 us a layer
# against 160 at the cell's shapes, where its copies alone take 138 (744
# GB/s) and its arithmetic alone 56, hidden behind them.

# positions a product takes at a time, the running softmax's step: 4096
# (a KV head's chosen blocks in one) 160 us, 2048 169, 1024 183, 512 207
SPARSE_READ_CHUNK = 4096


def sparse_read_applies(cache_shape, cache_dtype, d: int,
                        spec: SparseSpec) -> bool:
    """Whether the TPU reads a ``[batch, positions, groups * d]`` cache by
    :func:`sparse_read_attention`, from the shapes alone: a KV head fills
    whole 128-lane tiles, a block whole sublane tiles of the cache's type,
    the window whole blocks, and the bucket holds the window and a block
    of slack beside a chosen block (or there is nothing to skip)."""
    _, s_len, e = cache_shape
    sublanes = 8 * 4 // jnp.dtype(cache_dtype).itemsize
    return (d % 128 == 0 and e % d == 0 and spec.block % sublanes == 0
            and spec.window % spec.block == 0 and s_len % spec.block == 0
            and s_len >= spec.window + 2 * spec.block)


def _sparse_read_kernel(pos_ref, idx_ref, q_ref, pb_ref, k_hbm, v_hbm, o_ref,
                        kw, vw, kc, vc, sems, *, spec: SparseSpec,
                        groups: int, d: int, topk: int, span: int,
                        n_init: int, chunk: int):
    """One row a grid step (module comment above). ``pos_ref: [batch]``
    and ``idx_ref: [batch * groups * topk]`` (the chosen blocks) are
    scalar-prefetched; ``q_ref: [1, heads, d]``; ``pb_ref: [1, groups,
    topk * block]`` the position each chosen slot holds (past the cache
    where the slot is no real choice); ``k_hbm, v_hbm`` the caches where
    they lie. Slabs, two halves each: ``kw, vw: [2, span + n_init,
    groups * d]`` the window from its block-aligned start, then the
    initial blocks; ``kc, vc: [2, groups, topk * block, d]`` the chosen
    blocks, each KV head's own lanes. ``sems: [2, 4]``, one a slab a
    half: a slab's copies all signal it and ONE wait the size of the slab
    takes them all."""
    i = pl.program_id(0)
    block, window = spec.block, spec.window
    hpg = q_ref.shape[1] // groups
    s_len = k_hbm.shape[1]

    def window_start(r):
        w0 = (pos_ref[r] + 1 - window) // block * block
        return pl.multiple_of(jnp.clip(w0, 0, s_len - span), block)

    def fetch(r, half):
        w0 = window_start(r)
        for j, (hbm, fixed) in enumerate(((k_hbm, kw), (v_hbm, vw))):
            pltpu.make_async_copy(
                hbm.at[r, pl.ds(w0, span), :],
                fixed.at[half, pl.ds(0, span), :], sems.at[half, j]).start()
            pltpu.make_async_copy(
                hbm.at[r, pl.ds(0, n_init), :],
                fixed.at[half, pl.ds(span, n_init), :],
                sems.at[half, j]).start()

        def some(n, carry):     # a few blocks an iteration, written out
            for t in (n * unroll + u for u in range(unroll)):
                for g in range(groups):
                    at = pl.multiple_of(
                        idx_ref[(r * groups + g) * topk + t] * block, block)
                    to = pl.ds(pl.multiple_of(t * block, block), block)
                    for j, (hbm, chosen) in enumerate(((k_hbm, kc),
                                                       (v_hbm, vc))):
                        pltpu.make_async_copy(
                            hbm.at[r, pl.ds(at, block), pl.ds(g * d, d)],
                            chosen.at[half, g, to, :],
                            sems.at[half, 2 + j]).start()
            return carry

        unroll = math.gcd(topk, 8)
        jax.lax.fori_loop(0, topk // unroll, some, None)

    def wait(half):
        for j, slab in enumerate((kw, vw, kc, vc)):
            pltpu.make_async_copy(slab.at[half], slab.at[half],
                                  sems.at[half, j]).wait()

    half = i % 2

    @pl.when(i == 0)
    def _first():
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        fetch(i + 1, 1 - half)

    wait(half)
    pos = pos_ref[i]
    edge = pos - window         # positions up to here lie outside the window
    w0 = window_start(i)
    sm = 1.0 / math.sqrt(d)

    def parts(g):
        """``(keys, values, attended)`` a chunk: the chosen blocks' slots,
        then the window's and the initial blocks'."""
        for c in range(0, topk * block, chunk):
            at = slice(c, min(c + chunk, topk * block))
            yield (kc[half, g, at, :], vc[half, g, at, :],
                   pb_ref[0, g:g + 1, at] <= edge)
        for c in range(0, span + n_init, chunk):
            n = min(c + chunk, span + n_init) - c
            col = c + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            p = jnp.where(col < span, w0 + col, col - span)
            seen = (p <= edge) ^ ((col < span) & (p <= pos))
            lanes = slice(g * d, (g + 1) * d)
            yield (kw[half, c:c + n, lanes], vw[half, c:c + n, lanes], seen)

    for g in range(groups):
        qg = q_ref[0, g * hpg:(g + 1) * hpg, :]              # [hpg, d]
        m = jnp.full((hpg, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((hpg, 1), jnp.float32)
        acc = jnp.zeros((hpg, d), jnp.float32)
        for keys, values, seen in parts(g):
            s = jax.lax.dot_general(
                qg, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm     # [hpg, n]
            s = jnp.where(seen, s, NEG_INF)
            m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # a chunk may hold no attended position at all: its exp(0)s
            # must not count
            p = jnp.where(seen, jnp.exp(s - m_next), 0.0)
            alpha = jnp.exp(m - m_next)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(values.dtype), values,
                                        preferred_element_type=jnp.float32)
            m = m_next
        # the token's own position is in its window: l > 0
        o_ref[0, g * hpg:(g + 1) * hpg, :] = (acc / l).astype(o_ref.dtype)


# jitted so that a decoder's sparse layers share one trace and one lowered
# body of the kernel (``ops.attention.paged_decode_attention``)
@functools.partial(jax.jit, static_argnames=("spec", "groups", "interpret",
                                             "chunk"))
def sparse_read_attention(q, k_cache, v_cache, idx, ok, positions,
                          spec: SparseSpec, groups: int,
                          interpret: Optional[bool] = None,
                          chunk: Optional[int] = None):
    """:func:`gathered_decode_attention` as one Pallas kernel over the
    caches left in HBM (the same arguments, the same three results): for
    a row it copies the window (one run from a block-aligned start, a
    block longer than the window), the initial blocks and the chosen
    blocks into VMEM and attends them with a running softmax, float32
    accumulation over the cache's type. The masks are the gathered
    read's: a chosen or initial block gives its positions up to ``pos -
    window``, the window those after it and up to ``pos``; a position
    past the bucket reads as its last slot, as ``cache_update`` writes it.
    The bucket must hold the window, a block of slack and whole blocks; a
    TPU needs :func:`sparse_read_applies` besides. ``interpret=None``
    runs the Pallas interpreter off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    s_len, e = k_cache.shape[1:]
    block, window = spec.block, spec.window
    topk = idx.shape[-1]
    span, n_init = window + block, spec.init_blocks * block
    if (window % block or s_len % block or s_len < span + block
            or e != groups * d or h % groups):
        raise ValueError(f"sparse_read_attention: a {k_cache.shape} cache "
                         f"of {groups} KV heads of {d} does not hold a "
                         f"window of {window} in whole blocks of {block}")
    pos = jnp.clip(positions.astype(jnp.int32), 0, s_len - 1)
    idx = idx.astype(jnp.int32)
    in_block = jnp.arange(block, dtype=jnp.int32)
    pb = jnp.where(ok[..., None], idx[..., None] * block + in_block,
                   s_len).reshape(b, groups, topk * block)
    kernel = functools.partial(
        _sparse_read_kernel, spec=spec, groups=groups, d=d, topk=topk,
        span=span, n_init=n_init, chunk=int(chunk or SPARSE_READ_CHUNK))
    row = lambda i, *_: (i, 0, 0)  # noqa: E731
    dt = k_cache.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, d), row),
                  pl.BlockSpec((1, groups, topk * block), row),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, d), row),
        scratch_shapes=[pltpu.VMEM((2, span + n_init, e), dt),
                        pltpu.VMEM((2, span + n_init, e), dt),
                        pltpu.VMEM((2, groups, topk * block, d), dt),
                        pltpu.VMEM((2, groups, topk * block, d), dt),
                        pltpu.SemaphoreType.DMA((2, 4))],
    )
    params = None
    if not interpret:
        slabs = 2 * 2 * (span + n_init + topk * block) * e * dt.itemsize
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(slabs + (16 << 20)))
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        compiler_params=params, interpret=interpret,
    )(pos, idx.reshape(-1), q.astype(dt), pb, k_cache, v_cache)
    # what the masks above let through, and what the copies brought
    outside = jnp.clip(pos[:, None, None] - window + 1 - idx * block, 0,
                       block)
    fixed = jnp.minimum(pos + 1, window) + jnp.clip(pos - window + 1, 0,
                                                    n_init)
    attended = groups * fixed + jnp.where(ok, outside, 0).sum(axis=(1, 2))
    read = groups * (span + n_init + topk * block)
    return o, attended.astype(jnp.int32), jnp.full((b,), read, jnp.int32)
