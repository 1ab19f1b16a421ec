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
import math

import jax
import jax.numpy as jnp

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


def select_blocks(q, ck, positions, spec: SparseSpec, groups: int):
    """The chosen blocks of each query: ``q: [batch, time, heads, d]``,
    ``ck: [batch, n_compressed, groups * d]``, ``positions: [batch,
    time]`` each query's own position. Returns ``(idx, ok)``, both
    ``[batch, time, groups, k]``: block numbers by falling score and
    whether each is a real choice (a query with fewer candidate blocks
    than ``topk`` has fewer)."""
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
    vals, idx = jax.lax.top_k(jnp.transpose(score, (0, 2, 3, 1)),
                              min(spec.topk, nb))
    return idx.astype(jnp.int32), vals >= 0.0


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
    blocks, gather their keys and values, attend them with the window and
    the initial blocks. ``q: [batch, heads, d]``, ``positions: [batch]``
    the slot of the token (its own key and value already written).
    Returns ``(o [batch, heads, d] float32, attended [batch])``, the
    second the count of (KV head, position) pairs attended."""
    b, h, d = q.shape
    s_len = k_cache.shape[1]
    hpg = h // groups
    nb = s_len // spec.block
    window = min(spec.window, s_len)
    n_init = min(spec.init_blocks * spec.block, s_len)
    idx, ok = select_blocks(q[:, None], ck_cache, positions[:, None], spec,
                            groups)
    idx, ok = idx[:, 0], ok[:, 0]                        # [b, g, k]
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
    return jnp.concatenate(outs, axis=1), attended.astype(jnp.int32)
