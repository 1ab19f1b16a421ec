"""Collective scheduler: one planner for bucket layout, issue order, and
collective choice.

Three bucketed primitives (``bucketed_psum``, ``bucketed_psum_scatter``,
``bucketed_all_gather``) used to hand-coordinate bucket partitioning and
``optimization_barrier`` issue chains privately. This module is the single
owner of all three decisions:

- **layout** — :func:`bucket_partition`: reverse-topological,
  size-targeted buckets (the last layers' gradients — the first ones
  backprop produces — land in bucket 0), shared by every intent;
- **order** — one ``optimization_barrier`` chain ties bucket k+1's
  operands to bucket k's result, so XLA cannot merge or reorder the
  collectives and bucket k's exchange overlaps the backward pass still
  producing bucket k+1 (arXiv:1905.04035: collective performance during
  gradient accumulation dominates DP scaling; arXiv:2112.01075:
  decomposing one transfer into scheduled chunks);
- **choice** — per bucket:

  =============== ==========================================================
  ``variadic``    one variadic collective over the bucket's leaves (the
                  default; a single-bucket plan is the fused
                  single-exchange baseline — the ``:b0`` shape)
  ``densify``     the bucket's many small same-dtype leaves are flattened
                  into ONE dense buffer for a single ``psum`` and split
                  back after — densified accumulation (arXiv:1905.04035:
                  per-leaf sparse exchange loses to one dense buffer when
                  leaves are tiny); ``psum`` is elementwise, so the result
                  is bitwise the per-leaf exchange
  ``all_gather``  the native all-gather (the ring all-gather's (n-1)/n
                  payload), in its Varying -> Invariant form so the
                  gathered result type-checks against replicated
                  ``P()`` out_specs under ``shard_map``'s ``check_vma``
  =============== ==========================================================

Every plan is content-addressed: :attr:`CollectivePlan.digest` hashes the
(intent, layout, choices, leaf sizes/dtypes) and joins the AOT-cache step
key (``plan:<digest>`` tokens), so a changed layout or choice can never
silently reuse a stale executable — and the PRG205 collective audit looks
the digest up via :func:`lookup_plan` to verify the compiled module's
collective sequence matches what the plan promised.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from typing import Dict, Optional, Tuple

import numpy as np

# this module sits BELOW parallel/ in the import graph (parallel.
# compression re-exports from here), so it cannot import parallel.mesh
# at module scope; the axis-name constant is restated with its authority
# cross-referenced
DATA_AXIS = "data"   # parallel.mesh.DATA_AXIS

INTENTS = ("all_reduce", "reduce_scatter", "all_gather")

# densified accumulation thresholds: a bucket of >= MIN_LEAVES leaves,
# every one at most MAX_LEAF_BYTES and all one dtype, exchanges as one
# dense concatenated buffer instead of a variadic per-leaf collective
# (launch overhead amortizes; psum is elementwise so numerics are
# bitwise unchanged)
DENSIFY_MIN_LEAVES = 8
DENSIFY_MAX_LEAF_BYTES = 16 << 10   # 16 KiB


# --------------------------------------------------------------------------
# layout (the single shared implementation — parallel.compression and
# sharding.zero re-export / delegate here)
# --------------------------------------------------------------------------

def bucket_partition(sizes, bucket_bytes: int):
    """Partition leaf indices into size-targeted buckets, walking the
    leaves in REVERSE order (reverse-topological: backprop computes the
    deepest layers' grads first). Returns a list of index lists; every
    index appears exactly once. A leaf larger than ``bucket_bytes`` gets
    its own bucket."""
    buckets, cur, acc = [], [], 0
    for i in reversed(range(len(sizes))):
        if cur and acc + sizes[i] > bucket_bytes:
            buckets.append(cur)
            cur, acc = [], 0
        cur.append(i)
        acc += sizes[i]
    if cur:
        buckets.append(cur)
    return buckets


def bucket_layout(tree, bucket_bytes=None):
    """Host-side preview of an all-reduce schedule for a pytree of
    (possibly abstract) arrays: per-bucket payload bytes, in issue order.
    ``bucket_bytes=None`` returns one bucket holding the whole tree."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return []
    sizes = [l.size * np.dtype(l.dtype).itemsize for l in leaves]
    if bucket_bytes is None or len(leaves) <= 1:
        return [sum(sizes)]
    return [sum(sizes[i] for i in bucket)
            for bucket in bucket_partition(sizes, int(bucket_bytes))]


# --------------------------------------------------------------------------
# plans
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectivePlan:
    """One resolved exchange schedule: what moves, in which buckets, in
    which order, through which collective. Immutable and content-
    addressed — ``digest`` is the AOT-cache key component."""

    intent: str
    axis: str
    bucket_bytes: Optional[int]
    buckets: Tuple[Tuple[int, ...], ...]   # leaf indices, issue order
    choices: Tuple[str, ...]               # one per bucket
    leaf_sizes: Tuple[int, ...]            # payload bytes per leaf
    leaf_dtypes: Tuple[str, ...]
    digest: str = ""

    def bytes_moved(self) -> int:
        """Logical per-shard payload of one exchange."""
        return int(sum(self.leaf_sizes))

    def launches(self) -> int:
        """Collectives issued per exchange (1 = fused single exchange)."""
        return len(self.buckets)

    def bucket_bytes_list(self):
        return [sum(self.leaf_sizes[i] for i in b) for b in self.buckets]

    def choice_summary(self) -> str:
        return "+".join(sorted(set(self.choices)))

    def key_token(self) -> str:
        """The AOT-cache step-key component: ``plan:<digest>``."""
        return f"plan:{self.digest}"

    def summary(self) -> dict:
        """JSON-ready record (PRG205 audit / UI surfaces)."""
        return {
            "intent": self.intent,
            "axis": self.axis,
            "digest": self.digest,
            "buckets": self.launches(),
            "choices": list(self.choices),
            "bytes": self.bytes_moved(),
            "bucket_bytes": [int(b) for b in self.bucket_bytes_list()],
        }


def _leaf_meta(leaves, intent, full_sizes):
    """-> (payload bytes per leaf, dtype strs). For ``all_gather`` the
    payload is the GATHERED vector (``full_sizes``) — the same layout
    the matching reduce-scatter buckets on."""
    dtypes = [str(np.dtype(l.dtype)) for l in leaves]
    if intent == "all_gather":
        if full_sizes is None:
            raise ValueError("all_gather plans need full_sizes")
        sizes = [int(f) * np.dtype(l.dtype).itemsize
                 for f, l in zip(full_sizes, leaves)]
    else:
        sizes = [int(l.size) * np.dtype(l.dtype).itemsize for l in leaves]
    return sizes, dtypes


def _choose(intent, idxs, sizes, dtypes):
    """Per-bucket collective choice (see the module table)."""
    if intent == "all_reduce":
        if (len(idxs) >= DENSIFY_MIN_LEAVES
                and max(sizes[i] for i in idxs) <= DENSIFY_MAX_LEAF_BYTES
                and len({dtypes[i] for i in idxs}) == 1):
            return "densify"
        return "variadic"
    if intent == "reduce_scatter":
        # densification would re-cut the scattered slices (the scatter
        # of a concatenated buffer hands each shard a block of the
        # CONCATENATION, not per-leaf slices) — layout-changing, so
        # reduce-scatter always exchanges per-leaf
        return "variadic"
    if intent == "all_gather":
        return "all_gather"
    raise ValueError(f"unknown intent {intent!r}; expected one of "
                     f"{INTENTS}")


class _Stats:
    def __init__(self):
        self.plans_built = 0
        self.plan_cache_hits = 0


_STATS = _Stats()
_PLAN_CACHE: Dict[tuple, CollectivePlan] = {}
_BY_DIGEST: Dict[str, CollectivePlan] = {}
_LOCK = threading.Lock()


def stats() -> dict:
    """Process-global planner counters (bench_collectives.py record)."""
    with _LOCK:
        return {"plans_built": _STATS.plans_built,
                "plan_cache_hits": _STATS.plan_cache_hits,
                "registered": len(_BY_DIGEST)}


def lookup_plan(digest: str) -> Optional[CollectivePlan]:
    """Digest -> plan, for consumers holding only the AOT-cache key
    (the PRG205 collective audit). None when this process never built
    the plan (e.g. a key minted by an earlier run)."""
    with _LOCK:
        return _BY_DIGEST.get(digest)


def reset() -> None:
    """Test hook: drop cached plans and counters."""
    with _LOCK:
        _PLAN_CACHE.clear()
        _BY_DIGEST.clear()
        _STATS.plans_built = 0
        _STATS.plan_cache_hits = 0


class CollectiveScheduler:
    """The planner: takes a gradient/param pytree plus an intent and
    emits a :class:`CollectivePlan`. Stateless apart from the process-
    global plan cache — two schedulers over the same tree/intent emit
    the identical (same-digest) plan, on any process."""

    def __init__(self, axis_name: str = DATA_AXIS,
                 bucket_bytes: Optional[int] = None):
        self.axis_name = axis_name
        self.bucket_bytes = (None if bucket_bytes is None
                             else int(bucket_bytes))

    def plan(self, tree, intent: str,
             full_sizes=None) -> CollectivePlan:
        """Resolve the exchange schedule for ``tree`` (arrays, avals or
        ShapeDtypeStructs — only ``.size``/``.dtype`` are read)."""
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
        sizes, dtypes = _leaf_meta(leaves, intent, full_sizes)
        key = (intent, self.axis_name, self.bucket_bytes, tuple(sizes),
               tuple(dtypes))
        with _LOCK:
            cached = _PLAN_CACHE.get(key)
            if cached is not None:
                _STATS.plan_cache_hits += 1
                return cached
        if not leaves:
            buckets = ()
        elif self.bucket_bytes is None or len(leaves) <= 1:
            buckets = (tuple(range(len(leaves))),)
        else:
            buckets = tuple(
                tuple(b) for b in bucket_partition(sizes,
                                                   self.bucket_bytes))
        choices = tuple(_choose(intent, b, sizes, dtypes)
                        for b in buckets)
        digest = hashlib.sha1(repr(
            (intent, self.axis_name, buckets, choices, tuple(sizes),
             tuple(dtypes))).encode()).hexdigest()[:16]
        plan = CollectivePlan(
            intent=intent, axis=self.axis_name,
            bucket_bytes=self.bucket_bytes, buckets=buckets,
            choices=choices, leaf_sizes=tuple(sizes),
            leaf_dtypes=tuple(dtypes), digest=digest)
        with _LOCK:
            # re-check under the lock: a concurrent planner of the same
            # layout may have won the build race — one logical plan must
            # count (and record its telemetry) exactly once
            raced = _PLAN_CACHE.get(key)
            if raced is not None:
                _STATS.plan_cache_hits += 1
                return raced
            _PLAN_CACHE[key] = plan
            _BY_DIGEST[digest] = plan
            _STATS.plans_built += 1
        _record_plan(plan)
        return plan

    # --- execution (traced: runs inside jitted steps) ----------------------
    def execute(self, plan: CollectivePlan, tree):
        """Run one exchange under ``plan``. ``all_gather`` plans take the
        per-shard slice tree and return the gathered vectors, replicated
        on every shard."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            return tree
        out = [None] * len(leaves)
        pin = None
        for bucket, choice in zip(plan.buckets, plan.choices):
            vals = tuple(leaves[i] for i in bucket)
            if pin is not None:
                # order pin: this bucket's collective is scheduled after
                # the previous bucket's — a pure scheduling edge, no math
                pinned = jax.lax.optimization_barrier(vals + (pin,))
                vals = tuple(pinned[:-1])
            red = _run_bucket(plan, choice, vals)
            pin = red[0]
            for i, r in zip(bucket, red):
                out[i] = r
        return jax.tree_util.tree_unflatten(treedef, out)


def _run_bucket(plan, choice, vals):
    import jax
    import jax.numpy as jnp

    axis = plan.axis
    if choice == "variadic":
        if plan.intent == "reduce_scatter":
            return jax.lax.psum_scatter(vals, axis, scatter_dimension=0,
                                        tiled=True)
        return jax.lax.psum(vals, axis)
    if choice == "densify":
        # one dense fused buffer: flatten + concat, a single psum, split
        # back — elementwise reduction, bitwise the per-leaf exchange
        shapes = [v.shape for v in vals]
        counts = [int(np.prod(s)) if s else 1 for s in shapes]
        cat = jnp.concatenate([jnp.reshape(v, (-1,)) for v in vals])
        red = jax.lax.psum(cat, axis)
        out, off = [], 0
        for shape, n in zip(shapes, counts):
            out.append(jnp.reshape(
                jax.lax.slice_in_dim(red, off, off + n), shape))
            off += n
        return tuple(out)
    if choice == "all_gather":
        # Varying -> Invariant: the public lax.all_gather types its
        # result as still varying over the axis, which check_vma rejects
        # against the P() out_specs every ZeRO step returns its params
        # under; jax 0.9.0 has the invariant form only under jax._src
        from jax._src.lax.parallel import all_gather_invariant

        return tuple(all_gather_invariant(v, axis, axis=0, tiled=True)
                     for v in vals)
    raise ValueError(f"unknown collective choice {choice!r}")


# --------------------------------------------------------------------------
# module-level conveniences (the thin-wrapper surface compression uses)
# --------------------------------------------------------------------------

def plan_for(tree, intent: str, axis_name: str = DATA_AXIS,
             bucket_bytes=None, full_sizes=None) -> CollectivePlan:
    """Build (or fetch) the plan for one exchange without running it —
    key digests for ``aot_cache.wrap`` callsites, layouts for telemetry."""
    return CollectiveScheduler(axis_name, bucket_bytes).plan(
        tree, intent, full_sizes=full_sizes)


def exchange(tree, intent: str, axis_name: str = DATA_AXIS,
             bucket_bytes=None, full_sizes=None):
    """Plan + execute one exchange (the ``bucketed_*`` primitives'
    engine). Traced: call from inside jitted/shard_mapped steps."""
    sched = CollectiveScheduler(axis_name, bucket_bytes)
    plan = sched.plan(tree, intent, full_sizes=full_sizes)
    return sched.execute(plan, tree)


def _record_plan(plan: CollectivePlan) -> None:
    """Telemetry on each fresh plan: the per-(intent, choice) counter and
    the bytes/launches gauges feeding the UI System tab collective panel.
    Control-plane cadence (once per unique plan per process — plans are
    resolved at trace time, never per step), so recording is
    unconditional like the analysis/resilience events."""
    try:
        from deeplearning4j_tpu import telemetry

        telemetry.record_collective_plan(
            plan.intent, plan.choice_summary(), plan.bytes_moved(),
            plan.launches())
    except Exception:
        pass  # observability must never break a trace
