"""Kernel registry: named Pallas kernels, envelopes, tuned selection.

Each registered :class:`Kernel` declares

- a **shape/dtype envelope** (``supports(env)``) — the exact set of
  concrete problems its grid can cover; anything outside routes to
  stock XLA with zero behavior change;
- a **tiling/grid parameter space** (``candidates(env)``) the autotuner
  (``kernels.tuner``) sweeps per concrete ``(shape, dtype, backend)``;
- a **reference implementation** (``reference(env)``) — the ``jax.lax``
  path it must match numerically (the parity tests pin every kernel
  against it in interpret mode);
- the **builder** (``build(env, tiling)``) producing the Pallas
  callable for one tuned layout.

Selection (:meth:`KernelRegistry.select`) is a pure tuning-cache
lookup: only a TUNED envelope gets a kernel — an untuned shape is a
recorded fallback, never a guess. The per-kernel **tuning digest**
(8-hex over the winner table + kernel version, epoch-memoized) is what
the model step keys fold in as ``kern:<id>:<digest>`` tokens, so a
retune re-keys every kernel-bearing executable (PRG207 audits the
tokens against this registry).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.kernels import impls, tuner

# candidate block sweeps (clamped per-problem, deduped by effective
# tiling): sublane-multiples for rows, lane-width favorites for
# columns/contraction — the guide's (8/16, 128) tile floors
_BM_SWEEP = (512, 256, 128, 64, 32, 16, 8)
_BN_SWEEP = (256, 128, 64, 32, 16, 8)
_BK_SWEEP = (512, 256, 128, 64, 32, 16, 8)

_SUPPORTED_DTYPES = ("float32", "bfloat16")

# attention sweeps: flash (block_q, block_k) favors the MXU-shaped big
# blocks first (ops.attention._blk clamps per-problem, so the candidate
# space is the EFFECTIVE block set — small-T problems collapse to one
# candidate); paged decode sweeps the page (KV slots per DMA) down the
# pow2 ladder the cache buckets come from
_ATTN_BQ_SWEEP = (512, 256, 128)
_ATTN_BK_SWEEP = (512, 256, 128)
_PAGE_SWEEP = (128, 64, 32, 16, 8)


@dataclasses.dataclass(frozen=True)
class MatmulEnvelope:
    """One concrete matmul-class problem: [M, K] @ [K, N] in ``dtype``
    on ``backend`` ("tpu" = real Mosaic lowering, "interpret" = the
    Pallas interpreter — this container's mode), with an optional
    elementwise activation baked in the epilogue."""

    m: int
    k: int
    n: int
    dtype: str
    backend: str
    act: str = "identity"

    @property
    def key(self) -> str:
        return (f"{self.backend}:m{self.m}:k{self.k}:n{self.n}"
                f":{self.dtype}:{self.act}")

    @property
    def shape_bucket(self) -> str:
        """The telemetry label: shape class without backend/act noise."""
        return f"m{self.m}_k{self.k}_n{self.n}"


@dataclasses.dataclass(frozen=True)
class AttentionEnvelope:
    """One concrete attention problem. ``tq`` is the query length (1 for
    single-token decode), ``tk`` the key length — for the paged decode
    kernel that is the KV cache bucket, so every hop up the pow2 ladder
    is its own tuned envelope. ``masked`` marks a key-padding mask
    operand (train prefill over ragged batches); decode masking rides
    ``positions`` and is always on."""

    b: int
    h: int
    tq: int
    tk: int
    d: int
    dtype: str
    backend: str
    causal: bool = True
    masked: bool = False

    @property
    def key(self) -> str:
        return (f"{self.backend}:b{self.b}:h{self.h}:tq{self.tq}"
                f":tk{self.tk}:d{self.d}:{self.dtype}"
                f":c{int(self.causal)}:m{int(self.masked)}")

    @property
    def shape_bucket(self) -> str:
        return f"b{self.b}_h{self.h}_tq{self.tq}_tk{self.tk}_d{self.d}"


def _tiling_legal(env: MatmulEnvelope, tiling) -> bool:
    """The clamped blocks divide the problem exactly and, on the TPU
    backend, are blocks Mosaic will lower."""
    return (impls.tiling_valid(env.m, env.k, env.n, tiling)
            and (env.backend != "tpu"
                 or impls.mosaic_block_ok(env.m, env.k, env.n, tiling)))


def _sweep_candidates(env: MatmulEnvelope,
                      limit: Optional[int]) -> List[Tuple[int, int, int]]:
    seen, out = set(), []
    for bm in _BM_SWEEP:
        for bn in _BN_SWEEP:
            for bk in _BK_SWEEP:
                t = (bm, bn, bk)
                eff = impls.effective_tiling(env.m, env.k, env.n, t)
                if eff in seen or not _tiling_legal(env, t):
                    continue
                seen.add(eff)
                out.append(eff)
    # prefer big MXU-shaped tiles first so a capped sweep still sees
    # the plausible winners
    out.sort(key=lambda t: (-(t[0] * t[1]), -t[2]))
    return out[:limit] if limit else out


def _matmul_supports(env) -> bool:
    return (env.dtype in _SUPPORTED_DTYPES
            and env.m > 0 and env.k > 0 and env.n > 0
            and bool(_sweep_candidates(env, limit=1)))


def _activation(name: str):
    from deeplearning4j_tpu.conf.activations import Activation

    return Activation(name)


def _rand_inputs(env: MatmulEnvelope, seed: int, with_bias: bool):
    import jax
    import jax.numpy as jnp

    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.dtype(env.dtype)
    x = jax.random.normal(kx, (env.m, env.k), jnp.float32).astype(dt)
    w = jax.random.normal(kw, (env.k, env.n), jnp.float32).astype(dt)
    if not with_bias:
        return x, w
    b = jax.random.normal(kb, (env.n,), jnp.float32).astype(dt)
    return x, w, b


class Kernel:
    """Base registry entry. ``version`` participates in the tuning
    digest, so a kernel-body change invalidates every cached executable
    keyed on the old behavior."""

    kernel_id: str = ""
    version: int = 1

    def supports(self, env) -> bool:
        raise NotImplementedError

    def candidates(self, env, limit: Optional[int] = None):
        raise NotImplementedError

    def build(self, env, tiling):
        """-> callable over :meth:`make_inputs`-shaped args running the
        Pallas path with ``tiling``."""
        raise NotImplementedError

    def reference(self, env):
        """-> callable over the same args running the stock ``jax.lax``
        path this kernel must match."""
        raise NotImplementedError

    def make_inputs(self, env, seed: int = 0):
        raise NotImplementedError

    def tiling_ok(self, env, tiling) -> bool:
        """Whether a cached winner still legally covers ``env`` — the
        guard :meth:`KernelRegistry.select` runs before trusting a
        hand-edited / cross-version tuning-cache entry. Default: the
        winner must be one of this kernel's own candidates."""
        return tuple(tiling) in {tuple(t) for t in self.candidates(env)}


class _MatmulKernel(Kernel):
    """Shared matmul-class winner validation: a 3-tuple that is a legal
    tiling of the envelope (:func:`_tiling_legal`)."""

    def tiling_ok(self, env, tiling) -> bool:
        return len(tiling) == 3 and _tiling_legal(env, tiling)


class MatmulBiasActKernel(_MatmulKernel):
    """Tiled matmul + bias + elementwise activation in one pass — the
    dense / 1x1-conv forward class (``impls.matmul_bias_act``)."""

    kernel_id = "matmul_bias_act"
    version = 1

    def supports(self, env) -> bool:
        return _matmul_supports(env)

    def candidates(self, env, limit: Optional[int] = None):
        return _sweep_candidates(env, limit)

    def build(self, env, tiling):
        act = _activation(env.act)
        interpret = env.backend != "tpu"
        tiling = tuple(tiling)

        def fn(x, w, b):
            return impls.matmul_bias_act(x, w, b, act, tiling, interpret)

        return fn

    def reference(self, env):
        act = _activation(env.act)

        def ref(x, w, b):
            return act.apply(x @ w + b)

        return ref

    def make_inputs(self, env, seed: int = 0):
        return _rand_inputs(env, seed, with_bias=True)


class Int8MatmulBiasActKernel(_MatmulKernel):
    """Quantized-serving matmul: int8 x int8 -> int32 accumulate with the
    f32 scale/bias/activation epilogue fused in the same pass
    (``impls.matmul_bias_act_int8``). Serves ``QuantizedDenseLayer`` and —
    after the routing reshape — ``QuantizedConv1x1Layer``. The envelope
    machinery (candidates/tuner/on-disk cache/stock fallback/PRG207) is
    untouched: this is just a new dtype reaching the same sweeps."""

    kernel_id = "matmul_bias_act_int8"
    version = 1

    def supports(self, env) -> bool:
        return (env.dtype == "int8"
                and env.m > 0 and env.k > 0 and env.n > 0
                and bool(_sweep_candidates(env, limit=1)))

    def candidates(self, env, limit: Optional[int] = None):
        return _sweep_candidates(env, limit)

    def build(self, env, tiling):
        act = _activation(env.act)
        interpret = env.backend != "tpu"
        tiling = tuple(tiling)

        def fn(xq, wq, scale, b):
            return impls.matmul_bias_act_int8(xq, wq, scale, b, act,
                                              tiling, interpret)

        return fn

    def reference(self, env):
        import jax
        import jax.numpy as jnp

        act = _activation(env.act)

        def ref(xq, wq, scale, b):
            acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
            return act.apply(acc.astype(jnp.float32) * scale + b)

        return ref

    def make_inputs(self, env, seed: int = 0):
        import jax
        import jax.numpy as jnp

        kx, kw, ks, kb = jax.random.split(jax.random.PRNGKey(seed), 4)
        xq = jax.random.randint(kx, (env.m, env.k), -127, 128, jnp.int8)
        wq = jax.random.randint(kw, (env.k, env.n), -127, 128, jnp.int8)
        scale = jax.random.uniform(ks, (env.n,), jnp.float32, 0.5, 2.0) / 127
        b = jax.random.normal(kb, (env.n,), jnp.float32)
        return xq, wq, scale, b


class ConvBnActKernel(_MatmulKernel):
    """Fused 1x1-conv + batch-norm statistics — the dominant trace
    fusion class (round-2 ``ops/conv_fused`` experiment): the matmul
    emits y AND the per-channel sum / sum-of-squares in one output
    pass, so the train-mode BN statistics re-read of the activation
    disappears (normalize + activation stay in XLA where they fuse
    with whatever follows)."""

    kernel_id = "conv_bn_act"
    version = 1

    def supports(self, env) -> bool:
        return _matmul_supports(env)

    def candidates(self, env, limit: Optional[int] = None):
        return _sweep_candidates(env, limit)

    def build(self, env, tiling):
        interpret = env.backend != "tpu"
        tiling = tuple(tiling)

        def fn(x, w):
            return impls.matmul_stats(x, w, tiling, interpret)

        return fn

    def reference(self, env):
        import jax.numpy as jnp

        def ref(x, w):
            y = x @ w
            y32 = y.astype(jnp.float32)
            return y, jnp.sum(y32, axis=0), jnp.sum(y32 * y32, axis=0)

        return ref

    def make_inputs(self, env, seed: int = 0):
        return _rand_inputs(env, seed, with_bias=False)


def _attention_supports(env) -> bool:
    return (isinstance(env, AttentionEnvelope)
            and env.dtype in _SUPPORTED_DTYPES
            and env.b > 0 and env.h > 0 and env.d > 0
            and env.tq > 0 and env.tk > 0)


def _rand_attn(env, seed: int, shapes):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(env.dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return tuple(
        jax.random.normal(k, s, jnp.float32).astype(dt)
        for k, s in zip(keys, shapes))


class FlashAttentionKernel(Kernel):
    """Tiled online-softmax attention (``ops.attention.flash_attention``):
    (Bq, Bk)-blocked forward that never materializes the [Tq, Tk] score
    matrix, custom-VJP backward recomputing each probability tile from
    the saved row-max/row-sum stats. The tuned tiling is the
    ``(block_q, block_k)`` pair; ``ops.attention._blk`` clamps each to
    the effective legal block for the problem, so every candidate here
    IS its own effective tiling."""

    kernel_id = "flash_attention"
    version = 1

    def supports(self, env) -> bool:
        if not _attention_supports(env):
            return False
        # the kernel's lane-replication math needs d <= 128 or 128 | d
        return env.d <= 128 or env.d % 128 == 0

    def candidates(self, env, limit: Optional[int] = None):
        from deeplearning4j_tpu.ops import attention as A

        seen, out = set(), []
        for bq in _ATTN_BQ_SWEEP:
            for bk in _ATTN_BK_SWEEP:
                eff = (A._blk(bq, env.tq), A._blk(bk, env.tk))
                if eff in seen:
                    continue
                seen.add(eff)
                out.append(eff)
        out.sort(key=lambda t: (-(t[0] * t[1]), -t[0]))
        return out[:limit] if limit else out

    def build(self, env, tiling):
        from deeplearning4j_tpu.ops import attention as A

        bq, bk = (int(t) for t in tiling)
        causal = env.causal
        interpret = env.backend != "tpu"

        def fn(q, k, v, key_mask=None):
            return A.flash_attention(q, k, v, key_mask, causal=causal,
                                     block_q=bq, block_k=bk,
                                     interpret=interpret)

        return fn

    def reference(self, env):
        from deeplearning4j_tpu.ops import attention as A

        causal = env.causal

        def ref(q, k, v, key_mask=None):
            return A.reference_attention(q, k, v, key_mask, causal=causal)

        return ref

    def make_inputs(self, env, seed: int = 0):
        import jax
        import jax.numpy as jnp

        q, k, v = _rand_attn(env, seed, [(env.b, env.h, env.tq, env.d),
                                         (env.b, env.h, env.tk, env.d),
                                         (env.b, env.h, env.tk, env.d)])
        if not env.masked:
            return q, k, v
        # ragged key-padding mask: every row keeps at least one key
        lens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                  (env.b,), 1, env.tk + 1)
        km = (jnp.arange(env.tk)[None, :]
              < lens[:, None]).astype(jnp.float32)
        return q, k, v, km


class PagedDecodeAttentionKernel(Kernel):
    """Single-token decode against the KV cache as an in-kernel page
    gather (``ops.attention.paged_decode_attention``): the cache streams
    page-by-page and the grid is the list of the rows' LIVE pages, built
    from the scalar-prefetched positions, so a row's decode step costs
    O(used pages) instead of the masked full-cache read. The tuned
    tiling is the 1-tuple ``(page,)``; only divisors of the cache bucket
    are legal. The serving decode step runs the same kernel at
    ``ops.attention.decode_page``, without asking this registry."""

    kernel_id = "paged_decode_attention"
    version = 2  # 1 paged a [batch, max_len, heads, head_dim] cache

    def supports(self, env) -> bool:
        return (_attention_supports(env) and env.tq == 1
                and bool(self.candidates(env, limit=1)))

    def candidates(self, env, limit: Optional[int] = None):
        out = [(p,) for p in _PAGE_SWEEP
               if p <= env.tk and env.tk % p == 0]
        if not out and env.tk <= max(_PAGE_SWEEP):
            out = [(env.tk,)]  # tiny caches: one page covers the bucket
        return out[:limit] if limit else out

    def build(self, env, tiling):
        from deeplearning4j_tpu.ops import attention as A

        page = int(tiling[0])
        interpret = env.backend != "tpu"

        def fn(q, k_cache, v_cache, positions):
            return A.paged_decode_attention(q, k_cache, v_cache, positions,
                                            page=page, interpret=interpret)

        return fn

    def reference(self, env):
        from deeplearning4j_tpu.ops import attention as A

        def ref(q, k_cache, v_cache, positions):
            return A.decode_attention(q, k_cache, v_cache, positions)

        return ref

    def make_inputs(self, env, seed: int = 0):
        import jax
        import jax.numpy as jnp

        q, kc, vc = _rand_attn(env, seed, [(env.b, env.h, env.d),
                                           (env.b, env.tk, env.h * env.d),
                                           (env.b, env.tk, env.h * env.d)])
        pos = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (env.b,), 0, env.tk, jnp.int32)
        return q, kc, vc, pos


@dataclasses.dataclass(frozen=True)
class Selection:
    """One resolved routing decision."""

    kernel: Kernel
    env: object
    tiling: Tuple[int, ...]


class KernelRegistry:
    """Process-global name -> :class:`Kernel` table + tuned selection
    + epoch-memoized tuning digests."""

    def __init__(self, cache: Optional[tuner.TuningCache] = None):
        self._kernels: Dict[str, Kernel] = {}
        self._cache = cache if cache is not None else tuner.TUNING
        self._digests: Dict[str, Tuple[int, str]] = {}
        self._tag_memo: Optional[Tuple[int, Tuple[str, ...], str]] = None
        self._lock = threading.Lock()

    @property
    def tuning(self) -> tuner.TuningCache:
        return self._cache

    def register(self, kernel: Kernel) -> Kernel:
        with self._lock:
            self._kernels[kernel.kernel_id] = kernel
            self._digests.pop(kernel.kernel_id, None)
            self._tag_memo = None
        return kernel

    def get(self, kernel_id: str) -> Optional[Kernel]:
        return self._kernels.get(kernel_id)

    def ids(self) -> List[str]:
        return sorted(self._kernels)

    def select(self, kernel_id: str, env) -> Optional[Selection]:
        """The tuned kernel for one envelope, or None (untuned /
        unsupported / winner no longer legal) — None means stock XLA."""
        kernel = self._kernels.get(kernel_id)
        if kernel is None or not kernel.supports(env):
            return None
        win = self._cache.winner(kernel_id, env.key)
        if win is None:
            return None
        tiling = tuple(int(t) for t in win.get("tiling", ()))
        if not kernel.tiling_ok(env, tiling):
            # a hand-edited / cross-version winner that no longer covers
            # the problem: refuse it, fall back to stock XLA
            return None
        return Selection(kernel=kernel, env=env, tiling=tiling)

    def tuning_digest(self, kernel_id: str) -> str:
        """8-hex digest over the kernel's current winner table (+ its
        version); memoized against the tuning-cache epoch so the
        per-step re-key check stays two dict lookups."""
        epoch = self._cache.epoch
        with self._lock:
            memo = self._digests.get(kernel_id)
            if memo is not None and memo[0] == epoch:
                return memo[1]
        kernel = self._kernels.get(kernel_id)
        payload = {
            "version": getattr(kernel, "version", 0),
            "winners": self._cache.winners(kernel_id),
        }
        d = hashlib.sha256(
            json.dumps(payload, sort_keys=True,
                       separators=(",", ":")).encode()).hexdigest()[:8]
        with self._lock:
            self._digests[kernel_id] = (epoch, d)
        return d

    def cache_tag(self) -> str:
        """The ``:kern:<id>:<digest>`` token string step keys fold in —
        one token per registered kernel, so retuning ANY kernel mints
        new executables for every kernel-enabled step. Memoized against
        the tuning-cache epoch (like the per-kernel digests), so the hot
        decode loop's per-dispatch re-key check is one tuple compare
        instead of a join over every registered kernel."""
        epoch = self._cache.epoch
        ids = tuple(self.ids())
        with self._lock:
            memo = self._tag_memo
            if memo is not None and memo[0] == epoch and memo[1] == ids:
                return memo[2]
        tag = "".join(f":kern:{kid}:{self.tuning_digest(kid)}"
                      for kid in ids)
        with self._lock:
            self._tag_memo = (epoch, ids, tag)
        return tag


REGISTRY = KernelRegistry()
REGISTRY.register(MatmulBiasActKernel())
REGISTRY.register(Int8MatmulBiasActKernel())
REGISTRY.register(ConvBnActKernel())
REGISTRY.register(FlashAttentionKernel())
REGISTRY.register(PagedDecodeAttentionKernel())
