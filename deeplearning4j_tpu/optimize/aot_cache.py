"""AOT step-executable cache.

``jax.jit`` keeps a per-jit-object trace cache, so every model instance
that builds a fresh jitted step pays a full retrace+recompile even when an
identical network was compiled seconds ago — and a silent retrace (shape
drift, a rebuilt wrapper, a cloned model) is invisible until the step-time
spike shows up in a profile. This module makes compilation explicit and
shared:

- executables are keyed by ``(graph signature, step kind, input avals +
  shardings, donation set)`` and compiled ONCE per key via
  ``jit(...).lower(*args).compile()``;
- the key is process-global, so a cloned/re-instantiated model with the
  same configuration reuses the already-compiled executable instead of
  retracing;
- every dispatch records a hit or a miss, and misses record their compile
  seconds — surfaced through ``optimize.listeners.AotCacheStatsListener``
  and the ``ui.stats`` System tab, so "zero recompiles across repeated
  fit() calls" is an observable invariant instead of a hope.

The reference has no equivalent (each fit walks the op graph from Java
every iteration); this is the TPU-native hot-path contract: the ONLY
per-step host work is a cache lookup + one dispatch.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np


# --------------------------------------------------------------------------
# jax's persistent compilation cache: where compiled programs outlive the
# process. The rule, in this one place: a directory given from outside
# (JAX_COMPILATION_CACHE_DIR, which jax reads itself) is left alone and
# no directory is set in code; otherwise the cache lives at ONE fixed
# path in the checkout — the path is part of the cache key, so a
# directory that moves (tempfile, pid, time) never hits.
# --------------------------------------------------------------------------

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_cache")
_COMPILE_CACHE_PLACED = False


def place_compile_cache() -> str:
    """Apply the rule above (idempotent; every miss runs it, entry
    points may call it earlier so eager and init-time compiles are kept
    too). Returns the directory in effect. jax's 1 s minimum compile
    time for an entry is dropped to 0 unless the environment sets it:
    the decoder ladder is tens of sub-second executables."""
    global _COMPILE_CACHE_PLACED
    import jax

    if not _COMPILE_CACHE_PLACED:
        _COMPILE_CACHE_PLACED = True
        if not os.environ.get(COMPILE_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_COMPILE_CACHE_DIR)
        if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class AotCacheStats:
    """Process-global counters (thread-safe; the async fit loops dispatch
    from one thread but listeners may read from another)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with getattr(self, "_lock", threading.Lock()):
            self.hits = 0
            self.misses = 0
            self.compile_seconds = 0.0
            self.entries = 0
            self.fallbacks = 0
            self.overflows = 0
            self.last_miss_key = None
            self.dispatches = {}      # executable key -> times dispatched

    def record_hit(self, key=None):
        with self._lock:
            self.hits += 1
            if key is not None:
                self.dispatches[key] = self.dispatches.get(key, 0) + 1

    def record_first_dispatch(self, key):
        """The dispatch that follows a miss: no hit, but a run."""
        with self._lock:
            self.dispatches[key] = self.dispatches.get(key, 0) + 1

    def record_miss(self, key, seconds: float):
        with self._lock:
            self.misses += 1
            self.compile_seconds += float(seconds)
            self.entries += 1
            self.last_miss_key = key

    def record_fallback(self):
        with self._lock:
            self.fallbacks += 1

    def record_overflow(self):
        with self._lock:
            self.overflows += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "entries": self.entries,
                "compile_seconds": round(self.compile_seconds, 3),
                "fallbacks": self.fallbacks,
                "overflows": self.overflows,
            }


STATS = AotCacheStats()

# key -> compiled executable. Bounded: evicting a compiled XLA program to
# recompile it later is strictly worse than holding it, and a process that
# compiles >256 distinct step signatures has a retrace bug this cache
# exists to SURFACE (the stats keep counting either way).
_MAX_ENTRIES = 256
_EXECUTABLES: dict = {}
_LOCK = threading.Lock()


def stats() -> dict:
    """Current cache counters (the System-tab record)."""
    return STATS.snapshot()


def clear():
    """Drop every cached executable (tests; a long-lived server swapping
    model families can call this to release device programs). Identity
    pins are released with the entries they guarded. What
    :func:`programs` lists of the executables that were DISPATCHED stays
    (their host-side HLO module, nothing that holds device memory), until
    the next ``clear()`` replaces it: a trace taken while they ran can
    still be read by scope. Fetching a module from the runtime costs 0.05
    to 1.1 s an executable on the chip (14 s for the state-space
    decoder's thirteen, my chip run, PR 37), so it is not done here: a
    thread takes the dispatched executables, most dispatched first, and
    lets each go as soon as it has its module; :func:`programs` waits for
    it. ``clear()`` itself returns at once."""
    global _KEPT, _KEEPER
    with _LOCK:
        _KEPT = sorted((p for p in (Program(key, exe) for key, exe
                                    in _EXECUTABLES.items())
                        if p.dispatches), key=lambda p: -p.dispatches)
        _EXECUTABLES.clear()
        _ID_PINNED.clear()
        _KEEPER = None
        if _KEPT:
            _KEEPER = threading.Thread(
                target=lambda kept: [p._release() for p in kept],
                args=(_KEPT,), name="aot-cache-keeper")
            _KEEPER.start()
    STATS.reset()


# --------------------------------------------------------------------------
# the table of the loaded executables
# --------------------------------------------------------------------------

class Program:
    """One executable of the cache, as the device's trace knows it.

    ``kind`` is the step kind it was cached under (``fn_key``:
    ``decode_step:s1024:k4``, ``gen_prompt:t128:b1``,
    ``prefill_join:s1024:t128:b1``, ``train_step:d012+itc``),
    ``module_name`` the HLO module's name (what the trace's ``XLA
    Modules`` line prints before the brackets), ``dispatches`` how often
    it ran, ``trace_id`` what that line prints INSIDE the brackets where
    the runtime exposes it (``None`` on jax 0.9.0 / libtpu 0.0.34:
    ``telemetry.device_time.match_program`` then joins by the
    instructions' names and result types), ``scope_map()`` the
    instructions of the compiled text with the ``jax.named_scope`` each
    was written under. Nothing is read from the executable until one of
    those is asked for."""

    trace_id = None     # no runtime hands it out yet (the docstring)

    def __init__(self, key, exe):
        self.graph_key, self.kind, self.signature = key
        self.dispatches = STATS.dispatches.get(key, 0)
        self._exe = exe
        self._hlo = None
        self._map = None

    def _module(self):
        if self._hlo is None:
            self._hlo = self._exe.runtime_executable().hlo_modules()[0]
        return self._hlo

    def _release(self):
        """Keep the host-side HLO module, let the executable go (where
        the runtime hands no module out, :func:`programs` drops the
        entry)."""
        try:
            self._module()
        except Exception:
            pass
        self._exe = None

    @property
    def module_name(self) -> str:
        return self._module().name

    def text(self) -> str:
        """The compiled text, ``metadata={op_name=...}`` included."""
        return self._module().to_string()

    def scope_map(self) -> dict:
        """``{instruction name: telemetry.device_time.Op}``, parsed once."""
        if self._map is None:
            from deeplearning4j_tpu.telemetry import device_time

            self._map = device_time.parse_scopes(self.text())
        return self._map

    def __repr__(self):
        return (f"Program({self.kind!r}, dispatches={self.dispatches}, "
                f"loaded={self._exe is not None})")


_KEPT: list = []     # the dispatched executables of the last clear()
_KEEPER: Optional[threading.Thread] = None   # fetching their HLO modules


def programs() -> list:
    """The table: a :class:`Program` for every loaded executable, and for
    those the last :func:`clear` dropped after they had run. Built on
    demand; holds no device memory of its own."""
    keeper = _KEEPER
    if keeper is not None:
        keeper.join()
    with _LOCK:
        return [Program(key, exe) for key, exe in _EXECUTABLES.items()] \
            + [p for p in _KEPT if p._hlo is not None]


_NAMED_SHARDING = None  # lazy: keep this module importable without jax


def _leaf_sig(x):
    # jax Arrays cache their aval — ~0.1us vs ~6us for .shape/.dtype
    # property chains; this function runs per leaf per step
    a = getattr(x, "aval", None)
    if a is not None:
        global _NAMED_SHARDING
        if _NAMED_SHARDING is None:
            from jax.sharding import NamedSharding

            _NAMED_SHARDING = NamedSharding
        # sharding-aware signature (sharding subsystem): a leaf committed
        # to a mesh with a NON-TRIVIAL PartitionSpec keys its spec, so a
        # ZeRO-scattered opt tree, a TP-split param and their replicated
        # twins can never alias one executable (identical avals,
        # different layouts). Replicated/single-device leaves — the
        # single-model hot path — stay (shape, dtype) at one isinstance
        # check of extra cost.
        sh = getattr(x, "sharding", None)
        if type(sh) is _NAMED_SHARDING:
            spec = sh.spec
            if any(e is not None for e in spec):
                return (a.shape, a.dtype, str(spec))
        return (a.shape, a.dtype)
    if isinstance(x, np.ndarray) or hasattr(x, "dtype"):
        return (np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype")
                else x.dtype)
    # python scalars are weak-typed under jit; keyed by type
    return type(x).__name__


def signature_of(args):
    """Hashable abstract signature of a call's arguments: per-leaf
    (shape, dtype) + the argument treedef (which encodes structure,
    including None-vs-array optional args). Built from cached avals —
    this runs on the per-step dispatch path, so it must stay ~0.1us per
    leaf. Mesh-committed leaves with a non-trivial ``PartitionSpec``
    additionally key the spec (see ``_leaf_sig``) — sharded wrapper
    steps (ZeRO, partition-rule plans) cache through here, and two
    placements of the same avals must compile separately. Exotic
    layout mismatches outside the signature still fall back to the
    plain jit (see AotStep.__call__)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (tuple(map(_leaf_sig, leaves)), treedef)


# objects keyed by identity are PINNED here so their id() can never be
# recycled by the allocator and collide with a later object's key while
# the (immortal) executable cache still holds entries under it
_ID_PINNED: list = []


def pin_id(obj) -> int:
    """-> id(obj), with obj kept alive for the life of the cache."""
    with _LOCK:  # clear() mutates the pin list under the same lock
        _ID_PINNED.append(obj)
    return id(obj)


def graph_signature(obj, fallback=None) -> str:
    """Stable content key for a model configuration: the sha1 of its repr
    when that repr is deterministic, else an identity key (two instances
    then never share — the safe direction; the keyed object is pinned so
    CPython address reuse cannot alias it). conf objects are nested
    dataclasses whose reprs embed every hyperparameter; reprs containing
    raw object addresses simply fail to match across instances."""
    try:
        r = repr(obj)
    except Exception:
        r = None
    # "..." = numpy's large-array elision: the repr no longer uniquely
    # identifies the config, so fall back to identity (never shares)
    if r and "..." not in r:
        return hashlib.sha1(r.encode()).hexdigest()
    return f"id:{pin_id(obj if fallback is None else fallback)}"


class WarmupBudgetExceeded(RuntimeError):
    """A compile requested under an exhausted :class:`WarmupBudget`
    scope was refused. Raised BEFORE the compile starts, so the budget
    bounds work, not just accounting."""


class WarmupBudget:
    """Per-tenant cap on warmup compilation (multi-tenant serving: one
    model's warmup storm — a huge bucket ladder, a conf churning graph
    keys — must not monopolize the host's compile bandwidth while its
    co-tenants wait to come up).

    Activate with :func:`warmup_budget`; while the scope is active on
    the current thread, every FRESH compile through the cache (warm()
    or a dispatch miss) is charged to the budget, and a compile that
    would start with the budget exhausted raises
    :class:`WarmupBudgetExceeded` instead. Cache hits are free — a
    tenant whose buckets are already compiled (same conf as a live
    version) warms at zero cost. Thread-local: live traffic on other
    threads never sees another tenant's budget.
    """

    def __init__(self, name: str, max_compiles: Optional[int] = None,
                 max_compile_seconds: Optional[float] = None):
        self.name = name
        self.max_compiles = max_compiles
        self.max_compile_seconds = max_compile_seconds
        self.compiles = 0
        self.compile_seconds = 0.0
        self._lock = threading.Lock()

    def allow(self) -> bool:
        """Whether another compile may start under this budget."""
        with self._lock:
            if self.max_compiles is not None \
                    and self.compiles >= self.max_compiles:
                return False
            if self.max_compile_seconds is not None \
                    and self.compile_seconds >= self.max_compile_seconds:
                return False
            return True

    def charge(self, seconds: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_seconds += float(seconds)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "name": self.name,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 3),
                "max_compiles": self.max_compiles,
                "max_compile_seconds": self.max_compile_seconds,
            }


_BUDGET_SCOPE = threading.local()


def active_budget() -> Optional[WarmupBudget]:
    """The :class:`WarmupBudget` active on this thread (or None)."""
    return getattr(_BUDGET_SCOPE, "active", None)


@contextlib.contextmanager
def warmup_budget(budget: WarmupBudget):
    """Scope ``budget`` over this thread's compiles (nesting restores
    the outer scope on exit)."""
    prev = active_budget()
    _BUDGET_SCOPE.active = budget
    try:
        yield budget
    finally:
        _BUDGET_SCOPE.active = prev


# the compile-time program linter (analysis.program.on_compile), bound
# lazily on the first miss so importing this module never imports the
# analysis package; DL4J_TPU_PROGRAM_LINT=0 leaves it unbound
_LINT_HOOK = None
_LINT_INIT = False


def _program_lint(key, traced, exe) -> None:
    """Run the program linter over one fresh compile (caller holds
    ``_LOCK``). Lint failures never break a compile — except in strict
    mode, where ProgramLintError is the point."""
    global _LINT_HOOK, _LINT_INIT
    if not _LINT_INIT:
        _LINT_INIT = True
        if os.environ.get("DL4J_TPU_PROGRAM_LINT", "1") != "0":
            try:
                from deeplearning4j_tpu.analysis import program

                _LINT_HOOK = program.on_compile
            except Exception:
                _LINT_HOOK = None
    if _LINT_HOOK is None:
        return
    try:
        siblings = [k for k in _EXECUTABLES
                    if k[:2] == key[:2] and k != key]
        _LINT_HOOK(key, traced, exe, siblings)
    except Exception as e:
        if type(e).__name__ == "ProgramLintError":
            raise  # strict mode: surface the findings to the caller
        # any other lint crash must never take down a working compile


class AotStep:
    """A jitted step behind the executable cache.

    Call it exactly like the wrapped jit. The first call for a given
    input signature lowers + compiles (a recorded miss); every later call
    with the same signature — from this model instance or any other that
    shares the graph key — dispatches the cached executable (a hit).
    ``donate_argnums`` must be baked into ``jit_fn``; it is part of the
    key via ``fn_key`` so differently-donating wrappers never collide.
    """

    def __init__(self, jit_fn: Callable, graph_key: str, fn_key: str):
        self._jit = jit_fn
        self._key = (graph_key, fn_key)

    def _compile_locked(self, key, args):
        """Shared miss path (caller holds ``_LOCK``): returns
        ``(executable_or_None, newly_compiled)``. ``None`` means the
        cache is at ``_MAX_ENTRIES`` (a recorded overflow) — the caller
        falls back to the plain jit, whose own trace cache amortizes the
        signature; re-AOT-compiling per call would turn an evicted key
        into a compile-per-step pathology."""
        exe = _EXECUTABLES.get(key)
        if exe is not None:
            return exe, False
        if len(_EXECUTABLES) >= _MAX_ENTRIES:
            STATS.record_overflow()
            return None, False
        budget = active_budget()
        if budget is not None and not budget.allow():
            # refused BEFORE compiling: the budget bounds the work. Only
            # the budget-holder's own thread (a tenant warming up under
            # warmup_budget()) can land here — live traffic on other
            # threads compiles unbudgeted as always.
            raise WarmupBudgetExceeded(
                f"warmup budget {budget.name!r} exhausted "
                f"({budget.compiles} compiles, "
                f"{budget.compile_seconds:.2f}s) — refusing to compile "
                f"{key[1]}")
        place_compile_cache()
        t0 = time.perf_counter()
        # trace and lower as separate stages: .lower() runs the same
        # trace internally, but splitting keeps the jaxpr available for
        # the program linter at zero extra cost
        traced = self._jit.trace(*args)
        lowered = traced.lower()
        exe = lowered.compile()
        seconds = time.perf_counter() - t0
        STATS.record_miss(key, seconds)
        if budget is not None:
            budget.charge(seconds)
        _EXECUTABLES[key] = exe
        _program_lint(key, traced, exe)
        return exe, True

    def __call__(self, *args):
        key = self._key + (signature_of(args),)
        exe = _EXECUTABLES.get(key)
        if exe is None:
            with _LOCK:
                exe, _ = self._compile_locked(key, args)
            if exe is None:
                return self._jit(*args)
            STATS.record_first_dispatch(key)
            return exe(*args)
        try:
            out = exe(*args)
        except (TypeError, ValueError):
            # an input property outside the signature (committed mesh
            # sharding, exotic layout) diverged from the lowering — the
            # plain jit handles it (and compiles its own specialization).
            # Counted separately so the stats don't report a silent
            # retrace as a hit.
            STATS.record_fallback()
            return self._jit(*args)
        STATS.record_hit(key)
        return out

    def warm(self, *args) -> bool:
        """Compile-and-cache this signature WITHOUT dispatching — bucket
        warmup for serving engines (``parallel.batcher``): pre-compiling
        every padding bucket at server start costs compile time only, no
        device execution. Returns True when a new executable was compiled
        (a recorded miss), False when it was already cached (or the cache
        is full, a recorded overflow)."""
        key = self._key + (signature_of(args),)
        with _LOCK:
            _, compiled = self._compile_locked(key, args)
        return compiled

    # escape hatches for probes that want the raw jit (bench scripts call
    # .lower() for memory analysis)
    def lower(self, *args):
        return self._jit.lower(*args)

    @property
    def jit_fn(self):
        return self._jit


def wrap(jit_fn: Callable, graph_key: str, fn_key: str,
         enabled: Optional[bool] = None) -> Callable:
    """Wrap a jitted step in the AOT cache. ``enabled=False`` returns the
    jit untouched (env kill-switch honored when ``enabled`` is None)."""
    if enabled is None:
        enabled = os.environ.get("DL4J_TPU_AOT_CACHE", "1") != "0"
    return AotStep(jit_fn, graph_key, fn_key) if enabled else jit_fn
