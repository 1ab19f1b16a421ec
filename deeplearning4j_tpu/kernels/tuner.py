"""Per-shape kernel autotuner + persistent digest-verified tuning cache.

The autotuner benchmarks every legal tiling candidate a registry kernel
declares for one concrete ``(shape, dtype, backend)`` envelope and
records the winner into a process-global :class:`TuningCache`. The
cache persists to disk with the checkpoint discipline (canonical JSON,
sha256 content digest recorded inside the file, temp + ``os.replace``
publish), so winners tuned in one process select identically in the
next — and a hand-edited/corrupt file is REFUSED with a named error
(:class:`TuningCacheCorruptError`) while selection falls back to stock
XLA instead of running an unverified layout.

Every mutation bumps ``TuningCache.epoch``; the registry memoizes its
per-kernel tuning digests against the epoch, so the per-step "has the
winner set changed?" check the model fit paths run is two dict lookups,
not a hash pass.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

CACHE_VERSION = 1

# default trial protocol: candidates are compared by min-of-`trials`
# wall time after `warmup` discarded runs (min is the standard
# autotuner statistic: noise only ever ADDS time)
DEFAULT_WARMUP = 1
DEFAULT_TRIALS = 3
DEFAULT_MAX_CANDIDATES = 16


class TuningCacheCorruptError(RuntimeError):
    """A persisted tuning cache failed its digest/format verification.
    The cache refuses the file's winners (selection falls back to stock
    XLA); the error names the path and the reason."""

    def __init__(self, path: str, reason: str):
        super().__init__(f"kernel tuning cache {path!r} refused: {reason}")
        self.path = path
        self.reason = reason


def _canonical(winners: dict) -> str:
    return json.dumps(winners, sort_keys=True, separators=(",", ":"))


def _digest(winners: dict) -> str:
    return hashlib.sha256(_canonical(winners).encode()).hexdigest()


class TuningCache:
    """``kernel_id -> {envelope_key -> {"tiling": [bm, bn, bk],
    "ms": float}}`` with optional disk persistence.

    Thread-safe; ``epoch`` increments on every mutation (record / load /
    clear) so digest consumers can memoize against it.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._winners: Dict[str, Dict[str, dict]] = {}
        self.epoch = 0
        self.hits = 0
        self.misses = 0
        self.path: Optional[str] = None

    # --- selection --------------------------------------------------------
    def winner(self, kernel_id: str, env_key: str) -> Optional[dict]:
        """The recorded winner for one envelope (None = untuned — the
        caller falls back to stock XLA)."""
        with self._lock:
            rec = self._winners.get(kernel_id, {}).get(env_key)
            if rec is None:
                self.misses += 1
            else:
                self.hits += 1
            return dict(rec) if rec is not None else None

    def winners(self, kernel_id: str) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v)
                    for k, v in self._winners.get(kernel_id, {}).items()}

    def entries(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._winners.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": sum(len(v) for v in self._winners.values()),
                "hits": self.hits,
                "misses": self.misses,
                "epoch": self.epoch,
                "path": self.path,
            }

    # --- mutation ---------------------------------------------------------
    def record(self, kernel_id: str, env_key: str,
               tiling: Tuple[int, int, int], ms: float,
               backend: str = "") -> None:
        """Record one envelope's winning tiling (and persist when a path
        is bound)."""
        with self._lock:
            self._winners.setdefault(kernel_id, {})[env_key] = {
                "tiling": [int(t) for t in tiling],
                "ms": float(ms),
                "backend": backend,
            }
            self.epoch += 1
            if self.path is not None:
                self._save_locked()

    def clear(self) -> None:
        with self._lock:
            self._winners.clear()
            self.hits = self.misses = 0
            self.epoch += 1
            self.path = None

    # --- persistence ------------------------------------------------------
    def bind(self, path: str, load: bool = True) -> "TuningCache":
        """Attach a persistence path; an existing file is loaded (digest
        verified) and future records publish through it. A corrupt file
        raises :class:`TuningCacheCorruptError` AFTER resetting the
        in-memory winners — the process keeps running on stock XLA."""
        if load and os.path.exists(path):
            try:
                with open(path, "r") as f:
                    blob = json.load(f)
            except (OSError, ValueError) as e:
                self._refuse(path, f"unreadable JSON ({e})")
            if not isinstance(blob, dict) or "winners" not in blob \
                    or "digest" not in blob:
                self._refuse(path, "missing winners/digest fields")
            if int(blob.get("version", -1)) != CACHE_VERSION:
                self._refuse(path,
                             f"version {blob.get('version')!r} != "
                             f"{CACHE_VERSION}")
            if _digest(blob["winners"]) != blob["digest"]:
                self._refuse(path, "content digest mismatch")
            with self._lock:
                self._winners = {
                    str(k): {str(ek): dict(rec) for ek, rec in v.items()}
                    for k, v in blob["winners"].items()}
                self.epoch += 1
                self.path = path
        else:
            with self._lock:
                self.path = path
        return self

    def _refuse(self, path: str, reason: str) -> None:
        """Corruption: drop any half-loaded state, detach the path, and
        raise the NAMED error — selection falls back to stock XLA."""
        with self._lock:
            self._winners = {}
            self.epoch += 1
            self.path = None
        raise TuningCacheCorruptError(path, reason)

    def save(self) -> None:
        with self._lock:
            if self.path is None:
                raise ValueError("tuning cache has no bound path "
                                 "(call bind(path) first)")
            self._save_locked()

    def _save_locked(self) -> None:
        # checkpoint discipline: content digest recorded inside the
        # file, pid-suffixed temp + os.replace publish (a crash
        # mid-write leaves the prior complete file authoritative, and
        # two processes sharing one cache path never interleave writes
        # into the same temp file — the pod/serializer convention)
        blob = {
            "version": CACHE_VERSION,
            "winners": self._winners,
            "digest": _digest(self._winners),
        }
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(blob, f, sort_keys=True, indent=1)
        os.replace(tmp, self.path)


# the process-global cache every selection reads
TUNING = TuningCache()


def set_tuning_cache(path: str, load: bool = True) -> TuningCache:
    """Bind the process-global tuning cache to ``path`` (loading an
    existing file, digest-verified). Raises
    :class:`TuningCacheCorruptError` on a refused file — the in-memory
    cache is left EMPTY, so kernel selection safely falls back to
    stock XLA."""
    return TUNING.bind(path, load=load)


# --------------------------------------------------------------------------
# the autotune loop
# --------------------------------------------------------------------------

class AutotuneResult:
    def __init__(self, kernel_id: str, env_key: str,
                 tiling: Tuple[int, int, int], ms: float,
                 trials: List[dict]):
        self.kernel_id = kernel_id
        self.env_key = env_key
        self.tiling = tiling
        self.ms = ms
        self.trials = trials

    @property
    def refused(self) -> List[dict]:
        """The candidates the compiler (or the run) rejected, in sweep
        order: ``{"tiling": [...], "error": repr(exception)}``."""
        return [t for t in self.trials if "error" in t]

    def __repr__(self):
        return (f"AutotuneResult({self.kernel_id}, {self.env_key}, "
                f"tiling={self.tiling}, ms={self.ms:.3f}, "
                f"{len(self.trials)} candidates, "
                f"{len(self.refused)} refused)")


def autotune(kernel, env, cache: Optional[TuningCache] = None,
             warmup: int = DEFAULT_WARMUP, trials: int = DEFAULT_TRIALS,
             max_candidates: int = DEFAULT_MAX_CANDIDATES,
             record: bool = True) -> AutotuneResult:
    """Benchmark ``kernel``'s legal tilings for one envelope and record
    the winner.

    ``kernel`` is a ``registry.Kernel``; ``env`` its envelope object.
    Each candidate compiles one jitted wrapper, runs ``warmup`` settle
    calls, then takes min-of-``trials`` wall time with the outputs
    forced. Off-TPU the kernel executes through the Pallas interpreter,
    so timings rank the interpreter, not the MXU — the machinery
    (sweep, winner record, persistence, digest re-keying) is what the
    CPU container validates; real rankings need the TPU backend
    (docs/kernels.md states the caveat).
    """
    import jax

    from deeplearning4j_tpu import telemetry

    cache = TUNING if cache is None else cache
    if not kernel.supports(env):
        raise ValueError(f"kernel {kernel.kernel_id!r} does not support "
                         f"envelope {env.key!r}")
    cands = kernel.candidates(env, limit=max_candidates)
    if not cands:
        raise ValueError(f"no legal tilings for envelope {env.key!r}")
    args = kernel.make_inputs(env, seed=0)
    results = []
    for tiling in cands:
        fn = jax.jit(kernel.build(env, tiling))
        try:
            for _ in range(max(1, warmup)):
                jax.block_until_ready(fn(*args))
            best = float("inf")
            for _ in range(max(1, trials)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                best = min(best, time.perf_counter() - t0)
        except Exception as e:
            # a candidate the compiler rejects does not end the sweep
            # (Mosaic tile limits vary by chip); it stays in the result
            # with the compiler's message (AutotuneResult.refused)
            results.append({"tiling": list(tiling), "error": repr(e)})
            telemetry.record_autotune_trial(kernel.kernel_id)
            continue
        results.append({"tiling": list(tiling), "ms": best * 1e3})
        telemetry.record_autotune_trial(kernel.kernel_id)
    timed = [r for r in results if "ms" in r]
    if not timed:
        raise RuntimeError(
            f"autotune: every candidate failed for {env.key!r}: {results}")
    win = min(timed, key=lambda r: r["ms"])
    if record:
        cache.record(kernel.kernel_id, env.key, tuple(win["tiling"]),
                     win["ms"], backend=env.backend)
        telemetry.record_autotune_winner(kernel.kernel_id)
        telemetry.record_tuning_cache(cache.hits, cache.entries())
    return AutotuneResult(kernel.kernel_id, env.key, tuple(win["tiling"]),
                          win["ms"], results)
