"""Step-trace spans — the "where did this step's time go" primitive.

A span brackets one host-observable phase of a loop that feeds the
device::

    with telemetry.span("ingest"):
        features, labels = stage(batch)
    with telemetry.span("compute") as sp:
        loss = sp.set_result(train_step(...))   # async dispatch

The contract: **a span always lands in the bounded ring**, whether or not
``enable()`` was ever called. ``enable()`` turns on what is expensive:
the registry writes of the per-step helpers (``record_step``...: locks
and histograms), the ``host_gap`` clock, and ``sync=True``. A stall that
happens once in a day cannot be traced after the fact unless its last
seconds are already in memory, and a reader that comes after a benchmark
window (``benchmarks/readers/span_time.py``, ``idle_owner.py``) cannot
ask for recording beforehand; so the ring records without being asked,
and ``flightrec``'s bundle writes it out as ``trace.json``.

One record per finished span: name, start and duration on
``time.monotonic_ns()`` (the clock of ``tracing.py``, of the generation
engine's request handles and of the benchmark's trace window, so one
tie point serves all of them), its own ``id``, the ``parent_id`` of the
span that was open on the same thread when it started (the id, not the
name: two decode windows have the same name), the thread, and ``attrs``.
Counts ride on the span that did the work (``emitted``, ``joins``,
``lock_wait_us``...), so ratios are measured where the work happens and
outlive the object that did it.

The ring (process-wide, thread-safe under the GIL via
``deque(maxlen=...)``) holds ``RING_SIZE`` = 16,384 spans and evicts the
oldest silently. Sized from the two benchmark cells, as measured on
the chip's host (PERF.md, PR 25): the serving loop writes 6 spans a
decode window and 5 a prefill, the closed loop's caller 4 a request,
80 a second in all at nine windows a second; ``fit`` writes 4 a step
and a ``drain`` every twelfth, 40 a second at ten steps a second.
16,384 entries are 200 s of the busier of the two, and the reader runs
some 35 s after the window opened; a record is a tuple of nine fields,
so the full ring is about 3 MB. It can be exported as
Chrome-trace JSON (``chrome://tracing`` / Perfetto) or aggregated into
per-phase histograms (p50/p95/p99).

Cost: one ``Span`` object, two clock reads, one id and one ``deque``
append a span, 1.4 µs on the chip's host (1.8 µs with two attrs); no
lock, no registry write, no host sync
(pinned by tests/test_telemetry.py).

Timing is ``jax.block_until_ready``-aware: jax dispatch is asynchronous,
so a span around a jitted call measures only the enqueue (~µs) unless the
device result is forced. ``Span.set_result(x)`` registers the call's
output; after ``enable(sync=True)`` the span blocks on it before taking
the end timestamp, so the recorded duration is the real device time of
the phase. Otherwise nothing ever forces a host sync — the async fit
pipeline stays fully queued and the spans record host-side dispatch cost
only. A span whose work IS a host sync (``drain``, the engine's
``*.readback``) says so with the attr ``sync: true``.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

# Canonical training-phase names. Every instrumented training path
# (MultiLayerNetwork, ComputationGraph, SameDiff, ParallelWrapper,
# PipelineParallelWrapper) reports this same breakdown, and
# bench_resnet_profile.py --phases derives its row keys from these so the
# bench and the framework cannot drift (tests/test_telemetry.py).
# ``host_gap`` (round 11) is the time the host spends BETWEEN step
# dispatches — the launch-latency budget the fused multi-step driver
# amortizes over K steps; see host_gap_open/close below.
PHASE_INGEST = "ingest"
PHASE_COMPUTE = "compute"
PHASE_GRAD_SYNC = "grad_sync"
PHASE_HOST_GAP = "host_gap"
PHASES = (PHASE_INGEST, PHASE_COMPUTE, PHASE_GRAD_SYNC, PHASE_HOST_GAP)

RING_SIZE = 16384   # the module docstring says how it was sized

_enabled = False
_sync = False
_ring: "collections.deque" = collections.deque(maxlen=RING_SIZE)
_tls = threading.local()
_ids = itertools.count(1)   # next() is one C call: atomic under the GIL


class Span:
    __slots__ = ("name", "t0", "t1", "depth", "parent", "id", "parent_id",
                 "_result", "attrs")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.t0 = self.t1 = 0
        self.depth = 0
        self.parent: Optional[str] = None
        self.id = 0
        self.parent_id: Optional[int] = None
        self._result = None
        self.attrs = attrs

    def set_result(self, x):
        """Register the phase's device output; returned unchanged. In
        sync mode the span blocks on it before closing, so the duration
        covers the device work — in async mode it is never touched."""
        self._result = x
        return x

    def annotate(self, **kw):
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(kw)
        return self

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.depth = len(stack)
        self.id = next(_ids)
        if stack:
            self.parent = stack[-1].name
            self.parent_id = stack[-1].id
        stack.append(self)
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        if _sync and _enabled and self._result is not None:
            try:
                import jax

                jax.block_until_ready(self._result)
            except Exception:
                pass  # non-jax results (or deleted buffers) time as-is
        self.t1 = time.monotonic_ns()
        self._result = None  # never pin device buffers in the ring
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        _ring.append((self.name, self.t0, self.t1 - self.t0, self.depth,
                      self.parent, threading.get_ident(), self.attrs,
                      self.id, self.parent_id))
        return False


def span(name: str, **attrs) -> Span:
    """A timing span for one phase; always records into the ring.
    ``attrs`` are what is known when it opens; ``annotate`` adds what
    the work finds out."""
    return Span(name, attrs or None)


# --------------------------------------------------------------------------
# host-gap tracking (PHASE_HOST_GAP)
#
# jax dispatch is asynchronous, so a ``compute`` span measures only the
# enqueue — the cost the device actually SEES from the host is the gap
# between one dispatch returning and the next being issued (listener
# epilogues, health accounting, iterator work, batch staging). The fit
# loops bracket their dispatches with these helpers: ``host_gap_close(k)``
# right before a dispatch records the gap since the previous dispatch
# returned (annotated with the ``steps`` the upcoming dispatch fuses, so a
# K-step super-step's gap amortizes over K when aggregating per step) and
# ``host_gap_open()`` right after it re-arms the clock. State is
# thread-local; ``host_gap_reset()`` at fit entry re-arms from "now" so
# idle time between fits never records as a gap.
# --------------------------------------------------------------------------

def host_gap_reset() -> None:
    """Arm the gap clock at fit entry (records nothing)."""
    _tls.gap_open_ns = time.monotonic_ns() if _enabled else None


def host_gap_open() -> None:
    """Mark a step dispatch as returned: the host gap starts now."""
    if _enabled:
        _tls.gap_open_ns = time.monotonic_ns()


def host_gap_close(steps: int = 1) -> None:
    """About to dispatch the next step: record the elapsed host gap.
    ``steps`` = train steps the upcoming dispatch covers (K for a fused
    super-step) — consumers divide the gap by it for per-step cost."""
    if not _enabled:
        return
    t0 = getattr(_tls, "gap_open_ns", None)
    if t0 is None:
        return
    _tls.gap_open_ns = None
    t1 = time.monotonic_ns()
    _ring.append((PHASE_HOST_GAP, t0, t1 - t0, 0, None,
                  threading.get_ident(), {"steps": int(steps)},
                  next(_ids), None))


def host_gap_stop() -> None:
    """Disarm the gap clock (fit exit): idle time after a fit's last
    dispatch must never surface as a gap when some later call — a
    standalone ``fit_batch``, the next fit — closes the clock."""
    _tls.gap_open_ns = None


def host_gap_pause() -> None:
    """An INTENTIONAL host block is starting (the fit pipeline's
    ``drain`` parking on queued device results): stop the gap clock so
    device-wait time is never billed as host dispatch gap."""
    if _enabled and getattr(_tls, "gap_open_ns", None) is not None:
        _tls.gap_pause_ns = time.monotonic_ns()


def host_gap_resume() -> None:
    """The intentional block ended: shift the gap origin forward by the
    blocked interval."""
    t0 = getattr(_tls, "gap_pause_ns", None)
    if t0 is not None:
        _tls.gap_pause_ns = None
        if _enabled and getattr(_tls, "gap_open_ns", None) is not None:
            _tls.gap_open_ns += time.monotonic_ns() - t0


def enable(sync: bool = False, ring_size: int = RING_SIZE) -> None:
    """Turn on what costs more than a span: the registry writes of the
    ``record_*`` step helpers and the ``host_gap`` clock (spans land in
    the ring either way). ``sync=True`` makes spans block on their
    registered device result (``set_result``) for true per-phase device
    timing — at the cost of one host sync per span, so keep it off for
    production throughput runs."""
    global _enabled, _sync, _ring
    if ring_size != _ring.maxlen:
        _ring = collections.deque(_ring, maxlen=int(ring_size))
    _sync = bool(sync)
    _enabled = True


def disable() -> None:
    """Back to the default: spans still land in the ring; registry
    writes, the ``host_gap`` clock and ``sync`` are off."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def sync_mode() -> bool:
    return _enabled and _sync


def reset() -> None:
    """Drop recorded spans (flags untouched)."""
    _ring.clear()


def events() -> List[dict]:
    """Finished spans, oldest first, as dicts (``time.monotonic_ns``
    timestamps). ``parent`` is the parent's name, ``parent_id`` its
    ``id``."""
    return [{"name": n, "start_ns": t0, "duration_ns": dur, "depth": depth,
             "parent": parent, "thread": tid, "id": sid,
             "parent_id": parent_id,
             **({"attrs": attrs} if attrs else {})}
            for (n, t0, dur, depth, parent, tid, attrs, sid, parent_id)
            in list(_ring)]


def nearest_rank(sorted_vals, q: float):
    """Nearest-rank percentile (q in [0, 1]) over a sorted list — the ONE
    quantile definition shared by span phase stats and
    ``registry.Histogram`` so both /metrics surfaces agree."""
    n = len(sorted_vals)
    if n == 0:
        return float("nan")
    rank = max(1, -(-int(q * 1000 * n) // 1000))  # ceil(q*n), int math
    return sorted_vals[min(n, rank) - 1]


def phase_stats() -> Dict[str, dict]:
    """Aggregate the ring into per-phase duration histograms:
    ``{name: {count, total_ms, mean_ms, p50_ms, p95_ms, p99_ms, max_ms}}``
    (sorted by name — deterministic for a given ring)."""
    per: Dict[str, List[int]] = {}
    for rec in list(_ring):
        per.setdefault(rec[0], []).append(rec[2])
    out = {}
    for name in sorted(per):
        ds = sorted(per[name])
        total = sum(ds)
        out[name] = {
            "count": len(ds),
            "total_ms": total / 1e6,
            "mean_ms": total / len(ds) / 1e6,
            "p50_ms": nearest_rank(ds, 0.50) / 1e6,
            "p95_ms": nearest_rank(ds, 0.95) / 1e6,
            "p99_ms": nearest_rank(ds, 0.99) / 1e6,
            "max_ms": ds[-1] / 1e6,
        }
    return out


def export_chrome_trace(path: str) -> str:
    """Write the ring as Chrome-trace JSON (complete "X" events, µs),
    loadable in chrome://tracing / Perfetto / TensorBoard's trace viewer.
    Returns ``path``."""
    pid = os.getpid()
    evts = []
    for (name, t0, dur, depth, parent, tid, attrs, sid,
         parent_id) in list(_ring):
        args = {"depth": depth, "id": sid}
        if parent:
            args["parent"] = parent
            args["parent_id"] = parent_id
        if attrs:
            args.update(attrs)
        evts.append({"name": name, "ph": "X", "ts": t0 / 1e3,
                     "dur": dur / 1e3, "pid": pid, "tid": tid,
                     "args": args})
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": evts, "displayTimeUnit": "ms"}, f)
    return path
