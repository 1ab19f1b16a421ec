"""Iteration-level continuous batching for autoregressive generation.

``parallel.batcher.InferenceEngine`` coalesces requests into shared
launches at REQUEST granularity — right for one-shot inference, wrong
for generation, where a request is a token loop of unpredictable length:
batching whole loops means every sequence in a batch waits for the
longest one, and freed slots stay empty until the batch drains. This
engine schedules at TOKEN granularity (the vLLM iteration-level shape)
on top of ``nn.decoding.TransformerDecoder``:

- one persistent decode loop owns a device-resident state of
  ``max_batch`` KV-cache rows;
- every iteration dispatches ONE fused window of ``fused_steps=K``
  decode steps for the whole running batch (PR 7's scan-per-dispatch:
  K tokens per sequence per host dispatch, finished rows masked to
  no-ops in-graph);
- between windows, finished sequences (EOS / max-tokens / expired
  deadline) retire and free their rows, and waiting prompts prefill
  into the freed rows in one launch — no sequence ever waits for the
  batch to drain;
- the loop runs ONE WINDOW AHEAD of what it has read: window n+1 (and
  any join before it) is dispatched onto the state window n returns
  while n still runs, and only then are n's tokens read and accounted,
  so the host's work between windows is hidden behind the device's
  (:class:`GenerationEngine` says what is in flight and what that
  costs).

The admission-control surface is the batcher's, reused wholesale: the
same queue semantics, ``max_queue`` → :class:`ServerOverloadedError`
(503), per-request deadlines → :class:`DeadlineExpiredError`, malformed
prompts → :class:`BadRequestError` at submit, and a
:class:`~deeplearning4j_tpu.resilience.breaker.CircuitBreaker` shedding
at submit while the decode path is failing. Every executable (prefill,
join, decode, grow) is AOT-cached with its bucket geometry in the key;
``warmup()`` pre-compiles all of them, so steady-state traffic of any
prompt/output-length mix runs zero-recompile (``stats()`` exposes the
invariant).

Greedy decode through this engine is pinned token-identical to
``TransformerDecoder.generate`` (the sequential reference): the decode
arithmetic is row-independent and every row runs the same compiled
executables, so continuous scheduling changes WHEN a sequence's tokens
are computed, never WHAT they are.

One throughput feature rides on top, OFF by default and composable
with continuous batching:

- **Radix-tree prefix caching** (``prefix_cache=True``): finished
  prefills donate page-aligned KV blocks to a refcounted
  :class:`~deeplearning4j_tpu.parallel.prefix_cache.PrefixCache`;
  a new request pins the longest cached prefix at submit, the engine
  scatters the pinned pages into the joining row with the
  ``prefix_attach`` executable and prefills ONLY the suffix
  (``gen_prompt_sfx`` + ``prefix_join``) — TTFT drops by the share of
  the prompt served from cache. Pinned pages are decref'd on every
  terminal edge (finish, queue expiry, mid-generation deadline,
  dispatch failure, close), so the tree always returns to its
  steady-state page count. Its executables are keyed into the AOT cache
  (``prefix_attach:s:t:b``, ``gen_prompt_sfx:t:p:b``,
  ``prefix_join:s:t:b``) and ``warmup()`` pre-compiles every feasible
  geometry, so mixed hit/miss traffic stays zero-recompile.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import List, Optional, Sequence

import jax
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.decoding import TransformerDecoder, bucket_for
from deeplearning4j_tpu.telemetry import tracing
from deeplearning4j_tpu.optimize import aot_cache
from deeplearning4j_tpu.parallel.batcher import (
    BadRequestError,
    DeadlineExpiredError,
    ServerOverloadedError,
)
from deeplearning4j_tpu.parallel.prefix_cache import PrefixCache
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.breaker import (
    CircuitBreaker,
    CircuitOpenError,
)
from deeplearning4j_tpu.resilience.retry import SERVING_RETRY

_ENGINE_SEQ = itertools.count(1)


def _lock_wait(sp, asked_ns: int):
    """Called first thing under the engine's lock: put the time from
    asking for it (``asked_ns``) to holding it on ``sp`` as
    ``lock_wait_us``."""
    sp.annotate(lock_wait_us=(time.monotonic_ns() - asked_ns) / 1e3)


@dataclasses.dataclass
class GenerationConfig:
    """Scheduler policy knobs (the generation twin of
    ``BatchingConfig``)."""

    max_batch: int = 8          # KV-cache rows (running-batch capacity)
    fused_steps: int = 4        # K decode steps per host dispatch
    max_queue: int = 256        # waiting requests before 503 rejection
    timeout_ms: Optional[float] = None  # default per-request deadline
    kv_bucket_min: int = 32     # smallest KV length bucket
    prompt_bucket_min: int = 8  # smallest prompt padding bucket
    max_new_default: int = 64   # max_new_tokens when the caller omits it
    # radix-tree prompt-prefix KV cache. Off by default; page size is
    # the trie granularity in tokens, pages the LRU eviction budget.
    prefix_cache: bool = False
    prefix_page: int = 16
    prefix_cache_pages: int = 256
    # widest group of prompts one prefill launch takes (default
    # max_batch): a long-context model compiles one prefill per prompt
    # bucket at width 1, not one per power of two up to max_batch
    join_bucket_max: Optional[int] = None


class _GenRequest:
    __slots__ = ("tokens", "n", "max_new", "eos", "temp", "rng", "deadline",
                 "event", "out", "error", "t0", "t_join", "t_first",
                 "t_done", "row", "planned", "prefix_len", "prefix_nodes",
                 "trace")

    def __init__(self, tokens, max_new, eos, temp, rng, deadline, t0,
                 trace=None):
        self.tokens = tokens
        self.n = len(tokens)
        self.max_new = max_new
        self.eos = eos
        self.temp = temp
        self.rng = rng              # [2] uint32 per-request PRNG key
        self.deadline = deadline
        self.event = threading.Event()
        self.out: List[int] = []
        self.error: Optional[BaseException] = None
        # time.monotonic() seconds: submitted, picked from the queue
        # (t_join - t0 is the queue wait), first token, completion (or
        # failure: whenever ``event`` was set)
        self.t0 = t0
        self.t_join: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.row: Optional[int] = None
        # the most tokens ``out`` can hold once every program dispatched
        # for this request has been read: 1 for its prefill, K for each
        # decode window launched with it
        self.planned = 0
        self.prefix_len = 0          # tokens served from the prefix cache
        self.prefix_nodes: list = []  # pinned trie nodes (one pin each)
        self.trace = trace           # request trace (None when disabled)

    def complete(self, now: float):
        """Stamp ``t_done`` and wake the waiter."""
        self.t_done = now
        self.event.set()

    @property
    def ends_in_flight(self) -> bool:
        """Whether the programs already dispatched carry this request to
        ``max_new`` tokens: it ends by length inside them, whether or not
        a stop token comes first, and no later window can emit for it."""
        return self.planned >= self.max_new


class _Window:
    """One decode window in flight: dispatched, not yet read. ``rows`` is
    ITS OWN snapshot of row -> request (a row given away while the window
    runs still yields this window's column to the request that held it);
    the device arrays come with the launch."""

    __slots__ = ("rows", "kv_bucket", "toks", "emitted", "counts")

    def __init__(self, rows: dict):
        self.rows = rows
        self.kv_bucket = self.toks = self.emitted = self.counts = None


class _Prefill:
    """One prefill launch whose first tokens are not read yet."""

    __slots__ = ("kind", "joins", "bp", "tok", "active", "kv", "offset",
                 "seconds")

    def __init__(self, kind, joins, bp, tok, active, kv, offset, seconds):
        self.kind, self.joins, self.bp = kind, joins, bp
        self.tok, self.active = tok, active
        self.kv, self.offset = kv, offset   # for the prefix cache's pages
        self.seconds = seconds              # host time of stage + launch


class GenerationEngine:
    """Continuous-batching generation front of one causal LM.

    Usage::

        engine = GenerationEngine(net, GenerationConfig(max_batch=8))
        engine.warmup()                    # pre-compile every bucket/K
        toks = engine.generate([1, 2, 3], max_new_tokens=32)
        engine.close()

    ``model`` is a ``TransformerDecoder``, an initialized causal-LM
    ``ComputationGraph``, or a ``zoo.TransformerEncoder(lm_head=True)``
    config (initialized fresh). All scheduling state (row ownership,
    queue, output accumulation) lives behind one condition variable, the
    same discipline as the batcher; device state is touched only by the
    single decode-loop thread.

    **The loop runs one decode window ahead of what it has read.** Nothing
    a launch needs comes from a read-back: the donated state carries
    tokens, positions, liveness, ``max_new``, ``eos`` and the rng, and a
    row retires in-graph. So with window n in flight (dispatched, not
    read) an iteration admits, dispatches any join's ``prompt_fn`` and
    ``join_fn`` onto the state n returns, dispatches window n+1 behind
    them, and only THEN blocks on n's tokens, accounts them and reads the
    join's first tokens. The device runs n -> prompt -> join -> n+1 while
    the host does all of that; the depth is the constant 1 (a further
    window in flight would only delay a join by its whole length).

    - *What is in flight*: at most two windows at the moment of a
      read-back, each with its own snapshot of row -> request
      (:class:`_Window`), and the prefills dispatched between them.
      ``_rows`` is who owns a row for the NEXT launch.
    - *When a row is given away*: the host knows ``planned``, the tokens a
      request holds once everything dispatched is read (1 + K a window). A
      row whose request ends by length inside the windows in flight counts
      as free at admission, so the next prompt joins right behind the old
      request's last window and the batch stays full; the old request
      still gets its last tokens from that window's column.
    - *What a stop token costs*: an early end is seen at the read-back,
      one window late. The window already in flight carries the dead row
      (K row-steps that emit nothing, masked in-graph) and the row is
      free one window later than it could be. An end by length costs
      nothing.
    - *When it does not run ahead*: no window is launched unless a row can
      still emit in it, so an engine going idle launches nothing into an
      empty batch; with nothing in flight a join's first token is read at
      once, as there is nothing to hide the read behind. ``gen.wait`` is
      entered only with nothing in flight.
    - A deadline that passes is seen at a read-back too: ``release_fn``
      lands behind the window in flight, and what that window emitted for
      the dead request is dropped. A dispatch failure discards the windows
      in flight with the state and fails every request in them once.

    ``stats()`` counts how often it engages: ``windows_total``,
    ``windows_ahead_total`` (launched with another still unread),
    ``joins_ahead_total`` (rows given away before their last window was
    read), ``windows_empty_total`` (windows that emitted nothing).
    """

    def __init__(self, model, config: Optional[GenerationConfig] = None,
                 breaker: Optional[CircuitBreaker] = ...,
                 retry=..., name: Optional[str] = None):
        self.config = config or GenerationConfig()
        # multi-tenant identity (parallel.platform): same semantics as
        # the batcher — named engines label dl4j_decode_* series with
        # model=<name>, default their breaker to "serving:<name>" (one
        # /health key per model) and fire "decode.launch:<name>" so a
        # chaos plan can target exactly this tenant.
        self.name = name
        self._fault_site = (f"decode.launch:{name}" if name
                            else "decode.launch")
        cfg = self.config
        if isinstance(model, TransformerDecoder):
            self._dec = model
        elif hasattr(model, "params"):  # an initialized ComputationGraph
            self._dec = TransformerDecoder(
                model, max_batch=cfg.max_batch,
                kv_bucket_min=cfg.kv_bucket_min,
                prompt_bucket_min=cfg.prompt_bucket_min,
                join_bucket_max=cfg.join_bucket_max)
        elif hasattr(model, "decoder"):  # a zoo TransformerEncoder config
            self._dec = model.decoder(
                max_batch=cfg.max_batch,
                kv_bucket_min=cfg.kv_bucket_min,
                prompt_bucket_min=cfg.prompt_bucket_min,
                join_bucket_max=cfg.join_bucket_max)
        else:
            raise TypeError(
                "model must be a TransformerDecoder, a causal-LM "
                "ComputationGraph, or a zoo config with .decoder()")
        if self._dec.max_batch != cfg.max_batch:
            cfg.max_batch = self._dec.max_batch
        # a layer whose state is not K/V pages cannot be rebuilt from the
        # prefix cache's pages: refused here, by name, not at the first hit
        missing = (self._dec.walks_missing("prefill_suffix")
                   if cfg.prefix_cache else [])
        if missing:
            raise ValueError(
                f"prefix_cache=True is not supported with "
                f"{', '.join(missing)}: no prefill_suffix() for that "
                f"layer's state")
        self._prefix = (PrefixCache(cfg.prefix_page, cfg.prefix_cache_pages)
                        if cfg.prefix_cache else None)
        self._breaker = (CircuitBreaker(
            name=(f"serving:{name}" if name
                  else f"decode-{next(_ENGINE_SEQ)}"))
            if breaker is ... else breaker)
        self._retry = SERVING_RETRY if retry is ... else retry
        self._queue: deque = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # device decode state, owned by the decode loop. _rows is who
        # owns a row for the NEXT launch; _flight the decode windows
        # dispatched and not read, oldest first, each with the rows IT
        # decodes: both change under _cond only, _flight on the loop's
        # thread only. _unread, the prefills dispatched behind a window
        # in flight, is the loop's own.
        self._state = None
        self._S = self._dec.kv_ladder[0]
        self._rows: List[Optional[_GenRequest]] = [None] * cfg.max_batch
        self._flight: deque = deque()
        self._unread: List[_Prefill] = []
        self._joined_total = 0
        self._retired_total = 0
        self._tokens_total = 0
        # how often the loop runs ahead (class docstring)
        self._windows_total = 0
        self._windows_ahead_total = 0
        self._joins_ahead_total = 0
        self._windows_empty_total = 0
        # host wall time of the prefill launches and of the decode
        # windows, each INCLUDING the wait for the device's result
        self._prefill_seconds = 0.0
        # prompt tokens the prefill launches walked: the prompts' own, and
        # the launches' buckets (rows x positions, padding included)
        self._prefill_live_tokens = 0
        self._prefill_bucket_tokens = 0
        self._decode_seconds = 0.0
        # what the cached layers counted in-graph, summed over the windows
        self._layer_counts = dict.fromkeys(self._dec.counter_names, 0)
        # sequence number of the loop's iteration: every span of one
        # iteration (admit, prefills, decode window) carries it as ``n``
        self._window = 0
        # optional SLOMonitor (parallel.platform wires it): TTFT + error
        # outcomes observed synchronously at the same points telemetry
        # records them
        self._slo = None
        held = self._dec.state_bytes(self._S)
        self._state_kinds = ",".join(sorted(held))
        telemetry.record_decode_state_bytes(held)
        telemetry.register_generation_engine(self)

    # --- submit / wait ------------------------------------------------------
    def submit(self, tokens: Sequence[int], max_new_tokens: int = None,
               eos_id: Optional[int] = None, temperature: float = 0.0,
               seed: int = 0, timeout_ms=..., traceparent=None
               ) -> _GenRequest:
        """Validate and enqueue one generation request; returns a handle
        whose ``event`` fires when the token list (or error) is in.
        Admission order matches the batcher: malformed → 400, queue full
        → 503, breaker open → shed (503) — breaker LAST so a rejected
        request never burns a half-open probe ticket."""
        with telemetry.span("gen.submit"):
            return self._submit(tokens, max_new_tokens, eos_id,
                                temperature, seed, timeout_ms, traceparent)

    def _submit(self, tokens, max_new_tokens, eos_id, temperature, seed,
                timeout_ms, traceparent) -> _GenRequest:
        trace = tracing.start_trace(
            "generate", traceparent=traceparent,
            attrs={"model": self.name} if self.name else None)
        if max_new_tokens is None:
            max_new_tokens = self.config.max_new_default
        try:
            with telemetry.span("gen.submit.validate"):
                toks = self._dec.validate_request(tokens,
                                                  int(max_new_tokens))
                if temperature < 0:
                    raise ValueError("temperature must be >= 0")
                if eos_id is not None and not (
                        0 <= int(eos_id) < self._dec.vocab_size):
                    raise ValueError("eos_id outside the vocabulary")
        except ValueError as e:
            telemetry.record_decode_request("bad_request", model=self.name)
            tracing.finish_trace(trace, "bad_request")
            raise BadRequestError(str(e)) from None
        if timeout_ms is ...:
            timeout_ms = self.config.timeout_ms
        t0 = time.monotonic()
        deadline = t0 + timeout_ms / 1000.0 if timeout_ms else None
        # a device program and its read-back, on the caller's thread
        with telemetry.span("gen.submit.key", sync=True):
            rng = np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)
        req = _GenRequest(toks, int(max_new_tokens),
                          -1 if eos_id is None else int(eos_id),
                          float(temperature), rng, deadline, t0,
                          trace=trace)
        if self._prefix is not None:
            # pin the longest cached prefix NOW (refcounts on the whole
            # path) so eviction can't free the pages before the join;
            # fits() rejects matches whose padded suffix bucket would
            # push the row past max_len (the suffix join writes a
            # ts-wide block at offset m, so m + bucket(n - m) must fit).
            ladder = self._dec.prompt_ladder
            with telemetry.span("gen.submit.prefix") as sp:
                m, nodes = self._prefix.match(
                    req.tokens, limit=req.n - 1,
                    fits=lambda mm: mm + bucket_for(
                        req.n - mm, ladder) <= self._dec.max_len)
                sp.annotate(prefix_len=m)
            req.prefix_len = m
            req.prefix_nodes = list(nodes)
        try:
            with telemetry.span("gen.submit.enqueue") as sp:
                asked = time.monotonic_ns()
                with self._cond:
                    _lock_wait(sp, asked)
                    if self._stop:
                        tracing.finish_trace(trace, "shutdown")
                        raise RuntimeError("generation engine is closed")
                    if len(self._queue) >= self.config.max_queue:
                        telemetry.record_decode_request("rejected",
                                                        model=self.name)
                        tracing.finish_trace(trace, "rejected")
                        raise ServerOverloadedError(
                            f"generation queue full "
                            f"({self.config.max_queue} waiting)")
                    if (self._breaker is not None
                            and not self._breaker.allow()):
                        telemetry.record_decode_request("shed",
                                                        model=self.name)
                        tracing.finish_trace(trace, "shed")
                        raise CircuitOpenError(
                            f"circuit breaker {self._breaker.name!r} is "
                            f"{self._breaker.state}; request shed")
                    self._queue.append(req)
                    tracing.trace_event(
                        trace, "queued",
                        {"prefix_len": req.prefix_len} if req.prefix_len
                        else None)
                    self._cond.notify_all()
        except BaseException:
            self._release_prefix(req)
            raise
        self._ensure_thread()
        return req

    def _release_prefix(self, req: _GenRequest):
        """Drop the request's pins on its prefix-cache path. Called on
        EVERY terminal edge exactly once (the list is cleared), so the
        tree's refcounts always return to steady state."""
        nodes, req.prefix_nodes = req.prefix_nodes, []
        if nodes and self._prefix is not None:
            self._prefix.release(nodes)

    def result(self, req: _GenRequest) -> List[int]:
        """Block until ``req`` completes; returns its generated token
        ids (EOS included when hit) or raises its error."""
        req.event.wait()
        if req.error is not None:
            raise req.error
        return req.out

    def generate(self, tokens, **kw) -> List[int]:
        """Synchronous request: enqueue, join the running batch at the
        next iteration, collect tokens until EOS/max-tokens."""
        return self.result(self.submit(tokens, **kw))

    # --- warmup / stats -----------------------------------------------------
    def warmup(self, autotune_kernels: bool = False, **autotune_kw) -> dict:
        """Pre-compile every (KV bucket × K) decode window, every
        (prompt bucket × join bucket) prefill, every join/grow hop —
        compile-only, no dispatch. After this the zero-recompile
        invariant holds for ANY mix of prompt/output lengths up to
        ``max_len`` (pinned by test). With the prefix cache every
        feasible attach/suffix-prefill/suffix-join geometry is warmed
        too, so mixed hit/miss traffic stays zero-recompile.

        ``autotune_kernels`` (with ``conf.use_kernels``) tunes every
        prompt-bucket attention envelope FIRST, so the warmed prefill
        executables bake the flash winners — tuning after warmup would
        mint new ``kern:`` keys and re-warm from scratch."""
        if autotune_kernels and self._dec.use_kernels:
            from deeplearning4j_tpu import kernels

            kernels.autotune_decoder(self._dec, **autotune_kw)
        out = self._dec.warm_all(
            fused_steps=(1, self.config.fused_steps),
            prefix=self._prefix is not None)
        out["kernels"] = {"enabled": self._dec.use_kernels,
                          "tag": self._dec._ktag()}
        return out

    def queue_depth(self) -> int:
        return len(self._queue)

    def stats(self) -> dict:
        """Scheduler + cache counters: running-batch occupancy, rows in
        use, retire/join/token totals, current KV bucket, the AOT cache
        (zero-recompile invariant reads off ``misses``), breaker state.
        ``prefill_seconds`` and ``decode_seconds`` are HOST wall time of
        the prefill launches and the decode windows, the wait for the
        device's result (the read-back) included: not device time. A
        decode window's is its ``gen.decode`` span up to the read-back's
        end (the launch of the window ahead and the read of the one
        before); a prefill's leaves out what the loop did between its
        launch and the read of its first tokens. The four ``windows_*`` /
        ``joins_ahead_total`` counts say how often the loop ran ahead
        (class docstring). ``prefill_live_tokens_total`` over
        ``prefill_bucket_tokens_total``: the prompts' own tokens over the
        positions their launches walked (rows x prompt bucket: a layer
        that scans a prompt walks its padding too)."""
        with self._cond:
            out = {
                "rows": self.config.max_batch,
                "rows_in_use": sum(r is not None for r in self._rows),
                "occupancy": (sum(r is not None for r in self._rows)
                              / max(self.config.max_batch, 1)),
                "queued": len(self._queue),
                "kv_bucket": self._S,
                "fused_steps": self.config.fused_steps,
                "joined_total": self._joined_total,
                "retired_total": self._retired_total,
                "tokens_total": self._tokens_total,
                "windows_total": self._windows_total,
                "windows_ahead_total": self._windows_ahead_total,
                "joins_ahead_total": self._joins_ahead_total,
                "windows_empty_total": self._windows_empty_total,
                "prefill_seconds": round(self._prefill_seconds, 4),
                "prefill_live_tokens_total": self._prefill_live_tokens,
                "prefill_bucket_tokens_total": self._prefill_bucket_tokens,
                "decode_seconds": round(self._decode_seconds, 4),
                "layer_counts": dict(self._layer_counts),
            }
        out["buckets"] = {"kv": list(self._dec.kv_ladder),
                          "prompt": list(self._dec.prompt_ladder),
                          "join": list(self._dec.join_ladder)}
        out["kernels"] = {"enabled": self._dec.use_kernels,
                          "tag": self._dec._ktag()}
        out["aot_cache"] = aot_cache.stats()
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        if self._breaker is not None:
            out["circuit_breaker"] = self._breaker.status()
        return out

    @property
    def breaker(self) -> Optional[CircuitBreaker]:
        return self._breaker

    @property
    def decoder(self) -> TransformerDecoder:
        return self._dec

    # --- decode loop --------------------------------------------------------
    def _ensure_thread(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._cond:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name="dl4j-decode-loop", daemon=True)
                self._thread.start()

    def _span(self, name: str, **attrs):
        """A span of the loop's current iteration."""
        return telemetry.span(name, n=self._window, **attrs)

    def _live_rows(self) -> dict:
        """``{row: request}`` of the rows that can still emit in a window
        launched now: owned, and not certain to end inside what is
        already dispatched."""
        return {b: r for b, r in enumerate(self._rows)
                if r is not None and not r.ends_in_flight}

    def _idle_locked(self) -> bool:
        return not (self._queue or self._flight or self._live_rows())

    def _loop(self):
        """One iteration, with window n in flight: admit -> dispatch the
        joins behind n -> dispatch window n+1 behind them -> read and
        account n -> read the joins' first tokens."""
        while True:
            self._window += 1
            with self._span("gen.admit") as sp:
                asked = time.monotonic_ns()
                with self._cond:
                    _lock_wait(sp, asked)
                    if not self._stop and self._idle_locked():
                        with self._span("gen.wait"):
                            while not self._stop and self._idle_locked():
                                self._cond.wait(0.1)
                    if self._stop:
                        self._flight.clear()
                        return
                    self._expire_queued_locked(time.monotonic())
                    joins = self._pick_joins_locked()
                    sp.annotate(joins=len(joins), queued=len(self._queue))
            try:
                if joins:
                    self._do_prefill(joins)
                self._do_decode()
                while self._unread:
                    p = self._unread.pop(0)
                    with self._span("gen.prefill", kind=p.kind):
                        self._account_prefill(p)
            except Exception as e:  # noqa: BLE001 — loop must survive
                self._on_dispatch_failure(e)

    def _expire_queued_locked(self, now: float):
        if not self._queue:
            return
        live = deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                req.error = DeadlineExpiredError(
                    "request deadline expired after "
                    f"{(now - req.t0) * 1000:.1f} ms in queue")
                telemetry.record_decode_request("expired", now - req.t0, model=self.name)
                tracing.finish_trace(req.trace, "expired")
                self._release_prefix(req)
                req.complete(now)
            else:
                live.append(req)
        if len(live) != len(self._queue):
            self._queue = live

    def _pick_joins_locked(self) -> List[_GenRequest]:
        """Token-granularity admission: every iteration, as many waiting
        prompts as there are free cache rows join the running batch —
        FIFO, no waiting for a drain. A row whose request ends by length
        inside the windows in flight is free already: the join is
        dispatched behind that request's last window, before it is
        read."""
        free = [b for b, r in enumerate(self._rows)
                if r is None or r.ends_in_flight]
        n = min(len(free), len(self._queue))
        joins = []
        now = time.monotonic() if n else None
        for b in free[:n]:
            req = self._queue.popleft()
            req.t_join = now
            req.row = b
            req.planned = 1
            if self._rows[b] is not None:
                self._joins_ahead_total += 1
                telemetry.record_decode_run_ahead(joins_ahead=1)
            self._rows[b] = req
            if req.trace is not None:
                req.trace.event("join", {"row": req.row})
            joins.append(req)
        return joins

    def _grow_to(self, target: int) -> bool:
        """Make the cache hold ``target`` positions; whether a program
        was dispatched for it (a new state, or the hop to a wider KV
        bucket), which then has a ``gen.grow`` span of its own."""
        s2 = bucket_for(target, self._dec.kv_ladder)
        if self._state is not None and s2 <= self._S:
            return False
        with self._span("gen.grow",
                        kv_from=self._S if self._state is not None else 0,
                        kv_to=max(self._S, s2)):
            if self._state is None:
                self._S = max(self._S, s2)
                self._state = self._dec.new_state(self._S)
            else:
                self._state = self._dec.grow_fn(self._S, s2)(self._state)
                self._S = s2
            telemetry.record_decode_state_bytes(
                self._dec.state_bytes(self._S))
        return True

    def _do_prefill(self, joins: List[_GenRequest]):
        """Prompt ingestion for this iteration's joins: cold prompts
        prefill in one full launch (and donate their KV pages to the
        prefix cache); prefix-cache hits prefill only their suffix,
        grouped by suffix bucket so each group's geometry is a warmed
        AOT key."""
        cold = [r for r in joins if not r.prefix_len]
        hits = [r for r in joins if r.prefix_len]
        widest = self._dec.join_ladder[-1]
        for i in range(0, len(cold), widest):
            self._prefill_cold(cold[i:i + widest])
        if hits:
            groups = {}
            for r in hits:
                ts = bucket_for(r.n - r.prefix_len,
                                self._dec.prompt_ladder)
                groups.setdefault(ts, []).append(r)
            for ts in sorted(groups):
                self._prefill_suffix_group(groups[ts], ts)

    def _prefill_cold(self, joins: List[_GenRequest]):
        cfg = self.config
        t0 = time.monotonic()
        tp = bucket_for(max(r.n for r in joins), self._dec.prompt_ladder)
        bp = bucket_for(len(joins), self._dec.join_ladder)
        live = sum(r.n for r in joins)
        self._prefill_live_tokens += live
        self._prefill_bucket_tokens += bp * tp
        with self._span("gen.prefill", kind="cold", joins=len(joins),
                        prompt_bucket=tp, rows=bp, live_tokens=live,
                        bucket_tokens=bp * tp,
                        state_kinds=self._state_kinds):
            with self._span("gen.prefill.stage"):
                self._grow_to(max(tp, self._S))
                prompts = np.full((bp, tp), self._dec.pad_id, np.int32)
                lengths = np.zeros((bp,), np.int32)
                rows = np.full((bp,), cfg.max_batch, np.int32)  # OOB = dropped
                max_new = np.ones((bp,), np.int32)
                eos = np.full((bp,), -1, np.int32)
                temps = np.zeros((bp,), np.float32)
                rng = np.zeros((bp, 2), np.uint32)
                for i, r in enumerate(joins):
                    prompts[i, :r.n] = r.tokens
                    lengths[i] = r.n
                    rows[i] = r.row
                    max_new[i] = r.max_new
                    eos[i] = r.eos
                    temps[i] = r.temp
                    rng[i] = r.rng

            def once():
                faults.fault_point(self._fault_site)
                return self._dec.prompt_fn(tp, bp)(
                    self._net_params(), prompts, lengths, max_new, eos,
                    temps, rng)

            with self._span("gen.prefill.launch", prompt_bucket=tp,
                            state_kinds=self._state_kinds):
                kv, tok, active, rng2 = self._call_prefill(once, joins)
                self._state = self._dec.join_fn(self._S, tp, bp)(
                    self._state, kv, rows, tok, lengths, max_new, eos, temps,
                    rng2, active)
            for r in joins:
                if r.trace is not None:
                    r.trace.event("prefill",
                                  {"prompt_bucket": tp, "rows": bp})
            self._launched_prefill(_Prefill(
                "cold", joins, bp, tok, active,
                kv if self._prefix is not None else None, 0,
                time.monotonic() - t0))

    def _call_prefill(self, once, joins):
        """One prefill launch, retried (never past the joins' earliest
        deadline) when the engine has a retry policy."""
        if self._retry is None:
            return once()
        deadlines = [r.deadline for r in joins if r.deadline is not None]
        return self._retry.call(
            once, deadline=min(deadlines) if deadlines else None,
            op=self._fault_site)

    def _prefill_suffix_group(self, joins: List[_GenRequest], ts: int):
        """One prefix-HIT join group (shared suffix bucket ``ts``): the
        pinned pages are host-assembled into a padded ``[bp, tpre]``
        block, the suffix prefills against them in one launch, then the
        pages scatter into the rows (``prefix_attach``) and the suffix
        KV lands at each row's per-row offset (``prefix_join``). Every
        member passed the submit-time ``fits`` check for THIS ts, so
        ``prefix_len + ts <= max_len`` holds row-wise and the grown
        bucket covers the widest row."""
        cfg = self.config
        t0 = time.monotonic()
        max_m = max(r.prefix_len for r in joins)
        tpre = bucket_for(max_m, self._dec.prompt_ladder)
        # suffix joins always pad to the full join width: one compiled
        # width per (ts, tpre, s) keeps the prefix warm set small, and
        # padding rows scatter out of bounds (dropped)
        bp = cfg.max_batch
        live = sum(r.n - r.prefix_len for r in joins)
        self._prefill_live_tokens += live
        self._prefill_bucket_tokens += bp * ts
        with self._span("gen.prefill", kind="suffix", joins=len(joins),
                        prompt_bucket=ts, rows=bp, live_tokens=live,
                        bucket_tokens=bp * ts):
            with self._span("gen.prefill.stage"):
                self._grow_to(max(max_m + ts, self._S))
                suffix = np.full((bp, ts), self._dec.pad_id, np.int32)
                suf_lens = np.zeros((bp,), np.int32)
                plens = np.zeros((bp,), np.int32)
                lengths = np.zeros((bp,), np.int32)
                rows = np.full((bp,), cfg.max_batch, np.int32)  # OOB = dropped
                max_new = np.ones((bp,), np.int32)
                eos = np.full((bp,), -1, np.int32)
                temps = np.zeros((bp,), np.float32)
                rng = np.zeros((bp, 2), np.uint32)
                pkv = None
                for i, r in enumerate(joins):
                    blk = self._prefix.assemble(r.prefix_nodes, tpre)
                    if pkv is None:
                        pkv = {name: {
                            "k": np.zeros((bp,) + b["k"].shape,
                                          b["k"].dtype),
                            "v": np.zeros((bp,) + b["v"].shape,
                                          b["v"].dtype)}
                            for name, b in blk.items()}
                    for name, b in blk.items():
                        pkv[name]["k"][i] = b["k"]
                        pkv[name]["v"][i] = b["v"]
                    suffix[i, :r.n - r.prefix_len] = r.tokens[r.prefix_len:]
                    suf_lens[i] = r.n - r.prefix_len
                    plens[i] = r.prefix_len
                    lengths[i] = r.n
                    rows[i] = r.row
                    max_new[i] = r.max_new
                    eos[i] = r.eos
                    temps[i] = r.temp
                    rng[i] = r.rng

            def once():
                faults.fault_point(self._fault_site)
                return self._dec.suffix_prompt_fn(ts, tpre, bp)(
                    self._net_params(), suffix, suf_lens, pkv, plens,
                    max_new, eos, temps, rng)

            with self._span("gen.prefill.launch"):
                kv, tok, active, rng2 = self._call_prefill(once, joins)
                self._state = self._dec.prefix_attach_fn(self._S, tpre, bp)(
                    self._state, pkv, rows, plens)
                self._state = self._dec.suffix_join_fn(self._S, ts, bp)(
                    self._state, kv, rows, tok, plens, lengths, max_new,
                    eos, temps, rng2, active)
            for r in joins:
                if r.trace is not None:
                    r.trace.event("prefix_attach",
                                  {"prefix_len": r.prefix_len,
                                   "suffix_bucket": ts})
            # the trie is extended with the hit requests' own suffix pages
            # (page extension: next time a LONGER shared prefix hits)
            self._launched_prefill(_Prefill(
                "suffix", joins, bp, tok, active,
                kv if self._prefix is not None else None, "prefix",
                time.monotonic() - t0))

    def _launched_prefill(self, p: _Prefill):
        """Called inside the ``gen.prefill`` span that launched ``p``.
        Behind a window in flight the first tokens are read after the
        next launch (the loop opens a second ``gen.prefill`` span for
        them); with nothing in flight there is nothing to hide the read
        behind, and it happens here."""
        if self._flight:
            self._unread.append(p)
        else:
            self._account_prefill(p)

    def _insert_pages(self, joins, kv, offset):
        """Donate a prefill launch's KV to the prefix cache: full pages
        of each request's prompt that the trie lacks. ``kv`` is the
        device block ``[bp, t, heads * hd]`` per layer; ``offset`` is 0
        for a cold prefill or ``"prefix"`` when ``kv`` holds only the
        suffix (page starts shift down by the row's prefix length — the
        prefix portion is already in the tree and pinned, so the slicer
        is never asked for it). Device→host transfer happens at most
        once per launch, and only when a new page is actually created.
        The inserted path's pins are appended to the request's node
        list, so its own pages cannot be evicted before it retires and
        every pin still releases on the usual terminal edges; a request
        that reached one while its prefill was in flight donates
        nothing."""
        host = {}

        def make_slicer(i, off):
            def slicer(start, stop):
                blk = {}
                for name in kv:
                    if name not in host:
                        host[name] = {"k": np.asarray(kv[name]["k"]),
                                      "v": np.asarray(kv[name]["v"])}
                    h = host[name]
                    blk[name] = {
                        "k": h["k"][i, start - off:stop - off].copy(),
                        "v": h["v"][i, start - off:stop - off].copy()}
                return blk
            return slicer

        for i, r in enumerate(joins):
            if r.t_done is not None:
                continue
            off = r.prefix_len if offset == "prefix" else 0
            nodes = self._prefix.insert(r.tokens, r.n, make_slicer(i, off))
            r.prefix_nodes = list(r.prefix_nodes) + list(nodes)

    def _account_prefill(self, p: _Prefill):
        """Read a prefill's first tokens and account them (first token,
        TTFT, who was born retired). The prefix cache's pages leave the
        device here too: a blocking copy, like the tokens'."""
        t0 = time.monotonic()
        with self._span("gen.prefill.readback", sync=True):
            tok = np.asarray(p.tok)
            active = np.asarray(p.active)
            if self._prefix is not None:
                self._insert_pages(p.joins, p.kv, p.offset)
        now = time.monotonic()
        seconds = p.seconds + now - t0
        with self._span("gen.prefill.account") as sp:
            asked = time.monotonic_ns()
            with self._cond:
                _lock_wait(sp, asked)
                # a join that completed while its prefill was in flight
                # (close, a failure) has nothing to account
                joins = [(i, r) for i, r in enumerate(p.joins)
                         if r.t_done is None]
                # counted before a row born retired wakes its waiter, as
                # the decode window counts
                telemetry.record_decode_prefill(len(joins), p.bp, seconds)
                for i, r in joins:
                    r.out.append(int(tok[i]))
                    r.t_first = now
                    telemetry.record_decode_first_token(now - r.t0)
                    if r.trace is not None:
                        r.trace.event("first_token")
                    if self._slo is not None:
                        self._slo.observe(self.name or "default",
                                          ttft=now - r.t0)
                    if not active[i]:
                        self._finish_locked(r, now)
                self._joined_total += len(joins)
                self._tokens_total += len(joins)
                self._prefill_seconds += seconds
        if self._breaker is not None:
            self._breaker.on_success()

    def _do_decode(self):
        if not self._flight and not self._live_rows():
            return
        with self._span("gen.decode") as parent:
            self._decode_window(parent)

    def _decode_window(self, parent):
        """The iteration's decode part, under one ``gen.decode`` span that
        reads back and accounts EXACTLY ONE window: the oldest in flight.
        Before that read it launches the window ahead, on the state the
        one in flight returns; with nothing in flight (the first
        iteration of a burst) it launches that one first."""
        t0 = time.monotonic()
        if not self._flight:
            self._launch_window()
        self._launch_window()
        if self._flight:        # not closed before anything was launched
            self._read_window(parent, t0)

    def _launch_window(self) -> bool:
        """Plan and dispatch ``decode_fn(S, K)`` for the rows that can
        still emit, if there are any. The cache is grown from an upper
        bound the host has without a read-back: a row holds at most
        ``n + planned - 1`` positions once everything dispatched has run,
        so a bucket hop is never late."""
        k = self.config.fused_steps
        with self._span("gen.decode.plan") as sp:
            asked = time.monotonic_ns()
            with self._cond:
                _lock_wait(sp, asked)
                rows = self._live_rows()
                if not rows:
                    sp.annotate(grew=False, rows=0)
                    return False
                max_pos = max(r.n + r.planned - 1 for r in rows.values())
                for r in rows.values():
                    r.planned += k
                ahead = bool(self._flight)
                self._windows_total += 1
                self._windows_ahead_total += ahead
                telemetry.record_decode_run_ahead(windows=1,
                                                  windows_ahead=ahead)
                w = _Window(rows)
                self._flight.append(w)
            sp.annotate(grew=self._grow_to(
                min(max_pos + k, self._dec.max_len)), rows=len(rows))
            w.kv_bucket = self._S
        # NO retry on decode windows: the state pytree is donated into
        # the executable, so a mid-flight failure may have consumed it —
        # _on_dispatch_failure resets instead
        with self._span("gen.decode.launch"):
            faults.fault_point(self._fault_site)
            self._state, w.toks, w.emitted, *w.counts = self._dec.decode_fn(
                self._S, k)(self._net_params(), self._state)
        return True

    def _read_window(self, parent, t0: float):
        """Read back the oldest window in flight → account its tokens to
        the requests IT decoded → release the rows whose deadline passed
        (behind whatever is in flight by now)."""
        cfg = self.config
        k = cfg.fused_steps
        w = self._flight[0]
        with self._span("gen.decode.readback", sync=True):
            toks = np.asarray(w.toks)
            emitted = np.asarray(w.emitted)
            # the cached layers' counters, same read-back
            counts = dict(zip(self._dec.counter_names,
                              np.asarray(w.counts[0]).tolist())) \
                if w.counts else {}
        now = time.monotonic()
        n_emitted = 0
        released = []
        with self._span("gen.decode.account") as sp:
            asked = time.monotonic_ns()
            with self._cond:
                _lock_wait(sp, asked)
                self._flight.popleft()
                finished, expired = [], []
                for b, req in w.rows.items():
                    if req.t_done is not None:
                        # completed while this window ran (a deadline, a
                        # close): what it emitted for the row is dropped
                        continue
                    n_row = 0
                    done = False
                    for i in range(toks.shape[0]):
                        if not emitted[i, b]:
                            break
                        t = int(toks[i, b])
                        req.out.append(t)
                        n_row += 1
                        if t == req.eos or len(req.out) >= req.max_new:
                            done = True
                            break
                    n_emitted += n_row
                    if req.trace is not None:
                        req.trace.event("decode_window", {
                            "k": k, "kv_bucket": w.kv_bucket,
                            "tokens": n_row,
                            "ms": round((now - t0) * 1000.0, 3)})
                    if done:
                        finished.append(req)
                    elif req.deadline is not None and now > req.deadline:
                        expired.append(req)
                self._tokens_total += n_emitted
                if not n_emitted and not self._stop:
                    self._windows_empty_total += 1
                    telemetry.record_decode_run_ahead(windows_empty=1)
                self._decode_seconds += now - t0
                for name, n in counts.items():
                    self._layer_counts[name] += n
                # count BEFORE a finished request's waiter wakes: a
                # snapshot taken as generate() returns holds this
                # window's tokens, and /metrics agrees with the request
                gone = finished + expired
                telemetry.record_decode_layer_counts(counts)
                telemetry.record_decode_iteration(
                    n_emitted, len(w.rows), cfg.max_batch,
                    sum(r is not None and r not in gone
                        for r in self._rows), k, now - t0)
                for req in finished:
                    self._finish_locked(req, now)
                for req in expired:
                    # a row already given away is the next request's: the
                    # join behind this one's last window overwrites it
                    if self._rows[req.row] is req:
                        self._rows[req.row] = None
                        released.append(req.row)
                    req.error = DeadlineExpiredError(
                        "deadline expired mid-generation after "
                        f"{len(req.out)} tokens")
                    telemetry.record_decode_request(
                        "expired", now - req.t0, model=self.name)
                    tracing.finish_trace(req.trace, "expired",
                                         {"tokens": len(req.out)})
                    self._release_prefix(req)
                    req.complete(now)
                sp.annotate(finished=len(finished), expired=len(expired))
        if released:
            with self._span("gen.decode.release", rows=len(released)):
                keep = np.ones((cfg.max_batch,), bool)
                keep[released] = False
                self._state = self._dec.release_fn(self._S)(self._state,
                                                            keep)
        if self._breaker is not None:
            self._breaker.on_success()
        parent.annotate(k=k, kv_bucket=w.kv_bucket, rows=len(w.rows),
                        emitted=n_emitted)

    def _net_params(self):
        return self._dec.params

    def _finish_locked(self, req: _GenRequest, now: float):
        if self._rows[req.row] is req:      # not given away already
            self._rows[req.row] = None
        self._retired_total += 1
        telemetry.record_decode_request("ok", now - req.t0, model=self.name)
        tracing.finish_trace(req.trace, "done",
                             {"tokens": len(req.out)})
        if self._slo is not None:
            self._slo.observe(self.name or "default", ok=True,
                              seconds=now - req.t0)
        self._release_prefix(req)
        req.complete(now)

    def _abandon_locked(self, error: BaseException, outcome: str,
                        attrs: Optional[dict] = None) -> List[_GenRequest]:
        """Fail every request that holds a row or rides a window in
        flight, each once, and free the rows; returns them. (A join whose
        prefill is unread holds its row: rows change hands at admission
        only.)"""
        now = time.monotonic()
        failed = []
        for req in itertools.chain(
                self._rows, *(w.rows.values() for w in self._flight)):
            if req is None or req.t_done is not None:
                continue
            req.error = error if req.error is None else req.error
            tracing.finish_trace(req.trace, outcome, attrs)
            self._release_prefix(req)
            req.complete(now)
            failed.append(req)
        self._rows = [None] * self.config.max_batch
        return failed

    def _on_dispatch_failure(self, e: BaseException):
        """A prefill/decode dispatch or read-back raised. The decode
        state may have been donated into the failed executable, so it
        cannot be trusted, and neither can a window in flight on it:
        fail every request that holds a row or rides such a window (the
        batcher fails its batch the same way), reset to a fresh zeroed
        state, and count the breaker failure — persistent failure trips
        it open and submits shed."""
        with self._cond:
            for _ in self._abandon_locked(e, "rollback",
                                          {"error": type(e).__name__}):
                telemetry.record_decode_request("error", model=self.name)
                if self._slo is not None:
                    self._slo.observe(self.name or "default", ok=False)
            self._flight.clear()
        self._unread.clear()
        self._state = self._dec.new_state(self._S)
        if self._breaker is not None:
            self._breaker.on_failure()

    # --- lifecycle ----------------------------------------------------------
    def close(self):
        """Stop the decode loop; queued requests, those that hold a row
        and those in a window in flight fail with a shutdown error. The
        loop reads what it has in flight to the end (the tokens are
        dropped) and goes. Idempotent."""
        with self._cond:
            self._stop = True
            err = RuntimeError("generation engine closed")
            now = time.monotonic()
            for req in self._queue:
                req.error = err
                tracing.finish_trace(req.trace, "shutdown")
                self._release_prefix(req)
                req.complete(now)
            self._queue.clear()
            self._abandon_locked(err, "shutdown")
            self._cond.notify_all()
        telemetry.unregister_generation_engine(self)
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5)
        self._thread = None
        self._state = None
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
