"""Unified telemetry: step-trace spans, metrics registry, export surfaces.

Three pillars (docs/observability.md has the guided tour):

1. **Spans** (``telemetry.span("ingest"|"compute"|"grad_sync"|
   "fit.next_batch"|"drain"|"gen.decode"...)``): a low-overhead,
   nesting-aware span API. Spans ALWAYS land in one bounded ring
   (16,384 entries, ``time.monotonic_ns``, each with its own ``id`` and
   its ``parent_id``), whether or not ``enable()`` was called; exported
   as Chrome-trace JSON and aggregated into per-phase p50/p95/p99
   histograms. ``enable(sync=True)`` makes spans
   ``jax.block_until_ready`` their registered result so durations are
   true device times; otherwise a span never syncs.
2. **Registry** (``telemetry.registry.REGISTRY``): process-wide
   counters/gauges/histograms — steps, examples, collective bytes,
   ingest bytes, pipeline bubble fraction — plus scrape-time collectors
   for AOT-cache stats, device-memory watermarks and host RSS.
3. **Export**: ``/metrics`` (Prometheus text) + ``/metrics.json`` on
   ``ui.server.UIServer``, a ``TelemetryListener`` bridging into
   ``ui.stats`` storages, and ``dump_jsonl`` for offline diffing.

``enable()`` turns on what costs more than a span: the registry writes
of the per-step helpers (``record_step``, ``record_collective``,
``record_ingest``...), the ``host_gap`` clock and ``sync``. With
telemetry disabled (the default) each of those costs ONE flag check — no
lock, no host sync — and a span costs its one ring record (1 to 2 µs).
Scrape surfaces (collectors, ``/metrics``) work even while disabled.
"""

from __future__ import annotations

import weakref

from deeplearning4j_tpu.telemetry import flightrec as flightrec  # noqa: F401
from deeplearning4j_tpu.telemetry import health as health  # noqa: F401
from deeplearning4j_tpu.telemetry import registry as registry  # noqa: F401
from deeplearning4j_tpu.telemetry import slo as slo  # noqa: F401
from deeplearning4j_tpu.telemetry import spans as spans  # noqa: F401
from deeplearning4j_tpu.telemetry import tracing as tracing  # noqa: F401
from deeplearning4j_tpu.telemetry.flightrec import (  # noqa: F401
    FlightRecorder,
    flight_recorder,
)
from deeplearning4j_tpu.telemetry.health import (  # noqa: F401
    AnomalyPolicy,
    DivergenceError,
    HealthMonitor,
)
from deeplearning4j_tpu.telemetry.export import (  # noqa: F401
    TelemetryListener,
    dump_jsonl,
    telemetry_record,
)
from deeplearning4j_tpu.telemetry.registry import REGISTRY  # noqa: F401
from deeplearning4j_tpu.telemetry.spans import (  # noqa: F401
    PHASE_COMPUTE,
    PHASE_GRAD_SYNC,
    PHASE_HOST_GAP,
    PHASE_INGEST,
    PHASES,
    enable,
    enabled,
    disable,
    events,
    export_chrome_trace,
    host_gap_close,
    host_gap_open,
    host_gap_pause,
    host_gap_reset,
    host_gap_resume,
    host_gap_stop,
    phase_stats,
    span,
    sync_mode,
)


def reset() -> None:
    """Clear recorded spans, request traces AND metrics
    (flags/collectors untouched) — the per-test / per-bench-round zero
    point."""
    spans.reset()
    tracing.reset()
    REGISTRY.reset()


# --------------------------------------------------------------------------
# hot-path registry helpers (each is one flag check until ``enable()``)
# --------------------------------------------------------------------------

def record_step(path: str, examples: int = 0, steps: int = 1) -> None:
    """Count one host dispatch's optimization steps (and examples) for a
    training path: ``multilayer`` / ``graph`` / ``samediff`` /
    ``parallel`` / ``pipeline``. A fused K-step super-step passes
    ``steps=K`` so the counters keep K=1 semantics (K steps, K*B
    examples per dispatch)."""
    if not spans._enabled:
        return
    REGISTRY.counter("dl4j_training_steps_total",
                     help="optimization steps", path=path).inc(steps)
    if examples:
        REGISTRY.counter("dl4j_training_examples_total",
                         help="examples consumed", path=path).inc(examples)


def record_collective(op: str, nbytes: float, buckets: int = 1) -> None:
    """Count one cross-replica exchange: ``nbytes`` = per-shard payload
    crossing the interconnect, ``buckets`` = collectives issued for it
    (1 = single fused all-reduce)."""
    if not spans._enabled:
        return
    REGISTRY.counter("dl4j_collective_bytes_total",
                     help="per-shard bytes exchanged", op=op).inc(nbytes)
    REGISTRY.counter("dl4j_collective_ops_total",
                     help="collectives issued", op=op).inc(buckets)


def record_bucket_layout(op: str, bucket_bytes_list) -> None:
    """Record a bucketed collective's layout (once per compiled schedule):
    bucket count gauge + per-bucket byte sizes histogram."""
    if not spans._enabled:
        return
    REGISTRY.gauge("dl4j_collective_buckets",
                   help="buckets in the collective schedule", op=op).set(
        len(bucket_bytes_list))
    h = REGISTRY.histogram("dl4j_collective_bucket_bytes",
                           help="per-bucket payload bytes", op=op)
    for b in bucket_bytes_list:
        h.observe(b)


def record_collective_plan(intent: str, choice: str, nbytes: float,
                           launches: int) -> None:
    """Record one freshly planned collective schedule
    (``comms.scheduler``): the ``dl4j_collective_plan_total{intent,
    choice}`` counter plus per-plan bytes/launches gauges feeding the UI
    System tab collective panel — the scheduler's CHOICES (variadic /
    densify / all-gather) made observable per fit.
    Unconditional like the control-plane events below: plans resolve at
    trace time (once per unique layout per process), never per step."""
    REGISTRY.counter("dl4j_collective_plan_total",
                     help="collective plans built by the scheduler",
                     intent=intent, choice=choice).inc()
    REGISTRY.gauge("dl4j_collective_plan_bytes",
                   help="logical per-shard payload of the newest plan",
                   intent=intent).set(nbytes)
    REGISTRY.gauge("dl4j_collective_plan_launches",
                   help="collectives issued per exchange by the newest "
                        "plan", intent=intent).set(launches)


def record_ingest(nbytes: float, batches: int = 1) -> None:
    """Count host->device batch staging (DeviceRingIterator and friends)."""
    if not spans._enabled:
        return
    REGISTRY.counter("dl4j_ingest_batches_total",
                     help="batches staged to device").inc(batches)
    REGISTRY.counter("dl4j_ingest_bytes_total",
                     help="bytes staged to device").inc(nbytes)


def record_pipeline_schedule(n_stages: int, n_micro: int,
                             schedule: str) -> None:
    """Record a pipeline wrapper's static bubble fraction
    ``(S-1)/(S+M-1)`` — the drain/fill cost both GPipe and 1F1B
    (PipeDream-flush) schedules pay."""
    if not spans._enabled:
        return
    frac = (n_stages - 1) / max(n_stages + n_micro - 1, 1)
    REGISTRY.gauge("dl4j_pipeline_bubble_fraction",
                   help="(S-1)/(S+M-1) fill/drain bubble",
                   schedule=schedule).set(frac)
    REGISTRY.gauge("dl4j_pipeline_stages", schedule=schedule).set(n_stages)
    REGISTRY.gauge("dl4j_pipeline_microbatches",
                   schedule=schedule).set(n_micro)


def record_shard_bytes(param_bytes: float, opt_bytes: float,
                       mesh=None) -> None:
    """Publish the per-device parameter / optimizer-state footprint of
    the active placement (``dl4j_shard_param_bytes`` /
    ``dl4j_shard_opt_bytes``, one series per mesh device) — the gauge
    pair that makes ZeRO's per-chip memory saving MEASURABLE instead of
    asserted. Recorded unconditionally (placement happens once per
    ``fit``/plan resolve, never per step); with ``mesh=None`` a single
    unlabeled series is set."""
    devices = (list(mesh.devices.flat) if mesh is not None else [None])
    for d in devices:
        labels = {"device": str(d)} if d is not None else {}
        REGISTRY.gauge("dl4j_shard_param_bytes",
                       help="per-device parameter bytes under the "
                            "active sharding plan", **labels).set(
            param_bytes)
        REGISTRY.gauge("dl4j_shard_opt_bytes",
                       help="per-device optimizer-state bytes under "
                            "the active sharding plan", **labels).set(
            opt_bytes)


def record_step_seconds(seconds: float, path: str = "listener") -> None:
    """Observe one step duration into the registry histogram (the
    ProfilerListener / OpProfiler routing)."""
    if not spans._enabled:
        return
    REGISTRY.histogram("dl4j_step_seconds", help="host-observed step time",
                       path=path).observe(seconds)


# --------------------------------------------------------------------------
# serving metrics (parallel.batcher / parallel.serving)
#
# Unlike the per-step training helpers above these record UNCONDITIONALLY:
# a serving process wants its request/batch counters without opting into
# span recording, and one registry update per HTTP request (~1µs) is noise
# next to the network round-trip it measures. docs/serving.md lists the
# series.
# --------------------------------------------------------------------------

def record_serving_request(status: str, seconds: float = None,
                           model: str = None) -> None:
    """Count one inference request terminal state: ``ok`` / ``error`` /
    ``bad_request`` / ``rejected`` (queue full) / ``expired`` (deadline);
    ``seconds`` = submit-to-completion latency when the request made it
    into the queue. ``model`` labels the series for named (multi-tenant
    platform) engines; unnamed engines keep the unlabeled series."""
    labels = {"model": model} if model else {}
    REGISTRY.counter("dl4j_serving_requests_total",
                     help="inference requests by terminal status",
                     status=status, **labels).inc()
    if seconds is not None:
        REGISTRY.histogram("dl4j_serving_request_seconds",
                           help="submit-to-result request latency",
                           **labels).observe(seconds)


def record_serving_batch(rows: int, padded_rows: int, requests: int,
                         seconds: float, model: str = None) -> None:
    """Record one shared device launch: fill ratio (real rows / padded
    bucket rows), rows and coalesced-request histograms, launch time.
    ``model`` labels the series for named engines (per-tenant views)."""
    labels = {"model": model} if model else {}
    REGISTRY.counter("dl4j_serving_batches_total",
                     help="shared inference launches", **labels).inc()
    REGISTRY.histogram("dl4j_serving_batch_fill_ratio",
                       help="real rows / padded bucket rows",
                       **labels).observe(rows / max(padded_rows, 1))
    REGISTRY.histogram("dl4j_serving_batch_rows",
                       help="real rows per shared launch",
                       **labels).observe(rows)
    REGISTRY.histogram("dl4j_serving_batch_requests",
                       help="requests coalesced per launch",
                       **labels).observe(requests)
    REGISTRY.histogram("dl4j_serving_batch_seconds",
                       help="shared launch wall time",
                       **labels).observe(seconds)


def record_platform_event(event: str, model: str = None) -> None:
    """Count one platform control-plane event (``parallel.platform``):
    ``swap`` / ``canary_deploy`` / ``canary_rollback`` / ``promote`` /
    ``host_rejected`` — unconditional, these are rare lifecycle events,
    never per-request hot-path work. docs/serving.md lists the series."""
    labels = {"model": model} if model else {}
    REGISTRY.counter(f"dl4j_platform_{event}_total",
                     help="multi-tenant platform lifecycle events",
                     **labels).inc()


# --------------------------------------------------------------------------
# resilience metrics (resilience/: faults, retry, breaker, session)
#
# Unconditional like the serving helpers: these record rare control-plane
# events (a retry, a breaker trip, a resume, an injected fault), never
# per-step hot-path work — an operator wants them without opting into
# span recording. docs/resilience.md lists the series.
# --------------------------------------------------------------------------

def record_retry(op: str) -> None:
    """Count one scheduled retry (first attempts are not retries)."""
    REGISTRY.counter("dl4j_retries_total",
                     help="retries scheduled by RetryPolicy", op=op).inc()


def record_resume(scope: str = "job") -> None:
    """Count one TrainingSession resume from a snapshot.
    ``scope="job"`` = whole-process failure (preemption, injected step
    fault, crash restart); ``scope="host"`` = one pod host died
    (``HostDeathError`` at the ``pod.heartbeat`` site) and the whole
    job resumed from the last distributed snapshot."""
    REGISTRY.counter("dl4j_resumes_total",
                     help="training resumes from snapshot",
                     scope=scope).inc()


def record_pod_hosts(n_hosts: int) -> None:
    """Publish the pod shape (``dl4j_pod_hosts``) — how many hosts the
    active snapshot/training topology spans (1 = single-host; an
    emulated pod reports its emulated width)."""
    REGISTRY.gauge("dl4j_pod_hosts",
                   help="hosts in the active pod topology").set(n_hosts)


def record_pod_shard(host: int, nbytes: int, seconds: float) -> None:
    """One host's pod-snapshot shard written: per-host shard bytes
    gauge + shard write-time histogram."""
    REGISTRY.gauge("dl4j_pod_snapshot_shard_bytes",
                   help="bytes in this host's newest snapshot shard",
                   host=str(host)).set(nbytes)
    REGISTRY.histogram("dl4j_pod_shard_write_seconds",
                       help="per-host shard write time").observe(seconds)


def record_pod_snapshot_seconds(seconds: float) -> None:
    """One full distributed snapshot (all shards + manifests + the
    coordinator commit) observed into ``dl4j_pod_snapshot_seconds``."""
    REGISTRY.histogram("dl4j_pod_snapshot_seconds",
                       help="distributed snapshot wall time").observe(
        seconds)


def record_pod_restore_seconds(seconds: float) -> None:
    """One pod-snapshot restore (verify + aggregate + rebuild) observed
    into ``dl4j_pod_restore_seconds``."""
    REGISTRY.histogram("dl4j_pod_restore_seconds",
                       help="distributed restore wall time").observe(
        seconds)


def record_fault_injected(site: str, action: str) -> None:
    """Count one fired fault-plan injection."""
    REGISTRY.counter("dl4j_faults_injected_total",
                     help="deterministic fault injections fired",
                     site=site, action=action).inc()


def record_analysis_finding(rule: str, severity: str) -> None:
    """Count one unwaived static-analysis finding (the program linter
    records at compile time, so a live process's ``/metrics`` shows what
    lint saw without re-running the CLI). Unconditional like the other
    control-plane events: findings are per-compile, never per-step."""
    REGISTRY.counter("dl4j_analysis_findings_total",
                     help="static-analysis findings (analysis/ linters)",
                     rule=rule, severity=severity).inc()


def record_canary_accuracy(model: str, delta: float) -> None:
    """Record one canary accuracy-arm shadow compare
    (``parallel.platform``): the max-abs output delta between the canary
    (e.g. an int8 quantized version) and its f32 incumbent on one sampled
    request. Gauge = last observed delta; the counter tracks sample
    volume. Rate-bounded by ``CanaryGate.accuracy_sample``, and only
    active while a gated canary is live — not steady-state hot-path
    work."""
    REGISTRY.gauge("dl4j_canary_accuracy_delta",
                   help="last canary-vs-incumbent output delta",
                   model=model).set(float(delta))
    REGISTRY.counter("dl4j_canary_accuracy_samples_total",
                     help="canary accuracy-arm shadow compares",
                     model=model).inc()


def record_kernel_selected(kernel: str, shape_bucket: str) -> None:
    """Count one kernel-registry routing decision (``kernels.routing``):
    a tuned Pallas kernel was selected for a concrete shape class
    inside a fresh trace. Unconditional like the other control-plane
    events: selection happens at trace time (once per executable),
    never per step."""
    REGISTRY.counter("dl4j_kernel_selected_total",
                     help="tuned kernel selections at trace time",
                     kernel=kernel, shape_bucket=shape_bucket).inc()


def record_autotune_trial(kernel: str) -> None:
    """Count one autotuner candidate benchmark (``kernels.tuner``)."""
    REGISTRY.counter("dl4j_kernel_autotune_trials_total",
                     help="autotune candidate tilings benchmarked",
                     kernel=kernel).inc()


def record_autotune_winner(kernel: str) -> None:
    """Count one autotuner winner recorded into the tuning cache."""
    REGISTRY.counter("dl4j_kernel_autotune_winners_total",
                     help="autotune winners recorded", kernel=kernel).inc()


def record_tuning_cache(hits: int, entries: int) -> None:
    """Publish the kernel tuning cache's cumulative hit count and entry
    count (control-plane cadence: selection and autotune events)."""
    REGISTRY.gauge("dl4j_kernel_tuning_cache_hits",
                   help="tuning-cache winner lookups that hit").set(hits)
    REGISTRY.gauge("dl4j_kernel_tuning_cache_entries",
                   help="tuned envelopes in the cache").set(entries)


def record_slo_transition(tenant: str, to_state: str) -> None:
    """Count one SLO alert-state transition (``telemetry.slo``):
    unconditional like the other control-plane events — transitions are
    rare by construction (hysteresis), never per-request work. The
    current state/burn gauges are scrape-time collectors."""
    REGISTRY.counter("dl4j_slo_transitions_total",
                     help="SLO alert-state transitions",
                     tenant=tenant, to=to_state).inc()


def record_circuit_state(name: str, state_code: int,
                         transition: bool = True) -> None:
    """Publish a breaker's state (0=closed, 1=half_open, 2=open); counts
    the transition too unless this is the initial publish."""
    REGISTRY.gauge("dl4j_circuit_state",
                   help="0=closed 1=half_open 2=open",
                   breaker=name).set(state_code)
    if transition:
        REGISTRY.counter("dl4j_circuit_transitions_total",
                         help="breaker state transitions",
                         breaker=name, to=str(state_code)).inc()


# --------------------------------------------------------------------------
# generation metrics (parallel.generation — iteration-level continuous
# batching for autoregressive decode). Unconditional like the serving
# helpers: one registry update per decode ITERATION (not per token),
# noise next to a device dispatch. docs/serving.md lists the series.
# --------------------------------------------------------------------------

def record_decode_request(status: str, seconds: float = None,
                          model: str = None) -> None:
    """Count one generation-request terminal state (``ok`` / ``error`` /
    ``bad_request`` / ``rejected`` / ``expired`` / ``shed``);
    ``seconds`` = submit-to-last-token latency when it ran. ``model``
    labels the series for named (multi-tenant platform) engines."""
    labels = {"model": model} if model else {}
    REGISTRY.counter("dl4j_decode_requests_total",
                     help="generation requests by terminal status",
                     status=status, **labels).inc()
    if seconds is not None:
        REGISTRY.histogram("dl4j_decode_request_seconds",
                           help="submit-to-completion generation latency",
                           **labels).observe(seconds)


def record_decode_iteration(tokens: int, active_rows: int, capacity: int,
                            rows_in_use: int, k: int,
                            seconds: float) -> None:
    """One decode window: tokens actually emitted, running-batch
    occupancy, KV-cache rows in use, per-token latency (window wall
    time / K — the iteration-granularity inter-token latency)."""
    REGISTRY.counter("dl4j_decode_tokens_total",
                     help="tokens generated (all sequences)").inc(tokens)
    REGISTRY.gauge("dl4j_decode_batch_occupancy",
                   help="active rows / max_batch in the running "
                        "decode batch").set(
        active_rows / max(capacity, 1))
    REGISTRY.gauge("dl4j_decode_kv_rows_in_use",
                   help="KV-cache rows currently owned by sequences").set(
        rows_in_use)
    if k > 0:
        REGISTRY.histogram("dl4j_decode_token_seconds",
                           help="per-token decode latency "
                                "(window time / K)").observe(seconds / k)


def record_decode_run_ahead(windows: int = 0, windows_ahead: int = 0,
                            joins_ahead: int = 0,
                            windows_empty: int = 0) -> None:
    """How often the generation loop runs ahead of what it has read
    (``parallel.generation.GenerationEngine``): decode windows launched,
    those launched with another still unread, joins placed in a row
    before its last window was read, windows that emitted nothing."""
    for name, n, text in (
            ("windows", windows, "decode windows launched"),
            ("windows_ahead", windows_ahead,
             "decode windows launched ahead of a read-back"),
            ("joins_ahead", joins_ahead,
             "joins placed before the row's last window was read"),
            ("windows_empty", windows_empty,
             "decode windows that emitted nothing")):
        if n:
            REGISTRY.counter(f"dl4j_decode_{name}_total", help=text).inc(n)


def record_decode_prefill(rows: int, bucket_rows: int,
                          seconds: float) -> None:
    """One prefill launch: joining sequences, padded join-bucket fill,
    prompt-ingestion wall time (the prefill side of the prefill/decode
    split). Each joining row samples its first
    token in the prefill launch, so those count as generated tokens."""
    REGISTRY.counter("dl4j_decode_prefills_total",
                     help="prompt prefill launches").inc()
    REGISTRY.counter("dl4j_decode_tokens_total",
                     help="tokens generated (all sequences)").inc(rows)
    REGISTRY.histogram("dl4j_decode_prefill_fill_ratio",
                       help="joining rows / padded join bucket").observe(
        rows / max(bucket_rows, 1))
    REGISTRY.histogram("dl4j_decode_prefill_seconds",
                       help="prefill launch wall time").observe(seconds)


def record_decode_first_token(seconds: float) -> None:
    """Time-to-first-token for one request (submit → prefill sample)."""
    REGISTRY.histogram("dl4j_decode_first_token_seconds",
                       help="submit-to-first-token latency").observe(
        seconds)


def record_decode_state_bytes(by_kind: dict) -> None:
    """Bytes of per-row state a generation engine's caches hold, by kind
    (``kv``, ``kv_ring``, ``compressed_keys``, ``recurrent``,
    ``conv_window``, ``latent``): set at engine build and at every hop to a
    wider KV bucket."""
    for kind, n in by_kind.items():
        REGISTRY.gauge("dl4j_gen_state_bytes",
                       help="bytes of per-row decode state by kind",
                       kind=kind).set(n)


def record_decode_layer_counts(counts: dict) -> None:
    """What the cached layers counted in-graph over one decode window
    (``nn.decoding``: summed over the active rows and the window's steps,
    read with the window's tokens): ``dl4j_<name>_total`` each —
    ``sparse_attended_positions`` / ``sparse_context_positions`` /
    ``sparse_read_positions`` (what the read streamed to attend that) per
    (sparse-layer query, KV head), ``sparse_dense_fallback_queries``,
    ``recurrent_state_updates``, ``ssm_state_updates`` (active row x
    state-space layer a step), ``delta_state_updates`` (active row x
    delta-rule layer a step), ``shortconv_state_updates`` (active row x
    short-convolution layer a step); ``decode_kv_read_positions`` /
    ``decode_kv_bucket_positions`` per (attention layer, row, step; a
    latent layer's too): the
    cached positions streamed over the positions held (a bucket; for a
    window layer's ring the row's context); ``moe_routed_slots`` (live
    token x chosen expert), ``moe_experts_touched``,
    ``moe_expert_layer_steps``, ``moe_max_load`` and ``moe_experts_read``
    per (expert layer, step): experts with a live token, layer-steps with
    one, the fullest expert's slots, experts whose matrices the step's
    product streamed (the touched ones by the TPU's kernel, all those
    held by the batched product of every other platform)."""
    for name, n in counts.items():
        REGISTRY.counter(f"dl4j_{name}_total",
                         help="summed in-graph by the decode window "
                              "(docs/observability.md)").inc(n)


def record_prefix_cache(hits: int = 0, misses: int = 0, evictions: int = 0,
                        pages: int = None, hit_tokens: int = 0) -> None:
    """Radix prefix-cache accounting: lookups that matched at least one
    page vs cold misses, refcount-0 pages LRU-evicted, live page count
    after the operation, and prompt tokens whose prefill was skipped."""
    if hits:
        REGISTRY.counter("dl4j_prefix_cache_hits_total",
                         help="prompt lookups matching >=1 cached "
                              "page").inc(hits)
    if misses:
        REGISTRY.counter("dl4j_prefix_cache_misses_total",
                         help="prompt lookups with no cached "
                              "prefix").inc(misses)
    if evictions:
        REGISTRY.counter("dl4j_prefix_cache_evictions_total",
                         help="refcount-0 KV pages LRU-evicted").inc(
            evictions)
    if pages is not None:
        REGISTRY.gauge("dl4j_prefix_cache_pages",
                       help="live KV pages in the radix tree").set(pages)
    if hit_tokens:
        REGISTRY.counter("dl4j_prefix_cache_hit_tokens_total",
                         help="prompt tokens served from cached KV "
                              "(prefill skipped)").inc(hit_tokens)


_SERVING_ENGINES = weakref.WeakSet()


def register_serving_engine(engine) -> None:
    """Track a live ``InferenceEngine``; ``dl4j_serving_queue_depth`` is
    collected at scrape time as the SUM over live engines, so several
    engines in one process (two servers, a restart's old+new pair) are
    additive instead of overwriting each other's gauge."""
    _SERVING_ENGINES.add(engine)


def unregister_serving_engine(engine) -> None:
    _SERVING_ENGINES.discard(engine)


_GENERATION_ENGINES = weakref.WeakSet()


def register_generation_engine(engine) -> None:
    """Track a live ``GenerationEngine`` for the scrape-time queue-depth
    collector (same additive multi-engine semantics as serving)."""
    _GENERATION_ENGINES.add(engine)


def unregister_generation_engine(engine) -> None:
    _GENERATION_ENGINES.discard(engine)


# --------------------------------------------------------------------------
# scrape-time collectors (run on snapshot/render, never per step)
# --------------------------------------------------------------------------

@REGISTRY.register_collector
def _collect_serving_queue_depth(reg) -> None:
    engines = list(_SERVING_ENGINES)
    if engines:
        reg.gauge("dl4j_serving_queue_depth",
                  help="pending serving requests").set(
            sum(e.queue_depth() for e in engines))


@REGISTRY.register_collector
def _collect_decode_queue_depth(reg) -> None:
    engines = list(_GENERATION_ENGINES)
    if engines:
        reg.gauge("dl4j_decode_queue_depth",
                  help="generation requests waiting for a cache row").set(
            sum(e.queue_depth() for e in engines))


@REGISTRY.register_collector
def _collect_slo_metrics(reg) -> None:
    for mon in slo.monitors():
        for tenant, snap in mon.snapshot().items():
            reg.gauge("dl4j_slo_state",
                      help="0=ok 1=warn 2=page",
                      tenant=tenant).set(slo.STATE_CODE[snap["state"]])
            for objective, b in snap["burn_rates"].items():
                for window in ("short", "long"):
                    reg.gauge("dl4j_slo_burn_rate",
                              help="violation fraction / objective "
                                   "budget per rolling window",
                              tenant=tenant, objective=objective,
                              window=window).set(b[window])


@REGISTRY.register_collector
def _collect_aot_cache(reg) -> None:
    from deeplearning4j_tpu.optimize import aot_cache

    st = aot_cache.stats()
    for k in ("hits", "misses", "entries", "fallbacks", "overflows"):
        reg.gauge(f"dl4j_aot_cache_{k}",
                  help="AOT step-executable cache").set(st[k])
    reg.gauge("dl4j_aot_cache_compile_seconds_total").set(
        st["compile_seconds"])
    total = st["hits"] + st["misses"]
    reg.gauge("dl4j_aot_cache_hit_ratio",
              help="hits / (hits + misses)").set(
        st["hits"] / total if total else 0.0)


@REGISTRY.register_collector
def _collect_device_memory(reg) -> None:
    import jax

    for d in jax.local_devices():
        try:
            ms = d.memory_stats() or {}
        except Exception:
            ms = {}
        if "bytes_in_use" in ms:
            reg.gauge("dl4j_device_bytes_in_use", device=str(d)).set(
                ms["bytes_in_use"])
        if "peak_bytes_in_use" in ms:
            reg.gauge("dl4j_device_peak_bytes",
                      help="HBM high-watermark", device=str(d)).set(
                ms["peak_bytes_in_use"])
    try:
        live = jax.live_arrays()
        reg.gauge("dl4j_live_arrays",
                  help="process-wide live jax.Array handles").set(len(live))
        reg.gauge("dl4j_live_array_bytes").set(
            sum(getattr(a, "nbytes", 0) or 0 for a in live))
    except Exception:
        pass


@REGISTRY.register_collector
def _collect_host_memory(reg) -> None:
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        import os

        reg.gauge("dl4j_host_rss_bytes").set(
            rss_pages * os.sysconf("SC_PAGE_SIZE"))
    except Exception:
        pass


def prometheus_text() -> str:
    """The full ``/metrics`` payload: registry metrics + span phase
    histograms rendered as summaries."""
    text = REGISTRY.render_prometheus()
    phases = phase_stats()
    if phases:
        lines = ["# TYPE dl4j_phase_ms summary"]
        for name, st in phases.items():
            for q in ("p50", "p95", "p99"):
                lines.append(
                    f'dl4j_phase_ms{{phase="{name}",quantile='
                    f'"0.{q[1:]}"}} {st[f"{q}_ms"]:.9g}')
            lines.append(f'dl4j_phase_ms_sum{{phase="{name}"}} '
                         f'{st["total_ms"]:.9g}')
            lines.append(f'dl4j_phase_ms_count{{phase="{name}"}} '
                         f'{st["count"]}')
        text += "\n".join(lines) + "\n"
    return text
