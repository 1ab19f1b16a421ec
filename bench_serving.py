"""Closed-loop multi-client serving benchmark (ISSUE 5 acceptance): N
concurrent clients, each looping submit -> wait -> submit against

  locked   — the pre-round-9 baseline: one global lock, one exact-shape
             forward per request (``InferenceServer`` with
             ``batching=None``), and
  batched  — the dynamic micro-batching engine: shared padded launches,
             power-of-two buckets, zero recompiles after ``warmup()``
             (``parallel.batcher.InferenceEngine``).

Reports req/s, rows/s, latency p50/p95/p99, engine fill ratio, and the
speedup; writes ``bench_serving.json``. The acceptance bar is >= 4x
throughput at 8 clients on the CPU proxy.

Runs on CPU by default. ``--tpu`` runs on the chip and refuses to run
unless jax reports one (the chip belongs to one process at a time).

``--smoke`` is the ``make serve-smoke`` path: start a real HTTP
``InferenceServer``, fire concurrent ``/predict`` clients, scrape
``/metrics``, stop cleanly, assert the engine never recompiled.
"""

import argparse
import json
import os
import sys
import threading
import time


def _pin_cpu():
    """CPU plus the device-count flag, set before ``import jax``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # 8 virtual devices: the ParallelInference-backed deployment (the
        # default --backend) shards launches the way a TPU pod slice does
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _build_net(n_in, hidden, n_out, seed=0):
    import numpy as np

    from deeplearning4j_tpu.conf import Activation, InputType, WeightInit
    from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.conf.losses import LossMCXENT
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.conf.updaters import Sgd
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(0.1)).weight_init(WeightInit.XAVIER).list()
            .layer(DenseLayer(n_out=hidden, activation=Activation.RELU))
            .layer(DenseLayer(n_out=hidden, activation=Activation.RELU))
            .layer(OutputLayer(n_out=n_out, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(n_in)).build())
    net = MultiLayerNetwork(conf).init()
    # one throwaway fit step so serving hits a realistic trained model
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(32, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, 32)]
    net.fit(x, y)
    return net


def _quantiles(sorted_ms):
    def q(p):
        if not sorted_ms:
            return 0.0
        i = min(int(p * len(sorted_ms)), len(sorted_ms) - 1)
        return sorted_ms[i]

    return {"p50_ms": round(q(0.50), 3), "p95_ms": round(q(0.95), 3),
            "p99_ms": round(q(0.99), 3)}


def _closed_loop(predict, clients, seconds, sizes, n_in):
    """``clients`` threads loop predict(x) for ``seconds``; returns
    (requests, rows, sorted per-request latencies ms)."""
    import numpy as np

    stop = threading.Event()
    lat = [[] for _ in range(clients)]
    rows = [0] * clients

    def run(ci):
        rng = np.random.default_rng(ci)
        payloads = [rng.normal(size=(s, n_in)).astype(np.float32)
                    for s in sizes]
        i = 0
        while not stop.is_set():
            x = payloads[i % len(payloads)]
            t0 = time.perf_counter()
            predict(x)
            lat[ci].append((time.perf_counter() - t0) * 1000.0)
            rows[ci] += x.shape[0]
            i += 1

    threads = [threading.Thread(target=run, args=(ci,))
               for ci in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = sorted(ms for per in lat for ms in per)
    return len(flat), sum(rows), flat, wall


def bench(args):
    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import (
        BatchingConfig,
        InferenceEngine,
    )

    net = _build_net(args.n_in, args.hidden, args.n_out)
    sizes = tuple(int(s) for s in args.sizes.split(","))
    results = {"clients": args.clients, "seconds": args.seconds,
               "sizes": list(sizes), "backend": args.backend,
               "model": f"mlp {args.n_in}-{args.hidden}x2-{args.n_out}"}

    if args.backend == "pi":
        # the deployment the ISSUE targets: serving behind a sharded
        # ParallelInference, where EVERY launch pays multi-device dispatch
        # — the cost dynamic batching exists to amortize (on this CPU
        # proxy a 1-row sharded launch costs the same ~2 ms as a 32-row
        # one; a TPU pod slice behaves the same way)
        from deeplearning4j_tpu.parallel import ParallelInference

        baseline_model = ParallelInference(net, bucketize=False)  # old pad
        engine_model = ParallelInference(net)
    else:
        baseline_model = engine_model = net

    # --- locked baseline: global lock, one request per launch -------------
    lock = threading.Lock()

    def locked_predict(x):
        # host materialization included — the engine demux pays it too
        with lock:
            return np.asarray(baseline_model.output(x))

    def measure(predict):
        """Best round by req/s: the box is shared, a slow round means
        background contention, not a slower serving path."""
        best = None
        for _ in range(max(args.rounds, 1)):
            n_req, n_rows, lat, wall = _closed_loop(
                predict, args.clients, args.seconds, sizes, args.n_in)
            cur = {"req_per_s": round(n_req / wall, 1),
                   "rows_per_s": round(n_rows / wall, 1),
                   **_quantiles(lat)}
            if best is None or cur["req_per_s"] > best["req_per_s"]:
                best = cur
        return best

    for s in sizes:  # prime every request shape out of the measurement
        locked_predict(np.zeros((s, args.n_in), np.float32))
    results["locked"] = measure(locked_predict)

    # --- batched engine ---------------------------------------------------
    eng = InferenceEngine(engine_model, BatchingConfig(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        settle_ms=args.settle_ms),
        graph_opt=not args.no_graph_opt and args.backend != "pi")
    warm = eng.warmup()
    miss0 = aot_cache.stats()["misses"]
    results["batched"] = measure(eng.predict)
    recompiles = aot_cache.stats()["misses"] - miss0
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    fill = snap.get("dl4j_serving_batch_fill_ratio", {})
    per_batch = snap.get("dl4j_serving_batch_requests", {})
    results["batched"].update({
        "warmup": warm,
        "recompiles_after_warmup": recompiles,
        "mean_fill_ratio": round(fill.get("mean", 0.0), 3),
        "mean_requests_per_launch": round(per_batch.get("mean", 0.0), 2),
    })
    eng.close()

    results["speedup"] = round(
        results["batched"]["req_per_s"]
        / max(results["locked"]["req_per_s"], 1e-9), 2)

    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nlocked  : {results['locked']['req_per_s']:>9} req/s   "
          f"p95 {results['locked']['p95_ms']} ms")
    print(f"batched : {results['batched']['req_per_s']:>9} req/s   "
          f"p95 {results['batched']['p95_ms']} ms")
    print(f"speedup : {results['speedup']}x   "
          f"(recompiles after warmup: {recompiles})")
    if args.assert_speedup and results["speedup"] < args.assert_speedup:
        print(f"FAIL: speedup {results['speedup']} < {args.assert_speedup}")
        return 1
    return 0


def bench_multi_model(args):
    """``--multi-model``: the two-tenant isolation A/B (ISSUE 13). One
    healthy tenant and one tenant whose CANARY version is degraded by a
    seeded fault plan serve concurrent closed-loop traffic on one
    platform host. Reports per-tenant req/s, latency quantiles, shed
    counts, the automatic-rollback record, and the two isolation
    invariants: the healthy tenant's responses stay byte-identical and
    the host performs ZERO recompiles after warmup while the canary
    trips, sheds, and rolls back. ``--assert-isolation`` exits 1 if
    either invariant breaks or the gate never trips."""
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import BatchingConfig
    from deeplearning4j_tpu.parallel.platform import (
        CanaryGate,
        ModelPlatform,
        ModelRegistry,
        TenantConfig,
    )
    from deeplearning4j_tpu.resilience import FaultPlan
    from deeplearning4j_tpu.telemetry import REGISTRY

    net_a = _build_net(args.n_in, args.hidden, args.n_out, seed=1)
    net_b = _build_net(args.n_in, args.hidden + 32, args.n_out, seed=2)
    # v2 = same conf, "newly trained" weights (the real rollout shape:
    # same conf-derived AOT graph key, so the canary warms for free)
    net_b2 = type(net_b)(net_b.conf).init()
    net_b2.set_params_flat(np.asarray(net_b.params_flat()) + 0.05)

    reg = ModelRegistry(tempfile.mkdtemp(prefix="dl4j_mt_bench_"))
    reg.publish("tenant_a", net_a)
    reg.publish("tenant_b", net_b)
    reg.publish("tenant_b", net_b2)
    plat = ModelPlatform(reg, seed=7)
    cfg = TenantConfig(batching=BatchingConfig(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        settle_ms=args.settle_ms))
    plat.deploy("tenant_a", config=cfg)
    plat.deploy("tenant_b", version=1, config=cfg)

    probe = np.zeros((2, args.n_in), np.float32)
    y_a0 = np.asarray(plat.predict("tenant_a", probe)).tobytes()
    plat.deploy_canary("tenant_b", 2, fraction=0.5,
                       gate=CanaryGate(max_consecutive_failures=5))
    miss0 = aot_cache.stats()["misses"]
    req0 = {
        k: v for k, v in REGISTRY.snapshot(run_collectors=False).items()
        if k.startswith("dl4j_serving_requests_total")}

    stop = threading.Event()
    per_tenant = {"tenant_a": {"lat": [], "ok": 0, "failed": 0},
                  "tenant_b": {"lat": [], "ok": 0, "failed": 0}}
    healthy_identical = [True]

    def client(tenant, ci):
        import numpy as _np

        rng = _np.random.default_rng(ci)
        rec = per_tenant[tenant]
        payloads = [rng.normal(size=(s, args.n_in)).astype(_np.float32)
                    for s in (1, 2, 3, 4)]
        i = 0
        while not stop.is_set():
            x = payloads[i % 4]
            t0 = time.perf_counter()
            try:
                plat.predict(tenant, x)
                rec["lat"].append((time.perf_counter() - t0) * 1000.0)
                rec["ok"] += 1
            except Exception:
                rec["failed"] += 1
            i += 1

    def probe_healthy():
        # the byte-identity monitor rides WITH the chaos, not after it
        while not stop.is_set():
            y = np.asarray(plat.predict("tenant_a", probe)).tobytes()
            if y != y_a0:
                healthy_identical[0] = False
            time.sleep(0.01)

    plan = FaultPlan(seed=11).inject("serving.launch:tenant_b#canary")
    half = max(args.clients // 2, 1)
    threads = ([threading.Thread(target=client, args=("tenant_a", ci))
                for ci in range(half)]
               + [threading.Thread(target=client, args=("tenant_b", ci))
                  for ci in range(half)]
               + [threading.Thread(target=probe_healthy)])
    with plan.armed():
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(args.seconds)
        stop.set()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

    recompiles = aot_cache.stats()["misses"] - miss0
    post = np.asarray(plat.predict("tenant_b", probe)).tobytes()
    y_b_v1 = np.asarray(net_b.output(probe)).tobytes()
    st_b = plat.stats()["tenant_b"]
    rollback = st_b.get("last_rollback")
    req1 = {
        k: v for k, v in REGISTRY.snapshot(run_collectors=False).items()
        if k.startswith("dl4j_serving_requests_total")}
    sheds = {k: req1[k] - req0.get(k, 0) for k in req1
             if '"shed"' in k or '"error"' in k or '"rejected"' in k}
    plat.close()

    results = {"mode": "multi-model", "clients": args.clients,
               "seconds": args.seconds, "wall": round(wall, 2),
               "fault_plan": "seed=11 serving.launch:tenant_b#canary",
               "platform_seed": 7}
    for name, rec in per_tenant.items():
        lat = sorted(rec["lat"])
        results[name] = {
            "req_per_s": round(len(lat) / wall, 1),
            "ok": rec["ok"], "failed": rec["failed"],
            **_quantiles(lat)}
    results["tenant_b"]["rollback"] = rollback
    results["shed_error_counts"] = {
        k.split("{", 1)[1].rstrip("}"): v for k, v in sorted(sheds.items())
        if v}
    results["recompiles_after_warmup"] = recompiles
    results["healthy_tenant_bytes_identical"] = healthy_identical[0]
    results["incumbent_restored_after_rollback"] = post == y_b_v1

    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    ra, rb = results["tenant_a"], results["tenant_b"]
    print(f"\ntenant_a (healthy): {ra['req_per_s']:>8} req/s  "
          f"p95 {ra['p95_ms']} ms  failed {ra['failed']}")
    print(f"tenant_b (canary) : {rb['req_per_s']:>8} req/s  "
          f"p95 {rb['p95_ms']} ms  failed {rb['failed']}")
    print(f"rollback: {rollback and rollback['reason']!r} "
          f"@ request {rollback and rollback['at_request']}   "
          f"recompiles {recompiles}   "
          f"healthy identical {healthy_identical[0]}")
    if args.assert_isolation:
        ok = (recompiles == 0 and healthy_identical[0]
              and rollback is not None
              and results["incumbent_restored_after_rollback"]
              and ra["failed"] == 0)
        print("OK" if ok else "FAIL: isolation invariant broken")
        return 0 if ok else 1
    return 0


def bench_traces(args):
    """``--traces``: request-tracing overhead A/B on the batched engine.
    The same closed-loop traffic runs twice — tracing OFF (the module
    flag short-circuits ``start_trace`` to one boolean check) then ON
    (every request carries a span through queued → admitted → grouped →
    launched → demuxed) — and the JSON carries both throughputs, the
    overhead fraction against ``--trace-overhead-budget``, the
    trace-derived queue-wait / batch-wait / launch breakdown, and the
    zero-recompile check for BOTH modes (tracing is host-side
    monotonic_ns + list appends; it must never mint an AOT key)."""
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import (
        BatchingConfig,
        InferenceEngine,
    )
    from deeplearning4j_tpu.telemetry import tracing

    net = _build_net(args.n_in, args.hidden, args.n_out)
    sizes = tuple(int(s) for s in args.sizes.split(","))
    eng = InferenceEngine(net, BatchingConfig(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        settle_ms=args.settle_ms), graph_opt=not args.no_graph_opt)
    eng.warmup()

    def measure():
        best = None
        for _ in range(max(args.rounds, 1)):
            n_req, _rows, lat, wall = _closed_loop(
                eng.predict, args.clients, args.seconds, sizes, args.n_in)
            cur = {"req_per_s": round(n_req / wall, 1), **_quantiles(lat)}
            if best is None or cur["req_per_s"] > best["req_per_s"]:
                best = cur
        return best

    results = {"mode": "traces", "clients": args.clients,
               "seconds": args.seconds, "rounds": args.rounds,
               "sizes": list(sizes)}
    tracing.disable()
    miss0 = aot_cache.stats()["misses"]
    results["tracing_off"] = measure()
    results["tracing_off"]["recompiles_after_warmup"] = (
        aot_cache.stats()["misses"] - miss0)
    tracing.enable(seed=7, sample_every=64)
    miss1 = aot_cache.stats()["misses"]
    results["tracing_on"] = measure()
    results["tracing_on"]["recompiles_after_warmup"] = (
        aot_cache.stats()["misses"] - miss1)
    results["tracing_on"]["sampler"] = tracing.stats()
    bd = tracing.stage_breakdown()
    results["tracing_on"]["stage_breakdown"] = {
        k: v for k, v in bd.items() if v is not None}
    tracing.disable()
    eng.close()

    off = results["tracing_off"]["req_per_s"]
    on = results["tracing_on"]["req_per_s"]
    overhead = round(1.0 - on / max(off, 1e-9), 4)
    results["overhead_fraction"] = overhead
    results["overhead_budget"] = args.trace_overhead_budget

    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\ntracing off: {off:>9} req/s   on: {on:>9} req/s   "
          f"overhead {overhead:+.1%} (budget {args.trace_overhead_budget:.0%})")
    ok = (overhead <= args.trace_overhead_budget
          and results["tracing_off"]["recompiles_after_warmup"] == 0
          and results["tracing_on"]["recompiles_after_warmup"] == 0)
    print("OK" if ok else "FAIL: tracing overhead/recompile budget broken")
    return 0 if ok else 1


def bench_quant(args):
    """``--quant``: the f32-vs-int8 quantized-serving A/B (ISSUE 20).
    Two fresh platforms serve the same closed-loop traffic:

      f32   — publish v1, deploy, measure.
      int8  — publish v1, calibrate + quantize -> publish v2, deploy v1,
              ``deploy_canary`` v2 behind an accuracy-armed gate, drive
              canary traffic, ``promote`` (which pre-warms the quantized
              executables), then measure the promoted quantized serving.

    Reports per-mode req/s, latency quantiles and
    recompiles-after-warmup (asserted ZERO for both — the quantized
    version must be fully warmed at promote time, not on first
    traffic), plus the canary's observed ``accuracy_max_delta``.

    Honest caveat baked into the JSON: on the CPU proxy XLA often runs
    int8 dot products SLOWER than f32 (no VNNI path through this
    emitter), so the ratio here validates the plumbing + accuracy, not
    the TPU speedup — that A/B is one ``--tpu`` run away.
    """
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.nn import inference_opt as iopt
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import BatchingConfig
    from deeplearning4j_tpu.parallel.platform import (
        CanaryGate,
        ModelPlatform,
        ModelRegistry,
        TenantConfig,
    )

    sizes = tuple(int(s) for s in args.sizes.split(","))
    cfg = TenantConfig(batching=BatchingConfig(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        settle_ms=args.settle_ms))
    results = {"mode": "quant", "clients": args.clients,
               "seconds": args.seconds, "sizes": list(sizes),
               "n_in": args.n_in, "hidden": args.hidden,
               "platform_seed": 7,
               "cpu_proxy_note": (
                   "CPU XLA int8 dot is often slower than f32 (no VNNI "
                   "path); this leg validates plumbing + accuracy, the "
                   "TPU speed A/B is one --tpu run away")}

    def measure(predict):
        best = None
        for _ in range(max(args.rounds, 1)):
            n_req, _rows, lat, wall = _closed_loop(
                predict, args.clients, args.seconds, sizes, args.n_in)
            cur = {"req_per_s": round(n_req / wall, 1), **_quantiles(lat)}
            if best is None or cur["req_per_s"] > best["req_per_s"]:
                best = cur
        return best

    # ---- mode 1: f32 incumbent on a fresh platform -----------------------
    net = _build_net(args.n_in, args.hidden, args.n_out, seed=1)
    reg = ModelRegistry(tempfile.mkdtemp(prefix="dl4j_quant_bench_"))
    reg.publish("m", net)
    plat = ModelPlatform(reg, seed=7)
    plat.deploy("m", version=1, config=cfg)
    miss0 = aot_cache.stats()["misses"]
    results["f32"] = measure(lambda x: plat.predict("m", x))
    results["f32"]["recompiles_after_warmup"] = (
        aot_cache.stats()["misses"] - miss0)
    plat.close()

    # ---- mode 2: int8 canary -> promote on a fresh platform --------------
    rng = np.random.default_rng(0)
    cal_batches = [rng.normal(size=(32, args.n_in)).astype(np.float32)
                   for _ in range(4)]
    rec = iopt.calibrate(net, cal_batches)
    qnet = iopt.quantize_for_inference(net, rec)
    plat2 = ModelPlatform(reg, seed=7)
    plat2.deploy("m", version=1, config=cfg)
    reg.publish("m", qnet)
    plat2.deploy_canary("m", version=2, fraction=0.5,
                        gate=CanaryGate(min_requests=8,
                                        max_accuracy_delta=0.25,
                                        accuracy_sample=1.0))
    miss_canary = aot_cache.stats()["misses"]
    for i in range(24):
        x = np.random.default_rng(100 + i).normal(
            size=(sizes[i % len(sizes)], args.n_in)).astype(np.float32)
        plat2.predict("m", x)
    canary_recompiles = aot_cache.stats()["misses"] - miss_canary
    canary = plat2.stats()["m"].get("canary") or {}
    promoted = plat2.promote("m")
    miss1 = aot_cache.stats()["misses"]
    results["int8"] = measure(lambda x: plat2.predict("m", x))
    results["int8"]["recompiles_after_warmup"] = (
        aot_cache.stats()["misses"] - miss1)
    results["int8"]["canary_recompiles"] = canary_recompiles
    results["int8"]["promoted_version"] = promoted["version"]
    results["accuracy_max_delta"] = canary.get("accuracy_max_delta")
    results["accuracy_samples"] = canary.get("accuracy_samples")
    results["quantization"] = {"scheme": rec.scheme,
                               "calibration_digest": rec.digest[:8]}
    plat2.close()

    speed = round(results["int8"]["req_per_s"]
                  / max(results["f32"]["req_per_s"], 1e-9), 3)
    results["int8_over_f32"] = speed

    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    rf, rq = results["f32"], results["int8"]
    print(f"\nf32 : {rf['req_per_s']:>8} req/s  p95 {rf['p95_ms']} ms  "
          f"recompiles {rf['recompiles_after_warmup']}")
    print(f"int8: {rq['req_per_s']:>8} req/s  p95 {rq['p95_ms']} ms  "
          f"recompiles {rq['recompiles_after_warmup']}  "
          f"(canary {canary_recompiles})")
    print(f"accuracy_max_delta {results['accuracy_max_delta']} over "
          f"{results['accuracy_samples']} samples   "
          f"int8/f32 {speed}x (CPU proxy)")
    ok = (rf["recompiles_after_warmup"] == 0
          and rq["recompiles_after_warmup"] == 0
          and canary_recompiles == 0
          and promoted["version"] == 2
          and results["accuracy_max_delta"] is not None
          and results["accuracy_max_delta"] <= 0.25)
    print("OK" if ok else "FAIL: quantized-serving invariant broken")
    return 0 if ok else 1


def smoke(args):
    """make serve-smoke: HTTP server up -> concurrent predicts ->
    /metrics scrape -> clean stop."""
    import urllib.request

    import numpy as np

    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import BatchingConfig
    from deeplearning4j_tpu.parallel.serving import InferenceServer

    net = _build_net(args.n_in, args.hidden, args.n_out)
    server = InferenceServer(net, batching=BatchingConfig(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms)
    ).start(port=0, warmup=True)
    base = f"http://127.0.0.1:{server.port}"
    miss0 = aot_cache.stats()["misses"]
    errors = []

    def client(ci):
        rng = np.random.default_rng(ci)
        for i in range(8):
            n = 1 + (ci + i) % 5
            x = rng.normal(size=(n, args.n_in)).astype(np.float32)
            req = urllib.request.Request(
                base + "/predict",
                json.dumps({"inputs": [x.tolist()]}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                body = json.loads(r.read())
            if len(body["outputs"][0]) != n:
                errors.append(f"client {ci}: demux row count mismatch")

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    text = urllib.request.urlopen(base + "/metrics",
                                  timeout=10).read().decode()
    server.stop()
    recompiles = aot_cache.stats()["misses"] - miss0
    ok = (not errors and recompiles == 0
          and "dl4j_serving_requests_total" in text
          and "dl4j_serving_batches_total" in text)
    print(f"serve-smoke: {args.clients} clients x 8 ragged predicts, "
          f"recompiles={recompiles}, errors={errors or 'none'}, "
          f"metrics={'ok' if 'dl4j_serving' in text else 'MISSING'}")
    print("OK" if ok else "FAIL")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rounds", type=int, default=3,
                    help="measurement rounds per mode; best req/s wins")
    ap.add_argument("--sizes", default="1,2,3,4",
                    help="comma list of request row counts cycled per client")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--settle-ms", type=float, default=0.2)
    ap.add_argument("--backend", choices=("pi", "single"), default="pi",
                    help="pi = sharded ParallelInference deployment "
                         "(default), single = bare network")
    ap.add_argument("--n-in", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--n-out", type=int, default=10)
    ap.add_argument("--no-graph-opt", action="store_true")
    ap.add_argument("--out", default="bench_serving.json")
    ap.add_argument("--assert-speedup", type=float, default=0.0,
                    help="exit 1 if batched/locked speedup is below this")
    ap.add_argument("--smoke", action="store_true",
                    help="HTTP round-trip smoke instead of the benchmark")
    ap.add_argument("--multi-model", action="store_true",
                    help="two-tenant platform isolation A/B: healthy "
                         "tenant + fault-injected canary, per-tenant "
                         "req/s / p95 / sheds / rollback / recompiles")
    ap.add_argument("--assert-isolation", action="store_true",
                    help="with --multi-model: exit 1 unless the healthy "
                         "tenant stayed byte-identical with zero "
                         "recompiles and the canary rolled back")
    ap.add_argument("--traces", action="store_true",
                    help="request-tracing overhead A/B: the same "
                         "closed-loop traffic with tracing off then on, "
                         "plus the trace-derived stage breakdown")
    ap.add_argument("--trace-overhead-budget", type=float, default=0.25,
                    help="with --traces: exit 1 if tracing-on loses more "
                         "than this fraction of tracing-off req/s")
    ap.add_argument("--quant", action="store_true",
                    help="f32-vs-int8 quantized serving A/B: calibrate, "
                         "quantize, canary with the accuracy gate, "
                         "promote, measure — zero recompiles both modes")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the real accelerator (default: CPU pin)")
    args = ap.parse_args()
    if args.tpu:
        from deeplearning4j_tpu.util.device import banner, require_tpu

        dev = require_tpu("bench_serving.py --tpu")
        print("# " + banner(dev), flush=True)
    else:
        _pin_cpu()
    if args.multi_model:
        if args.out == "bench_serving.json":
            args.out = "bench_serving_mt.json"
        return bench_multi_model(args)
    if args.traces:
        if args.out == "bench_serving.json":
            args.out = "bench_serving_traces.json"
        return bench_traces(args)
    if args.quant:
        if args.out == "bench_serving.json":
            args.out = "bench_serving_quant.json"
        return bench_quant(args)
    return smoke(args) if args.smoke else bench(args)


if __name__ == "__main__":
    sys.exit(main())
