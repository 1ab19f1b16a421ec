"""Telemetry layer: spans (nesting/aggregation/Chrome trace), metrics
registry (determinism, prometheus format), /metrics endpoint, disabled-mode
fast path, training-path instrumentation, and the bench phase-name drift
check (ISSUE 3 acceptance criteria)."""

import json
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry as tel
from deeplearning4j_tpu.conf import Activation, InputType
from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.conf.losses import LossMCXENT
from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
from deeplearning4j_tpu.conf.updaters import Sgd
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tel.disable()
    tel.reset()
    yield
    tel.disable()
    tel.reset()


def _net(seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=4, activation=Activation.TANH))
            .layer(OutputLayer(n_out=2, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(3))
            .build())
    return MultiLayerNetwork(conf).init()


def _ds(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet(rng.normal(size=(n, 3)).astype(np.float32),
                   np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)])


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

def test_disabled_mode_zero_allocation_fast_path(monkeypatch):
    """The contract since PR 25: with telemetry never enabled a span
    still lands in the bounded ring, and that is ALL it does: no
    registry write and no host sync. ``enable()`` is what turns on the
    registry, the host-gap clock and ``sync``."""
    import jax

    syncs = []
    monkeypatch.setattr(jax, "block_until_ready", syncs.append)
    assert not tel.enabled() and not tel.sync_mode()
    before = tel.REGISTRY.snapshot(run_collectors=False)
    with tel.span("ingest"):
        pass
    with tel.span("compute") as sp:
        assert sp.set_result("a device result") == "a device result"
    tel.record_step("multilayer", 32)
    tel.record_collective("grad_psum", 4096)
    tel.record_ingest(1 << 20)
    tel.host_gap_reset()
    tel.host_gap_open()
    tel.host_gap_close()
    assert [e["name"] for e in tel.events()] == ["ingest", "compute"]
    assert tel.REGISTRY.snapshot(run_collectors=False) == before
    assert set(tel.phase_stats()) == {"ingest", "compute"}
    # sync=True holds only while enabled
    tel.enable(sync=True)
    tel.disable()
    with tel.span("compute") as sp:
        sp.set_result("a device result")
    assert syncs == []
    tel.enable(sync=True)
    with tel.span("compute") as sp:
        sp.set_result("a device result")
    assert syncs == ["a device result"]


def test_span_nesting_records_depth_and_parent():
    tel.enable()
    with tel.span("outer"):
        with tel.span("inner"):
            time.sleep(0.001)
    evts = tel.events()
    by_name = {e["name"]: e for e in evts}
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["outer"]["parent_id"] is None
    assert by_name["inner"]["parent_id"] == by_name["outer"]["id"]
    # inner closes first and is contained in outer
    assert by_name["inner"]["duration_ns"] <= by_name["outer"]["duration_ns"]


def test_span_aggregation_math():
    tel.enable()
    # synthesize spans with known durations by direct ring writes
    for ms in (1, 2, 3, 4, 100):
        s = tel.spans.Span("phase")
        s.t0 = 0
        s.t1 = ms * 1_000_000
        tel.spans._ring.append((s.name, s.t0, s.t1, 0, None, 0, None,
                                0, None))
    st = tel.phase_stats()["phase"]
    assert st["count"] == 5
    assert st["total_ms"] == pytest.approx(110.0)
    assert st["mean_ms"] == pytest.approx(22.0)
    # nearest-rank percentiles: p50 = ceil(0.5*5)=3rd -> 3ms,
    # p95/p99 = ceil(4.75)/ceil(4.95) = 5th -> 100ms
    assert st["p50_ms"] == pytest.approx(3.0)
    assert st["p95_ms"] == pytest.approx(100.0)
    assert st["p99_ms"] == pytest.approx(100.0)
    assert st["max_ms"] == pytest.approx(100.0)


def test_span_ring_is_bounded():
    tel.enable(ring_size=16)
    for i in range(50):
        with tel.span("s"):
            pass
    assert len(tel.events()) == 16
    tel.enable(ring_size=tel.spans.RING_SIZE)  # restore the default


def test_default_ring_holds_ring_size_spans_and_no_more():
    """Never enabled, never resized: the ring is bounded at RING_SIZE
    (16,384: the module docstring says how it was sized) and evicts the
    oldest in silence."""
    assert tel.spans.RING_SIZE == 16384
    assert tel.spans._ring.maxlen == tel.spans.RING_SIZE
    for i in range(tel.spans.RING_SIZE + 100):
        with tel.span("s") as sp:
            sp.annotate(i=i)
    evts = tel.events()
    assert len(evts) == tel.spans.RING_SIZE
    assert evts[0]["attrs"]["i"] == 100
    assert evts[-1]["attrs"]["i"] == tel.spans.RING_SIZE + 99


def test_spans_read_the_monotonic_clock():
    """One clock for spans, request traces, the engine's handles and the
    benchmark's trace window: ``time.monotonic_ns``."""
    t0 = time.monotonic_ns()
    with tel.span("s"):
        pass
    t1 = time.monotonic_ns()
    (e,) = tel.events()
    assert t0 <= e["start_ns"] <= e["start_ns"] + e["duration_ns"] <= t1


def test_span_ids_tell_two_spans_of_one_name_apart_across_threads():
    import threading

    def work(tag):
        for _ in range(50):
            with tel.span("window") as outer:
                outer.annotate(tag=tag)
                with tel.span("child"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    evts = tel.events()
    by_id = {e["id"]: e for e in evts}
    assert len(by_id) == len(evts) == 400
    for e in evts:
        if e["name"] == "child":
            parent = by_id[e["parent_id"]]
            assert parent["name"] == "window"
            assert parent["thread"] == e["thread"]
            assert parent["start_ns"] <= e["start_ns"]
        else:
            assert e["parent_id"] is None
    # each window owns exactly one child
    owners = [e["parent_id"] for e in evts if e["name"] == "child"]
    assert len(set(owners)) == 200


def test_chrome_trace_export(tmp_path):
    tel.enable()
    with tel.span("compute") as sp:
        sp.annotate(step=3)
    path = tel.export_chrome_trace(str(tmp_path / "sub" / "trace.json"))
    data = json.load(open(path))
    evts = data["traceEvents"]
    assert evts and evts[0]["ph"] == "X"
    assert evts[0]["name"] == "compute"
    assert evts[0]["args"]["step"] == 3
    assert evts[0]["dur"] >= 0
    assert evts[0]["args"]["id"] == tel.events()[0]["id"]


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_snapshot_deterministic():
    def build():
        r = MetricsRegistry()
        r.counter("steps", path="mln").inc(3)
        r.gauge("mem", device="cpu:0").set(1.5)
        h = r.histogram("lat")
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        return r

    s1 = build().snapshot()
    s2 = build().snapshot()
    assert s1 == s2
    # stable key order (sorted) -> identical serialization
    assert json.dumps(s1) == json.dumps(s2)
    assert s1['steps{path="mln"}'] == 3.0
    assert s1["lat"]["count"] == 3
    assert s1["lat"]["p50"] == pytest.approx(0.2)


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_registry_collector_best_effort():
    r = MetricsRegistry()

    @r.register_collector
    def bad(reg):
        raise RuntimeError("probe down")

    @r.register_collector
    def good(reg):
        reg.gauge("up").set(1)

    snap = r.snapshot()
    assert snap["up"] == 1.0


def test_prometheus_text_format():
    tel.enable()
    tel.record_step("multilayer", 32)
    tel.record_collective("grad_psum", 4096, 2)
    with tel.span("compute"):
        pass
    text = tel.prometheus_text()
    assert "# TYPE dl4j_training_steps_total counter" in text
    assert 'dl4j_training_steps_total{path="multilayer"} 1' in text
    assert 'dl4j_collective_bytes_total{op="grad_psum"} 4096' in text
    # scrape-time collectors contribute the AOT-cache ratio
    assert "dl4j_aot_cache_hit_ratio" in text
    # span phases render as a summary
    assert 'dl4j_phase_ms{phase="compute",quantile="0.50"}' in text
    assert 'dl4j_phase_ms_count{phase="compute"} 1' in text


# --------------------------------------------------------------------------
# instrumented training paths
# --------------------------------------------------------------------------

def test_training_run_produces_trace_and_metrics(tmp_path):
    """Acceptance (a)+(b): one training run with telemetry enabled yields
    a Chrome trace with ingest/compute/grad_sync spans and a /metrics
    scrape with step histograms, AOT-cache ratio, collective bytes."""
    from deeplearning4j_tpu.parallel.wrapper import (
        ParallelWrapper,
        TrainingMode,
    )

    tel.enable(sync=True)
    net = _net()
    from deeplearning4j_tpu.profiler import ProfilerListener

    net.set_listeners(ProfilerListener(warmup_iterations=1))
    pw = ParallelWrapper(net, workers=2,
                         training_mode=TrainingMode.SHARED_GRADIENTS,
                         gradient_bucket_mb=0.001, prefetch_buffer=0)
    ds = _ds(n=8)
    pw.fit(ds, epochs=4)

    # (a) Chrome trace with all three phases
    path = tel.export_chrome_trace(str(tmp_path / "trace.json"))
    names = {e["name"] for e in json.load(open(path))["traceEvents"]}
    assert set(tel.PHASES) <= names

    # (b) scrape content
    text = tel.prometheus_text()
    assert "dl4j_aot_cache_hit_ratio" in text
    assert 'dl4j_collective_bytes_total{op="grad_psum"}' in text
    assert "dl4j_step_seconds" in text  # ProfilerListener -> registry
    st = tel.phase_stats()
    for phase in tel.PHASES:
        assert st[phase]["count"] >= 4


def test_multilayer_and_graph_record_steps():
    tel.enable()
    net = _net()
    ds = _ds()
    for _ in range(3):
        net.fit_batch(ds)
    snap = tel.REGISTRY.snapshot(run_collectors=False)
    assert snap['dl4j_training_steps_total{path="multilayer"}'] == 3.0
    assert snap['dl4j_training_examples_total{path="multilayer"}'] == 24.0
    st = tel.phase_stats()
    assert st["ingest"]["count"] == 3
    assert st["compute"]["count"] == 3


def _graph():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
            .graph_builder().add_inputs("in")
            .add_layer("h", DenseLayer(n_out=4, activation=Activation.TANH),
                       "in")
            .add_layer("out", OutputLayer(n_out=2,
                                          activation=Activation.SOFTMAX,
                                          loss_fn=LossMCXENT()), "h")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(3)).build())
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("build", [_net, _graph],
                         ids=["multilayer", "graph"])
def test_fit_span_tree_without_enabling_telemetry(build):
    """``fit`` -> ``fit.next_batch`` / ``ingest`` / ``compute`` /
    ``listeners``, and one ``drain`` per DISPATCH_DEPTH steps plus the
    forced one at the epoch's end: the same breakdown from both network
    classes, recorded with telemetry never enabled."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn import io as nn_io
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Seen(TrainingListener):
        n = 0

        def iteration_done(self, model, iteration, epoch, score):
            self.n += 1

    assert not tel.enabled()
    net = build()
    seen = Seen()
    net.set_listeners(seen)
    steps = 2 * nn_io.DISPATCH_DEPTH + 3
    net.fit(ListDataSetIterator([_ds(seed=i) for i in range(steps)]))
    assert seen.n == steps
    evts = tel.events()
    (root,) = [e for e in evts if e["name"] == "fit"]
    assert root["attrs"] == {"epochs": 1} and root["parent_id"] is None
    kids = [e for e in evts if e["parent_id"] == root["id"]]
    count = {}
    for e in kids:
        count[e["name"]] = count.get(e["name"], 0) + 1
        assert e["thread"] == root["thread"]
    assert count == {"fit.next_batch": steps + 1, "ingest": steps,
                     "compute": steps, "grad_sync": steps,
                     "listeners": steps, "drain": 3}
    drains = [e["attrs"] for e in kids if e["name"] == "drain"]
    assert drains == [{"sync": True, "steps": nn_io.DISPATCH_DEPTH}] * 2 + [
        {"sync": True, "steps": 3}]
    # per step, in order: wait for input, stage it, enqueue, listeners
    order = [e["name"] for e in sorted(kids, key=lambda e: e["start_ns"])]
    assert order[:5] == ["fit.next_batch", "ingest", "compute", "grad_sync",
                         "listeners"]
    assert order[-2:] == ["fit.next_batch", "drain"]
    # nothing was written to the registry
    snap = tel.REGISTRY.snapshot(run_collectors=False)
    assert not any(k.startswith("dl4j_training_steps_total") for k in snap)


def test_device_ring_iterator_records_ingest():
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.datasets.prefetch import DeviceRingIterator

    tel.enable()
    batches = [_ds(n=4, seed=i) for i in range(4)]
    it = DeviceRingIterator(ListDataSetIterator(batches), depth=2)
    assert len(list(it)) == 4
    snap = tel.REGISTRY.snapshot(run_collectors=False)
    assert snap["dl4j_ingest_batches_total"] == 4.0
    assert snap["dl4j_ingest_bytes_total"] > 0


def test_metrics_endpoint_on_ui_server():
    from deeplearning4j_tpu.ui.server import UIServer

    tel.enable()
    tel.record_step("multilayer", 16)
    ui = UIServer()
    port = ui.start(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "# TYPE dl4j_training_steps_total counter" in body
        assert "dl4j_aot_cache_hit_ratio" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=10) as r:
            rec = json.loads(r.read())
        assert "telemetry" in rec and "phases" in rec
        assert ('dl4j_training_steps_total{path="multilayer"}'
                in rec["telemetry"])
    finally:
        ui.stop()


def test_telemetry_listener_bridges_into_storage():
    from deeplearning4j_tpu.ui.stats import InMemoryStatsStorage

    tel.enable()
    storage = InMemoryStatsStorage()
    net = _net()
    net.set_listeners(tel.TelemetryListener(storage, frequency=1,
                                            session_id="t"))
    net.fit(_ds(), epochs=2)
    recs = storage.records()
    assert recs and recs[0]["session"] == "t"
    assert "telemetry" in recs[0] and "phases" in recs[0]


def test_dump_jsonl_round_trip(tmp_path):
    tel.enable()
    tel.record_step("graph", 8)
    p = str(tmp_path / "round.jsonl")
    tel.dump_jsonl(p, extra={"round": "r07"})
    tel.dump_jsonl(p)
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 2
    assert lines[0]["round"] == "r07"
    assert 'dl4j_training_steps_total{path="graph"}' in lines[0]["telemetry"]


def test_telemetry_overhead_bound():
    """Acceptance (c): per-step telemetry cost (3 spans + step counters,
    async mode — the instrumentation every training path adds) is <2% of
    step time on idle hardware (~9µs vs ~300µs even for a toy CPU net;
    ~0.4% of the 2.4ms ResNet-50 TPU step). Asserted with a GENEROUS
    bound (<25%) so a loaded 2-core CI box cannot flake: the per-step
    cost is measured over 2000 reps (stable), the step time as a min of
    several timed runs, instead of differencing two noisy full-loop
    timings whose variance exceeds the effect."""
    net = _net()
    ds = _ds(n=16)
    net.fit_batch(ds)  # compile outside the timed region

    def steps_per_sec(n=40):
        t0 = time.perf_counter()
        for _ in range(n):
            net.fit_batch(ds)
        return (time.perf_counter() - t0) / n

    step_s = min(steps_per_sec() for _ in range(3))

    tel.enable()  # async mode: no host sync added

    def one_step_instrumentation():
        with tel.span(tel.PHASE_INGEST):
            pass
        with tel.span(tel.PHASE_COMPUTE) as sp:
            sp.set_result(None)
        with tel.span(tel.PHASE_GRAD_SYNC) as sp:
            sp.set_result(None)
        tel.record_step("multilayer", 16)

    reps = 2000
    costs = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            one_step_instrumentation()
        costs.append((time.perf_counter() - t0) / reps)
    tel.disable()
    overhead = min(costs) / step_s
    assert overhead < 0.25, (min(costs), step_s, overhead)


# --------------------------------------------------------------------------
# bench <-> framework phase-name drift check
# --------------------------------------------------------------------------

def test_bench_phase_keys_match_telemetry_phases():
    import bench_resnet_profile as brp

    # the bench imports telemetry.PHASES and derives its --phases row
    # keys from them, so both sides report the same phase vocabulary
    assert set(brp.PHASE_ROWS) == set(tel.PHASES)
    assert (brp.PHASE_INGEST, brp.PHASE_COMPUTE, brp.PHASE_GRAD_SYNC,
            brp.PHASE_HOST_GAP) == tel.PHASES
    for phase, keys in brp.PHASE_ROWS.items():
        assert keys, f"phase {phase} has no bench rows"
        if phase != tel.PHASE_COMPUTE:  # compute rows are the step probes
            for k in keys:
                assert k == phase or k.startswith(phase + "_"), (phase, k)


# --------------------------------------------------------------------------
# satellites: profiler round-trip, FileStatsStorage, PerformanceListener
# --------------------------------------------------------------------------

def test_profiler_trace_round_trip(tmp_path):
    import glob

    import jax.numpy as jnp

    from deeplearning4j_tpu.profiler import OpProfiler

    prof = OpProfiler.get_instance()
    d = str(tmp_path / "xprof" / "run1")
    with prof.trace(d):
        jnp.sum(jnp.ones((8, 8))).block_until_ready()
    assert glob.glob(d + "/**/*", recursive=True), "trace dir empty"
    # double stop is a no-op
    assert prof.stop_trace() is None
    assert prof.stop_trace() is None
    # plain start/stop returns the dir; a third stop is again a no-op
    d2 = str(tmp_path / "xprof" / "run2")
    prof.start_trace(d2)
    assert prof.stop_trace() == d2
    assert prof.stop_trace() is None


def test_profiler_listener_routes_into_registry():
    from deeplearning4j_tpu.profiler import ProfilerListener

    tel.enable()
    pl = ProfilerListener(warmup_iterations=0)
    for i in range(4):
        pl.iteration_done(None, i, 0, 0.0)
        time.sleep(0.001)
    snap = tel.REGISTRY.snapshot(run_collectors=False)
    h = snap['dl4j_step_seconds{path="profiler"}']
    assert h["count"] == 3  # deltas between 4 iterations
    assert h["sum"] > 0


def test_file_stats_storage_skips_corrupt_lines(tmp_path):
    from deeplearning4j_tpu.ui.stats import FileStatsStorage

    p = str(tmp_path / "stats.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"iteration": 0, "score": 1.0}) + "\n")
        f.write('{"iteration": 1, "score"')  # truncated mid-write
        f.write("\n\n")
        f.write("[1, 2, 3]\n")  # valid JSON, not a record
        f.write(json.dumps({"iteration": 2, "score": 0.5}) + "\n")
    st = FileStatsStorage(p)
    recs = st.records()
    assert [r["iteration"] for r in recs] == [0, 2]
    assert st.corrupt_lines == 2
    # storage stays appendable after a damaged load
    st.put({"iteration": 3})
    assert FileStatsStorage(p).records()[-1] == {"iteration": 3}


def test_performance_listener_refit_and_batches_per_sec():
    import io

    from deeplearning4j_tpu.optimize.listeners import PerformanceListener

    class _M:
        last_batch_size = 10

    out = io.StringIO()
    pl = PerformanceListener(frequency=1, stream=out)
    m = _M()
    # first fit: two quick iterations -> high rate
    pl.iteration_done(m, 0, 0, 0.0)
    time.sleep(0.01)
    pl.iteration_done(m, 1, 0, 0.0)
    first_rate = pl.last_examples_per_sec
    assert first_rate is not None
    # refit after an idle gap: on_epoch_start re-primes the window, so
    # the stale timestamp must NOT depress the first post-refit rate
    time.sleep(0.25)
    pl.on_epoch_start(m, 1)
    pl.iteration_done(m, 2, 1, 0.0)  # primes only — no rate over the gap
    rate_after_prime = pl.last_examples_per_sec
    assert rate_after_prime == first_rate  # unchanged: no stale report
    time.sleep(0.01)
    pl.iteration_done(m, 3, 1, 0.0)
    assert pl.last_examples_per_sec == pytest.approx(
        pl.last_batches_per_sec * 10)
    # the post-refit window excludes the 0.25s gap -> rate stays high
    assert pl.last_batches_per_sec > 1.0 / 0.2
    assert "batches/sec" in out.getvalue()
