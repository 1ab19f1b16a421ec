"""The two readers of the program's own spans (``readers/span_time.py``,
``readers/idle_owner.py``) on a ring and a trace whose answers are known
by hand, and a CPU rehearsal of each cell whose line holds the metrics
that need no device trace.

The synthetic traces go through the real reduction
(``trace_reduce.summarize``); the synthetic ring takes the place of
``deeplearning4j_tpu.telemetry.spans.events()``. All times below are
written in ms and handed over in ns.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, trace_reduce
from benchmarks.readers import idle_owner, program_spans, span_time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1e6


def _span(sid, name, a, b, thread, parent=None, zero_ms=0.0, **attrs):
    return {"name": name, "start_ns": int((a + zero_ms) * MS),
            "duration_ns": int(round((b - a) * MS)), "depth": 0,
            "parent": None, "thread": thread, "id": sid,
            "parent_id": parent, **({"attrs": attrs} if attrs else {})}


@pytest.fixture
def ring(monkeypatch):
    """``ring(events)`` puts a synthetic ring under the readers."""
    from deeplearning4j_tpu.telemetry import spans

    def put(events):
        monkeypatch.setattr(spans, "events",
                            lambda: [dict(e) for e in events])
    return put


def _summary(modules, window, host=()):
    planes = {"devices": {0: {"modules": [(n, a * MS, b * MS)
                                          for n, a, b in modules],
                              "ops": []}},
              "host": [(trace_reduce.WINDOW_SPAN, window[0] * MS,
                        window[1] * MS)]
              + [("bench:" + n, a * MS, b * MS) for n, a, b in host]}
    return trace_reduce.summarize(planes)


def _params(metric):
    return harness.load_json(ROOT, "benchmarks", "metrics",
                             metric + ".json")["params"]


# --------------------------------------------------------------------------
# serving: the engine's loop on thread 1, a caller of submit on thread 2
# --------------------------------------------------------------------------

ZERO_S = 1000.0          # host monotonic seconds at the trace clock's zero


def _serving(zero_ms=ZERO_S * 1e3):
    def s(*a, **kw):
        return _span(*a, zero_ms=zero_ms, **kw)

    loop = [
        s(1, "gen.admit", 8, 11, 1, lock_wait_us=100.0, n=1),
        s(2, "gen.decode", 12, 112, 1, emitted=32, n=1),
        s(3, "gen.decode.plan", 12, 14, 1, 2, lock_wait_us=200.0),
        s(4, "gen.decode.launch", 14, 19, 1, 2),
        s(5, "gen.decode.readback", 19, 111, 1, 2, sync=True),
        s(6, "gen.decode.account", 111, 112, 1, 2, lock_wait_us=300.0),
        s(7, "gen.admit", 112, 113, 1, lock_wait_us=0.0, n=2),
        s(8, "gen.prefill", 113, 119, 1, joins=2, n=2),
        s(9, "gen.prefill.stage", 113, 114, 1, 8),
        s(10, "gen.prefill.launch", 114, 115, 1, 8),
        s(11, "gen.prefill.readback", 115, 118.5, 1, 8, sync=True),
        s(12, "gen.prefill.account", 118.5, 119, 1, 8, lock_wait_us=400.0),
        s(13, "gen.decode", 119, 207, 1, emitted=30, n=2),
        s(14, "gen.decode.plan", 119, 121, 1, 13, lock_wait_us=0.0),
        s(15, "gen.decode.launch", 121, 129, 1, 13),
        s(16, "gen.decode.readback", 129, 206, 1, 13, sync=True),
        s(17, "gen.decode.account", 206, 207, 1, 13, lock_wait_us=0.0),
        s(18, "gen.admit", 207, 260, 1, lock_wait_us=0.0, n=3),
        s(19, "gen.wait", 208, 259, 1, 18, n=3),
    ]
    caller = [
        s(20, "gen.submit", 111, 117, 2),
        s(21, "gen.submit.key", 112, 116, 2, 20, sync=True),
        s(22, "gen.submit.enqueue", 116, 116.5, 2, 20, lock_wait_us=5.0),
    ]
    # a child ends, and is written, before its parent
    events = sorted(loop + caller,
                    key=lambda e: e["start_ns"] + e["duration_ns"])
    # the first window's tokens are seen 1 ms after it ends on the
    # device, the second's the moment it ends
    trace = _summary([("jit_fn(1)", 20, 110), ("jit_fn(2)", 115.5, 118),
                      ("jit_fn(1)", 130, 206)], (10, 210))
    obs = {"trace": trace, "traced": {"t_start": ZERO_S + 0.010},
           "window": {"t0": ZERO_S + 0.005, "t_end": ZERO_S + 0.300},
           "notes": {}}
    return events, obs


def test_serving_idle_is_owned_by_the_right_spans(ring):
    events, obs = _serving()
    ring(events)
    # idle: 10..20, 110..115.5, 118..130, 206..210 = 31.5 ms, of which
    # 1 ms between gen.admit and gen.decode is nobody's span and 2 ms lie
    # under gen.wait (nothing to do: not counted as owned)
    got = idle_owner.read(None, obs, _params("idle_owned_pct.backlog"))
    assert got == pytest.approx(100.0 * 28.5 / 31.5)
    table = obs["notes"]["idle_by_program_span"]
    assert table["gen.decode.launch"]["idle_ms"] == pytest.approx(13.0)
    assert table["gen.decode.launch"]["longest_ms"] == pytest.approx(8.0)
    assert table["gen.decode.plan"]["idle_ms"] == pytest.approx(4.0)
    assert table["gen.decode.readback"]["idle_ms"] == pytest.approx(3.0)
    assert table["gen.decode.account"]["idle_ms"] == pytest.approx(2.0)
    assert table["gen.admit"]["idle_ms"] == pytest.approx(3.0)
    assert table["gen.prefill.readback"]["idle_ms"] == pytest.approx(1.0)
    assert table["gen.wait"]["idle_ms"] == pytest.approx(2.0)
    assert table["gen.admit -> gen.decode"]["idle_ms"] == pytest.approx(1.0)
    assert sum(r["idle_ms"] for r in table.values()) == pytest.approx(31.5)
    # the caller's thread: what it was doing while the device idled
    assert obs["notes"]["idle_meanwhile_on_other_threads"] == {
        "gen.submit.key": pytest.approx(3.5),
        "gen.submit": pytest.approx(1.0)}
    assert obs["notes"]["clock_tie_shift_ms"] == pytest.approx(0.0)
    assert obs["notes"]["clock_tie_residual_ms"] == pytest.approx(0.0)
    assert obs["notes"]["result_seen_after_ms"] == {
        "median": pytest.approx(1.0), "widest": pytest.approx(1.0)}
    assert obs["notes"]["traced_window"] == {
        "program_runs": 2, "gen.decode.launch": 2, "gen.decode.readback": 2,
        "gaps_over_10_ms": 1,
        "span_coverage_pct": pytest.approx(100.0 * 199 / 200)}


def test_serving_tie_is_made_again_on_the_windows(ring):
    # the harness's one tie point 4.6 ms late (as in one chip run of
    # seven): every window would end after its read-back returned. The
    # reader shifts the host's spans until the quickest read-back returns
    # the moment its window ends, and reads as with a sound tie
    events, obs = _serving(zero_ms=ZERO_S * 1e3 - 4.6)
    ring(events)
    assert idle_owner.read(None, obs, _params(
        "idle_owned_pct.backlog")) == pytest.approx(100.0 * 28.5 / 31.5)
    assert obs["notes"]["clock_tie_shift_ms"] == pytest.approx(4.6)
    assert obs["notes"]["clock_tie_residual_ms"] == pytest.approx(0.0)
    assert obs["notes"]["idle_by_program_span"]["gen.decode.launch"][
        "idle_ms"] == pytest.approx(13.0)


@pytest.mark.parametrize("fault", ["clock_30_ms_off", "run_before_launch"])
def test_serving_wrong_tie_leaves_the_metric_out(ring, fault):
    events, obs = _serving(
        zero_ms=ZERO_S * 1e3 + (30.0 if fault == "clock_30_ms_off" else 0))
    ring(events)
    if fault == "run_before_launch":
        # the second window starts on the device 1.5 ms before the loop
        # began to launch it (121): no shift can be right
        obs["trace"] = _summary([("jit_fn(1)", 20, 110),
                                 ("jit_fn(1)", 119.5, 206)], (10, 210))
    assert idle_owner.read(None, obs, _params(
        "idle_owned_pct.backlog")) is None
    assert "idle_by_program_span" not in obs["notes"]


def test_serving_span_times(ring):
    events, obs = _serving()
    ring(events)
    # the loop's own time outside the read-backs and gen.wait: admits
    # 3 + 1 + 2, decode 0 + plan 2 + launch 5 + account 1 (twice: 2 + 8
    # + 1), prefill 0 + stage 1 + launch 1 + account 0.5 = 27.5, over two
    # decode windows
    assert span_time.read(None, obs, _params(
        "loop_host_ms_per_window.backlog")) == pytest.approx(27.5 / 2)
    # the ring begins at 8 ms, the window ends at 300: 1 ms between the
    # first admit and the first window, 40 ms after the last admit
    assert obs["notes"]["span_coverage"]["covered_pct"] == pytest.approx(
        100.0 * (292 - 1 - 40) / 292)
    assert obs["notes"]["span_coverage"]["holes_ms"][
        "gen.admit -> window_end"] == pytest.approx(40.0)
    assert span_time.read(None, obs, _params(
        "decode_account_ms.backlog")) == pytest.approx(1.0)
    assert span_time.read(None, obs, _params(
        "loop_lock_wait_ms_per_window.backlog")) == pytest.approx(1.0 / 2)
    assert span_time.read(None, obs, _params(
        "submit_ms.backlog")) == pytest.approx(6.0)
    assert obs["notes"]["submit_children_ms"] == {
        "gen.submit.enqueue": pytest.approx(0.5),
        "gen.submit.key": pytest.approx(4.0)}
    assert span_time.read(None, obs, _params(
        "prefill_ms_per_join.backlog")) == pytest.approx(6.0 / 2)


def test_self_time_is_duration_less_children(ring):
    ring(_serving(zero_ms=0.0)[0])
    events = program_spans.events()
    kids = program_spans.children_of(events)
    by_id = {e["id"]: e for e in events}
    assert program_spans.self_ns(by_id[2], kids) == 0
    assert program_spans.self_ns(by_id[18], kids) == pytest.approx(2 * MS)
    assert program_spans.self_ns(by_id[20], kids) == pytest.approx(1.5 * MS)


# --------------------------------------------------------------------------
# training: fit on thread 5, two steps between drains, a step 100 ms
# --------------------------------------------------------------------------

def _training(stray_host_span=False):
    zero_ms = 5e6

    def s(*a, **kw):
        return _span(*a, zero_ms=zero_ms, **kw)

    fit = s(100, "fit", -1000, 2000, 5, epochs=1)
    kids = [
        s(101, "drain", -100, -1, 5, 100, sync=True, steps=2),
        s(102, "fit.next_batch", -0.5, 52, 5, 100),   # the profiler starts
        s(103, "ingest", 52.1, 60, 5, 100),
        s(104, "compute", 60, 61, 5, 100),
        s(105, "fit.next_batch", 61.1, 61.3, 5, 100),
        s(106, "ingest", 61.3, 69, 5, 100),
        s(107, "compute", 69, 70, 5, 100),
        s(108, "drain", 70.2, 261.5, 5, 100, sync=True, steps=2),
        s(109, "fit.next_batch", 262, 262.2, 5, 100),
        s(110, "ingest", 262.2, 300, 5, 100),
        s(111, "compute", 300, 301, 5, 100),
        s(112, "fit.next_batch", 301.1, 301.3, 5, 100),
        s(113, "ingest", 301.3, 309, 5, 100),
        s(114, "compute", 309, 310, 5, 100),
        s(115, "drain", 310.2, 501.8, 5, 100, sync=True, steps=2),
    ]
    # the set-up's fit, earlier, with a longer wait for its first batch
    earlier = [s(90, "fit.next_batch", -9000, -8000, 5, 91),
               s(91, "fit", -9100, -7000, 5, epochs=1)]
    host = [("next_batch", 0, 52), ("next_batch", 61.15, 61.25),
            ("next_batch", 262.05, 262.15), ("next_batch", 301.15, 301.25),
            ("fit_between_steps", 52, 61.15)]
    if stray_host_span:
        host.append(("next_batch", 150, 150.2))
    trace = _summary([("jit_step(7)", 61.5, 161.5),
                      ("jit_step(7)", 161.5, 261.5),
                      ("jit_step(7)", 301.5, 401.5),
                      ("jit_step(7)", 401.5, 501.5)], (50, 450), host)
    obs = {"trace": trace, "traced": None,
           "window": {"seconds": 3.0, "steps": 30}, "notes": {}}
    return earlier + kids + [fit], obs


def test_training_tie_and_idle_after_drain(ring):
    events, obs = _training()
    ring(events)
    # one drain ends inside the traced window, at 261.5, the moment the
    # last step it waited for ends; the next step program starts at 301.5
    assert idle_owner.read(None, obs, _params(
        "idle_after_drain_ms.train")) == pytest.approx(40.0)
    # idle 50..61.5 and 261.5..301.5 = 51.5 ms; fit's own time between
    # its children (0.1 + 0.1 and 0.5 + 0.1) is nobody's span
    assert idle_owner.read(None, obs, _params(
        "idle_owned_pct.train")) == pytest.approx(100.0 * 50.7 / 51.5)
    table = obs["notes"]["idle_by_program_span"]
    assert table["ingest"]["idle_ms"] == pytest.approx(7.9 + 0.2 + 37.8 + 0.2)
    assert table["ingest"]["longest_ms"] == pytest.approx(37.8)
    assert table["compute"]["idle_ms"] == pytest.approx(2.0)
    assert table["fit.next_batch"]["idle_ms"] == pytest.approx(2.6)
    assert "drain" not in table
    assert table["drain -> fit.next_batch"]["idle_ms"] == pytest.approx(0.5)
    assert obs["notes"]["clock_tie_shift_ms"] == pytest.approx(0.0)
    assert obs["notes"]["clock_tie_residual_ms"] == pytest.approx(0.0)
    assert obs["notes"]["result_seen_after_ms"]["widest"] == \
        pytest.approx(0.0)
    assert obs["notes"]["traced_window"] == {
        "program_runs": 3, "compute": 4, "drain": 1, "gaps_over_10_ms": 2,
        "span_coverage_pct": pytest.approx(100.0 * (400 - 1.2) / 400)}


def test_training_nesting_check_makes_the_reader_return_none(ring):
    events, obs = _training(stray_host_span=True)
    ring(events)
    # a harness next_batch span where the program has no fit.next_batch:
    # the tie cannot be right
    assert idle_owner.read(None, obs, _params(
        "idle_after_drain_ms.train")) is None
    assert idle_owner.read(None, obs, _params(
        "idle_owned_pct.train")) is None
    assert "clock_tie_residual_ms" not in obs["notes"]


def test_training_span_times_read_the_windows_fit_only(ring):
    events, obs = _training()
    ring(events)
    assert span_time.read(None, obs, _params(
        "ingest_ms_per_step.train")) == pytest.approx(
            (7.9 + 7.7 + 37.8 + 7.7) / 4)
    assert span_time.read(None, obs, _params(
        "dispatch_ms_per_step.train")) == pytest.approx(1.0)
    cover = obs["notes"]["span_coverage"]
    assert cover["thread_of"] == "fit"
    # fit -1000..2000; its children cover 99 + 52.5 + ... as listed
    own = 3000 - (99 + 52.5 + 7.9 + 1 + 0.2 + 7.7 + 1 + 191.3 + 0.2 + 37.8
                  + 1 + 0.2 + 7.7 + 1 + 191.6)
    assert cover["covered_pct"] == pytest.approx(100.0 * (3000 - own) / 3000)


def test_no_ring_no_trace_no_metric(ring):
    _events, obs = _serving()
    # the commit before PR 25: spans are recorded only when asked, and
    # carry no id
    ring([])
    for name in ("idle_owned_pct.backlog", "idle_owned_pct.train",
                 "idle_after_drain_ms.train"):
        assert idle_owner.read(None, obs, _params(name)) is None
    assert span_time.read(None, obs, _params("submit_ms.backlog")) is None
    ring([{"name": "compute", "start_ns": 1, "duration_ns": 2, "depth": 0,
           "parent": None, "thread": 1}])
    assert span_time.read(None, obs, _params(
        "dispatch_ms_per_step.train")) is None
    # a CPU rehearsal has no device trace
    events, obs = _serving()
    ring(events)
    obs["trace"] = None
    assert idle_owner.read(None, obs, _params(
        "idle_owned_pct.backlog")) is None


# --------------------------------------------------------------------------
# the cells, rehearsed on the CPU: the span-only metrics are in the line
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cell,expected", [
    ("gpt2-large-serve-backlog", [
        "loop_host_ms_per_window.backlog", "decode_account_ms.backlog",
        "loop_lock_wait_ms_per_window.backlog", "submit_ms.backlog",
        "prefill_ms_per_join.backlog"]),
    ("resnet50-train-b256", [
        "ingest_ms_per_step.train", "dispatch_ms_per_step.train"]),
])
def test_rehearsal_line_holds_the_span_metrics(cell, expected):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", harness_flag()], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is False
    for name in expected:
        assert line["metrics"][name]["value"] >= 0, name
        assert line["metrics"][name]["unit"] == "ms"
    # what needs the device trace is left out, not guessed
    assert not any(name.startswith("idle_") for name in line["metrics"])
    cover = line["notes"]["span_coverage"]
    assert cover["covered_pct"] > 90.0 and cover["ring_spans_per_s"] > 0


def harness_flag():
    from benchmarks import run

    return run.REHEARSAL_FLAG
