"""The trace reduction on intervals whose answer is known by hand, and on
a small trace recorded on the chip (``testdata/tiny_tpu.xplane.pb``: three
runs of one jitted program ``tanh(x @ x) @ x`` on a TPU v5 lite, the host
sleeping 20 ms after each; PR 24)."""

import os

import pytest

from benchmarks import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "tiny_tpu.xplane.pb")


def test_union_and_gaps_by_hand():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (20, 21)])
    assert busy == [(0, 3), (5, 9), (20, 21)]
    assert tr.total(busy) == 8
    assert tr.gaps(busy, 0, 25) == [(3, 5), (9, 20), (21, 25)]
    assert tr.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_names():
    op = ("%convolution_add_fusion.12 = bf16[8,8]{1,0} fusion(bf16[8,8] "
          "%convolution.3), kind=kOutput, calls=%fused_computation.7")
    assert tr.short_name(op) == "convolution_add_fusion.12"


def test_self_time_of_nested_operations():
    # a while loop over 0..100 whose body ran twice; a later, separate op
    ev = [(0, 100, "while"), (10, 30, "body"), (40, 60, "body"),
          (45, 50, "inner"), (120, 130, "after")]
    assert tr.self_times(ev) == [60, 20, 15, 5, 10]
    assert tr.self_times(list(reversed(ev))) == [10, 5, 15, 20, 60]


def _synthetic():
    ops = [("%a.1 = f32[] add()", 0, 10), ("%b.2 = f32[] mul()", 10, 30),
           ("%a.1 = f32[] add()", 100, 110), ("%b.2 = f32[] mul()", 110, 130)]
    mods = [("jit_step(11)", 0, 30), ("jit_step(11)", 100, 130),
            ("jit_other(22)", 140, 150)]
    host = [("bench:trace_window", 0, 200), ("bench:next_batch", 35, 95)]
    return {"devices": {0: {"modules": mods, "ops": ops}}, "host": host}


def test_summary_by_hand():
    s = tr.summarize(_synthetic())
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx(60e-9)
    assert s["idle_share"] == pytest.approx(1 - 60 / 200)
    assert tr.program_runs(s, r"^jit_step\(") == [(0, 30), (100, 130)]
    assert tr.op_seconds(s, [r"^%b\."]) == pytest.approx(40e-9)
    # the longest gaps: 30..100 (the benchmark's own span covers it) and
    # 130..200 (nobody's)
    names = dict((round(sec * 1e9), name) for name, sec in s["top_gaps"])
    assert names[70] == "next_batch" or names[70] == "unattributed"
    top = sorted(s["top_gaps"], key=lambda g: -g[1])
    assert [round(g[1] * 1e9) for g in top[:2]] == [70, 70]


def test_host_spans_are_tied_to_the_trace_by_the_sync_program():
    planes = _synthetic()
    planes["devices"][0]["modules"] += [("jit_bench_sync(7)", 40, 50)]
    # the sync program's result was ready on the host at 100.0 s of the
    # monotonic clock: that is 50 ns on the trace's clock
    host = tr.host_spans(
        planes, [("next_batch", 100.0 - 15e-9, 100.0 + 45e-9),
                 ("before_the_window", 99.0, 99.5)],
        t_open=100.0 - 50e-9, t_stop=100.0 + 150e-9, t_sync_done=100.0,
        sync_program="bench_sync")
    assert [n for n, _a, _b in host] == ["bench:trace_window",
                                         "bench:next_batch"]
    assert host[0][1:] == pytest.approx((0.0, 200.0), abs=1e-3)
    assert host[1][1:] == pytest.approx((35.0, 95.0), abs=1e-3)
    planes["host"] = host
    s = tr.summarize(planes)
    assert s["window_s"] == pytest.approx(200e-9, rel=1e-3)
    assert dict((round(sec * 1e9), n) for n, sec in s["top_gaps"])[70] \
        in ("next_batch", "unattributed")
    # no sync run in the trace: the trace's clock begins at the opening
    del planes["devices"][0]["modules"][-1]
    host = tr.host_spans(planes, [], 5.0, 5.0 + 200e-9, None, "bench_sync")
    assert host == [("bench:trace_window", pytest.approx(0.0, abs=1e-3),
                     pytest.approx(200.0, abs=1e-3))]


def test_pick_most_runs():
    planes = _synthetic()
    planes["devices"][0]["modules"] += [("jit_step(33)", 160, 170)]
    s = tr.summarize(planes)
    assert len(tr.program_runs(s, r"^jit_step\(")) == 3
    assert tr.program_runs(s, r"^jit_step\(", "most_runs") == \
        [(0, 30), (100, 130)]


def test_no_device_is_an_error():
    with pytest.raises(tr.NoDeviceTrace):
        tr.summarize({"devices": {}, "host": []})


def test_recorded_tpu_trace():
    planes = tr.read_planes(TRACE)
    assert list(planes["devices"]) == [0]
    dev = planes["devices"][0]
    assert len(dev["modules"]) == 3
    assert all(n.startswith("jit_tiny_prog(") for n, _a, _b in dev["modules"])
    s = tr.summarize(planes)
    runs = tr.program_runs(s, r"^jit_tiny_prog\(")
    assert len(runs) == 3
    # brute force, independent of union(): sample the window at 1 us
    lo = min(a for _n, a, _b in dev["ops"])
    hi = max(b for _n, _a, b in dev["ops"])
    step = 1000.0
    n = int((hi - lo) / step)
    hit = 0
    for i in range(n):
        t = lo + (i + 0.5) * step
        hit += any(a <= t < b for _n, a, b in dev["ops"])
    assert s["busy_s"] == pytest.approx(hit * step * 1e-9, rel=0.02)
    # the host slept 20 ms after each run: two gaps of at least that
    between = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    assert all(g >= 20e6 for g in between)
    assert s["idle_share"] > 0.9
    # every op lies inside a program run
    for _n, a, b in dev["ops"]:
        assert any(ra - 1 <= a and b <= rb + 1 for ra, rb in runs)
