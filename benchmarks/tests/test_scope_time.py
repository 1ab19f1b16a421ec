"""``readers/scope_time.py`` on synthetic summaries whose answers are
known by hand: one program in the traced window gives the numbers, two
fingerprints give ``None``, and so does a program without the table, a
table that is not the traced program's, and a run without a trace. The
synthetic traces go through the real reduction
(``trace_reduce.summarize``); the table is a stand-in for
``deeplearning4j_tpu.optimize.aot_cache.programs()``. Times are written
in us and handed over in ns."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import harness, trace_reduce
from benchmarks.readers import scope_time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
US = 1e3
K = 4


def _params(metric):
    return harness.load_json(ROOT, "benchmarks", "metrics",
                             metric + ".json")["params"]


def _op(scope, result="f32[8,128]{1,0}", **kw):
    from deeplearning4j_tpu.telemetry import device_time

    base = dict(scope=scope, backward=False, in_while=True, hoisted=False,
                result=result, opcode="fusion", via="")
    base.update(kw)
    return device_time.Op(**base)


def _program(kind, table, dispatches):
    return SimpleNamespace(kind=kind, module_name="jit_fn", trace_id=None,
                           dispatches=dispatches, scope_map=lambda: table)


# one decode window of 1,000 us: a convert before the loop (hoisted), the
# while over 900 us whose body holds the operations of K = 4 steps:
# feed-forward 100, two attention classes 40 + 20, head 30, sampling 10,
# the loop's counter (no scope) 5: 205 a step, 820 a window; the while's
# own time is the 80 us its body does not cover
def _window(start, names=("jit_fn(11)",)):
    f = "f32[8,128]{1,0}"
    ops = [(f"%convert.9 = {f} convert(f32[8,128] %w)", start, start + 100),
           (f"%while.1 = (s32[], {f}) while((s32[], {f}) %t)", start + 100,
            start + 1000)]
    at = start + 110
    for _step in range(K):
        for name, us in (("fusion.1", 100), ("fusion.2", 40),
                         ("fusion.3", 20), ("fusion.4", 30),
                         ("fusion.5", 10), ("add.7", 5)):
            ops.append((f"%{name} = {f} fusion({f} %x)", at, at + us))
            at += us
    return ops


TABLE = {
    "convert.9": _op(("ffn", "b0_ffn"), in_while=False, hoisted=True),
    "while.1": _op((), opcode="while", in_while=False,
                   result="(s32[], f32[8,128]{1,0})"),
    "fusion.1": _op(("ffn", "b0_ffn")),
    "fusion.2": _op(("attn.sparse", "b0_mix")),
    "fusion.3": _op(("attn.lightning", "b1_mix", "cache.write")),
    "fusion.4": _op(("head", "output")),
    "fusion.5": _op(("sample",)),
    "add.7": _op(()),
}


def _obs(windows, modules=None):
    ops, mods = [], []
    for i, start in enumerate(windows):
        name = (modules or ["jit_fn(11)"] * len(windows))[i]
        mods.append((name, start * US, (start + 1000) * US))
        ops += [(n, a * US, b * US) for n, a, b in _window(start)]
    hi = max(windows) + 1000
    planes = {"devices": {0: {"modules": mods, "ops": ops}},
              "host": [(trace_reduce.WINDOW_SPAN, 0, hi * US)]}
    return {"trace": trace_reduce.summarize(planes), "notes": {}}


@pytest.fixture
def table(monkeypatch):
    """``table(programs)`` puts a stand-in table under the reader."""
    from deeplearning4j_tpu.optimize import aot_cache

    def put(programs):
        monkeypatch.setattr(aot_cache, "programs", lambda: list(programs),
                            raising=False)
    return put


CTX = SimpleNamespace(config={"serving": {"fused_steps": K}})


def test_one_program_gives_the_numbers(table):
    table([_program("gen_prompt:t64:b1", {}, 900),          # another kind
           _program("decode_step:s64:k4", {}, 2),           # a cold bucket
           _program("decode_step:s128:k4", TABLE, 50)])
    obs = _obs([0, 1000, 2000])
    read = lambda m: scope_time.read(CTX, obs, _params(m))  # noqa: E731
    assert read("ffn_scope_ms_per_step.doc16k") == pytest.approx(0.100)
    assert read("attn_scope_ms_per_step.doc16k") == pytest.approx(0.060)
    assert read("head_scope_ms_per_step.doc16k") == pytest.approx(0.040)
    # the loop's counter and the while's own time, of a window's 1,000 us
    assert read("unnamed_scope_pct.doc16k") == pytest.approx(10.0)
    note = obs["notes"]["device_ms_by_scope"]
    assert (note["kind"], note["runs"], note["dispatches"]) == (
        "decode_step:s128:k4", 3, 50)
    assert note["ms_per_run"] == pytest.approx(1.0)
    assert note["scopes"]["window.prepare"] == pytest.approx(0.1)
    assert note["scopes"]["attn.sparse"] == pytest.approx(0.16)
    assert note["covered_pct"] == pytest.approx(100.0)
    json.dumps(note)


def test_a_run_cut_by_the_window_counts_as_the_part_it_is(table):
    table([_program("decode_step:s128:k4", TABLE, 50)])
    obs = _obs([0, 1000, 2000])
    lo, hi = obs["trace"]["window_ns"]
    planes_window = (lo + 600 * US, hi)          # cuts the first run
    ops, mods = [], []
    for start in (0, 1000, 2000):
        mods.append(("jit_fn(11)", start * US, (start + 1000) * US))
        ops += [(n, a * US, b * US) for n, a, b in _window(start)]
    obs = {"trace": trace_reduce.summarize({
        "devices": {0: {"modules": mods, "ops": ops}},
        "host": [(trace_reduce.WINDOW_SPAN,) + planes_window]}),
        "notes": {}}
    got = scope_time.read(CTX, obs, _params("ffn_scope_ms_per_step.doc16k"))
    # 2 whole runs and 40% of one: its last 400 us hold the end of step 3
    # and step 4, 20 + 100 us of feed-forward of the run's 400 (what a cut
    # run holds is not a run's mix: the error is at most one run's)
    assert got == pytest.approx((800 + 120) / 2.4 / K * 1e-3, rel=1e-6)


@pytest.mark.parametrize("case", [
    "two_fingerprints", "no_table", "another_programs_table", "no_trace",
    "never_dispatched"])
def test_the_reader_returns_none_rather_than_a_polluted_number(
        table, monkeypatch, case):
    obs = _obs([0, 1000, 2000])
    programs = [_program("decode_step:s128:k4", TABLE, 50)]
    if case == "two_fingerprints":      # a prompt program ran in the window
        obs = _obs([0, 1000, 2000],
                   ["jit_fn(11)", "jit_fn(22)", "jit_fn(11)"])
    elif case == "no_table":            # the parent commit's program
        from deeplearning4j_tpu.optimize import aot_cache

        monkeypatch.delattr(aot_cache, "programs", raising=False)
        programs = None
    elif case == "another_programs_table":    # same names, other shapes
        programs = [_program("decode_step:s128:k4", {
            n: op._replace(result="f32[16,128]{1,0}")
            for n, op in TABLE.items()}, 50)]
    elif case == "no_trace":
        obs = {"trace": None, "notes": {}}
    elif case == "never_dispatched":
        programs = [_program("decode_step:s128:k4", TABLE, 0)]
    if programs is not None:
        table(programs)
    for metric in ("ffn_scope_ms_per_step.doc16k",
                   "unnamed_scope_pct.doc16k"):
        assert scope_time.read(CTX, obs, _params(metric)) is None
    assert "device_ms_by_scope" not in obs["notes"]


def test_train_metrics_split_forward_backward_and_updater(table):
    f = "f32[8,128]{1,0}"
    step = [("conv.1", 300), ("conv.2", 500), ("adam.3", 150), ("loss.4", 30),
            ("copy.5", 20)]
    ops, mods, at = [], [], 0.0
    for _run in range(3):
        mods.append(("jit_step(7)", at * US, (at + 1000) * US))
        for name, us in step:
            ops.append((f"%{name} = {f} fusion({f} %x)", at * US,
                        (at + us) * US))
            at += us
    obs = {"trace": trace_reduce.summarize({
        "devices": {0: {"modules": mods, "ops": ops}},
        "host": [(trace_reduce.WINDOW_SPAN, 0, at * US)]}), "notes": {}}
    table([_program("train_step:d012+itc", {
        "conv.1": _op(("res2a_conv",), in_while=False),
        "conv.2": _op(("res2a_conv",), in_while=False, backward=True),
        "adam.3": _op(("updater",), in_while=False),
        "loss.4": _op(("loss",), in_while=False),
        "copy.5": _op((), in_while=False)}, 40)])
    read = lambda m: scope_time.read(CTX, obs, _params(m))  # noqa: E731
    assert read("forward_scope_ms_per_step.train") == pytest.approx(0.330)
    assert read("backward_scope_ms_per_step.train") == pytest.approx(0.500)
    assert read("updater_scope_ms_per_step.train") == pytest.approx(0.150)
    assert read("unnamed_scope_pct.train") == pytest.approx(2.0)
    assert obs["notes"]["device_ms_by_scope"]["scopes"][
        "transpose(res2a_conv)"] == pytest.approx(0.5)


def test_manifest_lists_the_eight_metrics_in_their_two_cells():
    manifest = harness.load_manifest()
    mine = {m["name"]: m for m in manifest["per_layer"]
            if harness.load_json(ROOT, "benchmarks", "metrics",
                                 m["name"] + ".json")["reader"]
            == "scope_time"}
    assert len(mine) == 8
    for name, m in mine.items():
        cell = {"doc16k": "minicpm-sala-serve-doc16k",
                "train": "resnet50-train-b256"}[name.rsplit(".", 1)[1]]
        assert m["workloads"] == [cell] and m["source"] == "device_trace"
