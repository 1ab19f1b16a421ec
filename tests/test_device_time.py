"""The device's time under the program's own names: the compiled text's
``op_name`` metadata read into scopes (``telemetry.device_time
.parse_scopes``), the table of the loaded executables
(``optimize.aot_cache.programs``), and the join of a profiler trace with
that table (``device_time.by_scope``), on a text fixture, on a trace
recorded on the chip (``benchmarks/testdata/tiny_tpu.xplane.pb``) and on
a synthetic trace of two programs that share an operation's name. The
scopes' coverage of the programs compiled for the v5e is in
``tests/test_kv_cache_layout.py``, beside the other ahead-of-time
compiles."""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.optimize import aot_cache
from deeplearning4j_tpu.telemetry import device_time as dt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(ROOT, "benchmarks", "testdata", "tiny_tpu.xplane.pb")

# what a compiler writes, cut to what the parser reads: an entry with a
# plain scope, a backward operation, a convert the compiler made for the
# loop's operand, a fusion without a name of its own, a bare prefetch; a
# loop whose body holds a scoped fusion and the loop's own counter
TEXT = '''HloModule jit_fn, is_scheduled=true

%fused_computation.1 (param_0.1: f32[4,8]) -> f32[4,8] {
  %param_0.1 = f32[4,8]{1,0} parameter(0)
  %mul.2 = f32[4,8]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(fn)/norm/b0_norm1/mul"}
  ROOT %add.3 = f32[4,8]{1,0} add(%mul.2, %param_0.1), metadata={op_name="jit(fn)/residual/b0_res1/add" stack_frame_id=4}
}

%fused_computation.2 (param_0.2: f32[4,8]) -> f32[4,8] {
  %param_0.2 = f32[4,8]{1,0} parameter(0)
  ROOT %tanh.1 = f32[4,8]{1,0} tanh(%param_0.2), metadata={op_name="jit(fn)/while/body/closed_call/ffn/b0_ffn/tanh"}
}

%body.1 (arg.1: (s32[], f32[4,8], bf16[8,8])) -> (s32[], f32[4,8], bf16[8,8]) {
  %arg.1 = (s32[], f32[4,8]{1,0}, bf16[8,8]{1,0}) parameter(0)
  %gte.1 = f32[4,8]{1,0} get-tuple-element(%arg.1), index=1
  %gte.2 = s32[] get-tuple-element(%arg.1), index=0
  %gte.3 = bf16[8,8]{1,0} get-tuple-element(%arg.1), index=2
  %copy-start.1 = (f32[4,8]{1,0:S(1)}, f32[4,8]{1,0}, u32[]) copy-start(%gte.1)
  %copy-done.1 = f32[4,8]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[4,8]{1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fn)/while/body/closed_call/ffn/b0_ffn/tanh"}
  %dot.5 = f32[4,8]{1,0} dot(%fusion.1, %gte.3), metadata={op_name="jit(fn)/while/body/closed_call/attn.mha/b0_attn/cache.write/dot_general"}
  %add.9 = s32[] add(%gte.2, %gte.2), metadata={op_name="jit(fn)/while/body/add"}
  ROOT %tuple.2 = (s32[], f32[4,8]{1,0}, bf16[8,8]{1,0}) tuple(%add.9, %dot.5, %gte.3)
}

%cond.1 (arg.2: (s32[], f32[4,8], bf16[8,8])) -> pred[] {
  %arg.2 = (s32[], f32[4,8]{1,0}, bf16[8,8]{1,0}) parameter(0)
  %gte.4 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %lt.1 = pred[] compare(%gte.4, %gte.4), direction=LT, metadata={op_name="jit(fn)/while/cond/lt"}
}

ENTRY %main.1 (x.1: f32[4,8], w.1: f32[8,8]) -> f32[4,8] {
  %x.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %w.1 = f32[8,8]{1,0} parameter(1), metadata={op_name="w"}
  %exp.1 = f32[4,8]{1,0} exponential(%x.1), metadata={op_name="jit(fn)/embed/embed/exp" stack_frame_id=2}
  %grad.1 = f32[4,8]{1,0} multiply(%exp.1, %x.1), metadata={op_name="jit(fn)/jit(main)/transpose(jvp(res2a_conv))/inner/mul"}
  %fusion.7 = f32[4,8]{1,0} fusion(%grad.1), kind=kLoop, calls=%fused_computation.1
  %convert.4 = bf16[8,8]{1,0} convert(%w.1)
  %hoisted.1 = f32[4,8]{1,0} negate(%fusion.7), metadata={op_name="jit(fn)/while/body/closed_call/ffn/b0_ffn/neg"}
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], f32[4,8]{1,0}, bf16[8,8]{1,0}) tuple(%constant.1, %hoisted.1, %convert.4)
  %while.1 = (s32[], f32[4,8]{1,0}, bf16[8,8]{1,0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(fn)/while"}
  %gte.9 = f32[4,8]{1,0} get-tuple-element(%while.1), index=1
  ROOT %copy.3 = f32[4,8]{1,0} copy(%gte.9)
}
'''


@pytest.fixture(scope="module")
def table():
    return dt.parse_scopes(TEXT)


@pytest.mark.parametrize("name,scope,backward,in_while,hoisted,via,group", [
    # a plain scope: class, then vertex
    ("exp.1", ("embed", "embed"), False, False, False, "", "embed"),
    # the backward pass: transpose(jvp(x)) unwrapped, jit(..) dropped
    ("grad.1", ("res2a_conv", "inner"), True, False, False, "",
     "transpose(res2a_conv)"),
    # a fusion without a name of its own: its root's
    ("fusion.7", ("residual", "b0_res1"), False, False, False, "root",
     "residual"),
    # inside the while's body, as compiled
    ("fusion.1", ("ffn", "b0_ffn"), False, True, False, "", "ffn"),
    ("dot.5", ("attn.mha", "b0_attn", "cache.write"), False, True, False,
     "", "attn.mha"),
    # the compiler's prefetch, bare: the product that waits for it
    ("copy-done.1", ("ffn", "b0_ffn"), False, True, False, "user", "ffn"),
    ("copy-start.1", ("ffn", "b0_ffn"), False, True, False, "user", "ffn"),
    # written in the loop's body, compiled before the loop
    ("hoisted.1", ("ffn", "b0_ffn"), False, False, True, "",
     "window.prepare"),
    # bare, and feeds the loop: the converts before the while
    ("convert.4", ("window.prepare",), False, False, False, "user",
     "window.prepare"),
    # the loop's own counter and test: the window's bookkeeping
    ("add.9", ("window.account",), False, True, False, "loop",
     "window.account"),
    ("lt.1", ("window.account",), False, True, False, "loop",
     "window.account"),
    # the loop itself, what leaves it: no scope
    ("while.1", (), False, False, False, "", "unnamed"),
    ("copy.3", (), False, False, False, "", "unnamed"),
])
def test_scope_map_of_a_compiled_text(table, name, scope, backward, in_while,
                                      hoisted, via, group):
    op = table[name]
    assert (op.scope, op.backward, op.in_while, op.hoisted, op.via) == (
        scope, backward, in_while, hoisted, via)
    assert dt.group_of(op) == group


def test_scope_map_holds_what_runs_and_not_what_is_fused(table):
    assert "mul.2" not in table and "tanh.1" not in table
    assert table["fusion.1"].opcode == "fusion"
    assert table["while.1"].result.startswith("(s32[]")
    assert dt.shapes_of(table["copy-done.1"].result) == "f32[4,8]"
    assert dt.traced_shapes(
        "%copy-done.1 = f32[4,8]{1,0:T(4,128)S(1)} copy-done((f32[4,8], "
        "u32[]) %copy-start.1)") == "f32[4,8]"


def test_a_loop_of_one_trip_was_inlined_not_hoisted():
    """No ``while`` is left in the text: what its body held was not
    hoisted out of anything, and keeps its own scope."""
    text = TEXT[:TEXT.index("%body.1")] + '''ENTRY %main.1 (x.1: f32[4,8]) -> f32[4,8] {
  %x.1 = f32[4,8]{1,0} parameter(0)
  ROOT %neg.1 = f32[4,8]{1,0} negate(%x.1), metadata={op_name="jit(fn)/while/body/closed_call/ffn/b0_ffn/neg"}
}
'''
    op = dt.parse_scopes(text)["neg.1"]
    assert (op.scope, op.hoisted, dt.group_of(op)) == (
        ("ffn", "b0_ffn"), False, "ffn")


# --- the table of the loaded executables -------------------------------------

def _scoped(w, x):
    with jax.named_scope("ffn"), jax.named_scope("b0_ffn"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("head"):
        return jnp.sum(h * h)


def test_programs_lists_kind_and_dispatches_and_survives_clear(monkeypatch):
    aot_cache.clear()
    parsed = []
    real = dt.parse_scopes
    monkeypatch.setattr(dt, "parse_scopes",
                        lambda text: parsed.append(1) or real(text))
    step = aot_cache.wrap(jax.jit(_scoped), "g:test_device_time",
                          "decode_step:s64:k4")
    idle = aot_cache.wrap(jax.jit(lambda x: x + 1), "g:test_device_time",
                          "gen_release:s64")
    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    for _ in range(3):
        step(w, x)
    idle.warm(x)                        # compiled, never dispatched
    rows = {p.kind: p for p in aot_cache.programs()}
    assert rows["decode_step:s64:k4"].dispatches == 3
    assert rows["gen_release:s64"].dispatches == 0
    assert rows["decode_step:s64:k4"].module_name == "jit__scoped"
    assert rows["decode_step:s64:k4"].trace_id is None
    assert not parsed                   # no map until one is asked for
    first = rows["decode_step:s64:k4"].scope_map()
    assert parsed == [1]
    assert {dt.group_of(op) for op in first.values()} >= {"ffn", "head"}

    aot_cache.clear()                   # the executables go, the table stays
    assert aot_cache.stats()["entries"] == 0
    kept = aot_cache.programs()
    assert [p.kind for p in kept] == ["decode_step:s64:k4"]   # dispatched
    assert kept[0].dispatches == 3 and kept[0]._exe is None
    assert kept[0].scope_map().keys() == first.keys()
    aot_cache.clear()                   # the next clear replaces it
    assert aot_cache.programs() == []


# --- a trace and the table ----------------------------------------------------

def _op(scope, **kw):
    base = dict(scope=scope, backward=False, in_while=False, hoisted=False,
                result="bf16[2048,2048]{1,0}", opcode="fusion", via="")
    base.update(kw)
    return dt.Op(**base)


def _program(kind, module, table, dispatches=1, trace_id=None):
    return SimpleNamespace(kind=kind, module_name=module, trace_id=trace_id,
                           dispatches=dispatches, scope_map=lambda: table)


def test_by_scope_on_a_trace_recorded_on_the_chip():
    """Three runs of ``tanh(x @ x) @ x`` (181 us each): the first product
    under ``ffn``, the second under ``head``, the prefetch of ``x`` bare
    in the hand-made table."""
    table = {"convolution_tanh_fusion": _op(("ffn", "b0_ffn")),
             "fusion": _op(("head", "output")),
             "copy-done": _op(("ffn", "b0_ffn"), via="user"),
             "copy-start": _op((), opcode="copy-start", result=(
                 "(bf16[2048,2048]{1,0:S(1)}, bf16[2048,2048]{1,0}, "
                 "u32[]{:S(2)})"))}
    other = _program("gen_prompt:t128:b1", "jit_tiny_prog",
                     {"fusion": _op(("head",), result="bf16[8,8]{1,0}")})
    mine = _program("decode_step:s1024:k4", "jit_tiny_prog", table)
    (p,) = dt.by_scope(TRACE, [other, mine])["programs"]
    assert (p["kind"], p["runs"]) == ("decode_step:s1024:k4", 3)
    assert p["module"].startswith("jit_tiny_prog(")
    assert p["ms_per_run"] == pytest.approx(0.1809, rel=1e-3)
    assert p["device_share_pct"] == pytest.approx(100.0)
    assert p["scopes"]["ffn"] == pytest.approx(0.08995, rel=1e-3)
    assert p["scopes"]["head"] == pytest.approx(0.09094, rel=1e-3)
    # the copy-start carries no scope: listed by name
    assert [r[0] for r in p["unnamed_ops"]] == ["copy-start"]
    assert p["unnamed_pct"] < 0.01
    assert sum(p["scopes"].values()) + p["between_ops_ms"] == pytest.approx(
        p["ms_per_run"])
    assert p["detail"]["ffn/b0_ffn"] == pytest.approx(p["scopes"]["ffn"])
    assert "jit_tiny_prog" in dt.format_table({"programs": [p]})


def _two_programs():
    """Two programs whose operations are both named ``fusion.1`` and
    ``fusion.2``, their runs interleaved; the second program's ``while``
    spans its body."""
    a1 = "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kLoop"
    a2 = "%fusion.2 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %fusion.1)"
    b1 = "%fusion.1 = f32[64,128]{1,0} fusion(f32[64,128]{1,0} %p)"
    bw = "%while.3 = (s32[], f32[64,128]{1,0}) while((s32[]) %t)"
    b2 = "%fusion.2 = f32[64,128]{1,0} fusion(f32[64,128]{1,0} %g)"
    ops, mods = [], []
    for start in (0, 2000):
        mods.append(("jit_fn(11)", start, start + 100))
        ops += [(a1, start, start + 60), (a2, start + 60, start + 100)]
        mods.append(("jit_fn(22)", start + 1000, start + 1400))
        ops += [(b1, start + 1000, start + 1100),
                (bw, start + 1100, start + 1400),
                (b2, start + 1150, start + 1250),
                (b2, start + 1250, start + 1350)]
    return {0: {"modules": mods, "ops": ops}}


def test_by_scope_gives_each_run_its_own_programs_time():
    small = {"fusion.1": _op(("ffn",), result="f32[8,128]{1,0}"),
             "fusion.2": _op(("head",), result="f32[8,128]{1,0}")}
    big = {"fusion.1": _op(("attn.mha",), result="f32[64,128]{1,0}"),
           "fusion.2": _op(("ssm", "b0_mix", "ssm.step"), in_while=True,
                           result="f32[64,128]{1,0}"),
           "while.3": _op((), opcode="while",
                          result="(s32[], f32[64,128]{1,0})")}
    table = [_program("decode_step:s64:k4", "jit_fn", small),
             _program("gen_prompt:t64:b1", "jit_fn", big)]
    got = {p["kind"]: p for p in dt.by_scope(_two_programs(), table)[
        "programs"]}
    step, prompt = got["decode_step:s64:k4"], got["gen_prompt:t64:b1"]
    assert (step["module"], prompt["module"]) == ("jit_fn(11)", "jit_fn(22)")
    assert step["runs"] == prompt["runs"] == 2
    assert step["scopes"] == {"ffn": pytest.approx(60e-6),
                              "head": pytest.approx(40e-6)}
    # the while's own time is what its body does not cover
    assert prompt["scopes"] == {"ssm": pytest.approx(200e-6),
                                "attn.mha": pytest.approx(100e-6),
                                "unnamed": pytest.approx(100e-6)}
    assert prompt["detail"]["ssm/b0_mix/ssm.step"] == pytest.approx(200e-6)
    assert prompt["unnamed_ops"][0][0] == "while.3"
    assert step["device_share_pct"] == pytest.approx(20.0)
    assert prompt["between_ops_ms"] == pytest.approx(0.0, abs=1e-12)


def test_by_scope_joins_by_the_trace_identifier_where_the_table_has_one():
    same = {"fusion.1": _op(("ffn",), result="f32[8,128]{1,0}"),
            "fusion.2": _op(("head",), result="f32[8,128]{1,0}")}
    table = [_program("gen_prompt:t8:b1", "jit_fn", same, trace_id=11),
             _program("gen_prompt:t16:b1", "jit_fn", same, trace_id=33)]
    got = dt.by_scope(_two_programs(), table)["programs"]
    kinds = {p["module"]: p["kind"] for p in got}
    # 22 has no identifier in the table and its shapes match no entry
    assert kinds == {"jit_fn(11)": "gen_prompt:t8:b1", "jit_fn(22)": None}
    # without identifiers two entries hold the run equally well: no guess
    for p in table:
        p.trace_id = None
    got = dt.by_scope(_two_programs(), table)["programs"]
    assert {p["module"]: p["kind"] for p in got} == {
        "jit_fn(11)": None, "jit_fn(22)": None}
    assert got[0]["unnamed_pct"] == pytest.approx(100.0)


def test_by_scope_leaves_out_a_run_the_trace_opened_or_closed_inside_of():
    op = "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kLoop"
    spans = [(0, 20), (100, 200), (300, 400), (500, 600), (700, 800),
             (900, 930)]                    # the first and the last are cut
    trace = {0: {"modules": [("jit_fn(11)", a, b) for a, b in spans],
                 "ops": [(op, a, b) for a, b in spans]}}
    table = [_program("decode_step:s64:k4", "jit_fn",
                      {"fusion.1": _op(("ffn",), result="f32[8,128]{1,0}")})]
    (p,) = dt.by_scope(trace, table)["programs"]
    assert (p["runs"], p["ms_per_run"]) == (4, pytest.approx(100e-6))
    assert p["scopes"] == {"ffn": pytest.approx(100e-6)}
