"""Static-analysis subsystem: program (jaxpr/HLO) + source (AST) linters.

Every rule gets a SEEDED-DEFECT fixture (a minimal program/source sample
carrying exactly the bug the rule exists to catch) plus a negative
control, and the repo itself must come out clean: the source pass over
``deeplearning4j_tpu/`` reports zero unwaived findings, and the
donation audit over real train-step executables reports full aliasing.
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import findings as fmod
from deeplearning4j_tpu.analysis import program, source
from deeplearning4j_tpu.analysis.findings import LOG, Finding, parse_waivers

pytestmark = pytest.mark.analysis


def rules_of(findings, waived=None):
    out = []
    for f in findings:
        if waived is None or f.waived == waived:
            out.append(f.rule)
    return out


# ==========================================================================
# program rules (seeded defects via trace_artifact: no cache-global state)
# ==========================================================================

def test_prg201_undonated_train_step_detected():
    def step(params, opt, x):
        g = x * 2.0
        return params - g, opt + 1.0

    args = (jnp.ones((16,)), jnp.ones((16,)), jnp.ones((16,)))
    art = program.trace_artifact(jax.jit(step), args,
                                 fn_key="train_step:seeded")
    assert "PRG201" in rules_of(program.lint_program(art))


def test_prg201_donated_train_step_clean():
    def step(params, opt, x):
        return params - x, opt + 1.0

    args = (jnp.ones((16,)), jnp.ones((16,)), jnp.ones((16,)))
    art = program.trace_artifact(
        jax.jit(step, donate_argnums=(0, 1)), args,
        fn_key="train_step:seeded")
    assert "PRG201" not in rules_of(program.lint_program(art))


def test_prg201_not_applied_to_inference_kinds():
    art = program.trace_artifact(
        jax.jit(lambda x: x * 2.0), (jnp.ones((4,)),), fn_key="output")
    assert rules_of(program.lint_program(art)) == []


def test_prg201_covers_prefix_kinds():
    """The prefix-cache attach/join executables consume the donated
    decode state: an undonated fixture under either kind trips PRG201, a
    donated one is clean, and the suffix PREFILL (reads shared refcounted
    pages — must NOT donate) stays exempt by construction."""
    def step(state, upd):
        return state + upd

    args = (jnp.ones((16,)), jnp.ones((16,)))
    for kind in ("prefix_attach:s32:t8:b2", "prefix_join:s32:t8:b2"):
        art = program.trace_artifact(jax.jit(step), args, fn_key=kind)
        assert "PRG201" in rules_of(program.lint_program(art)), kind
        art = program.trace_artifact(
            jax.jit(step, donate_argnums=(0,)), args, fn_key=kind)
        assert "PRG201" not in rules_of(program.lint_program(art)), kind
    art = program.trace_artifact(jax.jit(step), args,
                                 fn_key="gen_prompt_sfx:t8:p16:b2")
    assert "PRG201" not in rules_of(program.lint_program(art))


def test_prg202_baked_constant():
    big = np.ones((512, 1024), np.float32)  # 2 MiB closure capture

    def step(x):
        return (jnp.asarray(big) @ x).sum()

    art = program.trace_artifact(jax.jit(step), (jnp.ones((1024,)),),
                                 fn_key="output", compile=False)
    hits = [f for f in program.lint_program(art) if f.rule == "PRG202"]
    assert hits and hits[0].severity == fmod.WARN
    assert "2.0 MiB" in hits[0].message


def test_prg203_f64_promotion_leak():
    with jax.enable_x64(True):
        def step(x):
            return x.astype("float64").sum() * 2.0

        art = program.trace_artifact(
            jax.jit(step), (jnp.ones((8,), "float32"),),
            fn_key="score", compile=False)
        assert "PRG203" in rules_of(program.lint_program(art))


def test_prg203_silent_when_caller_passes_f64():
    with jax.enable_x64(True):
        art = program.trace_artifact(
            jax.jit(lambda x: x.sum()), (jnp.ones((8,), "float64"),),
            fn_key="score", compile=False)
        assert "PRG203" not in rules_of(program.lint_program(art))


def test_prg204_host_callback():
    def step(x):
        y = jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y.sum()

    art = program.trace_artifact(jax.jit(step), (jnp.ones((4,)),),
                                 fn_key="train_step:cb", compile=False)
    hits = [f for f in program.lint_program(art) if f.rule == "PRG204"]
    assert hits and hits[0].severity == fmod.ERROR
    assert "pure_callback" in hits[0].message


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _shard_mapped(body, n_out):
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(body, mesh=_one_device_mesh(), in_specs=P("data"),
                         out_specs=(P("data"),) * n_out)


def test_prg205_zero_step_that_all_reduces():
    def body(g):
        return (jax.lax.psum(g, "data"),)  # dense all-reduce: the defect

    art = program.trace_artifact(
        jax.jit(_shard_mapped(body, 1)), (jnp.ones((4,)),),
        fn_key="pw_zero:n1:b0", compile=False)
    hits = [f for f in program.lint_program(art) if f.rule == "PRG205"]
    assert hits and hits[0].severity == fmod.ERROR
    assert "reduce-scatter" in hits[0].message


def test_prg205_unordered_bucket_chain():
    def body(g):
        a = jax.lax.psum_scatter(g, "data", scatter_dimension=0,
                                 tiled=True)
        b = jax.lax.psum_scatter(g * 2.0, "data", scatter_dimension=0,
                                 tiled=True)
        return a, b  # two buckets, no optimization_barrier pin

    art = program.trace_artifact(
        jax.jit(_shard_mapped(body, 2)), (jnp.ones((4,)),),
        fn_key="pw_zero:n1:b4096", compile=False)
    hits = [f for f in program.lint_program(art) if f.rule == "PRG205"]
    assert hits and hits[0].severity == fmod.WARN
    assert "optimization_barrier" in hits[0].message


def test_prg205_clean_on_real_zero_exchange():
    """The repo's own bucketed exchange (scatter + barrier chain) must
    pass its own audit."""
    from deeplearning4j_tpu.parallel.compression import (
        bucketed_psum_scatter,
    )

    def body(g):
        tree = {"a": g, "b": g * 2.0, "c": g * 3.0}
        out = bucketed_psum_scatter(tree, "data", bucket_bytes=8)
        return out["a"], out["b"], out["c"]

    art = program.trace_artifact(
        jax.jit(_shard_mapped(body, 3)), (jnp.ones((4,)),),
        fn_key="pw_zero:n1:b8", compile=False)
    assert "PRG205" not in rules_of(program.lint_program(art))


def test_prg206_python_scalar_churn():
    from deeplearning4j_tpu.optimize.aot_cache import signature_of

    x = jnp.ones((4,))
    sig_int = signature_of((x, 1))        # python scalar leaf
    args = (x, np.int32(1))

    art = program.trace_artifact(jax.jit(lambda a, b: a + b), args,
                                 fn_key="adhoc", compile=False,
                                 sibling_sigs=(sig_int,))
    hits = [f for f in program.lint_program(art) if f.rule == "PRG206"]
    assert hits and "python scalar" in hits[0].message


def test_prg206_shape_change_is_a_legitimate_miss():
    from deeplearning4j_tpu.optimize.aot_cache import signature_of

    sig_other = signature_of((jnp.ones((8,)), np.int32(1)))
    art = program.trace_artifact(
        jax.jit(lambda a, b: a + b), (jnp.ones((4,)), np.int32(1)),
        fn_key="adhoc", compile=False, sibling_sigs=(sig_other,))
    assert "PRG206" not in rules_of(program.lint_program(art))


def test_prg206_fires_through_the_live_cache():
    """Integration: the aot_cache miss hook reports scalar churn for
    real — two calls differing only in a python-vs-np scalar leaf."""
    from deeplearning4j_tpu.optimize import aot_cache

    LOG.clear()
    step = aot_cache.wrap(jax.jit(lambda a, b: a + b),
                          "prg206-integration", "adhoc")
    x = jnp.ones((3,))
    step(x, np.float32(2.0))
    step(x, 2.0)  # python float: same shapes, churned signature
    # locations carry the first 12 chars of the graph key
    assert any(f.rule == "PRG206" and "prg206-integ" in f.location
               for f in LOG.items())


def test_program_waiver_by_key():
    def step(params, x):
        return params - x

    art = program.trace_artifact(jax.jit(step),
                                 (jnp.ones((4,)), jnp.ones((4,))),
                                 fn_key="train_step:waived-fixture")
    try:
        program.waive_program("PRG201", "waived-fixture",
                              "fixture: donation intentionally absent")
        fs = program.lint_program(art)
    finally:
        program._WAIVERS.clear()
    hits = [f for f in fs if f.rule == "PRG201"]
    assert hits and hits[0].waived
    assert "intentionally absent" in hits[0].waiver_reason


# ==========================================================================
# source rules (seeded-defect fixtures as inline modules)
# ==========================================================================

def lint(src: str, today=None):
    return source.lint_source(textwrap.dedent(src), "fix.py", today=today)


def test_src101_host_sync_fixture():
    fs = lint('''
        import jax
        import numpy as np

        def build():
            def step(params, x):
                a = params["w"].item()
                b = float(x.sum())
                c = np.asarray(x)
                x.block_until_ready()
                return a + b + c.sum()
            return jax.jit(step)
    ''')
    assert rules_of(fs).count("SRC101") == 4
    assert all(f.severity == fmod.ERROR for f in fs)


def test_src101_host_code_not_flagged():
    fs = lint('''
        import numpy as np

        def host_metrics(loss):
            return float(np.asarray(loss))  # never traced: fine
    ''')
    assert "SRC101" not in rules_of(fs)


def test_src101_reaches_through_builder_and_nested_calls():
    """The fixpoint follows the repo idiom: jit(step) where step calls
    raw = self.train_step_fn(...) whose returned inner fn syncs."""
    fs = lint('''
        import jax

        class Net:
            def train_step_fn(self):
                def fn(params, x):
                    return float(x.sum())
                return fn

            def build(self):
                raw = self.train_step_fn()

                def step(params, x):
                    return raw(params, x)

                return jax.jit(step, donate_argnums=(0,))
    ''')
    assert "SRC101" in rules_of(fs)


def test_src102_unlocked_mutation_fixture():
    fs = lint('''
        import threading

        _REG = {}
        _LOCK = threading.Lock()

        def put(k, v):
            with _LOCK:
                _REG[k] = v

        def put_fast(k, v):
            _REG[k] = v  # the defect: same registry, no lock
    ''')
    hits = [f for f in fs if f.rule == "SRC102"]
    assert len(hits) == 1 and "put_fast" in hits[0].message


def test_src102_locked_suffix_and_init_exempt():
    fs = lint('''
        import threading

        class Reg:
            def __init__(self):
                self._m = {}
                self._lock = threading.Lock()
                self._m["boot"] = 1

            def put(self, k, v):
                with self._lock:
                    self._m[k] = v

            def _put_locked(self, k, v):
                self._m[k] = v  # caller holds the lock: exempt
    ''')
    assert "SRC102" not in rules_of(fs)


def test_src103_wallclock_and_rng_fixture():
    fs = lint('''
        import time
        import numpy as np
        import jax

        def build():
            def step(x):
                t = time.time()
                r = np.random.rand(4)
                return x.sum() + t + r.sum()
            return jax.jit(step)
    ''')
    assert rules_of(fs).count("SRC103") == 2


def test_src105_bracketing_fixture():
    fs = lint('''
        from deeplearning4j_tpu import telemetry

        def dispatch(step, batch):
            telemetry.host_gap_close()
            return step(batch)     # no host_gap_open, no fault_point

        def fit(it):
            telemetry.host_gap_reset()
            for b in it:
                dispatch(None, b)  # no host_gap_stop
    ''')
    msgs = " | ".join(f.message for f in fs if f.rule == "SRC105")
    assert rules_of(fs).count("SRC105") == 3
    assert "host_gap_open" in msgs
    assert "host_gap_stop" in msgs
    assert "fault_point" in msgs


def test_src105_clean_when_bracketed():
    fs = lint('''
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.resilience import faults

        def dispatch(step, batch):
            batch = faults.fault_point("train.step", batch)
            telemetry.host_gap_close()
            out = step(batch)
            telemetry.host_gap_open()
            return out

        def fit(it):
            telemetry.host_gap_reset()
            try:
                for b in it:
                    dispatch(None, b)
            finally:
                telemetry.host_gap_stop()
    ''')
    assert "SRC105" not in rules_of(fs)


def test_src106_unused_import_fixture():
    fs = lint('''
        import os
        import json as j
        from typing import List, Optional

        def f(x: Optional[int]):
            return os.sep + str(x)
    ''')
    hits = sorted(f.message for f in fs if f.rule == "SRC106")
    assert len(hits) == 2  # j, List; Optional and os are used
    assert "'List'" in hits[0] and "'j'" in hits[1]


@pytest.mark.obs
def test_src107_unfinished_request_span_fixture():
    """Seeded defect: a request span opened and never finished anywhere
    in the module — the trace leaks and the tail sampler never sees it."""
    fs = lint('''
        from deeplearning4j_tpu.telemetry import tracing

        def submit(x):
            t = tracing.start_trace("predict")
            return x, t
    ''')
    hits = [f for f in fs if f.rule == "SRC107"]
    assert hits and hits[0].severity == fmod.ERROR


@pytest.mark.obs
def test_src107_leaky_raise_warns():
    """The module does finish traces, but a function that both opens a
    span and raises without a finish on its own error edges leaks the
    span on exactly the abnormal path the sampler always keeps."""
    fs = lint('''
        from deeplearning4j_tpu.telemetry import tracing

        def submit(x):
            t = tracing.start_trace("predict")
            if x is None:
                raise ValueError("x required")
            return t

        def retire(t):
            tracing.finish_trace(t, "done")
    ''')
    hits = [f for f in fs if f.rule == "SRC107"]
    assert hits and hits[0].severity == fmod.WARN


@pytest.mark.obs
def test_src107_negative_control_and_xprof_exempt():
    # finish on every edge (the batcher/generation idiom): clean
    fs = lint('''
        from deeplearning4j_tpu.telemetry import tracing

        def submit(x):
            t = tracing.start_trace("predict")
            if x is None:
                tracing.finish_trace(t, "bad_request")
                raise ValueError("x required")
            return t

        def retire(t):
            tracing.finish_trace(t, "done")
    ''')
    assert "SRC107" not in rules_of(fs)
    # jax.profiler.start_trace is the XProf capture API, a different
    # protocol (stop_trace), not a request span: exempt
    fs = lint('''
        import jax

        def capture(path):
            jax.profiler.start_trace(path)
    ''')
    assert "SRC107" not in rules_of(fs)


def test_src106_exemptions():
    fs = lint('''
        from deeplearning4j_tpu.analysis import findings as findings  # re-export
        import fancyplugin  # noqa: F401

        try:
            import optional_backend
        except ImportError:
            optional_backend = None

        __all__ = ["exported"]
        from somewhere import exported
    ''')
    assert "SRC106" not in rules_of(fs)


# ==========================================================================
# waivers
# ==========================================================================

WAIVED_SRC = '''
    import jax

    def build():
        def step(x):
            return float(x.sum())  # dl4j: waive SRC101 %s— fixture accepts
        return jax.jit(step)
'''


def test_waiver_honored():
    fs = lint(WAIVED_SRC % "")
    hits = [f for f in fs if f.rule == "SRC101"]
    assert hits and hits[0].waived
    assert hits[0].waiver_reason == "fixture accepts"
    assert fmod.summarize(fs)["actionable"] == 0


def test_waiver_unexpired_dates_honored():
    fs = lint(WAIVED_SRC % "until=2999-01-01 ", today="2026-08-04")
    assert [f for f in fs if f.rule == "SRC101"][0].waived


def test_waiver_expired_stops_suppressing():
    fs = lint(WAIVED_SRC % "until=2020-01-01 ", today="2026-08-04")
    hits = [f for f in fs if f.rule == "SRC101"]
    assert hits and not hits[0].waived
    assert "waiver expired 2020-01-01" in hits[0].message
    assert fmod.summarize(fs)["actionable"] >= 1


def test_stale_waiver_flagged():
    fs = lint('''
        import os

        def f():
            return os.sep  # dl4j: waive SRC101 — nothing to suppress
    ''')
    hits = [f for f in fs if f.rule == "SRC100"]
    assert len(hits) == 1 and "suppresses nothing" in hits[0].message


def test_waiver_parser():
    ws = parse_waivers("x = 1  # dl4j: waive SRC101,SRC103 "
                       "until=2026-12-31 — two rules at once\n")
    assert ws[0].rules == ("SRC101", "SRC103")
    assert ws[0].until == "2026-12-31"
    assert ws[0].reason == "two rules at once"


# ==========================================================================
# findings log + metric + surfaces
# ==========================================================================

def test_findings_log_feeds_metric_and_snapshot():
    from deeplearning4j_tpu.telemetry import REGISTRY

    LOG.clear()
    LOG.record(Finding(rule="PRG204", severity="ERROR",
                       message="fixture", location="graph=x kind=y"))
    LOG.record(Finding(rule="SRC101", severity="ERROR", message="w",
                       location="a.py:1", waived=True,
                       waiver_reason="ok"))
    snap = LOG.snapshot()
    assert snap["counts"]["PRG204/ERROR"] == 1
    assert snap["counts"]["SRC101/ERROR"] == 1  # waived still listed...
    reg = REGISTRY.snapshot(run_collectors=False)
    key = 'dl4j_analysis_findings_total{rule="PRG204",severity="ERROR"}'
    assert reg[key] >= 1  # ...but only unwaived findings hit the metric
    assert ('dl4j_analysis_findings_total{rule="SRC101"'
            not in " ".join(reg))
    LOG.clear()


def test_analysis_endpoint_on_ui_server():
    import json
    import urllib.request

    from deeplearning4j_tpu.ui.server import UIServer

    LOG.clear()
    LOG.record(Finding(rule="PRG202", severity="WARN",
                       message="fixture const", location="graph=z kind=k"))
    ui = UIServer()
    port = ui.start(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/analysis", timeout=10) as r:
            body = json.loads(r.read())
    finally:
        ui.stop()
        LOG.clear()
    assert body["counts"]["PRG202/WARN"] == 1
    assert body["findings"][0]["rule"] == "PRG202"


# ==========================================================================
# the repo itself is clean
# ==========================================================================

def test_repo_source_tree_is_clean():
    import os

    root = os.path.join(os.path.dirname(__file__), "..",
                        "deeplearning4j_tpu")
    fs = source.lint_paths(os.path.abspath(root))
    actionable = [f for f in fs if not f.waived
                  and fmod.severity_at_least(f.severity, fmod.WARN)]
    assert actionable == [], "\n" + "\n".join(
        f.render() for f in actionable)


def test_repo_train_steps_pass_program_lint_and_donation_audit():
    """Compile-and-fit one MLN and one graph step with the lint hook
    live; their executables must produce zero findings and full
    donation aliasing in the audit."""
    from deeplearning4j_tpu.conf import Activation, InputType
    from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.conf.losses import LossMCXENT
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    rng = np.random.RandomState(3)
    x = rng.randn(8, 5).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
    conf = (NeuralNetConfiguration.builder().seed(3).list()
            .layer(DenseLayer(n_out=17, activation=Activation.TANH))
            .layer(OutputLayer(n_out=3, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(5)).build())
    net = MultiLayerNetwork(conf).init()
    LOG.clear()
    net.fit(x, y, epochs=1)
    gkey = net._graph_key()

    mine = [f for f in LOG.items() if gkey[:12] in f.location]
    assert mine == [], "\n".join(f.render() for f in mine)
    audit = {k: v for k, v in program.donation_audit().items()
             if k[0] == gkey}
    assert audit, "train step never reached the lint hook"
    assert all(v["aliases"] for v in audit.values()), audit


def test_every_cached_train_kind_is_donated_process_wide():
    """The global invariant the satellite demands: by this point in the
    suite every train-kind executable the process compiled (whatever
    test built it) aliases its buffers."""
    bad = {k: v for k, v in program.donation_audit().items()
           if v["aliases"] == 0}
    assert bad == {}, bad
