"""Pallas kernel subsystem tests (kernels/): registry parity against the
XLA references, per-shape autotuner + persistent digest-verified tuning
cache, cache-keyed selection through the model fit paths, PRG207, and
the backend decision (a TPU-keyed envelope never builds an interpreted
kernel).

Every kernel here executes through the Pallas INTERPRETER (tier-1 runs
on the CPU) — the same kernel bodies a TPU run lowers through Mosaic, so
the numerics and the selection/fallback/re-key machinery are validated
end to end; the real lowering is chip_smoke.py's kernel phase.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import kernels
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.activations import Activation
from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.conf.layers_cnn import (
    ConvolutionLayer,
    ConvolutionMode,
    FusedConvBN1x1,
)
from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
from deeplearning4j_tpu.conf.updaters import Adam
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.kernels.registry import (
    AttentionEnvelope,
    MatmulEnvelope,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize import aot_cache

pytestmark = pytest.mark.kernels


@pytest.fixture(autouse=True)
def _fresh_tuning():
    kernels.TUNING.clear()
    yield
    kernels.TUNING.clear()


def _env(m, k, n, dtype="float32", act="identity"):
    return MatmulEnvelope(m=m, k=k, n=n, dtype=dtype,
                          backend="interpret", act=act)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _max_delta(a, b):
    return max(float(np.max(np.abs(x - y))) if x.size else 0.0
               for x, y in zip(_leaves(a), _leaves(b)))


def _conv_dense_conf(use_kernels, width=16, seed=7, compute_dtype=None,
                     act=Activation.RELU):
    b = NeuralNetConfiguration.builder().seed(seed).updater(
        Adam(learning_rate=1e-3))
    if compute_dtype:
        b = b.compute_dtype(compute_dtype)
    if use_kernels:
        b = b.use_kernels()
    return (b.list()
            .layer(FusedConvBN1x1(n_out=8, activation=act))
            .layer(DenseLayer(n_out=width, activation=act))
            .layer(OutputLayer(n_out=4))
            .set_input_type(it.Convolutional(4, 4, 3))
            .build())


def _batch(batch=8, seed=0, classes=4, img=4, chans=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(batch, img, img, chans)).astype(np.float32)
    Y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]
    return X, Y


def _fit(net, X, Y, steps=3):
    for _ in range(steps):
        net.fit_batch(DataSet(X.copy(), Y.copy()))
    return net


# --------------------------------------------------------------------------
# the backend decision
# --------------------------------------------------------------------------

def test_backend_follows_default_backend(monkeypatch):
    assert kernels.backend() == "interpret"     # tier-1 is pinned to cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the only decision: no probe compile, no downgrade to interpret
    assert kernels.backend() == "tpu"
    assert not hasattr(kernels, "capability")


def _kernel_envelopes(backend):
    mm = dict(m=32, k=128, n=128, dtype="float32", backend=backend)
    attn = dict(b=1, h=2, d=16, dtype="float32", backend=backend)
    return [
        ("matmul_bias_act", MatmulEnvelope(act="relu", **mm)),
        ("conv_bn_act", MatmulEnvelope(**mm)),
        ("matmul_bias_act_int8",
         MatmulEnvelope(act="relu", **{**mm, "dtype": "int8"})),
        ("flash_attention",
         AttentionEnvelope(tq=128, tk=128, masked=True, **attn)),
        ("paged_decode_attention", AttentionEnvelope(tq=1, tk=64, **attn)),
    ]


@pytest.mark.parametrize("backend", ["tpu", "interpret"])
def test_envelope_backend_decides_interpret(backend):
    """A TPU-keyed envelope is never built with ``interpret=True`` (and
    an interpret-keyed one always is) — for every registry kernel,
    forward and, where there is one, the custom-VJP backward."""
    from deeplearning4j_tpu.analysis import program

    envs = _kernel_envelopes(backend)
    assert sorted(kid for kid, _ in envs) == kernels.REGISTRY.ids()
    for kid, env in envs:
        k = kernels.REGISTRY.get(kid)
        fn = k.build(env, k.candidates(env, limit=1)[0])
        args = k.make_inputs(env)
        flags = program.pallas_interpret_flags(fn, *args)
        if kid == "flash_attention":
            flags += program.pallas_interpret_flags(
                jax.grad(lambda q, *r: jnp.sum(fn(q, *r))), *args)
        assert flags and set(flags) == {backend != "tpu"}, (kid, flags)


def test_tpu_candidates_obey_mosaic_block_rule():
    """TPU-keyed matmul sweeps only propose blocks Mosaic lowers (last
    two dims multiples of (8, 128) or the whole dim); the interpreter
    keeps every exact divisor."""
    from deeplearning4j_tpu.kernels import impls

    k = kernels.REGISTRY.get("matmul_bias_act")
    tpu = MatmulEnvelope(m=64, k=256, n=256, dtype="float32",
                         backend="tpu")
    cpu = MatmulEnvelope(m=64, k=256, n=256, dtype="float32",
                         backend="interpret")
    tc, cc = k.candidates(tpu), k.candidates(cpu)
    assert tc and set(tc) < set(cc)
    assert all(impls.mosaic_block_ok(64, 256, 256, t) for t in tc)
    assert (64, 64, 64) in cc and (64, 64, 64) not in tc
    assert not k.tiling_ok(tpu, (64, 64, 64))    # nor as a cached winner
    assert k.tiling_ok(cpu, (64, 64, 64))


# --------------------------------------------------------------------------
# numerical parity: every registry kernel vs its XLA reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("act", ["identity", "relu", "tanh"])
def test_matmul_bias_act_parity_f32(act):
    env = _env(32, 24, 16, act=act)
    k = kernels.REGISTRY.get("matmul_bias_act")
    assert k.supports(env)
    args = k.make_inputs(env, seed=3)
    ref = np.asarray(k.reference(env)(*args))
    for tiling in k.candidates(env, limit=4):
        got = np.asarray(k.build(env, tiling)(*args))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_matmul_bias_act_parity_bf16():
    env = _env(16, 32, 8, dtype="bfloat16", act="relu")
    k = kernels.REGISTRY.get("matmul_bias_act")
    args = k.make_inputs(env, seed=4)
    ref = np.asarray(k.reference(env)(*args), np.float32)
    got = np.asarray(k.build(env, k.candidates(env, limit=1)[0])(*args),
                     np.float32)
    # bf16 storage: the kernel accumulates f32 and rounds once, the
    # reference rounds per-op — agreement to bf16 resolution
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.1)


def test_matmul_stats_parity():
    env = _env(64, 16, 8)
    k = kernels.REGISTRY.get("conv_bn_act")
    args = k.make_inputs(env, seed=5)
    ry, rs, rq = (np.asarray(a) for a in k.reference(env)(*args))
    for tiling in k.candidates(env, limit=4):
        y, s, q = (np.asarray(a)
                   for a in k.build(env, tiling)(*args))
        np.testing.assert_allclose(y, ry, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(s, rs, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(q, rq, rtol=1e-4, atol=1e-3)


def test_kernel_gradients_match_reference():
    env = _env(16, 8, 8, act="tanh")
    k = kernels.REGISTRY.get("matmul_bias_act")
    tiling = k.candidates(env, limit=1)[0]
    x, w, b = k.make_inputs(env, seed=6)

    def loss_k(x, w, b):
        return jnp.sum(k.build(env, tiling)(x, w, b) ** 2)

    def loss_r(x, w, b):
        return jnp.sum(k.reference(env)(x, w, b) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# autotuner + tuning cache
# --------------------------------------------------------------------------

def test_autotune_records_winner_and_counters():
    from deeplearning4j_tpu import telemetry

    telemetry.reset()
    env = _env(32, 16, 8, act="relu")
    k = kernels.REGISTRY.get("matmul_bias_act")
    res = kernels.autotune(k, env, max_candidates=4)
    assert res.tiling in [tuple(t) for t in k.candidates(env, limit=4)]
    win = kernels.TUNING.winner("matmul_bias_act", env.key)
    assert tuple(win["tiling"]) == res.tiling
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    trials = snap.get(
        'dl4j_kernel_autotune_trials_total{kernel="matmul_bias_act"}', 0)
    assert trials >= len([r for r in res.trials])
    assert snap.get(
        'dl4j_kernel_autotune_winners_total{kernel="matmul_bias_act"}',
        0) >= 1
    assert snap.get("dl4j_kernel_tuning_cache_entries", 0) >= 1


def test_autotune_keeps_refused_candidates_with_their_message():
    """A candidate the compiler rejects does not end the sweep, and is
    not silent: it stays in the result with the error (what
    chip_smoke.py prints per kernel)."""
    env = _env(32, 16, 8)
    real = kernels.REGISTRY.get("matmul_bias_act")
    bad = real.candidates(env, limit=2)[0]

    class Refusing(type(real)):
        def build(self, env, tiling):
            if tuple(tiling) == tuple(bad):
                def fn(*args):
                    raise ValueError("Mosaic says no")
                return fn
            return super().build(env, tiling)

    res = kernels.autotune(Refusing(), env, max_candidates=2, record=False)
    assert [tuple(r["tiling"]) for r in res.refused] == [tuple(bad)]
    assert "Mosaic says no" in res.refused[0]["error"]
    assert res.tiling != tuple(bad)


def test_tuning_digest_tracks_winner_set():
    d0 = kernels.tuning_digest("matmul_bias_act")
    env = _env(32, 16, 8)
    kernels.TUNING.record("matmul_bias_act", env.key, (8, 8, 8), 1.0)
    d1 = kernels.tuning_digest("matmul_bias_act")
    assert d0 != d1
    # a DIFFERENT winner for the same envelope re-digests again
    kernels.TUNING.record("matmul_bias_act", env.key, (16, 8, 8), 0.9)
    assert kernels.tuning_digest("matmul_bias_act") not in (d0, d1)


def test_winner_persists_on_disk_and_reloads(tmp_path):
    path = str(tmp_path / "tuning.json")
    kernels.set_tuning_cache(path)
    env = _env(32, 16, 8, act="relu")
    k = kernels.REGISTRY.get("matmul_bias_act")
    res = kernels.autotune(k, env, max_candidates=4)
    # a FRESH cache object (a new process's view) loads the same winner
    fresh = kernels.TuningCache().bind(path)
    assert tuple(fresh.winner("matmul_bias_act",
                              env.key)["tiling"]) == res.tiling
    # and a fresh registry over it derives the same digest -> the same
    # kern:<id>:<digest> key tokens -> warmed executables stay valid
    from deeplearning4j_tpu.kernels.registry import KernelRegistry

    r2 = KernelRegistry(cache=fresh)
    for kern in (kernels.registry.MatmulBiasActKernel(),
                 kernels.registry.ConvBnActKernel()):
        r2.register(kern)
    assert r2.tuning_digest("matmul_bias_act") == \
        kernels.tuning_digest("matmul_bias_act")


@pytest.mark.slow
def test_winner_persists_across_real_processes(tmp_path):
    path = str(tmp_path / "tuning.json")
    kernels.set_tuning_cache(path)
    env = _env(32, 16, 8, act="relu")
    kernels.autotune(kernels.REGISTRY.get("matmul_bias_act"), env,
                     max_candidates=4)
    digest = kernels.tuning_digest("matmul_bias_act")
    code = (
        "import json\n"
        "from deeplearning4j_tpu import kernels\n"
        f"kernels.set_tuning_cache({path!r})\n"
        f"w = kernels.TUNING.winner('matmul_bias_act', {env.key!r})\n"
        "print(json.dumps({'tiling': w['tiling'], "
        "'digest': kernels.tuning_digest('matmul_bias_act')}))\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, check=True,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:"
                              "/bin:/usr/local/bin"}, cwd="/root/repo")
    blob = json.loads(out.stdout.strip().splitlines()[-1])
    assert tuple(blob["tiling"]) == tuple(
        kernels.TUNING.winner("matmul_bias_act", env.key)["tiling"])
    assert blob["digest"] == digest


def test_tuning_cache_corruption_named_error_and_fallback(tmp_path):
    path = str(tmp_path / "tuning.json")
    kernels.set_tuning_cache(path)
    env = _env(32, 16, 8, act="relu")
    kernels.autotune(kernels.REGISTRY.get("matmul_bias_act"), env,
                     max_candidates=2)
    # tamper with the published winners: the recorded digest no longer
    # matches the content
    blob = json.loads(open(path).read())
    blob["winners"]["matmul_bias_act"][env.key]["tiling"] = [99, 99, 99]
    open(path, "w").write(json.dumps(blob))
    kernels.TUNING.clear()
    with pytest.raises(kernels.TuningCacheCorruptError) as ei:
        kernels.set_tuning_cache(path)
    assert "digest mismatch" in str(ei.value)
    # fallback: the cache refused the file entirely -> selection is
    # stock XLA (None), and a use_kernels net still trains
    assert kernels.REGISTRY.select("matmul_bias_act", env) is None
    net = MultiLayerNetwork(_conv_dense_conf(True, width=17)).init()
    X, Y = _batch()
    _fit(net, X, Y, steps=1)
    # unreadable garbage is refused with the same named error
    open(path, "w").write("{not json")
    with pytest.raises(kernels.TuningCacheCorruptError):
        kernels.set_tuning_cache(path)


def test_select_refuses_illegal_hand_edited_winner():
    env = _env(32, 16, 8)
    # a "winner" that does not divide the problem (hand-edited cache)
    kernels.TUNING.record("matmul_bias_act", env.key, (24, 7, 5), 1.0)
    assert kernels.REGISTRY.select("matmul_bias_act", env) is None


# --------------------------------------------------------------------------
# model wiring: off-by-default, parity, fallback, re-keying
# --------------------------------------------------------------------------

def test_use_kernels_off_by_default_bitwise():
    conf_default = _conv_dense_conf(False, width=18)
    assert conf_default.use_kernels is False
    net_a = MultiLayerNetwork(conf_default).init()
    net_b = MultiLayerNetwork(_conv_dense_conf(False, width=18)).init()
    assert net_a._ktag() == ""
    X, Y = _batch(seed=1)
    _fit(net_a, X, Y)
    _fit(net_b, X, Y)
    for a, b in zip(_leaves(net_a.params), _leaves(net_b.params)):
        assert np.array_equal(a, b)


def test_use_kernels_untuned_is_bitwise_stock_xla():
    """use_kernels=True with an EMPTY tuning cache routes nothing: the
    trace is the stock trace, pinned bitwise against the off net."""
    net_off = MultiLayerNetwork(_conv_dense_conf(False, width=19)).init()
    net_on = MultiLayerNetwork(_conv_dense_conf(True, width=19)).init()
    X, Y = _batch(seed=2)
    _fit(net_off, X, Y)
    _fit(net_on, X, Y)
    for a, b in zip(_leaves(net_off.params), _leaves(net_on.params)):
        assert np.array_equal(a, b)
    for a, b in zip(_leaves(net_off.opt_state),
                    _leaves(net_on.opt_state)):
        assert np.array_equal(a, b)


def test_kernel_path_training_parity_f32():
    """The acceptance pin: kernel-path training on a conv net tracks
    the stock-XLA path numerically (interpret mode on CPU)."""
    batch = 8
    conf_on = _conv_dense_conf(True, width=20)
    kernels.autotune_model(conf_on, batch, max_candidates=4)
    net_on = MultiLayerNetwork(conf_on).init()
    net_off = MultiLayerNetwork(_conv_dense_conf(False, width=20)).init()
    X, Y = _batch(batch, seed=3)
    _fit(net_on, X, Y, steps=4)
    _fit(net_off, X, Y, steps=4)
    assert _max_delta(net_on.params, net_off.params) < 1e-4
    assert _max_delta(net_on.state, net_off.state) < 1e-4
    # output parity (the routed dense rides eval too)
    yo = np.asarray(net_on.output(X))
    yr = np.asarray(net_off.output(X))
    np.testing.assert_allclose(yo, yr, rtol=1e-4, atol=1e-4)


def test_kernel_path_training_parity_bf16_storage():
    batch = 8
    conf_on = _conv_dense_conf(True, width=21, compute_dtype="bfloat16")
    kernels.autotune_model(conf_on, batch, max_candidates=2)
    net_on = MultiLayerNetwork(conf_on).init()
    net_off = MultiLayerNetwork(
        _conv_dense_conf(False, width=21, compute_dtype="bfloat16")).init()
    X, Y = _batch(batch, seed=4)
    _fit(net_on, X, Y, steps=3)
    _fit(net_off, X, Y, steps=3)
    # bf16 compute: per-op rounding differs between the fused epilogue
    # and the stock pass; f32 masters keep the drift at bf16 resolution
    assert _max_delta(net_on.params, net_off.params) < 0.05


def test_fallback_on_untuned_shape_zero_recompile_churn():
    batch = 8
    conf = _conv_dense_conf(True, width=22)
    kernels.autotune_model(conf, batch, max_candidates=2)
    net = MultiLayerNetwork(conf).init()
    X, Y = _batch(batch, seed=5)
    _fit(net, X, Y, steps=1)
    # an UNTUNED batch size: every routed layer falls back to stock XLA
    X6, Y6 = _batch(6, seed=6)
    _fit(net, X6, Y6, steps=1)
    m0 = aot_cache.stats()["misses"]
    _fit(net, X6, Y6, steps=2)
    _fit(net, X, Y, steps=2)
    assert aot_cache.stats()["misses"] == m0, \
        "fallback shapes must not churn recompiles"


def test_retune_mints_new_executable():
    batch = 8
    conf = _conv_dense_conf(True, width=23)
    kernels.autotune_model(conf, batch, max_candidates=2)
    net = MultiLayerNetwork(conf).init()
    X, Y = _batch(batch, seed=7)
    _fit(net, X, Y, steps=2)
    tag0 = net._ktag()
    assert "kern:matmul_bias_act:" in tag0
    assert "kern:conv_bn_act:" in tag0
    m0 = aot_cache.stats()["misses"]
    _fit(net, X, Y, steps=1)
    assert aot_cache.stats()["misses"] == m0  # warmed
    # retune: force a different winner for the dense envelope
    envs = dict(kernels.plan_envelopes(conf, batch))
    env = envs["matmul_bias_act"]
    cur = tuple(kernels.TUNING.winner("matmul_bias_act",
                                      env.key)["tiling"])
    alt = next(t for t in kernels.REGISTRY.get(
        "matmul_bias_act").candidates(env) if t != cur)
    kernels.TUNING.record("matmul_bias_act", env.key, alt, 0.0)
    assert net._ktag() != tag0
    _fit(net, X, Y, steps=1)
    assert aot_cache.stats()["misses"] > m0, \
        "a retuned kernel must be a NEW executable"


def test_conv1x1_layer_routes():
    b = NeuralNetConfiguration.builder().seed(11).updater(
        Adam(learning_rate=1e-3)).use_kernels()
    conf = (b.list()
            .layer(ConvolutionLayer(
                n_out=8, kernel_size=(1, 1), stride=(1, 1),
                convolution_mode=ConvolutionMode.SAME,
                activation=Activation.RELU))
            .layer(OutputLayer(n_out=4))
            .set_input_type(it.Convolutional(4, 4, 3))
            .build())
    batch = 8
    planned = kernels.plan_envelopes(conf, batch)
    assert any(kid == "matmul_bias_act" and e.m == batch * 16
               for kid, e in planned)
    kernels.autotune_model(conf, batch, max_candidates=2)
    from deeplearning4j_tpu import telemetry

    telemetry.reset()
    net = MultiLayerNetwork(conf).init()
    X, Y = _batch(batch, seed=8)
    _fit(net, X, Y, steps=2)
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    assert any(k.startswith('dl4j_kernel_selected_total{'
                            'kernel="matmul_bias_act"') for k in snap), snap
    # parity vs the stock conv
    off = (NeuralNetConfiguration.builder().seed(11).updater(
        Adam(learning_rate=1e-3)).list()
        .layer(ConvolutionLayer(
            n_out=8, kernel_size=(1, 1), stride=(1, 1),
            convolution_mode=ConvolutionMode.SAME,
            activation=Activation.RELU))
        .layer(OutputLayer(n_out=4))
        .set_input_type(it.Convolutional(4, 4, 3))
        .build())
    net_off = MultiLayerNetwork(off).init()
    _fit(net_off, X, Y, steps=2)
    assert _max_delta(net.params, net_off.params) < 1e-4


def test_conv1x1_strided_dropout_parity():
    """Regression (review finding): the routed 1x1 conv must draw its
    dropout mask over the FULL input before the stride subsample, like
    the stock forward — a post-slice draw is a different stream for the
    same rng and the on/off paths diverge by far more than kernel
    rounding."""
    def conf(use_k):
        b = NeuralNetConfiguration.builder().seed(17).updater(
            Adam(learning_rate=1e-3))
        if use_k:
            b = b.use_kernels()
        return (b.list()
                .layer(ConvolutionLayer(
                    n_out=8, kernel_size=(1, 1), stride=(2, 2),
                    convolution_mode=ConvolutionMode.SAME,
                    activation=Activation.RELU, dropout=0.5))
                .layer(OutputLayer(n_out=4))
                .set_input_type(it.Convolutional(6, 6, 3))
                .build())

    batch = 8
    kernels.autotune_model(conf(True), batch, max_candidates=2)
    net_on = MultiLayerNetwork(conf(True)).init()
    net_off = MultiLayerNetwork(conf(False)).init()
    X, Y = _batch(batch, seed=12, img=6)
    _fit(net_on, X, Y, steps=2)
    _fit(net_off, X, Y, steps=2)
    # same seed -> same full-shape bernoulli stream on both paths
    assert _max_delta(net_on.params, net_off.params) < 1e-4


def test_graph_vertex_routes_and_parity():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def build(use_k):
        b = NeuralNetConfiguration.builder().seed(13).updater(
            Adam(learning_rate=1e-3))
        if use_k:
            b = b.use_kernels()
        gb = (b.graph_builder()
              .add_inputs("in")
              .set_input_types(it.FeedForward(12))
              .add_layer("d1", DenseLayer(n_out=24,
                                          activation=Activation.RELU),
                         "in")
              .add_layer("out", OutputLayer(n_out=3), "d1")
              .set_outputs("out"))
        return gb.build()

    conf_on = build(True)
    env = _env(8, 12, 24, act="relu")
    kernels.autotune(kernels.REGISTRY.get("matmul_bias_act"), env,
                     max_candidates=2)
    g_on = ComputationGraph(conf_on).init()
    g_off = ComputationGraph(build(False)).init()
    rng = np.random.default_rng(9)
    X = rng.normal(size=(8, 12)).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    for _ in range(3):
        g_on.fit_batch(DataSet(X.copy(), Y.copy()))
        g_off.fit_batch(DataSet(X.copy(), Y.copy()))
    assert _max_delta(g_on.params, g_off.params) < 1e-4
    assert "kern:" in g_on._ktag()


# --------------------------------------------------------------------------
# attention kernels: flash prefill + paged decode
# --------------------------------------------------------------------------

def _attn_env(b=2, h=2, tq=16, tk=16, d=8, dtype="float32", causal=True,
              masked=False):
    return AttentionEnvelope(b=b, h=h, tq=tq, tk=tk, d=d, dtype=dtype,
                             backend="interpret", causal=causal,
                             masked=masked)


@pytest.mark.parametrize("causal,masked", [(True, False), (False, False),
                                           (True, True)])
def test_flash_attention_parity_f32(causal, masked):
    env = _attn_env(causal=causal, masked=masked)
    k = kernels.REGISTRY.get("flash_attention")
    assert k.supports(env)
    args = k.make_inputs(env, seed=3)
    ref = np.asarray(k.reference(env)(*args))
    for tiling in k.candidates(env, limit=4):
        got = np.asarray(k.build(env, tiling)(*args))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_flash_attention_parity_bf16():
    env = _attn_env(dtype="bfloat16")
    k = kernels.REGISTRY.get("flash_attention")
    args = k.make_inputs(env, seed=4)
    ref = np.asarray(k.reference(env)(*args), np.float32)
    got = np.asarray(k.build(env, k.candidates(env, limit=1)[0])(*args),
                     np.float32)
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.1)


def test_flash_attention_tall_query_parity():
    """Tq != Tk (the prefill_suffix join shape): the kernel's single
    off = Tk - Tq causal rule must match the reference exactly."""
    env = _attn_env(tq=8, tk=24, masked=True)
    k = kernels.REGISTRY.get("flash_attention")
    args = k.make_inputs(env, seed=5)
    ref = np.asarray(k.reference(env)(*args))
    got = np.asarray(k.build(env, k.candidates(env, limit=1)[0])(*args))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_flash_gradient_parity():
    """The custom-VJP backward (blockwise recompute from the saved
    row-max/row-sum stats) tracks the reference gradients — the pin the
    train-path routing rests on."""
    env = _attn_env(tq=16, tk=16)
    k = kernels.REGISTRY.get("flash_attention")
    tiling = k.candidates(env, limit=1)[0]
    q, kk, v = k.make_inputs(env, seed=6)

    def loss(fn):
        return lambda q, kk, v: jnp.sum(fn(q, kk, v) ** 2)

    gk = jax.grad(loss(k.build(env, tiling)), argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss(k.reference(env)), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_paged_decode_parity_f32_per_candidate():
    env = _attn_env(b=4, tq=1, tk=32)
    k = kernels.REGISTRY.get("paged_decode_attention")
    assert k.supports(env)
    args = k.make_inputs(env, seed=7)
    ref = np.asarray(k.reference(env)(*args))
    cands = [tuple(t) for t in k.candidates(env)]
    assert len(cands) >= 2  # 32 admits at least pages 32, 16, 8
    for tiling in cands:
        got = np.asarray(k.build(env, tiling)(*args))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_paged_decode_ragged_occupancy_parity():
    """Per-row positions at every occupancy extreme — empty-but-one,
    page-boundary, mid-page, full cache — match the masked full-cache
    read for every legal page size."""
    env = _attn_env(b=5, tq=1, tk=32)
    k = kernels.REGISTRY.get("paged_decode_attention")
    q, kc, vc, _ = k.make_inputs(env, seed=8)
    pos = jnp.asarray([0, 7, 8, 21, 31], jnp.int32)
    ref = np.asarray(k.reference(env)(q, kc, vc, pos))
    for tiling in k.candidates(env):
        got = np.asarray(k.build(env, tiling)(q, kc, vc, pos))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_paged_decode_parity_bf16():
    env = _attn_env(b=2, tq=1, tk=16, dtype="bfloat16")
    k = kernels.REGISTRY.get("paged_decode_attention")
    args = k.make_inputs(env, seed=9)
    ref = np.asarray(k.reference(env)(*args), np.float32)
    got = np.asarray(k.build(env, k.candidates(env, limit=1)[0])(*args),
                     np.float32)
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.1)


def test_attention_routing_untuned_is_stock():
    """Empty tuning cache: the attention entry point declines (None)
    — the caller runs stock XLA, zero behavior change."""
    env = _attn_env()
    k = kernels.REGISTRY.get("flash_attention")
    q, kk, v = k.make_inputs(env, seed=10)
    assert kernels.maybe_flash_attention(q, kk, v, causal=True) is None


def test_attention_routing_tuned_selects_and_records():
    from deeplearning4j_tpu import telemetry

    telemetry.reset()
    env = _attn_env()
    k = kernels.REGISTRY.get("flash_attention")
    kernels.autotune(k, env, max_candidates=2, trials=1)
    q, kk, v = k.make_inputs(env, seed=11)
    out = kernels.maybe_flash_attention(q, kk, v, causal=True)
    assert out is not None
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(k.reference(env)(q, kk, v)),
                               rtol=1e-5, atol=1e-5)
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    assert any(k_.startswith('dl4j_kernel_selected_total{'
                             'kernel="flash_attention"') for k_ in snap)


def _attn_net(use_k, seed=11):
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    return TransformerEncoder(num_classes=3, embed_dim=16, n_heads=2,
                              n_layers=1, max_len=8, seed=seed,
                              use_kernels=use_k).init()


def test_self_attention_layer_train_parity():
    """The train-fit acceptance pin: a transformer classifier with the
    routed flash kernel tracks the stock path through eval AND through
    optimizer steps (forward + custom-VJP backward in the real loss)."""
    stock = _attn_net(False)
    kern = _attn_net(True)
    for kid, env in kernels.plan_envelopes(kern.conf, 4):
        k = kernels.REGISTRY.get(kid)
        if k and k.supports(env):
            kernels.autotune(k, env, max_candidates=1, trials=1)
    assert "kern:flash_attention:" in kern._ktag()
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(4, 8, 16)), np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    np.testing.assert_allclose(np.asarray(kern.output(x)),
                               np.asarray(stock.output(x)),
                               rtol=1e-5, atol=1e-5)
    stock.fit(x, y, epochs=3)
    kern.fit(x, y, epochs=3)
    assert _max_delta(stock.params, kern.params) < 1e-3


def test_self_attention_untuned_is_bitwise_stock():
    stock = _attn_net(False, seed=12)
    kern = _attn_net(True, seed=12)
    rng = np.random.default_rng(1)
    x = np.asarray(rng.normal(size=(4, 8, 16)), np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    stock.fit(x, y, epochs=2)
    kern.fit(x, y, epochs=2)
    for a, b in zip(_leaves(stock.params), _leaves(kern.params)):
        assert np.array_equal(a, b)


@pytest.mark.slow
def test_flash_autotune_full_sweep():
    """The heaviest tuning leg: the full (block_q, block_k) candidate
    space at a shape big enough to split blocks, through the interpreter.
    Slow-marked; tier-1 covers the limited sweeps above."""
    env = _attn_env(b=1, h=1, tq=256, tk=256, d=8)
    k = kernels.REGISTRY.get("flash_attention")
    cands = [tuple(t) for t in k.candidates(env)]
    assert len(cands) >= 2
    res = kernels.autotune(k, env, trials=1)
    assert tuple(res.tiling) in cands
    sel = kernels.REGISTRY.select("flash_attention", env)
    assert sel is not None and tuple(sel.tiling) == tuple(res.tiling)


def test_cache_tag_memoized_against_epoch():
    """cache_tag() is a per-dispatch hot path: repeated calls must hit
    the (epoch, ids) memo — same object, no re-digest — and a tuning
    mutation must bump the epoch and re-mint."""
    t0 = kernels.REGISTRY.cache_tag()
    assert kernels.REGISTRY.cache_tag() is t0
    env = _attn_env()
    kernels.TUNING.record("flash_attention", env.key, (128, 128), 1.0)
    t1 = kernels.REGISTRY.cache_tag()
    assert t1 != t0
    assert kernels.REGISTRY.cache_tag() is t1


# --------------------------------------------------------------------------
# program-linter integration: PRG207 + the donation audit
# --------------------------------------------------------------------------

def test_prg207_seeded_defects_and_negative_control():
    from deeplearning4j_tpu.analysis import program

    fn = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,))
    # unknown kernel id -> ERROR
    art = program.trace_artifact(fn, (x,),
                                 fn_key="output:kern:nope:deadbeef")
    rules = [(f.rule, f.severity) for f in program.lint_program(art)]
    assert ("PRG207", "ERROR") in rules
    # stale digest -> ERROR naming the mismatch
    art = program.trace_artifact(
        fn, (x,), fn_key="output:kern:matmul_bias_act:00000000")
    finds = [f for f in program.lint_program(art) if f.rule == "PRG207"]
    assert finds and finds[0].severity == "ERROR"
    assert "mismatches" in finds[0].message
    # negative control: the CURRENT digest audits clean
    d = kernels.tuning_digest("matmul_bias_act")
    art = program.trace_artifact(
        fn, (x,), fn_key=f"output:kern:matmul_bias_act:{d}")
    assert not [f for f in program.lint_program(art)
                if f.rule == "PRG207"]
    # no tokens: the rule stays silent
    art = program.trace_artifact(fn, (x,), fn_key="output")
    assert not [f for f in program.lint_program(art)
                if f.rule == "PRG207"]


def test_prg207_attention_step_kinds_seeded_and_clean():
    """PRG207 over the serving step kinds the attention kernels key:
    a decode_step key with a stale flash digest is an ERROR, an unknown
    paged id is an ERROR, and keys carrying the CURRENT digests audit
    clean. PRG201 classification: every kernel-bearing decode/prefill
    kind stays a train kind (the token is a suffix)."""
    from deeplearning4j_tpu.analysis import program

    fn = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,))
    art = program.trace_artifact(
        fn, (x,), fn_key="decode_step:s16:k1:kern:flash_attention:00000000")
    finds = [f for f in program.lint_program(art) if f.rule == "PRG207"]
    assert finds and finds[0].severity == "ERROR"
    assert "mismatches" in finds[0].message
    art = program.trace_artifact(
        fn, (x,), fn_key="prefill_join:s16:t8:b2:kern:paged_decode:bad00bad")
    rules = [(f.rule, f.severity) for f in program.lint_program(art)]
    assert ("PRG207", "ERROR") in rules
    # negative control: current digests on an attention-bearing key
    df = kernels.tuning_digest("flash_attention")
    dp = kernels.tuning_digest("paged_decode_attention")
    key = (f"decode_step:s16:k2:kern:flash_attention:{df}"
           f":kern:paged_decode_attention:{dp}")
    art = program.trace_artifact(fn, (x,), fn_key=key)
    assert not [f for f in program.lint_program(art)
                if f.rule == "PRG207"]
    for kind in ("decode_step", "prefill", "prefix_join"):
        assert (f"{kind}:s16:kern:flash_attention:{df}").startswith(
            program.TRAIN_KIND_PREFIXES)


def test_kernel_bearing_step_donates_and_audits_clean():
    from deeplearning4j_tpu.analysis import program

    batch = 8
    conf = _conv_dense_conf(True, width=24)
    kernels.autotune_model(conf, batch, max_candidates=2)
    net = MultiLayerNetwork(conf).init()
    X, Y = _batch(batch, seed=10)
    _fit(net, X, Y, steps=1)
    audit = program.donation_audit()
    mine = {k: v for k, v in audit.items()
            if k[0] == net._graph_key() and "kern:" in k[1]}
    assert mine, f"no kernel-bearing train compile audited: {audit.keys()}"
    for key, rec in mine.items():
        assert rec["aliases"] is None or rec["aliases"] > 0, \
            f"kernel-bearing step {key} lost donation"
        assert rec["findings"] == 0, \
            f"kernel-bearing step {key} has lint findings"


# --------------------------------------------------------------------------
# telemetry + UI surface
# --------------------------------------------------------------------------

def test_kernel_telemetry_and_ui_panel():
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.ui.server import UIServer

    telemetry.reset()
    batch = 8
    conf = _conv_dense_conf(True, width=25)
    kernels.autotune_model(conf, batch, max_candidates=2)
    net = MultiLayerNetwork(conf).init()
    X, Y = _batch(batch, seed=11)
    _fit(net, X, Y, steps=1)
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    selected = [k for k in snap
                if k.startswith("dl4j_kernel_selected_total")]
    assert selected, snap
    assert any('shape_bucket="' in k for k in selected)
    assert snap.get("dl4j_kernel_tuning_cache_entries", 0) >= 2
    ui = UIServer()
    html = ui.render_html()
    assert "Kernels (autotuner)" in html
    assert "dl4j_kernel_selected_total" in html
