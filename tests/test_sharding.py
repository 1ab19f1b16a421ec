"""Declarative sharding subsystem (sharding/): rule tables, ShardingPlan,
ZeRO optimizer-state sharding, sharding-aware checkpoints.

Runs on the conftest's 8 virtual CPU devices — the same simulated mesh
the ParallelWrapper suites use. The load-bearing invariants:

- ZeRO mode trains BIT-identical (params AND updater state) to the
  all-reduce DP path on the same stream;
- a snapshot saved from a sharded run restores digest-verified onto a
  DIFFERENT mesh shape;
- the reduce-scatter/all-gather ops feed the same collective counter
  series bucketed_psum populates;
- sharded executables get their own AOT-cache keys (zero recompiles
  across refits, no aliasing between placements).
"""

import json
import os
import shutil
import tempfile
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.conf import Activation, InputType, WeightInit
from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.conf.losses import LossMCXENT
from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
from deeplearning4j_tpu.conf.updaters import Adam, Nesterovs, Sgd
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    ArrayDataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.sharding import (
    ShardingPlan,
    ZeroSpec,
    create_opt_spec,
    match_partition_rules,
)

pytestmark = pytest.mark.sharding


def _conf(updater=None, seed=12345):
    return (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Adam(learning_rate=0.05))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_out=16, activation=Activation.TANH))
            .layer(OutputLayer(n_out=3, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(4))
            .build())


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _train(updater=None, n=64, batch=16, epochs=2, **kw):
    net = MultiLayerNetwork(_conf(updater)).init()
    pw = ParallelWrapper(net, workers=kw.pop("workers", 8), **kw)
    x, y = _data(n)
    pw.fit(ArrayDataSetIterator(x, y, batch=batch), epochs=epochs)
    return net, pw


def _bit_identical(a, b):
    la = jax.tree_util.tree_leaves((a.params, a.opt_state))
    lb = jax.tree_util.tree_leaves((b.params, b.opt_state))
    assert jax.tree_util.tree_structure(a.opt_state) == \
        jax.tree_util.tree_structure(b.opt_state)
    for u, v in zip(la, lb):
        assert np.asarray(u).shape == np.asarray(v).shape
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# ---------------------------------------------------------------------------
# rule tables
# ---------------------------------------------------------------------------

def _toy_params():
    return {"0": {"W": np.zeros((4, 16), np.float32),
                  "b": np.zeros((16,), np.float32)},
            "1": {"W": np.zeros((16, 3), np.float32),
                  "s": np.zeros((), np.float32)}}


def test_match_partition_rules_first_match_wins_and_scalars_skip():
    specs = match_partition_rules(
        [(r"0/W$", P("model")), (r"W$", P(None, "model")), (r".*", P())],
        _toy_params())
    assert specs["0"]["W"] == P("model")          # first match wins
    assert specs["1"]["W"] == P(None, "model")
    assert specs["0"]["b"] == P()
    # the scalar never consults the table (no rule matches "1/s" besides
    # the catch-all, but even without one it would replicate)
    assert specs["1"]["s"] == P()


def test_match_partition_rules_scalar_skips_without_catchall():
    specs = match_partition_rules(
        [(r"W$", P(None, "model")), (r"b$", P())], _toy_params())
    assert specs["1"]["s"] == P()


def test_unmatched_param_raises_with_nearest_rule():
    with pytest.raises(ValueError) as exc:
        match_partition_rules([(r"0/Wq$", P("model"))],
                              {"0": {"W": np.zeros((4, 4), np.float32)}})
    msg = str(exc.value)
    assert "no partition rule matches param '0/W'" in msg
    assert "0/Wq" in msg                          # nearest-rule suggestion


def test_rule_wider_than_rank_raises():
    with pytest.raises(ValueError, match="rank"):
        match_partition_rules(
            [(r"b$", P(None, None, "model")), (r".*", P())], _toy_params())


def test_create_opt_spec_clones_moments_replicates_scalars():
    params = _toy_params()
    specs = match_partition_rules([(r"W$", P(None, "model")), (r".*", P())],
                                  params)
    opt = {k: {pk: {"m": np.zeros_like(v), "v": np.zeros_like(v),
                    "t": np.zeros((), np.float32)}
               for pk, v in d.items()} for k, d in params.items()}
    ospecs = create_opt_spec(specs, opt)
    assert ospecs["0"]["W"]["m"] == P(None, "model")   # cloned
    assert ospecs["0"]["W"]["v"] == P(None, "model")
    assert ospecs["0"]["W"]["t"] == P()                # scalar state
    assert ospecs["0"]["b"]["m"] == P()
    # stateless updaters (empty dicts) survive the walk
    ospecs2 = create_opt_spec(specs, {k: {pk: {} for pk in d}
                                      for k, d in params.items()})
    assert ospecs2["0"]["W"] == {}


def test_plan_strict_raises_on_indivisible_and_demote_replicates():
    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    params = _toy_params()
    strict = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                          mesh=mesh)
    with pytest.raises(ValueError, match="not divisible"):
        strict.param_specs(params)                 # 1/W is 16x3
    lax_plan = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                            mesh=mesh, demote_indivisible=True)
    specs = lax_plan.param_specs(params)
    assert specs["0"]["W"] == P(None, "model")
    assert specs["1"]["W"] == P(None, None)        # demoted dim
    rows = {r["path"]: r for r in lax_plan.explain(fmt="json")["params"]}
    assert rows["1/W"].get("demoted") is True


def test_plan_explain_and_cache_tag():
    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    params = _toy_params()
    plan = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                        mesh=mesh, demote_indivisible=True)
    with pytest.raises(ValueError):
        plan.cache_tag()                           # unresolved
    plan.param_specs(params)
    tag = plan.cache_tag()
    text = plan.explain()
    assert "0/W" in text and "model" in text
    data = plan.explain(fmt="json")
    assert data["mesh"] == {"data": 4, "model": 2}
    assert len(data["params"]) == 4
    # same rules + same mesh -> same tag; different mesh -> different
    plan2 = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                         mesh=mesh, demote_indivisible=True)
    plan2.param_specs(params)
    assert plan2.cache_tag() == tag
    plan3 = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                         mesh=mesh_mod.single_host_mesh(data=8),
                         demote_indivisible=True)
    plan3.param_specs(params)
    assert plan3.cache_tag() != tag


def test_zoo_rule_tables_resolve_on_real_nets():
    from deeplearning4j_tpu.zoo import rules as zoo_rules
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder
    from deeplearning4j_tpu.zoo.models import LeNet

    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    tr = TransformerEncoder(num_classes=2, embed_dim=8, n_heads=2,
                            n_layers=1, max_len=8).init()
    plan = zoo_rules.plan_for(zoo_rules.transformer_rules(), mesh=mesh)
    specs = plan.param_specs(tr.params)
    assert specs["b0_attn"]["Wq"] == P(None, "model")
    assert specs["b0_attn"]["Wo"] == P("model", None)
    assert specs["b0_ff1"]["W"] == P(None, "model")
    assert specs["b0_ff2"]["W"] == P("model", None)
    assert specs["b0_ln1"]["gain"] == P() if "gain" in specs["b0_ln1"] \
        else True                                  # norms replicated
    ln = LeNet(num_classes=10).init()
    plan2 = zoo_rules.plan_for(zoo_rules.lenet_rules(), mesh=mesh)
    specs2 = plan2.param_specs(ln.params)
    assert specs2["0"]["W"] == P(None, None, None, "model")
    assert specs2["5"]["W"] == P(None, "model")
    assert specs2["0"]["b"] == P()


# ---------------------------------------------------------------------------
# ZeRO numerics: bit-identity with the all-reduce DP path
# ---------------------------------------------------------------------------
#
# The bitwise reference is the EXPLICIT all-reduce exchange
# (``gradient_bucket_mb=0``: the same per-shard gradients, psum where
# ZeRO reduce-scatters). The default SPMD step is a different program —
# XLA's partitioner picks its own reduction order — so it agrees to the
# last ulp or two, not bitwise.

_ALLREDUCE = {"gradient_bucket_mb": 0}


def _ulp_close(a, b):
    for u, v in zip(jax.tree_util.tree_leaves((a.params, a.opt_state)),
                    jax.tree_util.tree_leaves((b.params, b.opt_state))):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=1e-5, atol=1e-6)


def test_zero_bit_identical_to_allreduce_dp():
    ref, _ = _train(**_ALLREDUCE)
    zero, pw = _train(zero_optimizer=True)
    _bit_identical(ref, zero)
    _ulp_close(_train()[0], zero)
    # and the optimizer state REALLY lives scattered on device: each
    # leaf of the live tree is a flat padded vector sharded over 'data'
    leaf = jax.tree_util.tree_leaves(pw._opt)[0]
    assert leaf.ndim == 1
    shard = leaf.addressable_shards[0].data
    assert shard.shape[0] * 8 == leaf.shape[0]


def test_zero_bit_identical_with_ragged_tail_and_buckets():
    ref, _ = _train(n=61, **_ALLREDUCE)            # ragged final batch
    zero, _ = _train(n=61, zero_optimizer=True)
    _bit_identical(ref, zero)
    bucketed, _ = _train(n=61, zero_optimizer=True,
                         gradient_bucket_mb=0.0001)
    _bit_identical(ref, bucketed)


def test_zero_bit_identical_momentum_and_stateless_updaters():
    for upd in (Nesterovs(learning_rate=0.02, momentum=0.9),
                Sgd(learning_rate=0.05)):
        ref, _ = _train(updater=upd, **_ALLREDUCE)
        zero, _ = _train(updater=upd, zero_optimizer=True)
        _bit_identical(ref, zero)


def test_zero_bit_identical_computation_graph():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def _gconf():
        g = (NeuralNetConfiguration.builder().seed(9)
             .updater(Adam(learning_rate=0.05))
             .weight_init(WeightInit.XAVIER).graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.feed_forward(4)))
        g.add_layer("d", DenseLayer(n_out=16, activation=Activation.TANH),
                    "in")
        g.add_layer("out", OutputLayer(n_out=3,
                                       activation=Activation.SOFTMAX,
                                       loss_fn=LossMCXENT()), "d")
        g.set_outputs("out")
        return g.build()

    x, y = _data()

    def train(**kw):
        net = ComputationGraph(_gconf()).init()
        ParallelWrapper(net, workers=8, **kw).fit(
            ArrayDataSetIterator(x, y, batch=16), epochs=2)
        return net

    zero = train(zero_optimizer=True)
    _bit_identical(train(**_ALLREDUCE), zero)
    _ulp_close(train(), zero)


def test_zero_mode_refusals():
    net = MultiLayerNetwork(_conf()).init()
    from deeplearning4j_tpu.parallel import ThresholdAlgorithm, TrainingMode

    with pytest.raises(ValueError, match="zero_optimizer"):
        ParallelWrapper(net, training_mode=TrainingMode.AVERAGING,
                        zero_optimizer=True)
    with pytest.raises(ValueError, match="zero_optimizer"):
        ParallelWrapper(net, threshold_algorithm=ThresholdAlgorithm(1e-3),
                        zero_optimizer=True)
    with pytest.raises(ValueError, match="fused_steps"):
        ParallelWrapper(net, zero_optimizer=True, fused_steps=4)


def test_zero_health_skip_matches_dp_skip():
    from deeplearning4j_tpu.telemetry import health

    def batches(poison):
        rng = np.random.default_rng(3)
        out = []
        for i in range(4):
            x = rng.normal(size=(16, 4)).astype(np.float32)
            if i == poison:
                x = x + np.nan
            out.append(DataSet(x, np.eye(3, dtype=np.float32)[
                np.arange(16) % 3]))
        return out

    try:
        health.configure(policy=health.AnomalyPolicy.SKIP_STEP,
                         record_flights=False)
        ref = MultiLayerNetwork(_conf()).init()
        ParallelWrapper(ref, workers=8).fit(
            ListDataSetIterator(batches(2)), epochs=1)
        r_ref = dict(health.report())
        health.configure(policy=health.AnomalyPolicy.SKIP_STEP,
                         record_flights=False)
        zero = MultiLayerNetwork(_conf()).init()
        ParallelWrapper(zero, workers=8, zero_optimizer=True).fit(
            ListDataSetIterator(batches(2)), epochs=1)
        r_zero = dict(health.report())
    finally:
        health.disable()
    _bit_identical(ref, zero)
    assert r_zero["nonfinite_steps"] == r_ref["nonfinite_steps"] == 1
    assert r_zero["skipped_steps"] == r_ref["skipped_steps"] == 1


# ---------------------------------------------------------------------------
# DP x TP partition-rule training
# ---------------------------------------------------------------------------

def test_partition_rules_dp_tp_matches_dp():
    ref, _ = _train()
    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    plan = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                        mesh=mesh, demote_indivisible=True)
    tp, pw = _train(workers=4, mesh=mesh, partition_rules=plan)
    la = jax.tree_util.tree_leaves((ref.params, ref.opt_state))
    lb = jax.tree_util.tree_leaves((tp.params, tp.opt_state))
    for u, v in zip(la, lb):
        # GSPMD-partitioned matmuls: same math, compiler-chosen
        # reduction order -> allclose, not bitwise
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=5e-5, atol=5e-6)
    # the TP split is real: the first dense kernel is sharded 2-way on
    # its output features during training
    w0 = pw._params["0"]["W"]
    assert w0.addressable_shards[0].data.shape == (4, 8)


# ---------------------------------------------------------------------------
# AOT cache: sharding-keyed executables
# ---------------------------------------------------------------------------

def test_zero_refit_zero_recompiles_and_no_dp_aliasing():
    from deeplearning4j_tpu.optimize import aot_cache

    net, pw = _train(zero_optimizer=True, epochs=1)
    misses = aot_cache.stats()["misses"]
    # refit on a FRESH wrapper over the same (retrained) model: the
    # sharding-keyed executable is a cache hit, zero recompiles
    pw2 = ParallelWrapper(net, workers=8, zero_optimizer=True)
    x, y = _data()
    pw2.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    assert aot_cache.stats()["misses"] == misses


def test_signature_keys_shardings():
    from deeplearning4j_tpu.optimize.aot_cache import signature_of

    mesh = mesh_mod.single_host_mesh()
    x = np.zeros((8, 4), np.float32)
    rep = jax.device_put(x, mesh_mod.replicated_spec(mesh))
    sharded = jax.device_put(x, mesh_mod.data_parallel_spec(mesh))
    assert signature_of((rep,)) != signature_of((sharded,))
    # two identically-sharded arrays share a signature
    sharded2 = jax.device_put(x, mesh_mod.data_parallel_spec(mesh))
    assert signature_of((sharded,)) == signature_of((sharded2,))


# ---------------------------------------------------------------------------
# collective-counter parity (regression-pins the series names)
# ---------------------------------------------------------------------------

def test_zero_feeds_same_collective_counters_as_bucketed_psum():
    from deeplearning4j_tpu import telemetry

    telemetry.enable()
    try:
        telemetry.reset()
        _train(zero_optimizer=True, gradient_bucket_mb=0.0001, epochs=1)
        snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    finally:
        telemetry.disable()
        telemetry.reset()
    # the SAME series every other exchange feeds, new op labels
    for op in ("grad_reduce_scatter", "param_all_gather"):
        assert snap[f'dl4j_collective_bytes_total{{op="{op}"}}'] > 0
        assert snap[f'dl4j_collective_ops_total{{op="{op}"}}'] > 0
        assert snap[f'dl4j_collective_buckets{{op="{op}"}}'] > 1
        hist = snap[f'dl4j_collective_bucket_bytes{{op="{op}"}}']
        assert hist["count"] > 1
    # both halves move the same payload on the same bucket layout
    assert snap['dl4j_collective_bytes_total{op="grad_reduce_scatter"}'] \
        == snap['dl4j_collective_bytes_total{op="param_all_gather"}']


def test_shard_bytes_gauges_show_one_eighth_opt_state():
    from deeplearning4j_tpu import telemetry

    telemetry.reset()
    net, pw = _train(zero_optimizer=True, epochs=1)
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    opt_total = sum(np.asarray(v).nbytes
                    for v in jax.tree_util.tree_leaves(net.opt_state))
    per_dev = [v for k, v in snap.items()
               if k.startswith("dl4j_shard_opt_bytes")]
    assert per_dev, "gauge missing"
    # <= ~1/8 of the unsharded footprint (+ padding slack)
    assert max(per_dev) <= opt_total / 8 * 1.25
    telemetry.reset()


# ---------------------------------------------------------------------------
# sharding-aware checkpoints: save on mesh A, restore on mesh B
# ---------------------------------------------------------------------------

def test_session_snapshot_gathers_and_restores_onto_different_mesh():
    from deeplearning4j_tpu.resilience import TrainingSession
    from deeplearning4j_tpu.util.serializer import file_digest

    d = tempfile.mkdtemp()
    try:
        net = MultiLayerNetwork(_conf()).init()
        pw = ParallelWrapper(net, workers=8, zero_optimizer=True)
        sess = TrainingSession(pw, d, snapshot_every_n_iterations=100)
        x, y = _data()
        sess.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
        snap_params = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), net.params)
        snap_opt = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), net.opt_state)
        # the manifest digest matches the bytes on disk (gather-on-save
        # went through the same atomic temp+replace as every snapshot)
        entry = sess.snapshots()[-1]
        assert file_digest(os.path.join(d, entry["file"])) \
            == entry["digest"]

        # "new process", DIFFERENT mesh shape: 4-way ZeRO wrapper
        net_b = MultiLayerNetwork(_conf()).init()
        pw_b = ParallelWrapper(net_b, workers=4, zero_optimizer=True)
        sess_b = TrainingSession(pw_b, d)
        restored = sess_b.resume()
        for k in snap_params:
            for pk in snap_params[k]:
                np.testing.assert_array_equal(
                    np.asarray(restored.params[k][pk]),
                    snap_params[k][pk])
        r_opt = jax.tree_util.tree_leaves(restored.opt_state)
        for u, v in zip(r_opt, jax.tree_util.tree_leaves(snap_opt)):
            np.testing.assert_array_equal(np.asarray(u), v)
        # and the restored state TRAINS on the new mesh (re-scattered
        # onto 4 shards)
        sess_b.fit(ArrayDataSetIterator(x, y, batch=16), to_epoch=2)
        assert pw_b.model.epoch == 2
        leaf = jax.tree_util.tree_leaves(pw_b._opt)[0]
        assert leaf.addressable_shards[0].data.shape[0] * 4 \
            == leaf.shape[0]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_session_refuses_non_exact_wrapper_modes():
    """Model-level snapshots can't capture AVERAGING replica divergence
    or threshold residuals — those wrapper modes must be refused at
    session construction, not silently resumed wrong."""
    from deeplearning4j_tpu.parallel import ThresholdAlgorithm, TrainingMode
    from deeplearning4j_tpu.resilience import TrainingSession

    d = tempfile.mkdtemp()
    try:
        net = MultiLayerNetwork(_conf()).init()
        with pytest.raises(ValueError, match="SHARED_GRADIENTS"):
            TrainingSession(ParallelWrapper(
                net, training_mode=TrainingMode.AVERAGING), d)
        with pytest.raises(ValueError, match="SHARED_GRADIENTS"):
            TrainingSession(ParallelWrapper(
                net, threshold_algorithm=ThresholdAlgorithm(1e-3)), d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_session_kill_and_resume_bit_identical_same_mesh():
    from deeplearning4j_tpu.resilience import TrainingSession, faults

    d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        ref = MultiLayerNetwork(_conf()).init()
        TrainingSession(ParallelWrapper(ref, workers=8,
                                        zero_optimizer=True),
                        d1, snapshot_every_n_iterations=2).fit(
            ArrayDataSetIterator(*_data(), batch=16), epochs=2)

        net = MultiLayerNetwork(_conf()).init()
        sess = TrainingSession(
            ParallelWrapper(net, workers=8, zero_optimizer=True),
            d2, snapshot_every_n_iterations=2, max_restarts=0)
        plan = faults.FaultPlan(seed=1)
        plan.inject("train.step", on_calls=[5], action="raise")
        with pytest.raises(faults.InjectedFault):
            with plan.armed():
                sess.fit(ArrayDataSetIterator(*_data(), batch=16),
                         epochs=2)
        # fresh wrapper, same mesh, resume from directory alone
        net_b = MultiLayerNetwork(_conf()).init()
        sess_b = TrainingSession(
            ParallelWrapper(net_b, workers=8, zero_optimizer=True), d2)
        sess_b.resume()
        sess_b.fit(ArrayDataSetIterator(*_data(), batch=16), to_epoch=2)
        _bit_identical(ref, sess_b._net)
    finally:
        shutil.rmtree(d1, ignore_errors=True)
        shutil.rmtree(d2, ignore_errors=True)


def test_checkpoint_listener_gathers_live_wrapper_state():
    """write_model DURING a wrapper fit (a CheckpointListener firing
    mid-run) serializes the CURRENT trained state — gathered from the
    live (ZeRO-scattered) device trees through the _live_trainer hook —
    not the stale pre-fit host copy. After fit the hook is DISARMED:
    the model's host arrays are authoritative again, so later solo
    training can never be clobbered by old device trees."""
    from deeplearning4j_tpu.optimize.checkpoint import CheckpointListener
    from deeplearning4j_tpu.util import serializer

    d = tempfile.mkdtemp()
    try:
        net = MultiLayerNetwork(_conf()).init()
        pre = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), net.params)
        net.set_listeners(CheckpointListener(
            d, save_every_n_iterations=2, keep_last=2))
        pw = ParallelWrapper(net, workers=8, zero_optimizer=True)
        x, y = _data()
        pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
        # the mid-fit checkpoint holds TRAINED params (the stale pre-fit
        # host copy would equal `pre`), gathered behind the atomic save
        lst = net.listeners[0]
        cp = lst.list_checkpoints()[0]
        restored = lst.load_checkpoint(cp.number)
        moved = any(
            not np.array_equal(np.asarray(restored.params[k][pk]),
                               pre[k][pk])
            for k in pre for pk in pre[k])
        assert moved, "mid-fit checkpoint captured the stale host copy"
        assert lst.verify(cp)
        # and the hook disarmed at fit end
        assert net._live_trainer is None
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# debugging surfaces
# ---------------------------------------------------------------------------

def test_sharding_endpoint_and_system_panel():
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.ui.stats import collect_system_metrics

    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    plan = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                        mesh=mesh, demote_indivisible=True)
    plan.param_specs(_toy_params())
    sysm = collect_system_metrics()
    assert any(p["mesh"] == {"data": 4, "model": 2}
               for p in sysm.get("sharding_plans", []))
    ui = UIServer()
    port = ui.start(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/sharding") as r:
            plans = json.loads(r.read())
        assert any(p["mesh"] == {"data": 4, "model": 2} for p in plans)
        assert any(r_["path"] == "0/W" for p in plans
                   for r_ in p["params"])
        html_page = ui.render_html()
        assert "Sharding plans" in html_page
    finally:
        ui.stop()


def test_zero_spec_roundtrip():
    tree = {"a": np.arange(13, dtype=np.float32).reshape(13),
            "b": np.arange(6, dtype=np.float32).reshape(2, 3)}
    z = ZeroSpec(tree, 8)
    mesh = mesh_mod.single_host_mesh()
    scattered = z.scatter_host(tree, mesh, "data")
    leaves = jax.tree_util.tree_leaves(scattered)
    assert all(l.shape[0] % 8 == 0 for l in leaves)
    back = z.gather_host(scattered)
    for k in tree:
        np.testing.assert_array_equal(back[k], tree[k])
    assert z.bytes_per_device() == (2 + 1) * 4     # ceil(13/8)+ceil(6/8)


# ---------------------------------------------------------------------------
# pod-refactor parity: the make_array-based scatter/gather pinned BITWISE
# against the legacy numpy round-trip at process_count == 1 (the pod
# scale-out rebuilt these paths on jax.make_array_from_callback /
# host_gather; every existing green path must be unperturbed)
# ---------------------------------------------------------------------------

def test_make_array_scatter_matches_legacy_device_put_bitwise():
    """ZeroSpec.scatter_host now stages through mesh.stage_host
    (make_array_from_callback on pods); at process_count == 1 the
    arrays must be BITWISE the legacy jax.device_put staging, and
    gather_host must be bitwise np.asarray."""
    from jax.sharding import NamedSharding

    tree = {"a": np.arange(13, dtype=np.float32),
            "b": np.arange(6, dtype=np.float32).reshape(2, 3)}
    z = ZeroSpec(tree, 8)
    mesh = mesh_mod.single_host_mesh()
    new = z.scatter_host(tree, mesh, "data")
    # the legacy route, re-implemented inline
    sh = NamedSharding(mesh, P("data"))
    leaves = jax.tree_util.tree_leaves(tree)
    legacy = []
    for leaf, padded, dt in zip(leaves, z.padded_sizes, z.dtypes):
        flat = np.zeros((padded,), dt)
        flat[:leaf.size] = np.asarray(leaf).reshape(-1)
        legacy.append(jax.device_put(flat, sh))
    for n, l in zip(jax.tree_util.tree_leaves(new), legacy):
        assert n.sharding == l.sharding
        np.testing.assert_array_equal(np.asarray(n), np.asarray(l))
    # and the explicit make_array_from_callback staging agrees too
    cb = jax.make_array_from_callback(
        legacy[0].shape, sh,
        lambda idx: np.asarray(legacy[0])[idx])
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(legacy[0]))
    back = z.gather_host(new)
    for k in tree:
        np.testing.assert_array_equal(back[k], tree[k])


def test_plan_place_parity_with_device_put():
    """ShardingPlan.place (the comms.reshard host route) pinned bitwise
    against direct device_put placement under the same shardings —
    plan placement is one of the paths the pod refactor re-staged."""
    mesh = mesh_mod.single_host_mesh(data=4, model=2)
    plan = ShardingPlan([(r"W$", P(None, "model")), (r".*", P())],
                        mesh=mesh, demote_indivisible=True)
    params = _toy_params()
    specs = plan.param_specs(params)
    placed = plan.place(params, specs)
    shardings = plan.shardings(specs)
    legacy = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(np.asarray(x), s), params, shardings)
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(legacy)):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_fit_checkpoint_roundtrip_parity(tmp_path):
    """ZeRO fit -> mid-training write_model (gather-on-save through the
    new host_gather) -> restore: bitwise the wrapper's live state. Pins
    that the pod refactor's gather cannot silently perturb the
    checkpoint path at process_count == 1."""
    from deeplearning4j_tpu.util import params as params_util
    from deeplearning4j_tpu.util.serializer import (
        restore_multi_layer_network,
        write_model,
    )

    net = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(net, workers=8, zero_optimizer=True)
    x, y = _data(32)
    pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    path = os.path.join(str(tmp_path), "zero.zip")
    write_model(net, path)
    restored = restore_multi_layer_network(path)
    np.testing.assert_array_equal(np.asarray(restored.params_flat()),
                                  np.asarray(net.params_flat()))
    np.testing.assert_array_equal(
        np.asarray(params_util.flatten_state_like(restored.opt_state)),
        np.asarray(params_util.flatten_state_like(net.opt_state)))
