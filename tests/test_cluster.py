"""Cluster training masters (Spark-equivalent layer) — single-process over
the 8-device CPU mesh, plus a REAL 2-process jax.distributed run over
loopback (reference test strategy §4: PS/Spark tests run in-process over
loopback Aeron / local[*] SparkContext)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


from deeplearning4j_tpu.conf import Activation, InputType
from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.conf.losses import LossMCXENT
from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
from deeplearning4j_tpu.conf.updaters import Sgd
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.cluster import (
    ParameterAveragingTrainingMaster,
    SharedTrainingMaster,
    SparkDl4jMultiLayer,
)


def _conf(seed=12345):
    return (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=8, activation=Activation.TANH))
            .layer(OutputLayer(n_out=3, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(4))
            .build())


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


@pytest.mark.parametrize("master_fn", [
    lambda: ParameterAveragingTrainingMaster(averaging_frequency=2),
    lambda: SharedTrainingMaster(),            # exact all-reduce
    lambda: SharedTrainingMaster(threshold=1e-4),
])
def test_masters_train(master_fn):
    master = master_fn()
    net = MultiLayerNetwork(_conf())
    net.init()
    x, y = _data()
    spark_net = SparkDl4jMultiLayer(None, net, master)
    it = ArrayDataSetIterator(x, y, batch=32)
    s0 = None
    for ep in range(8):
        spark_net.fit(it)
        if s0 is None:
            s0 = spark_net.score
    assert spark_net.score < s0
    ev = net.evaluate(ArrayDataSetIterator(x, y, batch=32))
    assert ev.accuracy() > 0.3


_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    pid = int(sys.argv[1]); port = sys.argv[2]; outdir = sys.argv[3]
    sys.path.insert(0, {repo!r})
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=2, process_id=pid)
    import numpy as np
    from tests.test_cluster import _conf, _data
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.cluster import (
        SharedTrainingMaster, SparkDl4jMultiLayer)

    net = MultiLayerNetwork(_conf())
    net.init()
    x, y = _data(64)
    # this process's partition (the reference's RDD partition)
    half = 32
    xs, ys = x[pid*half:(pid+1)*half], y[pid*half:(pid+1)*half]
    spark_net = SparkDl4jMultiLayer(None, net, SharedTrainingMaster())
    it = ArrayDataSetIterator(xs, ys, batch=32)
    for _ in range(5):
        spark_net.fit(it)
    flat = np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree_util.tree_leaves(net.params)])
    np.save(os.path.join(outdir, f"params_{{pid}}.npy"), flat)
    print("WORKER_DONE", pid, spark_net.score)
""")


# the N-process loopback probe + spawn machinery now lives in
# tests/pod_harness.py (shared with the pod-scale-out suite)
from tests import pod_harness


def test_two_process_distributed_matches_single(tmp_path):
    """2 hosts x 4 devices == 1 host x 8 devices == the same math."""
    pod_harness.require_multiprocess(2)
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    port = "29877"
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), port, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
        for i in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        assert "WORKER_DONE" in out
    p0 = np.load(tmp_path / "params_0.npy")
    p1 = np.load(tmp_path / "params_1.npy")
    # both hosts hold identical params after the shared-gradient exchange
    np.testing.assert_allclose(p0, p1, rtol=1e-6, atol=1e-7)

    # and they match a single-process run over the full data on 8 devices
    net = MultiLayerNetwork(_conf())
    net.init()
    x, y = _data(64)
    single = SparkDl4jMultiLayer(None, net, SharedTrainingMaster())
    it = ArrayDataSetIterator(x, y, batch=64)
    for _ in range(5):
        single.fit(it)
    import jax as _jax
    flat = np.concatenate([np.asarray(l).ravel()
                           for l in _jax.tree_util.tree_leaves(net.params)])
    np.testing.assert_allclose(p0, flat, rtol=5e-5, atol=1e-6)


def test_fit_raw_arrays_uses_batch_size_per_worker():
    net = MultiLayerNetwork(_conf())
    net.init()
    x, y = _data(64)
    sn = SparkDl4jMultiLayer(None, net,
                             SharedTrainingMaster(batch_size_per_worker=4))
    sn.fit(x, y)  # 8 workers * 4 rows -> 2 batches of 32
    assert net.iteration == 2
