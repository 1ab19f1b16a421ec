"""Unified collective scheduler + cross-mesh resharding (comms/).

Pins the PR-12 contracts:

- plan determinism: same tree + intent -> identical CollectivePlan
  digest, in-process (cache hit) and across processes;
- choice rules: variadic single-exchange for sub-threshold trees,
  densified accumulation for many-tiny-leaf buckets, the native
  all-gather (typed replicated) for every gather;
- bit-identity: scheduler-routed exchanges == the pre-scheduler
  primitives (inline legacy copies below) on the simulated 8-device
  mesh, and every scheduler-routed ParallelWrapper mode == its legacy
  route on real training;
- plan digests key the AOT cache (changed layout -> new executable,
  identical rebuild -> zero recompiles);
- PRG205 understands plans (promised reduce-scatter compiled to
  all-reduce -> ERROR);
- cross-mesh reshard of a live training state bitwise == the host
  gather/scatter route; publish_to_engine serves the trained weights
  with zero recompiles.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.comms import reshard as _  # noqa: F401 (package)
from deeplearning4j_tpu.comms import scheduler
from deeplearning4j_tpu.comms.reshard import (
    publish_to_engine,
    reshard,
    reshard_training_state,
)
from deeplearning4j_tpu.parallel.compression import (
    bucketed_all_gather,
    bucketed_psum,
    bucketed_psum_scatter,
)
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, shard_map

pytestmark = pytest.mark.comms


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), (DATA_AXIS,))


def _tree(rng, rows=4):
    return {
        "a": jnp.asarray(rng.normal(size=(rows, 8, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(rows, 2)).astype(np.float32)),
        "c": [jnp.asarray(rng.normal(size=(rows, 17)).astype(np.float32)),
              jnp.asarray(rng.normal(size=(rows, 1)).astype(np.float32))],
    }


def _bit_identical(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# the pre-scheduler primitives, inline (the legacy route the scheduler
# must reproduce bitwise)
# --------------------------------------------------------------------------

def _legacy_psum(tree, axis_name, bucket_bytes=None):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    if bucket_bytes is None or len(leaves) <= 1:
        return jax.tree_util.tree_unflatten(
            treedef, list(jax.lax.psum(tuple(leaves), axis_name)))
    sizes = [l.size * l.dtype.itemsize for l in leaves]
    out = [None] * len(leaves)
    pin = None
    for bucket in scheduler.bucket_partition(sizes, int(bucket_bytes)):
        vals = tuple(leaves[i] for i in bucket)
        if pin is not None:
            pinned = jax.lax.optimization_barrier(vals + (pin,))
            vals = tuple(pinned[:-1])
        red = jax.lax.psum(vals, axis_name)
        pin = red[0]
        for i, r in zip(bucket, red):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


def _legacy_psum_scatter(tree, axis_name, bucket_bytes=None):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree

    def scatter(vals):
        return jax.lax.psum_scatter(vals, axis_name, scatter_dimension=0,
                                    tiled=True)

    if bucket_bytes is None or len(leaves) <= 1:
        return jax.tree_util.tree_unflatten(treedef,
                                            list(scatter(tuple(leaves))))
    sizes = [l.size * l.dtype.itemsize for l in leaves]
    out = [None] * len(leaves)
    pin = None
    for bucket in scheduler.bucket_partition(sizes, int(bucket_bytes)):
        vals = tuple(leaves[i] for i in bucket)
        if pin is not None:
            pinned = jax.lax.optimization_barrier(vals + (pin,))
            vals = tuple(pinned[:-1])
        red = scatter(vals)
        pin = red[0]
        for i, r in zip(bucket, red):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


def _legacy_all_gather(tree, axis_name, full_sizes, bucket_bytes=None):
    """Masked-psum gather: each shard deposits its slice into a zeros
    vector and a psum reassembles — the bitwise oracle for the native
    all-gather."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    index = jax.lax.axis_index(axis_name)
    contribs = []
    for sl, full in zip(leaves, full_sizes):
        m = sl.shape[0]
        contribs.append(jax.lax.dynamic_update_slice(
            jnp.zeros((int(full),), sl.dtype), sl, (index * m,)))
    return _legacy_psum(jax.tree_util.tree_unflatten(treedef, contribs),
                        axis_name, bucket_bytes)


# --------------------------------------------------------------------------
# plans
# --------------------------------------------------------------------------

def test_plan_determinism_and_cache():
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    before = scheduler.stats()
    p1 = scheduler.plan_for(tree, "all_reduce", DATA_AXIS, 64)
    p2 = scheduler.plan_for(tree, "all_reduce", DATA_AXIS, 64)
    assert p1.digest == p2.digest and p1 is p2
    after = scheduler.stats()
    assert after["plan_cache_hits"] >= before["plan_cache_hits"] + 1
    # layout changes change the digest; intent changes change the digest
    # (64 packs every leaf alone; 500 packs three together)
    p3 = scheduler.plan_for(tree, "all_reduce", DATA_AXIS, 500)
    assert p3.buckets != p1.buckets
    assert p3.digest != p1.digest
    flat = [jnp.zeros((16,)), jnp.zeros((16,))]
    p4 = scheduler.plan_for(flat, "reduce_scatter", DATA_AXIS, 64)
    p5 = scheduler.plan_for(flat, "all_reduce", DATA_AXIS, 64)
    assert p4.digest != p5.digest
    # registry lookup round-trips (the PRG205 path)
    assert scheduler.lookup_plan(p1.digest) is p1


def test_plan_digest_identical_across_processes():
    code = (
        "import jax.numpy as jnp;"
        "from deeplearning4j_tpu.comms import scheduler;"
        "t={'a': jnp.zeros((4,8,3)), 'b': jnp.zeros((4,2)),"
        " 'c':[jnp.zeros((4,17)), jnp.zeros((4,1))]};"
        "print(scheduler.plan_for(t,'all_reduce','data',64).digest)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                         "PYTHONPATH": "/root/repo"})
    rng = np.random.default_rng(0)
    here = scheduler.plan_for(_tree(rng), "all_reduce", DATA_AXIS, 64)
    assert out.stdout.strip() == here.digest


def test_plan_choice_rules():
    rng = np.random.default_rng(1)
    # sub-threshold tree -> ONE variadic exchange, no barrier chain
    p = scheduler.plan_for(_tree(rng), "all_reduce", DATA_AXIS, None)
    assert p.launches() == 1 and p.choices == ("variadic",)
    # many tiny same-dtype leaves in one bucket -> densify
    tiny = [jnp.zeros((4, 3), jnp.float32) for _ in range(12)]
    p = scheduler.plan_for(tiny, "all_reduce", DATA_AXIS, 10 ** 9)
    assert p.choices == ("densify",)
    # mixed dtypes never densify
    mixed = ([jnp.zeros((4, 3), jnp.float32) for _ in range(8)]
             + [jnp.zeros((4, 3), jnp.bfloat16) for _ in range(4)])
    p = scheduler.plan_for(mixed, "all_reduce", DATA_AXIS, 10 ** 9)
    assert "densify" not in p.choices
    # a big leaf in the bucket disables densify
    big = [jnp.zeros((4, 3), jnp.float32) for _ in range(8)] \
        + [jnp.zeros((64, 1024), jnp.float32)]
    p = scheduler.plan_for(big, "all_reduce", DATA_AXIS, 10 ** 9)
    assert "densify" not in p.choices
    # reduce-scatter never densifies (layout-changing)
    flat = [jnp.zeros((16,), jnp.float32) for _ in range(12)]
    p = scheduler.plan_for(flat, "reduce_scatter", DATA_AXIS, 10 ** 9)
    assert set(p.choices) == {"variadic"}
    # gather: always the native all-gather
    p = scheduler.plan_for([jnp.zeros((4,))], "all_gather", DATA_AXIS,
                           full_sizes=[16])
    assert p.choices == ("all_gather",)


def test_unknown_intent_raises():
    with pytest.raises(ValueError, match="intent"):
        scheduler.plan_for([jnp.zeros((4,))], "gossip", DATA_AXIS)


def test_bucket_partition_shared_implementation():
    from deeplearning4j_tpu.parallel import compression

    assert compression.bucket_partition is scheduler.bucket_partition
    assert compression.bucket_layout is scheduler.bucket_layout
    from deeplearning4j_tpu.sharding.zero import ZeroSpec

    z = ZeroSpec({"w": np.zeros((10, 3), np.float32)}, 4)
    assert z.layout_bytes(None) == [z.padded_sizes[0] * 4]


# --------------------------------------------------------------------------
# bit-identity vs the legacy primitives
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bucket_bytes", [None, 64, 10 ** 9])
def test_scheduler_psum_bitwise_vs_legacy(bucket_bytes):
    mesh = _mesh()
    rng = np.random.default_rng(2)
    tree = _tree(rng)
    specs = jax.tree_util.tree_map(lambda _: P(DATA_AXIS), tree)
    got = jax.jit(shard_map(
        lambda t: bucketed_psum(t, DATA_AXIS, bucket_bytes), mesh,
        in_specs=(specs,), out_specs=specs))(tree)
    want = jax.jit(shard_map(
        lambda t: _legacy_psum(t, DATA_AXIS, bucket_bytes), mesh,
        in_specs=(specs,), out_specs=specs))(tree)
    _bit_identical(got, want)


def test_densified_bucket_bitwise_vs_legacy():
    mesh = _mesh()
    rng = np.random.default_rng(3)
    tiny = {str(i): jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))
            for i in range(12)}
    plan = scheduler.plan_for(tiny, "all_reduce", DATA_AXIS, 10 ** 9)
    assert plan.choices == ("densify",)   # the choice actually exercises
    specs = jax.tree_util.tree_map(lambda _: P(DATA_AXIS), tiny)
    got = jax.jit(shard_map(
        lambda t: bucketed_psum(t, DATA_AXIS, 10 ** 9), mesh,
        in_specs=(specs,), out_specs=specs))(tiny)
    want = jax.jit(shard_map(
        lambda t: _legacy_psum(t, DATA_AXIS, 10 ** 9), mesh,
        in_specs=(specs,), out_specs=specs))(tiny)
    _bit_identical(got, want)


@pytest.mark.parametrize("bucket_bytes", [None, 8, 10 ** 9])
def test_scheduler_zero_exchange_bitwise_vs_legacy(bucket_bytes):
    """reduce-scatter + all-gather round trip == legacy, bitwise."""
    mesh = _mesh()
    rng = np.random.default_rng(4)
    flat = tuple(jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
                 for _ in range(3))
    full = [16, 16, 16]

    def routed(t):
        sl = bucketed_psum_scatter(t, DATA_AXIS, bucket_bytes)
        return bucketed_all_gather(sl, DATA_AXIS, full, bucket_bytes)

    def legacy(t):
        sl = _legacy_psum_scatter(t, DATA_AXIS, bucket_bytes)
        return _legacy_all_gather(sl, DATA_AXIS, full, bucket_bytes)

    in_specs = (tuple(P() for _ in flat),)
    out_specs = tuple(P() for _ in flat)
    got = jax.jit(shard_map(routed, mesh, in_specs=in_specs,
                            out_specs=out_specs))(flat)
    want = jax.jit(shard_map(legacy, mesh, in_specs=in_specs,
                             out_specs=out_specs))(flat)
    _bit_identical(got, want)


def test_all_gather_is_native_and_typed_replicated():
    """The one gather route: a native all-gather (no all-reduce in the
    traced step) whose result passes replicated ``P()`` out_specs under
    check_vma and is bitwise what the masked psum gathers."""
    from deeplearning4j_tpu.analysis import program

    mesh = _mesh()
    rng = np.random.default_rng(5)
    sl = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))

    def masked(s):
        (out,) = _legacy_all_gather((s,), DATA_AXIS, [16])
        return out

    def native(s):
        (out,) = bucketed_all_gather((s,), DATA_AXIS, [16])
        return out

    want = jax.jit(shard_map(masked, mesh, in_specs=(P(DATA_AXIS),),
                             out_specs=P()))(sl)
    native_jit = jax.jit(shard_map(native, mesh, in_specs=(P(DATA_AXIS),),
                                   out_specs=P()))
    np.testing.assert_array_equal(np.asarray(native_jit(sl)),
                                  np.asarray(want))
    prims = {e.primitive.name
             for e in program.iter_eqns(native_jit.trace(sl).jaxpr)}
    assert "all_gather_invariant" in prims
    assert not prims & program.ALL_REDUCE_PRIMS


# --------------------------------------------------------------------------
# wrapper routing: every explicit-exchange mode through the scheduler
# bit-identical to the legacy route
# --------------------------------------------------------------------------

def _mlp(updater=None, seed=12345):
    from deeplearning4j_tpu.conf import Activation, InputType, WeightInit
    from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.conf.losses import LossMCXENT
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Adam(learning_rate=0.05))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_out=16, activation=Activation.TANH))
            .layer(OutputLayer(n_out=3, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(12))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 12)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=n)]
    return x, y


def _legacy_route(monkeypatch):
    """Point every explicit wrapper exchange at the inline legacy
    primitives (and neutralize plan-digest key differences by clearing
    the AOT cache around the run)."""
    from deeplearning4j_tpu.parallel import compression, wrapper

    monkeypatch.setattr(wrapper, "bucketed_psum", _legacy_psum)
    monkeypatch.setattr(wrapper, "bucketed_psum_scatter",
                        _legacy_psum_scatter)
    monkeypatch.setattr(compression, "bucketed_all_gather",
                        _legacy_all_gather)
    monkeypatch.setattr(compression, "bucketed_psum", _legacy_psum)


@pytest.mark.parametrize("mode_kw", [
    {"gradient_bucket_mb": 0.0002},                     # SHARED_GRADIENTS
    {"zero_optimizer": True, "gradient_bucket_mb": 0.0002},      # ZeRO
    {"zero_optimizer": True},                           # ZeRO fused
])
def test_wrapper_scheduler_route_bit_identical(mode_kw, monkeypatch):
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    x, y = _data(n=60)  # ragged tail over 8 workers

    def run(legacy):
        if legacy:
            _legacy_route(monkeypatch)
        aot_cache.clear()
        net = _mlp()
        pw = ParallelWrapper(net, workers=8, prefetch_buffer=0, **mode_kw)
        pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=2)
        monkeypatch.undo()
        return net

    a, b = run(legacy=False), run(legacy=True)
    _bit_identical(a.params, b.params)
    _bit_identical(a.opt_state, b.opt_state)
    aot_cache.clear()


def test_wrapper_threshold_and_averaging_scheduler_route(monkeypatch):
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.compression import ThresholdAlgorithm
    from deeplearning4j_tpu.parallel.wrapper import (
        ParallelWrapper,
        TrainingMode,
    )

    x, y = _data(n=64, seed=3)
    for kw in ({"threshold_algorithm": ThresholdAlgorithm(1e-3),
                "gradient_bucket_mb": 0.0002},
               {"training_mode": TrainingMode.AVERAGING,
                "averaging_frequency": 2,
                "gradient_bucket_mb": 0.0002}):
        def run(legacy):
            from deeplearning4j_tpu.datasets.iterators import (
                ArrayDataSetIterator as It,
            )

            if legacy:
                _legacy_route(monkeypatch)
            aot_cache.clear()
            net = _mlp(seed=7)
            pw = ParallelWrapper(net, workers=8, prefetch_buffer=0, **kw)
            pw.fit(It(x, y, batch=16), epochs=2)
            monkeypatch.undo()
            return net

        a, b = run(legacy=False), run(legacy=True)
        _bit_identical(a.params, b.params)
        _bit_identical(a.opt_state, b.opt_state)
    aot_cache.clear()


def test_plan_digest_keys_aot_cache_and_zero_recompiles():
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    x, y = _data(n=64, seed=9)
    net = _mlp(seed=21)
    pw = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                         gradient_bucket_mb=0.0002)
    pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    key = pw._step._key[1]
    assert key.startswith("pw_bucketed:") and "plan:" in key
    digest = key.split("plan:")[1].split(":")[0]
    assert scheduler.lookup_plan(digest) is not None
    misses = aot_cache.stats()["misses"]
    # fresh wrapper, identical config -> same plan digest -> zero misses
    pw2 = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                          gradient_bucket_mb=0.0002)
    pw2.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    assert aot_cache.stats()["misses"] == misses
    # changed bucket layout -> different plan -> different executable
    pw3 = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                          gradient_bucket_mb=0.0005)
    pw3.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    assert aot_cache.stats()["misses"] > misses
    assert pw3._step._key[1] != key


# --------------------------------------------------------------------------
# PRG205 plan audit
# --------------------------------------------------------------------------

def test_prg205_flags_plan_promised_scatter_compiled_allreduce():
    from deeplearning4j_tpu.analysis import program

    mesh = _mesh()
    flat = [jnp.zeros((16,), jnp.float32) for _ in range(2)]
    plan = scheduler.plan_for(flat, "reduce_scatter", DATA_AXIS, None)

    def cheat(t):   # all-reduces where the plan promised reduce-scatter
        return [jax.lax.psum(x, DATA_AXIS) for x in t]

    jit_fn = jax.jit(shard_map(cheat, mesh,
                               in_specs=([P(), P()],),
                               out_specs=[P(), P()]))
    art = program.trace_artifact(
        jit_fn, (flat,), graph_key="t",
        fn_key=f"pw_zero:n4:b0:{plan.key_token()}", compile=False)
    hits = [f for f in program.lint_program(art) if f.rule == "PRG205"]
    assert hits and any("promised reduce-scatter" in f.message
                        for f in hits)
    assert any(f.severity == "ERROR" for f in hits)


def test_prg205_scheduler_routed_zero_step_passes():
    from deeplearning4j_tpu.analysis import program
    from deeplearning4j_tpu.sharding.zero import ZeroSpec

    mesh = _mesh()
    tree = {"w": jnp.zeros((40, 3), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}
    z = ZeroSpec(tree, 4)
    rs_plan, ag_plan = z.exchange_plans(DATA_AXIS, 64)

    def step(t):
        sl = bucketed_psum_scatter(z.flat_padded(t), DATA_AXIS, 64)
        return z.assemble(sl, DATA_AXIS, 64)

    jit_fn = jax.jit(shard_map(step, mesh, in_specs=(P(),),
                               out_specs=P()))
    art = program.trace_artifact(
        jit_fn, (tree,), graph_key="t",
        fn_key=f"pw_zero:n4:b64:{rs_plan.key_token()}"
               f":{ag_plan.key_token()}", compile=False)
    assert [f for f in program.lint_program(art)
            if f.rule == "PRG205"] == []


def test_prg205_repo_zero_wrapper_compiles_clean():
    """The real scheduler-routed ZeRO step through the live AOT cache
    leaves no PRG205 findings (the extended audit resolves its plan
    digests and the compiled module matches)."""
    from deeplearning4j_tpu.analysis import findings, program
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    aot_cache.clear()
    program.reset()
    findings.LOG.clear()
    x, y = _data(n=32, seed=11)
    net = _mlp(seed=33)
    pw = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                         zero_optimizer=True, gradient_bucket_mb=0.0002)
    pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    bad = [f for f in findings.LOG.items()
           if f.rule == "PRG205" and not f.waived]
    assert bad == []
    aot_cache.clear()


# --------------------------------------------------------------------------
# cross-mesh reshard
# --------------------------------------------------------------------------

def test_reshard_array_across_meshes_bitwise():
    src_mesh, dst_mesh = _mesh(8), _mesh(4)
    rng = np.random.default_rng(6)
    host = rng.normal(size=(16, 8)).astype(np.float32)
    x = jax.device_put(jnp.asarray(host),
                       NamedSharding(src_mesh, P(DATA_AXIS)))
    for spec in (P(DATA_AXIS), P(), P(None, DATA_AXIS)):
        tgt = NamedSharding(dst_mesh, spec)
        out = reshard(x, tgt)
        assert out.sharding == tgt
        np.testing.assert_array_equal(np.asarray(out), host)
    # replicated -> sharded, scalars, and host inputs all work
    s = jnp.float32(3.5)
    out = reshard(s, NamedSharding(dst_mesh, P()))
    assert float(out) == 3.5
    out = reshard(host, NamedSharding(dst_mesh, P(DATA_AXIS)))
    np.testing.assert_array_equal(np.asarray(out), host)


def test_zero_spec_device_scatter_matches_host_scatter():
    from deeplearning4j_tpu.sharding.zero import ZeroSpec

    mesh = _mesh(8)
    rng = np.random.default_rng(7)
    tree = {"w": rng.normal(size=(37, 3)).astype(np.float32),
            "b": rng.normal(size=(3,)).astype(np.float32)}
    dev_tree = jax.tree_util.tree_map(jnp.asarray, tree)
    z = ZeroSpec(tree, 8)
    host = z.scatter_host(tree, mesh, DATA_AXIS)
    dev = z.scatter(dev_tree, mesh, DATA_AXIS)
    _bit_identical(host, dev)
    for a, b in zip(jax.tree_util.tree_leaves(host),
                    jax.tree_util.tree_leaves(dev)):
        assert b.sharding == a.sharding
    # numpy input routes to the host path, same result
    _bit_identical(z.scatter(tree, mesh, DATA_AXIS), host)


def test_live_training_state_reshard_bitwise_vs_host_route():
    """The satellite pin: a live ZeRO training state on the 8-way mesh
    moves to a 4-way wrapper through comms.reshard bitwise-identically
    to the host gather/scatter round-trip — and training continues
    identically on both."""
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    from deeplearning4j_tpu.optimize import checkpoint as ckpt

    x, y = _data(n=64, seed=13)
    net = _mlp(seed=55)
    src = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                          zero_optimizer=True)
    src.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)

    # host route: gather to host arrays, restore onto a fresh model,
    # restage a fresh 4-way wrapper from those host arrays
    src.sync_model()
    snap = ckpt.snapshot_training_state(net)
    host_net = _mlp(seed=55)
    ckpt.restore_training_state(host_net, snap)
    dst_host = ParallelWrapper(host_net, workers=4, prefetch_buffer=0,
                               zero_optimizer=True)
    dst_host._setup()

    # device route: slice-intersection hand-off, no host gather
    dst_dev = ParallelWrapper(_mlp(seed=55), workers=4, prefetch_buffer=0,
                              zero_optimizer=True)
    reshard_training_state(src, dst_dev)
    dst_dev._setup()

    _bit_identical(dst_host._params, dst_dev._params)
    _bit_identical(dst_host._state, dst_dev._state)
    _bit_identical(dst_host._opt, dst_dev._opt)
    # both continue training to the same place (re-prestage: the
    # explicit _setup above consumed the one-shot hand-off)
    reshard_training_state(src, dst_dev)
    x2, y2 = _data(n=32, seed=14)
    dst_host.fit(ArrayDataSetIterator(x2, y2, batch=8), epochs=1)
    dst_dev.fit(ArrayDataSetIterator(x2, y2, batch=8), epochs=1)
    _bit_identical(dst_host.model.params, dst_dev.model.params)
    _bit_identical(dst_host.model.opt_state, dst_dev.model.opt_state)


def test_reshard_training_state_refuses_non_exact_modes():
    from deeplearning4j_tpu.parallel.wrapper import (
        ParallelWrapper,
        TrainingMode,
    )

    src = ParallelWrapper(_mlp(), workers=8, prefetch_buffer=0)
    with pytest.raises(ValueError, match="no staged"):
        reshard_training_state(
            src, ParallelWrapper(_mlp(), workers=4, prefetch_buffer=0))
    src._setup()
    avg = ParallelWrapper(_mlp(), workers=4, prefetch_buffer=0,
                          training_mode=TrainingMode.AVERAGING)
    with pytest.raises(ValueError, match="SHARED_GRADIENTS"):
        reshard_training_state(src, avg)


# --------------------------------------------------------------------------
# publish_to_engine
# --------------------------------------------------------------------------

def test_publish_to_engine_serves_trained_weights_zero_recompiles():
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.batcher import (
        BatchingConfig,
        InferenceEngine,
    )
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    x, y = _data(n=64, seed=15)
    net = _mlp(seed=77)
    engine = InferenceEngine(net, BatchingConfig(max_batch=8,
                                                 max_delay_ms=5))
    try:
        engine.warmup()
        stale = np.asarray(engine.predict(x[:4]))
        pw = ParallelWrapper(net, workers=8, prefetch_buffer=0,
                             zero_optimizer=True)
        pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
        misses = aot_cache.stats()["misses"]
        published = publish_to_engine(pw, engine)
        assert published is engine.model
        fresh = np.asarray(engine.predict(x[:4]))
        assert not np.array_equal(stale, fresh)  # weights actually moved
        # ground truth: the host-route output of the trained model
        pw.sync_model()
        want = np.asarray(net.output(x[:4]))
        np.testing.assert_allclose(fresh, want, rtol=1e-6, atol=1e-7)
        # the published model reuses every warmed executable
        assert aot_cache.stats()["misses"] == misses
    finally:
        engine.close()


def test_publish_to_engine_graph_opt_false_is_donation_safe():
    """A graph_opt=False engine publishes WITHOUT the inference pass's
    param copy, and an already-replicated wrapper tree reshards through
    the identity fast-path — the hand-off must still copy those leaves,
    or the wrapper's next donated train dispatch deletes the buffers
    the engine is serving from (review-round regression)."""
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.parallel.batcher import (
        BatchingConfig,
        InferenceEngine,
    )
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    x, y = _data(n=32, seed=17)
    net = _mlp(seed=88)
    pw = ParallelWrapper(net, workers=8, prefetch_buffer=0)
    pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
    engine = InferenceEngine(net, BatchingConfig(max_batch=8,
                                                 max_delay_ms=5),
                             graph_opt=False)
    try:
        publish_to_engine(pw, engine)
        live = {id(l) for l in jax.tree_util.tree_leaves(
            (pw._params, pw._state))}
        pub = {id(l) for l in jax.tree_util.tree_leaves(
            (engine.model.params, engine.model.state))}
        assert not (live & pub), "engine serves the wrapper's live buffers"
        want = np.asarray(engine.predict(x[:4]))
        # the wrapper trains on (donating its staged trees); the engine
        # must keep serving the published snapshot
        pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=1)
        np.testing.assert_array_equal(np.asarray(engine.predict(x[:4])),
                                      want)
    finally:
        engine.close()


# --------------------------------------------------------------------------
# telemetry + UI
# --------------------------------------------------------------------------

def test_plan_counter_and_gauges_recorded():
    from deeplearning4j_tpu import telemetry

    telemetry.reset()
    scheduler.reset()
    tree = [jnp.zeros((4, 5), jnp.float32), jnp.zeros((4,), jnp.float32)]
    plan = scheduler.plan_for(tree, "all_reduce", DATA_AXIS, 32)
    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    key = ('dl4j_collective_plan_total'
           f'{{choice="{plan.choice_summary()}",intent="all_reduce"}}')
    assert snap.get(key) == 1
    assert snap.get('dl4j_collective_plan_bytes{intent="all_reduce"}') \
        == plan.bytes_moved()
    assert snap.get(
        'dl4j_collective_plan_launches{intent="all_reduce"}') \
        == plan.launches()


def test_collectives_panel_and_system_metrics():
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.ui.stats import collect_system_metrics

    telemetry.reset()
    scheduler.plan_for([jnp.zeros((8,), jnp.float32)], "all_reduce",
                       DATA_AXIS)
    ui = UIServer()
    html = ui.render_html()
    assert "Collectives (scheduler)" in html
    assert "dl4j_collective_plan_total" in html
    sysm = collect_system_metrics()
    assert sysm["collective_plans"]["plans_built"] >= 1
