"""Collective-scheduler A/B benchmark (ISSUE 12 acceptance): the SAME
training workloads — a bucketed data-parallel MLN and a ZeRO-sharded MLN
on the simulated 8-device mesh — driven through

  legacy     — the pre-scheduler primitives (inline copies of the old
               ``bucketed_psum`` / ``bucketed_psum_scatter`` /
               ``bucketed_all_gather`` loops, monkeypatched in), and
  scheduler  — the unified ``comms.scheduler`` route (plan-keyed AOT
               executables, densified buckets, probe-gated gather).

Per mode and workload it records: per-shard bytes moved and collective
launches (the ``dl4j_collective_*`` counters), bucket counts, host
dispatches, wall time per step, AOT-cache misses, and the scheduler's
plan-cache hits. Writes ``bench_collectives.json``; the committed A/B
record is ``BENCH_collectives_r01.json``. ``--smoke`` asserts the
scheduler route regresses NEITHER collective launches NOR bytes vs
legacy (the CPU proxy can't show the overlap win — XLA CPU runs
collectives sequentially — so the bar is "same schedule, no regression,
plans observable").

CPU-pinned: it counts launches and bytes on 8 virtual devices and needs
no chip.
"""

import argparse
import json
import os
import time


def _pin_cpu():
    """CPU plus the device-count flag, set before ``import jax``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _legacy_primitives():
    """Inline copies of the pre-scheduler exchange loops (the PR-9/PR-2
    implementations) — the baseline the scheduler must not regress."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.comms.scheduler import bucket_partition

    def legacy_psum(tree, axis_name, bucket_bytes=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            return tree
        if bucket_bytes is None or len(leaves) <= 1:
            return jax.tree_util.tree_unflatten(
                treedef, list(jax.lax.psum(tuple(leaves), axis_name)))
        sizes = [l.size * l.dtype.itemsize for l in leaves]
        out = [None] * len(leaves)
        pin = None
        for bucket in bucket_partition(sizes, int(bucket_bytes)):
            vals = tuple(leaves[i] for i in bucket)
            if pin is not None:
                pinned = jax.lax.optimization_barrier(vals + (pin,))
                vals = tuple(pinned[:-1])
            red = jax.lax.psum(vals, axis_name)
            pin = red[0]
            for i, r in zip(bucket, red):
                out[i] = r
        return jax.tree_util.tree_unflatten(treedef, out)

    def legacy_psum_scatter(tree, axis_name, bucket_bytes=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            return tree

        def scatter(vals):
            return jax.lax.psum_scatter(vals, axis_name,
                                        scatter_dimension=0, tiled=True)

        if bucket_bytes is None or len(leaves) <= 1:
            return jax.tree_util.tree_unflatten(
                treedef, list(scatter(tuple(leaves))))
        sizes = [l.size * l.dtype.itemsize for l in leaves]
        out = [None] * len(leaves)
        pin = None
        for bucket in bucket_partition(sizes, int(bucket_bytes)):
            vals = tuple(leaves[i] for i in bucket)
            if pin is not None:
                pinned = jax.lax.optimization_barrier(vals + (pin,))
                vals = tuple(pinned[:-1])
            red = scatter(vals)
            pin = red[0]
            for i, r in zip(bucket, red):
                out[i] = r
        return jax.tree_util.tree_unflatten(treedef, out)

    def legacy_all_gather(tree, axis_name, index, full_sizes,
                          bucket_bytes=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            return tree
        contribs = []
        for sl, full in zip(leaves, full_sizes):
            m = sl.shape[0]
            contribs.append(jax.lax.dynamic_update_slice(
                jnp.zeros((int(full),), sl.dtype), sl, (index * m,)))
        return legacy_psum(
            jax.tree_util.tree_unflatten(treedef, contribs),
            axis_name, bucket_bytes)

    return legacy_psum, legacy_psum_scatter, legacy_all_gather


def _net(seed=12345):
    from deeplearning4j_tpu.conf import Activation, InputType, WeightInit
    from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.conf.losses import LossMCXENT
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Adam(learning_rate=0.01))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_out=256, activation=Activation.RELU))
            .layer(DenseLayer(n_out=256, activation=Activation.RELU))
            .layer(DenseLayer(n_out=128, activation=Activation.RELU))
            .layer(OutputLayer(n_out=10, activation=Activation.SOFTMAX,
                               loss_fn=LossMCXENT()))
            .set_input_type(InputType.feed_forward(64))
            .build())
    return MultiLayerNetwork(conf).init()


def _counters():
    from deeplearning4j_tpu import telemetry

    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    bytes_total = sum(v for k, v in snap.items()
                      if k.startswith("dl4j_collective_bytes_total")
                      and not isinstance(v, dict))
    ops_total = sum(v for k, v in snap.items()
                    if k.startswith("dl4j_collective_ops_total")
                    and not isinstance(v, dict))
    return bytes_total, ops_total


def _run_workload(mode, workload, steps, batch):
    """One (mode, workload) leg: fresh net + wrapper, warm step, timed
    steps, counter deltas."""
    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.comms import scheduler
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    aot_cache.clear()
    telemetry.reset()
    telemetry.enable(sync=True)
    kw = ({"gradient_bucket_mb": 0.05} if workload == "dp_bucketed"
          else {"zero_optimizer": True, "gradient_bucket_mb": 0.05})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(steps * batch, 64)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, size=steps * batch)]
    net = _net()
    pw = ParallelWrapper(net, workers=8, prefetch_buffer=0, **kw)
    it = ArrayDataSetIterator(x[:batch], y[:batch], batch=batch)
    pw.fit(it, epochs=1)                       # warm: compile + stage
    plan_stats0 = scheduler.stats()
    b0, o0 = _counters()
    misses0 = aot_cache.stats()["misses"]
    it = ArrayDataSetIterator(x, y, batch=batch)
    t0 = time.perf_counter()
    pw.fit(it, epochs=1)
    wall = time.perf_counter() - t0
    b1, o1 = _counters()
    plan_stats1 = scheduler.stats()
    telemetry.disable()
    buckets = {
        k.split('op="')[1].rstrip('"}'): v
        for k, v in telemetry.REGISTRY.snapshot(
            run_collectors=False).items()
        if k.startswith("dl4j_collective_buckets")}
    return {
        "mode": mode,
        "workload": workload,
        "steps": steps,
        "dispatches": steps,
        "collective_bytes": b1 - b0,
        "collective_launches": o1 - o0,
        "buckets_per_exchange": buckets,
        "wall_s_per_step": round(wall / steps, 6),
        "recompiles_after_warmup": aot_cache.stats()["misses"] - misses0,
        "plan_cache_hits": (plan_stats1["plan_cache_hits"]
                            - plan_stats0["plan_cache_hits"]),
        "plans_built": plan_stats1["plans_built"],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--out", default="bench_collectives.json")
    ap.add_argument("--smoke", action="store_true",
                    help="assert scheduler regresses neither launches "
                         "nor bytes vs legacy")
    args = ap.parse_args()
    _pin_cpu()

    from unittest import mock

    from deeplearning4j_tpu.parallel import compression, wrapper

    legacy_psum, legacy_scatter, legacy_gather = _legacy_primitives()
    rows = []
    for workload in ("dp_bucketed", "zero"):
        for mode in ("legacy", "scheduler"):
            patches = []
            if mode == "legacy":
                patches = [
                    mock.patch.object(wrapper, "bucketed_psum",
                                      legacy_psum),
                    mock.patch.object(wrapper, "bucketed_psum_scatter",
                                      legacy_scatter),
                    mock.patch.object(compression, "bucketed_psum",
                                      legacy_psum),
                    mock.patch.object(compression, "bucketed_all_gather",
                                      legacy_gather),
                ]
            for p in patches:
                p.start()
            try:
                rows.append(_run_workload(mode, workload, args.steps,
                                          args.batch))
            finally:
                for p in patches:
                    p.stop()
            print(json.dumps(rows[-1], indent=2))

    by = {(r["workload"], r["mode"]): r for r in rows}
    summary = {}
    for workload in ("dp_bucketed", "zero"):
        leg, sch = by[(workload, "legacy")], by[(workload, "scheduler")]
        summary[workload] = {
            "launches_legacy": leg["collective_launches"],
            "launches_scheduler": sch["collective_launches"],
            "bytes_legacy": leg["collective_bytes"],
            "bytes_scheduler": sch["collective_bytes"],
            "step_wall_ratio_sched_over_legacy": round(
                sch["wall_s_per_step"] / max(leg["wall_s_per_step"],
                                             1e-9), 3),
        }
    out = {"rows": rows, "summary": summary,
           "note": ("CPU proxy: XLA CPU serializes collectives, so the "
                    "overlap/densify win does not show in wall time "
                    "here; the bar is schedule parity — launches and "
                    "bytes no worse than the legacy primitives, zero "
                    "recompiles after warmup, plans observable.")}
    print(json.dumps(summary, indent=2))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")

    if args.smoke:
        for workload, s in summary.items():
            assert s["launches_scheduler"] <= s["launches_legacy"], \
                f"{workload}: scheduler issues more collectives"
            assert s["bytes_scheduler"] <= s["bytes_legacy"], \
                f"{workload}: scheduler moves more bytes"
        for r in rows:
            assert r["recompiles_after_warmup"] == 0, \
                f"{r['mode']}/{r['workload']}: recompiled after warmup"
        print("SMOKE OK: no regression in launches or bytes; "
              "zero recompiles after warmup")


if __name__ == "__main__":
    main()
