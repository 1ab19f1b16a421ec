"""ResNet-50 training-step breakdown on the chip (the measured basis
for BASELINE.md's MFU analysis — re-run this script to regenerate). The
first line of output names the device jax ran on; timings from anything
but a TPU are a CPU proxy of the control flow, not device numbers.

Decomposes the batch-256 bf16 train step into:
- full step (fwd + bwd + Adam, donated buffers, dependent-chain sync);
- forward-only loss and value_and_grad (updater cost by subtraction);
- per-PREFIX forward and forward+backward costs at each stage boundary
  (stem, res2..res5, head) — the per-stage cost is the difference of
  consecutive prefixes, so transposed-bwd-conv costs land in the stage
  that owns them.

Protocol: every closure is jitted; each measurement queues N identical
calls then waits once (``block_until_ready`` on the last result), min of
3 reps. Queuing identical calls is safe here because the inputs are the same arrays
every call (the round-1 OOM-stall came from chained UN-donated train
steps holding N params trees alive). Backward closures return a scalar
REDUCED FROM THE GRADS — returning only the loss value lets XLA
dead-code-eliminate the whole backward pass (the first version of this
script did exactly that and measured fwd+bwd == fwd).
"""

import argparse
import dataclasses
import json
import time

import numpy as np

from deeplearning4j_tpu.telemetry import PHASES

PHASE_INGEST, PHASE_COMPUTE, PHASE_GRAD_SYNC, PHASE_HOST_GAP = PHASES

# --phases / --fused-steps output rows, keyed off the framework's
# canonical phase names (deeplearning4j_tpu.telemetry.PHASES) so the
# bench breakdown and the telemetry spans cannot drift apart — pinned by
# tests/test_telemetry.py
PHASE_ROWS = {
    PHASE_INGEST: (f"{PHASE_INGEST}_h2d", f"{PHASE_INGEST}_after_overlap"),
    PHASE_COMPUTE: ("step_cached_fit", "step_streaming", "step_ring"),
    PHASE_GRAD_SYNC: (PHASE_GRAD_SYNC,),
    PHASE_HOST_GAP: (f"{PHASE_HOST_GAP}_per_step_k1",
                     f"{PHASE_HOST_GAP}_per_step_fused"),
}

BATCH = 256
IMG = 224
CLASSES = 1000
N = 6

BOUNDARIES = ["stem_bn", "stem_pool", "res2c_relu", "res3d_relu",
              "res4f_relu", "res5c_relu", "avgpool"]


def _sync(x):
    import jax

    jax.block_until_ready(x)


def timed(fn, *args, n=N, reps=3):
    out = fn(*args)
    _sync(out)  # compile + settle
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        _sync(out)
        ms = (time.perf_counter() - t0) * 1000.0 / n
        best = min(best, ms)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--img", type=int, default=IMG,
                    help="input resolution (default 224; shrink for "
                         "CPU-proxy runs of --fused-steps/--phases)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--s2d", action="store_true",
                    help="exact space-to-depth stem rewrite (MLPerf trick)")
    ap.add_argument("--quick", action="store_true",
                    help="only train_step / fwd / fwd+bwd (skip prefixes)")
    ap.add_argument("--phases", action="store_true",
                    help="per-phase step breakdown (ingest / compute / "
                         "sync overlap) instead of the prefix sweep")
    ap.add_argument("--fused-steps", type=int, default=0,
                    help="K-step fused A/B: train the same batch stream "
                         "through the per-step path (K=1) and the fused "
                         "lax.scan driver (fused_steps=K), reporting the "
                         "telemetry-measured host gap per step, img/s, "
                         "recompiles after the first super-step, and the "
                         "K=1 vs K final-params max |delta| (0.0 = "
                         "bit-identical; conv bodies may show ulp-level "
                         "compilation variance — docs/observability.md)")
    ap.add_argument("--health", action="store_true",
                    help="enable the in-graph health guards (WARN policy) "
                         "so train_step / --phases rows measure the "
                         "guarded step — compare against a run without "
                         "the flag for the guard overhead (<5%% target)")
    ap.add_argument("--kernels", action="store_true",
                    help="Pallas kernel-registry A/B: autotune every "
                         "routable envelope at this batch, then train "
                         "fresh nets through stock XLA and the "
                         "use_kernels path, reporting img/s per mode, "
                         "recompiles after warmup (must be 0), and the "
                         "final-params max |delta|. Off-TPU the kernels "
                         "run via the Pallas interpreter — correctness "
                         "proxy only, not a speed measurement "
                         "(docs/kernels.md)")
    args = ap.parse_args()
    batch = args.batch
    img = int(args.img)

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.device import banner, describe
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    dev = describe()
    print("# " + banner(dev), flush=True)

    if args.health:
        from deeplearning4j_tpu.telemetry import health

        health.configure(policy=health.AnomalyPolicy.WARN)

    model = ResNet50(num_classes=CLASSES, height=img, width=img,
                     updater=Adam(learning_rate=1e-3))
    model.stem_space_to_depth = bool(args.s2d)
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    net = ComputationGraph(cfg).init()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (batch, img, img, 3),
                                 dtype=np.uint8))
    y = jnp.asarray(np.eye(CLASSES, dtype=np.float32)[
        rng.integers(0, CLASSES, batch)])
    lmask = jnp.ones((batch,), jnp.float32)

    rows = {}

    # ---- Pallas kernel-registry A/B (ROADMAP item 5) ---------------------
    if args.kernels:
        from deeplearning4j_tpu import kernels as kern
        from deeplearning4j_tpu.datasets.dataset import DataSet as _DS
        from deeplearning4j_tpu.optimize import aot_cache

        n_steps = 6
        cfg_on = dataclasses.replace(cfg, use_kernels=True)
        tuned = kern.autotune_model(cfg_on, batch, max_candidates=8)
        rows["kernels_tuned_envelopes"] = len(tuned)
        print(f"# kernels backend={kern.backend()} "
              f"tuned={len(tuned)} envelopes")

        def run(cfgx, label):
            netx = ComputationGraph(cfgx).init()  # fresh net per mode
            ds = _DS(np.asarray(x), np.asarray(y))
            netx.fit_batch(ds)  # compile + settle
            netx.fit_batch(ds)
            miss0 = aot_cache.stats()["misses"]
            t0 = time.perf_counter()
            for _ in range(n_steps):
                netx._fit_batch_async(ds)
            _ = float(netx.score_value)
            wall = time.perf_counter() - t0
            rows[f"imgs_per_sec_{label}"] = n_steps * batch / wall
            rows[f"recompiles_after_warmup_{label}"] = (
                aot_cache.stats()["misses"] - miss0)
            return netx

        net_a = run(cfg, "xla")
        net_b = run(cfg_on, "kernels")
        rows["kernels_params_max_delta"] = max(
            float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                  - jnp.asarray(b, jnp.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(net_a.params),
                            jax.tree_util.tree_leaves(net_b.params)))
        assert rows["recompiles_after_warmup_xla"] == 0
        assert rows["recompiles_after_warmup_kernels"] == 0
        if args.json:
            print(json.dumps({kk: round(v, 4) for kk, v in rows.items()}))
            return
        print(f"\nResNet-50 batch {batch} kernel-registry A/B "
              f"({n_steps} steps/mode)\n")
        for kk, v in rows.items():
            print(f"{kk:>32} {v:>10.4f}")
        return

    # ---- K-step fused A/B (round 11): host gap per step, before/after ----
    if args.fused_steps:
        from deeplearning4j_tpu import telemetry
        from deeplearning4j_tpu.datasets.dataset import DataSet as _DS
        from deeplearning4j_tpu.datasets.iterators import (
            ListDataSetIterator,
        )
        from deeplearning4j_tpu.optimize import aot_cache

        k = int(args.fused_steps)
        n_super = 4
        n_steps = k * n_super
        rngf = np.random.default_rng(11)
        base = [(rngf.integers(0, 256, (batch, img, img, 3),
                               dtype=np.uint8),
                 np.eye(CLASSES, dtype=np.float32)[
                     rngf.integers(0, CLASSES, batch)])
                for _ in range(n_steps)]

        def stream():
            # fresh numpy copies per run: write_back migrates arrays to
            # device, and both modes must stage the same host stream
            return ListDataSetIterator(
                [_DS(np.array(f), np.array(l)) for f, l in base])

        def run(kk, label):
            netx = ComputationGraph(cfg).init()
            netx.fit(stream(), epochs=1, fused_steps=kk)  # compile+settle
            miss0 = aot_cache.stats()["misses"]
            # throughput epoch: fully async pipeline, telemetry off
            t0 = time.perf_counter()
            netx.fit(stream(), epochs=1, fused_steps=kk)
            jax.block_until_ready(netx.params)
            wall = time.perf_counter() - t0
            rows[f"imgs_per_sec_{label}"] = n_steps * batch / wall
            rows[f"recompiles_after_warmup_{label}"] = (
                aot_cache.stats()["misses"] - miss0)
            # host-gap epoch: sync-mode spans block on each dispatch's
            # device result, so the gap between spans is PURE host
            # dispatch-loop work (no device overlap / thread starvation)
            telemetry.reset()
            telemetry.enable(sync=True)
            netx.fit(stream(), epochs=1, fused_steps=kk)
            jax.block_until_ready(netx.params)
            telemetry.disable()
            evs = [e for e in telemetry.events()
                   if e["name"] == PHASE_HOST_GAP]
            gap_ms = sum(e["duration_ns"] for e in evs) / 1e6
            gsteps = sum(e.get("attrs", {}).get("steps", 1) for e in evs)
            rows[f"{PHASE_HOST_GAP}_per_step_{label}"] = (
                gap_ms / max(gsteps, 1))
            return netx

        net1 = run(1, "k1")
        netk = run(k, "fused")
        # the acceptance invariant: K=1 and K=K train IDENTICALLY on the
        # same stream (max |param delta| 0.0 = bit-identical)
        rows["fused_params_max_delta"] = max(
            float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                  - jnp.asarray(b, jnp.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(net1.params),
                            jax.tree_util.tree_leaves(netk.params)))
        if args.json:
            print(json.dumps({kk: round(v, 4) for kk, v in rows.items()}))
            return
        print(f"\nResNet-50 batch {batch} fused-{k} A/B "
              f"({n_steps} steps, {n_super} super-steps)\n")
        for kk, v in rows.items():
            print(f"{kk:>32} {v:>10.4f}")
        return

    params, state = net.params, net.state

    # ---- full production step (donated, dependent chain via fit path) ----
    from deeplearning4j_tpu.datasets.dataset import DataSet

    ds = DataSet(np.asarray(x), np.asarray(y))
    net.fit_batch(ds)  # compile + settle
    net.fit_batch(ds)
    t0 = time.perf_counter()
    for _ in range(N):
        net.fit_batch(ds)  # fit_batch syncs (float(loss)) per call
    rows["train_step"] = (time.perf_counter() - t0) * 1000.0 / N
    params, state = net.params, net.state  # post-donation trees

    # ---- per-phase breakdown: ingest / compute / sync-after-overlap ----
    # (round 6 — the denominator for the "<3% of step time in gradient
    # sync + ingest" criterion; on one chip gradient sync is 0 and the
    # ingest share is whatever the double-buffered ring fails to hide)
    if args.phases:
        from deeplearning4j_tpu.datasets.dataset import DataSet as _DS
        from deeplearning4j_tpu.datasets.iterators import (
            ListDataSetIterator,
        )
        from deeplearning4j_tpu.datasets.prefetch import DeviceRingIterator

        rng2 = np.random.default_rng(7)
        n_stream = 6
        fresh = [
            _DS(rng2.integers(0, 256, (batch, img, img, 3),
                              dtype=np.uint8),
                np.eye(CLASSES, dtype=np.float32)[
                    rng2.integers(0, CLASSES, batch)])
            for _ in range(n_stream)]

        # raw host->device transfer cost of one uint8 batch (fresh buffer
        # per rep so no caching), value-synced
        ing = []
        for ds_f in fresh[:3]:
            t0 = time.perf_counter()
            dev = jax.device_put(np.asarray(ds_f.features))
            _sync(dev)
            ing.append((time.perf_counter() - t0) * 1000.0)
        rows[f"{PHASE_INGEST}_h2d"] = min(ing)

        def stream_ms(iterator):
            t0 = time.perf_counter()
            net.fit(iterator, epochs=1)
            _ = net.score_value  # sync
            return ((time.perf_counter() - t0) * 1000.0) / n_stream

        # compute baseline through the SAME fit loop, batches already
        # device-resident (write_back migrated them on a priming epoch) —
        # so the streaming/ring deltas isolate INGEST, not fit-loop host
        # overhead vs a bare-jit dispatch
        cached = ListDataSetIterator(fresh)
        net.fit(cached, epochs=1)  # priming epoch: migrate + settle
        rows["step_cached_fit"] = stream_ms(cached)
        # sequential streaming: transfer serialized with the step
        rows["step_streaming"] = stream_ms(ListDataSetIterator([
            _DS(np.array(d.features), np.array(d.labels))
            for d in fresh]))
        # double-buffered ring: batch N+1's device_put overlaps step N
        rows["step_ring"] = stream_ms(DeviceRingIterator(
            ListDataSetIterator([
                _DS(np.array(d.features), np.array(d.labels))
                for d in fresh]), depth=2, donate=True))

        comp = rows["step_cached_fit"]
        ring = rows["step_ring"]
        rows[f"{PHASE_INGEST}_after_overlap"] = max(0.0, ring - comp)
        rows[PHASE_GRAD_SYNC] = 0.0  # single chip: no DP collective
        denom = max(ring, comp)
        rows["sync_plus_ingest_pct_of_step"] = round(
            100.0 * (rows[PHASE_GRAD_SYNC]
                     + rows[f"{PHASE_INGEST}_after_overlap"])
            / denom, 2)

    if args.phases:
        if args.json:
            print(json.dumps({k: round(v, 2) for k, v in rows.items()}))
            return
        print(f"\nResNet-50 batch {batch} per-PHASE breakdown (ms)\n")
        for k, v in rows.items():
            print(f"{k:>28} {v:>9.2f}")
        print("\nstep share of (grad sync + unhidden ingest): "
              f"{rows['sync_plus_ingest_pct_of_step']:.2f}%")
        return

    # ---- forward-only loss + value_and_grad ----
    def loss_fn(p, feats):
        loss, _ = net._loss(p, state, (feats,), (y,), (None,), (lmask,),
                            rng=None, train=True)
        return loss

    def grad_scalar(vg_out):
        # depend on EVERY grad leaf or XLA DCEs the backward pass
        v, g = vg_out
        return v + sum(jnp.sum(l.astype(jnp.float32))
                       for l in jax.tree_util.tree_leaves(g))

    fwd = jax.jit(loss_fn)
    rows["forward_loss"] = timed(fwd, params, x)

    vg = jax.jit(lambda p, f: grad_scalar(jax.value_and_grad(loss_fn)(p, f)))
    rows["forward_backward"] = timed(vg, params, x)

    # ---- per-prefix forward / forward+backward ----
    def prefix_fn(boundary):
        keep = set()
        for name in net._topo:
            keep.add(name)
            if name == boundary:
                break
        skip = set(net._topo) - keep

        def run(p, feats):
            feats = net._dequant(feats, 0)
            fp, (feats,) = net._fwd_cast(p, (feats,))
            acts, _, _ = net._forward(fp, state, (feats,), train=True,
                                      rng=None, skip=skip)
            return acts[boundary].astype(jnp.float32).sum()

        return run

    for b in ([] if args.quick else BOUNDARIES):
        f = prefix_fn(b)
        rows[f"fwd_to_{b}"] = timed(jax.jit(f), params, x)
        g = jax.jit(lambda p, feats, _f=f: grad_scalar(
            jax.value_and_grad(_f)(p, feats)))
        rows[f"fwdbwd_to_{b}"] = timed(g, params, x)

    if args.json:
        print(json.dumps({k: round(v, 2) for k, v in rows.items()}))
        return

    print(f"\nResNet-50 batch {batch} bf16 breakdown (ms; {N} queued "
          f"calls/sync, min of 3 reps)\n")
    print(f"{'probe':>22} {'ms':>9}")
    for k, v in rows.items():
        print(f"{k:>22} {v:>9.1f}")
    if not args.quick:
        print("\nper-stage deltas (prefix differences):")
        prev_f = prev_b = 0.0
        for b in BOUNDARIES:
            fv, bv = rows[f"fwd_to_{b}"], rows[f"fwdbwd_to_{b}"]
            print(f"{b:>22} fwd {fv - prev_f:>7.1f}  "
                  f"fwd+bwd {bv - prev_b:>7.1f}")
            prev_f, prev_b = fv, bv
    upd = rows["train_step"] - rows["forward_backward"]
    print(f"\nupdater+overheads (train_step - fwd_bwd): {upd:.1f} ms")


if __name__ == "__main__":
    main()
