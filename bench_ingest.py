"""Streaming-ingest experiment (round-2 verdict item #7): host-to-device
transfer bandwidth before and after a big program has run, and two
mitigations for fresh-batch training — chunked staging sizes and
double-buffered transfer-during-compute. Runs on the chip only.

Phases:
1. h2d bandwidth BEFORE any big program: one 38 MB uint8 batch,
   then a chunk-size sweep (1/4/38 MB pieces).
2. Compile + run the ResNet-50 batch-256 bf16 train step (the "big
   program").
3. h2d bandwidth AFTER: same sweep.
4. Fresh-batch training three ways: sequential (device_put then step),
   chunked staging, and double-buffered (a host thread device_puts
   batch k+1 while step k computes).
"""

import dataclasses
import json
import threading
import time

import numpy as np

BATCH = 256
IMG = 224
CLASSES = 1000
STEPS = 6


def _bw(ms, nbytes):
    return nbytes / 1e6 / (ms / 1e3)


def put_ms(arr, chunks=1):
    """Time device_put of arr (split into `chunks` row-chunks), synced."""
    import jax

    t0 = time.perf_counter()
    if chunks == 1:
        jax.device_put(arr).block_until_ready()
    else:
        pieces = np.array_split(arr, chunks, axis=0)
        jax.block_until_ready([jax.device_put(p) for p in pieces])
    return (time.perf_counter() - t0) * 1000.0


def sweep(rng, label, results):
    x = rng.integers(0, 256, (BATCH, IMG, IMG, 3), dtype=np.uint8)
    nb = x.nbytes
    for chunks in (1, 4, 16, 64):
        ms = min(put_ms(rng.integers(0, 256, x.shape, dtype=np.uint8),
                        chunks) for _ in range(2))
        results[f"{label}_h2d_{chunks}chunks_MBps"] = round(_bw(ms, nb), 1)


def main():
    import jax

    from deeplearning4j_tpu.util.device import banner, require_tpu

    dev = require_tpu("bench_ingest.py")
    print("# " + banner(dev), flush=True)
    rng = np.random.default_rng(0)
    results = {"device": dev}

    sweep(rng, "pre", results)

    # the big program
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    cfg = ResNet50(num_classes=CLASSES, height=IMG, width=IMG,
                   updater=Adam(learning_rate=1e-3)).conf()
    cfg = dataclasses.replace(cfg, compute_dtype="bfloat16")
    net = ComputationGraph(cfg).init()

    def fresh_ds():
        return DataSet(
            rng.integers(0, 256, (BATCH, IMG, IMG, 3), dtype=np.uint8),
            np.eye(CLASSES, dtype=np.float32)[
                rng.integers(0, CLASSES, BATCH)])

    warm = fresh_ds()
    for _ in range(3):
        net.fit_batch(warm)

    sweep(rng, "post", results)

    # ---- fresh-batch training, three ways ----
    def run_steps(feed):
        t0 = time.perf_counter()
        for i in range(STEPS):
            feed(i)
        jax.block_until_ready(net.params)  # sync tail
        dt = time.perf_counter() - t0
        return STEPS * BATCH / dt

    batches = [fresh_ds() for _ in range(STEPS + 1)]

    results["fresh_seq_img_per_s"] = round(run_steps(
        lambda i: net.fit_batch(batches[i])), 1)

    # chunked staging: device_put in 16 pieces, concat on device, fit
    import jax.numpy as jnp

    def chunked(i):
        ds = batches[i]
        pieces = [jax.device_put(p)
                  for p in np.array_split(ds.features, 16, axis=0)]
        ds.features = jnp.concatenate(pieces, axis=0)
        net.fit_batch(ds)

    batches = [fresh_ds() for _ in range(STEPS + 1)]
    results["fresh_chunked_img_per_s"] = round(run_steps(chunked), 1)

    # double-buffered: a host thread device_puts batch k+1 during step k
    batches = [fresh_ds() for _ in range(STEPS + 1)]
    staged = {0: jax.device_put(batches[0].features)}
    lock = threading.Lock()

    def stage(i):
        dev = jax.device_put(batches[i].features)
        with lock:
            staged[i] = dev

    def double_buffered(i):
        t = threading.Thread(target=stage, args=(i + 1,))
        t.start()
        with lock:
            f = staged.pop(i, None)
        if f is None:
            t.join()
            with lock:
                f = staged.pop(i, None)
        ds = batches[i]
        if f is not None:
            ds.features = f
        net.fit_batch(ds)
        t.join()

    results["fresh_double_buffered_img_per_s"] = round(
        run_steps(double_buffered), 1)

    # cached reference (the bench.py regime)
    cached = batches[0]
    for _ in range(2):
        net.fit_batch(cached)  # write-back caches device arrays
    results["cached_img_per_s"] = round(run_steps(
        lambda i: net.fit_batch(cached)), 1)

    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
