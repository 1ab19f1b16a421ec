"""Benchmark harness — runs on the TPU chip and refuses anything else.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Headline benchmark (SURVEY.md §6 / BASELINE.json): **ResNet-50 training
images/sec/chip** (dl4j-zoo ResNet50 equivalent, BASELINE config #2). The
reference ships no published numbers (BASELINE.md), so the first measured
value defines the baseline; vs_baseline = measured/recorded once
BENCH_BASELINE.json exists (written on first run, keyed per metric).

Protocol (BASELINE.md): median of >=3 timed runs, compile excluded, fixed
batch size, per-chip numbers. Whole-graph jitted train step (forward +
backward + Adam fused into one XLA program) — the TPU-native inversion of
the reference's per-op JNI dispatch.
"""

import json
import statistics
import time
from pathlib import Path

import numpy as np

N_BATCHES = 12

METRIC = "resnet50_train_images_per_sec_per_chip"
BATCH = 256
IMG = 224
CLASSES = 1000
RUNS = 5
BASELINE_FILE = Path(__file__).parent / "BENCH_BASELINE.json"


def main():
    import dataclasses

    import jax

    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.device import banner, require_tpu
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    dev = require_tpu("bench.py")
    print("# " + banner(dev), flush=True)
    # protocol v4: batch 256 + the bf16 compute policy (f32 master params,
    # bf16 forward/backward — conf.compute_dtype). Measured on v5e: device
    # step 64ms -> 34ms at batch 64, 115ms at batch 256 (2.2x throughput);
    # see BASELINE.md MFU table.
    model = ResNet50(num_classes=CLASSES, height=IMG, width=IMG,
                     updater=Adam(learning_rate=1e-3))
    # EXACT space-to-depth stem rewrite (MLPerf trick; equivalence pinned
    # by tests/test_zoo.py) — measured ~4% device fwd+bwd win, BASELINE.md
    # round-3 MFU section
    model.stem_space_to_depth = True
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    net = ComputationGraph(cfg).init()

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    rng = np.random.default_rng(42)
    # uint8 image batches: the realistic image-pipeline dtype. They cross
    # the host->device link as bytes (4x less traffic) and are
    # dequantized to [0,1] floats INSIDE the compiled step
    # (ImagePreProcessingScaler's math moved on-device).
    batches = [DataSet(
        rng.integers(0, 256, (BATCH, IMG, IMG, 3), dtype=np.uint8),
        np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, BATCH)])
        for _ in range(N_BATCHES)]
    it = ListDataSetIterator(batches)

    # warmup: first step compiles; two more reach the steady state
    for _ in range(3):
        net.fit_batch(batches[0])
    jax.block_until_ready(net.params)

    run_rates = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        # fit() overlaps host->device transfer and dispatch with compute
        # (bounded async depth); epoch end syncs
        net.fit(it, epochs=1)
        dt = time.perf_counter() - t0
        run_rates.append(N_BATCHES * BATCH / dt)

    images_per_sec = statistics.median(run_rates)

    baselines = {}
    if BASELINE_FILE.exists():
        baselines = json.loads(BASELINE_FILE.read_text())
        # migrate pre-graph-zoo flat format {"images_per_sec": ...} to the
        # per-metric format, preserving the recorded LeNet baseline
        if "images_per_sec" in baselines:
            baselines = {"lenet_mnist_train_images_per_sec_per_chip": {
                "value": baselines["images_per_sec"],
                "config": baselines.get("config", ""),
                "device": baselines.get("device", ""),
            }}
    if METRIC not in baselines:
        baselines[METRIC] = {
            "value": images_per_sec,
            "config": f"ResNet50 train, batch={BATCH}, {IMG}x{IMG}x3 uint8 in, "
                      f"{CLASSES} classes, f32 params + bf16 compute policy",
            "device": dev["kind"],
        }
        BASELINE_FILE.write_text(json.dumps(baselines, indent=2))
    base = baselines[METRIC]["value"]
    vs = images_per_sec / base if base else 1.0

    out = {
        "metric": METRIC,
        "value": round(images_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(vs, 3),
        "device": dev,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
