"""Capture and summarize an XProf trace of the production ResNet-50 train
step (this script regenerates BASELINE.md's per-op breakdown table).

Usage: ``python bench_xprof.py [outdir]`` on the chip only (it refuses
any other platform). Prints per-category
device-time aggregates from the Chrome trace the profiler writes.
"""

import collections
import dataclasses
import glob
import gzip
import json
import re
import sys

import numpy as np

STEPS = 3


def main():
    import jax

    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.device import banner, require_tpu
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    dev = require_tpu("bench_xprof.py")
    print("# " + banner(dev), flush=True)
    outdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/xprof_trace"
    model = ResNet50(num_classes=1000, height=224, width=224,
                     updater=Adam(learning_rate=1e-3))
    model.stem_space_to_depth = True
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    net = ComputationGraph(cfg).init()
    rng = np.random.default_rng(42)
    ds = DataSet(
        rng.integers(0, 256, (256, 224, 224, 3), dtype=np.uint8),
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, 256)])
    for _ in range(3):
        net.fit_batch(ds)

    jax.profiler.start_trace(outdir)
    for _ in range(STEPS):
        net._fit_batch_async(ds)
    jax.block_until_ready(net.params)
    jax.profiler.stop_trace()

    traces = sorted(glob.glob(outdir + "/plugins/profile/*/*.trace.json.gz"))
    with gzip.open(traces[-1]) as f:
        tr = json.load(f)
    ev = tr["traceEvents"]
    device_pids = {e["pid"] for e in ev
                   if e.get("ph") == "M" and e.get("name") == "process_name"
                   and "TPU" in str(e["args"].get("name"))}
    agg = collections.defaultdict(float)
    cnt = collections.Counter()
    step_ms = 0.0
    for e in ev:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        name = e["name"]
        if name.startswith("jit_"):
            step_ms += e.get("dur", 0) / 1000.0
            continue
        if re.fullmatch(r"\d+", name):
            continue
        cat = re.sub(r"[.\d]+$", "", name)
        agg[cat] += e.get("dur", 0) / 1000.0
        cnt[cat] += 1
    print(f"step wall on device: {step_ms / STEPS:.2f} ms "
          f"(x{STEPS} steps traced)")
    for k in sorted(agg, key=lambda k: -agg[k])[:15]:
        print(f"{agg[k] / STEPS:8.2f} ms/step  x{cnt[k] // STEPS:5d}  {k}")


if __name__ == "__main__":
    main()
