"""End-to-end A/B: ResNet-50 production train step with the fused
1x1-conv+BN-stats Pallas kernel (``FusedConvBN1x1``, 36 sites) vs the
unfused reference topology — the round-3 verdict's missing measurement
(the kernel was only ever timed standalone; a 20-step aggregate of the
production step is what the user pays for).

Protocol (BASELINE.md): batch 256 bf16 policy, device-cached batch
(write-back), 20 queued async steps + ONE ``block_until_ready`` per rep,
configs alternated A/B/A/B across reps so drift hits both arms,
min-of-reps reported. Runs on the chip only: ``python bench_fused_ab.py``.
"""

import dataclasses
import json
import time

import numpy as np

STEPS = 20
REPS = 3
BATCH = 256
IMG = 224
CLASSES = 1000


def build(fused):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    model = ResNet50(num_classes=CLASSES, height=IMG, width=IMG,
                     updater=Adam(learning_rate=1e-3))
    model.stem_space_to_depth = True
    model.fused_conv_bn = fused
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    return ComputationGraph(cfg).init()


def main():
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util.device import banner, require_tpu
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    dev = require_tpu("bench_fused_ab.py")
    print("# " + banner(dev), flush=True)
    rng = np.random.default_rng(42)
    ds = DataSet(
        rng.integers(0, 256, (BATCH, IMG, IMG, 3), dtype=np.uint8),
        np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, BATCH)])

    nets = {}
    nets["unfused"] = build(False)
    nets["fused"] = build(True)
    # same weights on both arms (remap is 1:1)
    import jax.numpy as jnp

    p, s = ResNet50.fused_param_remap(
        jax.tree_util.tree_map(lambda a: np.asarray(a).copy(),
                               dict(nets["unfused"].params)),
        jax.tree_util.tree_map(lambda a: np.asarray(a).copy(),
                               dict(nets["unfused"].state)))
    nets["fused"].params = jax.tree_util.tree_map(jnp.asarray, p)
    nets["fused"].state = jax.tree_util.tree_map(jnp.asarray, s)

    results = {"device": dev}
    for name, net in nets.items():
        for _ in range(3):  # compile + settle
            net.fit_batch(ds)
        results[f"{name}_times_ms"] = []

    for rep in range(REPS):
        for name, net in nets.items():
            t0 = time.perf_counter()
            for _ in range(STEPS):
                net._fit_batch_async(ds)
            jax.block_until_ready(net.params)
            dt = (time.perf_counter() - t0) * 1000.0 / STEPS
            results[f"{name}_times_ms"].append(round(dt, 2))
            print(f"rep {rep} {name}: {dt:.2f} ms/step", flush=True)

    for name in nets:
        results[f"{name}_ms_per_step"] = min(results[f"{name}_times_ms"])
    a = results["unfused_ms_per_step"]
    b = results["fused_ms_per_step"]
    results["delta_ms"] = round(a - b, 2)
    results["speedup"] = round(a / b, 4)
    results["img_per_sec_unfused"] = round(BATCH / a * 1000.0, 1)
    results["img_per_sec_fused"] = round(BATCH / b * 1000.0, 1)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
