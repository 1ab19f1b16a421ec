"""Conv-efficiency experiment matrix (round-5 verdict item #1).

The round-4 XProf trace put the ResNet-50 step's conv share at ~62-66 ms
against a ~16 ms bf16 roofline (~26% MXU over 234 fusions, largest
3.2 ms) and BASELINE.md called "that is XLA's conv efficiency" a
hypothesis. This script turns the hypothesis into measurements — the
committed experiment matrix the verdict asked for:

  1. **Batch sweep** (128 / 256 / 384 / 512) under the full round-4
     production config (bf16 policy, s2d stem, one-pass BN, uint8
     device-cached batch) — does more parallelism lift conv MXU
     occupancy, and what batch maximizes img/s?
  2. **XLA TPU flag probe** — `--xla_tpu_scoped_vmem_limit_kib` (bigger
     scoped vmem lets the Mosaic/XLA scheduler pipeline deeper) and
     latency-hiding-scheduler toggles, applied via child-process env
     (XLA flags are read at backend init, so each cell re-execs).
  3. **NCHW-vs-NHWC layout probe** — the dominant ResNet-50 conv shapes
     timed standalone (fwd and fwd+bwd) in both data layouts, bf16,
     isolating XLA's per-layout conv emitter efficiency from the
     end-to-end graph.

Protocol per cell (BASELINE.md): 3 compile/settle steps, then REPS x
STEPS queued async steps with ONE ``block_until_ready``, min-of-reps —
identical to bench_fused_ab.py so cells are comparable with the round-4
A/B numbers. Runs on the chip only: ``python bench_conv_matrix.py``. The
chip belongs to one process at a time, so the parent never touches jax:
it spawns one child per cell, each child names its device and refuses
anything but a TPU, and results land in bench_conv_matrix.json.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

STEPS = 20
REPS = 3
IMG = 224
CLASSES = 1000
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "bench_conv_matrix.json")

# bf16 matrix-unit peak in FLOP/s by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s). A kind that is not here is an
# error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_bf16(dev):
    if dev["kind"] not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no bf16 peak recorded for device_kind {dev['kind']!r}; add "
            f"it to PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[dev["kind"]]


def build_net(batch):
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    model = ResNet50(num_classes=CLASSES, height=IMG, width=IMG,
                     updater=Adam(learning_rate=1e-3))
    model.stem_space_to_depth = True
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    return ComputationGraph(cfg).init()


def child_train(batch):
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util.device import banner, require_tpu

    dev = require_tpu("bench_conv_matrix.py")
    print(f"# {banner(dev)} batch={batch} "
          f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}",
          file=sys.stderr, flush=True)
    net = build_net(batch)
    rng = np.random.default_rng(42)
    ds = DataSet(
        rng.integers(0, 256, (batch, IMG, IMG, 3), dtype=np.uint8),
        np.eye(CLASSES, dtype=np.float32)[
            rng.integers(0, CLASSES, batch)])
    for _ in range(3):
        net.fit_batch(ds)
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            net._fit_batch_async(ds)
        jax.block_until_ready(net.params)
        times.append((time.perf_counter() - t0) * 1000.0 / STEPS)
    ms = min(times)
    print(json.dumps({"device": dev,
                      "ms_per_step": round(ms, 2),
                      "img_per_sec": round(batch / ms * 1000.0, 1),
                      "times_ms": [round(t, 2) for t in times]}))


# Dominant ResNet-50 conv shapes (NHWC: B,H,W,C x kh,kw,Cin,Cout). The
# 3x3s carry most FLOPs; the 1x1s dominate by count (the trace's 234
# fusions). Batch fixed at 256 to match the production cell.
PROBE_SHAPES = [
    ("res2_3x3", (56, 56, 64), (3, 3, 64, 64)),
    ("res3_3x3", (28, 28, 128), (3, 3, 128, 128)),
    ("res4_3x3", (14, 14, 256), (3, 3, 256, 256)),
    ("res5_3x3", (7, 7, 512), (3, 3, 512, 512)),
    ("res4_1x1_expand", (14, 14, 256), (1, 1, 256, 1024)),
    ("res4_1x1_reduce", (14, 14, 1024), (1, 1, 1024, 256)),
]


def child_layout(batch=256, chain=24):
    """Per-shape conv timing via IN-JIT chaining: one dispatch runs
    ``chain`` dependent conv applications (y_{i+1} = conv(y_i, W)), so
    the cost of launching a dispatch is amortized over the chain and
    does not drown a sub-millisecond conv. The 1x1 expand/reduce pair chains as
    reduce(expand(x)). An im2col+dot_general variant of the 3x3 measures
    whether XLA's conv emitter leaves MXU matmul throughput on the
    table at the cost of 9x activation traffic."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.util.device import require_tpu

    dev = require_tpu("bench_conv_matrix.py")
    peak = _peak_bf16(dev)
    results = {"device": dev}

    def timed_chain(fn, x, label, n_ops):
        f = jax.jit(fn)
        jax.block_until_ready(f(x))
        times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            outs = [f(x) for _ in range(4)]
            jax.block_until_ready(outs)
            times.append((time.perf_counter() - t0) * 1000.0
                         / (4 * n_ops))
        results[label] = round(min(times), 3)

    for name, xs, ks in PROBE_SHAPES:
        h, w, cin = xs
        kh, kw, _, cout = ks
        rng = np.random.default_rng(0)
        x_nhwc = jnp.asarray(rng.normal(size=(batch, h, w, cin)),
                             jnp.bfloat16)
        scale = 1.0 / np.sqrt(kh * kw * cin)
        k_hwio = jnp.asarray(rng.normal(size=ks) * scale, jnp.bfloat16)
        x_nchw = jnp.transpose(x_nhwc, (0, 3, 1, 2))
        k_oihw = jnp.transpose(k_hwio, (3, 2, 0, 1))
        paired = cin != cout
        if paired:
            k2_hwio = jnp.asarray(
                rng.normal(size=(kh, kw, cout, cin)) / np.sqrt(
                    kh * kw * cout), jnp.bfloat16)
            k2_oihw = jnp.transpose(k2_hwio, (3, 2, 0, 1))

        def chain_fwd(x, k, k2, dn, n):
            def body(_, y):
                y = jax.lax.conv_general_dilated(
                    y, k, (1, 1), "SAME", dimension_numbers=dn)
                if k2 is not None:
                    y = jax.lax.conv_general_dilated(
                        y, k2, (1, 1), "SAME", dimension_numbers=dn)
                return y
            # static bounds -> scan lowering -> reverse-differentiable
            return jax.lax.fori_loop(0, n, body, x)

        def chain_bwd(x, k, k2, dn, n):
            # d(chain)/dk: fwd chain + full reverse sweep in one
            # program; shorter chain than fwd — the scan saves one
            # activation residual per iteration (res2's 103 MB x 24
            # would brush the 16 GB HBM)
            def loss(kk):
                return jnp.sum(
                    chain_fwd(x, kk, k2, dn, n).astype(jnp.float32))
            return jax.grad(loss)(k)

        for layout, x, k, k2, dn in (
                ("nhwc", x_nhwc, k_hwio,
                 k2_hwio if paired else None, ("NHWC", "HWIO", "NHWC")),
                ("nchw", x_nchw, k_oihw,
                 k2_oihw if paired else None, ("NCHW", "OIHW", "NCHW"))):
            # close over k/k2/dn (dn is a static string tuple — passing
            # it through jit as an argument would fail to trace)
            nf = chain // 2 if paired else chain
            nb = max(nf // 3, 4)
            timed_chain(lambda x, k=k, k2=k2, dn=dn, n=nf:
                        chain_fwd(x, k, k2, dn, n),
                        x, f"{name}_{layout}_fwd_ms",
                        n_ops=nf * (2 if paired else 1))
            timed_chain(lambda x, k=k, k2=k2, dn=dn, n=nb:
                        chain_bwd(x, k, k2, dn, n),
                        x, f"{name}_{layout}_fwd+bwd_ms",
                        n_ops=nb * (2 if paired else 1))

        if (kh, kw) == (3, 3):
            # im2col: patches [B*H*W, 9*Cin] @ [9*Cin, Cout]
            kmat = k_hwio.reshape(kh * kw * cin, cout)

            def im2col_fwd(x, kmat=kmat):
                def body(_, y):
                    p = jax.lax.conv_general_dilated_patches(
                        y, (kh, kw), (1, 1), "SAME",
                        dimension_numbers=("NHWC", "HWIO", "NHWC"))
                    z = jax.lax.dot_general(
                        p.reshape(-1, kh * kw * cin), kmat,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return z.reshape(batch, h, w, cout).astype(
                        jnp.bfloat16)
                return jax.lax.fori_loop(0, chain, body, x)

            timed_chain(im2col_fwd, x_nhwc, f"{name}_im2col_fwd_ms",
                        n_ops=chain)

        # bf16 MXU roofline (fwd): 2*B*H*W*Cin*Cout*kh*kw FLOPs at this
        # device_kind's bf16 peak
        flops = 2 * batch * h * w * cin * cout * kh * kw
        results[f"{name}_roofline_fwd_ms"] = round(
            flops / peak * 1000.0, 3)
        results[f"{name}_mxu_pct_nhwc_fwd"] = round(
            100.0 * results[f"{name}_roofline_fwd_ms"]
            / max(results[f"{name}_nhwc_fwd_ms"], 1e-9), 1)
        print(f"# {name}: {json.dumps({k2: v for k2, v in results.items() if k2.startswith(name)})}",
              file=sys.stderr, flush=True)

    # MXU reference: chained square bf16 matmuls — what this chip can
    # ACTUALLY sustain, the denominator that decides
    # whether the conv numbers above are "XLA leaving 4x on the table"
    # or "the achievable roof". 4096^3: 137 GFLOP/op.
    for dim in (2048, 4096, 8192):
        a = jnp.asarray(np.random.default_rng(2).normal(
            size=(dim, dim)) / np.sqrt(dim), jnp.bfloat16)

        def mm_chain(x, n=chain):
            def body(_, y):
                return jax.lax.dot_general(
                    y, a, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.bfloat16)
            return jax.lax.fori_loop(0, n, body, x)

        timed_chain(mm_chain, a, f"matmul{dim}_fwd_ms", n_ops=chain)
        rl = 2 * dim ** 3 / peak * 1000.0
        results[f"matmul{dim}_roofline_ms"] = round(rl, 3)
        results[f"matmul{dim}_mxu_pct"] = round(
            100.0 * rl / max(results[f"matmul{dim}_fwd_ms"], 1e-9), 1)
        print(f"# matmul{dim}: {results[f'matmul{dim}_fwd_ms']} ms "
              f"({results[f'matmul{dim}_mxu_pct']}% of the "
              f"{dev['kind']} bf16 peak)",
              file=sys.stderr, flush=True)
    print(json.dumps(results))


def child_kernels(batch=8, img=8, steps=8, out_path=None, smoke=False):
    """Pallas kernel-registry A/B on a small fused-conv net (ROADMAP
    item 5): autotune every routable envelope at this batch, then train
    FRESH nets per mode — stock XLA vs ``use_kernels`` — on the same
    stream, reporting img/s per mode, recompiles-after-warmup (asserted
    0 for both), and the final-params max |delta| (the parity record).

    Sized for the CPU proxy: off-TPU the kernels execute through the
    Pallas INTERPRETER, so the kernels-mode img/s measures the
    interpreter, not the MXU — the committed JSON records parity, the
    zero-recompile contract, and the autotuner machinery; speed claims
    need the TPU backend (docs/kernels.md states the caveat)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import kernels as kern
    from deeplearning4j_tpu.conf import inputs as it
    from deeplearning4j_tpu.conf.activations import Activation
    from deeplearning4j_tpu.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.conf.layers_cnn import FusedConvBN1x1
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize import aot_cache

    def conf(use_k):
        b = NeuralNetConfiguration.builder().seed(42).updater(
            Adam(learning_rate=1e-3))
        if use_k:
            b = b.use_kernels()
        return (b.list()
                .layer(FusedConvBN1x1(n_out=16,
                                      activation=Activation.RELU))
                .layer(FusedConvBN1x1(n_out=16,
                                      activation=Activation.RELU))
                .layer(DenseLayer(n_out=32, activation=Activation.RELU))
                .layer(OutputLayer(n_out=10))
                .set_input_type(it.Convolutional(img, img, 8))
                .build())

    rng = np.random.default_rng(0)
    X = rng.normal(size=(batch, img, img, 8)).astype(np.float32)
    Y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]

    results = {"backend": kern.backend(), "batch": batch, "img": img,
               "steps": steps}
    t0 = time.perf_counter()
    tuned = kern.autotune_model(conf(True), batch, max_candidates=8)
    results["autotune_s"] = round(time.perf_counter() - t0, 2)
    results["tuned_envelopes"] = len(tuned)
    results["winners"] = {r.env_key: list(r.tiling) for r in tuned}

    def run(use_k, label):
        net = MultiLayerNetwork(conf(use_k)).init()
        ds = DataSet(X.copy(), Y.copy())
        net.fit_batch(ds)  # compile + settle
        net.fit_batch(ds)
        miss0 = aot_cache.stats()["misses"]
        t0 = time.perf_counter()
        for _ in range(steps):
            net._fit_batch_async(ds)
        _ = float(net.score_value)
        wall = time.perf_counter() - t0
        results[f"img_per_sec_{label}"] = round(steps * batch / wall, 1)
        results[f"recompiles_after_warmup_{label}"] = (
            aot_cache.stats()["misses"] - miss0)
        return net

    net_a = run(False, "xla")
    net_b = run(True, "kernels")
    results["params_max_delta"] = max(
        float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                              - jnp.asarray(b, jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(net_a.params),
                        jax.tree_util.tree_leaves(net_b.params)))
    results["note"] = (
        "CPU proxy: kernels ran through the Pallas interpreter — "
        "img_per_sec_kernels measures the interpreter, not the MXU; "
        "the record here is parity + zero recompiles + the tuned "
        "winner set. Re-run on a TPU backend for speed."
        if results["backend"] != "tpu" else
        "TPU backend: real Mosaic lowering.")
    assert results["recompiles_after_warmup_xla"] == 0, results
    assert results["recompiles_after_warmup_kernels"] == 0, results
    if smoke:
        assert results["tuned_envelopes"] >= 2, results
        assert results["params_max_delta"] < 1e-3, results
    blob = json.dumps(results, indent=1)
    print(blob)
    if out_path:
        with open(out_path, "w") as f:
            f.write(blob + "\n")
        print(f"# wrote {out_path}", file=sys.stderr)


CELLS = [
    # (cell name, kind, batch, extra XLA flags)
    ("b128", "train", 128, ""),
    ("b256_control", "train", 256, ""),
    ("b384", "train", 384, ""),
    ("b512", "train", 512, ""),
    # --xla_tpu_* flags are libtpu's and go via LIBTPU_INIT_ARGS (the
    # "LIBTPU:" prefix): jaxlib's own XLA_FLAGS parser fatals on flags
    # it does not know
    ("b256_vmem64m", "train", 256,
     "LIBTPU:--xla_tpu_scoped_vmem_limit_kib=65536"),
    ("b256_vmem128m", "train", 256,
     "LIBTPU:--xla_tpu_scoped_vmem_limit_kib=131072"),
    ("b256_no_lhs", "train", 256,
     "LIBTPU:--xla_tpu_enable_latency_hiding_scheduler=false"),
    ("layout_probe", "layout", 256, ""),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=["train", "layout"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--cells", default="",
                    help="comma-separated subset of cell names")
    ap.add_argument("--kernels", action="store_true",
                    help="in-process Pallas kernel-registry A/B "
                         "(stock XLA vs use_kernels, fresh nets per "
                         "mode; CPU-proxy sized — see child_kernels)")
    ap.add_argument("--kernels-batch", type=int, default=8)
    ap.add_argument("--kernels-img", type=int, default=8)
    ap.add_argument("--kernels-steps", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="with --kernels: assert parity + tuned "
                         "envelopes (make kernels-smoke)")
    ap.add_argument("--out", default="",
                    help="with --kernels: also write the JSON here")
    args = ap.parse_args()
    if args.kernels:
        child_kernels(args.kernels_batch, args.kernels_img,
                      args.kernels_steps, out_path=args.out or None,
                      smoke=args.smoke)
        return
    if args.child == "train":
        child_train(args.batch)
        return
    if args.child == "layout":
        child_layout(args.batch)
        return

    want = set(filter(None, args.cells.split(",")))
    results = {}
    if os.path.exists(OUT):
        results = json.load(open(OUT))
    for name, kind, batch, flags in CELLS:
        if want and name not in want:
            continue
        env = dict(os.environ)
        if flags.startswith("LIBTPU:"):
            env["LIBTPU_INIT_ARGS"] = flags[len("LIBTPU:"):]
        elif flags:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " " + flags).strip()
        cmd = [sys.executable, os.path.abspath(__file__),
               "--child", kind, "--batch", str(batch)]
        print(f"== {name}: {' '.join(cmd)} flags={flags!r}", flush=True)
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=1200)
        wall = time.perf_counter() - t0
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else ""
        try:
            cell = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            cell = {"error": (proc.stderr or proc.stdout)[-800:],
                    "rc": proc.returncode}
        cell["wall_s"] = round(wall, 1)
        cell["flags"] = flags
        results[name] = cell
        print(json.dumps({name: cell}), flush=True)
        json.dump(results, open(OUT, "w"), indent=1)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
