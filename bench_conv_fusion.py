"""Conv+BN+ReLU fusion experiment at ResNet-50 stage shapes (round-2
verdict item #2: try the Pallas BN-epilogue experiment and commit the
result, positive or negative — BASELINE.md carries the conclusion).

For each shape (stem 7x7/s2, stage-1 1x1 and 3x3, stage-1 1x1 expand),
batch 256 bf16:
- conv only (XLA);
- conv + train-mode BN (batch stats) + ReLU (XLA fusion);
- for 1x1 convs: a Pallas kernel computing the matmul AND the per-channel
  sum / sum-of-squares in ONE output pass (the BN-stats read of y is
  folded into the matmul epilogue; the normalize+ReLU pass still reads y
  once). XLA's schedule is write-y, read-y-for-stats, read-y-normalize —
  the kernel removes one full activation pass.

Protocol as bench_resnet_profile.py: N queued calls + one
``block_until_ready``, min of 3. Runs on the chip only.
"""

import functools
import json
import time

import numpy as np

N = 40
B = 256


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.util.device import banner, require_tpu

    dev = require_tpu("bench_conv_fusion.py")
    print("# " + banner(dev), flush=True)

    _sync = jax.block_until_ready

    def timed(fn, *args):
        out = fn(*args)
        _sync(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(N):
                out = fn(*args)
            _sync(out)
            best = min(best, (time.perf_counter() - t0) * 1000.0 / N)
        return best

    rng = np.random.default_rng(0)

    def mk(shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32),
                           jnp.bfloat16)

    SHAPES = [
        ("stem7x7s2", (B, 224, 224, 3), (7, 7, 3, 64), (2, 2), "SAME"),
        ("s1_1x1", (B, 56, 56, 64), (1, 1, 64, 64), (1, 1), "VALID"),
        ("s1_3x3", (B, 56, 56, 64), (3, 3, 64, 64), (1, 1), "SAME"),
        ("s1_1x1x4", (B, 56, 56, 64), (1, 1, 64, 256), (1, 1), "VALID"),
    ]
    results = {"device": dev}
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, w, s, p):
        return jax.lax.conv_general_dilated(x, w, s, p,
                                            dimension_numbers=dn)

    def conv_bn_relu(x, w, s, p, gamma, beta):
        y = conv(x, w, s, p)
        y32 = y.astype(jnp.float32)
        mean = jnp.mean(y32, axis=(0, 1, 2))
        var = jnp.var(y32, axis=(0, 1, 2))
        yh = (y32 - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
        return jnp.maximum(yh, 0.0).astype(x.dtype)

    for name, xs, ws, s, p in SHAPES:
        x, w = mk(xs), mk(ws)
        cout = ws[-1]
        gamma = jnp.ones((cout,), jnp.float32)
        beta = jnp.zeros((cout,), jnp.float32)
        f1 = jax.jit(lambda x, w, _s=s, _p=p: conv(x, w, _s, _p)
                     .astype(jnp.float32).sum())
        results[f"{name}_conv_ms"] = round(timed(f1, x, w), 2)
        f2 = jax.jit(lambda x, w, g, b, _s=s, _p=p:
                     conv_bn_relu(x, w, _s, _p, g, b)
                     .astype(jnp.float32).sum())
        results[f"{name}_conv_bn_relu_ms"] = round(
            timed(f2, x, w, gamma, beta), 2)

    # ---- Pallas fused 1x1-conv (matmul) + BN-stats single pass ----
    # grid over (row blocks, col blocks); the kernel writes the y tile and
    # accumulates per-channel sum / sumsq into per-row-block partials
    # (reduced outside — tiny [nbm, C] arrays), so y is READ ZERO extra
    # times for statistics.
    BM, BN_, BK = 512, 128, 128

    def fused_kernel(x_ref, w_ref, y_ref, s_ref, q_ref, acc, *, nk):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            y = acc[...]
            y_ref[...] = y.astype(y_ref.dtype)
            s_ref[...] = jnp.sum(y, axis=0).reshape(s_ref.shape)
            q_ref[...] = jnp.sum(y * y, axis=0).reshape(q_ref.shape)

    def fused_1x1_bn_relu(x, w, gamma, beta):
        b, h, wd, cin = x.shape
        cout = w.shape[-1]
        m = b * h * wd
        x2 = x.reshape(m, cin)
        w2 = w.reshape(cin, cout)
        nbm, nbn, nbk = m // BM, max(cout // BN_, 1), max(cin // BK, 1)
        bn_ = min(BN_, cout)
        bk = min(BK, cin)
        y, ssum, sq = pl.pallas_call(
            functools.partial(fused_kernel, nk=nbk),
            grid=(nbm, nbn, nbk),
            in_specs=[pl.BlockSpec((BM, bk), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bk, bn_), lambda i, j, k: (k, j))],
            out_specs=[pl.BlockSpec((BM, bn_), lambda i, j, k: (i, j)),
                       pl.BlockSpec((1, 1, bn_), lambda i, j, k: (i, 0, j)),
                       pl.BlockSpec((1, 1, bn_), lambda i, j, k: (i, 0, j))],
            out_shape=[
                jax.ShapeDtypeStruct((m, cout), x.dtype),
                jax.ShapeDtypeStruct((nbm, 1, cout), jnp.float32),
                jax.ShapeDtypeStruct((nbm, 1, cout), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((BM, bn_), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
        )(x2, w2)
        mean = jnp.sum(ssum[:, 0], axis=0) / m
        var = jnp.sum(sq[:, 0], axis=0) / m - mean * mean
        yh = (y.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + 1e-5) \
            * gamma + beta
        return jnp.maximum(yh, 0.0).astype(x.dtype).reshape(b, h, wd, cout)

    for name, xs, ws in [("s1_1x1", (B, 56, 56, 64), (1, 1, 64, 64)),
                         ("s1_1x1x4", (B, 56, 56, 64), (1, 1, 64, 256))]:
        x, w = mk(xs), mk(ws)
        cout = ws[-1]
        gamma = jnp.ones((cout,), jnp.float32)
        beta = jnp.zeros((cout,), jnp.float32)
        fp = jax.jit(lambda x, w, g, b: fused_1x1_bn_relu(x, w, g, b)
                     .astype(jnp.float32).sum())
        # correctness vs the XLA reference first
        ref = conv_bn_relu(x, w, (1, 1), "VALID", gamma, beta)
        got = fused_1x1_bn_relu(x, w, gamma, beta)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        results[f"{name}_pallas_fused_maxerr"] = round(err, 4)
        results[f"{name}_pallas_fused_ms"] = round(
            timed(fp, x, w, gamma, beta), 2)

    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
