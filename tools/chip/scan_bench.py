"""Chip timing of ONE Mamba layer's prompt scan (PR 35; PERF.md §6) at the
``jamba2-3b-serve-reason1k`` cell's shapes: one row, 5,120 channels, a
state of 16, float32, at the cell's prompt buckets (64 ... 2,048), both
ways on the device:

- ``selective_scan_loop``: the ``lax.scan`` over positions (what every CPU
  run lowers to), at its ``LOOP_UNROLL``;
- ``selective_scan_kernel``: the Pallas kernel (what a program lowered for
  a TPU runs).

Times are the program's on the DEVICE (a trace with the host tracer off,
as the harness traces). It also says how far the kernel's output and final
state lie from the loop's ON THE CHIP, with a mask and a given ``h0``. Run
it through the chip tool from the root of a checkout; it writes
``chiprun_out/scan_bench.json``. ``tiny`` rehearses on the CPU (the kernel
through the interpreter; no device plane: no time).

    python tools/chip/scan_bench.py [buckets=64,512,2048] [tiny]
"""
import glob
import json
import os
import shutil
import sys

sys.path.insert(0, os.getcwd())      # run from the root of a checkout

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import selective_scan as ss

TINY = "tiny" in sys.argv
BUCKETS = [64, 128, 256, 512, 1024, 2048]
for a in sys.argv[1:]:
    if a.startswith("buckets="):
        BUCKETS = [int(x) for x in a[8:].split(",")]


def device_us(run):
    """Mean device time of the one program ``run()`` launches again and
    again, or ``None`` where the trace has no device plane."""
    from benchmarks import trace_reduce as tr

    d = os.path.abspath(".bench_trace/scan")
    shutil.rmtree(d, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=options)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    try:
        path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
        summ = tr.summarize(tr.read_planes(path))
    except Exception as ex:  # noqa: BLE001 — the CPU has no device plane
        print(f"# no device time: {ex!r}", flush=True)
        return None
    runs = max(summ["fullest"]["programs"].values(),
               key=lambda r: sum(b - a for a, b in r))
    return round(sum(b - a for a, b in runs) / len(runs) * 1e-3, 1)


def main():
    d, n = (1024, 16) if TINY else (5120, 16)
    buckets = [16, 64] if TINY else BUCKETS
    reps = 2 if TINY else 10
    interpret = jax.default_backend() != "tpu"
    out = {"device": jax.devices()[0].device_kind, "channels": d, "state": n,
           "rows": 1, "parts": []}

    def timed(name, fn, args, **more):
        f = jax.jit(fn)
        res = f(*args)
        jax.block_until_ready(res)

        def runs():
            for _ in range(reps):
                r = f(*args)
            jax.block_until_ready(r)

        rec = {"part": name, "device_us": device_us(runs), **more}
        print(json.dumps(rec), flush=True)
        out["parts"].append(rec)
        return res

    for t in buckets:
        ks = jax.random.split(jax.random.PRNGKey(t), 7)
        x = jax.random.normal(ks[0], (1, t, d))
        mask = (jnp.arange(t)[None] < (3 * t) // 4).astype(jnp.float32)
        dt = ss._masked(jax.nn.softplus(
            jax.random.normal(ks[1], (1, t, d)) - 4.0), mask)
        a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, d))
        b = jax.random.normal(ks[2], (1, t, n))
        c = jax.random.normal(ks[3], (1, t, n))
        skip = jnp.ones((d,))
        h0 = jax.random.normal(ks[4], (1, n, d))
        args = (x, dt, a, b, c, skip, h0)
        y0, h_loop = timed(
            "loop", lambda *v: ss.selective_scan_loop(*v[:6], None, v[6]),
            args, bucket=t, unroll=ss.LOOP_UNROLL)
        y1, h_kernel = timed(
            "kernel", lambda *v: ss.selective_scan_kernel(
                *v, interpret=interpret), args, bucket=t)
        out["parts"][-1].update(
            y_gap_to_loop=float(jnp.abs(y1 - y0).max()),
            state_gap_to_loop=float(jnp.abs(h_kernel - h_loop).max()),
            y_scale=float(jnp.abs(y0).max()))
        print(json.dumps(out["parts"][-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/scan_bench.json", "w") as f:
        json.dump(out, f, indent=1)
    shutil.rmtree(".bench_trace", ignore_errors=True)


if __name__ == "__main__":
    main()
