"""Chip timing of ``RoutedExpertsLayer``'s two expert products (PR 33;
PERF.md §6) at Trinity-Mini's widths: hidden 2048, 128 experts of 1024,
top 8, bfloat16 matrices. For a step of ``n`` live rows, the whole layer
(route, experts, shared expert) with every token through every expert
held and with the slots grouped by expert (``jax.lax.ragged_dot``): where
the two cross is where ``conf.layers_moe.EVERY_EXPERT_SLOTS`` belongs.
Times are the program's on the DEVICE (a trace with the host tracer off,
as the harness traces), beside the host's clock over the same runs. Run
it through the chip tool from the root of a checkout; it writes
``chiprun_out/moe_crossover.json``. ``tiny`` rehearses on the CPU (no
device plane there: the device time is left out).

    python tools/chip/moe_crossover.py [rows=16,32,64,128,256] [tiny]
"""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())      # run from the root of a checkout

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.conf import layers_moe

TINY = "tiny" in sys.argv
ROWS = [16, 32, 64, 128, 256]
for a in sys.argv[1:]:
    if a.startswith("rows="):
        ROWS = [int(x) for x in a[5:].split(",")]


def device_us(run, reps):
    """Mean device time of the one program ``run()`` launches ``reps``
    times, or ``None`` where the trace has no device plane."""
    from benchmarks import trace_reduce as tr

    d = os.path.abspath(".bench_trace/moe")
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
        programs = tr.summarize(tr.read_planes(path))["fullest"]["programs"]
    except Exception as ex:  # noqa: BLE001 — the CPU has no device plane
        print(f"# no device time: {ex!r}", flush=True)
        return None
    runs = max(programs.values(), key=lambda r: sum(b - a for a, b in r))
    return round(sum(b - a for a, b in runs) / len(runs) * 1e-3, 1)


def main():
    d, e, h, k = (64, 16, 32, 4) if TINY else (2048, 128, 1024, 8)
    layer = layers_moe.RoutedExpertsLayer(
        n_out=d, n_experts=e, n_hidden=h, top_k=k, n_shared_hidden=h,
        route_scale=2.826, weight_dtype="bfloat16")
    params = layer.init(jax.random.PRNGKey(33), type("T", (), {"size": d})())
    out = {"device": jax.devices()[0].device_kind, "hidden": d, "experts": e,
           "expert_hidden": h, "top_k": k, "rows": []}
    for n in ROWS:
        x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
        live = jnp.ones((n,), bool)
        rec = {"rows": n, "slots_an_expert": n * k / e}
        for name, slots in (("every_expert", (0, 1 << 30)),
                            ("grouped", (1, 0))):
            layers_moe.EVERY_EXPERT_SLOTS = slots
            f = jax.jit(lambda p, x: layer.forward_live(p, x, live))
            rec["experts_touched"] = int(
                f(params, x)[1]["moe_experts_touched"])
            reps = 2 if TINY else 20

            def runs():     # the same random rows each time: the device
                for _ in range(reps):       # runs its programs in turn
                    y = f(params, x)[0]
                y.block_until_ready()

            t = time.perf_counter()
            runs()
            rec[name + "_host_us"] = round(
                (time.perf_counter() - t) / reps * 1e6, 1)
            rec[name + "_us"] = device_us(runs, reps)
        print(json.dumps(rec), flush=True)
        out["rows"].append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_crossover.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
