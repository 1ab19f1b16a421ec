"""``benchmarks/calibrate.py`` for a serving cell whose nearest planted fault
is not its driver's ``NEAREST_FAULT``: per seed the program and the faults
``--every`` names; on the first ``--control-seeds`` seeds the float8 control
and every fault of the configuration's reference. One line a seed on stdout
and in ``chiprun_out/calibrate/<cell>.jsonl``.

    python tools/chip/calibrate_faults.py --workload <cell> --every f1,f2
"""
import argparse
import json
import os
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

harness.keep_compile_cache_in_checkout()

import importlib  # noqa: E402

import jax  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--workload", required=True)
ap.add_argument("--every", required=True)
ap.add_argument("--seeds", type=int, default=8)
ap.add_argument("--control-seeds", type=int, default=2)
ap.add_argument("--first-seed", type=int, default=4_200_000_001)
ap.add_argument("--seconds", type=float, default=8.0)
ap.add_argument("--rehearse-on-cpu-at-tiny-size", dest="rehearsal",
                action="store_true")
args = ap.parse_args()
args.trace, args.seed = 0, args.first_seed
manifest = harness.load_manifest()
cell = harness.find(manifest["workloads"], args.workload, "workload")
d = jax.devices()
ctx = harness.Context(manifest, cell, args, {
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)},
    time.monotonic())
driver = importlib.import_module(f"benchmarks.drivers.{ctx.config['driver']}")
faults = ctx.module("reference").FAULTS
every = tuple(args.every.split(","))
os.makedirs(os.path.join("chiprun_out", "calibrate"), exist_ok=True)
with open(os.path.join("chiprun_out", "calibrate",
                       cell["name"] + ".jsonl"), "a") as out:
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        obs = driver.measure(ctx, seed, args.seconds, False,
                             keep_programs=True)
        first = i < args.control_seeds
        checked = driver.check(ctx, obs.pop("weights"), obs.pop("served"),
                               control=first,
                               faults=faults if first else every)
        n = obs["counters"]
        row = {"seed": seed, "program": checked["numbers"],
               "checked_tokens": checked["checked_tokens"],
               "end_to_end": obs["end_to_end"],
               "wrong_length": obs["wrong_length"],
               "experts_read": n.get("moe_experts_read"),
               "expert_layer_steps": n.get("moe_expert_layer_steps"),
               "seconds": time.monotonic() - t0}
        row.update({k: v for k, v in checked.items()
                    if isinstance(v, dict) and k != "numbers"})
        line = json.dumps(row)
        print("calibrate", line, flush=True)
        out.write(line + "\n")
        out.flush()
