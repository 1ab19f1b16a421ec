#!/usr/bin/env python3
"""Read, on the chip and in ONE process, what a cell's limits are set
from (run by the builder, never by a benchmark run):

    python benchmarks/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

For each seed it sets the cell up as a run does and prints every number
``correct`` compares; for the first ``--control-seeds`` seeds also the
control (the reference put in the program's place, computed in the
configuration's ``control_dtype``) and, for a training cell, the planted
fault "half of the batch left out". Writes ``chiprun_out/calibrate/<cell>.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="sweep the open loop over these requests a "
                         "second instead (serving cells)")
    ap.add_argument("--rehearse-on-cpu-at-tiny-size", dest="rehearsal",
                    action="store_true")
    args = ap.parse_args()
    args.trace, args.seed = 0, args.first_seed

    import importlib

    from benchmarks import harness

    harness.keep_compile_cache_in_checkout()
    import jax

    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], args.workload, "workload")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearsal and device["platform"] != "tpu":
        print(f"calibrate: needs the chip, found {device}", file=sys.stderr)
        return 2
    ctx = harness.Context(manifest, cell, args, device, time.monotonic())
    driver = importlib.import_module(
        f"benchmarks.drivers.{ctx.config['driver']}")
    rows = (driver.sweep if args.rates else driver.calibrate)(ctx, args)
    out = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out, exist_ok=True)
    name = cell["name"] + (".sweep" if args.rates else "")
    with open(os.path.join(out, name + ".json"), "w") as f:
        json.dump({"device": device, "rows": rows}, f, indent=1)
    gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
