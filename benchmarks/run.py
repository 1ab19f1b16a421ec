#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: finds the cell in ``BENCHMARK.json``, loads its
configuration and traffic files, hands them to the configuration's driver
(``benchmarks/drivers/<name>.py``), which sets up (counted as
``setup_s``), measures for ``--seconds`` and checks what the timed path
produced against the plain reference. The last line of standard output is
the one JSON object the driver of the checks reads.

It refuses anything but ``platform == "tpu"`` with as many chips as the
cell asks for (exit 2, no result line), and a checkout without the
program (exit 3). ``--rehearse-on-cpu-at-tiny-size`` runs the same
control flow on whatever backend there is with each file's ``rehearsal``
overrides; its line says ``"correct": false`` and ``"rehearsal": true``,
so it can never pass for a chip run.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REHEARSAL_FLAG = "--rehearse-on-cpu-at-tiny-size"
NO_CHIP_EXIT = 2
NO_PROGRAM_EXIT = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(REHEARSAL_FLAG, dest="rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import harness

    harness.keep_compile_cache_in_checkout()
    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], args.workload, "workload")
    try:
        import deeplearning4j_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmarks/run.py: the program is not in this checkout "
              f"({e}); nothing to measure", file=sys.stderr)
        return NO_PROGRAM_EXIT

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearsal and (device["platform"] != "tpu"
                               or device["count"] < cell["chips"]):
        print(f"benchmarks/run.py: cell {cell['name']!r} needs "
              f"{cell['chips']} TPU chip(s); jax reports {device}. It does "
              f"not fall back.", file=sys.stderr)
        return NO_CHIP_EXIT

    ctx = harness.Context(manifest=manifest, cell=cell, args=args,
                          device=device, t_process_start=T_PROCESS_START)
    print(f"# {cell['name']} | platform: {device['platform']} device_kind: "
          f"{device['kind']} devices: {device['count']} | seed {args.seed} "
          f"seconds {args.seconds} trace {args.trace}"
          + (" | REHEARSAL, not a chip run" if args.rehearsal else ""),
          flush=True)
    driver = importlib.import_module(
        f"benchmarks.drivers.{ctx.config['driver']}")
    obs = driver.run(ctx)
    line = harness.result_line(ctx, obs)
    harness.print_compared(line["compared"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
