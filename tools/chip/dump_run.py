"""benchmarks/run.py with --trace 1, and besides its result line every traced
operation's time, count and HLO text in chiprun_out/<label>.ops.json.

    python tools/chip/dump_run.py <label> --workload ... --seed ... --seconds ... --trace 1
"""
import json
import os
import sys

ROOT = os.getcwd()
sys.path.insert(0, ROOT)
label, argv = sys.argv[1], sys.argv[2:]

from benchmarks import harness, run  # noqa: E402

_reduce = harness.TraceWindow.reduce


def reduce_and_dump(self):
    summ = _reduce(self)
    if summ:
        full = summ["fullest"]
        out = {
            "window_s": summ["window_s"], "busy_s": summ["busy_s"],
            "programs_ms": {n: [round((b - a) * 1e-6, 4) for a, b in runs]
                            for n, runs in full["programs"].items()},
            "ops": sorted(([k, rec[3], rec[0], rec[1], rec[2][:400]]
                           for k, rec in full["ops"].items()),
                          key=lambda r: -r[1]),
        }
        from deeplearning4j_tpu import telemetry
        out["counters"] = {
            n: telemetry.REGISTRY.counter(n).value
            for n in ("dl4j_decode_kv_read_positions_total",
                      "dl4j_decode_kv_bucket_positions_total")}
        path = f"chiprun_out/{label}.ops.json"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f)
    return summ


harness.TraceWindow.reduce = reduce_and_dump
sys.exit(run.main(argv))
