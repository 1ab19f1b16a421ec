"""benchmarks/run.py, and besides its result line: with --trace 1 every traced
operation's time, count and HLO text in chiprun_out/<label>.ops.json; with
either the program's `dl4j_decode_*_total` counters as the run left them (the
loop's run-ahead counts among them, where the program has them) on stderr.

    python tools/chip/dump_run.py <label> --workload ... --seed ... --seconds ... --trace 0|1
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
    from deeplearning4j_tpu import telemetry

    summ = _reduce(self)
    if summ:
        full = summ["fullest"]
        out = {
            "window_s": summ["window_s"], "busy_s": summ["busy_s"],
            "programs_ms": {n: [round((b - a) * 1e-6, 4) for a, b in runs]
                            for n, runs in full["programs"].items()},
            # for reading a gap by hand: every run's start (ms from the
            # traced window's opening), the gaps over a millisecond, and the
            # program's own spans there on the host's clock, whose zero
            # `host_t_start_s` is the opening as the harness stamped it
            "program_starts_ms": {
                n: [round((a - summ["window_ns"][0]) * 1e-6, 3)
                    for a, _b in runs]
                for n, runs in full["programs"].items()},
            "gaps_ms": [[round((a - summ["window_ns"][0]) * 1e-6, 3),
                         round((b - a) * 1e-6, 3)]
                        for a, b in full["gaps"] if b - a > 1e6],
            "host_t_start_s": self.t_start,
            "spans": [[e["name"], e["thread"],
                       round(e["start_ns"] * 1e-6 - 1e3 * self.t_start, 3),
                       round(e["duration_ns"] * 1e-6, 3)]
                      for e in telemetry.spans.events()
                      if -50.0 < e["start_ns"] * 1e-9 - self.t_start
                      < summ["window_s"] + 0.05],
            "ops": sorted(([k, rec[3], rec[0], rec[1], rec[2][:400]]
                           for k, rec in full["ops"].items()),
                          key=lambda r: -r[1]),
        }
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
rc = run.main(argv)
from deeplearning4j_tpu import telemetry  # noqa: E402

snap = telemetry.REGISTRY.snapshot(run_collectors=False)
print("# decode counters (whole process):", json.dumps(
    {n: v for n, v in sorted(snap.items()) if n.startswith("dl4j_decode_")
     and n.endswith("_total") and "{" not in n}), file=sys.stderr)
sys.exit(rc)
