"""benchmarks/run.py, and besides its result line: with --trace 1 the device's
time by program kind and by scope (`telemetry.device_time.by_scope` over the
raw trace and `aot_cache.programs()`: exact in every cell, since an operation
goes to the program run that encloses it) in chiprun_out/<label>.scopes.json
and, as a table, in chiprun_out/<label>.scopes.txt and on stderr; the runs,
gaps and host spans of the traced window in chiprun_out/<label>.ops.json; with
either the program's `dl4j_decode_*_total` counters as the run left them (the
loop's run-ahead counts among them) and what `aot_cache.clear()` and its keeper
thread cost, on stderr.

    python tools/chip/dump_run.py <label> --workload ... --seed ... --seconds ... --trace 0|1
"""
import glob
import json
import os
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)
label, argv = sys.argv[1], sys.argv[2:]

from benchmarks import harness, run  # noqa: E402
from deeplearning4j_tpu.optimize import aot_cache  # noqa: E402
from deeplearning4j_tpu.telemetry import device_time  # noqa: E402

_reduce = harness.TraceWindow.reduce
_clear = aot_cache.clear
cleared = {}


def timed_clear():
    """`aot_cache.clear()` as the drivers call it before the readers run,
    with what it costs (outside every end-to-end metric) and how long its
    keeper thread then takes to fetch the dispatched executables' HLO
    modules (here it is waited for; in `benchmarks/run.py` it runs beside
    the reference's check)."""
    loaded = len(aot_cache.programs())
    t0 = time.monotonic()
    _clear()
    t1 = time.monotonic()
    kept = len(aot_cache.programs())
    cleared.update(seconds=t1 - t0, keeper_seconds=time.monotonic() - t1,
                   loaded=loaded, kept=kept)


aot_cache.clear = timed_clear


def by_scope(self):
    """The table by scope, from the raw trace, before `reduce` deletes it."""
    for t in self._threads:
        t.join()
    files = sorted(glob.glob(os.path.join(
        self.DIR, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return
    t0 = time.monotonic()
    try:
        table = device_time.by_scope(files[-1], aot_cache.programs())
    except ValueError as e:         # a CPU rehearsal has no device plane
        print(f"# no table by scope: {e}", file=sys.stderr)
        return
    table["by_scope_s"] = time.monotonic() - t0
    table["clear"] = dict(cleared)
    os.makedirs(os.path.dirname(f"chiprun_out/{label}"), exist_ok=True)
    with open(f"chiprun_out/{label}.scopes.json", "w") as f:
        json.dump(table, f)
    text = device_time.format_table(table)
    with open(f"chiprun_out/{label}.scopes.txt", "w") as f:
        f.write(text + "\n")
    print(text, file=sys.stderr)


def reduce_and_dump(self):
    from deeplearning4j_tpu import telemetry

    if self.t_stop is not None:
        by_scope(self)
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
print("# aot_cache.clear():", json.dumps(cleared), file=sys.stderr)
sys.exit(rc)
