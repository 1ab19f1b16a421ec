"""What every driver shares: the manifest and the files it names, the
profiler window, the device's memory, and the result line.

Driven by data: a cell, a configuration, a traffic mix and a per-layer
metric are each a file of their own, found by the name ``BENCHMARK.json``
gives them. Nothing here knows a cell or a metric by name.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def keep_compile_cache_in_checkout():
    """Before jax is imported: JAX's persistent compilation cache lives at
    ONE fixed path inside this checkout, whatever the machine's
    environment says, and is never trimmed. The program takes the
    directory from this variable (``aot_cache.place_compile_cache``). A
    machine-wide cap starves the serving cells: under the chip tool's
    192 MiB (``JAX_COMPILATION_CACHE_MAX_SIZE``) the decoder's 35
    executables, some 30 MiB each, evicted one another and every run
    compiled for 280 s (my chip runs, PR 24)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmarks: no {what} named {name!r} in "
                     f"BENCHMARK.json (have: {[e['name'] for e in entries]})")


def merge(a: dict, b: dict) -> dict:
    out = dict(a)
    for k, v in b.items():
        out[k] = merge(a[k], v) if isinstance(v, dict) and isinstance(
            a.get(k), dict) else v
    return out


class Context:
    """One run: the cell, its files, the arguments, the device."""

    def __init__(self, manifest, cell, args, device, t_process_start):
        from benchmarks import traffic_gen

        self.manifest = manifest
        self.cell = cell
        self.args = args
        self.device = device
        self.t_process_start = t_process_start
        self.rehearsal = bool(args.rehearsal)
        entry = find(manifest["configs"], cell["config"], "configuration")
        self.config = load_json(ROOT, entry["file"])
        if self.rehearsal:
            self.config = merge(self.config, self.config.get("rehearsal", {}))
        self.traffic = traffic_gen.load(cell["traffic"], self.rehearsal)
        # the cell's own file: the limits `correct` is held to
        self.cell_file = load_json(HERE, "cells", cell["name"] + ".json")
        if self.rehearsal:
            self.cell_file = merge(self.cell_file,
                                   self.cell_file.get("rehearsal", {}))
        peaks = load_json(HERE, "peaks.json")["devices"]
        if device["kind"] in peaks:
            self.peak = peaks[device["kind"]]
        elif self.rehearsal:
            self.peak = None
        else:
            raise SystemExit(
                f"benchmarks/peaks.json has no device_kind "
                f"{device['kind']!r}: add it with its source, never a "
                f"default")

    def module(self, kind: str):
        """The configuration's ``model``, ``reference`` or ``work``."""
        return importlib.import_module(
            f"benchmarks.{ {'model': 'models'}.get(kind, kind) }."
            f"{self.config[kind]}")

    def setup_seconds(self) -> float:
        return time.monotonic() - self.t_process_start


# --------------------------------------------------------------------------
# the profiler window
# --------------------------------------------------------------------------

SYNC_PROGRAM = "bench_sync"     # traces as ``jit_bench_sync(<fingerprint>)``


class TraceWindow:
    """A profiler trace over part of the measured window, reduced once it
    has stopped. The profiler runs for the traced seconds only, with the
    device's events alone: no Python tracer and NO host tracer. With host
    spans on (level 1 as well as the default 2) the runtime writes one
    event for every chunk of every batch it lays out for the device,
    916,000 a thread for 12 training steps: the first step under the
    profiler stalled for 5.2 to 5.6 s, the file was 360 to 780 MB and
    writing it took 56 to 120 s; with none the window reads 4% idle, the
    file is 19 MB and is written in 5 s (my chip runs, PR 24).

    The benchmark's own host spans are therefore kept here, on the host's
    monotonic clock (``annotate``). One point ties that clock to the
    trace's: a tiny program (``bench_sync``) sent to the device from a
    thread of its own when the window opens; the moment its result is
    ready on the host is the end of its event in the trace, to a fraction
    of a millisecond. The directory is inside the checkout, emptied
    before and after: a trace is never left on disk."""

    DIR = os.path.join(ROOT, ".bench_trace")

    def __init__(self, enabled: bool, seconds: float,
                 rehearsal: bool = False, after: float = 0.0):
        self.enabled = enabled
        self.rehearsal = rehearsal
        self.seconds = seconds
        self.after = after       # seconds of the window before it opens
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.summary = None
        self.on_stop = None      # called on the stopping thread, first
        self.spans: list = []    # (name, t0, t1), monotonic seconds
        self._sync = None
        self._sync_done: Optional[float] = None
        self._threads: list = []
        if enabled:
            import jax
            import jax.numpy as jnp

            def bench_sync(x):
                return x + 1

            bench_sync.__name__ = SYNC_PROGRAM
            self._sync = jax.jit(bench_sync)
            self._sync_arg = jnp.zeros((8, 128), jnp.float32)
            # compiled in set-up: nothing compiles inside the window
            self._sync(self._sync_arg).block_until_ready()

    def open(self):
        if not self.enabled or self.t_start is not None:
            return
        import threading

        import jax

        shutil.rmtree(self.DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.DIR, profiler_options=options)
        self.t_start = time.monotonic()
        _RECORDING.append(self.spans)

        def sync():
            self._sync(self._sync_arg).block_until_ready()
            self._sync_done = time.monotonic()

        t = threading.Thread(target=sync, name="bench-trace-sync")
        t.start()
        self._threads.append(t)

    def may_open(self, elapsed: float) -> bool:
        return (self.enabled and self.t_start is None
                and elapsed >= self.after)

    def due(self) -> bool:
        return (self.enabled and self.t_start is not None
                and self.t_stop is None
                and time.monotonic() - self.t_start >= self.seconds)

    def stop(self):
        if self.t_start is None or self.t_stop is not None:
            return
        import threading

        import jax

        if self.on_stop is not None:
            self.on_stop()
        self.t_stop = time.monotonic()
        _RECORDING.remove(self.spans)
        # writing the trace out takes seconds: not on the thread that
        # feeds the program
        t = threading.Thread(target=jax.profiler.stop_trace,
                             name="bench-trace-writer")
        t.start()
        self._threads.append(t)

    def reduce(self):
        """Read the trace (after the window has closed) and delete it."""
        if self.t_stop is None:
            return None
        from benchmarks import trace_reduce

        for t in self._threads:
            t.join()
        files = sorted(glob.glob(os.path.join(
            self.DIR, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        try:
            planes = trace_reduce.read_planes(files[-1])
            planes["host"] = trace_reduce.host_spans(
                planes, self.spans, self.t_start, self.t_stop,
                self._sync_done, SYNC_PROGRAM)
            self.summary = trace_reduce.summarize(planes)
        except trace_reduce.NoDeviceTrace:
            if not self.rehearsal:   # a CPU rehearsal has no device plane
                raise
        finally:
            shutil.rmtree(self.DIR, ignore_errors=True)
        return self.summary


_RECORDING: list = []    # the span lists of the windows that are open


class annotate:
    """A host span of the benchmark's own (``next_batch``, ``submit``,
    ``waiting_for_due_time``...), kept only while a traced window is
    open; two clock reads otherwise."""

    __slots__ = ("name", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        if _RECORDING:
            t1 = time.monotonic()
            for spans in _RECORDING:
                spans.append((self.name, self.t0, t1))
        return False


# --------------------------------------------------------------------------
# memory
# --------------------------------------------------------------------------

def memory_peak_bytes() -> dict:
    """Peak bytes on the fullest chip, read when the window has closed.
    On this runtime ``peak_bytes_in_use`` counts buffers only; what the
    loaded programs hold for their temporaries is ``bytes_reserved``
    (8.77 GB for the ResNet-50 step whose ``memory_analysis()`` declares
    8.81 GB of temporaries, my chip run, PR 24). The two peaks need not
    fall together (their sum passed the chip's ``bytes_limit`` in the
    serving cells), so the figure is the larger of the buffers' peak and
    of what is held at this moment, buffers and reservation together:
    what the chip certainly held at one time. The parts stay in the
    line."""
    import jax

    best = {"memory_peak_bytes": -1}
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        parts = {k: int(s.get(k, 0)) for k in (
            "peak_bytes_in_use", "bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit")}
        peak = max(parts["peak_bytes_in_use"],
                   parts["bytes_in_use"] + parts["bytes_reserved"])
        if peak > best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": peak, **parts}
    return best


# --------------------------------------------------------------------------
# the result line
# --------------------------------------------------------------------------

def _applies(metric: dict, cell: str, moved_present: bool) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return moved_present


def result_line(ctx: Context, obs: dict) -> dict:
    """``obs`` is what the driver observed: ``end_to_end`` values by
    name, ``correct``/``attempted``/``failed``, ``compared``, ``memory``,
    and for the readers ``trace``, ``counters``, ``window``."""
    manifest, cell = ctx.manifest, ctx.cell["name"]
    metrics = {}
    e2e_names = set()
    for m in manifest["end_to_end"]:
        if _applies(m, cell, True) and m["name"] in obs["end_to_end"]:
            e2e_names.add(m["name"])
            if not ctx.args.trace:
                metrics[m["name"]] = {"value": obs["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    if ctx.args.trace:
        for m in manifest["per_layer"]:
            if not _applies(m, cell, m["moves"] in e2e_names):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, obs, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(ctx.device)
    device["memory_peak_bytes"] = obs["memory"]["memory_peak_bytes"]
    line = {"correct": bool(obs["correct"]) and not ctx.rehearsal,
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"]),
            "metrics": metrics, "device": device}
    trace = obs.get("trace")
    if ctx.args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["top_ops"][:10],
                             "idle_gaps": trace["top_gaps"][:10]}
        from benchmarks import trace_reduce

        obs.setdefault("notes", {})["top_ops_text"] = \
            trace_reduce.top_ops_text(trace, 16)
        lo = trace["window_ns"][0]
        obs["notes"]["programs"] = {
            name: [len(runs), 1e-9 * sum(b - a for a, b in runs),
                   [round(1e-6 * (a - lo), 1) for a, _b in sorted(runs)[:6]]]
            for name, runs in trace["fullest"]["programs"].items()}
    if ctx.rehearsal:
        line["rehearsal"] = True
    line["notes"] = obs.get("notes", {})
    line["memory"] = obs["memory"]
    line["compared"] = obs["compared"]
    return line


def print_compared(compared: dict):
    """Each number compared beside its limit, as the last lines of
    standard error."""
    sys.stdout.flush()
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} value {c['value']:.6g} limit "
              f"{c['limit']:.6g} {verdict}", file=sys.stderr)
    sys.stderr.flush()
