"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` alone. What a trace of this
runtime (jax 0.9.0, libtpu 0.0.34, one v5e chip) holds, looked at by hand
in PR 24:

- a plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules`` (one
  event per run of a compiled program, named ``jit_<function>(<fingerprint>)``),
  ``XLA Ops`` (one event per operation, named by its whole HLO text,
  ``%name = type op(operands), ...``; the short name is the part before
  `` = ``), ``Steps`` and ``Async XLA Ops`` (copies in flight, which
  overlap the operations and are not counted as busy time);
- a plane ``/host:CPU`` with a line per thread. The benchmark traces with
  the host tracer off (``harness.TraceWindow`` says why) and brings its
  own spans (``host_spans``), named ``bench:<name>``, onto the trace's
  clock.

Times are nanoseconds in the trace and seconds in the summary.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_NAME = re.compile(r"^(?P<name>.*?)\((?P<id>\d+)\)$")
WINDOW_SPAN = "bench:trace_window"


class NoDeviceTrace(RuntimeError):
    """The trace holds no operation on a TPU (the driver of the checks
    refuses such a run; a CPU rehearsal has none by nature)."""


def short_name(op_name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The merged, sorted union of intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given its merged busy ones."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def self_times(events) -> List[float]:
    """For events ``(start, end, ...)`` of one line, in any order, the
    time of each that no event nested inside it covers: a ``while`` or a
    ``conditional`` spans the operations of its body on the same line, and
    only its own time says what it costs."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        a, b = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def read_planes(path: str) -> dict:
    """``{"devices": {n: {"modules": [...], "ops": [...]}}, "host": [...]}``
    with events as ``(name, start_ns, end_ns)``; host events are the
    ``bench:`` spans only."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.start_ns
                                 + e.duration_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host}


def host_spans(planes: dict, spans, t_open: float, t_stop: float,
               t_sync_done, sync_program: str) -> list:
    """The benchmark's own spans (``(name, t0, t1)`` on the host's
    monotonic clock, seconds) as ``bench:`` events on the trace's clock,
    with the window itself as ``bench:trace_window``. The two clocks are
    tied at one point: the moment the result of ``sync_program`` was ready
    on the host (``t_sync_done``) is the end of its run in the trace. If
    the trace holds no such run, the trace's clock is taken to begin when
    the window opened (right to within the time the profiler takes to
    start, some 50 ms)."""
    ends = [b for d in planes["devices"].values()
            for name, _a, b in d["modules"]
            if name.startswith("jit_" + sync_program + "(")]
    if ends and t_sync_done is not None:
        zero = t_sync_done - 1e-9 * min(ends)   # host time of trace time 0
    else:
        zero = t_open

    def at(t):
        return (t - zero) * 1e9

    out = [(WINDOW_SPAN, at(t_open), at(t_stop))]
    out += [("bench:" + name, at(a), at(b)) for name, a, b in spans
            if b > t_open and a < t_stop]
    return out


def summarize(planes: dict) -> dict:
    """The summary every trace reader works from. The window is the
    ``bench:trace_window`` span where the benchmark wrote one, else from
    the first device event to the last. Busy time is the union of the
    ``XLA Ops`` intervals inside the window (of the program runs where a
    runtime gives no operations), per device; ``busy_s`` is their mean
    over the devices, ``idle_share`` is that of the fullest-used one."""
    host = planes["host"]
    devices = planes["devices"]
    if not devices:
        raise NoDeviceTrace("the trace holds no /device:TPU plane")
    window = next(((a, b) for n, a, b in host if n == WINDOW_SPAN), None)
    if window is None:
        starts = [e[1] for d in devices.values()
                  for e in d["modules"] + d["ops"]]
        ends = [e[2] for d in devices.values()
                for e in d["modules"] + d["ops"]]
        if not starts:
            raise NoDeviceTrace("no operation ran on the device in the "
                                "traced window")
        window = (min(starts), max(ends))
    lo, hi = window
    per_device = {}
    for n, d in sorted(devices.items()):
        source = d["ops"] or d["modules"]
        busy = union(clip(((a, b) for _n, a, b in source), lo, hi))
        programs: Dict[str, List[Interval]] = {}
        for name, a, b in d["modules"]:
            if b <= lo or a >= hi:
                continue
            programs.setdefault(name, []).append((a, b))
        ops: Dict[str, List[float]] = {}
        inside = [(max(a, lo), min(b, hi), name) for name, a, b in d["ops"]
                  if b > lo and a < hi]
        for (a, b, name), own in zip(inside, self_times(inside)):
            rec = ops.setdefault(short_name(name), [0.0, 0, name, 0.0])
            rec[0] += (b - a) * 1e-9
            rec[1] += 1
            rec[3] += own * 1e-9
        per_device[n] = {
            "busy": busy, "busy_s": total(busy) * 1e-9,
            "gaps": gaps(busy, lo, hi), "programs": programs, "ops": ops}
    window_s = (hi - lo) * 1e-9
    fullest = max(per_device.values(), key=lambda d: d["busy_s"])
    busy_mean = sum(d["busy_s"] for d in per_device.values()) / len(
        per_device)
    if busy_mean <= 0:
        raise NoDeviceTrace("no operation ran on the device in the traced "
                            "window")
    top_ops = sorted(((name, rec[3]) for name, rec in fullest["ops"].items()),
                     key=lambda kv: -kv[1])
    spans = [(n[len("bench:"):], a, b) for n, a, b in host
             if n != WINDOW_SPAN]
    top_gaps = sorted(fullest["gaps"], key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_s, "window_ns": (lo, hi), "busy_s": busy_mean,
        "idle_share": 1.0 - fullest["busy_s"] / window_s,
        "devices": per_device, "fullest": fullest,
        "top_ops": [[n, s] for n, s in top_ops[:10]],
        "top_gaps": [[attribute(g, spans), (g[1] - g[0]) * 1e-9]
                     for g in top_gaps],
        "host_spans": spans}


def attribute(gap: Interval, spans) -> str:
    """What the host was doing in an idle gap, as far as the benchmark's
    own spans say: the span that covers most of it, else
    ``unattributed`` (the program's own loop has no annotations yet)."""
    best, best_cover = "unattributed", 0.0
    cover: Dict[str, float] = {}
    for name, a, b in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > 0:
            cover[name] = cover.get(name, 0.0) + c
    for name, c in cover.items():
        if c > best_cover and c >= 0.5 * (gap[1] - gap[0]):
            best, best_cover = name, c
    return best


def reduce_file(path: str) -> dict:
    return summarize(read_planes(path))


# --- what the readers ask of a summary -------------------------------------

def program_runs(summary: dict, pattern: str, pick: str = "all"):
    """The ``(start_ns, end_ns)`` runs, on the fullest-used device, of the
    programs whose name matches ``pattern``. Programs of one jitted
    function compiled for several shapes share a name and differ in the
    fingerprint in brackets: ``pick="most_runs"`` keeps the one
    fingerprint that ran most often, ``"most_time"`` the one that took
    most time."""
    rx = re.compile(pattern)
    found = {name: runs for name, runs in summary["fullest"]["programs"]
             .items() if rx.search(name)}
    if not found:
        return []
    if pick == "most_runs":
        found = dict([max(found.items(), key=lambda kv: len(kv[1]))])
    elif pick == "most_time":
        found = dict([max(found.items(), key=lambda kv: total(kv[1]))])
    elif pick != "all":
        raise ValueError(f"unknown pick {pick!r}")
    return sorted(r for runs in found.values() for r in runs)


def op_seconds(summary: dict, patterns: List[str]) -> float:
    """Summed device time of the operations whose name in the trace (the
    whole HLO text, ``%name = type op(operands), kind=..``) matches any
    of ``patterns``, on the fullest-used device."""
    rxs = [re.compile(p) for p in patterns]
    return sum(rec[0] for rec in summary["fullest"]["ops"].values()
               if any(rx.search(rec[2]) for rx in rxs))


def top_ops_text(summary: dict, n: int = 30, width: int = 220):
    """The ``n`` operations with most time, with the start of their HLO
    text: what a name pattern is written from."""
    ops = summary["fullest"]["ops"]
    top = sorted(ops, key=lambda k: -ops[k][3])[:n]

    def text(hlo):
        tail = re.search(r", kind=.*$", hlo)
        return hlo[:width] + (" ... " + tail.group(0)[2:120] if tail else "")

    return [[k, ops[k][3], ops[k][1], text(ops[k][2])] for k in top]
