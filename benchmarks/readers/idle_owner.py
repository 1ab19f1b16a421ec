"""Who, inside the program, owns the device's idle time: each idle gap of
the fullest-used device in the traced window, put down to what the thread
that feeds the device was doing in it, by the program's own spans
(``readers/program_spans.py``) brought onto the trace's clock.

``params``:

- ``loop``: the name of a span of the thread that feeds the device
  (``gen.decode``: the engine's loop; ``fit``). Every instant of that
  thread belongs to the deepest span open then (``program_spans.
  timeline``); ``root`` (optional, ``fit``) names a span that covers the
  whole loop and owns nothing itself.
- ``pattern``, ``pick``: the program the loop launches (as
  ``program_time``); ``launch``: the span that dispatches it
  (``gen.decode.launch``, ``compute``); ``wait``: the span in which the
  loop blocks on its result (``gen.decode.readback``; ``drain``, which
  waits for twelve). When a ``wait`` returns, the device's queue is empty.
- ``tie``: how the host's monotonic clock meets the trace's, to begin
  with. ``"window_open"`` (serving): ``obs["traced"]["t_start"]``, the
  host time at which the traced window opened, is ``window_ns[0]``.
  ``{"host": "next_batch", "span": "fit.next_batch"}`` (training, where
  ``obs`` carries no host time of the traced window): the harness's own
  ``host`` span in which it started the profiler (the one that holds
  ``window_ns[0]``) ran inside the longest program ``span`` of the loop,
  and both end when the iterator yields, microseconds apart; tied there,
  and CHECKED: every other ``host`` span of the harness must lie inside
  a program ``span``.
- ``returns``: ``"owned_pct"``: the share of all idle time that lies
  under a span of the loop other than ``not_owned`` (``gen.wait``:
  nothing to do is nobody's fault). ``"after_wait"``: the device's idle
  time from the end of each ``wait`` span to the start of the program's
  next run, in ms, mean per such span.

Either tie rests on ONE point that the harness took through a tiny
program of its own (``bench_sync``), and that point was 4.6 ms late in
one traced run of seven on the chip (my chip runs, PR 25): a run of the
decode window then "ended" 5.4 ms after the read-back that had returned
its tokens. So the tie is made again on the device's own events, which
are many: every ``wait`` that ends in the window is held against the end
of the run it waited for (the last to end before it), and the host's
spans are shifted so that the quickest of these waits returns the moment
its run ends (a result cannot be seen before it exists; the quickest was
seen 0.001 to 0.3 ms after on the chip, which is this tie's error). The
shift goes to ``notes`` as ``clock_tie_shift_ms``; one over 20 ms makes
the reader return ``None``. Then the other side is checked: launches and
runs are paired in order from the first ``wait``'s end, and no run may
start before its launch began; by how much one does is
``clock_tie_residual_ms`` (0 when the tie is right; over 1 ms the reader
returns ``None``: the metric is left out of the line, never wrong).

Also to ``obs["notes"]``: ``result_seen_after_ms`` (median and widest
time from a run's end to the end of the ``wait`` that saw it),
``idle_by_program_span`` (owner -> idle ms, pieces, longest),
``idle_meanwhile_on_other_threads`` (the same gaps by the spans of all
OTHER threads, the callers of ``submit``), and ``traced_window`` (the
counts of program runs, launches, waits and gaps over 10 ms there, and
the loop thread's span coverage).
"""

from benchmarks import trace_reduce
from benchmarks.readers import program_spans as ps
from benchmarks.readers.program_time import runs_of

INSIDE_NS = 0.2e6     # a harness span may stick out of the program's by this
EARLY_NS = 1.0e6      # a run may start this long "before" its launch
MAX_SHIFT_NS = 20e6   # a first tie further off than this is not mended
LONG_GAP_NS = 10e6


def read(ctx, obs, params):
    found = analyse(obs, params)
    if found is None:
        return None
    if params["returns"] == "owned_pct":
        idle = sum(v["idle_ms"] for v in found["owners"].values())
        owned = sum(v["idle_ms"] for name, v in found["owners"].items()
                    if v["in_span"] and name not in params.get(
                        "not_owned", ()))
        return 100.0 * owned / idle if idle > 0 else None
    if params["returns"] == "after_wait":
        waits = found["idle_after_ms"]
        return sum(waits) / len(waits) if waits else None
    raise ValueError(f"unknown returns {params['returns']!r}")


def analyse(obs, params):
    """The whole attribution, once per run: the metrics of one cell share
    it through ``obs``."""
    key = "idle_owner:" + params["loop"]
    if key not in obs:
        obs[key] = _analyse(obs, params)
    return obs[key]


def _analyse(obs, params):
    trace = obs.get("trace")
    evs = ps.events() if trace is not None else []
    loop = ps.last_named(evs, params["loop"])
    if loop is None:
        return None
    thread = loop["thread"]
    zero = _zero_ns(obs, trace, evs, loop, params["tie"])
    if zero is None:
        return None
    lo, hi = trace["window_ns"]
    runs = runs_of(obs, params)
    waits = [e["end_ns"] - zero for e in evs if e["thread"] == thread
             and e["name"] == params["wait"]]
    seen = _seen_after(runs, [w for w in waits if lo <= w < hi], hi)
    if not seen:
        return None
    shift = -min(seen)
    if abs(shift) > MAX_SHIFT_NS:
        return None
    for e in evs:                      # onto the trace's clock
        e["start_ns"] += shift - zero
        e["end_ns"] += shift - zero
    evs = [e for e in evs if e["end_ns"] > lo and e["start_ns"] < hi]
    mine = [e for e in evs if e["thread"] == thread]
    waits = [e for e in mine if e["name"] == params["wait"]
             and lo <= e["end_ns"] < hi]
    launches = sorted(e["start_ns"] for e in mine
                      if e["name"] == params["launch"])
    residual = _early_ns(runs, launches, waits[0]["end_ns"])
    if residual is None or residual > EARLY_NS:
        return None
    gaps = trace["fullest"]["gaps"]
    root = params.get("root")
    owners = _owners(gaps, ps.timeline(evs, thread, lo, hi, root))
    meanwhile = {}
    for other in {e["thread"] for e in evs} - {thread}:
        for name, rec in _owners(gaps, ps.timeline(evs, other, lo,
                                                   hi)).items():
            if rec["in_span"]:
                meanwhile[name] = meanwhile.get(name, 0.0) + rec["idle_ms"]
    idle_after = []
    for e in waits:
        nxt = next((a for a, _b in runs if a >= e["end_ns"]), None)
        if nxt is not None:
            idle_after.append(1e-6 * trace_reduce.total(trace_reduce.clip(
                gaps, e["end_ns"], nxt)))
    seen = sorted(t + shift for t in seen)
    notes = obs.setdefault("notes", {})
    notes["clock_tie_shift_ms"] = 1e-6 * shift
    notes["clock_tie_residual_ms"] = 1e-6 * residual
    notes["result_seen_after_ms"] = {"median": 1e-6 * seen[len(seen) // 2],
                                     "widest": 1e-6 * seen[-1]}
    notes["idle_by_program_span"] = {
        name: {"idle_ms": rec["idle_ms"], "pieces": rec["pieces"],
               "longest_ms": rec["longest_ms"]}
        for name, rec in sorted(owners.items(),
                                key=lambda kv: -kv[1]["idle_ms"])[:12]}
    notes["idle_meanwhile_on_other_threads"] = dict(
        sorted(meanwhile.items(), key=lambda kv: -kv[1])[:8])
    notes["traced_window"] = {
        "program_runs": sum(lo <= a and b <= hi for a, b in runs),
        params["launch"]: sum(lo <= t < hi for t in launches),
        params["wait"]: len(waits),
        "gaps_over_10_ms": sum(b - a > LONG_GAP_NS for a, b in gaps),
        "span_coverage_pct": ps.coverage_pct(evs, thread, lo, hi, root)[0]}
    return {"owners": owners, "idle_after_ms": idle_after}


def _seen_after(runs, waits, hi):
    """For each ``wait`` end, the time since the end of the run it waited
    for: the run whose end is nearest, within ``MAX_SHIFT_NS`` either way
    (runs are a window or a step apart, several times that). A run the
    trace's end cut short has no end; waits with no run's end that near
    are left out."""
    ends = [b for _a, b in runs if b <= hi]
    out = []
    for w in waits:
        near = min(ends, key=lambda b: abs(w - b), default=None)
        if near is not None and abs(w - near) <= MAX_SHIFT_NS:
            out.append(w - near)
    return out


def _early_ns(runs, launches, anchor):
    """Launches and runs paired in order from ``anchor``, the end of a
    wait, when the device's queue was empty: the widest amount by which a
    run starts BEFORE the launch that dispatched it began (0 when none
    does); ``None`` where there is nothing to pair."""
    launches = [t for t in launches if t >= anchor - EARLY_NS]
    starts = [a for a, _b in runs if a >= anchor]
    if not launches or not starts:
        return None
    return max(0.0, max(t - a for t, a in zip(launches, starts)))


def _zero_ns(obs, trace, evs, loop, tie):
    """The host's monotonic time, in ns, at the trace clock's zero; or
    ``None`` where the tie cannot be made or fails its check. ``loop`` is
    the window's one ``fit`` where the tie is made at its children."""
    lo = trace["window_ns"][0]
    if tie == "window_open":
        traced = obs.get("traced")
        return None if not traced else 1e9 * traced["t_start"] - lo
    theirs = sorted((a, b) for name, a, b in trace["host_spans"]
                    if name == tie["host"])
    mine = [e for e in evs if e["parent_id"] == loop["id"]
            and e["name"] == tie["span"]]
    opener = next(((a, b) for a, b in theirs if a <= lo <= b), None)
    if opener is None or not mine:
        return None
    longest = max(mine, key=lambda e: e["duration_ns"])
    zero = longest["end_ns"] - opener[1]
    inside = [(e["start_ns"] - zero, e["end_ns"] - zero) for e in mine]
    for a, b in theirs:
        if not any(x - INSIDE_NS <= a and b <= y + INSIDE_NS
                   for x, y in inside):
            return None
    return zero


def _owners(gaps, line):
    """``{owner: {idle_ms, pieces, longest_ms, in_span}}``: each idle gap
    cut by the timeline's segments (sorted, without overlap); what none
    covers is ``nobody``."""
    out = {}

    def add(name, ns, in_span):
        rec = out.setdefault(name, {"idle_ms": 0.0, "pieces": 0,
                                    "longest_ms": 0.0, "in_span": in_span})
        rec["idle_ms"] += 1e-6 * ns
        rec["pieces"] += 1
        rec["longest_ms"] = max(rec["longest_ms"], 1e-6 * ns)

    k = 0
    for a, b in sorted(gaps):
        while k < len(line) and line[k][1] <= a:
            k += 1
        covered, i = 0.0, k
        while i < len(line) and line[i][0] < b:
            c = min(b, line[i][1]) - max(a, line[i][0])
            if c > 0:
                add(line[i][2], c, line[i][3])
                covered += c
            i += 1
        if (b - a) - covered > 1.0:
            add("nobody", (b - a) - covered, False)
    return out
