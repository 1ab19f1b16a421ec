"""Host time the program's own spans took, in ms, from the program's ring
(``readers/program_spans.py``). Needs no device trace, so it reads in a
CPU rehearsal too.

``params``:

- ``names``: the span names that count.
- ``within`` (optional): the name of a span; the interval is then the
  last span of that name (the window's one ``fit``) and only the spans
  under it count. Without it the interval is the whole measured window
  (``obs["window"]["t0"/"t_end"]``, host monotonic seconds): all 30 s,
  not only the traced part.
- ``self_time`` (optional): a span's time less what its child spans
  cover, in place of its duration.
- ``attr`` (optional): sum that attr of the spans (a count the span
  carries, such as ``lock_wait_us``) in place of their time, times
  ``scale`` (default 1; 0.001 turns us into ms).
- ``per``: ``"span"`` for a mean per counted span; the name of another
  span for the sum over the count of THAT span in the interval (``per``
  ``gen.decode``: per decode window); ``"attr:<name>"`` for the sum over
  the summed attr of the counted spans (``gen.prefill`` per ``joins``).
- ``stat`` (optional, with ``per`` ``"span"``): ``"mean"`` (default) or
  ``"p95"`` (nearest rank).
- ``children_to_notes`` (optional): a key of ``obs["notes"]`` that gets
  the mean ms of each child span, by name, per counted span.

- ``coverage_of`` (optional): the name of a span of the loop's thread;
  ``obs["notes"]["span_coverage"]`` then gets the share of the interval,
  on that thread, that lies inside spans, the holes by name of their
  neighbours, and the spans a second the whole ring recorded there.
"""

import math

from benchmarks.readers import program_spans as ps


def interval(evs, obs, params):
    """``(the interval's spans, lo_ns, hi_ns, the root's name or None)``,
    or ``None`` where the ring holds no such interval."""
    if "within" in params:
        root = ps.last_named(evs, params["within"])
        if root is None:
            return None
        return (ps.descendants(evs, root), root["start_ns"], root["end_ns"],
                root["name"])
    window = obs.get("window") or {}
    if "t0" not in window:
        return None
    lo, hi = 1e9 * window["t0"], 1e9 * window["t_end"]
    return ([e for e in evs if lo <= e["start_ns"] < hi], lo, hi, None)


def read(ctx, obs, params):
    evs = ps.events()
    found = interval(evs, obs, params) if evs else None
    if found is None:
        return None
    inside, lo, hi, root = found
    names = set(params["names"])
    counted = [e for e in inside if e["name"] in names]
    if not counted:
        return None
    if "coverage_of" in params:
        _coverage(obs, evs, params["coverage_of"], lo, hi, root)
    kids = ps.children_of(inside)
    if "attr" in params:
        values = [float((e.get("attrs") or {}).get(params["attr"], 0.0))
                  * float(params.get("scale", 1.0)) for e in counted]
    elif params.get("self_time"):
        values = [1e-6 * ps.self_ns(e, kids) for e in counted]
    else:
        values = [1e-6 * e["duration_ns"] for e in counted]
    if "children_to_notes" in params:
        by_child = {}
        for e in counted:
            for c in kids.get(e["id"], ()):
                by_child[c["name"]] = (by_child.get(c["name"], 0.0)
                                       + 1e-6 * c["duration_ns"])
        obs.setdefault("notes", {})[params["children_to_notes"]] = {
            name: ms / len(counted) for name, ms in sorted(by_child.items())}
    per = params.get("per", "span")
    if per == "span":
        if params.get("stat", "mean") == "p95":
            s = sorted(values)
            return s[max(0, math.ceil(0.95 * len(s)) - 1)]
        return sum(values) / len(values)
    if per.startswith("attr:"):
        over = sum(float((e.get("attrs") or {}).get(per[5:], 0.0))
                   for e in counted)
    else:
        over = sum(e["name"] == per for e in inside)
    return sum(values) / over if over else None


def _coverage(obs, evs, of, lo, hi, root):
    loop = ps.last_named(evs, of)
    if loop is None:
        return
    notes, thread = obs.setdefault("notes", {}), loop["thread"]
    # a ring that wrapped (a CPU rehearsal's thousands of windows a
    # second) holds only the interval's end
    lo = max(lo, min(e["start_ns"] for e in evs))
    pct, holes = ps.coverage_pct(evs, thread, lo, hi, root)
    notes["span_coverage"] = {
        "thread_of": of, "covered_pct": pct,
        "holes_ms": dict(sorted(holes.items(), key=lambda kv: -kv[1])[:6]),
        "ring_spans_per_s": 1e9 * sum(lo <= e["start_ns"] < hi
                                      for e in evs) / (hi - lo)}
