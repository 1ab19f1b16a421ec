"""What the two program-span readers (``span_time``, ``idle_owner``) share:
the program's ring as a tree, the time each span has to itself, and the
thread a loop runs on. Not a reader itself.

The program records every span it closes into one bounded ring
(``deeplearning4j_tpu.telemetry.spans``) without being asked, on
``time.monotonic_ns()``: the host clock of the harness's ``TraceWindow``
and of the drivers' ``obs["window"]``. A program that lacks the ring, or
whose spans carry no ``id`` (the commit before PR 25), has nothing to
read: ``events()`` is then empty and every reader returns ``None``.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def events() -> List[dict]:
    """The ring, oldest first, each span with ``start_ns``/``end_ns``;
    empty where the program has no ring of identified spans."""
    try:
        from deeplearning4j_tpu.telemetry import spans
    except ImportError:
        return []
    out = []
    for e in spans.events():
        if "id" not in e:
            return []
        e["end_ns"] = e["start_ns"] + e["duration_ns"]
        out.append(e)
    return out


def children_of(evs: List[dict]) -> Dict[int, List[dict]]:
    kids: Dict[int, List[dict]] = {}
    for e in evs:
        if e["parent_id"] is not None:
            kids.setdefault(e["parent_id"], []).append(e)
    return kids


def own_segments(e: dict, kids: Dict[int, List[dict]]):
    """The parts of a span's interval that no child span covers, each as
    ``(start_ns, end_ns, what_ended_before, what_started_after)``."""
    out, at, prev = [], e["start_ns"], "entry"
    for c in sorted(kids.get(e["id"], ()), key=lambda c: c["start_ns"]):
        if c["start_ns"] > at:
            out.append((at, c["start_ns"], prev, c["name"]))
        at, prev = max(at, c["end_ns"]), c["name"]
    if e["end_ns"] > at:
        out.append((at, e["end_ns"], prev, "exit"))
    return out


def self_ns(e: dict, kids: Dict[int, List[dict]]) -> float:
    return sum(seg[1] - seg[0] for seg in own_segments(e, kids))


def last_named(evs: List[dict], name: str) -> Optional[dict]:
    """The span of that name that ended last."""
    found = [e for e in evs if e["name"] == name]
    return max(found, key=lambda e: e["end_ns"]) if found else None


def descendants(evs: List[dict], root: dict) -> List[dict]:
    """The spans under ``root``, children and theirs (``root`` left out).
    A child ends before its parent, so the ring holds it first."""
    ids, out = {root["id"]}, []
    for e in reversed(evs):
        if e["parent_id"] in ids:
            ids.add(e["id"])
            out.append(e)
    return out[::-1]


def timeline(evs: List[dict], thread: int, lo: float, hi: float,
             root: Optional[str] = None):
    """``[(start_ns, end_ns, owner, in_span)]`` over ``[lo, hi]`` of one
    thread, clipped, in order and without overlap: every instant belongs
    to the deepest span open on the thread then. The time between two
    top-level spans, and the time a span named ``root`` (``fit``, which
    covers its whole loop) has to itself, belong to the code between the
    two neighbouring spans: ``<earlier> -> <later>``, ``in_span``
    false."""
    mine = [e for e in evs if e["thread"] == thread
            and e["end_ns"] > lo and e["start_ns"] < hi]
    kids = children_of(mine)
    ids = {e["id"] for e in mine}
    segs = []
    for e in mine:
        for a, b, before, after in own_segments(e, kids):
            if e["name"] == root:
                segs.append((a, b, f"{before} -> {after}", False))
            else:
                segs.append((a, b, e["name"], True))
    at, prev = lo, "window_start"
    for e in sorted((e for e in mine if e["parent_id"] not in ids),
                    key=lambda e: e["start_ns"]):
        if e["start_ns"] > at:
            segs.append((at, e["start_ns"], f"{prev} -> {e['name']}",
                         False))
        at, prev = max(at, e["end_ns"]), e["name"]
    if hi > at:
        segs.append((at, hi, f"{prev} -> window_end", False))
    return sorted((max(a, lo), min(b, hi), n, s) for a, b, n, s in segs
                  if min(b, hi) > max(a, lo))


def coverage_pct(evs: List[dict], thread: int, lo: float, hi: float,
                 root: Optional[str] = None):
    """Share of ``[lo, hi]`` on that thread that lies inside a span
    (other than ``root``), and the holes by name (``{name: ms}``)."""
    holes: Dict[str, float] = {}
    for a, b, name, in_span in timeline(evs, thread, lo, hi, root):
        if not in_span:
            holes[name] = holes.get(name, 0.0) + (b - a) * 1e-6
    covered = (hi - lo) - 1e6 * sum(holes.values())
    return 100.0 * covered / (hi - lo), holes
