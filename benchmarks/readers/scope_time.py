"""Own device time of the operations under the program's own scopes, in
ms a run (or a step) of ONE program, or the share of that program's time
under no scope, in %.

The program puts a ``jax.named_scope`` on every operation and keeps a
table of its executables (``optimize.aot_cache.programs()``: kind,
dispatches, ``scope_map()``: instruction name -> scope). This reader
takes the table's most dispatched executable whose kind matches
``params["kind"]``, and files the traced operations' own time (a
``while`` less its body: ``trace_reduce.self_times``) under the scope of
the instruction of that name.

The summary keeps a traced operation under its short name alone, without
its program, so the join is exact only where the traced window ran ONE
program of the loop: the reader returns ``None`` unless exactly one
fingerprint matching ``params["pattern"]`` ran in the window, unless the
table holds the traced operations under their names WITH their results'
shapes for 99% of the time (``COVER``: another program would not), and in
a checkout whose program has no such table.

``params``: ``kind`` and ``pattern`` (regular expressions: on the table's
kind, on the module's name in the trace), then either ``unnamed_pct:
true`` or ``scopes`` (regular expressions on an operation's outermost
scope; ``window.prepare`` for what the compiler hoisted out of the
loop), with ``exclude`` (the same, taken out), ``direction``
(``forward`` | ``backward`` | ``any``: under ``transpose(..)`` or not)
and ``divide_by`` (a path into the configuration: the program runs that
many steps). The whole table goes to ``notes["device_ms_by_scope"]``.
"""

import re

from benchmarks import trace_reduce

COVER = 0.99
GROUPS_KEPT = 48        # lines of the table that go into the notes


def _config(ctx, path):
    node = ctx.config
    for key in path.split("."):
        node = node[key]
    return float(node)


def table(obs, params):
    """``{"kind", "module", "runs", "ms_per_run", "groups": {(scope,
    backward): ms a run}, "unnamed_ms", "covered_ms"}`` or ``None``;
    computed once a (kind, pattern)."""
    cache = obs.setdefault("_scope_time", {})
    key = (params["kind"], params["pattern"])
    if key not in cache:
        cache[key] = _table(obs, params)
        if cache[key] is not None:
            _note(obs, cache[key])
    return cache[key]


def _table(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return None
    try:
        from deeplearning4j_tpu.optimize import aot_cache
        from deeplearning4j_tpu.telemetry import device_time

        programs = aot_cache.programs()
    except (ImportError, AttributeError):
        return None         # a program without the table: nothing to read
    rx = re.compile(params["pattern"])
    ran = {name: runs for name, runs in trace["fullest"]["programs"].items()
           if rx.search(name)}
    if len(ran) != 1:
        return None
    (module, runs), = ran.items()
    kind = re.compile(params["kind"])
    mine = [p for p in programs if kind.search(p.kind) and p.dispatches]
    if not mine:
        return None
    program = max(mine, key=lambda p: p.dispatches)
    scopes = program.scope_map()
    lo, hi = trace["window_ns"]
    whole = [(a, b) for a, b in runs if a >= lo and b <= hi]
    if not whole:
        return None
    run_ns = sum(b - a for a, b in whole) / len(whole)
    # operations are clipped to the window, runs are not: count the runs
    # as the window holds them, a cut run as the part of one it is
    held = trace_reduce.total(trace_reduce.clip(runs, lo, hi)) / run_ns
    groups, known, total = {}, 0.0, 0.0
    for name, rec in trace["fullest"]["ops"].items():
        own = rec[3]
        total += own
        op = scopes.get(name)
        if op is not None and device_time.shapes_of(op.result) \
                != device_time.traced_shapes(rec[2]):
            op = None
        known += own if op is not None else 0.0
        group = device_time.group_of(op)
        at = (op.scope[0], True) if group.startswith("transpose(") \
            else (group, False)
        groups[at] = groups.get(at, 0.0) + 1e3 * own / held
    if total <= 0 or known < COVER * total:
        return None
    return {"kind": program.kind, "module": module, "runs": len(whole),
            "dispatches": program.dispatches, "ms_per_run": 1e-6 * run_ns,
            "groups": groups,
            "unnamed_ms": groups.get((device_time.UNNAMED, False), 0.0),
            "covered_ms": sum(groups.values())}


def _note(obs, t):
    top = sorted(t["groups"].items(), key=lambda kv: -kv[1])
    lines = {(f"transpose({s})" if back else s): ms
             for (s, back), ms in top[:GROUPS_KEPT]}
    if top[GROUPS_KEPT:]:
        lines[f"({len(top) - GROUPS_KEPT} more)"] = sum(
            ms for _k, ms in top[GROUPS_KEPT:])
    obs.setdefault("notes", {})["device_ms_by_scope"] = {
        "kind": t["kind"], "module": t["module"], "runs": t["runs"],
        "dispatches": t["dispatches"], "ms_per_run": t["ms_per_run"],
        "covered_ms": t["covered_ms"],
        "covered_pct": 100.0 * t["covered_ms"] / t["ms_per_run"],
        "scopes": lines}


def read(ctx, obs, params):
    t = table(obs, params)
    if t is None:
        return None
    if params.get("unnamed_pct"):
        return 100.0 * t["unnamed_ms"] / t["ms_per_run"]
    want = [re.compile(p) for p in params["scopes"]]
    drop = [re.compile(p) for p in params.get("exclude", ())]
    direction = params.get("direction", "any")
    ms = sum(v for (scope, backward), v in t["groups"].items()
             if any(rx.search(scope) for rx in want)
             and not any(rx.search(scope) for rx in drop)
             and (direction == "any"
                  or (direction == "backward") == backward))
    if "divide_by" in params:
        ms /= _config(ctx, params["divide_by"])
    return ms
