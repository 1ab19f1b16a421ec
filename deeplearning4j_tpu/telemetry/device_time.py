"""The device's time under the program's own names.

The host side has spans (``telemetry.spans``); the device runs compiled
programs whose operations XLA names ``fusion.1006``. What ties the two is
``jax.named_scope``: the decoder's walk (``nn/decoding.py``) and the
graph's forward (``nn/graph.py``) put every operation under a scope, the
scope is metadata of the lowered program (it costs nothing at run time),
the compiler carries it into the compiled text
(``metadata={op_name="jit(fn)/while/body/ffn/b3_ffn/dot_general"}``), and
the profiler's trace names each operation by the instruction it ran. This
module is the join:

- :func:`parse_scopes`: compiled text -> ``{instruction name: Op}``
  (``optimize.aot_cache.Program.scope_map`` calls it for a loaded
  executable);
- :func:`by_scope`: a profiler trace and ``aot_cache.programs()`` -> for
  every executable that ran, its kind, runs, ms a run, and the own time
  of each scope in ms a run, with the operations under no scope listed
  by name. An operation goes to the program run that encloses it in
  time, so two programs that both hold a ``fusion.1`` stay apart.

The operator's tool (``docs/observability.md``, "Device time by scope")
and what ``tools/chip/dump_run.py`` writes beside a result line. It feeds
no ``/metrics`` series and no exporter.

**The vocabulary.** A decoder program's first scope is a plan entry's
class (``SCOPE_CLASSES``, the layer type's ``scope_class``) or one of
``PROGRAM_SCOPES`` (what runs outside the walk); its second is the
vertex's name; a mixer's own scopes (``ssm.scan``, ``moe.experts``,
``cache.write``) lie beneath. A train step's first scope is a vertex's
name or one of ``TRAIN_SCOPES``; the backward pass carries the same
names inside ``transpose(jvp(...))``.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

# a plan entry's class: ``conf`` layer types carry one as ``scope_class``
SCOPE_CLASSES = (
    "embed", "pos", "norm", "attn.mha", "attn.full", "attn.window",
    "attn.sparse", "attn.lightning", "attn.delta", "attn.latent", "ssm",
    "mixer.shortconv", "moe", "ffn", "residual", "head")
# what a decoder program runs outside the walk. ``window.prepare`` is also
# given by :func:`group_of` to every operation the compiler hoisted out of
# the decode window's ``while`` (the float32 -> bfloat16 converts of the
# matrices: once a window, not once a step), whatever scope wrote it
PROGRAM_SCOPES = ("window.prepare", "sample", "cache.write", "window.account")
# what a train step runs beside its vertices
TRAIN_SCOPES = ("cast", "loss", "updater", "guards")

UNNAMED = "unnamed"


class _Instruction(NamedTuple):
    """A line of the compiled text, as :func:`parse_scopes` reads it."""

    result: str
    opcode: str
    op_name: Optional[str]
    called: List[Tuple[str, str]]    # (attribute, computation)
    operands: List[str]


class Op(NamedTuple):
    """One instruction of a compiled program, as the trace can show it."""

    scope: Tuple[str, ...]   # the named scopes around it, outermost first
    backward: bool           # written by the backward pass: transpose(...)
    in_while: bool           # lies in a ``while``'s body, as compiled
    hoisted: bool            # written inside a loop, compiled outside it
    result: str              # its result type, as the compiled text has it
    opcode: str
    via: str                 # whose ``op_name`` gave the scope: "" its own,
    #                          "root" a fusion's root, "user" / "operand" the
    #                          instruction it feeds / is fed by, "loop" the
    #                          decode window's own loop (``parse_scopes``)


# --------------------------------------------------------------------------
# compiled text -> {instruction: Op}
# --------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_CALLED_LIST = re.compile(
    r"\b(branch_computations|called_computations)=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
# name-stack entries that JAX's own primitives push: structure, no scope
_STRUCTURE = re.compile(
    r"^(while|body|cond|scan|closed_call|core_call|checkpoint|remat\d*|"
    r"rematted_computation|custom_jvp_call|custom_vjp_call|"
    r"custom_vjp_call_jaxpr|custom_lin|pallas_call|shard_map|"
    r"branch_\d+_fun|xla_pmap)$")
# control flow: its time is its body's, its scope no neighbour's
_CONTROL = frozenset({"while", "conditional", "call"})
# instructions that do no work of their own
NO_WORK = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota"})


def _bracketed(s: str) -> int:
    """The index of the bracket that closes ``s[0] == "("``."""
    depth = 0
    for i, c in enumerate(s):
        depth += c == "("
        depth -= c == ")"
        if depth == 0:
            return i
    return len(s) - 1


def _result_and_opcode(rest: str) -> Tuple[str, str, List[str]]:
    """``bf16[8,8]{1,0} fusion(%a, %b), kind=..`` -> its type, its opcode
    and its operands' names; a tuple type is taken to its closing
    bracket."""
    if rest.startswith("("):
        i = _bracketed(rest)
        result, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    opcode, paren, operands = tail.partition("(")
    operands = operands[:_bracketed("(" + operands)] if paren else ""
    return result, opcode.strip(), _OPERAND.findall(operands)


def _components(op_name: str) -> List[str]:
    out, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def scope_of(op_name: str) -> Tuple[Tuple[str, ...], bool, Optional[str]]:
    """``jit(step)/transpose(jvp(res2a))/conv/mul`` -> ``(("res2a",
    "conv"), True, None)``: the named scopes, whether the backward pass
    wrote it, and, where it was written inside a loop's body, the
    ``op_name`` of the outermost such ``while``. The last entry is the
    primitive's name; ``jit(..)``, ``while``, ``body``, ... are
    structure; ``jvp``, ``transpose``, ``vmap`` wrap the scope they
    transformed."""
    scope, backward, loop = [], False, None
    parts = _components(op_name)[:-1]
    for i, part in enumerate(parts):
        while True:
            m = _WRAPPER.match(part)
            if not m:
                break
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
        if part in ("body", "cond") and i and parts[i - 1] == "while" \
                and loop is None:
            loop = "/".join(parts[:i])
        if part and not _STRUCTURE.match(part):
            scope.append(part)
    return tuple(scope), backward, loop


def parse_scopes(text: str) -> Dict[str, Op]:
    """Every instruction of the compiled text that the device runs as an
    operation of its own (those of fused computations are not: a fusion
    is one operation), by name.

    The compiler writes instructions of its own, without ``op_name``:
    the copies that prefetch an operand into fast memory
    (``copy-start``/``copy-done``), layout copies, the float32 ->
    bfloat16 converts of a matrix product's operands. Such an instruction
    takes, in this order: the scope of the root of the computation it
    calls (a fusion); the scope of the first instruction that USES it,
    through others as bare as itself (a prefetch belongs to the product
    that waits for it; what feeds a layer's own loop belongs to that
    layer); ``window.prepare`` where that user is a ``while`` under no
    scope (it prepares the operands of the decode window's loop: the
    converts the compiler hoists out of it); the scope of what it is fed
    by (a copy of a cache behind the write). ``Op.via`` says which. What
    such a loop does for itself (its counter, its test, the stacking of
    each step's outputs: ``op_name`` ``jit(fn)/while/body/add``) is
    ``window.account``, via ``"loop"``.

    ``Op.hoisted``: written inside the body of a ``while`` under no
    scope that the compiled text still holds, and compiled outside every
    loop. (A loop of one trip is inlined: no ``while`` is left, and its
    operations were not hoisted.)"""
    comps: Dict[str, dict] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(2), {
                    "entry": bool(m.group(1)), "ops": {}, "root": None})
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        result, opcode, operands = _result_and_opcode(rest)
        called = _CALLED.findall(rest)
        for k, names in _CALLED_LIST.findall(rest):
            called += [(k, n.strip().lstrip("%")) for n in names.split(",")
                       if n.strip()]
        name_m = _OP_NAME.search(rest)
        current["ops"][m.group(2)] = _Instruction(
            result, opcode, name_m.group(1) if name_m else None, called,
            operands)
        if m.group(1):
            current["root"] = m.group(2)

    # which computations run as operations of their own, and which lie
    # inside a loop: walk from the entry
    inlined = {n for c in comps.values() for ins in c["ops"].values()
               for k, n in ins.called
               if k == "to_apply" and ins.opcode != "call"
               or k == "calls" and ins.opcode == "fusion"}
    runs: Dict[str, bool] = {}           # computation -> inside a while
    todo = [(n, False) for n, c in comps.items() if c["entry"]]
    while todo:
        name, looped = todo.pop()
        if name not in comps or name in runs and (runs[name] or not looped):
            continue
        runs[name] = looped
        for ins in comps[name]["ops"].values():
            todo += [(n, looped or ins.opcode == "while")
                     for _k, n in ins.called if n not in inlined]

    def root_name(comp: str, seen=()) -> Optional[str]:
        c = comps.get(comp)
        if c is None or c["root"] is None or comp in seen:
            return None
        root = c["ops"][c["root"]]
        if root.op_name:
            return root.op_name
        for _k, n in root.called:
            found = root_name(n, seen + (comp,))
            if found:
                return found
        return None

    # the loops the compiled text still holds, under no scope of their own
    bare_loops = {ins.op_name for c in comps.values()
                  for ins in c["ops"].values()
                  if ins.opcode == "while" and ins.op_name
                  and not scope_of(ins.op_name + "/x")[0]}
    out: Dict[str, Op] = {}
    for comp, looped in runs.items():
        ops = comps[comp]["ops"]
        bare = []
        for name, ins in ops.items():
            op_name, via = ins.op_name, ""
            if not op_name:
                op_name, via = next(filter(None, (
                    root_name(n) for _k, n in ins.called)), None), "root"
            scope, backward, loop = scope_of(op_name) \
                if op_name else ((), False, None)
            works = ins.opcode not in _CONTROL and ins.opcode not in NO_WORK
            if not scope and works and looped and loop in bare_loops:
                # the loop's own: its counter, its test, the stacking of
                # what each step hands out (the window's tokens, counts)
                scope, via = ("window.account",), "loop"
            hoisted = loop in bare_loops and not looped
            out[name] = Op(scope, backward, looped, hoisted, ins.result,
                           ins.opcode, via if scope else "")
            if not scope and works and not hoisted:
                bare.append(name)
        if not bare:
            continue
        users: Dict[str, List[str]] = {}
        for name, ins in ops.items():
            for operand in ins.operands:
                users.setdefault(operand, []).append(name)

        def reach(start, uses: bool) -> Optional[Op]:
            """The first scoped instruction that uses ``start`` (or that
            it is fed by), through bare ones, depth first; a ``while``
            among the users gives ``window.prepare``."""
            seen, todo = {start}, [start]
            while todo:
                name = todo.pop()
                step = []
                for other in (users.get(name, ()) if uses
                              else ops[name].operands):
                    if other in seen or other not in out:
                        continue
                    seen.add(other)
                    if out[other].scope:
                        return out[other]
                    opcode = ops[other].opcode
                    if uses and opcode == "while":
                        return out[other]._replace(scope=("window.prepare",))
                    # control flow's scope is no neighbour's; plumbing
                    # fans out to what is unrelated
                    if opcode not in _CONTROL and (
                            uses or opcode not in ("parameter", "tuple")):
                        step.append(other)
                todo += reversed(step)
            return None

        for name in bare:
            for uses, via in ((True, "user"), (False, "operand")):
                found = reach(name, uses)
                if found is not None:
                    out[name] = out[name]._replace(
                        scope=found.scope, backward=found.backward, via=via)
                    break
    return out


def group_of(op: Optional[Op]) -> str:
    """The line of the table an operation's time goes to: its outermost
    scope (``transpose(<scope>)`` for the backward pass),
    ``window.prepare`` for what was hoisted out of a loop, ``unnamed``
    for what carries no scope (or is not of this program)."""
    if op is None:
        return UNNAMED
    if op.hoisted:
        return "window.prepare"
    if not op.scope:
        return UNNAMED
    return f"transpose({op.scope[0]})" if op.backward else op.scope[0]


# --------------------------------------------------------------------------
# a trace and the table -> the device's time by program and by scope
# --------------------------------------------------------------------------

_MODULE = re.compile(r"^(?P<name>.*?)\((?P<id>\d+)\)$")


def short_name(op_text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    return op_text.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> List[float]:
    """For events ``(start, end, ...)`` of one line, the time of each
    that no event nested inside it covers: a ``while`` spans the
    operations of its body, and only its own time says what it costs."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in order:
        a, b = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def read_device_lines(xplane_path: str) -> Dict[int, dict]:
    """``{chip: {"modules": [(name, start_ns, end_ns)], "ops": [...]}}``
    of a profiler trace (``.xplane.pb``), read with jax alone."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices: Dict[int, dict] = {}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        dev = devices.setdefault(int(m.group(1)), {"modules": [], "ops": []})
        for line in plane.lines:
            key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
            if key:
                dev[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    return devices


def shapes_of(result: str) -> str:
    """A result type without its layouts: ``bf16[8,8]{1,0:T(8,128)}`` ->
    ``bf16[8,8]``."""
    return re.sub(r"\{[^}]*\}", "", result)


def traced_shapes(op_text: str) -> str:
    """:func:`shapes_of` the result of an operation as the trace names it
    (by its whole HLO text)."""
    rest = op_text.split(" = ", 1)
    return shapes_of(_result_and_opcode(rest[1])[0]) if len(rest) == 2 \
        else ""


def match_program(module: str, ops_seen: Dict[str, str], programs):
    """The entry of the table that the traced module ``jit_fn(<id>)`` ran:
    by the trace's identifier where the table has one, else the executable
    of that module name whose compiled text holds every traced operation
    under its name WITH its result's shapes (``ops_seen``: name ->
    :func:`traced_shapes`; two buckets of one function number their
    instructions alike and differ in their shapes). ``None`` when no entry
    holds 99% of them, or two hold as many."""
    m = _MODULE.match(module)
    name, ident = (m.group("name"), int(m.group("id"))) if m \
        else (module, None)
    named = [p for p in programs if p.dispatches and p.module_name == name]
    for p in named:
        if ident is not None and p.trace_id == ident:
            return p
    if not ops_seen:
        return None
    best, best_hits, tie = None, 0, False
    for p in named:
        table = p.scope_map()
        hits = sum(1 for n, shapes in ops_seen.items()
                   if n in table and shapes_of(table[n].result) == shapes)
        if hits > best_hits:
            best, best_hits, tie = p, hits, False
        elif hits == best_hits:
            tie = True
    if best is None or tie or best_hits < 0.99 * len(ops_seen):
        return None
    return best


def by_scope(xplane_path, programs: Iterable, unnamed_top: int = 12) -> dict:
    """The device's time by program and by scope, from a profiler trace
    (the path of an ``.xplane.pb``, or what :func:`read_device_lines`
    made of one) and ``optimize.aot_cache.programs()``.

    For every traced module (on the fullest-used chip): ``module`` (its
    name in the trace),
    ``kind`` (the table's ``fn_key``; ``None`` when the table holds no
    executable that matches), ``runs``, ``ms_per_run``,
    ``device_share_pct`` (of the busy time of all programs),
    ``scopes`` (``{group: ms a run}``, own time: a ``while`` less its
    body; groups as :func:`group_of`), ``detail`` (the same by
    ``<scope>/<scope>/...`` to three levels, for what lies beneath the
    class: the vertex, ``cache.write``, ``ssm.scan``), ``between_ops_ms``
    (the run's time no operation covers), ``unnamed_pct`` and
    ``unnamed_ops`` (name, ms a run, the start of its text) for the
    operations under no scope. Only whole runs are counted: the first
    (last) run of a trace that opened (closed) in the middle of it is
    left out (the profiler starts its event where the trace starts: 9 ms
    of a 48 ms decode window, my chip run, PR 37)."""
    programs = list(programs)
    devices = read_device_lines(xplane_path) \
        if isinstance(xplane_path, str) else xplane_path
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    dev = max(devices.values(),
              key=lambda d: sum(b - a for _n, a, b in d["modules"]))
    runs = sorted((a, b, n) for n, a, b in dev["modules"])
    ops = sorted((a, b, n) for n, a, b in dev["ops"])
    for end in (0, -1):
        # the trace's first (last) run, well under every other run of its
        # program: the trace opened (closed) inside it, its event is a part
        if not runs:
            break
        a, b, name = runs[end]
        others = [y - x for x, y, n in runs if n == name and (x, y) != (a, b)]
        if len(others) >= 3 and b - a < 0.8 * min(others):
            del runs[end]
    per_module: Dict[str, dict] = {}
    at = 0
    for a, b, module in runs:
        while at < len(ops) and ops[at][0] < a:
            at += 1
        end = at
        while end < len(ops) and ops[end][0] < b:
            end += 1
        inside = [e for e in ops[at:end] if e[1] <= b]
        at = end
        rec = per_module.setdefault(module, {
            "runs": 0, "ns": 0.0, "own": {}, "text": {}})
        rec["runs"] += 1
        rec["ns"] += b - a
        for (_a, _b, text), own in zip(inside, self_times(inside)):
            name = short_name(text)
            rec["own"][name] = rec["own"].get(name, 0.0) + own
            rec["text"].setdefault(name, text)
    busy = sum(r["ns"] for r in per_module.values()) or 1.0
    out = []
    for module, rec in sorted(per_module.items(),
                              key=lambda kv: -kv[1]["ns"]):
        program = match_program(
            module, {n: traced_shapes(t) for n, t in rec["text"].items()},
            programs)
        table = program.scope_map() if program is not None else {}
        n = rec["runs"]
        scopes: Dict[str, float] = {}
        detail: Dict[str, float] = {}
        unnamed = []
        for name, own in rec["own"].items():
            op = table.get(name)
            ms = 1e-6 * own / n
            group = group_of(op)
            scopes[group] = scopes.get(group, 0.0) + ms
            if group == UNNAMED:
                unnamed.append([name, ms, rec["text"][name][:160]])
            else:
                key = "/".join((("window.prepare",) if op.hoisted else ())
                               + ((group,) if op.backward else ())
                               + op.scope[op.backward:3])
                detail[key] = detail.get(key, 0.0) + ms
        ms_run = 1e-6 * rec["ns"] / n
        covered = sum(scopes.values())
        out.append({
            "module": module,
            "kind": program.kind if program is not None else None,
            "runs": n, "ms_per_run": ms_run,
            "device_share_pct": 100.0 * rec["ns"] / busy,
            "scopes": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
            "detail": dict(sorted(detail.items(), key=lambda kv: -kv[1])),
            "between_ops_ms": ms_run - covered,
            "unnamed_pct": 100.0 * scopes.get(UNNAMED, 0.0) / ms_run
            if ms_run else 0.0,
            "unnamed_ops": sorted(unnamed, key=lambda r: -r[1])[:unnamed_top],
        })
    return {"programs": out, "busy_ms": 1e-6 * busy}


def format_table(result: dict, scopes_top: int = 16) -> str:
    """:func:`by_scope`'s result as text: a block a program, a line a
    scope."""
    lines = []
    for p in result["programs"]:
        lines.append(
            f"{p['module']}  kind {p['kind']}  runs {p['runs']}  "
            f"{p['ms_per_run']:.3f} ms a run  "
            f"{p['device_share_pct']:.1f}% of the device  "
            f"unnamed {p['unnamed_pct']:.2f}%")
        for scope, ms in list(p["scopes"].items())[:scopes_top]:
            lines.append(f"    {scope:<28s} {ms:9.3f} ms  "
                         f"{100.0 * ms / p['ms_per_run']:5.1f}%")
        rest = list(p["scopes"].items())[scopes_top:]
        if rest:
            lines.append(f"    {'(' + str(len(rest)) + ' more)':<28s} "
                         f"{sum(ms for _s, ms in rest):9.3f} ms")
        lines.append(f"    {'(between operations)':<28s} "
                     f"{p['between_ops_ms']:9.3f} ms")
        for name, ms, text in p["unnamed_ops"][:6]:
            lines.append(f"      unnamed {name:<24s} {ms:8.3f} ms  "
                         f"{text[:90]}")
    return "\n".join(lines)
