"""Program linter: jaxpr + compiled-HLO checks on every AOT-cache miss.

The framework's memory and dispatch story rests on properties of the
COMPILED step executables that nothing used to verify: train steps must
donate their params/opt buffers (the fused/ZeRO memory claims are void
without input→output aliasing), no host callback may hide inside a step,
ZeRO steps must reduce-scatter rather than all-reduce, bucketed
collective chains must keep their ``optimization_barrier`` issue-order
pins, and closure-captured arrays must not get baked into executables as
constants (silent memory bloat + a recompile per captured object).
PyGraph (arXiv:2503.19779) makes the same argument for CUDA-graph
capture: whole-program dispatch is only safe when a compiler-side check
enforces the capture rules; arXiv:2112.01075 shows collective placement
is auditable from the lowered program alone.

``optimize.aot_cache`` calls :func:`on_compile` from its lower/compile
miss path (every executable the process ever caches passes through
here). Findings land in ``analysis.findings.LOG`` and the
``dl4j_analysis_findings_total`` metric; ``DL4J_TPU_PROGRAM_LINT=0``
disables the hook, ``=strict`` additionally raises on unwaived ERROR
findings (CI fixtures). The pass never retraces: the cache's miss path
already produces the Traced (jaxpr) and Compiled (HLO) artifacts, and
linting reads those.
"""

from __future__ import annotations

import dataclasses
import re
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.analysis.findings import (
    ERROR,
    WARN,
    Finding,
    LOG,
)

# step kinds whose executables MUST donate (alias) their buffers: the
# model train steps, the fused/tbptt scans, every ParallelWrapper SPMD
# step kind ("pw_*" — including the pod-path multi-process keys, which
# carry a ":p<N>" process-topology token so a pod executable never
# collides with a single-host one; donation + collective audit apply to
# them unchanged), and the KV-cached generation path — "decode_step*"
# consumes the whole decode state (the KV caches dominate it) every
# fused window, "prefill*" (prefill_join) scatters prompt KV into it,
# and "gen_release*" passes it through with rows masked; a non-donated
# decode-state executable silently doubles KV memory every token. The
# prefix-cache scatter ("prefix_attach*") and suffix join
# ("prefix_join*") consume the same decode state and donate for the
# same reason. The suffix PREFILL ("gen_prompt_sfx*")
# is deliberately absent: its prefix-page input is a shared refcounted
# buffer other requests attach concurrently, so it must NOT donate
# (same construction-level exemption as gen_prompt).
TRAIN_KIND_PREFIXES = ("train_step", "fused_scan", "tbptt_scan", "pw_",
                       "decode_step", "prefill", "gen_release",
                       "prefix_attach", "prefix_join")

# pod/reshard data-plane kinds (comms.reshard commit_compiled /
# recut_flat — the pod checkpoint restore-across-pod-shapes route):
# every OTHER program rule applies to them (baked consts, f64 leaks,
# callbacks, collective audit), but they are deliberately NOT in
# TRAIN_KIND_PREFIXES — exempting them from the PRG201 donation
# expectation BY CONSTRUCTION: a cross-placement recommit's source and
# target layouts have different per-device buffer sizes, which XLA
# cannot alias — demanding donation there would force a waiver on
# every pod restore (test_pod pins that they never enter the donation
# audit and compile finding-free).
RESHARD_KIND_PREFIXES = ("pod_recut", "reshard_commit")

# jax 0.9.0 primitive names: lax.psum under check_vma binds
# psum_invariant (plain psum only with the check off), psum_scatter
# binds reduce_scatter
ALL_REDUCE_PRIMS = frozenset({"psum", "psum_invariant"})
REDUCE_SCATTER_PRIMS = frozenset({"reduce_scatter"})
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
})

# closure-captured consts above WARN_BYTES are reported; above
# ERROR_BYTES they are treated as baked-in weights (the classic
# "jitted over self.params instead of passing them" bug)
CONST_WARN_BYTES = 1 << 20   # 1 MiB
CONST_ERROR_BYTES = 16 << 20  # 16 MiB


class ProgramLintError(RuntimeError):
    """Raised in strict mode when a compile produces an unwaived ERROR."""

    def __init__(self, findings: List[Finding]):
        super().__init__("; ".join(f.render() for f in findings))
        self.findings = findings


@dataclasses.dataclass
class ProgramArtifact:
    """Everything one compile exposes to the rules. ``jaxpr`` may be
    None (a jax without ``jit.trace``); jaxpr-based rules then skip.
    ``sibling_sigs``: signatures already cached under the same
    (graph_key, fn_key) — the recompile-hazard diff input."""

    graph_key: str
    fn_key: str
    jaxpr: object = None                  # ClosedJaxpr
    executable: object = None             # jax Compiled
    signature: object = None              # aot_cache.signature_of(args)
    sibling_sigs: Tuple = ()
    _aliases: object = dataclasses.field(default=False, repr=False)

    @property
    def location(self) -> str:
        return f"graph={str(self.graph_key)[:12]} kind={self.fn_key}"

    def is_train_kind(self) -> bool:
        return self.fn_key.startswith(TRAIN_KIND_PREFIXES)

    def alias_count(self):
        """Cached: ``executable.as_text()`` renders the whole optimized
        HLO module, so the donation rule and the audit must share one
        pass over it."""
        if self._aliases is False:
            self._aliases = (_alias_count(self.executable)
                             if self.executable is not None else None)
        return self._aliases


# --------------------------------------------------------------------------
# waivers (no source line to annotate: program waivers match on the
# cache key instead, registered next to the wrap() callsite)
# --------------------------------------------------------------------------

_WAIVERS: List[Tuple[str, str, str]] = []


def waive_program(rule: str, key_substring: str, reason: str) -> None:
    """Accept ``rule`` findings for executables whose
    ``graph_key + fn_key`` contains ``key_substring``. Register next to
    the ``aot_cache.wrap`` callsite the waiver justifies."""
    _WAIVERS.append((rule, key_substring, reason))


def _apply_waivers(art: ProgramArtifact,
                   findings: List[Finding]) -> List[Finding]:
    hay = f"{art.graph_key}{art.fn_key}"
    for f in findings:
        for rule, sub, reason in _WAIVERS:
            if f.rule == rule and sub in hay:
                f.waived = True
                f.waiver_reason = reason
                break
    return findings


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def iter_eqns(closed_jaxpr):
    """Yield every eqn in a ClosedJaxpr, recursing into sub-jaxprs
    (scan/while/cond bodies, pjit/shard_map call_jaxprs, custom-vjp
    branches) wherever they appear in eqn params."""
    seen = set()

    def walk(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else (v,)
                for sub in vals:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        yield from walk(inner)      # ClosedJaxpr
                    elif hasattr(sub, "eqns"):
                        yield from walk(sub)        # raw Jaxpr

    yield from walk(closed_jaxpr.jaxpr)


def pallas_interpret_flags(fn, *args) -> List[bool]:
    """The ``interpret`` param of every ``pallas_call`` that ``fn(*args)``
    traces (custom-VJP branches included). Tracing lowers nothing, so a
    TPU-keyed kernel build can be inspected on any backend."""
    import jax

    return [bool(e.params["interpret"])
            for e in iter_eqns(jax.make_jaxpr(fn)(*args))
            if e.primitive.name == "pallas_call"]


def _prim_counts(closed_jaxpr) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for eqn in iter_eqns(closed_jaxpr):
        n = eqn.primitive.name
        counts[n] = counts.get(n, 0) + 1
    return counts


def _alias_count(executable) -> Optional[int]:
    """Input→output alias entries in the compiled module (the HLO-level
    truth of donation: jit-side donate_argnums that XLA could not honor
    — dtype mismatch, non-donatable layout — silently drop the alias,
    which is exactly what this rule exists to surface). None = the
    backend exposed no HLO text (rule skips, intent check takes over)."""
    try:
        text = executable.as_text()
    except Exception:
        return None
    header = text.split("\n", 1)[0]
    i = header.find("input_output_alias={")
    if i < 0:
        return 0
    # balanced-brace scan: alias entries themselves contain "{}", so a
    # substring search for the closing brace picks the wrong one
    depth, start = 0, header.index("{", i)
    end = len(header)
    for j in range(start, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    seg = header[start:end + 1]
    return seg.count("may-alias") + seg.count("must-alias")


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------

def _rule_donation(art: ProgramArtifact, out: List[Finding]) -> None:
    """PRG201: a train-step executable with zero input→output aliases
    keeps TWO copies of params/opt state live across every step —
    defeats the fused-scan and ZeRO memory story and doubles peak HBM."""
    if not art.is_train_kind() or art.executable is None:
        return
    n = art.alias_count()
    if n == 0:
        out.append(Finding(
            rule="PRG201", severity=ERROR, location=art.location,
            message="train-step executable has no input/output donation "
                    "aliasing — params/opt buffers are copied, not "
                    "reused (add donate_argnums to the jit)"))


def _rule_baked_constants(art: ProgramArtifact, out: List[Finding]) -> None:
    """PRG202: large arrays captured as jaxpr consts are baked into the
    executable — silent device-memory bloat, and a fresh capture (a
    rebuilt closure) recompiles the whole program."""
    if art.jaxpr is None:
        return
    for c in getattr(art.jaxpr, "consts", ()):
        shape = getattr(c, "shape", None)
        dtype = getattr(c, "dtype", None)
        if shape is None or dtype is None:
            continue
        # sized from shape/dtype: jax wraps captured numpy arrays in a
        # TypedNdArray, which has no ``nbytes``
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if nbytes >= CONST_WARN_BYTES:
            sev = ERROR if nbytes >= CONST_ERROR_BYTES else WARN
            out.append(Finding(
                rule="PRG202", severity=sev, location=art.location,
                message=f"closure-captured constant {shape} {dtype} "
                        f"({nbytes / (1 << 20):.1f} MiB) baked into the "
                        f"executable — pass it as an argument"))


def _rule_dtype_promotion(art: ProgramArtifact, out: List[Finding]) -> None:
    """PRG203: f64 values inside a graph with no f64 inputs (a python
    float / enable_x64 promotion leak — doubles the op's cost on TPU,
    where f64 emulation is catastrophic). bf16→f32 promotions are NOT
    flagged: mixed-precision steps keep f32 masters/losses by design."""
    if art.jaxpr is None:
        return
    in_dtypes = {str(getattr(a, "dtype", "")) for a in art.jaxpr.in_avals}
    if "float64" in in_dtypes:
        return  # caller asked for f64 (x64 gradcheck); nothing leaked
    f64_prims = set()
    for eqn in iter_eqns(art.jaxpr):
        for v in eqn.outvars:
            if str(getattr(v.aval, "dtype", "")) == "float64":
                f64_prims.add(eqn.primitive.name)
    if f64_prims:
        out.append(Finding(
            rule="PRG203", severity=WARN, location=art.location,
            message=f"f64 values inside a graph with no f64 inputs "
                    f"(promotion leak in: "
                    f"{', '.join(sorted(f64_prims)[:6])})"))


def _rule_host_callback(art: ProgramArtifact, out: List[Finding]) -> None:
    """PRG204: a host callback inside a compiled step serializes the
    device on the host every dispatch — the exact sync the AOT cache
    exists to eliminate."""
    if art.jaxpr is None:
        return
    hits = sorted(set(_prim_counts(art.jaxpr)) & CALLBACK_PRIMS)
    if hits:
        out.append(Finding(
            rule="PRG204", severity=ERROR, location=art.location,
            message=f"host callback/transfer inside the compiled step: "
                    f"{', '.join(hits)}"))


def _rule_collectives(art: ProgramArtifact, out: List[Finding]) -> None:
    """PRG205: collective audit. (a) a ZeRO step whose gradient exchange
    all-reduces instead of reduce-scattering moves n× the bytes and
    replicates what sharding was meant to split; (b) a bucketed schedule
    with multiple scatter collectives but no ``optimization_barrier``
    lets XLA merge/reorder the buckets — the overlap schedule silently
    degrades to one fused exchange; (c) scheduler-emitted plans: a step
    key carrying ``plan:<digest>`` tokens promised a specific collective
    sequence — op kinds, bucket count, barrier chain — and the compiled
    module must deliver it (``comms.scheduler.lookup_plan`` resolves the
    digests). Single-bucket plans and the legacy ``:b0`` fused exchange
    are variadic single collectives with legitimately no ordering chain
    — exempt."""
    if art.jaxpr is None:
        return
    counts = _prim_counts(art.jaxpr)
    n_allreduce = sum(counts.get(p, 0) for p in ALL_REDUCE_PRIMS)
    n_scatter = sum(counts.get(p, 0) for p in REDUCE_SCATTER_PRIMS)
    n_barrier = counts.get("optimization_barrier", 0)
    _audit_scheduler_plans(art, counts, n_allreduce, n_scatter,
                           n_barrier, out)
    if art.fn_key.startswith("pw_zero"):
        if n_allreduce and not n_scatter:
            out.append(Finding(
                rule="PRG205", severity=ERROR, location=art.location,
                message="ZeRO-mode step contains all-reduce collectives "
                        "but no reduce-scatter — the gradient exchange "
                        "is not sharded"))
        # barrier audit only when the key declares a bucketed schedule
        # (":b<nonzero>"): the fused b0 exchange has one variadic
        # collective (per-leaf eqns) and legitimately no ordering chain.
        # Caveat: a bucket size that swallows the whole tree also yields
        # one bucket — that WARN means "your bucket config is inert",
        # which is worth hearing too.
        m = re.search(r":b(\d+)", art.fn_key)
        if (m and int(m.group(1)) > 0 and n_scatter >= 2
                and n_barrier == 0):
            out.append(Finding(
                rule="PRG205", severity=WARN, location=art.location,
                message=f"{n_scatter} scatter collectives with no "
                        f"optimization_barrier issue-order chain — "
                        f"buckets can merge/reorder"))


def _audit_scheduler_plans(art: ProgramArtifact, counts, n_allreduce,
                           n_scatter, n_barrier,
                           out: List[Finding]) -> None:
    """PRG205(c): verify the compiled collective sequence against every
    scheduler plan whose digest the step key carries."""
    digests = re.findall(r"plan:([0-9a-f]{8,40})", art.fn_key)
    if not digests:
        return
    try:
        from deeplearning4j_tpu.comms import scheduler as comms_sched

        plans = [p for d in digests
                 if (p := comms_sched.lookup_plan(d)) is not None]
    except Exception:
        return  # keys minted elsewhere / comms unavailable: nothing to say
    if not plans:
        return
    # the scheduler emits the Varying -> Invariant gather primitive
    n_gather = (counts.get("all_gather_invariant", 0)
                + counts.get("all_gather", 0))
    exp_barriers = sum(max(0, p.launches() - 1) for p in plans)
    for p in plans:
        if (p.intent == "reduce_scatter" and n_scatter == 0
                and n_allreduce):
            out.append(Finding(
                rule="PRG205", severity=ERROR, location=art.location,
                message=f"plan {p.digest} promised reduce-scatter but "
                        f"the module compiled all-reduce collectives "
                        f"only — the gradient exchange is not sharded"))
        if p.intent == "all_gather" and n_gather == 0:
            out.append(Finding(
                rule="PRG205", severity=WARN, location=art.location,
                message=f"plan {p.digest} promised a native all-gather "
                        f"but the module contains no gather "
                        f"collective"))
    # expected scatter launches: >= one psum_scatter eqn per leaf, so at
    # least one per bucket — fewer means buckets merged despite the pins
    exp_scatter = sum(p.launches() for p in plans
                      if p.intent == "reduce_scatter")
    if exp_scatter and 0 < n_scatter < exp_scatter:
        out.append(Finding(
            rule="PRG205", severity=WARN, location=art.location,
            message=f"scheduler plans promised >= {exp_scatter} "
                    f"reduce-scatter launches; module has {n_scatter} — "
                    f"buckets merged"))
    if exp_barriers and n_barrier < exp_barriers:
        out.append(Finding(
            rule="PRG205", severity=WARN, location=art.location,
            message=f"scheduler plans promised {exp_barriers} "
                    f"optimization_barrier issue-order pins; module has "
                    f"{n_barrier} — buckets can merge/reorder"))


def _near_miss(sig_a, sig_b) -> Optional[str]:
    """Classify two cache signatures as a near-miss recompile hazard.
    Returns a human reason, or None when the recompile was legitimate
    (shape change, different arity/structure)."""
    try:
        leaves_a, tree_a = sig_a
        leaves_b, tree_b = sig_b
    except (TypeError, ValueError):
        return None
    if tree_a != tree_b or len(leaves_a) != len(leaves_b):
        return None
    reasons = []
    for i, (a, b) in enumerate(zip(leaves_a, leaves_b)):
        if a == b:
            continue
        if isinstance(a, str) or isinstance(b, str):
            # one side traced as a weak-typed python scalar
            reasons.append(f"leaf {i}: python scalar vs array "
                           f"({a!r} vs {b!r})")
        elif (isinstance(a, tuple) and isinstance(b, tuple)
                and len(a) >= 2 and len(b) >= 2 and a[0] == b[0]):
            reasons.append(f"leaf {i}: same shape {a[0]}, dtype "
                           f"{a[1]} vs {b[1]} (weak-type churn?)")
        else:
            return None  # a real shape/layout change: legitimate miss
    return "; ".join(reasons) if reasons else None


# kern:<id>:<digest> tokens minted by kernels.cache_tag() into
# use_kernels step keys — the kernel-registry audit's input
_KERNEL_TOKEN_RE = re.compile(r"kern:([A-Za-z0-9_]+):([0-9a-f]{8})")


def _rule_kernel_registry(art: ProgramArtifact,
                          out: List[Finding]) -> None:
    """PRG207: executables whose key carries ``kern:<id>:<digest>``
    tokens promised to route through the Pallas kernel registry —
    (a) an id that does not resolve in the registry means the
    executable was keyed against kernels this process cannot audit
    (ERROR); (b) a key-time tuning digest that mismatches the
    registry's CURRENT winner table means the executable bakes a
    stale/unknown tuned layout — a retune is supposed to mint a NEW
    key, so a mismatch is a dispatch of an unverified kernel (ERROR).
    PRG201 applies unchanged to kernel-bearing train kinds (the token
    is a suffix; the kind prefix still classifies)."""
    tokens = _KERNEL_TOKEN_RE.findall(art.fn_key)
    if not tokens:
        return
    try:
        from deeplearning4j_tpu import kernels as kmod
    except Exception:
        out.append(Finding(
            rule="PRG207", severity=ERROR, location=art.location,
            message="step key carries kern:<id>:<digest> tokens but the "
                    "kernel registry is unavailable — the executable "
                    "cannot be audited"))
        return
    for kid, digest in tokens:
        if kmod.REGISTRY.get(kid) is None:
            out.append(Finding(
                rule="PRG207", severity=ERROR, location=art.location,
                message=f"key token kern:{kid}:{digest} does not resolve "
                        f"through the kernel registry (known kernels: "
                        f"{', '.join(kmod.REGISTRY.ids()) or 'none'})"))
            continue
        current = kmod.tuning_digest(kid)
        if digest != current:
            out.append(Finding(
                rule="PRG207", severity=ERROR, location=art.location,
                message=f"key-time tuning digest {digest} for kernel "
                        f"{kid!r} mismatches the registry's current "
                        f"winner table ({current}) — stale executable "
                        f"vs a retune; rebuild the step so the key "
                        f"re-mints"))


# q:<scheme>:<digest8> tokens minted by MultiLayerNetwork._qtag() into
# quantized-artifact step keys — the calibration-liveness audit's input.
# The leading (^|:) anchor keeps ids like "seq:..." from aliasing.
_QUANT_TOKEN_RE = re.compile(r"(?:^|:)q:([A-Za-z0-9_]+):([0-9a-f]{8})")


def _rule_quant_calibration(art: ProgramArtifact,
                            out: List[Finding]) -> None:
    """PRG208: executables whose key carries ``q:<scheme>:<digest8>``
    tokens were traced from a quantized artifact — (a) a scheme this
    build does not implement means the executable's math cannot be
    audited (ERROR); (b) a digest with no live calibration record means
    the executable outlived a recalibration or a registry restore never
    happened — it bakes scales no record vouches for (ERROR). A
    recalibration mints a new digest and therefore a new key; the stale
    executable surviving under the old token is exactly what this rule
    catches. PRG201 applies unchanged to quantized train kinds."""
    tokens = _QUANT_TOKEN_RE.findall(art.fn_key)
    if not tokens:
        return
    try:
        from deeplearning4j_tpu.nn import inference_opt as iopt
    except Exception:
        out.append(Finding(
            rule="PRG208", severity=ERROR, location=art.location,
            message="step key carries q:<scheme>:<digest> tokens but the "
                    "quantization pass is unavailable — the executable "
                    "cannot be audited"))
        return
    for scheme, digest in tokens:
        if scheme not in iopt.QUANT_SCHEMES:
            out.append(Finding(
                rule="PRG208", severity=ERROR, location=art.location,
                message=f"key token q:{scheme}:{digest} names a "
                        f"quantization scheme this build does not "
                        f"implement (supported: "
                        f"{', '.join(iopt.QUANT_SCHEMES)})"))
            continue
        rec = iopt.lookup_calibration(digest)
        if rec is None:
            out.append(Finding(
                rule="PRG208", severity=ERROR, location=art.location,
                message=f"key token q:{scheme}:{digest} does not resolve "
                        f"to a live calibration record — stale executable "
                        f"vs a recalibration (or a quantized restore that "
                        f"skipped ModelRegistry.load); rebuild the step "
                        f"so the key re-mints"))
        elif rec.scheme != scheme:
            out.append(Finding(
                rule="PRG208", severity=ERROR, location=art.location,
                message=f"key token q:{scheme}:{digest} resolves to a "
                        f"calibration record of scheme {rec.scheme!r} — "
                        f"token/record drift"))


def _rule_recompile_hazard(art: ProgramArtifact,
                           out: List[Finding]) -> None:
    """PRG206: this miss differs from an already-cached signature only
    in python-scalar/dtype leaves — the classic silent-recompile churn
    (an int passed one call, np.int32 the next). One finding per
    compile, naming the first near-miss sibling."""
    if art.signature is None:
        return
    for sib in art.sibling_sigs:
        reason = _near_miss(art.signature, sib)
        if reason:
            out.append(Finding(
                rule="PRG206", severity=WARN, location=art.location,
                message=f"near-miss recompile — signature churn, not a "
                        f"shape change: {reason}. Pin the argument's "
                        f"dtype (np.int32/np.float32) at the callsite"))
            return


_RULES = (
    _rule_donation,
    _rule_baked_constants,
    _rule_dtype_promotion,
    _rule_host_callback,
    _rule_collectives,
    _rule_kernel_registry,
    _rule_quant_calibration,
    _rule_recompile_hazard,
)


def lint_program(art: ProgramArtifact) -> List[Finding]:
    """Run every program rule over one compile's artifacts."""
    out: List[Finding] = []
    for rule in _RULES:
        rule(art, out)
    return _apply_waivers(art, out)


# --------------------------------------------------------------------------
# the AOT-cache hook
# --------------------------------------------------------------------------

# (graph_key, fn_key) -> {"aliases": int|None, "findings": int} for every
# train-kind compile this process performed — the donation-audit record
_AUDIT: Dict[Tuple[str, str], dict] = {}
_AUDIT_LOCK = threading.Lock()
# dedup: a (rule, graph, kind) triple is reported once per process, so a
# fallback-retracing loop cannot spam the log
_REPORTED: set = set()


def on_compile(key, traced, executable, sibling_keys=()) -> None:
    """Called by ``optimize.aot_cache`` after each lower/compile miss
    (under the cache lock — everything here is host-side and fast).
    ``key`` = (graph_key, fn_key, signature); ``traced`` = the jax
    Traced (or None); ``sibling_keys`` = cached keys sharing the
    (graph_key, fn_key) prefix."""
    graph_key, fn_key, signature = key[0], key[1], key[2]
    art = ProgramArtifact(
        graph_key=graph_key, fn_key=fn_key,
        jaxpr=getattr(traced, "jaxpr", None),
        executable=executable, signature=signature,
        sibling_sigs=tuple(k[2] for k in sibling_keys))
    findings = lint_program(art)
    if art.is_train_kind():
        with _AUDIT_LOCK:
            _AUDIT[(graph_key, fn_key)] = {
                "aliases": art.alias_count(),
                "findings": len([f for f in findings if not f.waived]),
            }
    fresh = []
    for f in findings:
        k = (f.rule, graph_key, fn_key)
        if k in _REPORTED:
            continue
        _REPORTED.add(k)
        LOG.record(f)
        fresh.append(f)
    strict = [f for f in fresh if not f.waived and f.severity == ERROR]
    if strict and _strict_mode():
        raise ProgramLintError(strict)


def _strict_mode() -> bool:
    import os

    return os.environ.get("DL4J_TPU_PROGRAM_LINT", "1") == "strict"


def donation_audit() -> Dict[Tuple[str, str], dict]:
    """Per-(graph_key, fn_key) donation record for every train-kind
    executable compiled this process. An entry with ``aliases == 0``
    is a step paying double params memory — the repo-clean test asserts
    there are none."""
    with _AUDIT_LOCK:
        return dict(_AUDIT)


def reset() -> None:
    """Test hook: forget audit + dedup state (the findings LOG is owned
    by the caller; clear it separately)."""
    with _AUDIT_LOCK:
        _AUDIT.clear()
    _REPORTED.clear()


# --------------------------------------------------------------------------
# standalone entry (tests / `python -m deeplearning4j_tpu.analysis program`)
# --------------------------------------------------------------------------

def trace_artifact(jit_fn, args, graph_key: str = "adhoc",
                   fn_key: str = "adhoc", compile: bool = True,
                   sibling_sigs: Tuple = ()) -> ProgramArtifact:
    """Build a ProgramArtifact from a jitted fn outside the cache —
    fixture tests and the CLI drive rules through this without touching
    process-global cache state."""
    from deeplearning4j_tpu.optimize.aot_cache import signature_of

    traced = jit_fn.trace(*args) if hasattr(jit_fn, "trace") else None
    executable = None
    if compile:
        lowered = (traced.lower() if traced is not None
                   else jit_fn.lower(*args))
        executable = lowered.compile()
    return ProgramArtifact(
        graph_key=graph_key, fn_key=fn_key,
        jaxpr=getattr(traced, "jaxpr", None),
        executable=executable, signature=signature_of(args),
        sibling_sigs=tuple(sibling_sigs))
