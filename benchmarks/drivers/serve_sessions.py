"""Driver of the long-session serving cells: every client's one request is
prefilled during set-up and the window measures the rows while they
DECODE (``GenerationEngine.submit`` ... the handles' ``out``), one chip.

What it shares with ``serve_generation`` it imports unchanged: the one
load loop (``_Load``), the way tokens are counted (``len(handle.out)``
less what each handle held when the window opened). What differs:

- the window opens when EVERY client's request has emitted its first
  token (all rows joined) and ``settle_seconds`` more have passed; the
  prefills are part of ``setup_s``. If they have not all joined after
  ``join_timeout_seconds`` the run fails with a message, it does not
  measure a half-full batch;
- no answer ends inside the window (the traffic's answers are longer than
  the window can produce), so the check takes rows IN FLIGHT at the close
  by the tokens they hold: ``checked_requests`` of them drawn from the
  seed, the longest context among them, each once through the plain
  reference, which computes the head for the served rows only;
- ``rows_short_of_window`` replaces ``answers_of_wrong_length``: a row in
  flight has to hold at least K tokens for every decode window that ran
  wholly inside the measured window.
"""

from __future__ import annotations

import gc
import math
import time

from benchmarks.drivers.serve_generation import _Load

COUNTERS = ("sparse_attended_positions", "sparse_context_positions",
            "sparse_dense_fallback_queries", "recurrent_state_updates")


def _program_counts() -> dict:
    """The cached layers' in-graph counters as the program's registry
    holds them; empty where the program has none."""
    from deeplearning4j_tpu import telemetry

    snap = telemetry.REGISTRY.snapshot(run_collectors=False)
    return {name: float(snap[f"dl4j_{name}_total"]) for name in COUNTERS
            if f"dl4j_{name}_total" in snap}


def _decode_windows_inside(t0: float, t1: float) -> int:
    """How many of the program's ``gen.decode`` spans lie wholly inside
    ``[t0, t1]`` (host monotonic seconds)."""
    from benchmarks.readers import program_spans

    lo, hi = 1e9 * t0, 1e9 * t1
    return sum(e["name"] == "gen.decode" and e["start_ns"] >= lo
               and e["end_ns"] <= hi for e in program_spans.events())


def measure(ctx, seed: int, seconds: float, tracing: bool,
            keep_programs: bool = False) -> dict:
    """Set-up (with every client's prefill), the window and the engine's
    close; the rows in flight at the close for the check."""
    model = ctx.module("model")     # first: no such program, no run
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.generation import GenerationEngine

    from benchmarks import harness, traffic_gen

    cfg, mix = ctx.config, ctx.traffic
    ref = ctx.module("reference")
    arrival = mix["arrival"]
    if arrival["process"] != "closed_loop":
        raise ValueError("serve_sessions drives a closed loop of sessions")
    aot_cache.place_compile_cache()

    # ---- set-up ---------------------------------------------------------
    weights = ref.init_weights(cfg, seed)
    dec, gen = model.build(cfg, weights)
    eng = GenerationEngine(dec, gen)
    warm = eng.warmup()
    print(f"# warm-up: {warm['compiled']} executables compiled in "
          f"{warm['compile_seconds']} s; buckets kv {warm['kv_buckets']} "
          f"prompt {warm['prompt_buckets']} join {warm['join_buckets']}; "
          f"at {ctx.setup_seconds():.1f} s", flush=True)
    trace = harness.TraceWindow(tracing,
                                float(ctx.cell_file["trace_seconds"]),
                                ctx.rehearsal)
    load = _Load(eng, traffic_gen.requests(mix, cfg["vocab_size"], seed),
                 arrival, trace)
    load.start()
    deadline = load.epoch + float(arrival["join_timeout_seconds"])
    while not all(f.handle.out for f in load.flying):
        if load.refused or load.done or time.monotonic() > deadline:
            joined = sum(bool(f.handle.out) for f in load.flying)
            errors = [repr(f.error) for f in load.refused + load.done][:3]
            eng.close()
            raise RuntimeError(
                f"serve_sessions: {joined} of {arrival['clients']} sessions "
                f"had joined after {time.monotonic() - load.epoch:.0f} s "
                f"(limit {arrival['join_timeout_seconds']} s; refused or "
                f"ended early: {errors}): the window is not opened on a "
                f"half-full batch")
        load.run_until(time.monotonic() + 0.05)
    t_joined = time.monotonic()
    load.run_until(t_joined + float(arrival["settle_seconds"]))
    trace.open()
    executables = aot_cache.stats()["misses"]
    setup_s = ctx.setup_seconds()
    print(f"# set-up {setup_s:.2f} s, {executables} executable(s); the "
          f"{len(load.flying)} sessions joined in "
          f"{t_joined - load.epoch:.1f} s", flush=True)

    # ---- the window -----------------------------------------------------
    t0 = time.monotonic()
    t_end = t0 + seconds
    load.window = (t0, t_end)
    sessions = list(load.flying)
    held = {id(f): len(f.handle.out) for f in sessions}
    aot_before = aot_cache.stats()
    stats_before = eng.stats()
    counts_before = _program_counts()
    traced = {}
    trace.on_stop = lambda: traced.update(
        stats=eng.stats(), t=time.monotonic(),
        held=[len(f.handle.out) for f in sessions])
    load.run_until(t_end)
    t_closed = time.monotonic()
    at_close = {id(f): len(f.handle.out) for f in sessions}
    stats_after = eng.stats()
    aot_after = aot_cache.stats()
    counts_after = _program_counts()
    trace.stop()
    memory = harness.memory_peak_bytes()
    windows = _decode_windows_inside(t0, t_closed)
    k = int(cfg["serving"]["fused_steps"])
    served = [(list(f.req.prompt), list(f.handle.out)[:at_close[id(f)]],
               held[id(f)]) for f in sessions if f in load.flying]
    eng.close()

    # ---- what the window says -------------------------------------------
    emitted = sum(at_close[id(f)] - held[id(f)] for f in sessions)
    ended = [f for f in load.done if f.t_done is not None and f.t_done >= t0]
    failed = [f for f in ended if f.error is not None] + [
        f for f in load.refused if f.t_submit >= t0]
    short = sum(at_close[id(f)] - held[id(f)] < windows * k
                for f in sessions)
    end_to_end = {"serve_tokens_per_s": emitted / (t_closed - t0),
                  "setup_s": setup_s}
    print(f"# window: {emitted} tokens emitted by {len(sessions)} rows in "
          f"{windows} whole decode windows of K = {k}; {len(ended)} answers "
          f"ended inside it", flush=True)
    for f in failed[:3]:
        print(f"# a request failed: {f.error!r}", flush=True)

    def delta(key):
        return stats_after[key] - stats_before[key]

    counters = {
        "compiles": aot_after["misses"] - aot_before["misses"],
        "aot_fallbacks": aot_after["fallbacks"],
        "executables": executables,
        "tokens": delta("tokens_total"), "joined": delta("joined_total"),
        "retired": delta("retired_total"),
        "decode_seconds": delta("decode_seconds")}
    for name, after in counts_after.items():
        counters[name] = after - counts_before.get(name, 0.0)
    if counters.get("sparse_context_positions"):
        counters["sparse_attended_pct"] = (
            100.0 * counters["sparse_attended_positions"]
            / counters["sparse_context_positions"])
    obs_traced = None
    if traced:
        obs_traced = {
            "t_start": trace.t_start, "t_stop": traced["t"],
            "tokens": traced["stats"]["tokens_total"]
            - stats_before["tokens_total"],
            "joined": traced["stats"]["joined_total"]
            - stats_before["joined_total"],
            "contexts_open": [len(f.req.prompt) + held[id(f)]
                              for f in sessions],
            "contexts_stop": [len(f.req.prompt) + n for f, n in zip(
                sessions, traced["held"])]}
    out = {
        "end_to_end": end_to_end,
        "attempted": len(sessions), "failed": len(failed),
        "memory": memory, "counters": counters, "traced": obs_traced,
        "served": served, "rows_short": short, "weights": weights,
        # (prompt, answer) of what ended, where a mix lets answers end
        "ended": [(list(f.req.prompt), list(f.handle.out))
                  for f in load.done if f.error is None],
        "trace_window": trace,
        "window": {"seconds": seconds, "t0": t0, "t_end": t_end},
        "notes": {"warm": {k_: warm[k_] for k_ in ("compiled",
                                                    "compile_seconds")},
                  "joined_after_s": t_joined - load.epoch,
                  "decode_windows": windows,
                  "answers_ended_in_window": len(ended),
                  "contexts_at_close": sorted(
                      len(f.req.prompt) + at_close[id(f)] for f in sessions),
                  "tokens": {"emitted": emitted,
                             "engine_counter": counters["tokens"]}}}
    del eng, dec, load, sessions
    if not keep_programs:
        aot_cache.clear()
    gc.collect()
    return out


def sample(served, seed: int, k: int):
    """``k`` of the rows in flight drawn from the seed, the longest
    context among them."""
    import numpy as np

    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [served[longest]] + [served[rest[int(i)]] for i in picks]


def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def check(ctx, weights, served, control: bool = False, faults=()) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sampled rows (every token a row holds, the
    window's and the ones before it); with ``control`` the same number for
    the tokens the reference puts first when it computes in the
    configuration's ``control_dtype``, and for each of ``faults`` with that
    mechanism broken in the reference (``reference.Forward(fault=)``).
    The sequences are padded to ONE length and the served rows to one
    count, so each program of the reference compiles once; only the
    served rows' logits are computed."""
    import numpy as np

    from benchmarks import correct

    ref = ctx.module("reference")
    cfg = ctx.config
    others = {}
    if control:
        others["control"] = ref.Forward(cfg, ref.lower_precision(
            cfg["control_dtype"]))
    for fault in faults:
        others[fault] = ref.Forward(cfg, fault=fault)
    fwd = ref.Forward(cfg)
    step = ref.TOKEN_BLOCK if cfg["serving"]["max_len"] > ref.TOKEN_BLOCK \
        else cfg["sparse_config"]["block_size"]
    pad_to = _pad_to(max((len(p) + len(o) for p, o, _ in served), default=1),
                     step)
    n_rows = _pad_to(max((len(o) for _, o, _ in served), default=1), 64)
    all_gaps, n_window = [], 0
    other_gaps = {name: [] for name in others}
    for prompt, out, held in served:
        seq = prompt + out[:-1]
        seq = seq + [0] * (pad_to - len(seq))       # causal: changes nothing
        rows = np.minimum(len(prompt) - 1 + np.arange(n_rows),
                          len(prompt) + len(out) - 2)
        logits = np.asarray(fwd(weights, seq, rows))[:len(out)]
        all_gaps += correct.logit_gaps(logits, 0, out)
        n_window += len(out) - held
        for name, other in others.items():
            theirs = np.asarray(other(weights, seq, rows)).argmax(-1)
            other_gaps[name] += correct.logit_gaps(
                logits, 0, theirs[:len(out)].tolist())
    out = {"numbers": gap_numbers(all_gaps), "checked_requests": len(served),
           "checked_tokens": len(all_gaps),
           "checked_tokens_of_the_window": n_window}
    out.update({name: gap_numbers(g) for name, g in other_gaps.items()})
    return out


def gap_numbers(gaps) -> dict:
    """``served_logit_gap``: the widest gap by which a served token's
    logit lies below the reference's best, in standard deviations of the
    reference's logits at its position; ``served_logit_gap_mean``: the
    mean of those gaps over the served tokens (a token the reference
    would have served too reads 0). The widest gap of some thousand
    tokens is a tail event of the configuration's own rounding; the mean
    is what a mechanism left out moves."""
    if not gaps:
        return {"served_logit_gap": math.inf, "served_logit_gap_mean": math.inf}
    return {"served_logit_gap": max(gaps),
            "served_logit_gap_mean": sum(gaps) / len(gaps)}


def run(ctx) -> dict:
    from benchmarks import correct

    obs = measure(ctx, ctx.args.seed, ctx.args.seconds,
                  bool(ctx.args.trace))
    obs["trace"] = obs.pop("trace_window").reduce()
    t_ref = time.monotonic()
    rows = sample(obs.pop("served"), ctx.args.seed,
                  int(ctx.cell_file["checked_requests"]))
    checked = check(ctx, obs.pop("weights"), rows)
    checked["numbers"]["rows_short_of_window"] = float(obs["rows_short"])
    ok, compared = correct.judge(checked["numbers"],
                                 ctx.cell_file["limits"])
    obs["correct"] = (ok and obs["attempted"] > 0
                      and obs["counters"]["tokens"] > 0
                      and not obs["counters"]["aot_fallbacks"])
    obs["compared"] = compared
    obs["notes"].update(
        reference_s=time.monotonic() - t_ref,
        served_logit_gap_widest=checked["numbers"]["served_logit_gap"],
        checked_requests=checked["checked_requests"],
        checked_tokens=checked["checked_tokens"],
        checked_tokens_of_the_window=checked["checked_tokens_of_the_window"],
        checked_contexts=[len(p) + len(o) for p, o, _ in rows])
    return obs


FAULTS = ("no_decay", "no_topk", "dense_attention")


def calibrate(ctx, args) -> list:
    """Program, control and the reference's planted faults against the
    reference, seed by seed, in one process, each over a short window at
    the cell's own load (``benchmarks/calibrate.py``)."""
    import json

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        obs = measure(ctx, seed, args.seconds, False, keep_programs=True)
        first = i < args.control_seeds
        checked = check(ctx, obs.pop("weights"),
                        sample(obs.pop("served"), seed,
                               int(ctx.cell_file["checked_requests"])),
                        control=first, faults=FAULTS if first else ())
        checked["numbers"]["rows_short_of_window"] = float(obs["rows_short"])
        row = {"seed": seed, "program": checked["numbers"],
               "checked_tokens": checked["checked_tokens"],
               "end_to_end": obs["end_to_end"],
               "seconds": time.monotonic() - t0}
        row.update({name: checked[name] for name in ("control",) + FAULTS
                    if name in checked})
        print("calibrate", json.dumps(row), flush=True)
        rows.append(row)
    return rows
