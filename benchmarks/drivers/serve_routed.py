"""Driver of the serving cells of a model with routed experts and window
layers: ``serve_generation``'s closed loop (``GenerationEngine.submit``
... the handle's ``event``), one chip.

What it shares with ``serve_generation`` it imports unchanged: the one
load loop (``_Load``), the sample of finished requests (``_sample``), the
way tokens are counted. What differs:

- the program's in-graph counters (``engine.stats()["layer_counts"]``:
  experts touched, routed slots, the fullest expert, the cached positions
  read and held) are read when the window opens, when the trace stops
  and when the window closes; the ratios the counter metrics report are
  made here;
- the check never holds ``[positions, vocabulary]`` logits: the plain
  reference computes the head for the served rows only, and the gaps are
  reduced on the device (a 200 k vocabulary); it compares the MEAN gap
  (``serve_sessions.gap_numbers``: the widest is the tail of a discrete
  choice swapped at a near-tie, and goes to ``notes``);
- ``calibrate`` also reads the reference's planted faults.
"""

from __future__ import annotations

import gc
import time

from benchmarks.drivers.serve_generation import _Load, _sample, percentile
from benchmarks.drivers.serve_sessions import gap_numbers

ROWS_BLOCK = 256      # served rows padded to a multiple of this
TOKEN_BLOCK = 1024    # sequences padded to a multiple of this
NEAREST_FAULT = "rotate_full"   # the planted fault that reads lowest


def _ratios(counts: dict, cfg: dict) -> dict:
    """The counter metrics, from the window's ``layer_counts``."""
    out = {}
    steps = counts.get("moe_expert_layer_steps")
    if steps:
        held = float(cfg.get("experts_held", (0, cfg["num_experts"]))[1])
        out["moe_experts_touched_pct"] = (
            100.0 * counts["moe_experts_touched"] / (steps * held))
        if counts["moe_routed_slots"]:
            out["moe_max_load_over_mean"] = (
                counts["moe_max_load"] * held / counts["moe_routed_slots"])
    if counts.get("decode_kv_bucket_positions"):
        out["kv_read_pct"] = (100.0 * counts["decode_kv_read_positions"]
                              / counts["decode_kv_bucket_positions"])
    return out


def measure(ctx, seed: int, seconds: float, tracing: bool,
            keep_programs: bool = False) -> dict:
    """Set-up, the window and the engine's close: everything a run takes
    from the program, and the sample of finished requests for the check."""
    model = ctx.module("model")     # first: no such program, no run
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.generation import GenerationEngine

    from benchmarks import harness, traffic_gen

    cfg, mix = ctx.config, ctx.traffic
    ref = ctx.module("reference")
    arrival = mix["arrival"]
    if arrival["process"] != "closed_loop":
        raise ValueError("serve_routed drives a closed loop")
    aot_cache.place_compile_cache()

    # ---- set-up ---------------------------------------------------------
    weights = ref.init_weights(cfg, seed)
    dec, gen = model.build(cfg, weights)
    eng = GenerationEngine(dec, gen)
    warm = eng.warmup()
    state_bytes = dec.state_bytes(dec.kv_ladder[-1])
    print(f"# warm-up: {warm['compiled']} executables compiled in "
          f"{warm['compile_seconds']} s; buckets kv {warm['kv_buckets']} "
          f"prompt {warm['prompt_buckets']} join {warm['join_buckets']}; "
          f"state {state_bytes}; at {ctx.setup_seconds():.1f} s", flush=True)
    stream = traffic_gen.requests(mix, cfg["vocab_size"], seed)
    trace = harness.TraceWindow(tracing,
                                float(ctx.cell_file["trace_seconds"]),
                                ctx.rehearsal)
    load = _Load(eng, traffic_gen.requests(mix, cfg["vocab_size"], seed,
                                           stream=1), arrival, trace)
    load.start()
    load.run_until(load.epoch + float(arrival["warm_seconds"]))
    trace.open()
    executables = aot_cache.stats()["misses"]
    setup_s = ctx.setup_seconds()
    print(f"# set-up {setup_s:.2f} s, {executables} executable(s)",
          flush=True)

    # ---- the window -----------------------------------------------------
    t0 = load.restart(stream)
    t_end = t0 + seconds
    load.window = (t0, t_end)
    held = {id(f): len(f.handle.out) for f in load.flying}
    aot_before = aot_cache.stats()
    stats_before = eng.stats()
    traced = {}
    trace.on_stop = lambda: traced.update(stats=eng.stats(),
                                          t=time.monotonic())
    load.run_until(t_end)
    t_closed = time.monotonic()
    emitted = sum(len(f.handle.out) - held.get(id(f), 0)
                  for f in load.flying + [d for d in load.done
                                          if d.t_done >= t0])
    stats_after = eng.stats()
    aot_after = aot_cache.stats()
    trace.stop()
    memory = harness.memory_peak_bytes()
    eng.close()

    # ---- what the window says -------------------------------------------
    ended = [f for f in load.done if t0 <= f.t_done < t_end]
    finished = [f for f in ended if f.error is None]
    failed = [f for f in ended if f.error is not None]
    failed += [f for f in load.refused if t0 <= f.t_submit < t_end]
    end_to_end = {"serve_tokens_per_s": emitted / (t_closed - t0),
                  "setup_s": setup_s}
    lat = [f.handle.t_first - f.t_submit for f in finished] or [0.0]
    print(f"# window: {emitted} tokens emitted; {len(finished)} requests "
          f"finished; ttft from submit p50 {1e3 * percentile(lat, 0.5):.1f} "
          f"ms p95 {1e3 * percentile(lat, 0.95):.1f} ms (no metric of this "
          f"cell)", flush=True)
    for f in (load.refused + [d for d in load.done if d.error])[:3]:
        print(f"# a request failed: {f.error!r}", flush=True)

    def delta(after, key):
        return after[key] - stats_before[key]

    def layer_counts(after):
        before = stats_before.get("layer_counts", {})
        return {k: v - before.get(k, 0)
                for k, v in after.get("layer_counts", {}).items()}

    counts = layer_counts(stats_after)
    counters = {
        "compiles": aot_after["misses"] - aot_before["misses"],
        "aot_fallbacks": aot_after["fallbacks"],
        "executables": executables,
        "tokens": delta(stats_after, "tokens_total"),
        "joined": delta(stats_after, "joined_total"),
        "retired": delta(stats_after, "retired_total"),
        "prefill_seconds": delta(stats_after, "prefill_seconds"),
        "decode_seconds": delta(stats_after, "decode_seconds"),
        "queued_at_start": stats_before["queued"],
        "queued_at_end": stats_after["queued"],
        **counts, **_ratios(counts, cfg)}
    records = [{"prompt": len(f.req.prompt), "out": len(f.handle.out),
                "t_first": f.handle.t_first, "t_done": f.t_done}
               for f in load.done if f.error is None]
    # the rows still decoding at the close were live in the traced part too
    records += [{"prompt": len(f.req.prompt), "out": len(f.handle.out),
                 "t_first": f.handle.t_first, "t_done": t_closed}
                for f in load.flying if f.handle.t_first is not None]
    obs_traced = None
    if traced:
        obs_traced = {"t_start": trace.t_start, "t_stop": traced["t"],
                      "tokens": delta(traced["stats"], "tokens_total"),
                      "joined": delta(traced["stats"], "joined_total"),
                      "layer_counts": layer_counts(traced["stats"])}
    wrong_length = sum(len(f.handle.out) != f.req.max_new for f in finished)
    sample = _sample(finished, seed, int(ctx.cell_file["checked_requests"]))
    served = [(list(f.req.prompt), list(f.handle.out)) for f in sample]
    out = {
        "end_to_end": end_to_end,
        "attempted": len(finished) + len(failed), "failed": len(failed),
        "memory": memory, "counters": counters, "requests": records,
        "traced": obs_traced, "served": served,
        "wrong_length": wrong_length, "weights": weights,
        "trace_window": trace,
        "window": {"seconds": seconds, "t0": t0, "t_end": t_end},
        "notes": {"warm": {k: warm[k] for k in ("compiled",
                                                "compile_seconds")},
                  "state_bytes": state_bytes,
                  "queued_at_start": stats_before["queued"],
                  "queued_at_end": stats_after["queued"],
                  "layer_counts": counts,
                  "tokens": {"emitted": emitted,
                             "engine_counter": counters["tokens"],
                             "in_finished_answers": sum(
                                 len(f.handle.out) for f in finished)}}}
    del eng, dec, load, finished, failed, sample
    if not keep_programs:
        aot_cache.clear()
    gc.collect()
    return out


def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def check(ctx, weights, served, control: bool = False, faults=(),
          own_precision=()) -> dict:
    """For each served token the gap by which its logit lies below the
    reference's best at its position, in standard deviations of the
    reference's logits there (``correct.logit_gaps``'s number, reduced on
    the device: the rows' logits over a 200 k vocabulary never reach the
    host); with ``control`` the same for the tokens the reference puts
    first when it computes in the configuration's ``control_dtype``, and
    for each of ``faults`` with that mechanism broken in the reference;
    with ``own_precision`` (``"stated"``: the router in float32, as the
    configuration states it and the program computes it; ``"rounded"``:
    the router's operands rounded too) for the tokens the reference puts
    first when it computes in the configuration's OWN
    ``matmul_operand_dtype``: what a faultless program may read.
    Sequences and served rows are padded to ONE length each, so every
    program of the reference compiles once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = ctx.module("reference")
    cfg = ctx.config
    others = {}
    if control:
        others["control"] = ref.Forward(cfg, ref.lower_precision(
            cfg["control_dtype"]))
    for fault in faults:
        others[fault] = ref.Forward(cfg, fault=fault)
    for router in own_precision:
        others[f"own_precision_router_{router}"] = ref.Forward(
            cfg, ref.lower_precision(cfg["matmul_operand_dtype"]),
            route_q=None if router == "rounded" else lambda x: x)
    fwd = ref.Forward(cfg)
    pad_to = _pad_to(max((len(p) + len(o) for p, o in served), default=1),
                     TOKEN_BLOCK)
    n_rows = _pad_to(max((len(o) for _, o in served), default=1), ROWS_BLOCK)

    @jax.jit
    def gaps_of(logits, tokens):
        got = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
        return (logits.max(axis=-1) - got) / logits.std(axis=-1)

    first_of = jax.jit(lambda logits: logits.argmax(axis=-1))
    all_gaps = []
    other_gaps = {name: [] for name in others}
    for prompt, out in served:
        seq = prompt + out[:-1]
        seq = seq + [0] * (pad_to - len(seq))       # causal: changes nothing
        rows = np.minimum(len(prompt) - 1 + np.arange(n_rows),
                          len(prompt) + len(out) - 2)
        tokens = np.zeros((n_rows,), np.int32)
        tokens[:len(out)] = out
        logits = fwd(weights, seq, rows)
        all_gaps += np.asarray(gaps_of(logits, tokens),
                               np.float64)[:len(out)].tolist()
        for name, other in others.items():
            theirs = first_of(other(weights, seq, rows))
            other_gaps[name] += np.asarray(
                gaps_of(logits, theirs), np.float64)[:len(out)].tolist()
        del logits
    out = {"numbers": gap_numbers(all_gaps), "checked_requests": len(served),
           "checked_tokens": len(all_gaps)}
    out.update({name: gap_numbers(g) for name, g in other_gaps.items()})
    return out


def run(ctx) -> dict:
    from benchmarks import correct

    obs = measure(ctx, ctx.args.seed, ctx.args.seconds,
                  bool(ctx.args.trace))
    obs["trace"] = obs.pop("trace_window").reduce()
    t_ref = time.monotonic()
    served = obs.pop("served")
    checked = check(ctx, obs.pop("weights"), served)
    # no stop token: every finished answer has the length it was asked for
    checked["numbers"]["answers_of_wrong_length"] = float(
        obs["wrong_length"])
    ok, compared = correct.judge(checked["numbers"],
                                 ctx.cell_file["limits"])
    obs["correct"] = (ok and obs["attempted"] > 0
                      and not obs["counters"]["aot_fallbacks"])
    obs["compared"] = compared
    obs["notes"].update(
        reference_s=time.monotonic() - t_ref,
        served_logit_gap_widest=checked["numbers"]["served_logit_gap"],
        checked_requests=checked["checked_requests"],
        checked_tokens=checked["checked_tokens"],
        checked_contexts=[len(p) + len(o) for p, o in served])
    return obs


def calibrate(ctx, args) -> list:
    """Program, control and the reference's planted faults against the
    reference, seed by seed, in one process, each over a short window at
    the cell's own load (``benchmarks/calibrate.py``). On EVERY seed: the
    program, the reference in the configuration's own precision with the
    router as stated (what a faultless program may read) and the fault
    that reads nearest the program (:data:`NEAREST_FAULT`); on the first
    ``--control-seeds`` also the control, the other faults and the own
    precision with the router rounded."""
    import json

    faults = ctx.module("reference").FAULTS
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        obs = measure(ctx, seed, args.seconds, False, keep_programs=True)
        first = i < args.control_seeds
        checked = check(
            ctx, obs.pop("weights"), obs.pop("served"), control=first,
            faults=faults if first else (NEAREST_FAULT,),
            own_precision=("stated", "rounded") if first else ("stated",))
        checked["numbers"]["answers_of_wrong_length"] = float(
            obs["wrong_length"])
        row = {"seed": seed, "program": checked["numbers"],
               "checked_tokens": checked["checked_tokens"],
               "end_to_end": obs["end_to_end"],
               "seconds": time.monotonic() - t0}
        row.update({name: numbers for name, numbers in checked.items()
                    if isinstance(numbers, dict) and name != "numbers"})
        print("calibrate", json.dumps(row), flush=True)
        rows.append(row)
    return rows
