"""Driver of the serving cells: ``GenerationEngine.submit`` ... the handle's
``event`` ... ``result()``, one chip.

Load comes from ONE thread of this process, for closed and open loops
alike: it submits what is due, and polls the handles of the requests in
flight (every half millisecond or so) to stamp each one's completion.
The engine's own loop thread is the only other one.

- closed loop (``clients`` outstanding): a client's next request is sent
  when its last one completes; the metric is the output tokens of every
  request that completed inside the window over the window.
- open loop (Poisson at a fixed rate): a request is due at a time the
  seed fixes; time to first token counts from that due time, not from
  ``submit``; requests still running when the window closes are waited
  for (up to ``drain_seconds``) and count with the wait they had.

The load runs for ``warm_seconds`` before the window opens (part of
set-up): the rows are full and every common executable has run once when
the first measured request arrives.

Once the window has closed and the memory has been read, the engine is
closed and its cache freed; the plain reference then runs once over a
sample of the requests the window finished (the longest in it) and reads,
for every served token, how far its logit lies below the reference's
best.
"""

from __future__ import annotations

import gc
import math
import time

POLL_S = 0.0005


class _InFlight:
    __slots__ = ("req", "handle", "t_due", "t_submit", "t_done", "client",
                 "error", "in_window")

    def __init__(self, req, handle, t_due, t_submit, client, in_window):
        self.req, self.handle = req, handle
        self.t_due, self.t_submit, self.client = t_due, t_submit, client
        self.t_done = None
        self.error = None
        self.in_window = in_window


def percentile(values, q):
    """Nearest rank: the smallest value with at least ``q`` of the
    sample at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


class _Load:
    """The one load loop. ``phase`` runs it until ``until`` (monotonic
    seconds); submissions stop there, completions are still stamped."""

    def __init__(self, eng, stream, arrival, trace):
        self.eng, self.stream, self.arrival = eng, stream, arrival
        self.trace = trace
        self.closed = arrival["process"] == "closed_loop"
        self.flying = []
        self.done = []
        self.refused = []
        self.next_req = None
        self.epoch = None          # monotonic time of the schedule's zero
        self.window = (math.inf, math.inf)

    def _submit(self, req, t_due, client):
        from benchmarks import harness

        now = time.monotonic()
        in_window = self.window[0] <= t_due < self.window[1]
        try:
            with harness.annotate("submit"):
                handle = self.eng.submit(req.prompt,
                                         max_new_tokens=req.max_new)
        except Exception as e:  # noqa: BLE001 — a refusal is a result
            rec = _InFlight(req, None, t_due, now, client, in_window)
            rec.error = e
            self.refused.append(rec)
            return
        self.flying.append(_InFlight(req, handle, t_due, now, client,
                                     in_window))

    def _reap(self):
        now = time.monotonic()
        current, self.flying = self.flying, []
        for f in current:
            if f.handle.event.is_set():
                f.t_done = now
                f.error = f.handle.error
                self.done.append(f)
                if self.closed and now < self.stop_at:
                    self._submit(next(self.stream), now, f.client)
            else:
                self.flying.append(f)

    def start(self):
        self.epoch = time.monotonic()
        self.stop_at = math.inf
        if self.closed:
            for c in range(int(self.arrival["clients"])):
                self._submit(next(self.stream), self.epoch, c)
        else:
            self.next_req = next(self.stream)

    def restart(self, stream):
        """The window opens: from here the requests come from ``stream``
        and their due times count from now, so that every seed's window
        holds the same whole cycles of the mix. A warm-up request that
        was not yet due is dropped; those in flight finish as they
        are."""
        self.stream = stream
        self.epoch = time.monotonic()
        if not self.closed:
            self.next_req = next(stream)
        return self.epoch

    def run_until(self, until, submit_until=None):
        """Poll and submit until ``until``; nothing is submitted at or
        after ``submit_until`` (default ``until``)."""
        from benchmarks import harness

        self.stop_at = until if submit_until is None else submit_until
        what = ("waiting_for_completion" if self.closed
                else "waiting_for_due_time")
        while True:
            now = time.monotonic()
            if now >= until:
                return
            if self.trace.due():
                self.trace.stop()
            self._reap()
            if not self.closed:
                while (self.next_req is not None
                       and self.epoch + self.next_req.t_due <= now
                       and self.epoch + self.next_req.t_due < self.stop_at):
                    self._submit(self.next_req,
                                 self.epoch + self.next_req.t_due, None)
                    self.next_req = next(self.stream)
            with harness.annotate(what):
                time.sleep(POLL_S)

    def drain(self, seconds):
        """Wait for the window's requests that are still in flight."""
        deadline = time.monotonic() + seconds
        self.stop_at = -math.inf
        while time.monotonic() < deadline:
            self._reap()
            if not any(f.in_window for f in self.flying):
                return
            time.sleep(POLL_S)


def measure(ctx, seed: int, seconds: float, tracing: bool,
            keep_programs: bool = False) -> dict:
    """Set-up, the window and the engine's close: everything a run takes
    from the program, and the sample of finished requests for the check.
    ``keep_programs`` (calibration, several windows in one process) leaves
    the compiled programs in the program's cache for the next window."""
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.generation import GenerationEngine

    from benchmarks import harness, traffic_gen

    cfg, mix = ctx.config, ctx.traffic
    ref = ctx.module("reference")
    arrival = mix["arrival"]
    aot_cache.place_compile_cache()

    # ---- set-up ---------------------------------------------------------
    weights = ref.init_weights(cfg, seed)
    dec, gen = ctx.module("model").build(cfg, weights)
    eng = GenerationEngine(dec, gen)
    warm = eng.warmup()
    print(f"# warm-up: {warm['compiled']} executables compiled in "
          f"{warm['compile_seconds']} s; buckets kv {warm['kv_buckets']} "
          f"prompt {warm['prompt_buckets']} join {warm['join_buckets']}; "
          f"at {ctx.setup_seconds():.1f} s", flush=True)
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
    # answers grow on their handles as the engine emits them: what the
    # requests in flight already hold is not the window's work
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
    if not load.closed:
        load.drain(float(arrival["drain_seconds"]))
    trace.stop()
    memory = harness.memory_peak_bytes()
    eng.close()

    # ---- what the window says -------------------------------------------
    if load.closed:
        ended = [f for f in load.done if t0 <= f.t_done < t_end]
        finished = [f for f in ended if f.error is None]
        failed = [f for f in ended if f.error is not None]
        failed += [f for f in load.refused if t0 <= f.t_submit < t_end]
        # every output token the window produced, in answers finished or
        # not, over the whole window. (Counting whole answers finished
        # inside it swings by 4% from run to run on one seed: one answer
        # of 256 tokens on either side of the close is 3% of the count.)
        end_to_end = {"serve_tokens_per_s": emitted / (t_closed - t0)}
        tokens = sum(len(f.handle.out) for f in finished)
        lat = [f.handle.t_first - f.t_submit for f in finished] or [0.0]
        print(f"# window: {emitted} tokens emitted; {len(finished)} "
              f"requests finished with {tokens} tokens "
              f"({tokens / seconds:.1f} a second); ttft from submit p50 "
              f"{1e3 * percentile(lat, 0.5):.1f} ms p95 "
              f"{1e3 * percentile(lat, 0.95):.1f} ms (no metric of this "
              f"cell)", flush=True)
    else:
        mine = [f for f in load.done + load.flying + load.refused
                if f.in_window]
        finished = [f for f in mine if f.t_done is not None
                    and f.error is None]
        failed = [f for f in mine if f.t_done is None
                  or f.error is not None]
        worst = float(arrival["drain_seconds"]) + seconds
        ttft = [f.handle.t_first - f.t_due for f in finished]
        tpot = [(f.t_done - f.handle.t_first) / (len(f.handle.out) - 1)
                for f in finished if len(f.handle.out) > 1]
        ttft += [worst] * len(failed)
        tpot += [worst] * len(failed)
        end_to_end = {
            "serve_ttft_p95_ms": 1e3 * percentile(ttft, 0.95),
            "serve_tpot_p95_ms": 1e3 * percentile(tpot, 0.95)}
        print(f"# window: {len(finished)} finished, {len(failed)} failed; "
              f"ttft p50 {1e3 * percentile(ttft, 0.5):.1f} ms p95 "
              f"{end_to_end['serve_ttft_p95_ms']:.1f} ms, tpot p50 "
              f"{1e3 * percentile(tpot, 0.5):.2f} ms p95 "
              f"{end_to_end['serve_tpot_p95_ms']:.2f} ms, queue at the "
              f"end {stats_after['queued']} (at the start "
              f"{stats_before['queued']})", flush=True)
    end_to_end["setup_s"] = setup_s
    for f in (load.refused + [d for d in load.done if d.error])[:3]:
        print(f"# a request failed: {f.error!r}", flush=True)
    late = [f.t_submit - f.t_due for f in finished + failed]

    def delta(key):
        return stats_after[key] - stats_before[key]

    counters = {
        "compiles": aot_after["misses"] - aot_before["misses"],
        "aot_fallbacks": aot_after["fallbacks"],
        "executables": executables,
        "tokens": delta("tokens_total"), "joined": delta("joined_total"),
        "retired": delta("retired_total"),
        "prefill_seconds": delta("prefill_seconds"),
        "decode_seconds": delta("decode_seconds"),
        "queued_at_start": stats_before["queued"],
        "queued_at_end": stats_after["queued"]}
    records = [{"prompt": len(f.req.prompt), "out": len(f.handle.out),
                "t_due": f.t_due, "t_submit": f.t_submit,
                "t_first": f.handle.t_first, "t_done": f.t_done}
               for f in load.done if f.error is None]
    obs_traced = None
    if traced:
        obs_traced = {"t_start": trace.t_start, "t_stop": traced["t"]}
        for key in ("tokens_total", "joined_total", "prefill_seconds",
                    "decode_seconds"):
            obs_traced[key.replace("_total", "")] = (
                traced["stats"][key] - stats_before[key])
    wrong_length = sum(len(f.handle.out) != f.req.max_new for f in finished)
    sample = _sample(finished, seed, int(ctx.cell_file["checked_requests"]))
    served = [(list(f.req.prompt), list(f.handle.out)) for f in sample]

    # ---- free the engine: the reference comes after ---------------------
    out = {
        "end_to_end": end_to_end,
        "attempted": len(finished) + len(failed), "failed": len(failed),
        "memory": memory, "counters": counters, "requests": records,
        "traced": obs_traced, "late": late, "served": served,
        "wrong_length": wrong_length,
        "weights": weights, "trace_window": trace,
        "window": {"seconds": seconds, "t0": t0, "t_end": t_end},
        "notes": {"warm": {k: warm[k] for k in ("compiled",
                                                "compile_seconds")},
                  "queued_at_start": stats_before["queued"],
                  "queued_at_end": stats_after["queued"],
                  # three counts of the window's tokens: on the handles,
                  # by the engine's counter, in answers that finished
                  "tokens": {"emitted": emitted,
                             "engine_counter": counters["tokens"],
                             "in_finished_answers": sum(
                                 len(f.handle.out) for f in finished)}}}
    del eng, dec, load, finished, failed, sample
    if not keep_programs:
        aot_cache.clear()
    gc.collect()
    return out


def check(ctx, weights, served, control: bool = False) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; with ``control`` also the same
    number for the tokens the reference puts first when it computes in
    the configuration's ``control_dtype``. Every sequence is padded to
    ONE length (the mix's longest prompt and answer), so the reference
    compiles once, and only the rows that predict served tokens leave
    the device."""
    import jax
    import numpy as np

    from benchmarks import correct

    ref = ctx.module("reference")
    fwd = ref.Forward(ctx.config)
    low = (ref.Forward(ctx.config, ref.lower_precision(
        ctx.config["control_dtype"])) if control else None)
    mix = ctx.traffic
    longest_out = int(mix["output_tokens"]["max"])
    pad_to = min(int(mix["prompt_tokens"]["max"]) + longest_out,
                 ctx.config["n_positions"])
    rows_of = jax.jit(lambda logits, start: jax.lax.dynamic_slice_in_dim(
        logits, start, longest_out))
    first_of = jax.jit(lambda logits, start: jax.lax.dynamic_slice_in_dim(
        logits, start, longest_out).argmax(-1))
    worst, worst_control, n_tokens = 0.0, 0.0, 0
    for prompt, out in served:
        seq = (prompt + out[:-1])
        seq = seq + [0] * (pad_to - len(seq))   # causal: changes nothing
        start = len(prompt) - 1                 # this row predicts out[0]
        logits = fwd(weights, seq)
        rows = np.asarray(rows_of(logits, start))
        gaps = correct.logit_gaps(rows, 0, out)
        worst = max(worst, max(gaps))
        n_tokens += len(gaps)
        if low is not None:
            theirs = np.asarray(first_of(low(weights, seq), start))
            worst_control = max(worst_control, max(correct.logit_gaps(
                rows, 0, theirs[:len(out)].tolist())))
    numbers = {"served_logit_gap": worst if served else math.inf}
    out = {"numbers": numbers, "checked_requests": len(served),
           "checked_tokens": n_tokens}
    if control:
        out["control"] = {"served_logit_gap": worst_control}
    return out


def run(ctx) -> dict:
    from benchmarks import correct

    obs = measure(ctx, ctx.args.seed, ctx.args.seconds,
                  bool(ctx.args.trace))
    obs["trace"] = obs.pop("trace_window").reduce()
    t_ref = time.monotonic()
    checked = check(ctx, obs.pop("weights"), obs.pop("served"))
    # no stop token: every finished answer has the length it was asked for
    checked["numbers"]["answers_of_wrong_length"] = float(
        obs["wrong_length"])
    ok, compared = correct.judge(checked["numbers"],
                                 ctx.cell_file["limits"])
    obs["correct"] = (ok and obs["attempted"] > 0
                      and not obs["counters"]["aot_fallbacks"])
    obs["compared"] = compared
    obs["notes"].update(reference_s=time.monotonic() - t_ref,
                        checked_requests=checked["checked_requests"],
                        checked_tokens=checked["checked_tokens"])
    return obs


def calibrate(ctx, args) -> list:
    """Program and control against the reference, seed by seed, in one
    process, each over a short window at the cell's own load
    (``benchmarks/calibrate.py``)."""
    import json

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        obs = measure(ctx, seed, args.seconds, False, keep_programs=True)
        checked = check(ctx, obs.pop("weights"), obs.pop("served"),
                        control=i < args.control_seeds)
        row = {"seed": seed, "program": checked["numbers"],
               "checked_tokens": checked["checked_tokens"],
               "end_to_end": obs["end_to_end"],
               "seconds": time.monotonic() - t0}
        if "control" in checked:
            row["control"] = checked["control"]
        print("calibrate", json.dumps(row), flush=True)
        rows.append(row)
    return rows


def sweep(ctx, args) -> list:
    """The open loop at each of ``args.rates`` requests a second, one
    window each, in one process: the knee is the highest rate at which
    the queue at the end of the window is no deeper than at its start and
    no request is refused (``benchmarks/calibrate.py --rates``)."""
    import json

    rows = []
    for rate in args.rates:
        ctx.traffic["arrival"] = dict(ctx.traffic["arrival"],
                                      process="poisson", rate_per_s=rate)
        # one whole cycle of the mix in the window, as in the cell
        ctx.traffic["cycle"] = max(8, round(rate * args.seconds))
        obs = measure(ctx, args.first_seed, args.seconds, False,
                      keep_programs=True)
        n = obs["counters"]
        row = {"rate_per_s": rate, "end_to_end": obs["end_to_end"],
               "attempted": obs["attempted"], "failed": obs["failed"],
               "queued_at_start": n["queued_at_start"],
               "queued_at_end": n["queued_at_end"],
               "tokens_per_s": n["tokens"] / args.seconds,
               "late_p95_ms": 1e3 * percentile(obs["late"], 0.95)}
        del obs
        gc.collect()
        print("sweep", json.dumps(row), flush=True)
        rows.append(row)
    return rows


def _sample(finished, seed, k):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    import numpy as np

    if not finished:
        return []
    longest = max(finished,
                  key=lambda f: len(f.req.prompt) + len(f.handle.out))
    rest = [f for f in finished if f is not longest]
    rng = np.random.default_rng([int(seed), 3])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[int(i)] for i in picks]
