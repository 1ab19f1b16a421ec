"""Closed-loop token-throughput benchmark for the generation engine
(ISSUE 11 continuous batching, ISSUE 16 prefix caching + speculative
decoding): a SHARED-PREFIX workload (every prompt opens with the same
system-prompt-style token block) runs through the same compiled KV-cache
executables in up to five legs,

  sequential  — one request at a time to completion
                (``TransformerDecoder.generate``),
  continuous  — the iteration-level scheduler
                (``parallel.generation.GenerationEngine``),
  prefix      — continuous + the radix-tree prefix cache
                (``--prefix-cache``): hits attach cached KV pages and
                prefill only the suffix,
  speculative — continuous + draft-model speculation
                (``--speculative``): a distilled 1-layer draft proposes
                ``--spec-tokens`` tokens per iteration, the target
                scores all K+1 positions in one ``spec_verify`` launch,
  combined    — prefix cache + speculation together (both flags),
  paged       — continuous over a ``use_kernels=True`` model
                (``--paged``): flash prefill + paged decode attention
                through the Pallas kernel registry, tuned before
                warmup; on the CPU proxy the kernel bodies run the
                Pallas interpreter, so this leg pins token identity +
                zero recompiles + the tuned winner set, not speed.

Every engine leg runs the workload twice: an UNTIMED settle pass that
pays each executable's one-time first-dispatch cost (and, in prefix
legs, seeds the trie — the timed pass then measures steady-state hits),
then the timed pass. The sequential baseline gets the same two-pass
treatment. Per leg the report carries tokens/s, wall seconds, the
prefill/decode split, TTFT quantiles (first-wave TTFT isolates prefill
latency from queue wait), greedy token-identity against the sequential
reference, recompiles after warmup (must be 0 across BOTH passes —
mixed hit/miss and accept/reject traffic included), and acceptance rate
for speculative legs. Writes ``bench_decode.json``;
``BENCH_decode_r02.json`` is the committed round-2 snapshot and
``BENCH_decode_r01.json`` the round-1 continuous-batching baseline the
speculative leg is judged against.

Methodology + honest caveats (docs/serving.md has the full discussion):
- CPU proxy by default — absolute tokens/s is meaningless off-chip; the
  CONTRAST is the result. All legs share every executable, so the
  deltas isolate scheduling, cache reuse, and launch economics, not
  kernels.
- The draft model is DISTILLED on the sequential leg's own outputs
  (next-token cross-entropy on the exact target streams, full-length
  position-aligned windows). The benchmark workload is deliberately
  low-entropy — greedy decode settles into attractor cycles a 1-layer
  draft can learn — so acceptance is high. Real-text acceptance depends
  entirely on the draft/target fit; the number reported here
  characterizes the ENGINE, not language-model speculation at large.
  ``--smoke`` swaps the distilled draft for an oracle draft (same
  config + seed as the target) so the machinery asserts don't depend
  on a training run.
- On the dispatch-bound CPU proxy a speculative window costs two
  launches (fused draft window + wide verify) against one plain fused
  window, so speculation only wins with draft K well past
  ``fused_steps`` and high acceptance — which is exactly the regime a
  real serving draft targets. TTFT wins for the prefix leg are
  suffix-only prefill vs full prefill.
"""

import argparse
import json
import os
import random
import time


def _pin_cpu():
    """CPU, set before ``import jax``."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def _mode(args):
    """The record's ``mode`` label, from the device jax reports and not
    from the flag: ``--tpu`` refuses to run on anything but a TPU."""
    if not args.tpu:
        return "cpu-proxy"
    from deeplearning4j_tpu.util.device import banner, require_tpu

    dev = require_tpu("bench_decode.py --tpu")
    print("# " + banner(dev), flush=True)
    return f"{dev['platform']}:{dev['kind']}"


def _workload(n, vocab, max_len, seed):
    """Shared-prefix closed-loop workload: every prompt opens with the
    same ``max_len // 4``-token block (the system-prompt / few-shot
    template pattern the prefix cache exists for — long enough that a
    cold prefill pays a prompt launch two buckets wider than the
    suffix-only hit path), followed by a per-request suffix of
    2..max_len//16 tokens; outputs fill most of the remaining context
    so decode dominates and speculative windows keep runway short of
    the context limit. Lengths come from a seeded stream so every leg
    sees identical traffic."""
    rng = random.Random(seed)
    shared = [rng.randrange(vocab) for _ in range(max(4, max_len // 4))]
    reqs = []
    for _ in range(n):
        plen = rng.randint(2, max(2, max_len // 16))
        prompt = shared + [rng.randrange(vocab) for _ in range(plen)]
        lo = max(3, max_len * 3 // 8)
        hi = max(lo, max_len * 5 // 8)
        mnew = max(3, min(rng.randint(lo, hi), max_len - len(prompt) - 1))
        reqs.append((prompt, mnew))
    return reqs


def _quantiles(vals):
    if not vals:
        return None
    s = sorted(vals)
    pick = lambda q: s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]  # noqa: E731
    return {"p50": round(pick(0.50), 4), "p95": round(pick(0.95), 4),
            "count": len(s)}


def _distill_draft(model_args, seqs, epochs):
    """Distill the draft on the target's own greedy streams: a 1-layer
    transformer half the target's width, trained with next-token
    cross-entropy on full-length POSITION-ALIGNED windows (training on
    shifted sub-windows leaves the later position embeddings untrained
    and collapses acceptance). Zero label rows past each sequence's end
    contribute zero loss — a free padding mask under MCXENT."""
    import numpy as np
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    a = model_args
    conf = TransformerEncoder(
        vocab_size=a.vocab, embed_dim=max(8, a.embed // 2),
        n_heads=max(1, a.heads // 2), n_layers=1, max_len=a.max_len,
        causal=True, lm_head=True, seed=7)
    net = conf.init()
    t = max(len(s) for s in seqs) - 1
    feats, labs = [], []
    for s in seqs:
        w = s + [0] * (t + 1 - len(s))
        feats.append(w[:t])
        oh = np.zeros((t, a.vocab), np.float32)
        n = len(s) - 1
        oh[np.arange(n), w[1:n + 1]] = 1.0
        labs.append(oh)
    feats = np.asarray(feats, np.int32)
    labs = np.asarray(labs, np.float32)
    t0 = time.monotonic()
    net.fit(feats, labs, epochs=epochs)
    fit_s = time.monotonic() - t0
    pred = np.asarray(net.output(feats)).argmax(-1)
    mask = labs.sum(-1) > 0
    agreement = float((pred == labs.argmax(-1))[mask].mean())
    dd = conf.decoder(net, max_batch=a.max_batch,
                      kv_bucket_min=a.max_len // 4, prompt_bucket_min=8)
    return dd, {"layers": 1, "embed": max(8, a.embed // 2),
                "epochs": epochs, "fit_seconds": round(fit_s, 1),
                "teacher_forced_agreement": round(agreement, 4),
                "kind": "distilled"}


def _oracle_draft(model_args):
    """Smoke-mode draft: the target's own config and seed — agreement is
    1.0 by construction, so the machinery asserts (acceptance recorded,
    identity, zero recompiles) don't hinge on a training run."""
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    a = model_args
    conf = TransformerEncoder(
        vocab_size=a.vocab, embed_dim=a.embed, n_heads=a.heads,
        n_layers=a.layers, max_len=a.max_len, causal=True,
        lm_head=True, seed=123)
    dd = conf.decoder(max_batch=a.max_batch, kv_bucket_min=a.max_len // 4,
                      prompt_bucket_min=8)
    return dd, {"kind": "oracle (same config+seed as target)"}


def _run_engine_leg(name, model, args, reqs, seq_out, draft=None,
                    prefix=False, tune_kernels=False):
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.generation import (
        GenerationConfig,
        GenerationEngine,
    )

    cfg = GenerationConfig(
        max_batch=args.max_batch, fused_steps=args.fused_steps,
        kv_bucket_min=args.max_len // 4, prompt_bucket_min=8,
        draft_conf=draft, spec_tokens=args.spec_tokens if draft else None,
        prefix_cache=prefix, prefix_page=args.prefix_page)
    dec = model.decoder(max_batch=args.max_batch,
                        kv_bucket_min=args.max_len // 4,
                        prompt_bucket_min=8)
    tune_info = None
    if tune_kernels:
        # tune BEFORE warmup: a later tune would bump the digest and
        # re-mint every kern:-keyed executable the warmup just built
        from deeplearning4j_tpu import kernels

        t0 = time.monotonic()
        tuned = kernels.autotune_decoder(dec, max_candidates=2, trials=1)
        tune_info = {"tuned_envelopes": len(tuned),
                     "autotune_seconds": round(time.monotonic() - t0, 2)}
    eng = GenerationEngine(dec, cfg)
    warm = eng.warmup()
    miss0 = aot_cache.stats()["misses"]

    # settle pass: identical traffic, untimed — one-time first-dispatch
    # costs land here, and prefix legs seed the trie so the timed
    # passes measure steady-state hits
    for h in [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]:
        eng.result(h)

    # best of N timed passes: CPU-proxy wall clock is noisy (shared
    # host, XLA thread-pool contention), so each leg re-runs the same
    # traffic and reports its best pass with every pass recorded
    passes = []
    identical = True
    best = None
    for _ in range(max(1, args.passes)):
        st0 = eng.stats()
        t0 = time.monotonic()
        handles = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
        out = [eng.result(h) for h in handles]
        wall = time.monotonic() - t0
        tokens = sum(len(o) for o in out)
        st1 = eng.stats()
        identical = identical and out == seq_out
        passes.append({"wall": wall, "tokens": tokens, "st0": st0,
                       "st1": st1, "handles": handles})
        if best is None or tokens / wall > best["tokens"] / best["wall"]:
            best = passes[-1]

    wall, tokens = best["wall"], best["tokens"]
    st0, st1, handles = best["st0"], best["st1"], best["handles"]
    recompiles = aot_cache.stats()["misses"] - miss0
    ttft_all = [h.t_first - h.t0 for h in handles if h.t_first is not None]
    first_wave = handles[:args.max_batch]
    ttft_wave = [h.t_first - h.t0 for h in first_wave
                 if h.t_first is not None]
    leg = {
        "tokens_per_sec": round(tokens / wall, 1),
        "wall_seconds": round(wall, 3),
        "tokens": tokens,
        "pass_tokens_per_sec": [round(p["tokens"] / p["wall"], 1)
                                for p in passes],
        "prefill_seconds": round(
            st1["prefill_seconds"] - st0["prefill_seconds"], 3),
        "decode_seconds": round(
            st1["decode_seconds"] - st0["decode_seconds"], 3),
        "ttft_s": _quantiles(ttft_all),
        "ttft_first_wave_s": _quantiles(ttft_wave),
        "greedy_identical_to_sequential": identical,
        "recompiles_after_warmup": recompiles,
        "warmup_executables": warm["compiled"],
        "warmup_compile_seconds": warm["compile_seconds"],
    }
    if draft is not None:
        leg["speculative"] = st1["speculative"]
        leg["spec_tokens"] = args.spec_tokens
    if prefix:
        pc = dict(st1["prefix_cache"])
        leg["prefix_cache"] = pc
    if tune_kernels:
        leg["kernels"] = dict(st1["kernels"])
        leg["kernels"].update(tune_info)
    eng.close()
    print(f"{name}: {leg['tokens_per_sec']} tok/s, identical={identical}, "
          f"recompiles={recompiles}"
          + (f", acceptance="
             f"{leg['speculative']['acceptance']:.3f}" if draft else "")
          + (f", hits={leg['prefix_cache']['hits']}" if prefix else ""))
    return leg


def bench_traces(args):
    """``--traces``: request-tracing overhead A/B on the continuous leg.
    The identical shared-prefix workload runs through two engines —
    tracing OFF (one boolean check per ``start_trace``) then ON with
    ``sample_every=1`` (every request carries its span through queued →
    join → prefill/prefix_attach → first_token → decode_window* →
    done) — and the JSON carries both tokens/s, the overhead fraction
    against ``--trace-overhead-budget``, the trace-derived queue-wait /
    decode-window breakdown, and the zero-recompile check for BOTH
    modes: tracing is host-side monotonic_ns + list appends and must
    never mint an AOT key. Token identity vs the sequential reference
    is asserted in both modes too — tracing must not perturb
    scheduling-order-sensitive outputs."""
    if not args.tpu:
        _pin_cpu()
    from deeplearning4j_tpu.telemetry import tracing
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    model = TransformerEncoder(
        vocab_size=args.vocab, embed_dim=args.embed, n_heads=args.heads,
        n_layers=args.layers, max_len=args.max_len, causal=True,
        lm_head=True, seed=123)
    dec = model.decoder(max_batch=args.max_batch,
                        kv_bucket_min=args.max_len // 4,
                        prompt_bucket_min=8)
    reqs = _workload(args.requests, args.vocab, args.max_len, args.seed)
    seq_out = [dec.generate(p, mn, fused_steps=args.fused_steps)
               for p, mn in reqs]

    tracing.disable()
    off = _run_engine_leg("traces-off", model, args, reqs, seq_out)
    tracing.enable(seed=7, sample_every=1)
    on = _run_engine_leg("traces-on", model, args, reqs, seq_out)
    on["sampler"] = tracing.stats()
    on["stage_breakdown"] = {
        k: v for k, v in tracing.stage_breakdown().items() if v is not None}
    tracing.disable()

    overhead = round(
        1.0 - on["tokens_per_sec"] / max(off["tokens_per_sec"], 1e-9), 4)
    results = {
        "bench": "decode_tracing_overhead",
        "mode": _mode(args),
        "workload": {"requests": args.requests, "seed": args.seed},
        "tracing_off": off,
        "tracing_on": on,
        "overhead_fraction": overhead,
        "overhead_budget": args.trace_overhead_budget,
    }
    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    print(f"tracing off: {off['tokens_per_sec']} tok/s   "
          f"on: {on['tokens_per_sec']} tok/s   overhead {overhead:+.1%} "
          f"(budget {args.trace_overhead_budget:.0%})")
    ok = (overhead <= args.trace_overhead_budget
          and off["recompiles_after_warmup"] == 0
          and on["recompiles_after_warmup"] == 0
          and off["greedy_identical_to_sequential"]
          and on["greedy_identical_to_sequential"])
    print("OK" if ok else "FAIL: tracing overhead/recompile/identity broken")
    return 0 if ok else 1


def bench(args):
    if not args.tpu:
        _pin_cpu()
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    model = TransformerEncoder(
        vocab_size=args.vocab, embed_dim=args.embed, n_heads=args.heads,
        n_layers=args.layers, max_len=args.max_len, causal=True,
        lm_head=True, seed=123)
    dec = model.decoder(max_batch=args.max_batch,
                        kv_bucket_min=args.max_len // 4,
                        prompt_bucket_min=8)
    reqs = _workload(args.requests, args.vocab, args.max_len, args.seed)

    # sequential per-request generation: the baseline being replaced,
    # and the distillation corpus for the speculative legs (settle pass
    # + best-of-N, same discipline as the engine legs)
    seq_out = [dec.generate(p, mn, fused_steps=args.fused_steps)
               for p, mn in reqs]
    seq_s = None
    for _ in range(max(1, args.passes)):
        t0 = time.monotonic()
        seq_out = [dec.generate(p, mn, fused_steps=args.fused_steps)
                   for p, mn in reqs]
        dt = time.monotonic() - t0
        seq_s = dt if seq_s is None else min(seq_s, dt)
    seq_tokens = sum(len(o) for o in seq_out)
    print(f"sequential: {round(seq_tokens / seq_s, 1)} tok/s")

    legs = {}
    legs["continuous"] = _run_engine_leg(
        "continuous", model, args, reqs, seq_out)
    if args.paged:
        # same weights (same seed) with use_kernels=True: flash prefill
        # + paged decode attention through the kernel registry, tuned
        # before warmup so the timed passes run the kern:-keyed
        # executables; token identity vs the STOCK sequential reference
        # is part of the leg
        model_k = TransformerEncoder(
            vocab_size=args.vocab, embed_dim=args.embed,
            n_heads=args.heads, n_layers=args.layers,
            max_len=args.max_len, causal=True, lm_head=True, seed=123,
            use_kernels=True)
        legs["paged"] = _run_engine_leg(
            "paged", model_k, args, reqs, seq_out, tune_kernels=True)
    draft = info = None
    if args.speculative:
        if args.smoke:
            draft, info = _oracle_draft(args)
        else:
            seqs = [p + o for (p, _), o in zip(reqs, seq_out)]
            draft, info = _distill_draft(args, seqs, args.distill_epochs)
            print(f"draft distilled: agreement "
                  f"{info['teacher_forced_agreement']} "
                  f"in {info['fit_seconds']}s")
    if args.prefix_cache:
        legs["prefix"] = _run_engine_leg(
            "prefix", model, args, reqs, seq_out, prefix=True)
    if draft is not None:
        legs["speculative"] = _run_engine_leg(
            "speculative", model, args, reqs, seq_out, draft=draft)
    if draft is not None and args.prefix_cache:
        legs["combined"] = _run_engine_leg(
            "combined", model, args, reqs, seq_out, draft=draft,
            prefix=True)

    cont = legs["continuous"]
    results = {
        "bench": "decode_continuous_batching_r02",
        "mode": _mode(args),
        "model": {"vocab": args.vocab, "embed": args.embed,
                  "heads": args.heads, "layers": args.layers,
                  "max_len": args.max_len},
        "engine": {"max_batch": args.max_batch,
                   "fused_steps": args.fused_steps,
                   "spec_tokens": args.spec_tokens,
                   "prefix_page": args.prefix_page},
        "workload": {"requests": args.requests, "seed": args.seed,
                     "shared_prefix_tokens": max(4, args.max_len // 4),
                     "total_tokens": cont["tokens"],
                     "two_pass": "settle pass untimed, second pass timed"},
        "sequential": {"tokens_per_sec": round(seq_tokens / seq_s, 1),
                       "wall_seconds": round(seq_s, 3),
                       "tokens": seq_tokens},
        "legs": legs,
        "speedup": round(cont["tokens_per_sec"]
                         / (seq_tokens / seq_s), 2),
        "greedy_identical_to_sequential": all(
            leg["greedy_identical_to_sequential"] for leg in legs.values()),
        "recompiles_after_warmup": sum(
            leg["recompiles_after_warmup"] for leg in legs.values()),
    }
    if info is not None:
        results["draft"] = info
    if os.path.exists("BENCH_decode_r01.json"):
        with open("BENCH_decode_r01.json") as f:
            r01 = json.load(f)
        base = r01["continuous"]["tokens_per_sec"]
        results["r01_continuous_baseline_tokens_per_sec"] = base
        if "speculative" in legs:
            results["speculative_vs_r01_baseline"] = round(
                legs["speculative"]["tokens_per_sec"] / base, 2)
            # same-run contrast, stated plainly: at toy scale the draft
            # is only ~2x cheaper per step than the 2-layer target, so
            # speculation's two-launch window need not beat the plain
            # fused window on the CPU proxy (see docs/serving.md)
            results["speculative_vs_continuous_same_run"] = round(
                legs["speculative"]["tokens_per_sec"]
                / cont["tokens_per_sec"], 2)
        if "prefix" in legs and cont["ttft_first_wave_s"] \
                and legs["prefix"]["ttft_first_wave_s"]:
            results["prefix_ttft_cut_vs_cold"] = round(
                1 - legs["prefix"]["ttft_first_wave_s"]["p50"]
                / max(cont["ttft_first_wave_s"]["p50"], 1e-9), 3)
    print(json.dumps(results, indent=2))
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    if args.smoke:
        assert results["greedy_identical_to_sequential"], \
            "a leg's greedy output != sequential reference"
        assert results["recompiles_after_warmup"] == 0, \
            f"{results['recompiles_after_warmup']} recompiles after warmup"
        assert results["speedup"] > 1.0, \
            f"continuous batching slower than sequential " \
            f"(speedup {results['speedup']})"
        if "prefix" in legs:
            assert legs["prefix"]["prefix_cache"]["hits"] > 0, \
                "prefix leg recorded no cache hits"
        if "speculative" in legs:
            acc = legs["speculative"]["speculative"]["acceptance"]
            assert 0.0 < acc <= 1.0, \
                f"speculative leg acceptance not recorded ({acc})"
        if "paged" in legs:
            kinfo = legs["paged"]["kernels"]
            assert kinfo["enabled"] and kinfo["tuned_envelopes"] > 0
            assert "kern:flash_attention:" in kinfo["tag"]
            assert "kern:paged_decode_attention:" in kinfo["tag"]
        print(f"decode-smoke OK: speedup {results['speedup']}x, "
              f"0 recompiles, token-identical"
              + (", prefix hits "
                 f"{legs['prefix']['prefix_cache']['hits']}"
                 if "prefix" in legs else "")
              + (", acceptance "
                 f"{legs['speculative']['speculative']['acceptance']:.2f}"
                 if "speculative" in legs else ""))
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--fused-steps", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--embed", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default="bench_decode.json")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="add the radix prefix-cache leg (+ combined leg "
                         "when --speculative is also set)")
    ap.add_argument("--paged", action="store_true",
                    help="add the use_kernels leg: flash prefill + paged "
                         "decode attention through the kernel registry "
                         "(CPU proxy runs the Pallas interpreter — the "
                         "leg pins identity + zero recompiles, not speed)")
    ap.add_argument("--speculative", action="store_true",
                    help="add the draft-model speculative leg; the draft "
                         "is distilled on the sequential leg's outputs")
    ap.add_argument("--spec-tokens", type=int, default=20,
                    help="draft tokens per speculative window (past "
                         "fused_steps: a window costs ~2 launches "
                         "regardless of K, so deeper drafts amortize)")
    ap.add_argument("--prefix-page", type=int, default=8,
                    help="prefix-cache page size in tokens")
    ap.add_argument("--distill-epochs", type=int, default=1200)
    ap.add_argument("--passes", type=int, default=3,
                    help="timed passes per leg; best is reported and "
                         "every pass recorded")
    ap.add_argument("--traces", action="store_true",
                    help="request-tracing overhead A/B: the continuous "
                         "leg with tracing off then on (sample_every=1), "
                         "plus the trace-derived stage breakdown")
    ap.add_argument("--trace-overhead-budget", type=float, default=0.25,
                    help="with --traces: exit 1 if tracing-on loses more "
                         "than this fraction of tracing-off tokens/s")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the real chip instead of the CPU proxy")
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + assertions (make decode-smoke); "
                         "uses an oracle draft instead of distilling")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 12)
        args.vocab, args.embed, args.max_len = 32, 16, 48
        args.max_batch = min(args.max_batch, 4)
        args.spec_tokens = min(args.spec_tokens, 6)
        args.prefix_page = 4
        args.passes = 1
    if not args.tpu:
        _pin_cpu()
    if args.traces:
        if args.out == "bench_decode.json":
            args.out = "bench_decode_traces.json"
        return bench_traces(args)
    return bench(args)


if __name__ == "__main__":
    raise SystemExit(main())
