"""The time to the first token of a LONE request against an engine with one row
decoding, at the backlog cell's configuration (GPT-2-large, K = 4, one KV bucket),
run from the root of a checkout (a parent unpacked under _chip_proof/ too):

    python <repo>/tools/chip/ttft_lone.py [--requests 20] [--seed 1]

One long request keeps a row decoding (so a window is always in flight where the
loop runs ahead); then `--requests` requests of 128 prompt tokens and 8 new ones
are submitted one at a time, each after the last one's end and a pause drawn from
the seed (0 to 60 ms: any phase of a 20 ms window), and `handle.t_first - handle.t0`
is printed for each, with the median and the extremes, and beside it the part of
it that `submit()` took on the caller's thread (its key is a device program) and
the wait from there to the admission (`t_join`). No metric of the benchmark.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=20)
ap.add_argument("--seed", type=int, default=1)
ap.add_argument("--tiny-on-cpu", action="store_true")
args = ap.parse_args()

import numpy as np  # noqa: E402

from benchmarks import harness  # noqa: E402

harness.keep_compile_cache_in_checkout()
import jax  # noqa: E402

from deeplearning4j_tpu.parallel.generation import GenerationEngine  # noqa: E402

cfg = harness.load_json(harness.ROOT, "benchmarks/configs/gpt2-large-serve.json")
if args.tiny_on_cpu:
    cfg = harness.merge(cfg, cfg["rehearsal"])
elif jax.devices()[0].platform != "tpu":
    sys.exit("ttft_lone.py measures on the TPU only")
from benchmarks.models import gpt2 as model  # noqa: E402
from benchmarks.reference import gpt2 as ref  # noqa: E402

dec, gen = model.build(cfg, ref.init_weights(cfg, args.seed))
rng = np.random.default_rng(args.seed)
n_prompt = min(128, cfg["n_positions"] // 4)
n_long = cfg["n_positions"] - n_prompt - 1


def prompt():
    return rng.integers(0, cfg["vocab_size"], n_prompt).tolist()


with GenerationEngine(dec, gen) as eng:
    eng.warmup()
    eng.generate(prompt(), max_new_tokens=8)        # every program has run once
    ttft, in_submit, to_join = [], [], []
    long_req = None
    for _ in range(args.requests):
        if long_req is None or long_req.event.is_set() or (
                len(long_req.out) > n_long - 64):
            if long_req is not None:
                eng.result(long_req)
            long_req = eng.submit(prompt(), max_new_tokens=n_long)
            while len(long_req.out) < 9:
                time.sleep(0.001)
        time.sleep(float(rng.uniform(0.0, 0.06)))
        h = eng.submit(prompt(), max_new_tokens=8)
        t_submitted = time.monotonic()
        eng.result(h)
        ttft.append(1e3 * (h.t_first - h.t0))
        in_submit.append(1e3 * (t_submitted - h.t0))
        to_join.append(1e3 * (h.t_join - t_submitted))
    stats = eng.stats()
print(json.dumps({
    "device": jax.devices()[0].device_kind, "requests": len(ttft),
    "ttft_ms": [round(t, 2) for t in ttft],
    "median_ms": statistics.median(ttft), "min_ms": min(ttft),
    "max_ms": max(ttft),
    "in_submit_median_ms": statistics.median(in_submit),
    "submit_to_join_median_ms": statistics.median(to_join),
    "join_to_first_median_ms": statistics.median(
        t - a - b for t, a, b in zip(ttft, in_submit, to_join)),
    "ahead": {k: v for k, v in stats.items() if k.startswith(("windows_",
                                                               "joins_"))}}))
