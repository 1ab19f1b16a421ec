"""The one general traffic generator.

A traffic mix is a data file under ``benchmarks/traffic/``: this module
turns it and ``--seed`` into the inputs of a run. A later PR adds a mix
by adding a file; it needs no code here as long as the mix is made of the
pieces below (length distributions, an arrival process, image batches).

Steadiness from seed to seed: every seed gets the SAME set of sizes and
arrival gaps, in another order. A "cycle" of ``n`` values is the ``n``
mid-quantiles of the distribution, fixed by the file; the seed only
permutes each cycle (and draws the token ids and pixels). So the work in
a window does not depend on the luck of the draw.
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Iterator, List, Tuple

import numpy as np

from benchmarks.harness import merge

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, rehearsal: bool = False) -> dict:
    """The mix ``traffic/<name>.json``; a ``"lengths"`` key names another
    file whose keys it inherits (two mixes that differ in arrival only
    share their lengths)."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if rehearsal:
        mix = merge(mix, mix.pop("rehearsal", {}))
    if "lengths" in mix:
        mix = {**load(mix["lengths"], rehearsal), **mix}
    mix["name"] = name
    return mix


# --------------------------------------------------------------------------
# quantile cycles
# --------------------------------------------------------------------------

def quantile_cycle(spec: dict, n: int) -> List[float]:
    """The ``n`` mid-quantiles ``(i + 0.5) / n`` of a distribution."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = spec["distribution"]
    if kind == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        nd = statistics.NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(u)) for u in us]
    elif kind == "exponential":
        vals = [-spec["mean"] * math.log(1.0 - u) for u in us]
        scale = spec["mean"] * n / sum(vals)   # the cycle's mean is exact
        vals = [v * scale for v in vals]
    elif kind == "constant":
        vals = [float(spec["value"])] * n
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def length_pairs(mix: dict) -> List[Tuple[int, int]]:
    """The fixed cycle of (prompt tokens, output tokens). The pairing of
    the two quantile lists is fixed by the file too (a permutation drawn
    from the constant 0), so that long prompts do not always meet long
    answers."""
    n = int(mix["cycle"])
    prompts = [int(round(v)) for v in quantile_cycle(mix["prompt_tokens"], n)]
    outputs = [int(round(v)) for v in quantile_cycle(mix["output_tokens"], n)]
    pairing = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outputs[int(pairing[i])]) for i in range(n)]


class Request:
    __slots__ = ("index", "prompt", "max_new", "t_due")

    def __init__(self, index, prompt, max_new, t_due):
        self.index = index
        self.prompt = prompt
        self.max_new = max_new
        self.t_due = t_due


def requests(mix: dict, vocab: int, seed: int,
             stream: int = 0) -> Iterator[Request]:
    """An endless stream of requests. ``t_due`` (seconds from the start of
    the stream) is set for an open-loop arrival process from the seed
    alone, never from a completion; in a closed loop it is ``None`` and
    the driver sends the next request of a client when its last one
    completes. ``stream`` picks another order of the same cycles from the
    same seed (the load that runs before the window opens)."""
    rng = np.random.default_rng([int(seed), 1, int(stream)])
    pairs = length_pairs(mix)
    n = len(pairs)
    arrival = mix["arrival"]
    gaps = None
    if arrival["process"] == "poisson":
        gaps = quantile_cycle({"distribution": "exponential",
                               "mean": 1.0 / arrival["rate_per_s"]}, n)
    elif arrival["process"] != "closed_loop":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    index, t = 0, 0.0
    while True:
        order = rng.permutation(n)
        gap_order = rng.permutation(n)
        for j in range(n):
            p_len, o_len = pairs[int(order[j])]
            if gaps is not None:
                t += gaps[int(gap_order[j])]
            prompt = rng.integers(0, vocab, p_len).tolist()
            yield Request(index, prompt, o_len,
                          t if gaps is not None else None)
            index += 1


def image_batches(mix: dict, cfg: dict, chips: int, seed: int):
    """``distinct_batches`` batches of ``(uint8 images, one-hot float32
    labels)`` for ``chips`` chips, on the host. All rows differ."""
    rng = np.random.default_rng([int(seed), 2])
    rows = int(mix["batch_per_chip"]) * chips
    size, ch, classes = cfg["image_size"], cfg["channels"], cfg["num_classes"]
    eye = np.eye(classes, dtype=np.float32)
    return [(rng.integers(0, 256, (rows, size, size, ch), dtype=np.uint8),
             eye[rng.integers(0, classes, rows)])
            for _ in range(int(mix["distinct_batches"]))]
