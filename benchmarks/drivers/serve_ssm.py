"""Driver of the serving cells of a model with state-space layers:
``serve_routed``'s closed loop and window, unchanged (``measure``: the
in-graph counters read when the window opens, when the trace stops and
when it closes). What differs:

- the check reads a SECOND number beside ``served_logit_gap_mean``:
  ``join_logit_gap_mean``, the mean gap of each checked answer's tokens 2
  to 4 alone, the tokens whose convolution still reaches into the prompt
  and whose scan starts from the state the join handed over. A state
  lost at the join moves those few tokens and is then forgotten: in the
  mean over a thousand-token answer it is one part in some hundreds;
- ``calibrate``: this model routes nothing, so there is no reading of
  the configuration's own precision with a router kept apart, and the
  planted fault that reads lowest is another.
"""

from __future__ import annotations

import time

from benchmarks.drivers.serve_routed import (ROWS_BLOCK, TOKEN_BLOCK, _pad_to,
                                             measure)
from benchmarks.drivers.serve_sessions import gap_numbers

NEAREST_FAULT = "no_conv_state_at_join"   # the planted fault that reads lowest
JOIN_TOKENS = slice(1, 4)       # an answer's tokens the join's states decide


def _numbers(per_request) -> dict:
    """``per_request``: each checked answer's gaps, token by token."""
    out = gap_numbers([g for gaps in per_request for g in gaps])
    after_join = [g for gaps in per_request for g in gaps[JOIN_TOKENS]]
    out["join_logit_gap_mean"] = (sum(after_join) / len(after_join)
                                  if after_join else float("inf"))
    return out


def check(ctx, weights, served, control: bool = False, faults=()) -> dict:
    """As ``serve_routed.check`` (for each served token the gap by which
    its logit lies below the reference's best at its position, in standard
    deviations of the reference's logits there, reduced on the device;
    with ``control`` the same for the tokens the reference puts first
    when it computes in the configuration's ``control_dtype``, and for
    each of ``faults`` with that mechanism broken in the reference), with
    :func:`_numbers` in place of the mean alone."""
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
    fwd = ref.Forward(cfg)
    pad_to = _pad_to(max((len(p) + len(o) for p, o in served), default=1),
                     TOKEN_BLOCK)
    n_rows = _pad_to(max((len(o) for _, o in served), default=1), ROWS_BLOCK)

    @jax.jit
    def gaps_of(logits, tokens):
        got = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
        return (logits.max(axis=-1) - got) / logits.std(axis=-1)

    first_of = jax.jit(lambda logits: logits.argmax(axis=-1))
    mine = []
    theirs = {name: [] for name in others}
    for prompt, out in served:
        seq = prompt + out[:-1]
        seq = seq + [0] * (pad_to - len(seq))       # causal: changes nothing
        rows = np.minimum(len(prompt) - 1 + np.arange(n_rows),
                          len(prompt) + len(out) - 2)
        tokens = np.zeros((n_rows,), np.int32)
        tokens[:len(out)] = out
        logits = fwd(weights, seq, rows)
        mine.append(np.asarray(gaps_of(logits, tokens),
                               np.float64)[:len(out)].tolist())
        for name, other in others.items():
            first = first_of(other(weights, seq, rows))
            theirs[name].append(np.asarray(
                gaps_of(logits, first), np.float64)[:len(out)].tolist())
        del logits
    out = {"numbers": _numbers(mine), "checked_requests": len(served),
           "checked_tokens": sum(len(g) for g in mine)}
    out.update({name: _numbers(g) for name, g in theirs.items()})
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
    program and the fault that reads nearest the program
    (:data:`NEAREST_FAULT`); on the first ``--control-seeds`` also the
    control and the other faults."""
    import json

    faults = ctx.module("reference").FAULTS
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        obs = measure(ctx, seed, args.seconds, False, keep_programs=True)
        first = i < args.control_seeds
        checked = check(ctx, obs.pop("weights"), obs.pop("served"),
                        control=first,
                        faults=faults if first else (NEAREST_FAULT,))
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
