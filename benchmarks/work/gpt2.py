"""Operations and bytes the ``gpt2-large-serve`` steps need, from shapes.

Counted for the algorithm, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token processed (prompt or output) passes the 12 n_embd^2
  parameters of each block's four attention projections and two
  feed-forward matrices once;
- the output head (n_embd x vocab) is needed once per token EMITTED (the
  last position of a prefill, every decode step), not once per prompt
  position, whatever the program computes;
- attention for a token at position p reads p + 1 keys and values:
  4 n_embd (p + 1) FLOPs a layer.

The embedding tables are gathers, not matrix products, and are not
counted. A decode step has to read every matrix once and the keys and
values of the positions the rows in use hold, not the padded bucket.
"""

from __future__ import annotations

from benchmarks.reference.gpt2 import dims, parameter_count  # noqa: F401

WIDTH = {"float32": 4, "bfloat16": 2}


def block_matmul_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["layers"] * (4 * d["e"] * d["e"] + 2 * d["e"] * d["ffn"])


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["vocab"]


def attention_flops(cfg: dict, positions_sum: float) -> float:
    """``positions_sum`` is the sum over the tokens of (p + 1)."""
    d = dims(cfg)
    return 4.0 * d["e"] * d["layers"] * positions_sum


def flops(cfg: dict, tokens_processed: float, tokens_emitted: float,
          positions_sum: float) -> float:
    return (2.0 * block_matmul_params(cfg) * tokens_processed
            + 2.0 * head_params(cfg) * tokens_emitted
            + attention_flops(cfg, positions_sum))


def decode_step_bytes(cfg: dict, live_positions: float) -> float:
    """Bytes one decode step has to read: every matrix once, and the keys
    and values of ``live_positions`` cached positions (summed over the
    rows in use)."""
    d = dims(cfg)
    weights = (block_matmul_params(cfg) + head_params(cfg)) * WIDTH[
        cfg["weight_dtype"]]
    kv = live_positions * 2 * d["layers"] * d["e"] * WIDTH[cfg["cache_dtype"]]
    return weights + kv


# --- what the share readers ask (readers/work_share.py) --------------------

def _traced_requests(obs):
    """For the requests alive in the traced part of the window: the
    prompt tokens prefilled in it, the sum of (p + 1) over the tokens
    processed in it, and the time average of the cached positions."""
    t = obs["traced"]
    a, b = t["t_start"], t["t_stop"]
    prompt_tokens = positions_sum = live_integral = 0.0
    for r in obs["requests"]:
        p, n = r["prompt"], r["out"]
        if a <= r["t_first"] < b:
            prompt_tokens += p
            positions_sum += p * (p + 1) / 2.0
        lo, hi = max(a, r["t_first"]), min(b, r["t_done"])
        if hi <= lo or n < 2:
            continue
        span = r["t_done"] - r["t_first"]
        f0, f1 = (lo - r["t_first"]) / span, (hi - r["t_first"]) / span
        steps = (n - 1) * (f1 - f0)
        mean_pos = p + (n - 1) * (f0 + f1) / 2.0
        positions_sum += steps * (mean_pos + 1)
        live_integral += (hi - lo) * mean_pos
    return prompt_tokens, positions_sum, live_integral / (b - a)


def step_mfu(ctx, obs, params):
    """The whole model step's share of the bf16 peak over the traced
    part of the window: the matrix unit is fed one bf16 pass at default
    precision also for float32 operands, so no other peak applies."""
    if not obs.get("traced"):
        return None
    prompt_tokens, positions_sum, _ = _traced_requests(obs)
    emitted = obs["traced"]["tokens"]
    decoded = emitted - obs["traced"]["joined"]
    need = flops(ctx.config, prompt_tokens + decoded, emitted, positions_sum)
    return need / ctx.peak["bf16_flops_per_s"], obs["trace"]["window_s"]


def decode_step_roofline(ctx, obs, params):
    """A decode step's least time (its bytes over the HBM peak; it is
    bandwidth-bound by two orders of magnitude) over its device time."""
    from benchmarks.readers import program_time

    if not obs.get("traced"):
        return None
    step_ms = program_time.read(ctx, obs, params)
    if not step_ms:
        return None
    _, _, live = _traced_requests(obs)
    least = decode_step_bytes(ctx.config, live) / ctx.peak["hbm_bytes_per_s"]
    return least, step_ms * 1e-3
