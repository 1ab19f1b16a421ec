"""Operations and bytes the ``trinity-mini-serve`` configuration needs, from
shapes. Counted for the mathematics, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token passes each layer's attention matrices once (q, k, v, the
  output gate, o) and the head once (a prompt: its last position only);
  a dense layer's feed-forward; an expert layer's router, its shared
  expert and ``num_experts_per_tok`` routed experts. The embedding is a
  gather and is not counted;
- attention: 4 d a (query head, position attended); a window layer's
  query attends at most ``sliding_window`` positions.

Bytes a decode step has to move: every matrix OUTSIDE the routed experts
once, the matrices of the experts TOUCHED (at least one live token chose
them: the program counts them), the keys and values of the live
contexts, a window layer's at most ``sliding_window`` a row.
"""

from __future__ import annotations

from benchmarks.reference.afmoe import dims

WIDTH = {"float32": 4, "bfloat16": 2}


def attention_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["head"] * (3 * d["heads"] + 2 * d["kv_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert."""
    d = dims(cfg)
    return 3 * d["e"] * d["expert_ffn"]


def shared_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["e"] * d["shared_ffn"]


def router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["experts"]


def dense_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["e"] * d["dense_ffn"]


def layer_params(cfg: dict, moe: bool) -> int:
    """The matrices of one layer as this holder has it (gains and the
    expert bias are not counted)."""
    if not moe:
        return attention_params(cfg) + dense_ffn_params(cfg)
    return (attention_params(cfg) + dims(cfg)["held"][1] * expert_params(cfg)
            + shared_params(cfg) + router_params(cfg))


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["vocab"]


def vocabulary_params(cfg: dict) -> int:
    """Embedding and untied head."""
    return 2 * head_params(cfg)


def parameter_count(cfg: dict) -> int:
    return vocabulary_params(cfg) + sum(
        layer_params(cfg, moe) for moe in dims(cfg)["moe"])


def weight_bytes(cfg: dict) -> float:
    """Everything in ``weight_dtype`` but the router (float32)."""
    w = WIDTH[cfg["weight_dtype"]]
    routers = sum(dims(cfg)["moe"]) * router_params(cfg)
    return (parameter_count(cfg) - routers) * w + routers * WIDTH["float32"]


def fixed_step_bytes(cfg: dict) -> float:
    """What every decode step reads whatever was routed: the head, each
    layer's attention, the dense feed-forward, the shared experts, the
    routers."""
    d = dims(cfg)
    w = WIDTH[cfg["weight_dtype"]]
    n_moe = sum(d["moe"])
    return (w * (head_params(cfg) + len(d["moe"]) * attention_params(cfg)
                 + (len(d["moe"]) - n_moe) * dense_ffn_params(cfg)
                 + n_moe * shared_params(cfg))
            + WIDTH["float32"] * n_moe * router_params(cfg))


def expert_bytes(cfg: dict) -> float:
    return expert_params(cfg) * WIDTH[cfg["weight_dtype"]]


def kv_position_bytes(cfg: dict) -> float:
    """One position's key and value in one layer."""
    d = dims(cfg)
    return 2 * d["kv_heads"] * d["head"] * WIDTH[cfg["cache_dtype"]]


def positions_read(cfg: dict, context: float) -> float:
    """Cached positions one token at ``context`` positions of context
    attends, summed over the layers."""
    d = dims(cfg)
    return sum(min(context, d["window"]) if sliding else context
               for sliding in d["sliding"])


def decode_step_bytes(cfg: dict, contexts, experts_touched: float) -> float:
    """One decode step over rows at ``contexts``; ``experts_touched``
    summed over the step's expert layers."""
    return (fixed_step_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + kv_position_bytes(cfg) * sum(positions_read(cfg, c)
                                           for c in contexts))


def token_matmul_flops(cfg: dict, with_head: bool = True) -> float:
    """The matrices one token passes."""
    d = dims(cfg)
    n_moe = sum(d["moe"])
    params = (len(d["moe"]) * attention_params(cfg)
              + (len(d["moe"]) - n_moe) * dense_ffn_params(cfg)
              + n_moe * (d["top_k"] * expert_params(cfg)
                         + shared_params(cfg) + router_params(cfg)))
    return 2.0 * (params + (head_params(cfg) if with_head else 0))


def attention_flops(cfg: dict, positions: float) -> float:
    """``positions``: (layer, position attended) pairs, every query head."""
    d = dims(cfg)
    return 4.0 * d["head"] * d["heads"] * positions


def decode_token_flops(cfg: dict, context: float) -> float:
    return token_matmul_flops(cfg) + attention_flops(
        cfg, positions_read(cfg, context))


def prompt_flops(cfg: dict, length: int) -> float:
    """A prompt of ``length`` tokens prefilled: every token through the
    layers, the head once, causal attention under each layer's window."""
    d = dims(cfg)
    w = min(d["window"], length)
    full = length * (length + 1) / 2.0
    windowed = w * (w + 1) / 2.0 + (length - w) * w
    pairs = sum(windowed if sliding else full for sliding in d["sliding"])
    return (token_matmul_flops(cfg, with_head=False) * length
            + 2.0 * head_params(cfg) + attention_flops(cfg, pairs))


# --- what the share readers ask (readers/work_share.py) --------------------

def _traced(obs):
    """For the traced part of the window: the prompts prefilled in it,
    the decoded tokens' contexts (as ``(steps, mean context)`` a request)
    and the time average of each live row's context."""
    t = obs.get("traced")
    if not t:
        return None
    a, b = t["t_start"], t["t_stop"]
    prompts, decoded, live = [], [], []
    for r in obs["requests"]:
        p, n = r["prompt"], r["out"]
        if a <= r["t_first"] < b:
            prompts.append(p)
        lo, hi = max(a, r["t_first"]), min(b, r["t_done"])
        if hi <= lo or n < 2:
            continue
        span = r["t_done"] - r["t_first"]
        f0, f1 = (lo - r["t_first"]) / span, (hi - r["t_first"]) / span
        mean_context = p + (n - 1) * (f0 + f1) / 2.0
        decoded.append(((n - 1) * (f1 - f0), mean_context))
        live.append(((hi - lo) / (b - a), mean_context))
    return prompts, decoded, live


def step_mfu(ctx, obs, params):
    """The whole model's share of the bf16 peak over the traced part of
    the window: the prompts prefilled in it and every token decoded in
    it."""
    found = _traced(obs)
    if found is None:
        return None
    prompts, decoded, _ = found
    need = (sum(prompt_flops(ctx.config, p) for p in prompts)
            + sum(steps * decode_token_flops(ctx.config, c)
                  for steps, c in decoded))
    return need / ctx.peak["bf16_flops_per_s"], obs["trace"]["window_s"]


def _steps_traced(ctx, obs, params) -> float:
    from benchmarks.readers.program_time import runs_of

    return len(runs_of(obs, params)) * float(
        ctx.config["serving"]["fused_steps"])


def _touched_a_step(ctx, obs, params):
    """Experts touched in one decode step, summed over its expert layers,
    by the program's own count over the traced part of the window."""
    steps = _steps_traced(ctx, obs, params)
    touched = (obs.get("traced") or {}).get("layer_counts", {}).get(
        "moe_experts_touched")
    if not steps or not touched:
        return None
    return touched / steps


def decode_step_roofline(ctx, obs, params):
    """A decode step's least time (its bytes over the HBM peak) over its
    device time. The rows' share of the time is their weight: a row that
    was live for half of the traced part counts half its context."""
    from benchmarks.readers import program_time

    found = _traced(obs)
    step_ms = program_time.read(ctx, obs, params)
    touched = _touched_a_step(ctx, obs, params)
    if found is None or not step_ms or touched is None:
        return None
    kv = kv_position_bytes(ctx.config) * sum(
        share * positions_read(ctx.config, c) for share, c in found[2])
    least = (fixed_step_bytes(ctx.config)
             + touched * expert_bytes(ctx.config) + kv)
    return least / ctx.peak["hbm_bytes_per_s"], step_ms * 1e-3


def moe_expert_roofline(ctx, obs, params):
    """The touched experts' matrices over the HBM peak, over the device
    time of the decode step's expert operations (the metric file's
    patterns): the roofline of whatever implements the grouped product.
    A lower bound of its bytes (the activations are left out), so it
    cannot read over 100."""
    from benchmarks import trace_reduce

    steps = _steps_traced(ctx, obs, params)
    touched = _touched_a_step(ctx, obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if touched is None or not taken:
        return None
    return (steps * touched * expert_bytes(ctx.config)
            / ctx.peak["hbm_bytes_per_s"], taken)


def moe_share_of_step(ctx, obs, params):
    """The expert operations' device time over the decode-window
    program's (``work_share`` turns the pair into a percentage)."""
    from benchmarks import trace_reduce
    from benchmarks.readers.program_time import runs_of

    runs = runs_of(obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if not runs or not taken:
        return None
    return taken, 1e-9 * sum(b - a for a, b in runs)
