"""Operations and bytes the ``gigachat35-serve`` configuration needs, from
shapes. Counted for the mathematics, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token passes each layer's mixer matrices once (a delta-rule
  mixer's ``W_qkvz``, ``W_ab``, ``W_o``; a latent mixer's ``W_dq``,
  ``W_uq``, ``W_dkv``, ``W_ukv``, ``W_o`` and the gate ``W_g``), the dense
  layer's feed-forward, an expert layer's router, its shared expert and
  ``num_experts_per_tok`` routed experts, and the head once (a prompt: its
  last position only). The embedding is a gather and is not counted;
- a delta-rule mixer's recurrence: 7 FLOPs a state element a token (the
  decay, the read ``S^T k``, the correction, the read-out ``S^T q``), the
  convolution's taps;
- latent attention: a prompt EXPANDED, ``2 (nope + rope) + 2 v`` a (query
  head, position attended); a decode step ABSORBED, ``2 (kv_rank + rope) +
  2 kv_rank`` a (query head, position attended) beside ``W_ukv`` once more
  a token (the queries carried into the latent and the values out).

Bytes a decode step has to move: every matrix OUTSIDE the routed experts
once, the matrices of the experts TOUCHED (the program counts them), both
delta-rule states read and written for the live rows, the latent vectors
of the live contexts.
"""

from __future__ import annotations

from benchmarks.reference.gigachat35 import delta_channels, dims
from benchmarks.work.afmoe import _traced

WIDTH = {"float32": 4, "bfloat16": 2}
F32 = WIDTH["float32"]


def delta_matrix_params(cfg: dict) -> int:
    """``W_qkvz``, ``W_ab``, ``W_o``."""
    d = dims(cfg)
    z = d["hv"] * d["dv"]
    return d["e"] * (delta_channels(d) + z) + d["e"] * 2 * d["hv"] + z * d["e"]


def delta_float32_params(cfg: dict) -> int:
    """The taps, ``A_log``, ``dt_bias``, the output norm's gain."""
    d = dims(cfg)
    return d["conv"] * delta_channels(d) + 2 * d["hv"] + d["dv"]


def latent_matrix_params(cfg: dict) -> int:
    """``W_dq``, ``W_uq``, ``W_dkv``, ``W_ukv``, ``W_o``, ``W_g``."""
    d = dims(cfg)
    e, h = d["e"], d["heads"]
    return (e * d["q_rank"] + d["q_rank"] * h * (d["nope"] + d["rope"])
            + e * (d["kv_rank"] + d["rope"])
            + d["kv_rank"] * h * (d["nope"] + d["v"]) + 2 * h * d["v"] * e)


def latent_float32_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["q_rank"] + d["kv_rank"]


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


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["vocab"]


def _counts(cfg: dict):
    """``(delta-rule layers, latent layers, expert layers, dense layers)``."""
    d = dims(cfg)
    n_latent, n_moe = sum(d["latent"]), sum(d["moe"])
    return (len(d["latent"]) - n_latent, n_latent, n_moe,
            len(d["moe"]) - n_moe)


def mixer_matrix_params(cfg: dict) -> int:
    n_delta, n_latent, _, _ = _counts(cfg)
    return (n_delta * delta_matrix_params(cfg)
            + n_latent * latent_matrix_params(cfg))


def parameter_count(cfg: dict) -> int:
    """Every parameter this holder has (the layers' norm gains and the
    experts' bias are not counted)."""
    n_delta, n_latent, n_moe, n_dense = _counts(cfg)
    return (mixer_matrix_params(cfg) + n_delta * delta_float32_params(cfg)
            + n_latent * latent_float32_params(cfg)
            + n_dense * dense_ffn_params(cfg)
            + n_moe * (dims(cfg)["held"][1] * expert_params(cfg)
                       + shared_params(cfg) + router_params(cfg))
            + 2 * head_params(cfg))


def _float32_params(cfg: dict) -> int:
    n_delta, n_latent, n_moe, _ = _counts(cfg)
    return (n_delta * delta_float32_params(cfg)
            + n_latent * latent_float32_params(cfg)
            + n_moe * router_params(cfg))


def weight_bytes(cfg: dict) -> float:
    """Matrices in ``weight_dtype``; the router and the mixers' small
    leaves float32."""
    small = _float32_params(cfg)
    return ((parameter_count(cfg) - small) * WIDTH[cfg["weight_dtype"]]
            + small * F32)


def fixed_step_bytes(cfg: dict) -> float:
    """What every decode step reads whatever was routed: the mixers'
    matrices, the dense feed-forward, the shared experts, the routers, the
    head."""
    _, _, n_moe, n_dense = _counts(cfg)
    w = WIDTH[cfg["weight_dtype"]]
    return (w * (mixer_matrix_params(cfg) + n_dense * dense_ffn_params(cfg)
                 + n_moe * shared_params(cfg) + head_params(cfg))
            + F32 * n_moe * router_params(cfg))


def expert_bytes(cfg: dict) -> float:
    return expert_params(cfg) * WIDTH[cfg["weight_dtype"]]


def state_row_bytes(cfg: dict) -> dict:
    """One row's state by kind, all layers (``latent`` a position)."""
    d = dims(cfg)
    n_delta, n_latent, _, _ = _counts(cfg)
    return {"recurrent": n_delta * d["hv"] * d["dk"] * d["dv"] * F32,
            "conv_window": n_delta * (d["conv"] - 1) * delta_channels(d) * F32,
            "latent": n_latent * (d["kv_rank"] + d["rope"])
            * WIDTH[cfg["cache_dtype"]]}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float) -> float:
    """One decode step over live rows at ``contexts``; ``experts_touched``
    summed over the step's expert layers."""
    row = state_row_bytes(cfg)
    return (fixed_step_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + 2.0 * len(contexts) * (row["recurrent"] + row["conv_window"])
            + row["latent"] * sum(contexts))


def delta_flops_per_token(cfg: dict) -> float:
    """A delta-rule mixer's recurrence and convolution, one token."""
    d = dims(cfg)
    return 7.0 * d["hv"] * d["dk"] * d["dv"] + 2.0 * d["conv"] * \
        delta_channels(d)


def token_matmul_flops(cfg: dict, with_head: bool = True) -> float:
    """The matrices one token passes."""
    d = dims(cfg)
    _, _, n_moe, n_dense = _counts(cfg)
    params = (mixer_matrix_params(cfg) + n_dense * dense_ffn_params(cfg)
              + n_moe * (d["top_k"] * expert_params(cfg) + shared_params(cfg)
                         + router_params(cfg)))
    return 2.0 * (params + (head_params(cfg) if with_head else 0))


def decode_attention_flops(cfg: dict, context: float) -> float:
    """The absorbed read of ``context`` positions in every latent layer,
    and ``W_ukv`` once more (the queries into the latent, the values
    out)."""
    d = dims(cfg)
    n_latent = _counts(cfg)[1]
    per = 2.0 * (d["kv_rank"] + d["rope"]) + 2.0 * d["kv_rank"]
    absorb = 2.0 * d["kv_rank"] * d["heads"] * (d["nope"] + d["v"])
    return n_latent * (d["heads"] * per * context + absorb)


def decode_token_flops(cfg: dict, context: float) -> float:
    return (token_matmul_flops(cfg)
            + _counts(cfg)[0] * delta_flops_per_token(cfg)
            + decode_attention_flops(cfg, context))


def prompt_flops(cfg: dict, length: int) -> float:
    """A prompt of ``length`` tokens prefilled: every token through the
    layers, the head once, causal attention expanded."""
    d = dims(cfg)
    n_delta, n_latent, _, _ = _counts(cfg)
    pairs = length * (length + 1) / 2.0
    per = 2.0 * (d["nope"] + d["rope"]) + 2.0 * d["v"]
    return (length * (token_matmul_flops(cfg, with_head=False)
                      + n_delta * delta_flops_per_token(cfg))
            + 2.0 * head_params(cfg)
            + n_latent * d["heads"] * per * pairs)


# --- what the share readers ask (readers/work_share.py) --------------------

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
    was live for half of the traced part counts half its states and half
    its context."""
    from benchmarks.readers import program_time

    found = _traced(obs)
    step_ms = program_time.read(ctx, obs, params)
    touched = _touched_a_step(ctx, obs, params)
    if found is None or not step_ms or touched is None:
        return None
    row = state_row_bytes(ctx.config)
    least = (fixed_step_bytes(ctx.config)
             + touched * expert_bytes(ctx.config)
             + sum(share * (2.0 * (row["recurrent"] + row["conv_window"])
                            + row["latent"] * c) for share, c in found[2]))
    return least / ctx.peak["hbm_bytes_per_s"], step_ms * 1e-3


def _op_seconds(obs, params) -> float:
    from benchmarks import trace_reduce

    return trace_reduce.op_seconds(obs["trace"], params["op_patterns"])


def delta_state_roofline(ctx, obs, params):
    """The delta rule's state read and written for the live rows in every
    traced decode step (the rows' time share their weight, as
    :func:`decode_step_roofline`) over the HBM peak, over the device time
    of the operations that update it (the metric file's patterns)."""
    found = _traced(obs)
    steps = _steps_traced(ctx, obs, params)
    taken = _op_seconds(obs, params)
    if found is None or not steps or not taken:
        return None
    live = sum(share for share, _c in found[2])
    need = 2.0 * steps * live * state_row_bytes(ctx.config)["recurrent"]
    return need / ctx.peak["hbm_bytes_per_s"], taken


def latent_read_roofline(ctx, obs, params):
    """The latent vectors of the live contexts in every traced decode step
    over the HBM peak, over the device time of the latent read (the
    metric file's patterns). The read streams whole pages, so it cannot
    read over 100."""
    found = _traced(obs)
    steps = _steps_traced(ctx, obs, params)
    taken = _op_seconds(obs, params)
    if found is None or not steps or not taken:
        return None
    need = steps * state_row_bytes(ctx.config)["latent"] * sum(
        share * c for share, c in found[2])
    return need / ctx.peak["hbm_bytes_per_s"], taken


def moe_expert_roofline(ctx, obs, params):
    """The touched experts' matrices over the HBM peak, over the device
    time of the decode step's expert operations (the metric file's
    patterns). A lower bound of the bytes (the activations are left out),
    so it cannot read over 100."""
    steps = _steps_traced(ctx, obs, params)
    touched = _touched_a_step(ctx, obs, params)
    taken = _op_seconds(obs, params)
    if touched is None or not taken:
        return None
    return (steps * touched * expert_bytes(ctx.config)
            / ctx.peak["hbm_bytes_per_s"], taken)
