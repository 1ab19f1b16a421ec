"""Operations and bytes the ``lfm2-8b-a1b-serve`` configuration needs, from
shapes. Counted for the mathematics, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token passes each layer's mixer matrices once (a short
  convolution's ``W_in``, ``W_out``; an attention layer's ``Wq``, ``Wk``,
  ``Wv``, ``Wo``), the dense layers' feed-forward, an expert layer's router
  and ``num_experts_per_tok`` routed experts, and the tied head once (a
  prompt: its last position only). The embedding is a gather and is not
  counted;
- a short convolution's taps and gates: ``2 d_conv + 2`` a channel a
  token;
- attention: ``4 head_dim`` a (query head, position attended), causal in a
  prompt.

Bytes a decode step has to move: every matrix OUTSIDE the routed experts
once (the embedding once, as the head), the matrices of the experts TOUCHED
(the program counts them), each live row's convolution rings read and
written, the keys and values of the live contexts.
"""

from __future__ import annotations

from benchmarks.reference.lfm2 import dims
from benchmarks.work.afmoe import _traced
from benchmarks.work.gigachat35 import (
    _op_seconds,
    _steps_traced,
    _touched_a_step,
)

WIDTH = {"float32": 4, "bfloat16": 2}
F32 = WIDTH["float32"]


def conv_matrix_params(cfg: dict) -> int:
    """A short convolution's ``W_in`` and ``W_out``."""
    e = dims(cfg)["e"]
    return e * 3 * e + e * e


def conv_float32_params(cfg: dict) -> int:
    """Its taps."""
    d = dims(cfg)
    return d["conv"] * d["e"]


def attn_matrix_params(cfg: dict) -> int:
    """``Wq``, ``Wk``, ``Wv``, ``Wo``."""
    d = dims(cfg)
    q, kv = d["heads"] * d["head"], d["kv_heads"] * d["head"]
    return 2 * d["e"] * q + 2 * d["e"] * kv


def expert_params(cfg: dict) -> int:
    """One routed expert."""
    d = dims(cfg)
    return 3 * d["e"] * d["expert_ffn"]


def router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["experts"]


def dense_ffn_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["e"] * d["dense_ffn"]


def head_params(cfg: dict) -> int:
    """The embedding, which is also the head."""
    d = dims(cfg)
    return d["e"] * d["vocab"]


def _counts(cfg: dict):
    """``(convolution layers, attention layers, expert layers, dense
    layers)``."""
    d = dims(cfg)
    n_attn, n_moe = sum(d["attn"]), sum(d["moe"])
    return (len(d["attn"]) - n_attn, n_attn, n_moe, len(d["moe"]) - n_moe)


def mixer_matrix_params(cfg: dict) -> int:
    n_conv, n_attn, _, _ = _counts(cfg)
    return (n_conv * conv_matrix_params(cfg)
            + n_attn * attn_matrix_params(cfg))


def parameter_count(cfg: dict) -> int:
    """Every parameter this stage holds (the norms' gains and the experts'
    bias are not counted)."""
    n_conv, _, n_moe, n_dense = _counts(cfg)
    return (mixer_matrix_params(cfg) + n_conv * conv_float32_params(cfg)
            + n_dense * dense_ffn_params(cfg)
            + n_moe * (dims(cfg)["held"][1] * expert_params(cfg)
                       + router_params(cfg))
            + head_params(cfg))


def weight_bytes(cfg: dict) -> float:
    """Matrices in ``weight_dtype``; the routers and the taps float32."""
    n_conv, _, n_moe, _ = _counts(cfg)
    small = n_conv * conv_float32_params(cfg) + n_moe * router_params(cfg)
    return ((parameter_count(cfg) - small) * WIDTH[cfg["weight_dtype"]]
            + small * F32)


def fixed_step_bytes(cfg: dict) -> float:
    """What every decode step reads whatever was routed: the mixers'
    matrices, the dense feed-forward, the routers, the head."""
    _, _, n_moe, n_dense = _counts(cfg)
    w = WIDTH[cfg["weight_dtype"]]
    return (w * (mixer_matrix_params(cfg) + n_dense * dense_ffn_params(cfg)
                 + head_params(cfg))
            + F32 * n_moe * router_params(cfg))


def expert_bytes(cfg: dict) -> float:
    return expert_params(cfg) * WIDTH[cfg["weight_dtype"]]


def state_row_bytes(cfg: dict) -> dict:
    """One row's state by kind, all layers (``kv`` a position)."""
    d = dims(cfg)
    n_conv, n_attn, _, _ = _counts(cfg)
    return {"conv_window": n_conv * (d["conv"] - 1) * d["e"] * F32,
            "kv": n_attn * 2 * d["kv_heads"] * d["head"]
            * WIDTH[cfg["cache_dtype"]]}


def decode_step_bytes(cfg: dict, contexts, experts_touched: float) -> float:
    """One decode step over live rows at ``contexts``; ``experts_touched``
    summed over the step's expert layers."""
    row = state_row_bytes(cfg)
    return (fixed_step_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + 2.0 * len(contexts) * row["conv_window"]
            + row["kv"] * sum(contexts))


def conv_flops_per_token(cfg: dict) -> float:
    """A short convolution's taps and its two gates, one token."""
    d = dims(cfg)
    return (2.0 * d["conv"] + 2.0) * d["e"]


def token_matmul_flops(cfg: dict, with_head: bool = True) -> float:
    """The matrices one token passes."""
    d = dims(cfg)
    _, _, n_moe, n_dense = _counts(cfg)
    params = (mixer_matrix_params(cfg) + n_dense * dense_ffn_params(cfg)
              + n_moe * (d["top_k"] * expert_params(cfg)
                         + router_params(cfg)))
    return 2.0 * (params + (head_params(cfg) if with_head else 0))


def attention_flops(cfg: dict, attended: float) -> float:
    """Every attention layer's scores and values over ``attended``
    (query, position) pairs a query head."""
    d = dims(cfg)
    return _counts(cfg)[1] * d["heads"] * 4.0 * d["head"] * attended


def decode_token_flops(cfg: dict, context: float) -> float:
    return (token_matmul_flops(cfg)
            + _counts(cfg)[0] * conv_flops_per_token(cfg)
            + attention_flops(cfg, context))


def prompt_flops(cfg: dict, length: int) -> float:
    """A prompt of ``length`` tokens prefilled: every token through the
    layers, the head once, causal attention."""
    return (length * (token_matmul_flops(cfg, with_head=False)
                      + _counts(cfg)[0] * conv_flops_per_token(cfg))
            + 2.0 * head_params(cfg)
            + attention_flops(cfg, length * (length + 1) / 2.0))


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


def decode_step_roofline(ctx, obs, params):
    """A decode step's least time (its bytes over the HBM peak) over its
    device time. The rows' share of the time is their weight: a row that
    was live for half of the traced part counts half its rings and half
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
             + sum(share * (2.0 * row["conv_window"] + row["kv"] * c)
                   for share, c in found[2]))
    return least / ctx.peak["hbm_bytes_per_s"], step_ms * 1e-3


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


def shortconv_roofline(ctx, obs, params):
    """The short convolutions' ``W_in`` and ``W_out`` and the live rows'
    rings read and written, in every traced decode step, over the HBM
    peak, over the device time of their decode operations (the metric
    file's patterns). A lower bound of the bytes (the activations are left
    out)."""
    found = _traced(obs)
    steps = _steps_traced(ctx, obs, params)
    taken = _op_seconds(obs, params)
    if found is None or not steps or not taken:
        return None
    cfg = ctx.config
    live = sum(share for share, _c in found[2])
    need = steps * (_counts(cfg)[0] * conv_matrix_params(cfg)
                    * WIDTH[cfg["weight_dtype"]]
                    + 2.0 * live * state_row_bytes(cfg)["conv_window"])
    return need / ctx.peak["hbm_bytes_per_s"], taken
