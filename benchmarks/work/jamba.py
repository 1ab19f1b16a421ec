"""Operations and bytes the ``jamba2-3b-serve`` configuration needs, from
shapes. Counted for the mathematics, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token passes each layer's mixer matrices once (a Mamba mixer's
  ``W_in``, ``W_x``, ``W_dt``, ``W_out``; an attention mixer's q, k, v,
  o), each layer's feed-forward and the tied head once (a prompt: its
  last position only). The embedding is a gather and is not counted;
- a Mamba mixer's recurrence: three multiply-adds a state element a
  token (the decay's product, the update, the read-out; the exponential
  is not counted), the convolution's taps;
- attention: 4 d a (query head, position attended).

Bytes a decode step has to move: every matrix once (the tied matrix
once), both Mamba states read and written for the live rows, the keys
and values of the live contexts. Bytes a prompt's scan has to move: a
position's ``x~``, ``dt``, ``B``, ``C`` in and ``y`` out, the state in and
out once a launch.
"""

from __future__ import annotations

from benchmarks.reference.jamba import dims
from benchmarks.work.afmoe import _traced

WIDTH = {"float32": 4, "bfloat16": 2}
F32 = WIDTH["float32"]


def mamba_matrix_params(cfg: dict) -> int:
    """``W_in``, ``W_x``, ``W_dt``, ``W_out``."""
    s = dims(cfg)
    return (s["e"] * 2 * s["d"] + s["d"] * (s["r"] + 2 * s["n"])
            + s["r"] * s["d"] + s["d"] * s["e"])


def mamba_float32_params(cfg: dict) -> int:
    """The taps and their bias, ``b_dt``, ``A_log``, ``D``, the three
    inner norms' gains."""
    s = dims(cfg)
    return (s["d"] * s["k"] + s["d"] + s["d"] + s["d"] * s["n"] + s["d"]
            + s["r"] + 2 * s["n"])


def mamba_params(cfg: dict) -> int:
    return mamba_matrix_params(cfg) + mamba_float32_params(cfg)


def attention_params(cfg: dict) -> int:
    s = dims(cfg)
    return s["e"] * s["head"] * 2 * (s["heads"] + s["kv_heads"])


def ffn_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s["e"] * s["ffn"]


def embedding_params(cfg: dict) -> int:
    """The one matrix that is the embedding and the head."""
    s = dims(cfg)
    return s["e"] * s["vocab"]


def _layers(cfg: dict):
    attn = sum(dims(cfg)["attn"])
    return len(dims(cfg)["attn"]) - attn, attn


def layer_params(cfg: dict) -> int:
    """The mixers and feed-forwards of all layers (the layers' two norm
    gains are not counted)."""
    n_mamba, n_attn = _layers(cfg)
    return (n_mamba * (mamba_params(cfg) + ffn_params(cfg))
            + n_attn * (attention_params(cfg) + ffn_params(cfg)))


def parameter_count(cfg: dict) -> int:
    return layer_params(cfg) + embedding_params(cfg)


def weight_bytes(cfg: dict) -> float:
    """Matrices in ``weight_dtype``, a Mamba mixer's small leaves float32."""
    small = _layers(cfg)[0] * mamba_float32_params(cfg)
    return ((parameter_count(cfg) - small) * WIDTH[cfg["weight_dtype"]]
            + small * F32)


def state_row_bytes(cfg: dict) -> dict:
    """One row's state by kind, all layers (``kv`` a position)."""
    s = dims(cfg)
    n_mamba, n_attn = _layers(cfg)
    return {"recurrent": n_mamba * s["n"] * s["d"] * F32,
            "conv_window": n_mamba * (s["k"] - 1) * s["d"] * F32,
            "kv": n_attn * 2 * s["kv_heads"] * s["head"]
            * WIDTH[cfg["cache_dtype"]]}


def decode_step_bytes(cfg: dict, contexts) -> float:
    """One decode step over live rows at ``contexts``: every matrix once,
    both Mamba states read and written a row, the live keys and values."""
    row = state_row_bytes(cfg)
    return (weight_bytes(cfg)
            + 2.0 * len(contexts) * (row["recurrent"] + row["conv_window"])
            + row["kv"] * sum(contexts))


def scan_flops_per_token(cfg: dict) -> float:
    s = dims(cfg)
    return 6.0 * s["n"] * s["d"] + 2.0 * s["k"] * s["d"]


def token_matmul_flops(cfg: dict, with_head: bool = True) -> float:
    """The matrices one token passes."""
    n_mamba, n_attn = _layers(cfg)
    params = (n_mamba * (mamba_matrix_params(cfg) + ffn_params(cfg))
              + n_attn * (attention_params(cfg) + ffn_params(cfg)))
    return 2.0 * (params + (embedding_params(cfg) if with_head else 0))


def attention_flops(cfg: dict, positions: float) -> float:
    """``positions``: (layer, position attended) pairs, every query head."""
    s = dims(cfg)
    return 4.0 * s["head"] * s["heads"] * positions


def decode_token_flops(cfg: dict, context: float) -> float:
    n_mamba, n_attn = _layers(cfg)
    return (token_matmul_flops(cfg) + n_mamba * scan_flops_per_token(cfg)
            + attention_flops(cfg, n_attn * context))


def prompt_flops(cfg: dict, length: int) -> float:
    """A prompt of ``length`` tokens prefilled: every token through the
    layers, the head once, causal attention."""
    n_mamba, n_attn = _layers(cfg)
    return (length * (token_matmul_flops(cfg, with_head=False)
                      + n_mamba * scan_flops_per_token(cfg))
            + 2.0 * embedding_params(cfg)
            + attention_flops(cfg, n_attn * length * (length + 1) / 2.0))


def prompt_scan_bytes(cfg: dict, positions: int) -> float:
    """One layer's scan over one row's bucket of ``positions``: ``x~``,
    ``dt`` and ``y`` of ``d`` channels and ``B``, ``C`` of ``n`` a position,
    the state in and out."""
    s = dims(cfg)
    return F32 * (positions * (3 * s["d"] + 2 * s["n"])
                  + 2 * s["n"] * s["d"])


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
    was live for half of the traced part counts half its state and half
    its context."""
    from benchmarks.readers import program_time

    found = _traced(obs)
    step_ms = program_time.read(ctx, obs, params)
    if found is None or not step_ms:
        return None
    row = state_row_bytes(ctx.config)
    least = (weight_bytes(ctx.config)
             + sum(share * (2.0 * (row["recurrent"] + row["conv_window"])
                            + row["kv"] * c) for share, c in found[2]))
    return least / ctx.peak["hbm_bytes_per_s"], step_ms * 1e-3


def prompt_bucket(cfg: dict, length: int) -> int:
    """The decoder's prompt bucket of a prompt: powers of two from
    ``serving.prompt_bucket_min``."""
    bucket = int(cfg["serving"]["prompt_bucket_min"])
    while bucket < length:
        bucket *= 2
    return bucket


def _scan_seconds(obs, params) -> float:
    """Device time of the traced prompt scans: the operations whose HLO
    text matches ``scan_pattern``."""
    from benchmarks import trace_reduce

    return trace_reduce.op_seconds(obs["trace"], [params["scan_pattern"]])


def ssm_scan_roofline(ctx, obs, params):
    """The bytes the traced prompts' scans must move (each Mamba layer
    over the bucket of each prompt whose first token fell in the traced
    part of the window, one prompt a launch) over the HBM peak, over the
    scan operations' device time: the roofline of whatever implements the
    scan. Bound by the vector unit, so a low share. The buckets come from
    the host's records and not from the operations' own shapes: the
    harness keys an operation by its short name, and one name stands for
    a scan of 64 positions in one program and of 2,048 in another."""
    found = _traced(obs)
    taken = _scan_seconds(obs, params)
    if found is None or not found[0] or not taken:
        return None
    cfg = ctx.config
    need = _layers(cfg)[0] * sum(
        prompt_scan_bytes(cfg, prompt_bucket(cfg, p)) for p in found[0])
    return need / ctx.peak["hbm_bytes_per_s"], taken


def _other_programs_seconds(obs, params):
    """Device time of the programs of the pattern that are NOT the one
    that ran most often (the decode window): the prompt and join
    programs."""
    from benchmarks import trace_reduce

    every = trace_reduce.program_runs(obs["trace"], params["pattern"], "all")
    most = trace_reduce.program_runs(obs["trace"], params["pattern"],
                                     "most_runs")
    return 1e-9 * (sum(b - a for a, b in every) - sum(b - a for a, b in most))


def ssm_scan_share_of_prefill(ctx, obs, params):
    """The prompt scans' device time over the prompt (and join)
    programs'."""
    taken = _scan_seconds(obs, params)
    programs = _other_programs_seconds(obs, params)
    if not taken or programs <= 0:
        return None
    return taken, programs


def ssm_share_of_step(ctx, obs, params):
    """The Mamba mixers' operations' device time in the decode window
    (the metric file's patterns) over the decode-window program's."""
    from benchmarks import trace_reduce
    from benchmarks.readers.program_time import runs_of

    runs = runs_of(obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if not runs or not taken:
        return None
    return taken, 1e-9 * sum(b - a for a, b in runs)
