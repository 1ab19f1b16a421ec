"""Operations and bytes the ``minicpm-sala-serve`` decode step needs, from
shapes. Counted for the mathematics, whatever implements it, 2 FLOPs a
multiply-accumulate:

- every token passes each layer's matrices once (q, k, v, the output gate
  and o of its mixer; gate, up and down of the feed-forward) and the head
  once; the embedding is a gather and is not counted;
- a ``lightning-attn`` layer updates and reads its state: per head
  ``lambda S + k^T v`` (2 d^2) and ``q S`` (2 d^2);
- a ``minicpm4`` layer scores the live compressed keys (2 d a key a query
  head) and attends the chosen positions (4 d a position a query head).

Bytes a decode step has to move: every matrix once; each live recurrent
state read once and written once, with that row's q, k and v; the
compressed keys of the live contexts (float32: they feed a discrete
choice); the keys and values of the chosen positions. NOT the whole KV
cache: that is what the selection is for.
"""

from __future__ import annotations

from benchmarks.reference.minicpm_sala import dims, parameter_count  # noqa: F401

WIDTH = {"float32": 4, "bfloat16": 2}


def mixer_params(cfg: dict, kind: str) -> int:
    d = dims(cfg)
    e = d["e"]
    if kind == "lightning-attn":
        return 5 * e * d["l_heads"] * d["l_head"]
    return 3 * e * d["heads"] * d["head"] + 2 * e * d["kv_heads"] * d["head"]


def layer_params(cfg: dict, kind: str) -> int:
    """The matrices of one layer (its norms' gains are not counted)."""
    d = dims(cfg)
    return mixer_params(cfg, kind) + 3 * d["e"] * d["ffn"]


def block_matmul_params(cfg: dict) -> int:
    return sum(layer_params(cfg, k) for k in dims(cfg)["kinds"])


def head_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["e"] * d["vocab"]


def vocabulary_params(cfg: dict) -> int:
    """Embedding and untied head."""
    return 2 * head_params(cfg)


def attended_positions(cfg: dict, context: float) -> float:
    """Positions a sparse-layer query attends at ``context`` positions of
    context: everything up to ``dense_len``, beyond it the initial
    blocks, the window and ``topk`` blocks (fewer where the context has
    fewer candidates)."""
    sp = cfg["sparse_config"]
    if context <= sp["dense_len"]:
        return context
    fixed = sp["init_blocks"] * sp["block_size"] + sp["window_size"]
    return min(context, fixed + sp["topk"] * sp["block_size"])


def state_bytes(cfg: dict, rows: float) -> float:
    """One layer's recurrent state of ``rows`` rows."""
    d = dims(cfg)
    return rows * d["l_heads"] * d["l_head"] ** 2 * WIDTH[cfg["state_dtype"]]


def linear_state_step_bytes(cfg: dict, rows: float) -> float:
    """One lightning layer, one step: the state read and written, and the
    rows' q, k and v (float32)."""
    d = dims(cfg)
    return 2 * state_bytes(cfg, rows) + 3 * rows * d["l_heads"] * d["l_head"] * 4


def sparse_attn_step_bytes(cfg: dict, contexts) -> float:
    """One sparse layer, one step: the live compressed keys and the chosen
    positions' keys and values, over the rows' ``contexts``."""
    d = dims(cfg)
    sp = cfg["sparse_config"]
    lanes = d["kv_heads"] * d["head"]
    total = 0.0
    for c in contexts:
        total += (c // sp["kernel_stride"]) * lanes * WIDTH["float32"]
        total += 2 * attended_positions(cfg, c) * lanes * WIDTH[
            cfg["cache_dtype"]]
    return total


def _count(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in dims(cfg)["kinds"])


def decode_step_bytes(cfg: dict, contexts) -> float:
    w = WIDTH[cfg["weight_dtype"]]
    return ((block_matmul_params(cfg) + head_params(cfg)) * w
            + _count(cfg, "lightning-attn") * linear_state_step_bytes(
                cfg, len(contexts))
            + _count(cfg, "minicpm4") * sparse_attn_step_bytes(cfg, contexts))


def decode_token_flops(cfg: dict, context: float) -> float:
    """One token decoded at ``context`` positions of context."""
    d = dims(cfg)
    sp = cfg["sparse_config"]
    flops = 2.0 * (block_matmul_params(cfg) + head_params(cfg))
    flops += _count(cfg, "lightning-attn") * 4.0 * d["l_heads"] * d["l_head"] ** 2
    sparse = 4.0 * d["head"] * d["heads"] * attended_positions(cfg, context)
    if context > sp["dense_len"]:
        sparse += 2.0 * d["head"] * d["heads"] * (context // sp["kernel_stride"])
    return flops + _count(cfg, "minicpm4") * sparse


# --- what the share readers ask (readers/work_share.py) --------------------

def _contexts(obs):
    """Each row's context in the middle of the traced part of the
    window."""
    t = obs.get("traced")
    if not t or not t.get("contexts_open"):
        return None
    return [(a + b) / 2.0 for a, b in zip(t["contexts_open"],
                                          t["contexts_stop"])]


def _steps_traced(ctx, obs, params) -> float:
    from benchmarks.readers.program_time import runs_of

    return len(runs_of(obs, params)) * float(
        ctx.config["serving"]["fused_steps"])


def step_mfu(ctx, obs, params):
    """The whole decode step's share of the bf16 peak over the traced
    part of the window (it holds no prefill: every token is a decode
    step's)."""
    contexts = _contexts(obs)
    if contexts is None:
        return None
    per_row = obs["traced"]["tokens"] / len(contexts)
    need = sum(per_row * decode_token_flops(ctx.config, c) for c in contexts)
    return need / ctx.peak["bf16_flops_per_s"], obs["trace"]["window_s"]


def decode_step_roofline(ctx, obs, params):
    """A decode step's least time (its bytes over the HBM peak: it is
    bandwidth-bound by two orders of magnitude) over its device time."""
    from benchmarks.readers import program_time

    contexts = _contexts(obs)
    step_ms = program_time.read(ctx, obs, params)
    if contexts is None or not step_ms:
        return None
    least = decode_step_bytes(ctx.config, contexts) / ctx.peak[
        "hbm_bytes_per_s"]
    return least, step_ms * 1e-3


def _mechanism(ctx, obs, params, bytes_a_step):
    from benchmarks import trace_reduce

    steps = _steps_traced(ctx, obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if not steps or not taken:
        return None
    return steps * bytes_a_step / ctx.peak["hbm_bytes_per_s"], taken


def sparse_attn_roofline(ctx, obs, params):
    """The sparse layers' score, top-k, gather and attend: compressed keys
    and chosen K/V over the HBM peak, over those operations' device
    time."""
    contexts = _contexts(obs)
    if contexts is None:
        return None
    return _mechanism(ctx, obs, params, _count(ctx.config, "minicpm4")
                      * sparse_attn_step_bytes(ctx.config, contexts))


def mixer_share_of_step(ctx, obs, params):
    """The two mechanisms' device time over the decode-window program's
    (``work_share`` turns the pair into a percentage)."""
    from benchmarks import trace_reduce
    from benchmarks.readers.program_time import runs_of

    runs = runs_of(obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if not runs or not taken:
        return None
    return taken, 1e-9 * sum(b - a for a, b in runs)
