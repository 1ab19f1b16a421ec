"""Plain reference of the ``lfm2-8b-a1b-serve`` configuration.

The ``lfm2_moe`` block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program. One full forward pass over one whole sequence, no cache, no
chunking, no batching:

- ``h0 = E[token]``: no scale, no position embedding;
- layer ``l`` with two RMS norms of a gain: ``h' = h + Op_l(n1(h))``,
  ``h'' = h' + FFN_l(n2(h'))``; ``Op_l`` is attention where ``layer_types``
  says ``full_attention`` and the short convolution where it says
  ``conv``;
- the short convolution: ``[B | C | x] = W_in u``; ``v = B * x``; ``z_t =
  sum_j w[j] v_{t-2+j}`` over 3 taps (zeros before the sequence's start),
  no bias, no activation; ``W_out (C * z)``;
- attention: 32 query heads over 8 KV heads of 64 (query head ``h`` reads
  KV head ``h // 4``); ``q``, ``k`` RMS-normed over each head with a gain;
  the half-split rotation (pair ``(x_i, x_{i+32})``) at theta 1e6; causal
  float32 softmax of ``q.k / 8``; ``Wo``;
- ``FFN`` of the layers below ``num_dense_layers``: ``Wd (silu(Wg u) * Wu
  u)``; of the others: ``s = sigmoid(Wr u)`` over the 32 experts, the 4
  largest of ``s + b`` chosen, ``w_e = s_e / (sum of the chosen s + 1e-6)``
  (times ``routed_scaling_factor`` 1), ``sum_e w_e Expert_e(u)``: a LOOP
  over the experts, each applied to every token with the weight zero where
  it was not chosen;
- ``logits = E n_f(h_L)``: the head IS the embedding matrix.

What the source's ``config.json`` does not state is ``assumed`` in the
configuration's file. The head is computed ONLY for the rows asked for.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import round_to, seed_key

QUERY_BLOCK = 128      # queries an attention slice holds
FAULTS = ("no_conv_state_at_join", "conv_silu", "no_c_gate",
          "no_expert_bias", "bias_in_weights", "no_qk_norm", "no_rotation",
          "no_route_norm")


def dims(cfg: dict) -> dict:
    served = list(cfg["layers_served"])
    first, count = cfg["experts_held"]
    if (cfg["conv_bias"] or not cfg["tie_word_embeddings"]
            or not cfg["use_expert_bias"] or not cfg["norm_topk_prob"]):
        raise ValueError("reference/lfm2 computes the published switches "
                         "only")
    return {"e": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head": cfg["hidden_size"] // cfg["num_attention_heads"],
            "conv": cfg["conv_L_cache"],
            "dense_ffn": cfg["intermediate_size"],
            "expert_ffn": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "held": (int(first), int(count) or cfg["num_experts"]),
            "attn": [cfg["layer_types"][i] == "full_attention"
                     for i in served],
            "moe": [i >= cfg["num_dense_layers"] for i in served]}


def weight_shapes(cfg: dict) -> dict:
    """``{vertex: {leaf: shape}}`` under the program's vertex names;
    layer ``i`` of the served slice is ``b{i}_*``. The tied head has no
    leaf of its own."""
    d = dims(cfg)
    e = d["e"]
    w, kv = d["heads"] * d["head"], d["kv_heads"] * d["head"]
    shapes = {"embed": {"W": (d["vocab"], e)}, "final_norm": {"gain": (e,)}}
    for i, (attn, moe) in enumerate(zip(d["attn"], d["moe"])):
        shapes[f"b{i}_norm1"] = {"gain": (e,)}
        shapes[f"b{i}_norm2"] = {"gain": (e,)}
        shapes[f"b{i}_mix"] = (
            {"Wq": (e, w), "Wk": (e, kv), "Wv": (e, kv), "Wo": (w, e),
             "q_norm": (d["head"],), "k_norm": (d["head"],)}
            if attn else
            {"W_in": (e, 3 * e), "conv_w": (d["conv"], e), "W_out": (e, e)})
        if moe:
            n, f = d["held"][1], d["expert_ffn"]
            shapes[f"b{i}_ffn"] = {"Wr": (e, d["experts"]),
                                   "b": (d["experts"],), "Wg": (n, e, f),
                                   "Wu": (n, e, f), "Wd": (n, f, e)}
        else:
            f = d["dense_ffn"]
            shapes[f"b{i}_ffn"] = {"Wg": (e, f), "Wu": (e, f), "Wd": (f, e)}
    return shapes


FLOAT32_LEAVES = ("Wr", "b", "conv_w")  # the router; the taps
GAINS = ("gain", "q_norm", "k_norm")
BIG = 1 << 28       # float32 bytes above which a leaf is drawn alone


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the device. The leaves of one kind and shape are
    drawn as ONE stacked array and cut (hundreds of draws in one program
    cost the TPU's compiler minutes), a leaf whose stack would pass ``BIG``
    float32 bytes one vertex at a time. Matrices N(0, initializer_range)
    in ``weight_dtype``, the router's in float32; the expert bias N(0,
    expert_bias_std) float32; block norm gains ``1 + N(0, range)``, the
    q/k norms' ``qk_gain_mean + N(0, range)`` (the configuration's
    ``assumed`` says why); the convolution's taps uniform in
    +-1/sqrt(3), float32."""
    std = cfg["initializer_range"]
    wd = jnp.dtype(cfg["weight_dtype"])
    d = dims(cfg)

    def draw(key, n, shape, leaf):
        full = (n,) + shape
        if leaf == "conv_w":
            bound = 1.0 / math.sqrt(d["conv"])
            return jax.random.uniform(key, full, jnp.float32, -bound, bound)
        z = jax.random.normal(key, full, jnp.float32)
        if leaf == "b":
            return cfg["expert_bias_std"] * z
        if leaf in GAINS:
            mean = cfg["qk_gain_mean"] if leaf != "gain" else 1.0
            return mean + std * z
        z = std * z
        return z if leaf in FLOAT32_LEAVES else z.astype(wd)

    draw = jax.jit(draw, static_argnums=(1, 2, 3))
    groups = {}
    shapes = weight_shapes(cfg)
    for vertex, leaves in sorted(shapes.items()):
        kind = vertex.split("_", 1)[-1] if vertex[0] == "b" else vertex
        for leaf, shape in sorted(leaves.items()):
            groups.setdefault((kind, leaf, shape), []).append(vertex)
    key = seed_key(seed)
    out = {v: {} for v in shapes}
    for i, ((_, leaf, shape), vertices) in enumerate(sorted(groups.items())):
        alone = 4 * len(vertices) * int(np.prod(shape)) > BIG
        for j, part in enumerate([[v] for v in vertices] if alone
                                 else [vertices]):
            z = draw(jax.random.fold_in(jax.random.fold_in(key, i), j),
                     len(part), shape, leaf)
            for v, a in zip(part, z):
                out[v][leaf] = a
            del z
    return out


# --------------------------------------------------------------------------
# the mathematics
# --------------------------------------------------------------------------

def _identity(x):
    return x


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def short_conv(cfg, u, p, joined, q=_identity, fault=None):
    """``u: [T, hidden]``. ``joined`` is the position of the first token
    the serving path decodes (the prompt's length):
    ``no_conv_state_at_join`` forgets there the inputs the prompt left."""
    d = dims(cfg)
    t, e, k = u.shape[0], d["e"], d["conv"]
    pos = jnp.arange(t)
    bcx = jnp.dot(q(u), q(p["W_in"]))
    b, c, x = bcx[:, :e], bcx[:, e:2 * e], bcx[:, 2 * e:]
    v = b * x
    z = 0.0
    for j in range(k):
        back = k - 1 - j                          # v_{t - back}
        tap = jnp.pad(v, ((back, 0), (0, 0)))[:t]
        if fault == "no_conv_state_at_join":
            tap = jnp.where(((pos >= joined) & (pos - back < joined))[:, None],
                            0.0, tap)
        z = z + p["conv_w"][j] * tap
    if fault == "conv_silu":
        z = jax.nn.silu(z)
    y = z if fault == "no_c_gate" else c * z
    return jnp.dot(q(y), q(p["W_out"]))


def _rotate(x, theta, fault=None):
    """``x: [T, heads, d]`` at positions ``0..T-1``, the half-split pairs
    ``(x[i], x[i + d/2])`` at ``theta^(-2i/d)``."""
    if fault == "no_rotation":
        return x
    t, _, dim = x.shape
    freq = theta ** (-np.arange(dim // 2, dtype=np.float64) * 2.0 / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, u, p, q=_identity, fault=None):
    d = dims(cfg)
    t = u.shape[0]
    h, g, hd = d["heads"], d["kv_heads"], d["head"]
    eps = cfg["norm_eps"]
    u = q(u)
    qh = jnp.dot(u, q(p["Wq"])).reshape(t, h, hd)
    kh = jnp.dot(u, q(p["Wk"])).reshape(t, g, hd)
    vh = jnp.dot(u, q(p["Wv"])).reshape(t, g, hd)
    if fault != "no_qk_norm":
        qh, kh = _rms(qh, p["q_norm"], eps), _rms(kh, p["k_norm"], eps)
    theta = float(cfg["rope_theta"])
    qh, kh = _rotate(qh, theta, fault), _rotate(kh, theta, fault)
    kh = jnp.repeat(kh, h // g, axis=1)                     # [T, h, hd]
    vh = jnp.repeat(vh, h // g, axis=1)
    pos = jnp.arange(t)

    def block(args):
        q_blk, t_blk = args                      # [B, heads, hd], [B]
        seen = pos <= t_blk[:, None]                         # [B, T]
        s = jnp.einsum("bhd,nhd->bhn", q(q_blk), q(kh)) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhn,nhd->bhd", q(w), q(vh)).reshape(-1, h * hd)

    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qs = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, h,
                                                         hd)
    ts = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(n, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, ts)).reshape(n * QUERY_BLOCK, h * hd)[:t]
    return jnp.dot(q(o), q(p["Wo"]))


def gated(x, wg, wu, wd, q=_identity):
    """``Wd (silu(Wg x) * Wu x)``, ``x`` already rounded."""
    return jnp.dot(q(jax.nn.silu(jnp.dot(x, q(wg))) * jnp.dot(x, q(wu))),
                   q(wd))


def routing(cfg, u, p, q=_identity, fault=None):
    """``[T, experts]``: each token's weight for every expert, zero where
    the expert was not chosen."""
    d = dims(cfg)
    s = jax.nn.sigmoid(jnp.dot(q(u), q(p["Wr"])))
    by = s if fault == "no_expert_bias" else s + p["b"]
    _, chosen = jax.lax.top_k(by, d["top_k"])
    hit = (chosen[..., None] == jnp.arange(d["experts"])).any(axis=-2)
    w = jnp.where(hit, s + p["b"] if fault == "bias_in_weights" else s, 0.0)
    if fault != "no_route_norm":
        w = w / (w.sum(axis=-1, keepdims=True) + cfg["route_eps"])
    return w * cfg["routed_scaling_factor"]


def routed_experts(cfg, u, p, q=_identity, fault=None):
    first, count = dims(cfg)["held"]
    w = routing(cfg, u, p, q, fault)
    x = q(u)

    def one(y, args):
        wg, wu, wd, we = args        # one expert's matrices, its weights [T]
        f32 = jnp.float32
        return y + we[:, None] * gated(x, wg.astype(f32), wu.astype(f32),
                                       wd.astype(f32), q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["Wg"], p["Wu"], p["Wd"], w[:, first:first + count].T))
    return y


def layer(cfg, x, attn, moe, n1, mix, n2, ffn, joined, q=_identity,
          fault=None):
    """One layer over ``x: [T, hidden]``. ``q`` rounds what the control
    rounds: both operands of every matrix product; the residual stream,
    norms, the convolution, the router's choice and softmax stay
    float32."""
    eps = cfg["norm_eps"]
    u = _rms(x, n1["gain"], eps)
    h = x + (attention(cfg, u, mix, q, fault) if attn
             else short_conv(cfg, u, mix, joined, q, fault))
    u = _rms(h, n2["gain"], eps)
    if moe:
        return h + routed_experts(cfg, u, ffn, q, fault)
    f32 = jnp.float32
    return h + gated(q(u), ffn["Wg"].astype(f32), ffn["Wu"].astype(f32),
                     ffn["Wd"].astype(f32), q)


def lower_precision(name: str):
    """The rounding of the control: the operands of every matrix product
    in the precision below the configuration's (``common.round_to``)."""
    return lambda x: round_to(x, name)


class Forward:
    """Logits ``[len(rows), vocab]`` (float32, on the device) of the
    positions ``rows`` of one sequence ``tokens: [T]``: a jitted program
    per kind of layer (reused by every layer of the kind), the embedding
    and the head. ``rows[0]`` is the prompt's last position (the row that
    predicts the first served token): what follows it went through the
    decode steps. ``q`` as in :func:`layer`; ``fault`` plants one of
    :data:`FAULTS` (``benchmarks/tests``: the reference with a mechanism
    broken, put in the program's place, must come out not correct)."""

    def __init__(self, cfg: dict, q=_identity, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.cfg = cfg
        d = dims(cfg)

        def small(t):
            """Everything but the experts' stacks in float32."""
            return {k: v if v.ndim == 3 else v.astype(jnp.float32)
                    for k, v in t.items()}

        self._embed = jax.jit(lambda tok, w: w[tok].astype(jnp.float32))
        self._layer = {
            kind: jax.jit(lambda x, n1, mix, n2, ffn, joined, kind=kind:
                          layer(cfg, x, kind[0], kind[1], small(n1),
                                small(mix), small(n2), small(ffn), joined, q,
                                fault))
            for kind in set(zip(d["attn"], d["moe"]))}
        self._head = jax.jit(lambda h, rows, norm, table: jnp.dot(
            q(_rms(h[rows], norm["gain"].astype(jnp.float32),
                   cfg["norm_eps"])),
            q(table.astype(jnp.float32)).T))

    def __call__(self, w: dict, tokens, rows):
        d = dims(self.cfg)
        rows = jnp.asarray(rows, jnp.int32)
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"])
            for i, kind in enumerate(zip(d["attn"], d["moe"])):
                h = self._layer[kind](
                    h, w[f"b{i}_norm1"], w[f"b{i}_mix"], w[f"b{i}_norm2"],
                    w[f"b{i}_ffn"], rows[0] + 1)
            return self._head(h, rows, w["final_norm"], w["embed"]["W"])
