"""Plain reference of the ``minicpm-sala-serve`` configuration.

MiniCPM-SALA's block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program. One full forward pass over one whole sequence, no cache, no
batching, no chunked recurrence:

- token path ``x = scale_emb * E[token]``; every layer ``h = x + c
  Mixer(RMSNorm(x))``, ``x' = h + c Wd(silu(Wg u) * Wu u)`` with ``u =
  RMSNorm(h)`` and ``c = scale_depth / sqrt(32)`` (the PUBLISHED depth,
  also in the cut); head ``W RMSNorm(x) / (hidden / dim_model_base)``;
- ``lightning-attn``: q/k RMS norm over each head's 128 with a gain,
  rotary positions (half-split pairs, theta 10000), per head ``S_t =
  lambda S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(128)) S_t`` as a
  ``lax.scan`` over TOKENS, RMS norm of each head's output, ``* sigmoid(Wg
  u)``, ``Wo``;
- ``minicpm4``: 32 query heads over 2 KV heads, q/k RMS norm, no rotation,
  causal softmax; beyond ``dense_len`` positions of context the selection
  written out below (:func:`chosen_blocks`), then dense masked softmax
  over exactly the chosen positions, in query blocks.

Departures from the published model, all ``assumed`` in the
configuration's file: the decay's slopes, the output norm per head, the
selection's sizes (MiniCPM4's ``sparse_config``), and that a block is a
candidate for the top-k only if it starts at or before ``t - window``
(it reaches outside the window) and is not an initial block.

It runs layer by layer (one layer's bfloat16 weights upcast, applied,
dropped), the feed-forward and the attention in blocks of tokens, and the
head ONLY for the rows asked for, so that a 28 k-token sequence fits
beside the weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import round_to, seed_key

PUBLISHED_LAYERS = 32
TOKEN_BLOCK = 2048     # tokens a feed-forward slice holds
QUERY_BLOCK = 64       # queries a sparse-attention slice holds


def dims(cfg: dict) -> dict:
    kinds = [cfg["mixer_types"][i] for i in cfg["layers_served"]]
    return {"e": cfg["hidden_size"], "ffn": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
            "l_heads": cfg["lightning_nh"], "l_head": cfg["lightning_head_dim"],
            "kinds": kinds, "indices": list(cfg["layers_served"]),
            "c": cfg["scale_depth"] / PUBLISHED_LAYERS ** 0.5,
            "logit_scale": cfg["dim_model_base"] / cfg["hidden_size"]}


def weight_shapes(cfg: dict) -> dict:
    """``{vertex: {leaf: shape}}`` under the program's vertex names;
    layer ``i`` of the served slice is ``b{i}_*``."""
    d = dims(cfg)
    e, f = d["e"], d["ffn"]
    shapes = {"embed": {"W": (d["vocab"], e)}, "final_norm": {"gain": (e,)},
              "output": {"W": (e, d["vocab"])}}
    for i, kind in enumerate(d["kinds"]):
        shapes[f"b{i}_norm1"] = {"gain": (e,)}
        shapes[f"b{i}_norm2"] = {"gain": (e,)}
        shapes[f"b{i}_ffn"] = {"Wg": (e, f), "Wu": (e, f), "Wd": (f, e)}
        shapes[f"b{i}_mix"] = _mixer_shapes(d, kind)
    return shapes


def _mixer_shapes(d: dict, kind: str) -> dict:
    e = d["e"]
    if kind == "lightning-attn":
        w, hd = d["l_heads"] * d["l_head"], d["l_head"]
        return {"Wq": (e, w), "Wk": (e, w), "Wv": (e, w), "Wg": (e, w),
                "Wo": (w, e), "q_norm": (hd,), "k_norm": (hd,),
                "o_norm": (hd,)}
    w, kv, hd = d["heads"] * d["head"], d["kv_heads"] * d["head"], d["head"]
    return {"Wq": (e, w), "Wk": (e, kv), "Wv": (e, kv), "Wg": (e, w),
            "Wo": (w, e), "q_norm": (hd,), "k_norm": (hd,)}


def parameter_count(cfg: dict, matrices_only: bool = False) -> int:
    total = 0
    for leaves in weight_shapes(cfg).values():
        for shape in leaves.values():
            if matrices_only and len(shape) < 2:
                continue
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def _groups(cfg: dict) -> dict:
    """``{(kind, leaf, shape): [vertex...]}``: the leaves drawn together."""
    groups = {}
    for vertex, leaves in sorted(weight_shapes(cfg).items()):
        kind = vertex.split("_", 1)[-1] if vertex[0] == "b" else vertex
        for leaf, shape in sorted(leaves.items()):
            groups.setdefault((kind, leaf, shape), []).append(vertex)
    return groups


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the device. The leaves of one kind and shape are
    drawn as ONE stacked array and cut (PR 24: 580 draws in a program
    cost the TPU's compiler 200 s, 20 arrays 26 s), one small jitted
    program a group, so that no more than one stacked array (3.2 GB in
    float32 for the twelve feed-forward matrices of a kind) is alive
    beside the weights. Matrices N(0, initializer_range) in
    ``weight_dtype``; gains ``1 + N(0, range)`` float32, those of the q
    and k norms ``qk_gain_mean + N(0, range)`` (the configuration's
    ``assumed`` says why)."""
    std = cfg["initializer_range"]
    wd = jnp.dtype(cfg["weight_dtype"])

    def draw(key, n, shape, mean):
        z = std * jax.random.normal(key, (n,) + shape, jnp.float32)
        z = (mean + z) if len(shape) == 1 else z.astype(wd)
        return tuple(z[j] for j in range(n))

    draw = jax.jit(draw, static_argnums=(1, 2, 3))
    key = seed_key(seed)
    out = {v: {} for v in weight_shapes(cfg)}
    for i, ((_, leaf, shape), vertices) in enumerate(
            sorted(_groups(cfg).items())):
        mean = (float(cfg["qk_gain_mean"]) if leaf in ("q_norm", "k_norm")
                else 1.0)
        for v, z in zip(vertices, draw(jax.random.fold_in(key, i),
                                       len(vertices), shape, mean)):
            out[v][leaf] = z
    return out


# --------------------------------------------------------------------------
# the mathematics
# --------------------------------------------------------------------------

def _identity(x):
    return x


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _rope(x, theta):
    """``x: [T, heads, d]`` at positions ``0..T-1``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decay(cfg: dict, published_layer: int, fault=None):
    """``lambda_h = exp(-s_h)``, ``s_h = 2^(-8h/H) (1 - l/31 + 1e-5)`` for
    head ``h = 1..H`` of published layer ``l``."""
    h = jnp.arange(1, cfg["lightning_nh"] + 1, dtype=jnp.float32)
    s = 2.0 ** (-8.0 * h / cfg["lightning_nh"]) * (
        1.0 - published_layer / (PUBLISHED_LAYERS - 1) + 1e-5)
    return jnp.ones_like(s) if fault == "no_decay" else jnp.exp(-s)


def lightning_mixer(cfg, u, p, published_layer, q=_identity, fault=None):
    d = dims(cfg)
    t = u.shape[0]
    nh, hd = d["l_heads"], d["l_head"]
    eps, u = cfg["rms_norm_eps"], q(u)

    def proj(name):
        return jnp.dot(u, q(p[name])).reshape(t, nh, hd)

    qh = _rms(proj("Wq"), p["q_norm"], eps)
    kh = _rms(proj("Wk"), p["k_norm"], eps)
    vh = proj("Wv")
    if cfg["lightning_use_rope"]:
        qh, kh = _rope(qh, cfg["rope_theta"]), _rope(kh, cfg["rope_theta"])
    qh = qh / hd ** 0.5
    lam = decay(cfg, published_layer, fault)[:, None, None]

    def step(s, qkv):
        q_t, k_t, v_t = qkv
        s = lam * s + q(k_t)[:, :, None] * q(v_t)[:, None, :]
        return s, jnp.einsum("hd,hde->he", q(q_t), q(s))

    _, o = jax.lax.scan(step, jnp.zeros((nh, hd, hd), jnp.float32),
                        (qh, kh, vh))
    if cfg["use_output_norm"]:
        o = _rms(o, p["o_norm"], eps)
    o = o.reshape(t, nh * hd)
    if cfg["use_output_gate"]:
        o = o * jax.nn.sigmoid(jnp.dot(u, q(p["Wg"])))
    return jnp.dot(q(o), q(p["Wo"]))


def compressed_keys(k, sp):
    """``c_j = mean(k[stride j : stride j + kernel])`` for every window
    inside the sequence: ``k: [T, kv, d]`` -> ``[n_c, kv, d]``."""
    t = k.shape[0]
    n_c = max((t - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)
    idx = (jnp.arange(n_c)[:, None] * sp["kernel_stride"]
           + jnp.arange(sp["kernel_size"])[None, :])
    return k[idx].mean(axis=1)


def chosen_blocks(sp, q_blk, t_blk, ck, n_blocks, hpg, q=_identity,
                  fault=None):
    """For queries ``q_blk: [B, heads, d]`` at positions ``t_blk: [B]``:
    a mask ``[B, kv, n_blocks]`` of the blocks the top-k chose.

    ``p = softmax_j(q . c_j / sqrt(d))`` over the compressed keys whose
    window ends at or before ``t``, summed over the ``hpg`` heads of a KV
    head; a block scores the maximum of ``p`` over the compressed keys
    that overlap it; candidates are the blocks past the initial ones that
    start at or before ``t - window``; the ``topk`` best are chosen (all
    of them where there are fewer)."""
    n_c, kv, d = ck.shape
    b = q_blk.shape[0]
    if n_c == 0 or fault == "no_topk":
        return jnp.zeros((b, kv, n_blocks), bool)
    qg = q_blk.reshape(b, kv, hpg, d)
    s = jnp.einsum("bgmd,jgd->bgmj", q(qg), q(ck)) / d ** 0.5
    ends = jnp.arange(n_c) * sp["kernel_stride"] + sp["kernel_size"] - 1
    valid = ends[None, :] <= t_blk[:, None]                  # [B, n_c]
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, -jnp.inf),
                       axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0).sum(axis=2)   # [B, kv, j]
    first = jnp.arange(n_c) * sp["kernel_stride"]
    lo = jnp.arange(n_blocks) * sp["block_size"]
    overlap = ((first[None, :] <= lo[:, None] + sp["block_size"] - 1)
               & (ends[None, :] >= lo[:, None]))             # [blocks, j]
    seen = overlap[None, :, :] & valid[:, None, :]           # [B, blocks, j]
    score = jnp.max(jnp.where(seen[:, None], p[:, :, None, :], -jnp.inf),
                    axis=-1)                                 # [B, kv, blocks]
    candidate = ((lo[None, :] <= t_blk[:, None] - sp["window_size"])
                 & (jnp.arange(n_blocks) >= sp["init_blocks"])[None, :])
    score = jnp.where(candidate[:, None, :], score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(sp["topk"], n_blocks))
    hit = (idx[..., None] == jnp.arange(n_blocks)) & (vals > -jnp.inf)[..., None]
    return hit.any(axis=-2)


def sparse_mixer(cfg, u, p, q=_identity, fault=None):
    d = dims(cfg)
    sp = cfg["sparse_config"]
    t = u.shape[0]
    nh, kv, hd = d["heads"], d["kv_heads"], d["head"]
    hpg = nh // kv
    eps, u = cfg["rms_norm_eps"], q(u)
    qh = jnp.dot(u, q(p["Wq"])).reshape(t, nh, hd)
    kh = jnp.dot(u, q(p["Wk"])).reshape(t, kv, hd)
    vh = jnp.dot(u, q(p["Wv"])).reshape(t, kv, hd)
    if cfg["qk_norm"]:
        qh, kh = _rms(qh, p["q_norm"], eps), _rms(kh, p["k_norm"], eps)
    ck = compressed_keys(kh, sp)
    n_blocks = -(-t // sp["block_size"])
    pos = jnp.arange(t)
    dense = fault == "dense_attention"

    def block(args):
        q_blk, t_blk = args                      # [B, heads, d], [B]
        chosen = chosen_blocks(sp, q_blk, t_blk, ck, n_blocks, hpg, q, fault)
        chosen = jnp.repeat(chosen, sp["block_size"], axis=-1)[..., :t]
        tq = t_blk[:, None, None]
        allowed = (chosen | (pos > tq - sp["window_size"])
                   | (pos < sp["init_blocks"] * sp["block_size"])
                   | (tq < sp["dense_len"]) | dense) & (pos <= tq)
        s = jnp.einsum("bgmd,ngd->bgmn", q(q_blk.reshape(-1, kv, hpg, hd)),
                       q(kh)) / hd ** 0.5
        w = jax.nn.softmax(jnp.where(allowed[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bgmn,ngd->bgmd", q(w), q(vh)).reshape(-1, nh * hd)

    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qs = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(
        n, QUERY_BLOCK, nh, hd)
    ts = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(n, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, ts)).reshape(n * QUERY_BLOCK, nh * hd)[:t]
    if cfg["attn_use_output_gate"]:
        o = o * jax.nn.sigmoid(jnp.dot(u, q(p["Wg"])))
    return jnp.dot(q(o), q(p["Wo"]))


def feed_forward(cfg, u, p, q=_identity):
    """``Wd (silu(Wg u) * Wu u)`` in slices of ``TOKEN_BLOCK`` tokens."""
    t, e = u.shape
    n = -(-t // TOKEN_BLOCK)
    u = jnp.pad(u, ((0, n * TOKEN_BLOCK - t), (0, 0)))
    wg, wu, wd = q(p["Wg"]), q(p["Wu"]), q(p["Wd"])

    def ff(x):
        x = q(x)
        return jnp.dot(q(jax.nn.silu(jnp.dot(x, wg)) * jnp.dot(x, wu)), wd)

    return jax.lax.map(ff, u.reshape(n, TOKEN_BLOCK, e)).reshape(-1, e)[:t]


def layer(cfg, x, kind, published_layer, norm1, mix, norm2, ffn,
          q=_identity, fault=None):
    """One layer over ``x: [T, hidden]``. ``q`` rounds what the control
    rounds: both operands of every matrix product; the residual stream,
    norms, softmax, rotation and decay stay float32."""
    c = dims(cfg)["c"]
    eps = cfg["rms_norm_eps"]
    u = _rms(x, norm1["gain"], eps)
    if kind == "lightning-attn":
        m = lightning_mixer(cfg, u, mix, published_layer, q, fault)
    else:
        m = sparse_mixer(cfg, u, mix, q, fault)
    h = x + c * m
    return h + c * feed_forward(cfg, _rms(h, norm2["gain"], eps), ffn, q)


def lower_precision(name: str):
    """The rounding of the control: the operands of every matrix product
    in the precision below the configuration's (``common.round_to``)."""
    return lambda x: round_to(x, name)


class Forward:
    """Logits ``[len(rows), vocab]`` (float32) of the positions ``rows``
    of one sequence ``tokens: [T]``: a jitted program per layer kind
    (reused by every layer of the kind), the embedding and the head.
    ``q`` as in :func:`layer`; ``fault`` plants one of ``no_decay``,
    ``no_topk``, ``dense_attention`` (``benchmarks/tests``: the reference
    with a mechanism broken, put in the program's place, must come out
    not correct)."""

    def __init__(self, cfg: dict, q=_identity, fault=None):
        self.cfg = cfg
        d = dims(cfg)

        def f32(t):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), t)

        self._embed = jax.jit(lambda tok, w: (
            cfg["scale_emb"] * w[tok].astype(jnp.float32)))
        self._layer = {
            kind: jax.jit(lambda x, index, n1, mix, n2, ffn, kind=kind: layer(
                cfg, x, kind, index, f32(n1), f32(mix), f32(n2), f32(ffn),
                q, fault))
            for kind in set(d["kinds"])}
        self._head = jax.jit(lambda h, rows, norm, out: (
            jnp.dot(q(_rms(h[rows], norm["gain"].astype(jnp.float32),
                           cfg["rms_norm_eps"])),
                    q(out["W"].astype(jnp.float32))) * d["logit_scale"]))

    def __call__(self, w: dict, tokens, rows):
        d = dims(self.cfg)
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"])
            for i, (kind, index) in enumerate(zip(d["kinds"], d["indices"])):
                h = self._layer[kind](
                    h, jnp.float32(index), w[f"b{i}_norm1"], w[f"b{i}_mix"],
                    w[f"b{i}_norm2"], w[f"b{i}_ffn"])
            return self._head(h, jnp.asarray(rows, jnp.int32),
                              w["final_norm"], w["output"])
