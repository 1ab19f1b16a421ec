"""Plain reference of the ``gigachat35-serve`` configuration.

The ``gigachat3_5`` block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program. One full forward pass over one whole sequence, position by
position where the mathematics is a recurrence, no cache, no chunking:

- ``h0 = E[token]``: no scale, no position embedding;
- layer ``l`` with four zero-centred RMS norms (gain ``1 + w``): ``h' = h +
  n2(Mixer_l(n1(h)))``, ``h'' = h' + n4(FFN_l(n3(h')))``;
- ``Mixer_l`` is latent attention where ``l`` is in
  ``full_attention_layers`` and the gated delta rule elsewhere;
- the gated delta rule: ``[q | k | v | z] = W_qkvz u``, ``[a | b] = W_ab
  u``; ``[q | k | v] <- silu(sum_j w_c[j] * x_{t-3+j})`` (zeros before the
  sequence's start); ``q^ = l2norm(q) / sqrt(128)``, ``k^ = l2norm(k)``,
  value head ``h`` reads key head ``h // 2``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; per position ``S <- e^g S``, ``S <-
  S + beta k^ (v - S^T k^)^T``, ``o = S^T q^`` (a ``lax.scan`` over
  positions, ``S: [64, 128, 128]``); ``W_o [n(o_h) * 2 sigmoid(z_h)]``;
- latent attention, EXPANDED: ``c_q = n(W_dq u)``, ``[q_nope | q_rope] =
  W_uq c_q``; ``[c_kv | k_rope] = W_dkv u``, ``c_kv <- n(c_kv)``; ``[k_nope
  | v] = W_ukv c_kv``; ``q_rope``, ``k_rope`` rotated pairwise ``(2i, 2i +
  1)`` at YaRN's frequencies; causal float32 softmax of ``[q_nope | q_rope]
  . [k_nope | k_rope]`` times ``mscale^2 / sqrt(192)``; the output times
  ``sigmoid(W_g u)``; ``W_o``;
- ``FFN`` of the dense layer: ``Wd (silu(min(Wg u, 10)) * clip(Wu u, -10,
  10))``; of the others: ``s = sigmoid(Wr u)`` over the 256 experts, the 8
  largest of ``s + b`` chosen, ``w_e = 2.5 s_e / (sum of the chosen s +
  1e-20)``, ``Shared(u) + sum_e w_e Expert_e(u)``: a LOOP over the experts
  held, each applied to every token with the weight zero where it was not
  chosen;
- ``logits = W_head n_f(h_L)`` over the vocabulary slice.

What the source's ``config.json`` does not state is ``assumed`` in the
configuration's file. ``experts_held`` cuts the loop to the experts one
holder has, as the program's layer is cut. The head is computed ONLY for
the rows asked for.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import round_to, seed_key

QUERY_BLOCK = 128      # queries an attention slice holds
FAULTS = ("rope_half_split", "no_yarn", "beta_one", "no_qk_l2norm",
          "no_delta_state_at_join", "no_conv_state_at_join",
          "no_output_gate", "route_scale_1")


def dims(cfg: dict) -> dict:
    served = list(cfg["layers_served"])
    first, count = cfg["experts_held"]
    full = set(cfg["full_attention_layers"])
    return {"e": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"],
            "hk": cfg["linear_num_key_heads"],
            "hv": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "conv": cfg["linear_conv_kernel_dim"],
            "dense_ffn": cfg["intermediate_size"],
            "expert_ffn": cfg["moe_intermediate_size"],
            "shared_ffn": cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            "experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "held": (int(first), int(count)),
            "latent": [i in full for i in served],
            "moe": [i >= cfg["first_k_dense_replace"] for i in served]}


def delta_channels(d: dict) -> int:
    """The convolved channels of a delta-rule mixer: q, k, v."""
    return 2 * d["hk"] * d["dk"] + d["hv"] * d["dv"]


def weight_shapes(cfg: dict) -> dict:
    """``{vertex: {leaf: shape}}`` under the program's vertex names;
    layer ``i`` of the served slice is ``b{i}_*``."""
    d = dims(cfg)
    e, h = d["e"], d["heads"]
    shapes = {"embed": {"W": (d["vocab"], e)}, "final_norm": {"gain": (e,)},
              "output": {"W": (e, d["vocab"])}}
    for i, (latent, moe) in enumerate(zip(d["latent"], d["moe"])):
        for norm in ("norm1", "mix_norm", "norm2", "ffn_norm"):
            shapes[f"b{i}_{norm}"] = {"gain": (e,)}
        if latent:
            shapes[f"b{i}_mix"] = {
                "W_dq": (e, d["q_rank"]), "q_norm": (d["q_rank"],),
                "W_uq": (d["q_rank"], h * (d["nope"] + d["rope"])),
                "W_dkv": (e, d["kv_rank"] + d["rope"]),
                "kv_norm": (d["kv_rank"],),
                "W_ukv": (d["kv_rank"], h * (d["nope"] + d["v"])),
                "W_o": (h * d["v"], e), "W_g": (e, h * d["v"])}
        else:
            z = d["hv"] * d["dv"]
            c = delta_channels(d)
            shapes[f"b{i}_mix"] = {
                "W_qkvz": (e, c + z), "W_ab": (e, 2 * d["hv"]),
                "conv_w": (d["conv"], c), "A_log": (d["hv"],),
                "dt_bias": (d["hv"],), "o_norm": (d["dv"],), "W_o": (z, e)}
        if moe:
            n, f, s = d["held"][1], d["expert_ffn"], d["shared_ffn"]
            shapes[f"b{i}_ffn"] = {
                "Wr": (e, d["experts"]), "b": (d["experts"],),
                "Wg": (n, e, f), "Wu": (n, e, f), "Wd": (n, f, e),
                "Sg": (e, s), "Su": (e, s), "Sd": (s, e)}
        else:
            f = d["dense_ffn"]
            shapes[f"b{i}_ffn"] = {"Wg": (e, f), "Wu": (e, f), "Wd": (f, e)}
    return shapes


FLOAT32_LEAVES = ("Wr", "b")    # the router: it feeds a discrete choice
BIG = 1 << 28       # float32 bytes above which a leaf is drawn alone


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the device. The leaves of one kind and shape are
    drawn as ONE stacked array and cut (hundreds of draws in one program
    cost the TPU's compiler minutes), a leaf whose stack would
    pass ``BIG`` float32 bytes one vertex at a time. Matrices N(0,
    initializer_range) in ``weight_dtype``, the router's and its bias in
    float32; zero-centred gains N(0, range), the latent attention's inner
    ones ``qk_gain_mean - 1 + N(0, range)`` (the configuration's
    ``assumed`` says why); ``A_log = log(A)``, ``A`` uniform in [1, 16];
    ``dt_bias`` the inverse softplus of steps log-uniform in [1e-3, 1e-1];
    the convolution's taps uniform in +-1/sqrt(4), float32."""
    std = cfg["initializer_range"]
    wd = jnp.dtype(cfg["weight_dtype"])
    d = dims(cfg)

    def draw(key, n, shape, leaf):
        full = (n,) + shape
        if leaf == "conv_w":
            bound = 1.0 / math.sqrt(d["conv"])
            return jax.random.uniform(key, full, jnp.float32, -bound, bound)
        if leaf == "A_log":
            return jnp.log(jax.random.uniform(key, full, jnp.float32,
                                              1.0, 16.0))
        if leaf == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, full, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        mean = (cfg["qk_gain_mean"] - 1.0 if leaf in ("q_norm", "kv_norm")
                else 0.0)
        z = mean + std * jax.random.normal(key, full, jnp.float32)
        return z if len(shape) == 1 or leaf in FLOAT32_LEAVES else z.astype(wd)

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


def _rms(x, w, eps):
    """Zero-centred: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def yarn_frequencies(cfg: dict, fault=None) -> np.ndarray:
    """``rope / 2`` frequencies: YaRN's blend of ``theta^(-2i/d)`` and the
    same over ``factor``, a linear ramp between the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context
    (``no_yarn``: the plain frequencies)."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    r = cfg["rope_scaling"]
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if fault == "no_yarn":
        return base

    def correction(rotations):
        return (dim * math.log(r["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(correction(r["beta_fast"])), 0)
    hi = min(math.ceil(correction(r["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base * (1 - ramp) + base / r["factor"] * ramp


def _rotate(x, freq, fault=None):
    """``x: [T, heads, d]`` at positions ``0..T-1``, neighbouring pairs
    ``(x[2i], x[2i+1])`` (``rope_half_split``: ``(x[i], x[i + d/2])``)."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if fault == "rope_half_split":
        a, b = x[..., :d // 2], x[..., d // 2:]
    else:
        a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent_attention(cfg, u, p, q=_identity, fault=None):
    d = dims(cfg)
    t = u.shape[0]
    h, nope, rope, dv = d["heads"], d["nope"], d["rope"], d["v"]
    eps = cfg["rms_norm_eps"]
    u = q(u)
    cq = _rms(jnp.dot(u, q(p["W_dq"])), p["q_norm"], eps)
    qh = jnp.dot(q(cq), q(p["W_uq"])).reshape(t, h, nope + rope)
    kv = jnp.dot(u, q(p["W_dkv"]))
    ckv = _rms(kv[:, :d["kv_rank"]], p["kv_norm"], eps)
    up = jnp.dot(q(ckv), q(p["W_ukv"])).reshape(t, h, nope + dv)
    freq = yarn_frequencies(cfg, fault)
    q_rope = _rotate(qh[..., nope:], freq, fault)
    k_rope = _rotate(kv[:, None, d["kv_rank"]:], freq, fault)
    qq = jnp.concatenate([qh[..., :nope], q_rope], -1)           # [T, h, 192]
    kk = jnp.concatenate([up[..., :nope],
                          jnp.broadcast_to(k_rope, (t, h, rope))], -1)
    vv = up[..., nope:]
    r = cfg["rope_scaling"]
    m = (1.0 if fault == "no_yarn"
         else 0.1 * r["mscale_all_dim"] * math.log(r["factor"]) + 1.0)
    scale = m * m / math.sqrt(nope + rope)
    pos = jnp.arange(t)

    def block(args):
        q_blk, t_blk = args                      # [B, heads, 192], [B]
        seen = pos <= t_blk[:, None]                         # [B, T]
        s = jnp.einsum("bhd,nhd->bhn", q(q_blk), q(kk)) * scale
        w = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhn,nhd->bhd", q(w), q(vv)).reshape(-1, h * dv)

    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qs = jnp.pad(qq, ((0, pad), (0, 0), (0, 0))).reshape(
        n, QUERY_BLOCK, h, nope + rope)
    ts = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(n, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, ts)).reshape(n * QUERY_BLOCK, h * dv)[:t]
    if fault != "no_output_gate":
        o = o * jax.nn.sigmoid(jnp.dot(u, q(p["W_g"])))
    return jnp.dot(q(o), q(p["W_o"]))


def delta_rule(cfg, u, p, joined, q=_identity, fault=None):
    """``u: [T, hidden]``. ``joined`` is the position of the first token
    the serving path decodes (the prompt's length): the two faults that
    drop a state at the join forget there what the prompt left."""
    d = dims(cfg)
    t = u.shape[0]
    hk, hv, dk, dv, k = d["hk"], d["hv"], d["dk"], d["dv"], d["conv"]
    c = delta_channels(d)
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    u = q(u)
    proj = jnp.dot(u, q(p["W_qkvz"]))
    x, z = proj[:, :c], proj[:, c:]
    ab = jnp.dot(u, q(p["W_ab"]))
    xc = 0.0
    for j in range(k):
        back = k - 1 - j                          # x_{t - back}
        tap = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        if fault == "no_conv_state_at_join":
            tap = jnp.where(((pos >= joined) & (pos - back < joined))[:, None],
                            0.0, tap)
        xc = xc + p["conv_w"][j] * tap
    xc = jax.nn.silu(xc)
    qh = xc[:, :hk * dk].reshape(t, hk, dk)
    kh = xc[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    vh = xc[:, 2 * hk * dk:].reshape(t, hv, dv)
    if fault != "no_qk_l2norm":
        qh, kh = _l2(qh), _l2(kh)
    qh = jnp.repeat(qh / math.sqrt(dk), hv // hk, axis=1)
    kh = jnp.repeat(kh, hv // hk, axis=1)
    beta = (jnp.ones((t, hv), jnp.float32) if fault == "beta_one"
            else jax.nn.sigmoid(ab[:, hv:]))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ab[:, :hv] + p["dt_bias"])

    def position(s, xs):
        q_t, k_t, v_t, g_t, b_t, at = xs
        if fault == "no_delta_state_at_join":
            s = jnp.where(at == joined, 0.0, s)
        s = s * jnp.exp(g_t)[:, None, None]
        read = jnp.sum(s * k_t[:, :, None], axis=1)          # S^T k  [hv, dv]
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(position, jnp.zeros((hv, dk, dv), jnp.float32),
                        (qh, kh, vh, g, beta, pos))
    y = _rms(o, p["o_norm"], eps) * (
        cfg["linear_sigmoid_gate_scale"]
        * jax.nn.sigmoid(z.reshape(t, hv, dv)))
    return jnp.dot(q(y.reshape(t, hv * dv)), q(p["W_o"]))


def gated(x, wg, wu, wd, limit, q=_identity):
    """``Wd (silu(min(Wg x, limit)) * clip(Wu x, -limit, limit))``, ``x``
    already rounded."""
    hidden = (jax.nn.silu(jnp.minimum(jnp.dot(x, q(wg)), limit))
              * jnp.clip(jnp.dot(x, q(wu)), -limit, limit))
    return jnp.dot(q(hidden), q(wd))


def routing(cfg, u, p, q=_identity, fault=None):
    """``[T, experts]``: each token's weight for every expert, zero where
    the expert was not chosen."""
    d = dims(cfg)
    s = jax.nn.sigmoid(jnp.dot(q(u), q(p["Wr"])))
    _, chosen = jax.lax.top_k(s + p["b"], d["top_k"])
    hit = (chosen[..., None] == jnp.arange(d["experts"])).any(axis=-2)
    w = jnp.where(hit, s, 0.0)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * (1.0 if fault == "route_scale_1"
                else cfg["routed_scaling_factor"])


def routed_experts(cfg, u, p, q=_identity, fault=None):
    d = dims(cfg)
    first, count = d["held"]
    limit = float(cfg["swiglu_limit"])
    w = routing(cfg, u, p, q, fault)
    x = q(u)

    def one(y, args):
        wg, wu, wd, we = args        # one expert's matrices, its weights [T]
        f32 = jnp.float32
        return y + we[:, None] * gated(x, wg.astype(f32), wu.astype(f32),
                                       wd.astype(f32), limit, q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["Wg"], p["Wu"], p["Wd"], w[:, first:first + count].T))
    f32 = jnp.float32
    return y + gated(x, p["Sg"].astype(f32), p["Su"].astype(f32),
                     p["Sd"].astype(f32), limit, q)


def layer(cfg, x, latent, moe, n1, mix, n2, n3, ffn, n4, joined, q=_identity,
          fault=None):
    """One layer over ``x: [T, hidden]``. ``q`` rounds what the control
    rounds: both operands of every matrix product; the residual stream,
    norms, gates, softmax, the convolution and the delta rule stay
    float32."""
    eps = cfg["rms_norm_eps"]
    u = _rms(x, n1["gain"], eps)
    m = (latent_attention(cfg, u, mix, q, fault) if latent
         else delta_rule(cfg, u, mix, joined, q, fault))
    h = x + _rms(m, n2["gain"], eps)
    u = _rms(h, n3["gain"], eps)
    if moe:
        f = routed_experts(cfg, u, ffn, q, fault)
    else:
        f32 = jnp.float32
        f = gated(q(u), ffn["Wg"].astype(f32), ffn["Wu"].astype(f32),
                  ffn["Wd"].astype(f32), float(cfg["swiglu_limit"]), q)
    return h + _rms(f, n4["gain"], eps)


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
            kind: jax.jit(lambda x, n1, mix, n2, n3, ffn, n4, joined,
                          kind=kind: layer(
                              cfg, x, kind[0], kind[1], small(n1), small(mix),
                              small(n2), small(n3), small(ffn), small(n4),
                              joined, q, fault))
            for kind in set(zip(d["latent"], d["moe"]))}
        self._head = jax.jit(lambda h, rows, norm, out: jnp.dot(
            q(_rms(h[rows], norm["gain"].astype(jnp.float32),
                   cfg["rms_norm_eps"])),
            q(out["W"].astype(jnp.float32))))

    def __call__(self, w: dict, tokens, rows):
        d = dims(self.cfg)
        rows = jnp.asarray(rows, jnp.int32)
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"])
            for i, kind in enumerate(zip(d["latent"], d["moe"])):
                h = self._layer[kind](
                    h, w[f"b{i}_norm1"], w[f"b{i}_mix"], w[f"b{i}_mix_norm"],
                    w[f"b{i}_norm2"], w[f"b{i}_ffn"], w[f"b{i}_ffn_norm"],
                    rows[0] + 1)
            return self._head(h, rows, w["final_norm"], w["output"])
