"""Plain reference of the ``jamba2-3b-serve`` configuration.

The ``jamba`` block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program. One full forward pass over one whole sequence, position by
position where the mathematics is a recurrence, no cache, no chunking:

- ``h0 = E[token]``: no scale, no position embedding anywhere;
- layer ``l`` with two RMS norms: ``h' = h + Mixer_l(n1(h))``, ``h'' = h' +
  FFN(n2(h'))``; ``Mixer_l`` is attention where ``l mod attn_layer_period
  = attn_layer_offset`` (layers 7 and 21) and Mamba elsewhere; every
  ``FFN`` is the dense ``Wd (silu(Wg u) * Wu u)`` (``num_experts`` 1);
- attention: 20 query heads over ONE KV head of 128, no biases, no
  rotation, no q/k norm, no gate; query ``t`` sees keys ``j <= t``;
  float32 softmax of ``q.k / sqrt(128)``; ``Wo``;
- Mamba (``d = 5120``, ``n = 16``, ``r = 160``): ``[x ; z] = W_in u``;
  ``x~_t = silu(b_c + sum_j w_c[j] * x_{t-3+j})`` (zeros before the
  sequence's start); ``[delta ; B ; C] = W_x x~_t``, each RMS-normed with
  a gain; ``dt_t = softplus(W_dt delta + b_dt)``; ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) * h_{t-1} + (dt_t * x~_t) B_t`` (a ``lax.scan`` over
  positions, ``h: [n, d]``); ``y_t = h_t C_t + D * x~_t``; ``W_out (y_t *
  silu(z_t))``;
- ``logits = E n_f(h_L)``: the head IS the embedding matrix.

What the source's ``config.json`` does not state is ``assumed`` in the
configuration's file. The head is computed ONLY for the rows asked for.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import round_to, seed_key

QUERY_BLOCK = 128      # queries an attention slice holds
FAULTS = ("no_scan_state_at_join", "no_conv_state_at_join", "no_inner_norms",
          "no_skip", "no_gate", "no_dt_bias", "rotate_attn", "attn_at_0_14")


def dims(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    if cfg["num_experts"] != 1 or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"] or not cfg["tie_word_embeddings"]:
        raise ValueError("reference/jamba computes the published switches "
                         "only")
    return {"e": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head": cfg["hidden_size"] // cfg["num_attention_heads"],
            "ffn": cfg["intermediate_size"],
            "d": cfg["mamba_expand"] * cfg["hidden_size"],
            "n": cfg["mamba_d_state"], "k": cfg["mamba_d_conv"],
            "r": cfg["mamba_dt_rank"],
            "attn": [i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
                     for i in range(layers)]}


def weight_shapes(cfg: dict) -> dict:
    """``{vertex: {leaf: shape}}`` under the program's vertex names."""
    s = dims(cfg)
    e, d, n, r, f = s["e"], s["d"], s["n"], s["r"], s["ffn"]
    w, kv = s["heads"] * s["head"], s["kv_heads"] * s["head"]
    shapes = {"embed": {"W": (s["vocab"], e)}, "final_norm": {"gain": (e,)}}
    for i, attn in enumerate(s["attn"]):
        shapes[f"b{i}_norm1"] = {"gain": (e,)}
        shapes[f"b{i}_norm2"] = {"gain": (e,)}
        shapes[f"b{i}_ffn"] = {"Wg": (e, f), "Wu": (e, f), "Wd": (f, e)}
        shapes[f"b{i}_mix"] = (
            {"Wq": (e, w), "Wk": (e, kv), "Wv": (e, kv), "Wo": (w, e)}
            if attn else
            {"W_in": (e, 2 * d), "conv_w": (s["k"], d), "conv_b": (d,),
             "W_x": (d, r + 2 * n), "dt_norm": (r,), "b_norm": (n,),
             "c_norm": (n,), "W_dt": (r, d), "b_dt": (d,), "A_log": (n, d),
             "D": (d,), "W_out": (d, e)})
    return shapes


GAINS = ("gain", "dt_norm", "b_norm", "c_norm")


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the device; the leaves of one kind and shape are
    drawn as ONE stacked array and cut (PR 24: hundreds of draws in a
    program cost the TPU's compiler minutes). Matrices N(0,
    initializer_range) in ``weight_dtype`` (``Wq`` and ``Wk`` times
    ``qk_init_scale``); gains ``1 + N(0, range)`` float32; what decides
    how long the state remembers as the published initialisation has it:
    ``A_log = log(1..n)`` a channel, ``D = 1``, ``b_dt`` the inverse
    softplus of steps log-uniform in [1e-3, 1e-1]; the convolution's taps
    and bias uniform in +-1/sqrt(d_conv) (the configuration's ``assumed``
    says why), float32."""
    std = cfg["initializer_range"]
    wd = jnp.dtype(cfg["weight_dtype"])
    s = dims(cfg)

    def draw(key, n, shape, leaf):
        full = (n,) + shape
        if leaf in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(s["k"])
            z = jax.random.uniform(key, full, jnp.float32, -bound, bound)
        elif leaf == "b_dt":
            step = jnp.exp(jax.random.uniform(
                key, full, jnp.float32, math.log(1e-3), math.log(1e-1)))
            z = step + jnp.log(-jnp.expm1(-step))
        elif leaf == "A_log":
            z = jnp.broadcast_to(jnp.log(jnp.arange(
                1, s["n"] + 1, dtype=jnp.float32))[:, None], full)
        elif leaf == "D":
            z = jnp.ones(full, jnp.float32)
        else:
            scale = cfg["qk_init_scale"] if leaf in ("Wq", "Wk") else 1.0
            z = (leaf in GAINS) + scale * std * jax.random.normal(
                key, full, jnp.float32)
            if len(shape) == 2:
                z = z.astype(wd)
        return tuple(z[j] for j in range(n))

    draw = jax.jit(draw, static_argnums=(1, 2, 3))
    groups = {}
    for vertex, leaves in sorted(weight_shapes(cfg).items()):
        kind = vertex.split("_", 1)[-1] if vertex[0] == "b" else vertex
        for leaf, shape in sorted(leaves.items()):
            groups.setdefault((kind, leaf, shape), []).append(vertex)
    key = seed_key(seed)
    out = {v: {} for v in weight_shapes(cfg)}
    for i, ((_, leaf, shape), vertices) in enumerate(sorted(groups.items())):
        for v, z in zip(vertices, draw(jax.random.fold_in(key, i),
                                       len(vertices), shape, leaf)):
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
    """``x: [T, heads, d]`` at positions ``0..T-1`` (half-split pairs)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, u, p, q=_identity, fault=None):
    s = dims(cfg)
    t = u.shape[0]
    nh, kv, hd = s["heads"], s["kv_heads"], s["head"]
    hpg = nh // kv
    u = q(u)
    qh = jnp.dot(u, q(p["Wq"])).reshape(t, nh, hd)
    kh = jnp.dot(u, q(p["Wk"])).reshape(t, kv, hd)
    vh = jnp.dot(u, q(p["Wv"])).reshape(t, kv, hd)
    if fault == "rotate_attn":
        qh, kh = _rope(qh, 10000.0), _rope(kh, 10000.0)
    pos = jnp.arange(t)

    def block(args):
        q_blk, t_blk = args                      # [B, heads, d], [B]
        seen = pos <= t_blk[:, None]                         # [B, T]
        sc = jnp.einsum("bgmd,ngd->bgmn", q(q_blk.reshape(-1, kv, hpg, hd)),
                        q(kh)) / hd ** 0.5
        w = jax.nn.softmax(jnp.where(seen[:, None, None, :], sc, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bgmn,ngd->bgmd", q(w), q(vh)).reshape(-1, nh * hd)

    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qs = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(
        n, QUERY_BLOCK, nh, hd)
    ts = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(n, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, ts)).reshape(n * QUERY_BLOCK, nh * hd)[:t]
    return jnp.dot(q(o), q(p["Wo"]))


def mamba(cfg, u, p, joined, q=_identity, fault=None):
    """``u: [T, hidden]``. ``joined`` is the position of the first token
    the serving path decodes (the prompt's length): the two faults that
    drop a state at the join forget there what the prompt left."""
    s = dims(cfg)
    t = u.shape[0]
    d, n, r, k, eps = s["d"], s["n"], s["r"], s["k"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    xz = jnp.dot(q(u), q(p["W_in"]))
    x, z = xz[:, :d], xz[:, d:]
    xc = p["conv_b"]
    for j in range(k):
        back = k - 1 - j                          # x_{t - back}
        tap = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        if fault == "no_conv_state_at_join":
            tap = jnp.where(((pos >= joined) & (pos - back < joined))[:, None],
                            0.0, tap)
        xc = xc + p["conv_w"][j] * tap
    xc = jax.nn.silu(xc)
    dbc = jnp.dot(q(xc), q(p["W_x"]))
    delta, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if fault != "no_inner_norms":
        delta = _rms(delta, p["dt_norm"], eps)
        b, c = _rms(b, p["b_norm"], eps), _rms(c, p["c_norm"], eps)
    dt = jnp.dot(q(delta), q(p["W_dt"]))
    dt = jax.nn.softplus(dt if fault == "no_dt_bias" else dt + p["b_dt"])
    a = -jnp.exp(p["A_log"])                      # [n, d]

    def position(h, xs):
        x_t, dt_t, b_t, c_t, at = xs
        if fault == "no_scan_state_at_join":
            h = jnp.where(at == joined, 0.0, h)
        h = jnp.exp(dt_t * a) * h + (dt_t * x_t) * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(position, jnp.zeros((n, d), jnp.float32),
                        (xc, dt, b, c, pos))
    if fault != "no_skip":
        y = y + p["D"] * xc
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    return jnp.dot(q(y), q(p["W_out"]))


def layer(cfg, x, attn, n1, mix, n2, ffn, joined, q=_identity, fault=None):
    """One layer over ``x: [T, hidden]``. ``q`` rounds what the control
    rounds: both operands of every matrix product; the residual stream,
    norms, softmax, the convolution and the scan stay float32."""
    eps = cfg["rms_norm_eps"]
    u = _rms(x, n1["gain"], eps)
    h = x + (attention(cfg, u, mix, q, fault) if attn
             else mamba(cfg, u, mix, joined, q, fault))
    u = q(_rms(h, n2["gain"], eps))
    hidden = jax.nn.silu(jnp.dot(u, q(ffn["Wg"]))) * jnp.dot(u, q(ffn["Wu"]))
    return h + jnp.dot(q(hidden), q(ffn["Wd"]))


def lower_precision(name: str):
    """The rounding of the control: the operands of every matrix product
    in the precision below the configuration's (``common.round_to``)."""
    return lambda x: round_to(x, name)


class Forward:
    """Logits ``[len(rows), vocab]`` (float32, on the device) of the
    positions ``rows`` of one sequence ``tokens: [T]``: a jitted program
    per kind of layer (reused by every layer of the kind), the embedding
    and the tied head. ``rows[0]`` is the prompt's last position (the row
    that predicts the first served token): what follows it went through
    the decode steps. ``q`` as in :func:`layer`; ``fault`` plants one of
    :data:`FAULTS` (``benchmarks/tests``: the reference with a mechanism
    broken, put in the program's place, must come out not correct)."""

    def __init__(self, cfg: dict, q=_identity, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.cfg = cfg
        self.kinds = list(dims(cfg)["attn"])
        # attention at layers 0 and 14: the two kinds of mixer change
        # places with their weights, layer 0 with 7 and 14 with 21
        self.mixer_of = list(range(len(self.kinds)))
        if fault == "attn_at_0_14":
            period = cfg["attn_layer_period"]
            for i, attn in enumerate(dims(cfg)["attn"]):
                if attn:
                    j = i - i % period
                    self.kinds[i], self.kinds[j] = False, True
                    self.mixer_of[i], self.mixer_of[j] = j, i

        def f32(tree):
            return {k: v.astype(jnp.float32) for k, v in tree.items()}

        self._embed = jax.jit(lambda tok, w: w[tok].astype(jnp.float32))
        self._layer = {
            attn: jax.jit(lambda x, n1, mix, n2, ffn, joined, attn=attn: layer(
                cfg, x, attn, f32(n1), f32(mix), f32(n2), f32(ffn), joined,
                q, fault))
            for attn in (False, True)}
        self._head = jax.jit(lambda h, rows, norm, table: jnp.dot(
            q(_rms(h[rows], norm["gain"].astype(jnp.float32),
                   cfg["rms_norm_eps"])),
            q(table.astype(jnp.float32)).T))

    def __call__(self, w: dict, tokens, rows):
        rows = jnp.asarray(rows, jnp.int32)
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"])
            for i, attn in enumerate(self.kinds):
                h = self._layer[attn](
                    h, w[f"b{i}_norm1"], w[f"b{self.mixer_of[i]}_mix"],
                    w[f"b{i}_norm2"], w[f"b{i}_ffn"], rows[0] + 1)
            return self._head(h, rows, w["final_norm"], w["embed"]["W"])
