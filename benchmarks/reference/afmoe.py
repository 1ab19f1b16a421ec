"""Plain reference of the ``trinity-mini-serve`` configuration.

The ``afmoe`` block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program. One full forward pass over one whole sequence, no cache, no
batching, no grouping of tokens by expert:

- ``h0 = E[token] * sqrt(hidden)`` (``mup_enabled``);
- layer ``l`` with four RMS norms: ``h' = h + n2(Attn(n1(h)))``, ``h'' =
  h' + n4(FFN(n3(h')))``;
- ``Attn``: 32 query heads over 4 KV heads of 128, no biases, q and k RMS
  normed over the 128 with a gain; a ``sliding_attention`` layer rotates q
  and k (half-split pairs, theta 10000) and query ``t`` sees keys ``t -
  window < j <= t``; a ``full_attention`` layer does not rotate and sees
  ``j <= t``; float32 softmax of ``q.k / sqrt(128)``; the output times
  ``sigmoid(Wg u)``; ``Wo``;
- ``FFN`` of the leading dense layers: ``Wd (silu(Wg u) * Wu u)``;
- ``FFN`` of the others: ``s = sigmoid(Wr u)`` over the 128 experts, the 8
  largest of ``s + b`` chosen, ``w_e = route_scale * s_e / (sum of the
  chosen s + 1e-20)``, ``Shared(u) + sum_e w_e Expert_e(u)``: a LOOP over
  the experts held, each applied to every token with the weight zero
  where it was not chosen. No capacity, no drop;
- ``logits = W_head n_f(h_L)``.

What the source's ``config.json`` does not state is ``assumed`` in the
configuration's file. ``experts_held`` (``[first, count]``, default all)
cuts the loop to the experts one holder has, as the program's layer is
cut; the shared expert is every holder's.

It runs layer by layer, the attention in blocks of queries, one expert's
matrices upcast at a time, and the head ONLY for the rows asked for, so
that a 9 k-token sequence fits beside the weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import round_to, seed_key

QUERY_BLOCK = 128      # queries an attention slice holds
FAULTS = ("no_shared", "no_route_norm", "top4", "no_window", "rotate_full",
          "no_gate")


def dims(cfg: dict) -> dict:
    served = list(cfg["layers_served"])
    first, count = cfg.get("experts_held", (0, cfg["num_experts"]))
    return {"e": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
            "dense_ffn": cfg["intermediate_size"],
            "expert_ffn": cfg["moe_intermediate_size"],
            "shared_ffn": cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
            "held": (int(first), int(count)),
            "window": cfg["sliding_window"],
            "sliding": [cfg["layer_types"][i] == "sliding_attention"
                        for i in served],
            "moe": [i >= cfg["num_dense_layers"] for i in served]}


def weight_shapes(cfg: dict) -> dict:
    """``{vertex: {leaf: shape}}`` under the program's vertex names;
    layer ``i`` of the served slice is ``b{i}_*``."""
    d = dims(cfg)
    e, hd = d["e"], d["head"]
    w, kv = d["heads"] * hd, d["kv_heads"] * hd
    shapes = {"embed": {"W": (d["vocab"], e)}, "final_norm": {"gain": (e,)},
              "output": {"W": (e, d["vocab"])}}
    for i, moe in enumerate(d["moe"]):
        for norm in ("norm1", "mix_norm", "norm2", "ffn_norm"):
            shapes[f"b{i}_{norm}"] = {"gain": (e,)}
        shapes[f"b{i}_mix"] = {"Wq": (e, w), "Wk": (e, kv), "Wv": (e, kv),
                               "Wg": (e, w), "Wo": (w, e), "q_norm": (hd,),
                               "k_norm": (hd,)}
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


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the device. The leaves of one kind and shape are
    drawn as ONE stacked array and cut (PR 24: hundreds of draws in a
    program cost the TPU's compiler minutes), the experts' stacks one
    layer at a time (a stack of four is 3.2 GB in float32). Matrices
    N(0, initializer_range) in ``weight_dtype``, the router's in float32;
    gains ``1 + N(0, range)`` float32, those of the q and k norms
    ``qk_gain_mean + N(0, range)`` (the configuration's ``assumed`` says
    why); the expert bias N(0, range) float32."""
    std = cfg["initializer_range"]
    wd = jnp.dtype(cfg["weight_dtype"])

    def draw(key, n, shape, mean, dtype):
        z = mean + std * jax.random.normal(key, (n,) + shape, jnp.float32)
        return tuple(z.astype(dtype)[j] for j in range(n))

    draw = jax.jit(draw, static_argnums=(1, 2, 3, 4))
    groups = {}
    for vertex, leaves in sorted(weight_shapes(cfg).items()):
        kind = vertex.split("_", 1)[-1] if vertex[0] == "b" else vertex
        for leaf, shape in sorted(leaves.items()):
            key = (kind, leaf, shape) + ((vertex,) if len(shape) == 3 else ())
            groups.setdefault(key, []).append(vertex)
    key = seed_key(seed)
    out = {v: {} for v in weight_shapes(cfg)}
    for i, (group, vertices) in enumerate(sorted(groups.items())):
        leaf, shape = group[1], group[2]
        mean = (float(cfg["qk_gain_mean"]) if leaf in ("q_norm", "k_norm")
                else 1.0 if leaf == "gain" else 0.0)
        dtype = (jnp.float32 if len(shape) == 1 or leaf in FLOAT32_LEAVES
                 else wd)
        for v, z in zip(vertices, draw(jax.random.fold_in(key, i),
                                       len(vertices), shape, mean, dtype)):
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


def attention(cfg, u, p, sliding: bool, q=_identity, fault=None):
    d = dims(cfg)
    t = u.shape[0]
    nh, kv, hd = d["heads"], d["kv_heads"], d["head"]
    hpg = nh // kv
    eps, u = cfg["rms_norm_eps"], q(u)
    qh = _rms(jnp.dot(u, q(p["Wq"])).reshape(t, nh, hd), p["q_norm"], eps)
    kh = _rms(jnp.dot(u, q(p["Wk"])).reshape(t, kv, hd), p["k_norm"], eps)
    vh = jnp.dot(u, q(p["Wv"])).reshape(t, kv, hd)
    if sliding or fault == "rotate_full":
        qh, kh = _rope(qh, cfg["rope_theta"]), _rope(kh, cfg["rope_theta"])
    window = d["window"] if sliding and fault != "no_window" else t
    pos = jnp.arange(t)

    def block(args):
        q_blk, t_blk = args                      # [B, heads, d], [B]
        tq = t_blk[:, None]
        seen = (pos <= tq) & (pos > tq - window)             # [B, T]
        s = jnp.einsum("bgmd,ngd->bgmn", q(q_blk.reshape(-1, kv, hpg, hd)),
                       q(kh)) / hd ** 0.5
        w = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bgmn,ngd->bgmd", q(w), q(vh)).reshape(-1, nh * hd)

    n = -(-t // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - t
    qs = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(
        n, QUERY_BLOCK, nh, hd)
    ts = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(n, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, ts)).reshape(n * QUERY_BLOCK, nh * hd)[:t]
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(jnp.dot(u, q(p["Wg"])))
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
    k = d["top_k"] // 2 if fault == "top4" else d["top_k"]
    _, chosen = jax.lax.top_k(s + p["b"], k)
    hit = (chosen[..., None] == jnp.arange(d["experts"])).any(axis=-2)
    w = jnp.where(hit, s, 0.0)
    if fault == "no_route_norm":
        return w
    if cfg["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg["route_scale"]


def routed_experts(cfg, u, p, q=_identity, fault=None, route_q=None):
    d = dims(cfg)
    first, count = d["held"]
    w = routing(cfg, u, p, route_q or q, fault)
    x = q(u)

    def one(y, args):
        wg, wu, wd, we = args        # one expert's matrices, its weights [T]
        f32 = jnp.float32
        return y + we[:, None] * gated(x, wg.astype(f32), wu.astype(f32),
                                       wd.astype(f32), q), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["Wg"], p["Wu"], p["Wd"], w[:, first:first + count].T))
    if fault != "no_shared":
        y = y + gated(x, p["Sg"].astype(jnp.float32),
                      p["Su"].astype(jnp.float32),
                      p["Sd"].astype(jnp.float32), q)
    return y


def layer(cfg, x, sliding, moe, n1, mix, n2, n3, ffn, n4, q=_identity,
          fault=None, route_q=None):
    """One layer over ``x: [T, hidden]``. ``q`` rounds what the control
    rounds: both operands of every matrix product; the residual stream,
    norms, softmax and rotation stay float32. ``route_q`` (default: as
    ``q``) rounds the router's operands alone."""
    eps = cfg["rms_norm_eps"]
    h = x + _rms(attention(cfg, _rms(x, n1["gain"], eps), mix, sliding, q,
                           fault), n2["gain"], eps)
    u = _rms(h, n3["gain"], eps)
    if moe:
        f = routed_experts(cfg, u, ffn, q, fault, route_q)
    else:
        f32 = jnp.float32
        f = gated(q(u), ffn["Wg"].astype(f32), ffn["Wu"].astype(f32),
                  ffn["Wd"].astype(f32), q)
    return h + _rms(f, n4["gain"], eps)


def lower_precision(name: str):
    """The rounding of the control: the operands of every matrix product
    in the precision below the configuration's (``common.round_to``)."""
    return lambda x: round_to(x, name)


class Forward:
    """Logits ``[len(rows), vocab]`` (float32, on the device) of the
    positions ``rows`` of one sequence ``tokens: [T]``: a jitted program
    per kind of layer (reused by every layer of the kind), the embedding
    and the head. ``q`` and ``route_q`` as in :func:`layer`; ``fault``
    plants one of :data:`FAULTS` (``benchmarks/tests``: the reference with
    a mechanism broken, put in the program's place, must come out not
    correct)."""

    def __init__(self, cfg: dict, q=_identity, fault=None, route_q=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.cfg = cfg
        d = dims(cfg)

        def small(t):
            """Everything but the experts' stacks in float32."""
            return {k: v if v.ndim == 3 else v.astype(jnp.float32)
                    for k, v in t.items()}

        self._embed = jax.jit(lambda tok, w: (
            cfg["hidden_size"] ** 0.5 * w[tok].astype(jnp.float32)))
        self._layer = {
            kind: jax.jit(lambda x, n1, mix, n2, n3, ffn, n4, kind=kind: layer(
                cfg, x, kind[0], kind[1], small(n1), small(mix), small(n2),
                small(n3), small(ffn), small(n4), q, fault, route_q))
            for kind in set(zip(d["sliding"], d["moe"]))}
        self._head = jax.jit(lambda h, rows, norm, out: jnp.dot(
            q(_rms(h[rows], norm["gain"].astype(jnp.float32),
                   cfg["rms_norm_eps"])),
            q(out["W"].astype(jnp.float32))))

    def __call__(self, w: dict, tokens, rows):
        d = dims(self.cfg)
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"])
            for i, kind in enumerate(zip(d["sliding"], d["moe"])):
                h = self._layer[kind](
                    h, w[f"b{i}_norm1"], w[f"b{i}_mix"], w[f"b{i}_mix_norm"],
                    w[f"b{i}_norm2"], w[f"b{i}_ffn"], w[f"b{i}_ffn_norm"])
            return self._head(h, jnp.asarray(rows, jnp.int32),
                              w["final_norm"], w["output"])
