"""Plain reference of the ``gpt2-large-serve`` configuration.

GPT-2's published block in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``: token + learned position
embeddings, pre-LayerNorm blocks ``x + Attn(LN(x))``, ``x + MLP(LN(x))``
with causal full attention and tanh-GELU, a final LayerNorm and the
output head. No cache, no batching, no kernels: one full forward pass
over a whole sequence. It imports nothing of the program; the weights
come from :func:`init_weights` (seeded, one jitted call).

The forward runs layer by layer (one small jitted program per layer
shape, reused by all 36) so that it fits beside nothing else and compiles
in seconds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import round_to, seed_key


def dims(cfg: dict) -> dict:
    e = cfg["n_embd"]
    return {"e": e, "heads": cfg["n_head"], "head": e // cfg["n_head"],
            "ffn": cfg["n_inner"] or 4 * e, "layers": cfg["n_layer"],
            "vocab": cfg["vocab_size"], "positions": cfg["n_positions"]}


def weight_shapes(cfg: dict) -> dict:
    """``{layer: {leaf: shape}}``; block ``i``'s layers are ``b{i}_*``."""
    d = dims(cfg)
    e, f = d["e"], d["ffn"]
    shapes = {"embed": {"W": (d["vocab"], e)},
              "pos": {"P": (d["positions"], e)},
              "final_ln": {"gain": (e,), "b": (e,)},
              "output": {"W": (e, d["vocab"]), "b": (d["vocab"],)}}
    for i in range(d["layers"]):
        shapes[f"b{i}_ln1"] = {"gain": (e,), "b": (e,)}
        shapes[f"b{i}_ln2"] = {"gain": (e,), "b": (e,)}
        shapes[f"b{i}_attn"] = {"Wq": (e, e), "Wk": (e, e), "Wv": (e, e),
                                "Wo": (e, e), "bq": (e,), "bk": (e,),
                                "bv": (e,), "bo": (e,)}
        shapes[f"b{i}_ff1"] = {"W": (e, f), "b": (f,)}
        shapes[f"b{i}_ff2"] = {"W": (f, e), "b": (e,)}
    return shapes


def parameter_count(cfg: dict) -> int:
    total = 0
    for leaves in weight_shapes(cfg).values():
        for shape in leaves.values():
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def _make_fn(cfg: dict):
    """``key -> weights``. The 36 blocks' leaves of one kind are drawn as
    ONE stacked array and cut into the blocks' own arrays: 20 random
    draws in the program, not 580 (which took the TPU's compiler 190 to
    213 s in every run, my chip runs, PR 24)."""
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]
    resid = (2.0 * cfg["n_layer"]) ** -0.5
    dtype = jnp.dtype(cfg["weight_dtype"])
    n = cfg["n_layer"]

    def draw(key, kind, leaf, shape):
        z = std * jax.random.normal(key, shape, jnp.float32)
        if leaf == "gain":
            z = 1.0 + z
        elif leaf == "Wo" or (kind == "ff2" and leaf == "W"):
            z = z * resid
        return z.astype(dtype)

    def make(key):
        out = {}
        i = 0
        for layer in ("embed", "pos", "final_ln", "output"):
            out[layer] = {}
            for leaf, shape in sorted(shapes[layer].items()):
                out[layer][leaf] = draw(jax.random.fold_in(key, i), layer,
                                        leaf, shape)
                i += 1
        for kind in ("ln1", "ln2", "attn", "ff1", "ff2"):
            for b in range(n):
                out[f"b{b}_{kind}"] = {}
            for leaf, shape in sorted(shapes[f"b0_{kind}"].items()):
                stacked = draw(jax.random.fold_in(key, i), kind, leaf,
                               (n,) + tuple(shape))
                i += 1
                for b in range(n):
                    out[f"b{b}_{kind}"][leaf] = stacked[b]
        return out

    return make


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights in ``cfg["weight_dtype"]``, on the device, one
    jitted call: N(0, initializer_range) everywhere, the two residual
    projections of each block scaled by 1/sqrt(2 n_layer), LayerNorm gain
    1 + N(0, range)."""
    return jax.jit(_make_fn(cfg))(seed_key(seed))


def _identity(x):
    return x


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["gain"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def block(cfg: dict, h, ln1, attn, ln2, ff1, ff2, q=_identity):
    """One pre-LN block over ``h: [T, E]``. ``q`` rounds what the control
    rounds: both operands of every matrix product (identity for the
    reference). The residual stream, LayerNorm, softmax and GELU stay
    float32, as they would beside a low-precision matrix unit."""
    d = dims(cfg)
    eps = cfg["layer_norm_epsilon"]
    t = h.shape[0]
    a = q(_layer_norm(h, ln1, eps))

    def proj(w, b):
        return (jnp.dot(a, q(attn[w])) + attn[b]).reshape(
            t, d["heads"], d["head"])

    qh, kh, vh = proj("Wq", "bq"), proj("Wk", "bk"), proj("Wv", "bv")
    s = jnp.einsum("thd,shd->hts", q(qh), q(kh)) * d["head"] ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", q(p), q(vh)).reshape(t, d["e"])
    h = h + jnp.dot(q(o), q(attn["Wo"])) + attn["bo"]
    f = q(_layer_norm(h, ln2, eps))
    f = _gelu_new(jnp.dot(f, q(ff1["W"])) + ff1["b"])
    return h + jnp.dot(q(f), q(ff2["W"])) + ff2["b"]


def lower_precision(name: str):
    """The rounding of the control: the operands of every matrix product
    in the precision below the one the configuration computes them in
    (``common.round_to``: by ``lax.reduce_precision``, the 8-bit type
    scaled per tensor)."""
    return lambda x: round_to(x, name)


class Forward:
    """Logits ``[T, vocab]`` (float32) of one sequence ``tokens: [T]``,
    layer by layer: three small jitted programs built once, the block's
    reused by every layer, so that a run's sample of sequences (padded to
    a few lengths) compiles each shape once. ``q`` as in :func:`block`."""

    def __init__(self, cfg: dict, q=_identity):
        self.cfg = cfg
        eps = cfg["layer_norm_epsilon"]

        def f32(t):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), t)

        self._embed = jax.jit(lambda tok, wte, wpe: (
            wte[tok].astype(jnp.float32)
            + wpe[:tok.shape[0]].astype(jnp.float32)))
        self._block = jax.jit(
            lambda h, ln1, attn, ln2, ff1, ff2: block(
                cfg, h, f32(ln1), f32(attn), f32(ln2), f32(ff1), f32(ff2),
                q))
        self._head = jax.jit(lambda h, ln, out: (
            jnp.dot(q(_layer_norm(h, f32(ln), eps)),
                    q(out["W"].astype(jnp.float32)))
            + out["b"].astype(jnp.float32)))

    def __call__(self, w: dict, tokens):
        with jax.default_matmul_precision("highest"):
            h = self._embed(jnp.asarray(tokens, jnp.int32), w["embed"]["W"],
                            w["pos"]["P"])
            for i in range(self.cfg["n_layer"]):
                h = self._block(h, w[f"b{i}_ln1"], w[f"b{i}_attn"],
                                w[f"b{i}_ln2"], w[f"b{i}_ff1"],
                                w[f"b{i}_ff2"])
            return self._head(h, w["final_ln"], w["output"])
