"""Plain reference of the ``resnet50-train`` configuration.

Straightforward ``jax.numpy`` / ``jax.lax`` in float32 under
``jax.default_matmul_precision("highest")``: the forward pass in training
mode (batch statistics), the softmax cross-entropy loss, its gradient by
``jax.grad`` and the Adam update. It imports nothing of the program and
takes nothing the program made: the weights come from :func:`init_weights`
(seeded, one jitted call), the batches from the benchmark's generator.

Layer names are the reference's own, built from the configuration file
(``stem``, ``res<stage><block>_<a|b|c|sc>``). ``benchmarks/models/resnet50.py``
maps them onto the program's parameter tree and refuses a tree that does
not match.

Memory: float32 activations of batch 256 at 224x224 do not fit 16 GB if
all are kept, and BatchNorm's batch statistics forbid splitting the batch
into blocks of rows. Every bottleneck block (and the stem) is therefore a
``jax.checkpoint``: its input is kept, its inside recomputed layer by
layer in the backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.common import round_to, seed_key

DIMNUMS = ("NHWC", "HWIO", "NHWC")


def blocks(cfg: dict):
    """``(name, (f1, f2, f3), stride, project)`` per bottleneck block."""
    out = []
    for si, (f1, f2, f3, reps) in enumerate(cfg["stages"]):
        for ri in range(reps):
            stride = 1 if (si == 0 or ri > 0) else 2
            out.append((f"res{si + 2}{chr(97 + ri)}", (f1, f2, f3), stride,
                        ri == 0))
    return out


def conv_table(cfg: dict):
    """Every convolution as ``(name, kh, kw, cin, cout, stride, h_out)``
    (square images; ``h_out`` is the output height = width). The single
    source of shapes for the weights, the forward pass and the FLOP
    count."""
    stem = cfg["stem"]
    size = cfg["image_size"]
    rows = []
    if stem["space_to_depth"]:
        # space-to-depth(2) + pad(1, 2) + 4x4/1 VALID over 4x the channels
        h = size // 2
        rows.append(("stem", 4, 4, 4 * cfg["channels"], stem["filters"], 1,
                     h))
    else:
        h = -(-size // stem["stride"])
        rows.append(("stem", stem["kernel"], stem["kernel"],
                     cfg["channels"], stem["filters"], stem["stride"], h))
    h = -(-h // stem["pool"]["stride"])
    cin = stem["filters"]
    for name, (f1, f2, f3), stride, project in blocks(cfg):
        h_out = -(-h // stride)
        rows.append((f"{name}_a", 1, 1, cin, f1, stride, h_out))
        rows.append((f"{name}_b", 3, 3, f1, f2, 1, h_out))
        rows.append((f"{name}_c", 1, 1, f2, f3, 1, h_out))
        if project:
            rows.append((f"{name}_sc", 1, 1, cin, f3, stride, h_out))
        cin, h = f3, h_out
    return rows


def weight_shapes(cfg: dict) -> dict:
    """``{layer: {leaf: shape}}`` of every trained parameter."""
    shapes = {}
    for name, kh, kw, cin, cout, _s, _h in conv_table(cfg):
        shapes[f"{name}_conv"] = {"W": (kh, kw, cin, cout)}
        shapes[f"{name}_bn"] = {"gamma": (cout,), "beta": (cout,)}
    feat = cfg["stages"][-1][2]
    shapes["output"] = {"W": (feat, cfg["num_classes"]),
                        "b": (cfg["num_classes"],)}
    return shapes


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded float32 weights, made on the device in one jitted call:
    He-normal convolutions and head, BatchNorm gain 1 + N(0, 0.1) and
    shift N(0, 0.1) (not 1 and 0, so that a fault in either shows),
    head bias N(0, 0.01)."""
    shapes = weight_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (layer, leaves) in enumerate(sorted(shapes.items())):
            out[layer] = {}
            for j, (leaf, shape) in enumerate(sorted(leaves.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                z = jax.random.normal(k, shape, jnp.float32)
                if leaf == "W":
                    fan_in = 1
                    for d in shape[:-1]:
                        fan_in *= d
                    z = z * (2.0 / fan_in) ** 0.5
                elif leaf == "gamma":
                    z = 1.0 + 0.1 * z
                elif leaf == "beta":
                    z = 0.1 * z
                else:
                    z = 0.01 * z
                out[layer][leaf] = z
        return out

    return make(seed_key(seed))


def init_bn_state(cfg: dict) -> dict:
    return {f"{name}_bn": {"mean": jnp.zeros((cout,), jnp.float32),
                           "var": jnp.ones((cout,), jnp.float32)}
            for name, _kh, _kw, _cin, cout, _s, _h in conv_table(cfg)}


def _identity(x):
    return x


def _conv(x, w, stride, padding, q):
    return lax.conv_general_dilated(
        q(x), q(w), window_strides=(stride, stride), padding=padding,
        dimension_numbers=DIMNUMS)


def _bn(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta, mean, var


def _conv_bn(w, name, x, stride, padding, eps, relu, q, stats):
    y = _conv(x, w[f"{name}_conv"]["W"], stride, padding, q)
    y, mean, var = _bn(y, w[f"{name}_bn"]["gamma"], w[f"{name}_bn"]["beta"],
                       eps)
    stats[f"{name}_bn"] = (mean, var)
    return jnp.maximum(y, 0.0) if relu else y


def _space_to_depth2(x):
    b, h, w, c = x.shape
    y = x.reshape(b, h // 2, 2, w // 2, 2, c)
    return y.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)


def forward(cfg: dict, w: dict, images_u8, q=_identity):
    """Logits ``[batch, classes]`` and the batch statistics of every
    BatchNorm, training mode. ``q`` rounds the operands of every
    convolution and of the head's matmul (identity for the reference;
    the control passes a lower precision)."""
    eps = cfg["batch_norm"]["eps"]
    stats = {}

    def stem(w_stem, u8):
        x = u8.astype(jnp.float32) * (1.0 / 255.0)
        local = {}
        if cfg["stem"]["space_to_depth"]:
            x = jnp.pad(_space_to_depth2(x), ((0, 0), (1, 2), (1, 2), (0, 0)))
            y = _conv_bn(w_stem, "stem", x, 1, "VALID", eps, True, q, local)
        else:
            y = _conv_bn(w_stem, "stem", x, cfg["stem"]["stride"], "SAME",
                         eps, True, q, local)
        k, s = cfg["stem"]["pool"]["kernel"], cfg["stem"]["pool"]["stride"]
        y = lax.reduce_window(y, -jnp.inf, lax.max, (1, k, k, 1),
                              (1, s, s, 1), "SAME")
        return y, local

    def bottleneck(name, stride, project, w_block, x):
        local = {}
        y = _conv_bn(w_block, f"{name}_a", x, stride, "SAME", eps, True, q,
                     local)
        y = _conv_bn(w_block, f"{name}_b", y, 1, "SAME", eps, True, q, local)
        y = _conv_bn(w_block, f"{name}_c", y, 1, "SAME", eps, False, q,
                     local)
        sc = (_conv_bn(w_block, f"{name}_sc", x, stride, "SAME", eps, False,
                       q, local) if project else x)
        return jnp.maximum(y + sc, 0.0), local

    def part(prefix):
        return {k: v for k, v in w.items() if k.startswith(prefix + "_")}

    x, local = jax.checkpoint(stem)(part("stem"), images_u8)
    stats.update(local)
    for name, _f, stride, project in blocks(cfg):
        fn = functools.partial(bottleneck, name, stride, project)
        x, local = jax.checkpoint(fn)(part(name), x)
        stats.update(local)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(q(x), q(w["output"]["W"])) + w["output"]["b"]
    return logits, stats


def loss_fn(cfg: dict, w: dict, images_u8, labels, q=_identity):
    """Mean over the batch of the softmax cross entropy."""
    logits, stats = forward(cfg, w, images_u8, q)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.sum(labels * logp, axis=-1)), stats


def adam_update(opt_cfg: dict, w, grads, m, v, t):
    """One Adam step; ``t`` counts from 1."""
    b1, b2 = opt_cfg["beta1"], opt_cfg["beta2"]
    lr, eps = opt_cfg["learning_rate"], opt_cfg["epsilon"]
    alpha = lr * (1.0 - b2 ** t) ** 0.5 / (1.0 - b1 ** t)
    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: b1 * a + (1.0 - b1) * g, m, grads)
    v = tm(lambda a, g: b2 * a + (1.0 - b2) * g * g, v, grads)
    w = tm(lambda p, a, b: p - alpha * a / (jnp.sqrt(b) + eps), w, m, v)
    return w, m, v


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, as one flat ``{"layer/leaf": norm}``."""
    return {f"{layer}/{leaf}": jnp.sqrt(jnp.sum(jnp.square(x)))
            for layer, leaves in sorted(tree.items())
            for leaf, x in sorted(leaves.items())}


def follow_steps(cfg: dict, weights: dict, batches, q=_identity,
                 rows=None):
    """Train ``len(batches)`` steps from ``weights`` and return what the
    comparison reads: the loss of every step, the leaf norms of the first
    gradient, the leaf norms of the parameters' change over all the
    steps, and BatchNorm's running statistics after the FIRST step
    (``running = decay * running + (1 - decay) * batch``, from mean 0 and
    variance 1): a pure forward pass of the seeded weights. After it the
    paths part for good, since Adam's first update is the sign of the
    gradient. ``rows`` keeps only the first ``rows`` rows of every batch (the
    planted fault "half of the batch left out").

    One jitted program per step (compiled once, in the compile cache)."""
    opt_cfg = cfg["optimizer"]

    decay = cfg["batch_norm"]["decay"]

    @jax.jit
    def step(w, m, v, bn, images, labels, t):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, images, labels, q), has_aux=True)(w)
        w2, m2, v2 = adam_update(opt_cfg, w, grads, m, v, t)
        bn2 = {k: {"mean": decay * bn[k]["mean"] + (1 - decay) * mean,
                   "var": decay * bn[k]["var"] + (1 - decay) * var}
               for k, (mean, var) in stats.items()}
        return w2, m2, v2, bn2, loss, leaf_norms(grads)

    @jax.jit
    def change(w_new, w_old):
        return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, w_new,
                                                 w_old))

    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    w, m, v, bn = weights, zeros, zeros, init_bn_state(cfg)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for i, (images, labels) in enumerate(batches):
            if rows is not None:
                images, labels = images[:rows], labels[:rows]
            w, m, v, bn, loss, gnorms = step(w, m, v, bn, images, labels,
                                             jnp.float32(i + 1))
            losses.append(loss)
            if i == 0:
                first_grad, first_bn = gnorms, bn
        delta = change(w, weights)
    return {"loss": [float(x) for x in losses],
            "grad_norm": {k: float(x) for k, x in first_grad.items()},
            "change_norm": {k: float(x) for k, x in delta.items()},
            "bn_state": flat_arrays(first_bn)}


def flat_arrays(tree) -> dict:
    """``{"layer/leaf": numpy array}`` of a two-level tree."""
    import numpy as np

    return {f"{layer}/{leaf}": np.asarray(x, np.float64)
            for layer, leaves in sorted(tree.items())
            for leaf, x in sorted(leaves.items())}


def lower_precision(name: str):
    """The operand rounding of the control: every operand of a matrix
    product rounded to the nearest precision below the configuration's
    ``compute_dtype``. Straight-through: the backward pass sees the
    rounded operands but its cotangents stay float32 (rounding them too
    flushes these small gradients to nought in float8, a control that
    fails for a reason no one would ship)."""
    return lambda x: x + lax.stop_gradient(round_to(x, name) - x)
