"""Builds the program's ResNet-50 from the configuration file.

The one place that touches the program's constructors for this
configuration (``bench.py``'s and ``chip_smoke.py``'s arguments): the zoo
model, the bf16 compute policy, the space-to-depth stem, Adam. The weights
are the benchmark's own (``reference.resnet50.init_weights``), placed into
the graph in place of ``init()``'s op-by-op initialisation; the tree that
``init()`` would build is read with ``jax.eval_shape`` and a mismatch is an
error, so a program whose parameters moved is refused and not mis-fed.
"""

from __future__ import annotations

import dataclasses


def build(cfg: dict, weights: dict, bn_state: dict):
    """An initialised ``ComputationGraph`` holding copies of ``weights``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    from benchmarks.models import require_same_tree

    opt = cfg["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"models/resnet50 builds Adam, not {opt['name']!r}")
    zoo = ResNet50(num_classes=cfg["num_classes"], height=cfg["image_size"],
                   width=cfg["image_size"], channels=cfg["channels"],
                   updater=Adam(learning_rate=opt["learning_rate"],
                                beta1=opt["beta1"], beta2=opt["beta2"],
                                epsilon=opt["epsilon"]))
    zoo.stem_space_to_depth = bool(cfg["stem"]["space_to_depth"])
    conf = zoo.conf()
    if cfg["compute_dtype"] != cfg["param_dtype"]:
        conf = dataclasses.replace(conf, compute_dtype=cfg["compute_dtype"])
    want = jax.eval_shape(
        lambda: (lambda n: (n.params, n.state))(ComputationGraph(conf).init()))
    require_same_tree("parameter", weights, want[0])
    require_same_tree("BatchNorm state", bn_state, want[1])
    net = ComputationGraph(conf)
    # copies: the step donates its parameters, the benchmark keeps its own
    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    net.params = copy(weights)
    net.state = copy(bn_state)
    net.opt_state = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: {"m": jnp.zeros_like(x), "v": jnp.zeros_like(x)}, t))(
            weights)
    return net
