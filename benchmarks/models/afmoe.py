"""Builds the program's Trinity-Mini (``afmoe``) decoder and engine from
the configuration.

The one place that touches the program's constructors for this
configuration: ``zoo.graphs.HybridDecoderLM`` (mixers ``window-attn`` /
``full-attn``, ``ffn_types``, ``post_norms``) -> ``ComputationGraph`` ->
``TransformerDecoder`` -> ``GenerationEngine``. The weights are the
benchmark's own (``reference.afmoe.init_weights``); the graph's
``init()`` is not run: the tree it would build is read with
``jax.eval_shape`` and a mismatch is an error. The decoder shares the
very arrays the reference later reads.

The program's layers are imported when THIS module is: a checkout whose
program lacks them fails here, before a weight is drawn.
"""

from __future__ import annotations

from deeplearning4j_tpu.conf.layers_hybrid import GatedAttentionLayer  # noqa: F401
from deeplearning4j_tpu.conf.layers_moe import RoutedExpertsLayer  # noqa: F401
from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

MIXER = {"sliding_attention": "window-attn", "full_attention": "full-attn"}


def zoo(cfg: dict) -> HybridDecoderLM:
    served = cfg["layers_served"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ValueError("layers_served must name num_hidden_layers layers")
    if (cfg["score_func"] != "sigmoid" or cfg["hidden_act"] != "silu"
            or not cfg["mup_enabled"] or cfg["tie_word_embeddings"]
            or cfg["rope_scaling"] is not None
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1):
        raise ValueError("models/afmoe builds the published switches only")
    s = cfg["serving"]
    first, count = cfg.get("experts_held", (0, cfg["num_experts"]))
    return HybridDecoderLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=[MIXER[cfg["layer_types"][i]] for i in served],
        ffn_types=["moe" if i >= cfg["num_dense_layers"] else "dense"
                   for i in served],
        moe={"n_experts": cfg["num_experts"],
             "n_hidden": cfg["moe_intermediate_size"],
             "top_k": cfg["num_experts_per_tok"],
             "n_shared_hidden": cfg["num_shared_experts"]
             * cfg["moe_intermediate_size"],
             "route_norm": cfg["route_norm"],
             "route_scale": cfg["route_scale"],
             "experts_held": (first, count)},
        post_norms=True, n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        window=cfg["sliding_window"], layer_indices=served,
        n_layers_total=len(cfg["layer_types"]), depth_for_scale=1,
        scale_emb=cfg["hidden_size"] ** 0.5, scale_depth=1.0,
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        max_len=s["max_len"], weight_dtype=cfg["weight_dtype"],
        cache_dtype=cfg["cache_dtype"], seed=0)


def build(cfg: dict, weights: dict):
    """``(decoder, generation_config)`` over ``weights``."""
    import jax

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.generation import GenerationConfig

    from benchmarks.models import require_same_tree

    model = zoo(cfg)
    conf = model.conf()
    want = jax.eval_shape(
        lambda: (lambda n: (n.params, n.state))(ComputationGraph(conf).init()))
    require_same_tree("parameter", weights, want[0])
    if want[1]:
        raise RuntimeError(f"the program's graph holds state: {want[1]}")
    net = ComputationGraph(conf)
    net.params, net.state, net.opt_state = weights, {}, {}
    s = cfg["serving"]
    dec = model.decoder(net, max_batch=s["max_batch"],
                        kv_bucket_min=s["kv_bucket_min"],
                        prompt_bucket_min=s["prompt_bucket_min"],
                        join_bucket_max=s["join_bucket_max"])
    gen = GenerationConfig(max_batch=s["max_batch"],
                           fused_steps=s["fused_steps"],
                           max_queue=s["max_queue"],
                           kv_bucket_min=s["kv_bucket_min"],
                           prompt_bucket_min=s["prompt_bucket_min"],
                           join_bucket_max=s["join_bucket_max"])
    return dec, gen
