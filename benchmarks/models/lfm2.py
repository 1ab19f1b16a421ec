"""Builds the program's LFM2-8B-A1B (``lfm2_moe``) decoder and engine from
the configuration.

The one place that touches the program's constructors for this
configuration: ``zoo.graphs.HybridDecoderLM`` (mixers ``short-conv`` /
``rope-attn``, ``ffn_types``, ``tie_head``) -> ``ComputationGraph`` ->
``TransformerDecoder`` -> ``GenerationEngine``. The weights are the
benchmark's own (``reference.lfm2.init_weights``); the graph's ``init()``
is not run: the tree it would build is read with ``jax.eval_shape`` and a
mismatch is an error. The decoder shares the very arrays the reference
later reads.

The program's layers are imported when THIS module is: a checkout whose
program lacks them fails here, before a weight is drawn.
"""

from __future__ import annotations

from deeplearning4j_tpu.conf.layers_hybrid import NormedAttentionLayer  # noqa: F401
from deeplearning4j_tpu.conf.layers_moe import RoutedExpertsLayer  # noqa: F401
from deeplearning4j_tpu.conf.layers_ssm import ShortConvLayer  # noqa: F401
from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM


def zoo(cfg: dict) -> HybridDecoderLM:
    served = cfg["layers_served"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ValueError("layers_served must name num_hidden_layers layers")
    if (cfg["model_type"] != "lfm2_moe" or cfg["conv_bias"]
            or not cfg["tie_word_embeddings"] or not cfg["use_expert_bias"]
            or not cfg["norm_topk_prob"]
            or set(cfg["layer_types"]) != {"conv", "full_attention"}):
        raise ValueError("models/lfm2 builds the published switches only")
    s = cfg["serving"]
    kinds = [cfg["layer_types"][i] for i in served]
    return HybridDecoderLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=["rope-attn" if k == "full_attention" else "short-conv"
                     for k in kinds],
        shortconv={"d_conv": cfg["conv_L_cache"]},
        ffn_types=["dense" if i < cfg["num_dense_layers"] else "moe"
                   for i in served],
        moe={"n_experts": cfg["num_experts"],
             "n_hidden": cfg["moe_intermediate_size"],
             "top_k": cfg["num_experts_per_tok"],
             "route_norm": cfg["norm_topk_prob"],
             "route_scale": float(cfg["routed_scaling_factor"]),
             "route_eps": float(cfg["route_eps"]),
             "experts_held": tuple(cfg["experts_held"])},
        n_heads=cfg["num_attention_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        rope_theta=float(cfg["rope_theta"]), tie_head=True,
        layer_indices=served, n_layers_total=len(served), depth_for_scale=1,
        scale_emb=1.0, scale_depth=1.0, eps=cfg["norm_eps"],
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
                        prompt_bucket_max=s.get("prompt_bucket_max"),
                        join_bucket_max=s["join_bucket_max"])
    gen = GenerationConfig(max_batch=s["max_batch"],
                           fused_steps=s["fused_steps"],
                           max_queue=s["max_queue"],
                           kv_bucket_min=s["kv_bucket_min"],
                           prompt_bucket_min=s["prompt_bucket_min"],
                           join_bucket_max=s["join_bucket_max"])
    return dec, gen
