"""Builds the program's GigaChat3.5 (``gigachat3_5``) decoder and engine from
the configuration.

The one place that touches the program's constructors for this
configuration: ``zoo.graphs.HybridDecoderLM`` (mixers ``gated-deltanet`` /
``mla``, ``ffn_types``, ``post_norms``, ``zero_centred_norms``,
``swiglu_limit``) -> ``ComputationGraph`` -> ``TransformerDecoder`` ->
``GenerationEngine``. The weights are the benchmark's own
(``reference.gigachat35.init_weights``); the graph's ``init()`` is not run:
the tree it would build is read with ``jax.eval_shape`` and a mismatch is
an error. The decoder shares the very arrays the reference later reads.

The program's layers are imported when THIS module is: a checkout whose
program lacks them fails here, before a weight is drawn.
"""

from __future__ import annotations

from deeplearning4j_tpu.conf.layers_delta import (  # noqa: F401
    GatedDeltaNetLayer,
    LatentAttentionLayer,
)
from deeplearning4j_tpu.conf.layers_moe import RoutedExpertsLayer  # noqa: F401
from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM


def zoo(cfg: dict) -> HybridDecoderLM:
    served = cfg["layers_served"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ValueError("layers_served must name num_hidden_layers layers")
    rope = cfg["rope_scaling"]
    if (cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["attention_bias"] or rope["type"] != "yarn"
            or rope["mscale"] != rope["mscale_all_dim"]
            or not cfg["rope_interleave"] or not cfg["norm_topk_prob"]
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["use_shared_expert_sigmoid"] or not cfg["gated_attention"]
            or cfg["num_nextn_predict_layers"]):
        raise ValueError("models/gigachat35 builds the published switches "
                         "only")
    s = cfg["serving"]
    full = set(cfg["full_attention_layers"])
    first, count = cfg["experts_held"]
    return HybridDecoderLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=["mla" if i in full else "gated-deltanet"
                     for i in served],
        ffn_types=["moe" if i >= cfg["first_k_dense_replace"] else "dense"
                   for i in served],
        moe={"n_experts": cfg["n_routed_experts"],
             "n_hidden": cfg["moe_intermediate_size"],
             "top_k": cfg["num_experts_per_tok"],
             "n_shared_hidden": cfg["n_shared_experts"]
             * cfg["moe_intermediate_size"],
             "route_norm": cfg["norm_topk_prob"],
             "route_scale": cfg["routed_scaling_factor"],
             "experts_held": (first, count)},
        delta={"key_heads": cfg["linear_num_key_heads"],
               "value_heads": cfg["linear_num_value_heads"],
               "key_dim": cfg["linear_key_head_dim"],
               "value_dim": cfg["linear_value_head_dim"],
               "d_conv": cfg["linear_conv_kernel_dim"],
               "gate_scale": float(cfg["linear_sigmoid_gate_scale"])},
        mla={"n_heads": cfg["num_attention_heads"],
             "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
             "nope_dim": cfg["qk_nope_head_dim"],
             "rope_dim": cfg["qk_rope_head_dim"],
             "value_dim": cfg["v_head_dim"],
             "rope_theta": float(cfg["rope_theta"]),
             "yarn_factor": float(rope["factor"]),
             "yarn_original": rope["original_max_position_embeddings"],
             "beta_fast": float(rope["beta_fast"]),
             "beta_slow": float(rope["beta_slow"]),
             "mscale_all_dim": float(rope["mscale_all_dim"])},
        post_norms=True, zero_centred_norms=True,
        swiglu_limit=float(cfg["swiglu_limit"]),
        n_heads=cfg["num_attention_heads"], head_dim=cfg["v_head_dim"],
        n_kv_heads=cfg["num_key_value_heads"], layer_indices=served,
        n_layers_total=len(served), depth_for_scale=1, scale_emb=1.0,
        scale_depth=1.0, eps=cfg["rms_norm_eps"], max_len=s["max_len"],
        weight_dtype=cfg["weight_dtype"], cache_dtype=cfg["cache_dtype"],
        seed=0)


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
