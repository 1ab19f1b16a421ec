"""Builds the program's Jamba2-3B (``jamba``) decoder and engine from the
configuration.

The one place that touches the program's constructors for this
configuration: ``zoo.graphs.HybridDecoderLM`` (mixers ``mamba`` /
``plain-attn``, ``tie_head``) -> ``ComputationGraph`` ->
``TransformerDecoder`` -> ``GenerationEngine``. The weights are the
benchmark's own (``reference.jamba.init_weights``); the graph's ``init()``
is not run: the tree it would build is read with ``jax.eval_shape`` and a
mismatch is an error. The decoder shares the very arrays the reference
later reads.

The program's layers are imported when THIS module is: a checkout whose
program lacks them fails here, before a weight is drawn.
"""

from __future__ import annotations

from deeplearning4j_tpu.conf.layers_hybrid import GroupedAttentionLayer  # noqa: F401
from deeplearning4j_tpu.conf.layers_ssm import MambaMixerLayer  # noqa: F401
from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM


def zoo(cfg: dict) -> HybridDecoderLM:
    if (cfg["hidden_act"] != "silu" or cfg["num_experts"] != 1
            or not cfg["tie_word_embeddings"] or not cfg["mamba_conv_bias"]
            or cfg["mamba_proj_bias"] or cfg["sliding_window"] is not None):
        raise ValueError("models/jamba builds the published switches only")
    s = cfg["serving"]
    layers = cfg["num_hidden_layers"]
    return HybridDecoderLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=["plain-attn" if i % cfg["attn_layer_period"]
                     == cfg["attn_layer_offset"] else "mamba"
                     for i in range(layers)],
        mamba={"d_inner": cfg["mamba_expand"] * cfg["hidden_size"],
               "d_state": cfg["mamba_d_state"], "d_conv": cfg["mamba_d_conv"],
               "dt_rank": cfg["mamba_dt_rank"]},
        n_heads=cfg["num_attention_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], tie_head=True,
        depth_for_scale=1, scale_emb=1.0, scale_depth=1.0,
        eps=cfg["rms_norm_eps"], max_len=s["max_len"],
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
