"""Builds the program's MiniCPM-SALA decoder and engine from the
configuration.

The one place that touches the program's constructors for this
configuration: ``zoo.graphs.HybridDecoderLM`` (``conf`` layers ->
``ComputationGraph``) -> ``TransformerDecoder`` -> ``GenerationEngine``.
The weights are the benchmark's own (``reference.minicpm_sala
.init_weights``); the graph's ``init()`` is not run: the tree it would
build is read with ``jax.eval_shape`` and a mismatch is an error. The
decoder shares the very arrays the reference later reads.

The program's builder is imported when THIS module is: a checkout whose
program lacks it fails here, before a weight is drawn.
"""

from __future__ import annotations

from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM


def zoo(cfg: dict) -> HybridDecoderLM:
    served = cfg["layers_served"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ValueError("layers_served must name num_hidden_layers layers")
    if (cfg["attn_use_rope"] or not cfg["lightning_use_rope"]
            or not cfg["qk_norm"] or not cfg["use_output_gate"]
            or not cfg["use_output_norm"] or not cfg["attn_use_output_gate"]
            or cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu"
            or cfg["lightning_nkv"] != cfg["lightning_nh"]
            or cfg["lightning_scale"] != "1/sqrt(d)"):
        raise ValueError("models/minicpm_sala builds the published switches "
                         "only")
    s = cfg["serving"]
    return HybridDecoderLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=[cfg["mixer_types"][i] for i in served],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        n_kv_heads=cfg["num_key_value_heads"],
        lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        layer_indices=served, n_layers_total=len(cfg["mixer_types"]),
        depth_for_scale=len(cfg["mixer_types"]),
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        dim_model_base=cfg["dim_model_base"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"], sparse=cfg["sparse_config"],
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
