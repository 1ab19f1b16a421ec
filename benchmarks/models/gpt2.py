"""Builds the program's GPT-2 decoder and engine from the configuration.

The one place that touches the program's constructors for this
configuration (``chip_smoke.py``'s arguments, at GPT-2-large's sizes):
``zoo.graphs.TransformerEncoder(lm_head=True, causal=True)`` ->
``TransformerDecoder`` -> ``GenerationEngine``. The weights are the
benchmark's own (``reference.gpt2.init_weights``); the graph's ``init()``
(838 M parameters made op by op) is not run: the tree it would build is
read with ``jax.eval_shape`` and a mismatch is an error. The decoder
shares the very arrays the reference later reads: nothing on the serving
path donates or writes its parameters.
"""

from __future__ import annotations


def build(cfg: dict, weights: dict):
    """``(decoder, generation_config)`` over ``weights``."""
    import jax

    from deeplearning4j_tpu.nn.decoding import TransformerDecoder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.generation import GenerationConfig
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    from benchmarks.models import require_same_tree

    if cfg["activation_function"] != "gelu_new":
        raise ValueError("models/gpt2 builds the tanh GELU only")
    zoo = TransformerEncoder(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        ffn_dim=cfg["n_inner"] or 4 * cfg["n_embd"],
        max_len=cfg["n_positions"], lm_head=True, causal=True, seed=0)
    conf = zoo.conf()
    want = jax.eval_shape(
        lambda: (lambda n: (n.params, n.state))(ComputationGraph(conf).init()))
    require_same_tree("parameter", weights, want[0])
    if want[1]:
        raise RuntimeError(f"the program's graph holds state: {want[1]}")
    net = ComputationGraph(conf)
    net.params, net.state, net.opt_state = weights, {}, {}
    s = cfg["serving"]
    dec = TransformerDecoder(net, max_batch=s["max_batch"],
                             max_len=cfg["n_positions"],
                             kv_bucket_min=s["kv_bucket_min"],
                             prompt_bucket_min=s["prompt_bucket_min"])
    gen = GenerationConfig(max_batch=s["max_batch"],
                           fused_steps=s["fused_steps"],
                           max_queue=s["max_queue"],
                           kv_bucket_min=s["kv_bucket_min"],
                           prompt_bucket_min=s["prompt_bucket_min"])
    return dec, gen
