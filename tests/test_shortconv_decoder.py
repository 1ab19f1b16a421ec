"""The short-convolution / attention decoder with routed experts on the CPU
at small sizes, seeded weights: ``conf.layers_ssm.ShortConvLayer`` and
``conf.layers_hybrid.NormedAttentionLayer`` beside
``conf.layers_moe.RoutedExpertsLayer`` -> ``zoo.graphs.HybridDecoderLM`` ->
``ComputationGraph`` -> ``TransformerDecoder`` -> ``GenerationEngine``
against the plain reference (``benchmarks/reference/lfm2.py``, which
imports nothing of the program): logits, not tokens; the ring step against
the span; what a join hands over; the expert bias.
"""

import os
import sys
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.models import lfm2 as model  # noqa: E402
from benchmarks.reference import lfm2 as ref  # noqa: E402
from deeplearning4j_tpu.conf import inputs as _it  # noqa: E402
from deeplearning4j_tpu.conf import layers_ssm  # noqa: E402
from deeplearning4j_tpu.conf.layers_moe import RoutedExpertsLayer  # noqa: E402
from deeplearning4j_tpu.conf.layers_ssm import (  # noqa: E402
    ShortConvLayer,
    conv_ring_step,
    conv_span,
    tail_to_ring,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationConfig,
    GenerationEngine,
)

pytestmark = pytest.mark.decode

VOCAB = 97
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv"]


def _cfg(**over):
    """The published layers 0 to 3: two convolutions over dense
    feed-forwards, then attention and a convolution over 16 routed
    experts (top 4, a selection bias); hidden 64, 4 query heads over 2 KV
    heads of 16."""
    cfg = {"model_type": "lfm2_moe", "hidden_size": 64,
           "intermediate_size": 128, "moe_intermediate_size": 32,
           "vocab_size": VOCAB, "num_attention_heads": 4,
           "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
           "layer_types": LAYER_TYPES, "num_dense_layers": 2,
           "num_experts": 16, "num_experts_per_tok": 4,
           "norm_topk_prob": True, "routed_scaling_factor": 1,
           "use_expert_bias": True, "rope_theta": 1000000, "norm_eps": 1e-5,
           "tie_word_embeddings": True, "layers_served": [0, 1, 2, 3],
           "num_hidden_layers": 4, "experts_held": [0, 16],
           "initializer_range": 0.3, "qk_gain_mean": 1.6,
           "expert_bias_std": 0.1, "route_eps": 1e-6,
           "weight_dtype": "float32", "cache_dtype": "float32",
           "serving": {"max_len": 128}}
    cfg.update(over)
    return cfg


def _net(cfg, seed=7):
    zoo = model.zoo(cfg)
    w = ref.init_weights(cfg, seed)
    net = ComputationGraph(zoo.conf())
    net.params, net.state, net.opt_state = w, {}, {}
    return zoo, net, w


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


# --- the graph's forward against the reference ------------------------------

def test_graph_output_matches_reference_and_the_walk():
    """``ComputationGraph.output``, the reference's full forward and the
    decoder's prompt walk give the same logits."""
    cfg = _cfg()
    zoo, net, w = _net(cfg)
    toks = _tokens(80, 1)
    probs = np.asarray(net.output(toks[None]))[0]
    logits = np.asarray(ref.Forward(cfg)(w, toks, np.arange(80)))
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(np.log(probs), want, atol=3e-4)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16)
    last, _ = jax.jit(dec._run_prompt)(
        net.params, np.pad(toks, (0, 48))[None], np.asarray([80], np.int32))
    np.testing.assert_allclose(np.asarray(last)[0], logits[-1], atol=2e-4,
                               rtol=2e-4)


@pytest.fixture(scope="module")
def sound():
    """The reference's logits after a join at position 40, its weights and
    tokens."""
    cfg = _cfg()
    w = ref.init_weights(cfg, 7)
    toks, rows = _tokens(100, 1), np.arange(39, 100)
    return cfg, w, toks, rows, np.asarray(ref.Forward(cfg)(w, toks, rows))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_faults_matter_at_these_sizes(sound, fault):
    """The sizes above make every mechanism live: the reference with one
    left out gives logits past the decode test's tolerance after the join
    at position 40."""
    cfg, w, toks, rows, logits = sound
    broken = np.asarray(ref.Forward(cfg, fault=fault)(w, toks, rows))
    assert np.abs(broken - logits).max() > 1e-2


def test_reference_refuses_an_unknown_fault():
    with pytest.raises(ValueError, match="unknown fault"):
        ref.Forward(_cfg(), fault="no_such")


# --- prefill, join, decode through a ring and a KV cache --------------------

# shorter than the ring (1, 2), as long as the taps (3), and a prompt of 40
# in a bucket of 64 that the convolution goes through in spans of 16
@pytest.mark.parametrize("prompt_len,span", [(1, 2048), (2, 2048),
                                             (3, 2048), (40, 16)])
def test_prefill_then_decode_matches_reference(prompt_len, span):
    """Teacher-forced: the prompt through ``prompt_fn``'s walk, its block
    joined into row 1 of a dirty state, then 12 given tokens one by one
    through the decode walk (the ring's one-token step, the bounded read);
    every step's LOGITS against the reference's full forward."""
    cfg = _cfg()
    zoo, net, w = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16 if span > 64 else 64)
    toks = _tokens(prompt_len + 12, 2)
    tp = next(b for b in dec.prompt_ladder if b >= prompt_len)
    prompts = np.full((1, tp), 5, np.int32)          # the padding is no zero
    prompts[0, :prompt_len] = toks[:prompt_len]
    lengths = np.asarray([prompt_len], np.int32)
    with mock.patch.object(layers_ssm, "SHORTCONV_TOKEN_SPAN", span):
        logits0, kv = jax.jit(dec._run_prompt)(net.params, prompts, lengths)
    assert kv["b0_mix"]["conv"].shape == (1, 2 * 64)      # no bucket in it
    assert kv["b2_mix"]["k"].shape == (1, tp, 2 * 16)
    state = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 3, a.dtype), dec.new_state(128))
    one = np.ones((1,), np.int32)
    state = dec.join_fn(128, tp, 1)(
        state, kv, np.asarray([1], np.int32), toks[prompt_len:prompt_len + 1]
        .astype(np.int32), lengths, 64 * one, -one,
        np.zeros((1,), np.float32), np.zeros((1, 2), np.uint32),
        np.ones((1,), bool))
    active = np.asarray([False, True, False])
    step = jax.jit(lambda p, t, pos, c: dec._run_token(p, t, pos, c,
                                                       active)[:3])
    got = [np.asarray(logits0)[0]]
    caches = state["caches"]
    for i in range(11):
        t = np.asarray([0, toks[prompt_len + i], 0], np.int32)
        pos = np.asarray([0, prompt_len + i, 0], np.int32)
        logits, caches, counts = step(net.params, t, pos, caches)
        got.append(np.asarray(logits)[1])
    assert int(counts["shortconv_state_updates"][1]) == 3    # three convs
    rows = prompt_len - 1 + np.arange(12)
    want = np.asarray(ref.Forward(cfg)(w, toks[:prompt_len + 11], rows))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_a_reused_row_inherits_no_ring_of_its_last_tenant():
    """Row 0 decodes one request, then a second request is joined into the
    same row: its tokens are the tokens it gives alone in a fresh engine
    (the rings and the KV cache of the first tenant all gone)."""
    cfg = _cfg()
    zoo, net, _ = _net(cfg)

    def engine():
        dec = zoo.decoder(net, max_batch=1, kv_bucket_min=128,
                          prompt_bucket_min=16, join_bucket_max=1)
        return GenerationEngine(dec, GenerationConfig(
            max_batch=1, fused_steps=2, kv_bucket_min=128,
            prompt_bucket_min=16, join_bucket_max=1))

    first, second = _tokens(30, 3), _tokens(9, 4)
    with engine() as eng:
        eng.result(eng.submit(first, max_new_tokens=20))
        reused = eng.result(eng.submit(second, max_new_tokens=12))
    with engine() as eng:
        alone = eng.result(eng.submit(second, max_new_tokens=12))
    assert list(reused) == list(alone)
    assert len(set(alone)) > 2        # the answer is no one repeated token


def test_a_join_writes_the_ring_whole():
    """``cache_join`` writes a row's ring whole, ``cache_release`` zeroes
    it: nothing of the row's last tenant is left, and the other rows keep
    theirs."""
    layer = ShortConvLayer(n_out=8)
    cache = {"conv": jnp.full((3, 16), 7.0)}
    block = {"conv": jnp.arange(16, dtype=jnp.float32)[None]}
    joined = layer.cache_join(cache, block, np.asarray([1]), 64)
    np.testing.assert_array_equal(joined["conv"][1], np.arange(16))
    np.testing.assert_array_equal(joined["conv"][0], np.full(16, 7.0))
    released = layer.cache_release(joined, np.asarray([True, False, True]))
    np.testing.assert_array_equal(released["conv"][1], np.zeros(16))
    np.testing.assert_array_equal(released["conv"][2], np.full(16, 7.0))


def test_state_bytes_by_kind_and_the_prefix_walk_refuses_by_name():
    """The rings do not grow with the bucket, the KV cache does; the
    prefix cache's suffix walk refuses the short convolution by name."""
    zoo, net, _ = _net(_cfg())
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=64,
                      prompt_bucket_min=16)
    small, large = dec.state_bytes(64), dec.state_bytes(128)
    assert set(small) == {"conv_window", "kv"}
    assert small["conv_window"] == large["conv_window"] == 3 * 2 * 2 * 64 * 4
    assert (small["kv"], large["kv"]) == (2 * 2 * 64 * 32 * 4,
                                          2 * 2 * 128 * 32 * 4)
    with pytest.raises(NotImplementedError, match="ShortConvLayer"):
        dec._need("prefill_suffix", "the prefix-cache suffix walk")


# --- the convolution ---------------------------------------------------------

def test_ring_step_without_the_activation_is_one_position_of_the_span():
    """A span of 9 positions after 2 carried inputs, the ring's one-token
    step position by position from the same inputs: the same outputs and,
    at the end, the span's last 2 inputs in the ring; with the activation
    both are ``silu`` of the same (the Mamba and delta-rule layers')."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, t, d = 3, 9, 16
    taps = jax.random.normal(ks[0], (3, d))
    tail = jax.random.normal(ks[1], (b, 2, d))
    x = jax.random.normal(ks[2], (b, t, d))
    mask = np.ones((b, t), np.float32)
    y, last = conv_span(tail, x, taps, mask, silu=False)
    y_silu, _ = conv_span(tail, x, taps, mask)
    np.testing.assert_allclose(y_silu, jax.nn.silu(y), atol=1e-6)
    start = 5                               # the carried inputs' positions
    ring = tail_to_ring(tail, np.full((b,), start))
    for i in range(t):
        pos = np.full((b,), start + i, np.int32)
        out, ring_next = conv_ring_step(ring, x[:, i], taps, pos, silu=False)
        out_silu, _ = conv_ring_step(ring, x[:, i], taps, pos)
        np.testing.assert_allclose(out, y[:, i], atol=1e-5)
        np.testing.assert_allclose(out_silu, jax.nn.silu(out), atol=1e-6)
        ring = ring_next
    np.testing.assert_allclose(ring, tail_to_ring(last,
                                                  np.full((b,), start + t)),
                               atol=1e-6)


def test_prefill_hands_over_the_last_real_values_of_v():
    """One layer alone: the block of a right-padded row holds its last two
    REAL values of ``v = B * x``, each in the ring's slot of its position
    (zeros where the row is shorter)."""
    layer = ShortConvLayer(n_out=8)
    p = layer.init(jax.random.PRNGKey(0), _it.FeedForward(size=8),
                   jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
    lengths = np.asarray([1, 7, 16])
    mask = (np.arange(16)[None] < lengths[:, None]).astype(np.float32)
    _, block = layer.cache_prefill(p, u, mask)
    bcx = np.asarray(jnp.dot(u, p["W_in"]))
    v = bcx[..., :8] * bcx[..., 16:]
    for row, n in enumerate(lengths):
        ring = np.zeros((2, 8), np.float32)
        for pos in range(max(0, n - 2), n):
            ring[pos % 2] = v[row, pos]
        np.testing.assert_allclose(
            np.asarray(block["conv"][row]).reshape(2, 8), ring, atol=1e-5)


# --- the router --------------------------------------------------------------

def test_the_expert_bias_moves_the_choice_and_not_the_weights():
    """``s + b`` chooses, ``s`` weighs: a bias that lifts one expert above
    the rest puts it in every token's choice, at the weight its own score
    gives; the weights of a choice are the chosen scores over their sum
    plus ``route_eps``."""
    layer = RoutedExpertsLayer(n_out=8, n_experts=16, n_hidden=4, top_k=4,
                               route_eps=1e-6)
    p = layer.init(jax.random.PRNGKey(3), _it.FeedForward(size=8),
                   jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (64, 8))
    s = jax.nn.sigmoid(jnp.dot(u, p["Wr"], precision="highest"))
    bias = jnp.zeros((16,)).at[5].set(10.0)
    for b in (jnp.zeros((16,)), bias):
        experts, w = layer.route({**p, "b": b}, u)
        chosen = jnp.take_along_axis(s, experts, axis=1)
        np.testing.assert_allclose(
            w, chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6),
            rtol=1e-6)
    plain, _ = layer.route({**p, "b": jnp.zeros((16,))}, u)
    lifted, _ = layer.route({**p, "b": bias}, u)
    assert bool(jnp.all(jnp.any(lifted == 5, axis=1)))
    assert not bool(jnp.all(jnp.any(plain == 5, axis=1)))
