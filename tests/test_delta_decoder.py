"""The gated delta-rule / latent-attention decoder on the CPU at small sizes,
seeded weights: ``conf.layers_delta.GatedDeltaNetLayer`` and
``LatentAttentionLayer`` beside ``conf.layers_moe.RoutedExpertsLayer`` ->
``zoo.graphs.HybridDecoderLM`` -> ``ComputationGraph`` ->
``TransformerDecoder`` -> ``GenerationEngine`` against the plain reference
(``benchmarks/reference/gigachat35.py``, which imports nothing of the
program): logits, not tokens; the chunked delta rule against its token
recurrence; the absorbed latent read against the expanded one; what a
join hands over.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.models import gigachat35 as model  # noqa: E402
from benchmarks.reference import gigachat35 as ref  # noqa: E402
from deeplearning4j_tpu.conf import inputs as _it  # noqa: E402
from deeplearning4j_tpu.conf import layers_delta  # noqa: E402
from deeplearning4j_tpu.conf.layers_delta import (  # noqa: E402
    GatedDeltaNetLayer,
    LatentAttentionLayer,
    yarn_inverse_frequencies,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops import attention  # noqa: E402
from deeplearning4j_tpu.ops import delta_rule as dr  # noqa: E402
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationConfig,
    GenerationEngine,
)

pytestmark = pytest.mark.decode

VOCAB = 97
_FF32 = _it.FeedForward(size=32)
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
        "type": "yarn"}


def _cfg(**over):
    """The published layers 2 (dense, delta rule), 3 (latent attention) and
    4 (delta rule), the last two with 16 routed experts of which 8 are
    held."""
    cfg = {"hidden_size": 32, "intermediate_size": 64,
           "moe_intermediate_size": 16, "vocab_size": VOCAB,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
           "qk_rope_head_dim": 8, "v_head_dim": 8, "qk_head_dim": 16,
           "linear_num_key_heads": 2, "linear_num_value_heads": 4,
           "linear_key_head_dim": 8, "linear_value_head_dim": 8,
           "linear_conv_kernel_dim": 4, "linear_sigmoid_gate_scale": 2,
           "n_routed_experts": 16, "num_experts": 16,
           "num_experts_per_tok": 4, "n_shared_experts": 1,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True,
           "n_group": 1, "topk_group": 1, "first_k_dense_replace": 3,
           "full_attention_layers": [3, 7, 11], "layers_served": [2, 3, 4],
           "num_hidden_layers": 3, "experts_held": [0, 8],
           "rope_theta": 100000, "rope_scaling": dict(ROPE),
           "rope_interleave": True, "gated_attention": True,
           "attention_bias": False, "use_shared_expert_sigmoid": False,
           "num_nextn_predict_layers": 0, "swiglu_limit": 10,
           "hidden_act": "silu", "tie_word_embeddings": False,
           "rms_norm_eps": 1e-6, "initializer_range": 0.3,
           "qk_gain_mean": 2.0, "weight_dtype": "float32",
           "cache_dtype": "float32", "serving": {"max_len": 128}}
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
    """``ComputationGraph.output`` (the chunked delta rule, the expanded
    latent attention), the reference's full forward (the token recurrence)
    and the decoder's prompt walk give the same logits."""
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


def test_reference_faults_matter_at_these_sizes():
    """The sizes above make every mechanism live: the reference with one
    left out gives other logits after the join at position 40."""
    cfg = _cfg()
    _, _, w = _net(cfg)
    toks, rows = _tokens(100, 1), np.arange(39, 100)
    sound = np.asarray(ref.Forward(cfg)(w, toks, rows))
    for fault in ref.FAULTS:
        broken = np.asarray(ref.Forward(cfg, fault=fault)(w, toks, rows))
        assert np.abs(broken - sound).max() > 1e-2, fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.Forward(cfg, fault="no_such")


# --- prefill, join, decode through three kinds of state ---------------------

# shorter than the convolution's 3 carried inputs (1, 2), exactly a bucket
# (16, 64), a chunk of 64 that straddles the padding (40, 70)
@pytest.mark.parametrize("prompt_len", [1, 2, 16, 40, 64, 70])
def test_prefill_then_decode_matches_reference(prompt_len):
    """Teacher-forced: the prompt through ``prompt_fn``'s walk, its block
    joined into row 1 of a dirty state, then 12 given tokens one by one
    through the decode walk (the delta rule's one-token step, the absorbed
    latent read); every step's LOGITS against the reference's full forward
    (the token recurrence, the expanded attention)."""
    cfg = _cfg()
    zoo, net, w = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16)
    toks = _tokens(prompt_len + 12, 2)
    tp = next(b for b in dec.prompt_ladder if b >= prompt_len)
    prompts = np.full((1, tp), 5, np.int32)          # the padding is no zero
    prompts[0, :prompt_len] = toks[:prompt_len]
    lengths = np.asarray([prompt_len], np.int32)
    logits0, kv = jax.jit(dec._run_prompt)(net.params, prompts, lengths)
    assert kv["b0_mix"]["state"].shape == (1, 4, 8, 8)    # no bucket in it
    assert kv["b0_mix"]["conv"].shape == (1, 3 * 64)
    assert kv["b1_mix"]["latent"].shape == (1, tp, 128)    # whole lane tiles
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
    assert int(counts["delta_state_updates"][1]) == 2      # two delta layers
    rows = prompt_len - 1 + np.arange(12)
    want = np.asarray(ref.Forward(cfg)(w, toks[:prompt_len + 11], rows))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_a_reused_row_inherits_no_state_of_its_last_tenant():
    """Row 0 decodes one request, then a second request is joined into the
    same row: its tokens are the tokens it gives alone in a fresh engine
    (the delta rule's state, the convolution's ring and the latent cache of
    the first tenant all gone)."""
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


def test_state_bytes_by_kind_and_the_prefix_walk_refuses_by_name():
    """The delta rule's two kinds of state do not grow with the bucket, the
    latent cache does; the prefix cache's suffix walk refuses both layers
    by name."""
    zoo, net, _ = _net(_cfg())
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=64,
                      prompt_bucket_min=16)
    small, large = dec.state_bytes(64), dec.state_bytes(128)
    assert small["recurrent"] == large["recurrent"] == 2 * 2 * 4 * 8 * 8 * 4
    assert small["conv_window"] == large["conv_window"] == 2 * 2 * 3 * 64 * 4
    assert (small["latent"], large["latent"]) == (2 * 64 * 128 * 4,
                                                  2 * 128 * 128 * 4)
    with pytest.raises(NotImplementedError, match="GatedDeltaNetLayer"):
        dec._need("prefill_suffix", "the prefix-cache suffix walk")
    assert any("LatentAttentionLayer" in m
               for m in dec.walks_missing("prefill_suffix"))


# --- the delta rule ----------------------------------------------------------

def _delta_inputs(b, t, h, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = dr.l2_normalize(jax.random.normal(ks[0], (b, t, h, dk))) / dk ** 0.5
    k = dr.l2_normalize(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


# 150 positions in chunks of 64: the second row's 97 real positions end in
# the middle of the second chunk, whose padding must write nothing
@pytest.mark.parametrize("chunk", [64, 16])
def test_chunked_form_is_the_recurrence_and_padding_writes_nothing(chunk):
    q, k, v, g, beta = _delta_inputs(2, 150, 3, 16, 8)
    valid = (np.arange(150)[None] < np.asarray([150, 97])[:, None]).astype(
        np.float32)
    s0 = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (2, 3, 16, 8))
    with jax.default_matmul_precision("highest"):
        o, s = dr.delta_rule_chunked(q, k, v, g, beta, valid, s0, chunk)
        for row, n in enumerate((150, 97)):
            want_o, want_s = dr.delta_rule_loop(
                q[row:row + 1, :n], k[row:row + 1, :n], v[row:row + 1, :n],
                g[row:row + 1, :n], beta[row:row + 1, :n], s0[row:row + 1])
            np.testing.assert_allclose(o[row, :n], want_o[0], atol=2e-5)
            np.testing.assert_allclose(s[row], want_s[0], atol=2e-5)


def test_decode_kernel_is_one_position_of_the_recurrence():
    """The Pallas kernel (interpreter) and the jnp step give what the token
    recurrence gives for one position, the state updated in place."""
    q, k, v, g, beta = _delta_inputs(3, 1, 32, 128, 128, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(2), (3, 32, 128, 128))
    want_o, want_s = dr.delta_rule_loop(q, k, v, g, beta, state)
    args = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0], state)
    assert dr.delta_rule_step_applies(state.shape)
    for o, s in (dr.delta_rule_step_xla(*args),
                 dr.delta_rule_step_kernel(*args, interpret=True)):
        np.testing.assert_allclose(o, want_o[:, 0], atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=1e-4)


def test_prefill_hands_over_the_last_real_inputs_and_spans_carry_both():
    """One layer alone: the block of a right-padded row holds the delta
    rule's state after its last real token and its last three real inputs,
    each in the ring's slot of its position; a sequence in one span and in
    four gives the same."""
    layer = GatedDeltaNetLayer(n_out=32, key_heads=2, value_heads=4,
                               key_dim=8, value_dim=8, gate_scale=2.0,
                               chunk=4)
    p = layer.init(jax.random.PRNGKey(0), _FF32, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32))
    lengths = np.asarray([2, 7, 16])
    mask = (np.arange(16)[None] < lengths[:, None]).astype(np.float32)
    y, block = layer.cache_prefill(p, u, mask)
    x = np.asarray(jnp.dot(u, p["W_qkvz"]))[..., :64]
    for row, n in enumerate(lengths):
        _, want = layer.cache_prefill(p, u[row:row + 1, :n], None)
        np.testing.assert_allclose(block["state"][row], want["state"][0],
                                   atol=1e-5)
        ring = np.zeros((3, 64), np.float32)
        for pos in range(max(0, n - 3), n):
            ring[pos % 3] = x[row, pos]
        np.testing.assert_allclose(
            np.asarray(block["conv"][row]).reshape(3, 64), ring, atol=1e-5)
    import unittest.mock as mock
    with mock.patch.object(layers_delta, "DELTA_TOKEN_SPAN", 4):
        y4, b4 = layer.cache_prefill(p, u, mask)
    np.testing.assert_allclose(y, y4, atol=1e-5)
    for leaf in ("state", "conv"):
        np.testing.assert_allclose(block[leaf], b4[leaf], atol=1e-5)


# --- latent attention --------------------------------------------------------

def _latent_layer():
    layer = LatentAttentionLayer(
        n_out=32, n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8,
        value_dim=8, rope_theta=100000.0, yarn_factor=8.0,
        yarn_original=32768)
    return layer, layer.init(jax.random.PRNGKey(3), _FF32, jnp.float32)


def test_absorbed_decode_is_the_expanded_attention():
    """A prompt of 20 through the EXPANDED form (every head's keys and
    values), then each of its last positions again through the ABSORBED
    decode step against the prompt's latent cache: the same outputs."""
    layer, p = _latent_layer()
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 32))
    with jax.default_matmul_precision("highest"):
        y, block = layer.cache_prefill(p, u)
        cache = layer.cache_join(layer.cache_init(2, 32, 32), block,
                                 np.arange(2), 32)
        for t in (0, 7, 19):
            pos = np.full((2,), t, np.int32)
            out, _, counts = layer.cache_step(p, u[:, t], cache, pos)
            np.testing.assert_allclose(out, y[:, t], atol=2e-5, rtol=2e-5)
    assert int(counts["decode_kv_bucket_positions"][0]) == 32


def test_latent_read_through_the_kernel_is_the_masked_read():
    """The paged kernel over a latent cache (values the first columns of
    the keys' own page, under the interpreter) against the masked read."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(ks[0], (3, 8, 640))
    cache = jax.random.normal(ks[1], (3, 512, 640)).astype(jnp.bfloat16)
    pos = jnp.asarray([0, 200, 511])
    want, read = attention.latent_decode_attention(q, cache, pos, 512, 0.05)
    got = attention.paged_decode_attention(q, cache, None, pos, 0.05,
                                           page=128, interpret=True,
                                           groups=1, value_width=512)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert read.tolist() == [512] * 3         # the masked read: the bucket
    with pytest.raises(ValueError, match="latent cache"):
        attention.paged_decode_attention(q, cache, None, pos, 0.05,
                                         interpret=True, groups=1)


def test_yarn_keeps_the_fast_pairs_and_divides_the_slow_ones():
    """The published 64 rotary dims: pairs 0..13 turn at theta's own
    frequencies, pairs 24.. at an eighth of them, a ramp between."""
    plain = 100000.0 ** (-np.arange(0, 64, 2) / 64)
    yarn = yarn_inverse_frequencies(64, 100000.0, 8.0, 32768, 32.0, 1.0)
    np.testing.assert_allclose(yarn[:14], plain[:14], rtol=1e-6)
    np.testing.assert_allclose(yarn[24:], plain[24:] / 8, rtol=1e-6)
    assert np.all((yarn[15:24] < plain[15:24]) & (yarn[15:24] > plain[15:24]
                                                  / 8))
    np.testing.assert_allclose(ref.yarn_frequencies(
        {"qk_rope_head_dim": 64, "rope_theta": 100000, "rope_scaling": ROPE}),
        yarn, rtol=1e-6)
