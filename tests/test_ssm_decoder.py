"""The state-space hybrid decoder on the CPU at small sizes, seeded weights:
``conf.layers_ssm.MambaMixerLayer`` and ``conf.layers_hybrid
.GroupedAttentionLayer`` under a tied head -> ``zoo.graphs.HybridDecoderLM``
-> ``ComputationGraph`` -> ``TransformerDecoder`` -> ``GenerationEngine``
against the plain reference (``benchmarks/reference/jamba.py``, which
imports nothing of the program): logits, not tokens; the two kinds of
state a Mamba layer keeps through prefill, join and decode; the scan's
kernel under the Pallas interpreter; the one matrix of a tied head.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.models import jamba as model  # noqa: E402
from benchmarks.reference import jamba as ref  # noqa: E402
from deeplearning4j_tpu.conf import inputs as _it  # noqa: E402
from deeplearning4j_tpu.conf import layers_ssm  # noqa: E402
from deeplearning4j_tpu.conf.layers_ssm import MambaMixerLayer  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops import selective_scan as ss  # noqa: E402
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationConfig,
    GenerationEngine,
)

pytestmark = pytest.mark.decode

VOCAB = 97
_FF8 = _it.FeedForward(size=8)


def _cfg(**over):
    """Eight layers, attention at 1 and 5 (period 4, offset 1)."""
    cfg = {"hidden_size": 32, "intermediate_size": 64, "vocab_size": VOCAB,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "num_hidden_layers": 8, "attn_layer_period": 4,
           "attn_layer_offset": 1, "mamba_d_state": 4, "mamba_d_conv": 4,
           "mamba_expand": 2, "mamba_dt_rank": 6, "mamba_conv_bias": True,
           "mamba_proj_bias": False, "num_experts": 1,
           "tie_word_embeddings": True, "hidden_act": "silu",
           "sliding_window": None, "rms_norm_eps": 1e-6,
           "initializer_range": 0.3, "qk_init_scale": 1.6,
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
    """``ComputationGraph.output`` (the tied head reads the embedding
    vertex's matrix), the reference's full forward and the decoder's
    prompt walk give the same logits."""
    cfg = _cfg()
    zoo, net, w = _net(cfg)
    assert "output" not in net.params        # one matrix, the embedding's
    toks = _tokens(48, 1)
    probs = np.asarray(net.output(toks[None]))[0]
    logits = np.asarray(ref.Forward(cfg)(w, toks, np.arange(48)))
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(np.log(probs), want, atol=3e-4)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16)
    last, _ = jax.jit(dec._run_prompt)(
        net.params, np.pad(toks, (0, 16))[None], np.asarray([48], np.int32))
    np.testing.assert_allclose(np.asarray(last)[0], logits[-1], atol=2e-4,
                               rtol=2e-4)


def test_prompt_ladder_stops_at_the_longest_prompt_served():
    """``prompt_bucket_max``: no prompt or join program above it is built,
    and a longer prompt is refused when it is validated, not at a launch;
    without it the ladder runs to ``max_len``."""
    zoo, net, _ = _net(_cfg())
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16, prompt_bucket_max=32,
                      join_bucket_max=1)
    assert dec.prompt_ladder == [16, 32] and dec.kv_ladder == [128]
    assert len(dec.validate_request(_tokens(32), 96)) == 32
    with pytest.raises(ValueError, match="largest prompt bucket 32"):
        dec.validate_request(_tokens(33), 8)
    assert zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                       prompt_bucket_min=16).prompt_ladder == [16, 32, 64,
                                                               128]


def test_reference_faults_matter_at_these_sizes():
    """The sizes above make every mechanism live: the reference with one
    left out gives other logits."""
    cfg = _cfg()
    _, _, w = _net(cfg)
    toks, rows = _tokens(64, 1), np.arange(20, 64)
    sound = np.asarray(ref.Forward(cfg)(w, toks, rows))
    for fault in ref.FAULTS:
        broken = np.asarray(ref.Forward(cfg, fault=fault)(w, toks, rows))
        assert np.abs(broken - sound).max() > 1e-2, fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.Forward(cfg, fault="no_such")


# --- prefill, join, decode through both states ------------------------------

# shorter than the convolution's 3 carried inputs (1, 2), exactly a bucket
# (16, 32), right-padded by more than the convolution's width (3, 9, 20)
@pytest.mark.parametrize("prompt_len", [1, 2, 3, 9, 16, 20, 32])
def test_prefill_then_decode_matches_reference(prompt_len):
    """Teacher-forced: the prompt through ``prompt_fn``'s walk, its block
    joined into row 1 of a dirty state, then 12 given tokens one by one
    through the decode walk; every step's LOGITS against the reference's
    full forward."""
    cfg = _cfg()
    zoo, net, w = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16)
    toks = _tokens(prompt_len + 12, 2)
    tp = 16 if prompt_len <= 16 else 32
    prompts = np.full((1, tp), 5, np.int32)          # the padding is no zero
    prompts[0, :prompt_len] = toks[:prompt_len]
    lengths = np.asarray([prompt_len], np.int32)
    logits0, kv = jax.jit(dec._run_prompt)(net.params, prompts, lengths)
    assert kv["b0_mix"]["state"].shape == (1, 4, 64)     # no bucket in it
    assert kv["b0_mix"]["conv"].shape == (1, 3 * 64)
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
                                                       active)[:2])
    got = [np.asarray(logits0)[0]]
    caches = state["caches"]
    for i in range(11):
        t = np.asarray([0, toks[prompt_len + i], 0], np.int32)
        pos = np.asarray([0, prompt_len + i, 0], np.int32)
        logits, caches = step(net.params, t, pos, caches)
        got.append(np.asarray(logits)[1])
    rows = prompt_len - 1 + np.arange(12)
    want = np.asarray(ref.Forward(cfg)(w, toks[:prompt_len + 11], rows))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_prefill_hands_over_the_last_real_inputs_not_the_buckets_tail():
    """One layer alone: the block of a right-padded row holds the state
    after its last real token and its last three real inputs, each in the
    ring's slot of its position (``p mod 3``), zeros where the prompt has
    fewer; a row of the same group that fills the bucket holds the
    bucket's tail."""
    layer = MambaMixerLayer(n_out=8, d_inner=16, d_state=4, dt_rank=3)
    p = layer.init(jax.random.PRNGKey(0), _FF8, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8))
    lengths = np.asarray([2, 5, 8])
    mask = (np.arange(8)[None] < lengths[:, None]).astype(np.float32)
    _, block = layer.cache_prefill(p, u, mask)
    x = np.asarray(jnp.dot(u, p["W_in"]))[..., :16]
    for row, n in enumerate(lengths):
        _, want = layer.cache_prefill(p, u[row:row + 1, :n], None)
        np.testing.assert_allclose(block["state"][row], want["state"][0],
                                   atol=1e-6)
        ring = np.zeros((3, 16), np.float32)
        for pos in range(max(0, n - 3), n):
            ring[pos % 3] = x[row, pos]
        np.testing.assert_allclose(
            np.asarray(block["conv"][row]).reshape(3, 16), ring, atol=1e-6)


def test_token_spans_carry_both_states(monkeypatch):
    """A sequence in one span and in four: the scan's state and the
    convolution's window cross the spans' edges."""
    layer = MambaMixerLayer(n_out=8, d_inner=16, d_state=4, dt_rank=3)
    p = layer.init(jax.random.PRNGKey(0), _FF8, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 8))
    mask = (np.arange(32)[None] < np.asarray([32, 13])[:, None]).astype(
        np.float32)
    y0, b0 = layer.cache_prefill(p, u, mask)
    monkeypatch.setattr(layers_ssm, "SSM_TOKEN_SPAN", 8)
    y1, b1 = layer.cache_prefill(p, u, mask)
    np.testing.assert_allclose(y0, y1, atol=1e-5)
    for leaf in ("state", "conv"):
        np.testing.assert_allclose(b0[leaf], b1[leaf], atol=1e-5)


# --- the scan ---------------------------------------------------------------

def _scan_inputs(rows, t, d, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (rows, t, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, t, d)) - 2)
    a = -jnp.exp(jax.random.normal(ks[2], (n, d)) * 0.5)
    b = jax.random.normal(ks[3], (rows, t, n))
    c = jax.random.normal(ks[4], (rows, t, n))
    skip = jax.random.normal(ks[5], (d,))
    h0 = jax.random.normal(ks[6], (rows, n, d))
    return x, dt, a, b, c, skip, h0


def test_scan_loop_is_the_recurrence_and_a_masked_position_stands_still():
    x, dt, a, b, c, skip, h0 = _scan_inputs(2, 12, 8, 3)
    mask = (np.arange(12)[None] < np.asarray([12, 7])[:, None]).astype(
        np.float32)
    y, h = ss.selective_scan_loop(x, dt, a, b, c, skip, mask, h0)
    want_h = np.asarray(h0, np.float64)
    for t in range(12):
        step = np.asarray(dt[:, t], np.float64) * mask[:, t, None]
        want_h = (np.exp(step[:, None, :] * np.asarray(a, np.float64))
                  * want_h + (step * np.asarray(x[:, t]))[:, None, :]
                  * np.asarray(b[:, t])[:, :, None])
        want_y = ((want_h * np.asarray(c[:, t])[:, :, None]).sum(1)
                  + np.asarray(skip) * np.asarray(x[:, t]))
        np.testing.assert_allclose(y[:, t], want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h, want_h, atol=1e-4, rtol=1e-4)
    # the row whose last five positions are masked: its state after 7
    _, h7 = ss.selective_scan_loop(x[1:, :7], dt[1:, :7], a, b[1:, :7],
                                   c[1:, :7], skip, None, h0[1:])
    np.testing.assert_allclose(h[1:], h7, atol=1e-6)
    # and the loop keeps no history of the state
    jaxpr = str(jax.make_jaxpr(lambda *v: ss.selective_scan_loop(*v))(
        x, dt, a, b, c, skip, mask, h0))
    assert "f32[12,2,3,8]" not in jaxpr and "f32[2,12,3,8]" not in jaxpr


# two time chunks of 256 and one short one; two blocks of 1,024 channels
@pytest.mark.parametrize("t", [512, 64])
def test_scan_kernel_matches_the_loop_under_the_interpreter(t):
    """With ``h0`` given and a mask: the kernel's grid (row, block of
    channels, time chunk), the state resident across the chunks."""
    x, dt, a, b, c, skip, h0 = _scan_inputs(2, t, 2048, 16, seed=t)
    assert ss.selective_scan_applies(t, 2048)
    assert not ss.selective_scan_applies(t, 1000)
    mask = (np.arange(t)[None] < np.asarray([t - 5, t // 2])[:, None]).astype(
        np.float32)
    y0, h_loop = ss.selective_scan_loop(x, dt, a, b, c, skip, mask, h0)
    y1, h_kernel = ss.selective_scan_kernel(
        x, ss._masked(dt, mask), a, b, c, skip, h0, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h_kernel, h_loop, atol=2e-5, rtol=2e-5)
    # off the TPU the entry point is the loop
    y2, h2 = jax.jit(ss.selective_scan)(x, dt, a, b, c, skip, mask, h0)
    np.testing.assert_allclose(y2, y0, atol=1e-6)
    np.testing.assert_allclose(h2, h_loop, atol=1e-6)


def test_scan_step_is_one_position_of_the_loop():
    x, dt, a, b, c, skip, h0 = _scan_inputs(3, 1, 8, 4)
    y, h = ss.selective_scan_loop(x, dt, a, b, c, skip, None, h0)
    y1, h1 = ss.selective_scan_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                    skip, h0)
    np.testing.assert_allclose(y[:, 0], y1, atol=1e-6)
    np.testing.assert_allclose(h, h1, atol=1e-6)


# --- through GenerationEngine -------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16, join_bucket_max=1)
    return cfg, zoo, net, dec


REQUESTS = [(50, 30), (2, 9), (40, 25), (16, 40), (20, 5), (1, 20)]


def _two_row_engine(dec):
    return GenerationEngine(dec, GenerationConfig(
        max_batch=2, fused_steps=4, kv_bucket_min=128, prompt_bucket_min=16,
        join_bucket_max=1))


def test_engine_rows_give_what_they_give_alone(served):
    """Six requests over two rows, enqueued together: they join and leave
    at different times, every row is reused, an idle row rides beside a
    live one, and each answer is token for token what ``generate`` gives
    the request alone; the layers' counts ride the windows' outputs and
    the prefills' tokens are summed."""
    _, _, _, dec = served
    with _two_row_engine(dec) as eng:
        with eng._cond:
            handles = [eng.submit(_tokens(n, 10 + i).tolist(),
                                  max_new_tokens=m)
                       for i, (n, m) in enumerate(REQUESTS)]
        got = [eng.result(h) for h in handles]
        stats = eng.stats()
    for i, (n, m) in enumerate(REQUESTS):
        alone = dec.generate(_tokens(n, 10 + i).tolist(), m, fused_steps=4)
        assert got[i] == alone, i
    counts = stats["layer_counts"]
    decoded = sum(m - 1 for _, m in REQUESTS)
    assert counts["ssm_state_updates"] == 6 * decoded    # six Mamba layers
    assert counts["decode_kv_bucket_positions"] == 2 * 128 * decoded
    assert stats["prefill_live_tokens_total"] == sum(n for n, _ in REQUESTS)
    # one prompt a launch: each walks its own bucket, padding included
    assert stats["prefill_bucket_tokens_total"] == 64 + 16 + 64 + 16 + 32 + 16


def test_a_rows_logits_do_not_depend_on_its_co_tenants():
    """One decode step of row 1 alone and among two live co-tenants."""
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16)
    caches = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(3), a.shape, a.dtype),
        dec.new_state(128)["caches"])
    t = np.asarray([5, 17, 60], np.int32)
    pos = np.asarray([30, 41, 7], np.int32)
    step = jax.jit(lambda a: dec._run_token(net.params, t, pos, caches, a)[0])
    alone = np.asarray(step(np.asarray([False, True, False])))[1]
    among = np.asarray(step(np.asarray([True, True, True])))[1]
    np.testing.assert_allclose(alone, among, atol=1e-5, rtol=1e-5)


def test_a_reused_row_inherits_neither_state_of_its_last_tenant(served):
    """A state full of a last tenant's values (the scan's state, the
    convolution's window and the keys and values alike), then a join of a
    prompt SHORTER than the convolution's window: the joined row decodes
    as from a clean state. No load is raced: the dirty state is the one
    ``generate`` starts from. And a release zeroes both Mamba states."""
    _, _, _, dec = served
    for n in (1, 2, 40):
        prompt = _tokens(n, 4).tolist()
        clean = dec.generate(prompt, 12)
        dirty = jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, 5, a.dtype), dec.new_state(128))
        real_new_state = dec.new_state
        dec.new_state = lambda s: dirty
        try:
            assert dec.generate(prompt, 12) == clean
        finally:
            dec.new_state = real_new_state
    full = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 5, a.dtype), dec.new_state(128))
    out = dec.release_fn(128)(full, np.asarray([True, False]))
    for leaf in ("state", "conv"):
        a = np.asarray(out["caches"]["b0_mix"][leaf])
        assert (a[0] == 5).all() and (a[1] == 0).all()


def test_state_bytes_by_kind_and_neither_mamba_state_grows():
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=32,
                      prompt_bucket_min=16)
    assert dec.kv_ladder == [32, 64, 128]
    for s in (32, 128):
        assert dec.state_bytes(s) == {
            "recurrent": 6 * 2 * 4 * 64 * 4, "conv_window": 6 * 2 * 3 * 64 * 4,
            "kv": 2 * 2 * 2 * s * 8 * 4}
    state = dec.grow_fn(32, 64)(dec.new_state(32))
    assert state["caches"]["b0_mix"]["state"].shape == (2, 4, 64)
    assert state["caches"]["b0_mix"]["conv"].shape == (2, 3 * 64)
    assert state["caches"]["b1_mix"]["k"].shape == (2, 64, 8)
    assert dec.counter_names == ["decode_kv_bucket_positions",
                                 "decode_kv_read_positions",
                                 "ssm_state_updates"]


def test_prefix_walk_refuses_the_state_space_layers_by_name(served):
    _, _, _, dec = served
    with pytest.raises(NotImplementedError, match="MambaMixerLayer"):
        dec._need("prefill_suffix", "the prefix-cache suffix walk")
    with pytest.raises(ValueError, match="MambaMixerLayer"):
        GenerationEngine(dec, GenerationConfig(
            max_batch=2, kv_bucket_min=128, prompt_bucket_min=16,
            join_bucket_max=1, prefix_cache=True))


# --- the tied head ------------------------------------------------------------

def test_tied_head_is_one_matrix_updated_through_both_uses():
    """Two training steps of a small tied model: the graph holds no head
    matrix, the embedding's gradient is the sum of the gradient through
    the lookup and the gradient through the head, and ``fit`` moves the
    one matrix."""
    zoo = model.zoo(_cfg(num_hidden_layers=2, attn_layer_period=2,
                         attn_layer_offset=1))
    net = ComputationGraph(zoo.conf()).init()
    assert "output" not in net.params and "output" not in net.opt_state
    assert net._tied == {"output": "embed"}
    toks = _tokens(2 * 12, 3).reshape(2, 12)
    labels = np.eye(VOCAB, dtype=np.float32)[_tokens(2 * 12, 4).reshape(2, 12)]

    def loss(table, head):
        """The same graph with the two uses of the matrix kept apart."""
        params = dict(net.params, embed={"W": table})
        acts, _, _ = net._forward(params, {}, [toks], False, None,
                                  skip={"output"})
        return net._vmap["output"].vertex.score(
            {"W": head}, acts["final_norm"], labels, None)

    w = net.params["embed"]["W"]
    g_lookup, g_head = jax.grad(loss, argnums=(0, 1))(w, w)
    assert float(jnp.abs(g_lookup).max()) > 0 and float(
        jnp.abs(g_head).max()) > 0
    whole = jax.grad(lambda p: net._loss(
        p, {}, [toks], [labels], [None], [None], None, train=False)[0])(
            net.params)
    np.testing.assert_allclose(whole["embed"]["W"], g_lookup + g_head,
                               atol=1e-6, rtol=1e-4)
    before = np.asarray(w).copy()
    for _ in range(2):
        net.fit(DataSet(toks, labels))
    after = np.asarray(net.params["embed"]["W"])
    assert "output" not in net.params
    assert np.abs(after - before).max() > 0
    # a token no row looked up moved too: through the head alone
    unseen = sorted(set(range(VOCAB)) - set(toks.reshape(-1).tolist()))
    assert np.abs(after[unseen] - before[unseen]).max() > 0


def test_tied_head_keeps_the_master_under_a_compute_policy():
    """``compute_dtype`` bfloat16: ``output`` runs the layers on the cast
    parameters and the tied head on the embedding's float32 MASTER, as an
    untied head's own matrix is kept."""
    import dataclasses

    from deeplearning4j_tpu.nn import io as nn_io

    zoo = model.zoo(_cfg(num_hidden_layers=2, attn_layer_period=2,
                         attn_layer_offset=1))
    net = ComputationGraph(dataclasses.replace(
        zoo.conf(), compute_dtype="bfloat16")).init()
    toks = _tokens(2 * 12, 5).reshape(2, 12)
    got = np.asarray(net.output(toks)[0])
    cast = nn_io.cast_floats(net.params, jnp.bfloat16)
    acts, _, _ = net._forward(cast, {}, [toks], False, None, skip={"output"})
    head = net._vmap["output"].vertex
    want, _ = head.forward(net.params["embed"], {}, acts["final_norm"])
    rounded, _ = head.forward(cast["embed"], {}, acts["final_norm"])
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=1e-6)
    assert np.abs(got - np.asarray(rounded, np.float32)).max() > 1e-4
