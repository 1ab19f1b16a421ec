"""The sparse/linear hybrid decoder on the CPU at small sizes, seeded
weights: ``conf.layers_hybrid`` -> ``zoo.graphs.HybridDecoderLM`` ->
``ComputationGraph`` -> ``TransformerDecoder`` -> ``GenerationEngine``
against the plain reference (``benchmarks/reference/minicpm_sala.py``,
which imports nothing of the program), and the per-layer cache interface
the decoder now walks.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import minicpm_sala as ref  # noqa: E402
from deeplearning4j_tpu.nn.decoding import TransformerDecoder  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops import block_sparse  # noqa: E402
from deeplearning4j_tpu.ops.linear_attention import (  # noqa: E402
    linear_attention_chunked,
    linear_attention_step,
)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationConfig,
    GenerationEngine,
)
from deeplearning4j_tpu.zoo.graphs import (  # noqa: E402
    HybridDecoderLM,
    TransformerEncoder,
)

pytestmark = pytest.mark.decode

SPARSE = {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
          "window_size": 16, "init_blocks": 1, "topk": 2, "dense_len": 32}
STACKS = {"lightning": [10, 11], "sparse": [9, 16], "mixed": [9, 10, 16, 18]}
PUBLISHED = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
             + ["lightning-attn"] * 6 + ["minicpm4"] * 2
             + ["lightning-attn"] * 4 + ["minicpm4"]
             + ["lightning-attn"] * 6 + ["minicpm4"] * 3)


def _cfg(stack="mixed", **over):
    served = STACKS[stack]
    cfg = {"hidden_size": 32, "intermediate_size": 64, "vocab_size": 97,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8,
           "mixer_types": PUBLISHED, "layers_served": served,
           "num_hidden_layers": len(served), "scale_emb": 12,
           "scale_depth": 1.4, "dim_model_base": 8, "rope_theta": 10000,
           "rms_norm_eps": 1e-6, "lightning_use_rope": True, "qk_norm": True,
           "use_output_norm": True, "use_output_gate": True,
           "attn_use_output_gate": True, "sparse_config": dict(SPARSE),
           "initializer_range": 0.3, "qk_gain_mean": 1.6,
           "weight_dtype": "float32", "cache_dtype": "float32",
           "state_dtype": "float32"}
    cfg.update(over)
    return cfg


def _zoo(cfg, max_len=128, **over):
    kw = dict(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        ffn_dim=cfg["intermediate_size"],
        mixer_types=[cfg["mixer_types"][i] for i in cfg["layers_served"]],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        n_kv_heads=cfg["num_key_value_heads"],
        lightning_heads=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        layer_indices=cfg["layers_served"], n_layers_total=32,
        depth_for_scale=32, scale_emb=cfg["scale_emb"],
        scale_depth=cfg["scale_depth"], dim_model_base=cfg["dim_model_base"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        sparse=cfg["sparse_config"], max_len=max_len,
        weight_dtype=cfg["weight_dtype"], cache_dtype=cfg["cache_dtype"])
    kw.update(over)
    return HybridDecoderLM(**kw)


def _net(cfg, seed=7, max_len=128):
    zoo = _zoo(cfg, max_len)
    net = ComputationGraph(zoo.conf())
    weights = ref.init_weights(cfg, seed)
    want = jax.eval_shape(lambda: ComputationGraph(zoo.conf()).init().params)
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    assert got == jax.tree_util.tree_map(
        lambda a: (a.shape, str(a.dtype)), want)
    net.params, net.state, net.opt_state = weights, {}, {}
    return zoo, net, weights


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n)


# --- the graph's forward against the reference ------------------------------

@pytest.mark.parametrize("stack", sorted(STACKS))
def test_graph_output_matches_reference(stack):
    cfg = _cfg(stack)
    _, net, w = _net(cfg)
    toks = _tokens(96, 1)          # three times dense_len
    probs = np.asarray(net.output(toks[None]))[0]
    logits = np.asarray(ref.Forward(cfg)(w, toks, np.arange(96)))
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(np.log(probs), want, atol=3e-4)


def test_reference_top_k_matters_at_these_sizes():
    """The sizes above make the selection live: the reference with the
    top-k dropped, or with dense attention, gives other logits."""
    cfg = _cfg("sparse")
    _, _, w = _net(cfg)
    toks, rows = _tokens(96, 1), np.arange(40, 96)
    sound = np.asarray(ref.Forward(cfg)(w, toks, rows))
    for fault in ("no_topk", "dense_attention"):
        broken = np.asarray(ref.Forward(cfg, fault=fault)(w, toks, rows))
        assert np.abs(broken - sound).max() > 1e-2, fault


# --- prefill, join, decode through the caches -------------------------------

@pytest.mark.parametrize("prompt_len", [9, 24, 50])     # under, at the
@pytest.mark.parametrize("stack", sorted(STACKS))       # edge of, beyond
def test_prefill_then_decode_matches_reference(stack, prompt_len):
    """Teacher-forced: the prompt through ``prompt_fn``'s walk, its block
    joined into row 1 of a dirty state, then 20 given tokens one by one
    through the decode walk; every step's logits against the reference's
    full forward (contexts cross ``dense_len`` = 32 on the way)."""
    cfg = _cfg(stack)
    zoo, net, w = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16)
    toks = _tokens(prompt_len + 20, 2)
    tp = 16 if prompt_len <= 16 else (32 if prompt_len <= 32 else 64)
    prompts = np.zeros((1, tp), np.int32)
    prompts[0, :prompt_len] = toks[:prompt_len]
    lengths = np.asarray([prompt_len], np.int32)
    logits0, kv = jax.jit(dec._run_prompt)(net.params, prompts, lengths)
    state = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 3, a.dtype), dec.new_state(128))
    one = np.ones((1,), np.int32)
    state = dec.join_fn(128, tp, 1)(
        state, kv, np.asarray([1], np.int32), toks[prompt_len:prompt_len + 1]
        .astype(np.int32), lengths, 64 * one, -one,
        np.zeros((1,), np.float32), np.zeros((1, 2), np.uint32),
        np.ones((1,), bool))
    step = jax.jit(lambda p, t, pos, c: dec._run_token(p, t, pos, c)[:2])
    got = [np.asarray(logits0)[0]]
    caches = state["caches"]
    for i in range(19):
        t = np.asarray([0, toks[prompt_len + i], 0], np.int32)
        pos = np.asarray([0, prompt_len + i, 0], np.int32)
        logits, caches = step(net.params, t, pos, caches)
        got.append(np.asarray(logits)[1])
    rows = prompt_len - 1 + np.arange(20)
    want = np.asarray(ref.Forward(cfg)(w, toks[:-1], rows))
    np.testing.assert_allclose(np.stack(got), want, atol=5e-4)


# --- the chunked recurrence --------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("t,valid_len", [(50, 50), (50, 37), (64, 64)])
def test_chunked_lightning_matches_token_recurrence(chunk, t, valid_len):
    rng = np.random.default_rng(chunk + t)
    b, h, d = 2, 3, 8
    q, k, v = (rng.normal(size=(b, t, h, d)).astype(np.float32)
               for _ in range(3))
    ld = -np.asarray([0.5, 0.05, 0.003], np.float32)
    valid = (np.arange(t)[None] < np.asarray([[valid_len], [t]])).astype(
        np.float32)
    o, s = linear_attention_chunked(q, k, v, ld, valid, chunk=chunk)
    state = jnp.zeros((b, h, d, d), jnp.float32)
    outs = []
    for i in range(t):
        o_i, new = linear_attention_step(q[:, i], k[:, i], v[:, i], state, ld)
        state = jnp.where(valid[:, i, None, None, None] > 0, new, state)
        outs.append(o_i)
    want = np.stack(outs, axis=1)
    live = valid[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(live, o, 0), np.where(live, want, 0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, state, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("stack,vertex", [("lightning", "b0_mix"),
                                          ("sparse", "b0_mix")])
def test_token_spans_change_nothing(stack, vertex):
    """A long prompt goes through a mixer ``token_span`` positions at a
    time (the float32 projections of 32 k positions are never all alive):
    the output and the prefilled block are those of the whole sequence."""
    import dataclasses

    cfg = _cfg(stack)
    _, net, _ = _net(cfg)
    layer = net._vmap[vertex].vertex.layer
    p = net.params[vertex]
    x = np.random.default_rng(9).normal(size=(2, 96, 32)).astype(np.float32)
    mask = (np.arange(96)[None] < np.asarray([[96], [70]])).astype(np.float32)
    whole_y, whole_block = layer.cache_prefill(p, x, mask)
    spans = dataclasses.replace(layer, token_span=32)
    y, block = spans.cache_prefill(p, x, mask)
    np.testing.assert_allclose(y, whole_y, atol=1e-5)
    for leaf in whole_block:
        np.testing.assert_allclose(block[leaf], whole_block[leaf], atol=1e-5)


def test_lightning_carry_continues_a_sequence():
    """Two segments through ``forward_with_carry`` equal one sequence:
    the state and the rotation's offset both carry."""
    cfg = _cfg("lightning")
    _, net, _ = _net(cfg)
    layer = net._vmap["b0_mix"].vertex.layer
    p = net.params["b0_mix"]
    x = np.random.default_rng(3).normal(size=(2, 40, 32)).astype(np.float32)
    whole, _ = layer.forward(p, {}, x)
    a, carry = layer.forward_with_carry(p, layer.zero_carry(2), x[:, :17])
    b, _ = layer.forward_with_carry(p, carry, x[:, 17:])
    np.testing.assert_allclose(np.concatenate([a, b], axis=1), whole,
                               atol=1e-4)


# --- the selection -----------------------------------------------------------

@pytest.mark.parametrize("t", [40, 63, 95])
def test_chosen_blocks_match_the_reference(t):
    """On random inputs (no near-ties) the program's chosen positions are
    the reference's, per KV head."""
    rng = np.random.default_rng(t)
    n, heads, groups, d = 96, 4, 2, 8
    spec = block_sparse.SparseSpec(kernel=8, stride=4, block=8, window=16,
                                   init_blocks=1, topk=2, dense_len=32)
    q = rng.normal(size=(1, 1, heads, d)).astype(np.float32)
    k = rng.normal(size=(1, n, groups * d)).astype(np.float32)
    ck = block_sparse.compress_keys(jnp.asarray(k), spec)
    pos = np.asarray([[t]], np.int32)
    idx, ok = block_sparse.select_blocks(jnp.asarray(q), ck, pos, spec, groups)
    got = np.asarray(block_sparse.allowed_positions(idx, ok, pos, n, spec))
    ck_ref = ref.compressed_keys(jnp.asarray(k[0]).reshape(n, groups, d),
                                 SPARSE)
    chosen = np.asarray(ref.chosen_blocks(
        SPARSE, jnp.asarray(q[0, 0])[None], jnp.asarray([t]), ck_ref,
        n // 8, heads // groups))[0]                 # [groups, blocks]
    p = np.arange(n)
    want = ((np.repeat(chosen, 8, axis=-1) | (p > t - 16) | (p < 8))
            & (p <= t))
    np.testing.assert_array_equal(got[0, 0], want)
    assert 0 < chosen.sum() <= 2 * groups
    # the complete compressed keys are the reference's
    n_c = ck_ref.shape[0]
    np.testing.assert_allclose(np.asarray(ck)[0, :n_c],
                               np.asarray(ck_ref).reshape(n_c, -1), atol=1e-6)


def test_sparse_decode_equals_masked_dense_attention():
    rng = np.random.default_rng(5)
    b, s, heads, groups, d = 3, 128, 4, 2, 8
    spec = block_sparse.SparseSpec(kernel=8, stride=4, block=8, window=16,
                                   init_blocks=1, topk=2, dense_len=32)
    q = rng.normal(size=(b, heads, d)).astype(np.float32)
    k = rng.normal(size=(b, s, groups * d)).astype(np.float32)
    v = rng.normal(size=(b, s, groups * d)).astype(np.float32)
    pos = np.asarray([40, 77, 127], np.int32)
    ck = block_sparse.compress_keys(jnp.asarray(k), spec)
    o, attended, read = block_sparse.sparse_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), ck, pos, spec, groups)
    idx, ok = block_sparse.select_blocks(q[:, None], ck, pos[:, None], spec,
                                         groups)
    mask = block_sparse.allowed_positions(idx, ok, pos[:, None], s, spec)
    want = block_sparse._grouped_attend(jnp.asarray(q)[:, None],
                                        jnp.asarray(k), jnp.asarray(v), mask,
                                        groups)[:, 0]
    np.testing.assert_allclose(o, want, atol=1e-5)
    np.testing.assert_array_equal(attended,
                                  np.asarray(mask)[:, 0].sum(axis=(1, 2)))
    assert (np.asarray(attended) < groups * (pos + 1)).all()
    # the window and the first block once, each KV head's two chosen blocks
    # with the other head's lanes beside them
    assert np.asarray(read).tolist() == [
        groups * (16 + 8 + groups * 2 * 8)] * b


# --- the decode read as a kernel, the choice without a sort -----------------

_READ_SPEC = dict(kernel=8, stride=4, block=8, window=16, init_blocks=1,
                  dense_len=32)
# positions of the rows of one batch, in a bucket of 128, top-k
_READ_CASES = {
    # pos - window = 24 and 61: the edge of the window falls inside a block
    # (24 = 3 * 8 is a block's FIRST position, 61 its sixth), which a
    # chosen block and the window both hold: attended once
    "a_chosen_block_straddles_the_edge": ([40, 77], 2),
    # block-aligned edges: pos - window + 1 starts a block
    "the_window_starts_a_block": ([39, 95, 127], 2),
    # candidates are the blocks 1 .. (pos - 16) // 8: two at 33, three at 47
    "fewer_candidates_than_topk": ([33, 47, 100], 4),
    "just_over_dense_len": ([32, 33, 34], 2),
    # a dense row (its output is discarded by the layer, its copies must
    # stay inside the cache), the bucket's last slot, a position past it
    "contexts_of_every_kind_in_one_batch": ([5, 15, 16, 60, 127, 130], 3),
}


def _read_inputs(positions, topk, dtype=np.float32, seed=3):
    rng = np.random.default_rng(seed)
    b, s, heads, groups, d = len(positions), 128, 4, 2, 8
    spec = block_sparse.SparseSpec(topk=topk, **_READ_SPEC)
    pos = np.asarray(positions, np.int32)
    q = jnp.asarray(rng.normal(size=(b, heads, d)).astype(np.float32))
    k = rng.normal(size=(b, s, groups * d)).astype(np.float32)
    v = rng.normal(size=(b, s, groups * d)).astype(np.float32)
    ck = block_sparse.compress_keys(jnp.asarray(k), spec)
    # past a row's position: a retired tenant's keys and values
    beyond = np.arange(s)[None, :, None] > pos[:, None, None]
    k = np.where(beyond, 300.0 * rng.normal(size=k.shape), k)
    v = np.where(beyond, 300.0 * rng.normal(size=v.shape), v)
    score = block_sparse.block_scores(q[:, None], ck, pos[:, None], spec,
                                      groups)[:, 0]
    return (spec, groups, q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(pos), score)


@pytest.mark.parametrize("chunk", [16, 4096])
@pytest.mark.parametrize("case", sorted(_READ_CASES))
def test_sparse_read_kernel_equals_the_gathered_read(case, chunk):
    """``sparse_read_attention`` (through the Pallas interpreter) against
    ``gathered_decode_attention`` on the same chosen blocks: the output
    to float32 rounding, the attended count exactly, with the running
    softmax in several steps and in one a slab; what it says it copied
    is the slabs' size."""
    positions, topk = _READ_CASES[case]
    spec, groups, q, k, v, pos, score = _read_inputs(positions, topk)
    vals, idx = jax.lax.top_k(score, topk)
    ok = vals >= 0.0
    if case == "fewer_candidates_than_topk":
        assert np.asarray(ok).sum(axis=-1).tolist() == [[2, 2], [3, 3],
                                                        [4, 4]]
    # a position past the bucket (a retired row's) reads as the last slot,
    # as the write clamps it
    want, attended, _ = block_sparse.gathered_decode_attention(
        q, k, v, idx, ok, jnp.minimum(pos, 127), spec, groups)
    got, counted, read = block_sparse.sparse_read_attention(
        q, k, v, idx, ok, pos, spec, groups, interpret=True, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(attended))
    copied = groups * (16 + 8 + 8 + topk * 8)
    assert np.asarray(read).tolist() == [copied] * len(positions)
    assert (np.asarray(read) >= np.asarray(counted)).all()


def test_sparse_read_kernel_in_the_caches_type():
    """bfloat16 caches: the products take bfloat16 operands and accumulate
    in float32, as the gathered read's do."""
    positions, topk = _READ_CASES["a_chosen_block_straddles_the_edge"]
    spec, groups, q, k, v, pos, score = _read_inputs(positions, topk,
                                                     jnp.bfloat16)
    idx, ok = block_sparse.rank_blocks(score, topk)
    want, attended, _ = block_sparse.gathered_decode_attention(
        q, k, v, idx, ok, pos, spec, groups)
    got, counted, _ = block_sparse.sparse_read_attention(
        q, k, v, idx, ok, pos, spec, groups, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.02)
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(attended))


def test_sparse_read_kernel_refuses_a_bucket_without_whole_blocks():
    spec, groups, q, k, v, pos, score = _read_inputs([40, 77], 2)
    idx, ok = block_sparse.rank_blocks(score, 2)
    with pytest.raises(ValueError, match="whole blocks"):
        block_sparse.sparse_read_attention(
            q, k[:, :30], v[:, :30], idx, ok, pos, spec, groups,
            interpret=True)


def _planted_scores(kind):
    rng = np.random.default_rng(11)
    score = rng.random((3, 2, 40)).astype(np.float32)
    if kind == "equal_scores":          # ties go to the lower block
        score[0, 0, 5:30] = 0.5
        score[1, 1, ::3] = score[1, 1, 0]
        score[2, :, :] = 0.25
    elif kind == "no_candidate_at_all":
        score[:] = -1.0
    elif kind == "fewer_candidates_than_k":
        score[0, 0, 3:] = -1.0
        score[1, :, 1:] = -1.0
        score[2, 1, :] = -1.0
    elif kind == "zeros_beside_candidates":     # a score of 0 is a choice
        score[:, :, 10:] = 0.0
        score[:, :, 30:] = -1.0
    return jnp.asarray(score)


@pytest.mark.parametrize("k", [1, 8, 40])
@pytest.mark.parametrize("kind", ["random", "equal_scores",
                                  "no_candidate_at_all",
                                  "fewer_candidates_than_k",
                                  "zeros_beside_candidates"])
def test_rank_blocks_is_top_k_without_the_sort(kind, k):
    """The same blocks in the same order as ``lax.top_k``, ties to the
    lower block number, and the same ``ok`` flags."""
    score = _planted_scores(kind)
    vals, idx = jax.lax.top_k(score, k)
    got, ok = jax.jit(lambda x: block_sparse.rank_blocks(x, k))(score)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(vals) >= 0.0)


def test_off_the_tpu_the_decode_read_is_the_gathered_one():
    """At a shape the kernel takes, a CPU run of
    ``sparse_decode_attention`` is ``top_k`` and the gathered read, bit
    for bit, and lowers to no kernel; the same function LOWERED for a TPU
    (from this host) holds the kernel and no ``top_k``."""
    rng = np.random.default_rng(9)
    b, s, heads, groups, d = 2, 512, 4, 2, 128
    spec = block_sparse.SparseSpec(kernel=32, stride=16, block=16, window=64,
                                   init_blocks=1, topk=4, dense_len=128)
    assert block_sparse.sparse_read_applies((b, s, groups * d), jnp.bfloat16,
                                            d, spec)
    assert not block_sparse.sparse_read_applies((b, s, groups * 8),
                                                jnp.bfloat16, 8, spec)
    assert not block_sparse.sparse_read_applies((b, 64, groups * d),
                                                jnp.bfloat16, d, spec)
    assert not block_sparse.sparse_read_applies(
        (b, s, groups * d), jnp.bfloat16, d,
        block_sparse.SparseSpec(kernel=16, stride=8, block=8, window=64))
    q = jnp.asarray(rng.normal(size=(b, heads, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, groups * d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, s, groups * d)), jnp.bfloat16)
    pos = jnp.asarray([200, 470], jnp.int32)
    ck = block_sparse.compress_keys(k, spec)

    def step(q, k, v, ck, pos):
        return block_sparse.sparse_decode_attention(q, k, v, ck, pos, spec,
                                                    groups)

    def by_hand(q, k, v, ck, pos):
        idx, ok = block_sparse.select_blocks(q[:, None], ck, pos[:, None],
                                             spec, groups)
        return block_sparse.gathered_decode_attention(
            q, k, v, idx[:, 0], ok[:, 0], pos, spec, groups)

    got = jax.jit(step)(q, k, v, ck, pos)
    want = jax.jit(by_hand)(q, k, v, ck, pos)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))
    # both KV heads' lanes ride with each head's chosen blocks
    assert np.asarray(got[2]).tolist() == [
        groups * (64 + 16 + 2 * 4 * 16)] * b
    traced = jax.jit(step).trace(q, k, v, ck, pos)
    here = traced.lower().as_text()
    assert "tpu_custom_call" not in here and "top_k" in here
    there = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in there and "top_k" not in there
    # and through the interpreter the kernel's path gives the same answer
    idx, ok = block_sparse.rank_blocks(block_sparse.block_scores(
        q[:, None], ck, pos[:, None], spec, groups)[:, 0], 4)
    o, attended, _ = block_sparse.sparse_read_attention(
        q, k, v, idx, ok, pos, spec, groups, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want[0]), atol=0.02)
    np.testing.assert_array_equal(np.asarray(attended), np.asarray(want[1]))


# --- through GenerationEngine -------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = _cfg("mixed")
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16)
    return cfg, zoo, net, dec


REQUESTS = [(50, 30), (12, 9), (40, 25), (33, 40), (20, 5), (60, 20)]


def _two_row_engine(dec):
    return GenerationEngine(dec, GenerationConfig(
        max_batch=2, fused_steps=4, kv_bucket_min=128, prompt_bucket_min=16))


def test_engine_rows_give_what_they_give_alone(served):
    """Six requests over two rows: they join and leave at different
    times, every row is reused, and each answer is token for token what
    ``generate`` gives the request alone."""
    _, _, _, dec = served
    with _two_row_engine(dec) as eng:
        handles = [eng.submit(_tokens(n, 10 + i).tolist(), max_new_tokens=m)
                   for i, (n, m) in enumerate(REQUESTS)]
        got = [eng.result(h) for h in handles]
        stats = eng.stats()
    assert stats["joined_total"] == len(REQUESTS)
    for i, (n, m) in enumerate(REQUESTS):
        alone = dec.generate(_tokens(n, 10 + i).tolist(), m, fused_steps=4)
        assert got[i] == alone, i


def test_engine_gives_rows_away_ahead_with_three_kinds_of_state(served):
    """The loop runs a window ahead: with the queue full from the start
    (all six enqueued before the loop admits) every request that ends by
    length hands its row to the next one BEHIND its last window, before
    that window is read. The recurrent state, the compressed keys and the
    KV pages of the row are the new tenant's from the next window on, and
    every answer is still what ``generate`` gives the request alone."""
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
    # four of the six joined a row whose tenant had not been read to its
    # end; no stop token, so no window ran empty
    assert stats["joins_ahead_total"] == len(REQUESTS) - 2
    assert stats["windows_empty_total"] == 0
    assert stats["windows_ahead_total"] >= stats["windows_total"] - 2


def test_engine_stop_token_with_a_window_ahead(served):
    """A stop token ends a row one window before the host sees it: the
    answer is the lone one's up to the stop token, with the other row
    decoding on beside it."""
    _, _, _, dec = served
    prompt, other = _tokens(33, 3).tolist(), _tokens(20, 4).tolist()
    alone = dec.generate(prompt, 30, fused_steps=4)
    eos = alone[9]
    want = alone[:alone.index(eos) + 1]
    with _two_row_engine(dec) as eng:
        with eng._cond:
            h = eng.submit(prompt, max_new_tokens=30, eos_id=eos)
            h2 = eng.submit(other, max_new_tokens=24)
        assert eng.result(h) == want
        assert eng.result(h2) == dec.generate(other, 24, fused_steps=4)
        assert h.out == want


def test_engine_deadline_with_a_window_in_flight_frees_all_three_states(
        served):
    """A deadline passes while a window with the row is in flight: the
    release lands behind it, its tokens for the row are dropped, and the
    next tenant of the row decodes as alone (the recurrent state was
    zeroed behind the window that still updated it)."""
    from deeplearning4j_tpu.parallel.batcher import DeadlineExpiredError
    from deeplearning4j_tpu.resilience.faults import FaultPlan

    _, _, _, dec = served
    prompt = _tokens(40, 6).tolist()
    alone = dec.generate(prompt, 12, fused_steps=4)
    plan = FaultPlan(seed=1).inject("decode.launch", probability=1.0,
                                    action="delay", delay_s=0.05)
    with _two_row_engine(dec) as eng:
        with plan.armed():
            dead = eng.submit(_tokens(50, 5).tolist(), max_new_tokens=60,
                              timeout_ms=250)
            with pytest.raises(DeadlineExpiredError):
                eng.result(dead)
        held = list(dead.out)
        assert eng.generate(prompt, max_new_tokens=12) == alone
        assert dead.out == held and eng.stats()["rows_in_use"] == 0


@pytest.mark.parametrize("kind", ["recurrent", "kv"])
def test_a_reused_row_shows_no_trace_of_its_last_tenant(served, kind):
    """A state full of a last tenant's values, then a join: the joined row
    decodes as from a clean state."""
    _, _, net, dec = served
    prompt = _tokens(40, 4).tolist()
    clean = dec.generate(prompt, 12)
    dirty = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 5, a.dtype), dec.new_state(128))
    real_new_state = dec.new_state
    dec.new_state = lambda s: dirty
    try:
        assert dec.generate(prompt, 12) == clean
    finally:
        dec.new_state = real_new_state


def test_release_zeroes_a_released_rows_recurrent_state(served):
    _, _, _, dec = served
    state = jax.tree_util.tree_map(
        lambda a: jnp.ones(a.shape, a.dtype), dec.new_state(128))
    out = dec.release_fn(128)(state, np.asarray([True, False]))
    s = np.asarray(out["caches"]["b1_mix"]["state"])
    assert s[0].all() and not s[1].any()
    assert not np.asarray(out["active"])[1]


def test_decode_window_returns_the_layers_counts(served):
    _, _, net, dec = served
    assert dec.counter_names == [
        "recurrent_state_updates", "sparse_attended_positions",
        "sparse_context_positions", "sparse_dense_fallback_queries",
        "sparse_read_positions"]
    state = dec.new_state(128)
    state = dict(state, active=jnp.asarray([True, True]),
                 positions=jnp.asarray([10, 70], jnp.int32),
                 max_new=jnp.asarray([99, 99], jnp.int32))
    _, toks, emitted, counts = dec.decode_fn(128, 4)(net.params, state)
    counts = dict(zip(dec.counter_names, np.asarray(counts).tolist()))
    assert np.asarray(emitted).all()
    # two lightning layers x two rows x four steps
    assert counts["recurrent_state_updates"] == 16
    # two sparse layers, two KV heads: row 0 dense (contexts 11..14), row 1
    # beyond dense_len (contexts 71..74)
    assert counts["sparse_dense_fallback_queries"] == 2 * 4
    assert counts["sparse_context_positions"] == 2 * 2 * (
        sum(range(11, 15)) + sum(range(71, 75)))
    assert counts["sparse_attended_positions"] < counts[
        "sparse_context_positions"]
    # what the steps streamed to attend that: off the TPU the gathered
    # read (the window of 16, the first block of 8, two chosen blocks of 8
    # a KV head with both heads' lanes) and, for the dense row's sake, the
    # first dense_len = 32 positions, all for both rows, two KV heads
    assert counts["sparse_read_positions"] == 2 * 2 * 4 * 2 * (
        16 + 8 + 2 * 2 * 8 + 32)
    assert counts["sparse_read_positions"] > counts[
        "sparse_attended_positions"]


def test_decode_window_through_the_kernel_gives_the_same_tokens(monkeypatch):
    """The decode window as a TPU lowers it, chosen here for a CPU run
    (the sortless choice and the kernel through the Pallas interpreter,
    steered in the test as ``test_kv_cache_layout`` steers the paged
    read): the tokens and the attended count of the gathered path, and
    ``sparse_read_positions`` says what the kernel copied."""
    from deeplearning4j_tpu.optimize import aot_cache

    def window():
        aot_cache.clear()       # the same graph, lowered the other way
        zoo, net, _ = _net(_cfg("sparse"))
        dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                          prompt_bucket_min=16)
        state = dec.new_state(128)
        state = dict(state, active=jnp.asarray([True, True]),
                     positions=jnp.asarray([40, 70], jnp.int32),
                     max_new=jnp.asarray([99, 99], jnp.int32))
        _, toks, _, counts = dec.decode_fn(128, 4)(net.params, state)
        return np.asarray(toks), dict(zip(dec.counter_names,
                                          np.asarray(counts).tolist()))

    want, gathered = window()
    kernel, choose = block_sparse.sparse_read_attention, \
        jax.lax.platform_dependent
    monkeypatch.setattr(block_sparse, "sparse_read_applies",
                        lambda *a: True)
    monkeypatch.setattr(
        block_sparse, "sparse_read_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *a, default=None, **per: (
            per["tpu"](*a) if getattr(per.get("tpu"), "__name__", "")
            == "in_place" else choose(*a, default=default, **per)))
    got, in_place = window()
    aot_cache.clear()
    np.testing.assert_array_equal(got, want)
    for name in ("sparse_attended_positions", "sparse_context_positions",
                 "sparse_dense_fallback_queries"):
        assert in_place[name] == gathered[name], name
    # two layers, two rows, four steps, two KV heads: the window from its
    # block's start (16 + 8), the first block, two chosen blocks a head
    assert in_place["sparse_read_positions"] == 2 * 2 * 4 * 2 * (
        24 + 8 + 2 * 8)
    assert gathered["sparse_read_positions"] == 2 * 2 * 4 * 2 * (
        16 + 8 + 2 * 2 * 8)


def test_engine_publishes_state_gauges_and_counters(served):
    from deeplearning4j_tpu import telemetry

    _, _, _, dec = served
    with _two_row_engine(dec) as eng:
        eng.generate(_tokens(45, 8).tolist(), max_new_tokens=10)
        text = telemetry.REGISTRY.render_prometheus()
    for kind in ("kv", "compressed_keys", "recurrent"):
        assert f'dl4j_gen_state_bytes{{kind="{kind}"}}' in text
    for name in ("sparse_attended_positions", "sparse_context_positions",
                 "sparse_dense_fallback_queries", "sparse_read_positions",
                 "recurrent_state_updates"):
        assert f"dl4j_{name}_total" in text
    assert eng.stats()["layer_counts"]["sparse_read_positions"] > 0
    spans = [e for e in telemetry.spans.events()
             if e["name"] in ("gen.prefill", "gen.prefill.launch")]
    assert spans and all(
        e["attrs"]["state_kinds"] == "compressed_keys,kv,recurrent"
        and e["attrs"]["prompt_bucket"] == 64 for e in spans[-2:])


def test_three_kinds_of_state_each_in_its_type():
    cfg = _cfg("mixed", weight_dtype="bfloat16", cache_dtype="bfloat16")
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=64,
                      prompt_bucket_min=16)
    caches = dec.new_state(64)["caches"]
    assert caches["b0_mix"]["k"].dtype == jnp.bfloat16
    assert caches["b0_mix"]["ck"].shape == (2, 16, 16)
    assert caches["b1_mix"]["state"].dtype == jnp.float32
    assert caches["b1_mix"]["state"].shape == (2, 4, 8, 8)
    assert net.params["b0_mix"]["Wq"].dtype == jnp.bfloat16
    assert net.params["b0_mix"]["q_norm"].dtype == jnp.float32
    assert caches["b0_mix"]["ck"].dtype == jnp.float32
    assert dec.state_bytes(64) == {
        "kv": 2 * 2 * 2 * 64 * 16 * 2, "compressed_keys": 2 * 2 * 16 * 16 * 4,
        "recurrent": 2 * 2 * 4 * 8 * 8 * 4}
    # bfloat16 weights and caches still decode what the float32 walk does
    out = dec.generate(_tokens(40, 6).tolist(), 6)
    assert len(out) == 6


def test_join_bucket_max_splits_a_group_of_joins(served):
    cfg, zoo, net, _ = served
    dec = zoo.decoder(net, max_batch=4, kv_bucket_min=128,
                      prompt_bucket_min=16, join_bucket_max=1)
    assert dec.join_ladder == [1]
    with GenerationEngine(dec, GenerationConfig(
            max_batch=4, fused_steps=2, kv_bucket_min=128,
            prompt_bucket_min=16, join_bucket_max=1)) as eng:
        hs = [eng.submit(_tokens(20 + i, i).tolist(), max_new_tokens=4)
              for i in range(4)]
        assert all(len(eng.result(h)) == 4 for h in hs)


# --- what the walks refuse, by name -----------------------------------------

def test_prefix_walk_refuses_the_new_layers_by_name(served):
    _, _, net, dec = served
    with pytest.raises(NotImplementedError) as e:
        dec._run_suffix(net.params, np.zeros((1, 16), np.int32),
                        np.ones((1,), np.int32), {}, np.ones((1,), np.int32))
    assert "'b0_mix' (BlockSparseAttentionLayer)" in str(e.value)
    assert "'b1_mix' (LightningAttentionLayer)" in str(e.value)
    assert "prefill_suffix" in str(e.value)


def test_engine_refuses_prefix_cache_by_name(served):
    _, _, _, dec = served
    with pytest.raises(ValueError) as e:
        GenerationEngine(dec, GenerationConfig(
            max_batch=2, kv_bucket_min=128, prompt_bucket_min=16,
            prefix_cache=True))
    assert "prefill_suffix" in str(e.value)
    assert "LightningAttentionLayer" in str(e.value)


def _graph_with(layer_or_vertex):
    from deeplearning4j_tpu.conf import InputType
    from deeplearning4j_tpu.conf.layers import (
        EmbeddingSequenceLayer,
        OutputLayer,
    )
    from deeplearning4j_tpu.conf.layers_attention import SelfAttentionLayer
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration

    g = (NeuralNetConfiguration.builder().seed(1).graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.recurrent(1, timesteps=16)))
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=11, n_out=8), "input")
    g.add_layer("attn", SelfAttentionLayer(n_out=8, n_heads=2, causal=True),
                "embed")
    g.add_layer("odd", layer_or_vertex, "attn")
    g.add_layer("output", OutputLayer(n_out=11), "odd")
    g.set_outputs("output")
    return ComputationGraph(g.build()).init()


def _refused():
    from deeplearning4j_tpu.conf.layers_attention import (
        LearnedSelfAttentionLayer,
        RecurrentAttentionLayer,
    )
    from deeplearning4j_tpu.conf.layers_cnn import (
        GlobalPoolingLayer,
        PoolingType,
    )
    from deeplearning4j_tpu.conf.layers_moe import MoELayer
    from deeplearning4j_tpu.conf.layers_rnn import LSTM

    return {"moe": lambda: MoELayer(n_experts=2, d_hidden=8, top_k=1),
            "pooling": lambda: GlobalPoolingLayer(
                pooling_type=PoolingType.AVG),
            "learned_attention": lambda: LearnedSelfAttentionLayer(
                n_out=8, n_heads=2, n_queries=16),
            "recurrent_attention": lambda: RecurrentAttentionLayer(
                n_out=8, n_heads=2),
            "lstm": lambda: LSTM(n_out=8)}


@pytest.mark.parametrize("kind", ["moe", "pooling", "learned_attention",
                                  "recurrent_attention", "lstm"])
def test_decoder_still_refuses_what_it_cannot_serve(kind):
    """A carry alone no longer refuses a layer (the lightning layer has
    one and is served); these are refused still: cross-row routing,
    pooling over time, the two older attention layers, and a recurrent
    layer without the cache interface."""
    net = _graph_with(_refused()[kind]())
    with pytest.raises(ValueError, match="'odd'.*not supported"):
        TransformerDecoder(net, max_batch=2, max_len=16)


# --- SelfAttentionLayer through the same interface --------------------------

def test_self_attention_serves_through_the_cache_interface():
    zoo = TransformerEncoder(vocab_size=31, embed_dim=16, n_heads=2,
                             n_layers=2, max_len=32, lm_head=True,
                             causal=True, seed=5)
    net = zoo.init()
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=32,
                      prompt_bucket_min=8)
    assert dec._cached == dec._attn and dec.counter_names == [
        "decode_kv_bucket_positions", "decode_kv_read_positions"]
    assert dec.state_bytes(32) == {"kv": 2 * 2 * 2 * 32 * 16 * 4}
    state = dec.new_state(32)
    out = dec.decode_fn(32, 2)(net.params, state)
    assert len(out) == 4            # the layers' counts beside the tokens
    assert np.asarray(out[3]).tolist() == [0, 0]    # no row is active
    layer = dec._layer("b0_attn")
    cache = layer.cache_init(2, 32, 16, jnp.float32)
    x = np.random.default_rng(0).normal(size=(2, 16)).astype(np.float32)
    pos = np.asarray([3, 9], np.int32)
    _, _, counts = layer.cache_step(net.params["b0_attn"], x, cache, pos)
    assert {n: np.asarray(c).tolist() for n, c in counts.items()} == {
        "decode_kv_read_positions": [32, 32],   # the masked read: all of it
        "decode_kv_bucket_positions": [32, 32]}
    prompt = _tokens(11, 3, 31).tolist()
    seq, want = list(prompt), []
    for _ in range(6):
        o = np.asarray(net.output(np.asarray([seq + [0] * (32 - len(seq))])))
        want.append(int(o[0, len(seq) - 1].argmax()))
        seq.append(want[-1])
    assert dec.generate(prompt, 6) == want


# --- the one walk, in its three shapes ----------------------------------------

@pytest.fixture(scope="module")
def gpt2_style():
    zoo = TransformerEncoder(vocab_size=97, embed_dim=16, n_heads=2,
                             n_layers=2, max_len=128, lm_head=True,
                             causal=True, seed=5)
    net = zoo.init()
    return net, zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                            prompt_bucket_min=16)


@pytest.mark.parametrize("decoder,walk", [
    ("gpt2_style", "prompt"), ("gpt2_style", "token"),
    ("gpt2_style", "suffix"), ("served", "prompt"), ("served", "token")])
def test_the_one_walk_in_each_of_its_shapes(request, decoder, walk):
    """``TransformerDecoder._walk`` under its three callers, on a prompt
    of 41 tokens (beyond ``dense_len`` for the hybrid) padded to a bucket
    of 64. ``prompt``: the logits are the graph's own ``output`` at the
    last valid position. ``token``: the prompt joined into row 1 of a
    cache and ONE token walked against it gives the logits the prompt
    walk gives on the prompt extended by that token. ``suffix`` (the
    layers with ``prefill_suffix``): the last 17 tokens walked against
    the first 24's K/V pages give the cold prompt walk's logits."""
    *_, net, dec = request.getfixturevalue(decoder)
    n, tp, s = 41, 64, 128
    toks = _tokens(n + 1, 11)
    padded = np.zeros((1, tp), np.int32)
    padded[0, :n] = toks[:n]
    lengths = np.asarray([n], np.int32)
    logits, kv = dec._run_prompt(net.params, padded, lengths)
    if walk == "prompt":
        # causal: the padding behind the prompt changes nothing before it
        probs = np.asarray(net.output(padded))[0, n - 1]
        np.testing.assert_allclose(jax.nn.log_softmax(logits[0]),
                                   np.log(probs), atol=2e-5)
        return
    if walk == "token":
        rows = np.asarray([1], np.int32)
        caches = {name: dec._layer(name).cache_join(c, kv[name], rows, s)
                  for name, c in dec.new_state(s)["caches"].items()}
        got, _, counts = dec._run_token(
            net.params, np.asarray([0, toks[n]], np.int32),
            np.asarray([0, n], np.int32), caches,
            active=np.asarray([False, True]))
        assert sorted(counts) == dec.counter_names
        padded[0, n] = toks[n]
        want, _ = dec._run_prompt(net.params, padded, lengths + 1)
        np.testing.assert_allclose(got[1], want[0], atol=2e-4)
        return
    pre, ts = 24, 32
    suffix = np.zeros((1, ts), np.int32)
    suffix[0, :n - pre] = toks[pre:n]
    pages = {name: {leaf: a[:, :32] for leaf, a in block.items()}
             for name, block in kv.items()}
    got, kv_sfx = dec._run_suffix(
        net.params, suffix, np.asarray([n - pre], np.int32), pages,
        np.asarray([pre], np.int32))
    np.testing.assert_allclose(got, logits, atol=2e-5)
    for name, block in kv_sfx.items():
        np.testing.assert_allclose(block["k"][:, :n - pre],
                                   kv[name]["k"][:, pre:n], atol=1e-5)


# --- the small layers --------------------------------------------------------

def test_gated_feed_forward_slices_long_inputs():
    from deeplearning4j_tpu.conf import InputType
    from deeplearning4j_tpu.conf.layers_hybrid import GatedFeedForwardLayer

    layer = GatedFeedForwardLayer(n_out=8, n_hidden=16, out_scale=0.5,
                                  rows_max=8)
    p = layer.init(jax.random.PRNGKey(0), InputType.recurrent(8))
    x = np.random.default_rng(1).normal(size=(2, 16, 8)).astype(np.float32)
    sliced, _ = layer.forward(p, {}, x)
    whole, _ = GatedFeedForwardLayer(
        n_out=8, n_hidden=16, out_scale=0.5).forward(p, {}, x)
    np.testing.assert_allclose(sliced, whole, atol=1e-6)
    want = 0.5 * (jax.nn.silu(x @ p["Wg"]) * (x @ p["Wu"])) @ p["Wd"]
    np.testing.assert_allclose(whole, want, atol=1e-5)


def test_hybrid_conf_round_trips_through_json():
    from deeplearning4j_tpu.conf.graph import ComputationGraphConfiguration

    conf = _zoo(_cfg("mixed")).conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    kinds = [type(v.vertex.layer).__name__ for v in again.vertices
             if hasattr(v.vertex, "layer")]
    assert kinds.count("LightningAttentionLayer") == 2
    assert kinds.count("BlockSparseAttentionLayer") == 2


def test_unknown_mixer_type_is_refused():
    with pytest.raises(ValueError, match="unknown mixer types"):
        _zoo(_cfg("mixed"), mixer_types=["hyena"], layer_indices=[0])
