"""The routed-experts decoder on the CPU at small sizes, seeded weights:
``conf.layers_moe.RoutedExpertsLayer`` and ``conf.layers_hybrid
.GatedAttentionLayer`` -> ``zoo.graphs.HybridDecoderLM`` ->
``ComputationGraph`` -> ``TransformerDecoder`` -> ``GenerationEngine``
against the plain reference (``benchmarks/reference/afmoe.py``, which
imports nothing of the program): logits, not tokens; the ring a window
layer keeps; the live tokens a layer without state is told.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.models import afmoe as model  # noqa: E402
from benchmarks.reference import afmoe as ref  # noqa: E402
from deeplearning4j_tpu.conf.layers_moe import (  # noqa: E402
    MoELayer,
    RoutedExpertsLayer,
)
from deeplearning4j_tpu.nn.decoding import TransformerDecoder  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops.attention import (  # noqa: E402
    grouped_causal_attention,
    reference_attention,
    window_ring_attention,
    window_ring_block,
    window_ring_update,
)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationConfig,
    GenerationEngine,
)

pytestmark = pytest.mark.decode

WINDOW = 16
PUBLISHED = (["sliding_attention"] * 3 + ["full_attention"]) * 8
STACKS = {"dense": [1], "window": [1, 5], "whole": [1, 5, 6, 7]}


def _cfg(stack="whole", **over):
    served = STACKS[stack]
    cfg = {"hidden_size": 32, "intermediate_size": 64,
           "moe_intermediate_size": 16, "vocab_size": 97,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "num_experts": 8, "num_experts_per_tok": 2,
           "num_shared_experts": 1, "num_dense_layers": 2,
           "layer_types": PUBLISHED, "layers_served": served,
           "num_hidden_layers": len(served), "sliding_window": WINDOW,
           "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
           "n_group": 1, "topk_group": 1, "hidden_act": "silu",
           "mup_enabled": True, "tie_word_embeddings": False,
           "rope_scaling": None, "rope_theta": 10000, "rms_norm_eps": 1e-5,
           "initializer_range": 0.3, "qk_gain_mean": 1.6,
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


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# --- the graph's forward against the reference ------------------------------

@pytest.mark.parametrize("stack", sorted(STACKS))
def test_graph_output_matches_reference(stack):
    cfg = _cfg(stack)
    _, net, w = _net(cfg)
    toks = _tokens(64, 1)          # four windows
    probs = np.asarray(net.output(toks[None]))[0]
    logits = np.asarray(ref.Forward(cfg)(w, toks, np.arange(64)))
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(np.log(probs), want, atol=3e-4)


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


# --- prefill, join, decode through the caches -------------------------------

# under the window, at its edge, beyond it, and past several wraps
@pytest.mark.parametrize("prompt_len", [9, 16, 24, 50])
@pytest.mark.parametrize("stack", ["window", "whole"])
def test_prefill_then_decode_matches_reference(stack, prompt_len):
    """Teacher-forced: the prompt through ``prompt_fn``'s walk, its block
    joined into row 1 of a dirty state, then 20 given tokens one by one
    through the decode walk; every step's LOGITS against the reference's
    full forward. The contexts cross the window of 16 and the ring wraps
    (a prompt of 50 has wrapped three times before its first step)."""
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
    assert kv["b0_mix"]["k"].shape == (1, WINDOW, 16)    # a ring, not tp
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
    for i in range(19):
        t = np.asarray([0, toks[prompt_len + i], 0], np.int32)
        pos = np.asarray([0, prompt_len + i, 0], np.int32)
        logits, caches = step(net.params, t, pos, caches)
        got.append(np.asarray(logits)[1])
    rows = prompt_len - 1 + np.arange(20)
    want = np.asarray(ref.Forward(cfg)(w, toks[:prompt_len + 19], rows))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_a_rows_logits_do_not_depend_on_its_co_tenants():
    """One decode step of row 1 alone and among two live co-tenants: the
    grouping of the slots by expert differs, the row's logits do not
    (beyond rounding)."""
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


# --- through GenerationEngine -------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                      prompt_bucket_min=16)
    return cfg, zoo, net, dec


REQUESTS = [(50, 30), (12, 9), (40, 25), (33, 40), (20, 5), (60, 20)]


def _two_row_engine(dec):
    return GenerationEngine(dec, GenerationConfig(
        max_batch=2, fused_steps=4, kv_bucket_min=128, prompt_bucket_min=16))


def test_engine_rows_give_what_they_give_alone(served):
    """Six requests over two rows, enqueued together: they join and leave
    at different times, every row is reused, an idle row rides beside a
    live one, and each answer is token for token what ``generate`` gives
    the request alone; the experts' counts ride the windows' outputs."""
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
    # three expert layers, two experts a token, live tokens only
    assert counts["moe_routed_slots"] == 3 * 2 * decoded
    assert counts["moe_experts_touched"] <= counts["moe_routed_slots"]
    assert counts["moe_max_load"] >= counts["moe_expert_layer_steps"]
    # a window layer reads its ring whole; its row holds its context
    assert counts["decode_kv_read_positions"] < \
        counts["decode_kv_bucket_positions"]


def test_a_reused_row_shows_no_trace_of_its_last_tenant(served):
    """A state full of a last tenant's values (rings and bucket alike),
    then a join of a prompt SHORTER than the window: the joined row
    decodes as from a clean state."""
    _, _, _, dec = served
    for n in (9, 40):
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


def test_idle_rows_and_padding_touch_no_expert(served):
    """By the counters: a decode window with one live row of two routes
    that row's slots alone, one with none routes nothing; a prompt's
    padding is routed nowhere."""
    _, _, net, dec = served
    names = dec.counter_names
    assert names == ["decode_kv_bucket_positions", "decode_kv_read_positions",
                     "moe_expert_layer_steps", "moe_experts_read",
                     "moe_experts_touched", "moe_max_load", "moe_routed_slots"]

    def window(active):
        state = dict(dec.new_state(128), active=jnp.asarray(active),
                     max_new=jnp.asarray([50, 50], jnp.int32))
        *_, counts = dec.decode_fn(128, 4)(net.params, state)
        return dict(zip(names, np.asarray(counts).tolist()))

    one = window([False, True])
    assert one["moe_routed_slots"] == 4 * 3 * 2          # K x layers x top_k
    assert one["moe_experts_touched"] == 4 * 3 * 2
    # one row's 2 slots over 8 experts go grouped: the touched are read
    assert one["moe_experts_read"] == one["moe_experts_touched"]
    assert one["moe_expert_layer_steps"] == 4 * 3
    assert one["moe_max_load"] == 4 * 3
    both = window([True, True])
    assert both["moe_routed_slots"] == 2 * one["moe_routed_slots"]
    none = window([False, False])
    assert (none["moe_routed_slots"] == none["moe_experts_touched"]
            == none["moe_expert_layer_steps"] == none["moe_max_load"]
            == none["moe_experts_read"] == 0)
    # the prompt walk: 9 real tokens in a bucket of 16
    tally = []
    prompts = np.zeros((1, 16), np.int32)
    prompts[0, :9] = _tokens(9, 3)
    key_mask = (np.arange(16)[None] < 9).astype(np.float32)
    dec._walk(net.params, prompts,
              lambda name, p, x: dec._layer(name).cache_prefill(
                  p, x, key_mask)[0],
              lengths=np.asarray([9], np.int32), live=key_mask,
              tally=tally.append)
    assert [int(c["moe_routed_slots"]) for c in tally] == [9 * 2] * 3


def test_state_bytes_by_kind_and_a_ring_that_does_not_grow():
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=2, kv_bucket_min=32,
                      prompt_bucket_min=16)
    assert dec.kv_ladder == [32, 64, 128]
    lane = 2 * 8 * 4                                 # a position's K, bytes
    for s in (32, 128):
        assert dec.state_bytes(s) == {
            "kv_ring": 3 * 2 * 2 * WINDOW * lane, "kv": 2 * 2 * s * lane}
    state = dec.grow_fn(32, 64)(dec.new_state(32))
    assert state["caches"]["b0_mix"]["k"].shape == (2, WINDOW, 16)
    assert state["caches"]["b3_mix"]["k"].shape == (2, 64, 16)
    # an answer that crosses two bucket hops is the one-bucket answer
    prompt = _tokens(20, 8).tolist()
    wide = zoo.decoder(net, max_batch=2, kv_bucket_min=128,
                       prompt_bucket_min=16)
    with GenerationEngine(dec, GenerationConfig(
            max_batch=2, fused_steps=4, kv_bucket_min=32,
            prompt_bucket_min=16)) as eng:
        assert eng.generate(prompt, max_new_tokens=70) == wide.generate(
            prompt, 70, fused_steps=4)


def test_prefix_walk_refuses_the_ring_layers_by_name(served):
    _, _, _, dec = served
    with pytest.raises(NotImplementedError, match="GatedAttentionLayer"):
        dec._need("prefill_suffix", "the prefix-cache suffix walk")


# --- the expert layer ---------------------------------------------------------

@pytest.mark.parametrize("experts,top_k,holders", [
    (8, 2, 4),          # each quarter of the experts
    (256, 8, 16),       # one chip's sixteenth of 256, a deployment's share
])
def test_expert_shares_add_up_to_the_uncut_reference_layer(experts, top_k,
                                                           holders):
    """``experts_held`` = each holder's ``experts / holders``: every holder
    routes over all the experts and computes its own experts' part and the
    shared expert; the parts, the shared expert counted once, add up to
    what the reference gives for the whole layer."""
    cfg = _cfg("window", num_experts=experts, num_experts_per_tok=top_k)
    _, _, w = _net(cfg)
    p = w["b1_ffn"]
    n = experts // holders
    u = jax.random.normal(jax.random.PRNGKey(1), (40, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_experts(cfg, u, p))
        shared = np.asarray(ref.gated(u, p["Sg"], p["Su"], p["Sd"]))
    total = np.zeros_like(want)
    for first in range(0, experts, n):
        layer = RoutedExpertsLayer(
            n_out=32, n_experts=experts, n_hidden=16, top_k=top_k,
            n_shared_hidden=16, route_scale=2.826, experts_held=(first, n))
        part = {**p, **{k: p[k][first:first + n] for k in ("Wg", "Wu", "Wd")}}
        y, counts = layer.forward_live(part, u, np.ones((40,), bool))
        held = dict(cfg, experts_held=(first, n))
        np.testing.assert_allclose(
            y, ref.routed_experts(held, u, part), atol=1e-5)
        assert int(counts["moe_experts_touched"]) <= n
        total += np.asarray(y) - shared
    np.testing.assert_allclose(total + shared, want, atol=2e-5)


def test_routing_is_dropless_whatever_the_load(monkeypatch):
    """Every token chooses the same two experts (a bias that lifts them
    over the rest): a capacity layer would drop most of them; here every
    slot is computed and the fullest expert holds every token."""
    from deeplearning4j_tpu.conf import layers_moe

    layer = RoutedExpertsLayer(n_out=16, n_experts=8, n_hidden=8, top_k=2)
    monkeypatch.setattr(layers_moe, "ROUTED_ROWS_MAX", 16)
    p = layer.init(jax.random.PRNGKey(0),
                   type("T", (), {"size": 16})(), jnp.float32)
    p["b"] = p["b"].at[jnp.asarray([3, 5])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16), jnp.float32)
    y, counts = layer.forward_live(p, x, np.ones((4, 16), bool))
    assert {k: int(v) for k, v in counts.items()} == {
        "moe_routed_slots": 128, "moe_experts_touched": 2,
        "moe_expert_layer_steps": 1, "moe_max_load": 64,
        # four slices of 16 tokens, 4 slots an expert: every expert held
        "moe_experts_read": 4 * 8}
    experts, w = layer.route(p, x.reshape(-1, 16))
    assert set(np.asarray(experts).ravel().tolist()) == {3, 5}
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, atol=1e-6)
    # slices of ROUTED_ROWS_MAX tokens give what the whole gives
    monkeypatch.undo()
    whole = layer.forward_live(p, x, np.ones((4, 16), bool))[0]
    np.testing.assert_allclose(y, whole, atol=1e-6)


# --- the few rows' product as a TPU lowers it: the touched experts' kernel ---

def _steer_to_the_kernel(monkeypatch):
    """What lowering for a TPU chooses, chosen here for a CPU run: the
    experts' ``tpu`` branch, its kernel through the Pallas interpreter,
    at widths that fill no 128-lane tile (steered in the test, as
    ``test_kv_cache_layout`` steers the paged read): the touched experts'
    for a step's rows, the one over tiles of 8 rows of one expert for a
    prompt's."""
    from deeplearning4j_tpu.ops import routed_experts

    kernel, choose = routed_experts.touched_experts_ffn, \
        jax.lax.platform_dependent
    tiles = routed_experts.grouped_experts_ffn
    monkeypatch.setattr(routed_experts, "touched_experts_applies",
                        lambda *a: True)
    monkeypatch.setattr(
        routed_experts, "touched_experts_ffn",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(
        routed_experts, "grouped_experts_ffn",
        lambda *a, **kw: tiles(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(routed_experts, "ROW_TILE", 8)
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *a, default=None, **per: (
            per["tpu"](*a) if getattr(per.get("tpu"), "__name__", "")
            in ("touched", "tiled") else choose(*a, default=default, **per)))


def _expert_layer(held=(0, 0)):
    layer = RoutedExpertsLayer(
        n_out=32, n_experts=16, n_hidden=16, top_k=2, n_shared_hidden=16,
        route_scale=2.826, experts_held=held)
    p = layer.init(jax.random.PRNGKey(4), type("T", (), {"size": 32})(),
                   jnp.float32)
    return layer, p


def _step_operands(layer, p, n, live=None):
    """What the layer hands the product for ``n`` seeded rows: ``(x, w
    [n, held], sizes [held])``."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 32), jnp.float32)
    live = jnp.ones((n,), bool) if live is None else jnp.asarray(live)
    *_, w, chosen, sizes = layer._held_slots(p, x, live)
    return x, layer._by_row(w, chosen), sizes


@pytest.mark.parametrize("rows", [1, 8, 32])
def test_kernel_reads_the_touched_experts_alone(rows):
    """The kernel under the Pallas interpreter against ``_every_expert``:
    the same sum, and with the matrices of every expert no row chose set
    to NaN still the same and finite, which a product over all of them
    cannot give (``0 * NaN``)."""
    from deeplearning4j_tpu.ops.routed_experts import touched_experts_ffn

    layer, p = _expert_layer()
    x, w, sizes = _step_operands(layer, p, rows)
    touched = np.asarray(sizes) > 0
    assert touched.any() and (rows > 8 or not touched.all())
    want = np.asarray(layer._every_expert(p, x, w))
    hole = jnp.where(jnp.asarray(touched)[:, None, None], 0.0, jnp.nan)
    stacks = [p[k] + hole for k in ("Wg", "Wu", "Wd")]
    got = np.asarray(touched_experts_ffn(x, *stacks, w, sizes,
                                         interpret=True))
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    if not touched.all():
        holed = dict(p, **dict(zip(("Wg", "Wu", "Wd"), stacks)))
        assert np.isnan(np.asarray(layer._every_expert(holed, x, w))).all()


@pytest.mark.parametrize("live", ["one_idle_row", "no_live_row"])
def test_kernel_gives_idle_rows_and_an_empty_list_zeros(live):
    """A row that is not live weighs nothing anywhere: its sum is zero
    beside its live neighbours' unchanged one; with no live row the list
    of touched experts is empty and the output is zeros whatever the
    matrices hold (the one grid step there is reads no matrix into it)."""
    from deeplearning4j_tpu.ops.routed_experts import (
        touched_experts_ffn,
        touched_list,
    )

    layer, p = _expert_layer()
    mask = np.ones((8,), bool)
    mask[[3] if live == "one_idle_row" else slice(None)] = False
    x, w, sizes = _step_operands(layer, p, 8, mask)
    stacks = [p[k] for k in ("Wg", "Wu", "Wd")]
    if live == "no_live_row":
        assert int(touched_list(sizes)[1]) == 0
        stacks = [a + jnp.nan for a in stacks]
    got = np.asarray(touched_experts_ffn(x, *stacks, w, sizes,
                                         interpret=True))
    assert (got[~mask] == 0).all()
    if mask.any():
        np.testing.assert_allclose(
            got, np.asarray(layer._every_expert(p, x, w)), atol=2e-5)
        assert np.abs(got[mask]).min(axis=1).max() > 0


def test_touched_list_is_the_touched_experts_in_order():
    from deeplearning4j_tpu.ops.routed_experts import touched_list

    sizes = jnp.asarray([0, 3, 0, 0, 1, 9, 0, 2], jnp.int32)
    ids, count = touched_list(sizes)
    assert int(count) == 4
    # the steps past the list's end (never run) name its last expert
    assert np.asarray(ids).tolist() == [1, 4, 5, 7, 7, 7, 7, 7]
    ids, count = touched_list(jnp.zeros((8,), jnp.int32))
    assert int(count) == 0 and np.asarray(ids).tolist() == [0] * 8


@pytest.mark.parametrize("rows", [1, 8, 32])
def test_layer_through_the_kernel_gives_the_layers_sum(rows, monkeypatch):
    """``forward_live`` as a TPU lowers it against itself as the CPU
    does (grouped at 1 and 8 rows, every expert held at 32), one idle
    row among them; ``moe_experts_read`` is the touched experts in the
    kernel's branch and in the grouped product's, and the experts held in
    the batched product's."""
    layer, p = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 32), jnp.float32)
    live = np.arange(rows) != 2
    want, plain = layer.forward_live(p, x, live)
    _steer_to_the_kernel(monkeypatch)
    got, kernel = layer.forward_live(p, x, live)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name in ("moe_routed_slots", "moe_experts_touched", "moe_max_load",
                 "moe_expert_layer_steps"):
        assert int(kernel[name]) == int(plain[name]), name
    assert int(kernel["moe_experts_read"]) == int(
        kernel["moe_experts_touched"]) <= 16
    assert int(plain["moe_experts_read"]) == (
        16 if rows == 32 else int(plain["moe_experts_touched"]))


def test_expert_shares_add_up_through_the_kernel(monkeypatch):
    """``experts_held`` quarters through the kernel's branch: each holder
    reads the touched ones of ITS four experts, and the parts, the shared
    expert counted once, add up to what one holder of all sixteen gives
    by the batched product."""
    whole, p = _expert_layer()
    u = jax.random.normal(jax.random.PRNGKey(1), (32, 32), jnp.float32)
    live = np.ones((32,), bool)
    want, counts = whole.forward_live(p, u, live)
    assert int(counts["moe_experts_read"]) == 16        # every expert held
    shared = np.asarray(ref.gated(u, p["Sg"], p["Su"], p["Sd"]))
    _steer_to_the_kernel(monkeypatch)
    total, read = np.zeros_like(shared), 0
    for first in (0, 4, 8, 12):
        layer, _ = _expert_layer(held=(first, 4))
        part = {**p, **{k: p[k][first:first + 4] for k in ("Wg", "Wu", "Wd")}}
        y, counts = layer.forward_live(part, u, live)
        assert int(counts["moe_experts_read"]) == int(
            counts["moe_experts_touched"]) <= 4
        read += int(counts["moe_experts_read"])
        total += np.asarray(y) - shared
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert read == 16       # 64 slots over 16 experts leave none untouched


def test_decode_window_through_the_kernel_gives_the_same_logits(monkeypatch):
    """One decode step of the whole stack as a TPU lowers its expert
    layers (the kernel through the interpreter, inside the jitted walk)
    against the CPU's: the same logits, the same routing counts, and
    ``moe_experts_read`` the touched experts where the CPU's grouped
    product read them too."""
    cfg = _cfg()
    zoo, net, _ = _net(cfg)
    dec = zoo.decoder(net, max_batch=3, kv_bucket_min=128,
                      prompt_bucket_min=16)
    caches = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(3), a.shape, a.dtype),
        dec.new_state(128)["caches"])
    t = np.asarray([5, 17, 60], np.int32)
    pos = np.asarray([30, 41, 7], np.int32)
    active = np.asarray([True, False, True])

    def step():
        logits, _, counts = jax.jit(
            lambda: dec._run_token(net.params, t, pos, caches, active))()
        return np.asarray(logits), {k: int(np.sum(np.where(
            active, v, 0) if np.ndim(v) else v)) for k, v in counts.items()}

    want, plain = step()
    _steer_to_the_kernel(monkeypatch)
    got, kernel = step()
    np.testing.assert_allclose(got[active], want[active], atol=2e-5,
                               rtol=2e-5)
    assert kernel == plain
    # three expert layers, two live rows of two slots each
    assert kernel["moe_routed_slots"] == 3 * 2 * 2
    assert 3 * 2 <= kernel["moe_experts_read"] == kernel[
        "moe_experts_touched"] <= 3 * 4


# --- a prompt's product as a TPU lowers it: tiles of one expert -------------

# slots an expert held, slots of other holders (sorted behind every
# group, no tile), the SwiGLU's clamp and the experts' hidden width, in
# tiles of 8 rows; at 256 the hidden width goes through in two blocks
GROUPED_CASES = {
    "skewed_with_empty_experts": ([0, 17, 0, 3, 0, 0, 1, 9], 0, 0.0, 16),
    "a_group_over_three_tiles": ([2, 20, 1, 1], 0, 0.0, 16),
    "held_subset_others_behind": ([5, 0, 9, 2], 40, 0.0, 16),
    "swiglu_limit": ([6, 11, 3, 12], 0, 0.5, 16),
    "slots_no_multiple_of_tm": ([7, 6, 13], 0, 0.0, 16),
    "hidden_in_two_blocks": ([4, 9, 0, 6], 13, 0.5, 256),
}


def _grouped_operands(sizes, others, top_k=2, d=32, h=16, n_out=24):
    """Seeded operands of the grouped product whose experts get exactly
    ``sizes`` slots, beside ``others`` slots of other holders, shuffled:
    ``(x, Wg, Wu, Wd, group, w, sizes)``."""
    held = len(sizes)
    group = np.repeat(np.arange(held + 1), list(sizes) + [others])
    rng = np.random.default_rng(sum(sizes) + others)
    group = rng.permutation(group).astype(np.int32)
    assert group.size % top_k == 0
    k = jax.random.split(jax.random.PRNGKey(group.size), 5)
    return (jax.random.normal(k[0], (group.size // top_k, d), jnp.float32),
            0.3 * jax.random.normal(k[1], (held, d, h), jnp.float32),
            0.3 * jax.random.normal(k[2], (held, d, h), jnp.float32),
            0.3 * jax.random.normal(k[3], (held, h, n_out), jnp.float32),
            jnp.asarray(group),
            jax.random.uniform(k[4], (group.size,), jnp.float32),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_kernel_gives_the_ragged_product(case, monkeypatch):
    """The kernel over tiles of one expert, under the Pallas interpreter,
    against ``grouped_experts_ragged`` (``jax.lax.ragged_dot``): the same
    sums to float32 rounding; with the matrices of every expert nobody
    chose set to NaN still the same and finite (those are never read)."""
    from deeplearning4j_tpu.ops import routed_experts

    monkeypatch.setattr(routed_experts, "ROW_TILE", 8)
    sizes, others, limit, h = GROUPED_CASES[case]
    # a step's matrices may hold one byte: as many blocks as whole tiles
    monkeypatch.setattr(routed_experts, "STEP_BYTES_MAX", 1)
    assert routed_experts.hidden_blocks(32, h, 24, 4) == h // 128 or h < 256
    x, Wg, Wu, Wd, group, w, sz = _grouped_operands(sizes, others, h=h)
    want = np.asarray(routed_experts.grouped_experts_ragged(
        x, Wg, Wu, Wd, group, w, sz, 2, limit))
    hole = jnp.where((sz > 0)[:, None, None], 0.0, jnp.nan)
    got = np.asarray(routed_experts.grouped_experts_ffn(
        x, Wg + hole, Wu + hole, Wd + hole, group, w, sz, 2, limit,
        interpret=True))
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # a token whose slots are all other holders' sums to zero
    mine = np.asarray(group).reshape(-1, 2) < len(sizes)
    assert (got[~mine.any(axis=1)] == 0).all()


def test_tile_layout_starts_each_expert_on_a_tile_of_its_own():
    from deeplearning4j_tpu.ops.routed_experts import tile_layout

    sizes = jnp.asarray([0, 10, 0, 3, 8], jnp.int32)
    group = jnp.asarray([4] * 8 + [1] * 10 + [3] * 3 + [5] * 3, jnp.int32)
    ids, count, slot, valid, row = tile_layout(group, sizes, 4)
    # 24 slots in tiles of 4: the bound is 24 // 4 + 5 tiles
    assert ids.shape == (11,) and slot.shape == valid.shape == (44,)
    assert int(count) == 3 + 1 + 2
    assert np.asarray(ids)[:6].tolist() == [1, 1, 1, 3, 4, 4]
    valid = np.asarray(valid)
    assert valid.sum() == 21 and not valid[24:].any()
    assert valid[:12].tolist() == [True] * 10 + [False] * 2
    # each held slot's row holds that slot; other holders' slots have none
    row, slot = np.asarray(row), np.asarray(slot)
    held = np.asarray(group) < 5
    assert (slot[row[held]] == np.flatnonzero(held)).all()
    assert valid[row[held]].all() and (row[~held] == 0).all()


@pytest.mark.parametrize("limit", [0.0, 0.5])
def test_grouped_kernel_gradient_is_the_ragged_products(limit, monkeypatch):
    """The kernel's ``custom_vjp``: its gradient with respect to the
    tokens, the three stacks and the slots' weights is ``ragged_dot``'s."""
    from deeplearning4j_tpu.ops import routed_experts

    monkeypatch.setattr(routed_experts, "ROW_TILE", 8)
    x, Wg, Wu, Wd, group, w, sizes = _grouped_operands([5, 0, 9, 2], 16)

    def grads(product, **kw):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(product(
            a[0], a[1], a[2], a[3], group, a[4], sizes, 2, limit, **kw))),
            argnums=(0, 1, 2, 3, 4))(x, Wg, Wu, Wd, w)

    want = grads(routed_experts.grouped_experts_ragged)
    got = grads(routed_experts.grouped_experts_ffn, interpret=True)
    for name, a, b in zip(("x", "Wg", "Wu", "Wd", "w"), got, want):
        assert np.abs(np.asarray(b)).max() > 0, name
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("held", [(0, 0), (4, 4)])
def test_prompt_through_the_grouped_kernel_gives_the_layers_sum(
        held, monkeypatch):
    """``forward_live`` of a prompt's 200 tokens (400 slots: beyond 24 an
    expert, so every platform groups them) as a TPU lowers it, through
    the kernel over tiles of 8 rows, against the CPU's ``ragged_dot``:
    the same sum, the same counts; a padding token routes nowhere."""
    layer, p = _expert_layer(held)
    x = jax.random.normal(jax.random.PRNGKey(41), (200, 32), jnp.float32)
    live = np.arange(200) % 17 != 3
    want, plain = layer.forward_live(p, x, live)
    _steer_to_the_kernel(monkeypatch)
    got, kernel = layer.forward_live(p, x, live)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert {k: int(v) for k, v in kernel.items()} == {
        k: int(v) for k, v in plain.items()}
    assert int(kernel["moe_experts_read"]) == int(
        kernel["moe_experts_touched"])
    assert int(kernel["moe_routed_slots"]) <= 2 * int(live.sum())


@pytest.mark.parametrize("dropless", [False, True])
def test_decoder_refuses_the_capacity_layer_and_serves_the_dropless(dropless):
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
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=31, n_out=16), "input")
    g.add_layer("attn", SelfAttentionLayer(n_out=16, n_heads=2, causal=True),
                "embed")
    odd = (RoutedExpertsLayer(n_out=16, n_experts=4, n_hidden=8, top_k=2)
           if dropless else MoELayer(n_experts=4, d_hidden=8, top_k=2))
    g.add_layer("odd", odd, "attn")
    g.add_layer("output", OutputLayer(n_out=31), "odd")
    g.set_outputs("output")
    net = ComputationGraph(g.build()).init()
    if not dropless:
        with pytest.raises(ValueError, match="'odd'.*MoELayer.*not supported"):
            TransformerDecoder(net, max_batch=2, max_len=16)
        return
    dec = TransformerDecoder(net, max_batch=2, max_len=16, kv_bucket_min=16,
                             prompt_bucket_min=8)
    assert "moe_routed_slots" in dec.counter_names
    assert len(dec.generate([1, 2, 3], 5)) == 5


# --- the ops ------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 16, 40])
@pytest.mark.parametrize("t", [64, 128])
def test_grouped_causal_attention_matches_the_oracle(window, t):
    rng = np.random.default_rng(t + window)
    b, h, g, d = 2, 4, 2, 8
    q = rng.normal(size=(b, t, h, d)).astype(np.float32)
    k = rng.normal(size=(b, t, g * d)).astype(np.float32)
    v = rng.normal(size=(b, t, g * d)).astype(np.float32)
    got = grouped_causal_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), g, window, q_chunk=32)
    kh = np.repeat(k.reshape(b, t, g, d), h // g, axis=2)
    vh = np.repeat(v.reshape(b, t, g, d), h // g, axis=2)
    pos = np.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen &= pos[None, :] > pos[:, None] - window
    s = np.einsum("bqhd,bkhd->bhqk", q, kh) / np.sqrt(d)
    s = np.where(seen[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), vh)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if not window:          # and the repository's own oracle
        ours = reference_attention(
            jnp.swapaxes(jnp.asarray(q), 1, 2), jnp.swapaxes(jnp.asarray(kh), 1, 2),
            jnp.swapaxes(jnp.asarray(vh), 1, 2), causal=True)
        np.testing.assert_allclose(got, jnp.swapaxes(ours, 1, 2), atol=2e-5)


@pytest.mark.parametrize("length", [1, 5, 16, 17, 40, 64])
def test_ring_block_holds_the_last_window_each_in_its_slot(length):
    t, w = 64, 16
    k = jnp.arange(2 * t * 3, dtype=jnp.float32).reshape(2, t, 3)
    ring = np.asarray(window_ring_block(
        k, jnp.asarray([length, t], jnp.int32), w))
    for j in range(w):
        live = [p for p in range(length) if p % w == j]
        want = np.asarray(k)[0, live[-1]] if live else np.zeros(3)
        np.testing.assert_array_equal(ring[0, j], want)
    np.testing.assert_array_equal(ring[1], np.asarray(k)[1, t - w:])


def test_ring_step_attends_the_window_and_nothing_older():
    """Tokens written one by one into a ring of 8 from a dirty start: at
    every position the read equals attention over the last 8 keys."""
    rng = np.random.default_rng(0)
    w, g, d, h = 8, 2, 4, 4
    keys = rng.normal(size=(30, g * d)).astype(np.float32)
    vals = rng.normal(size=(30, g * d)).astype(np.float32)
    kr = jnp.full((1, w, g * d), 9.0)
    vr = jnp.full((1, w, g * d), 9.0)
    for t in range(30):
        pos = jnp.asarray([t], jnp.int32)
        kr = window_ring_update(kr, jnp.asarray(keys[None, t:t + 1]), pos)
        vr = window_ring_update(vr, jnp.asarray(vals[None, t:t + 1]), pos)
        q = rng.normal(size=(1, h, d)).astype(np.float32)
        got = np.asarray(window_ring_attention(jnp.asarray(q), kr, vr, pos,
                                               g))[0]
        lo = max(0, t - w + 1)
        want = grouped_causal_attention(
            jnp.asarray(q)[:, None], jnp.asarray(keys[None, lo:t + 1]),
            jnp.asarray(vals[None, lo:t + 1]), g, offset=t - lo)[0, 0]
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_routed_conf_round_trips_through_json():
    from deeplearning4j_tpu.conf.graph import ComputationGraphConfiguration

    conf = model.zoo(_cfg()).conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    layers = {v.name: v.vertex.layer for v in again.vertices
              if hasattr(v.vertex, "layer")}
    kinds = [type(layers[n]).__name__
             for n in ("b0_mix", "b3_mix", "b0_ffn", "b1_ffn", "b1_ffn_norm")]
    assert kinds == ["GatedAttentionLayer", "GatedAttentionLayer",
                     "GatedFeedForwardLayer", "RoutedExpertsLayer",
                     "RMSNormLayer"]
    assert layers["b0_mix"].window == WINDOW
    assert layers["b3_mix"].window == 0
    assert layers["b3_mix"].rope_theta == 0.0
    assert tuple(layers["b1_ffn"].experts_held) == (0, 8)
