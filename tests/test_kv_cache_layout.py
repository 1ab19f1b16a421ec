"""The KV cache's layout, ``[batch, positions, heads * head_dim]``: the
three functions that read and write it against the oracle, the arithmetic
of the TPU's (8, 128) tiles, the decode executables' jaxprs (nothing
cache-sized but the write and the two products; the caches donated), and
the compiled decode window for a v5e (no relayout copy outside the loop,
one layout at rest and in the loop, the cache written by
``dynamic-update-slice`` and read by the paged kernel and by nothing else)
with no chip attached; and the hybrid decoder's decode window, prompt and
join compiled the same way (each kind of state, KV, compressed keys,
recurrent, taken whole by its write and its layer's read alone; the decode
window with no ``sort`` and no ``gather``), and the
routed-experts decoder's (a full layer's bucket, a window layer's ring;
no ``[tokens, experts, hidden]`` array in the prompt walk).

The ahead-of-time compiles load the TPU's compiler: they stay in THIS file,
and the topology is described inside a fixture, never at import.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import (
    DECODE_PAGE_BYTES,
    bounded_decode_attention,
    cache_update,
    chunk_decode_attention,
    decode_attention,
    decode_page,
    exact_parts_dot,
    paged_decode_attention,
    reference_attention,
)
from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

pytestmark = pytest.mark.decode

# (heads, head size): the benchmark's GPT-2-large, chip_smoke.py's
# GPT-2-small widths, a model with heads of 128, a toy
GEOMETRIES = [(20, 64), (12, 64), (8, 128), (4, 32)]


# --- the three functions against the oracle ---------------------------------

@pytest.mark.parametrize("tq", [1, 5])
@pytest.mark.parametrize("heads,hs", GEOMETRIES)
def test_cache_ops_match_reference(heads, hs, tq):
    """Write a ``tq``-token block at per-row positions into a cache full
    of stale values, attend, and compare each row with
    ``reference_attention`` over exactly the slots that row may see."""
    b, s = 3, 64
    rng = np.random.default_rng(heads * 1000 + hs + tq)
    positions = np.asarray([0, 17, s - tq], np.int32)
    e = heads * hs
    stale_k = rng.normal(size=(b, s, e)).astype(np.float32) * 3.0
    stale_v = rng.normal(size=(b, s, e)).astype(np.float32) * 3.0
    q = rng.normal(size=(b, tq, heads, hs)).astype(np.float32)
    k_new = rng.normal(size=(b, tq, e)).astype(np.float32)
    v_new = rng.normal(size=(b, tq, e)).astype(np.float32)

    kc = cache_update(jnp.asarray(stale_k), jnp.asarray(k_new), positions)
    vc = cache_update(jnp.asarray(stale_v), jnp.asarray(v_new), positions)
    assert kc.shape == (b, s, e)
    for i, p in enumerate(positions):
        want = stale_k[i].copy()
        want[p:p + tq] = k_new[i]
        np.testing.assert_array_equal(np.asarray(kc[i]), want)

    if tq == 1:
        got = decode_attention(jnp.asarray(q[:, 0]), kc, vc, positions)
        got = np.asarray(got)[:, None]
    else:
        got = np.asarray(chunk_decode_attention(jnp.asarray(q), kc, vc,
                                                positions))
    for i, p in enumerate(positions):
        live = p + tq  # slots 0 .. p + tq - 1; query j sees 0 .. p + j
        heads_first = lambda c: jnp.swapaxes(  # noqa: E731
            c[i:i + 1, :live].reshape(1, live, heads, hs), 1, 2)
        kh, vh = heads_first(kc), heads_first(vc)           # [1, h, t, d]
        qh = jnp.swapaxes(jnp.asarray(q[i:i + 1]), 1, 2)    # [1, h, tq, d]
        want = reference_attention(qh, kh, vh, causal=True)
        np.testing.assert_allclose(
            got[i], np.asarray(jnp.swapaxes(want, 1, 2))[0],
            rtol=2e-5, atol=2e-5)


# --- the bounded read against the masked read ---------------------------------

def _ragged(page, s):
    """Per-row positions at the places a page bound can go wrong: the
    first slot, the last slot of a page, the first of the next, the
    last of the bucket, and two in the middle of a page."""
    return np.asarray([0, page - 1, page, s - 1, 17, 2 * page + 5], np.int32)


def _cache(rng, shape, dtype):
    """Seeded keys or values in the cache's dtype, and the float32 array
    that holds the same numbers (a bfloat16 value is exact in float32)."""
    c = rng.normal(size=shape).astype(np.float32)
    if dtype == "bfloat16":
        c = np.asarray(jnp.asarray(c, jnp.bfloat16).astype(jnp.float32))
    return c


# (page, cache dtype): a float32 cache at the pages it has run at since
# PR 30, a bfloat16 one at the longer pages PR 38's decode_page gives it
# (the page stays bfloat16, the products are exact_parts_dot)
PAGED = [(16, "float32"), (128, "float32"), (256, "bfloat16"),
         (512, "bfloat16")]


@pytest.mark.parametrize("page,dtype", PAGED)
@pytest.mark.parametrize("heads,hs", GEOMETRIES)
def test_paged_read_matches_masked_read(heads, hs, page, dtype):
    """The kernel the decode step runs on the TPU (here through the
    Pallas interpreter) against ``decode_attention`` of the same numbers
    in float32, rows at ragged positions over caches FULL of a retired
    tenant's keys and values: large, finite, and different beyond every
    row's position, so a page or a slot read past the bound shows."""
    s = 4 * page
    positions = _ragged(page, s)
    b, e = len(positions), heads * hs
    rng = np.random.default_rng(heads * 100 + hs + page)
    k = _cache(rng, (b, s, e), dtype)
    v = _cache(rng, (b, s, e), dtype)
    q = jnp.asarray(rng.normal(size=(b, heads, hs)).astype(np.float32))
    want = np.asarray(decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                       positions))
    beyond = np.arange(s)[None, :, None] > positions[:, None, None]
    stale_k = np.where(beyond, 300.0 * rng.normal(size=k.shape), k)
    stale_v = np.where(beyond, 300.0 * rng.normal(size=v.shape), v)
    got = paged_decode_attention(
        q, jnp.asarray(stale_k, dtype), jnp.asarray(stale_v, dtype),
        positions, page=page, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,hs", GEOMETRIES)
def test_bounded_read_off_the_tpu_is_the_masked_read(heads, hs):
    """On the CPU the decode path IS the masked read, bit for bit, and
    says so: it read the whole bucket. A position past the bucket (a
    retired row's) reads as the last slot, as the write clamps it."""
    s, e = 256, heads * hs
    assert decode_page(s, e, 4) == 128 and decode_page(128, e, 4) is None
    assert decode_page(s, e + 64, 4) is None
    positions = np.asarray([0, 127, 128, s - 1, s + 3], np.int32)
    b = len(positions)
    rng = np.random.default_rng(heads + hs)
    k = jnp.asarray(rng.normal(size=(b, s, e)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, e)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, heads, hs)).astype(np.float32))
    got, read = jax.jit(bounded_decode_attention)(q, k, v, positions)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jax.jit(decode_attention)(q, k, v, positions)))
    assert np.asarray(read).tolist() == [s] * b
    paged = paged_decode_attention(q, k, v, positions, page=128,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


# (query heads, KV heads, head size): the routed-experts cell's, the
# state-space cell's (ONE KV head), a toy
GROUPED = [(32, 4, 128), (20, 1, 128), (8, 2, 16)]


@pytest.mark.parametrize("page,dtype", [(16, "float32"), (128, "float32"),
                                        (256, "bfloat16"),
                                        (1024, "bfloat16")])
@pytest.mark.parametrize("heads,groups,hs", GROUPED)
def test_grouped_paged_read_matches_masked_read(heads, groups, hs, page,
                                                dtype):
    """The same kernel with several query heads a KV head (through the
    Pallas interpreter) against the masked read of grouped caches holding
    the same numbers in float32, rows at ragged positions over a retired
    tenant's keys and values."""
    from deeplearning4j_tpu.ops.block_sparse import dense_decode_attention

    s = 4 * page
    positions = _ragged(page, s)
    b, e = len(positions), groups * hs
    rng = np.random.default_rng(heads * 100 + hs + page)
    k = _cache(rng, (b, s, e), dtype)
    v = _cache(rng, (b, s, e), dtype)
    q = jnp.asarray(rng.normal(size=(b, heads, hs)).astype(np.float32))
    want = np.asarray(dense_decode_attention(
        q, jnp.asarray(k), jnp.asarray(v), positions, groups))
    beyond = np.arange(s)[None, :, None] > positions[:, None, None]
    stale_k = np.where(beyond, 300.0 * rng.normal(size=k.shape), k)
    stale_v = np.where(beyond, 300.0 * rng.normal(size=v.shape), v)
    got = paged_decode_attention(
        q, jnp.asarray(stale_k, dtype), jnp.asarray(stale_v, dtype),
        positions, page=page, interpret=True, groups=groups)
    assert got.shape == (b, heads, hs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # off the TPU the bounded read is the masked one and says so
    kc, vc = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    got, read = jax.jit(lambda *a: bounded_decode_attention(
        *a, groups=groups))(q, kc, vc, positions)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.jit(
            dense_decode_attention, static_argnums=4)(
                q, kc, vc, positions, groups)), rtol=1e-5, atol=1e-6)
    assert np.asarray(read).tolist() == [s] * b
    with pytest.raises(ValueError, match="must share"):
        paged_decode_attention(q, jnp.asarray(k), jnp.asarray(v), positions,
                               page=page, interpret=True, groups=3)


@pytest.mark.parametrize("y_contract", [0, 1])
def test_exact_parts_product_is_the_highest_product(y_contract):
    """The stacked-parts product of a float32 operand and a bfloat16 one
    (both forms the kernel takes: ``P V`` contracts the page's rows, ``Q
    K^T`` its lanes) is the HIGHEST product to float32 rounding: each
    entry within 1e-6 of the sum of its terms' magnitudes (the two sum in
    different orders); rounding the float32 operand to ONE bfloat16 part
    misses by a hundred times that."""
    rng = np.random.default_rng(38 + y_contract)
    x = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(256, 512) if y_contract == 0
                               else (512, 256)), jnp.bfloat16)
    dims = (((1,), (y_contract,)), ((), ()))
    want = np.asarray(jax.lax.dot_general(
        x, y.astype(jnp.float32), dims,
        precision=jax.lax.Precision.HIGHEST))
    got = np.asarray(exact_parts_dot(x, y, y_contract))
    assert got.shape == want.shape == (32, 512)
    y32 = np.asarray(y.astype(jnp.float32))
    terms = np.abs(np.asarray(x)) @ np.abs(y32 if y_contract == 0 else y32.T)
    assert np.max(np.abs(got - want) / terms) < 1e-6
    one_part = np.asarray(jax.lax.dot_general(
        x.astype(jnp.bfloat16), y, dims, preferred_element_type=jnp.float32))
    assert np.max(np.abs(one_part - want) / terms) > 1e-4


def test_decode_page_follows_the_cache_bytes_a_position():
    """GPT-2-large's float32 cache of 1,280 lanes keeps pages of 128; a
    bfloat16 cache of ONE KV head of 128 (the state-space cell's) takes
    the page whose keys fill ``DECODE_PAGE_BYTES``, four KV heads
    (the routed-experts cell's) a quarter of it; a small bucket still
    holds two pages; a width off the lanes' tiles takes no kernel."""
    assert decode_page(1024, 1280, 4) == 128
    assert decode_page(8192, 128, 2) * 128 * 2 == DECODE_PAGE_BYTES
    assert decode_page(16384, 512, 2) * 512 * 2 == DECODE_PAGE_BYTES
    assert decode_page(8192, 128, 2) == 4 * decode_page(16384, 512, 2)
    assert decode_page(512, 128, 2) == 256 and decode_page(256, 128, 2) == 128
    assert decode_page(8192, 128, 4) == decode_page(8192, 128, 2) // 2
    assert decode_page(8192, 192, 2) is None


# --- what the engine says the step read ----------------------------------------

def _steer_to_the_tpu_branch(monkeypatch, page):
    """What lowering for a TPU chooses, chosen here for a CPU run: the
    decode path's ``tpu`` branch, its kernel through the Pallas
    interpreter, at a page a toy bucket holds several of."""
    from deeplearning4j_tpu.ops import attention

    kernel, choose = attention.paged_decode_attention, \
        jax.lax.platform_dependent
    monkeypatch.setattr(attention, "DECODE_PAGE", page)
    monkeypatch.setattr(attention, "DECODE_PAGE_BYTES", 0)
    monkeypatch.setattr(
        attention, "paged_decode_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *a, default=None, **per: (
            per["tpu"](*a) if getattr(per.get("tpu"), "__name__", "")
            == "paged" else choose(*a, default=default, **per)))


@pytest.mark.parametrize("read", ["masked", "paged"])
def test_engine_counts_the_positions_the_decode_step_reads(read, monkeypatch):
    """``dl4j_decode_kv_read_positions_total`` over
    ``dl4j_decode_kv_bucket_positions_total`` is the share of the bucket
    the decode steps streamed. Both add up from the requests alone: a
    request of ``n`` tokens after a prompt of ``L`` takes ``n - 1``
    decode steps at positions ``L .. L + n - 2`` (the prefill emits the
    first), each reads whole pages up to its position in every layer,
    and the bucket holds ``s`` positions a row a layer a step. On the
    CPU the page is the bucket (the masked read: ratio 1); through the
    TPU's branch it is the kernel's page."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.parallel.generation import (
        GenerationConfig,
        GenerationEngine,
    )

    s, layers = 64, 2
    page = s
    if read == "paged":
        page = 16
        _steer_to_the_tpu_branch(monkeypatch, page)
    # a vocabulary of its own per case: the executables are cached by graph
    zoo = TransformerEncoder(vocab_size=61 + page, embed_dim=128, n_heads=2,
                             n_layers=layers, max_len=s, causal=True,
                             lm_head=True, seed=11)
    dec = zoo.decoder(max_batch=4, kv_bucket_min=s, prompt_bucket_min=8)
    assert dec.counter_names == ["decode_kv_bucket_positions",
                                 "decode_kv_read_positions"]
    jobs = [(3, 6), (15, 9), (16, 5), (30, 12), (7, 3)]
    rng = np.random.default_rng(5)

    def series(name):
        return telemetry.REGISTRY.counter(
            f"dl4j_decode_kv_{name}_positions_total").value

    before = {n: series(n) for n in ("read", "bucket")}
    with GenerationEngine(dec, GenerationConfig(
            max_batch=4, fused_steps=2, kv_bucket_min=s,
            prompt_bucket_min=8)) as eng:
        reqs = [eng.submit(rng.integers(1, 60, size=n).tolist(),
                           max_new_tokens=m) for n, m in jobs]
        outs = [eng.result(r) for r in reqs]
        counts = eng.stats()["layer_counts"]
        text = telemetry.REGISTRY.render_prometheus()
    assert [len(o) for o in outs] == [m for _, m in jobs]
    steps = [p for n, m in jobs for p in range(n, n + m - 1)]
    want_read = layers * sum(-(-(p + 1) // page) * page for p in steps)
    want_bucket = layers * s * len(steps)
    assert counts == {"decode_kv_read_positions": want_read,
                      "decode_kv_bucket_positions": want_bucket}
    assert want_read <= want_bucket and (read == "masked") == (
        want_read == want_bucket)
    assert series("read") - before["read"] == want_read
    assert series("bucket") - before["bucket"] == want_bucket
    assert "dl4j_decode_kv_read_positions_total" in text
    assert "dl4j_decode_kv_bucket_positions_total" in text


def test_window_and_full_layers_count_what_they_read(monkeypatch):
    """A window layer and a full one behind the engine, the full layer's
    read through the TPU's branch (the paged kernel with grouped KV
    heads, interpreted): a step at position ``p`` streams the ring's 16
    slots and whole pages of the bucket up to ``p``, and holds ``p + 1``
    positions of context and the bucket's 64; the tokens are those of the
    masked read."""
    from deeplearning4j_tpu.parallel.generation import (
        GenerationConfig,
        GenerationEngine,
    )
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    s, window, page = 64, 16, 16

    def build():
        zoo = HybridDecoderLM(
            vocab_size=83, hidden=128, ffn_dim=64,
            mixer_types=["window-attn", "full-attn"], n_heads=4, head_dim=64,
            n_kv_heads=2, window=window, post_norms=True, max_len=s, seed=3)
        return zoo.decoder(max_batch=2, kv_bucket_min=s, prompt_bucket_min=8)

    jobs = [(3, 6), (20, 9), (30, 12)]
    prompts = [np.random.default_rng(i).integers(1, 80, size=n).tolist()
               for i, (n, _) in enumerate(jobs)]
    masked = build()
    want = [masked.generate(p, m, fused_steps=2)
            for p, (_, m) in zip(prompts, jobs)]
    _steer_to_the_tpu_branch(monkeypatch, page)
    from deeplearning4j_tpu.optimize import aot_cache
    aot_cache.clear()           # the same graph, lowered the other way
    dec = build()
    with GenerationEngine(dec, GenerationConfig(
            max_batch=2, fused_steps=2, kv_bucket_min=s,
            prompt_bucket_min=8)) as eng:
        outs = [eng.result(eng.submit(p, max_new_tokens=m))
                for p, (_, m) in zip(prompts, jobs)]
        counts = eng.stats()["layer_counts"]
    aot_cache.clear()
    assert outs == want
    steps = [p for n, m in jobs for p in range(n, n + m - 1)]
    assert counts == {
        "decode_kv_read_positions": sum(
            window + -(-(p + 1) // page) * page for p in steps),
        "decode_kv_bucket_positions": sum(p + 1 + s for p in steps)}


# --- tiles ---------------------------------------------------------------------

def _tiled_elems(shape, tile=(8, 128)):
    """Elements an array occupies when its two minor dimensions are tiled
    by ``tile`` (the TPU's layout for 32-bit types)."""
    up = lambda n, t: -(-n // t) * t  # noqa: E731
    lead = int(np.prod(shape[:-2], dtype=np.int64))
    return lead * up(shape[-2], tile[0]) * up(shape[-1], tile[1])


@pytest.mark.parametrize("heads,hs", GEOMETRIES)
def test_cache_shape_fills_its_tiles(heads, hs):
    """At the cell's batch and bucket the cache's tiled size IS its
    logical size for every model width that is a multiple of 128; the
    layout it replaced (``[.., heads, head_dim]`` minor) padded every
    geometry but heads of 128 in multiples of 8."""
    from deeplearning4j_tpu.conf.layers_attention import SelfAttentionLayer

    layer = SelfAttentionLayer(n_out=heads * hs, n_heads=heads, causal=True)
    shape = layer.kv_cache_shape(8, 1024, heads * hs)
    assert shape == (8, 1024, heads * hs)
    assert _tiled_elems(shape) == int(np.prod(shape))
    old = _tiled_elems((8, 1024, heads, hs)) / np.prod(shape)
    assert old == {(20, 64): 2.4, (12, 64): 8 / 3, (8, 128): 1.0,
                   (4, 32): 8.0}[(heads, hs)]


# --- jaxprs ----------------------------------------------------------------------

def _tiny_decoder():
    m = TransformerEncoder(vocab_size=48, embed_dim=16, n_heads=2,
                           n_layers=2, max_len=32, causal=True,
                           lm_head=True, seed=3)
    return m.decoder(max_batch=4, kv_bucket_min=32, prompt_bucket_min=8)


def _walk(jaxpr, in_scan=False):
    """Every equation at every depth, with whether a ``scan`` encloses it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        inner = in_scan or eqn.primitive.name == "scan"
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inner)


_WRAPPERS = {"pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
             "custom_vjp_call", "scan"}


def _cache_sized_primitives(fn, args, n_cache):
    """``(outside, inside)``: names of the primitives with an operand or a
    result of at least ``n_cache`` elements, outside and inside a scan."""
    found = (set(), set())
    for eqn, in_scan in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name in _WRAPPERS:
            continue
        avals = [v.aval for v in list(eqn.invars) + list(eqn.outvars)
                 if hasattr(v.aval, "shape")]
        if any(int(np.prod(a.shape)) >= n_cache for a in avals):
            found[in_scan].add(eqn.primitive.name)
    return found


def _executables():
    dec = _tiny_decoder()
    b, s = dec.max_batch, 32
    st = dec._struct_of(s)
    sds = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    dec.params)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    row = (i32(b), i32(b), i32(b), sds((b,), jnp.float32),
           sds((b, 2), jnp.uint32), sds((b,), jnp.bool_))
    # name -> (executable, arguments, index of the donated state,
    #          primitives that may touch something cache-sized
    #          outside a scan, and inside one)
    write_read = {"dynamic_update_slice", "dot_general"}
    return dec, {
        "decode": (dec.decode_fn(s, 4), (params, st), 1, set(), write_read),
        "join": (dec.join_fn(s, 8, 1),
                 (st, dec._kv_struct(1, 8), i32(1), i32(1)) + tuple(
                     sds((1,) + r.shape[1:], r.dtype) for r in row),
                 0, {"scatter"}, set()),
        "prefix_attach": (dec.prefix_attach_fn(s, 8, 1),
                          (st, dec._kv_struct(1, 8), i32(1), i32(1)),
                          0, {"scatter"}, set()),
        "suffix_join": (dec.suffix_join_fn(s, 8, 1),
                        (st, dec._kv_struct(1, 8), i32(1), i32(1), i32(1))
                        + tuple(sds((1,) + r.shape[1:], r.dtype)
                                for r in row),
                        0, {"dynamic_slice", "dynamic_update_slice"}, set()),
        "grow": (dec.grow_fn(16, s), (dec._struct_of(16),), None,
                 {"pad"}, set()),
    }


@pytest.mark.parametrize("name", ["decode", "join", "prefix_attach",
                                  "suffix_join", "grow"])
def test_executables_touch_the_cache_only_to_write_and_read_it(name):
    """No ``transpose``/``reshape``/``copy``/``select_n`` of anything
    cache-sized in any decoder executable: outside the decode window's
    scan NOTHING touches a cache, inside it only the token write and the
    two products do; the joins scatter, the bucket hop pads. And every
    executable that takes the state to rewrite it has it donated."""
    dec, table = _executables()
    step, args, donated, outside_ok, inside_ok = table[name]
    n_cache = int(np.prod(dec._layer(next(iter(dec._attn))).kv_cache_shape(
        dec.max_batch, 16 if name == "grow" else 32, 16)))
    outside, inside = _cache_sized_primitives(step.jit_fn, args, n_cache)
    assert outside <= outside_ok, (name, outside)
    assert inside <= inside_ok, (name, inside)
    if name == "decode":
        # both products and the write are really there to be seen
        assert inside == {"dot_general", "dynamic_update_slice"}
    if donated is not None:
        info = step.jit_fn.lower(*args).args_info[0][donated]
        leaves = jax.tree_util.tree_leaves(info["caches"])
        assert leaves and all(leaf.donated for leaf in leaves)


# --- the compiled decode window, for a v5e, without one ----------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             # the compiler's own moves of a whole buffer between HBM and
             # VMEM for the loop: same layout, another memory space
             "copy-start", "copy-done", "slice-start", "slice-done"}


def _without_layout_constraints(txt):
    """The compiled text less every ``operand_layout_constraints={...}``
    attribute: a custom call restates its operands' shapes there with
    the logical layout alone, which is no array of the program."""
    out, key = [], "operand_layout_constraints={"
    while key in txt:
        head, rest = txt.split(key, 1)
        depth, i = 1, 0
        while depth:
            depth += {"{": 1, "}": -1}.get(rest[i], 0)
            i += 1
        out.append(head)
        txt = rest[i:]
    return "".join(out) + txt


_INSTRUCTION = re.compile(
    r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(([^)]*)\)")


@pytest.mark.parametrize("heads,hs", GEOMETRIES[:3])
def test_compiled_decode_window_keeps_one_cache_layout(one_chip, heads, hs):
    """``decode_fn(1024, 4)`` of a two-layer decoder at the cell's batch,
    bucket and K, compiled by the TPU's own compiler: every cache, at
    rest (the entry parameters), in the ``while``, into the attention
    and in the results, has ONE tiled layout, row-major over ``[b, s,
    h * d]`` with (8, 128) tiles (which that shape fills exactly);
    ``ENTRY`` holds no operation with a cache-sized result but plumbing,
    so the donated caches alias straight through the loop; in the loop a
    cache is written by ``dynamic-update-slice`` alone (no cache-sized
    ``copy``, ``transpose`` or ``fusion``, no staging through VMEM and
    back) and READ by the paged kernel alone, which takes it from the
    write as it stands: the only operations with a whole cache among
    their operands are the write, the Mosaic custom call (one a layer,
    its K and its V) and the loop's own tuples; and the program's
    temporaries are smaller than two caches (the layout this replaced
    held a padded copy of every cache: 370 MB here, 8.9 GB for the
    benchmark's 36 layers)."""
    b, s, k = 8, 1024, 4
    layers = 2
    assert decode_page(s, heads * hs, 4) == 128
    m = TransformerEncoder(vocab_size=256, embed_dim=heads * hs,
                           n_heads=heads, n_layers=layers, max_len=s,
                           causal=True, lm_head=True, seed=0)
    dec = m.decoder(max_batch=b, kv_bucket_min=s, prompt_bucket_min=128)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                         sharding=one_chip)
    params = jax.tree_util.tree_map(sds, dec.params)
    state = jax.tree_util.tree_map(sds, dec._struct_of(s))
    compiled = dec.decode_fn(s, k).jit_fn.trace(params, state).lower(
        lowering_platforms=("tpu",)).compile()
    txt = _without_layout_constraints(compiled.as_text())

    dims = f"{b},{s},{heads * hs}"
    layouts = {re.sub(r"S\(\d+\)", "", lay) for lay in re.findall(
        r"f32\[" + dims + r"\](\{[^}]*\})", txt)}
    assert layouts == {"{2,1,0:T(8,128)}"}, layouts

    entry = txt[txt.index("\nENTRY "):]
    offenders = []
    for line in entry.splitlines():
        mo = _INSTRUCTION.match(line)
        if not mo or f"[{dims}]" not in mo.group(2):
            continue
        op = mo.group(3)
        if op == "custom-call" and "ConcatBitcast" in line:
            continue  # reassembles slice-start/-done pieces, moves nothing
        if op not in _PLUMBING:
            offenders.append(line.strip()[:200])
    assert not offenders, offenders

    produced = set(re.findall(
        r"=\s*f32\[" + dims + r"\]\{[^}]*\}\s+([\w\-]+)\(", txt))
    assert produced <= _PLUMBING | {"dynamic-update-slice", "custom-call",
                                    "fusion"}, produced
    fused_roots = set(re.findall(
        r"ROOT\s+%?[\w.\-]+\s*=\s*f32\[" + dims
        + r"\]\{[^}]*\}\s+([\w\-]+)\(", txt))
    assert fused_roots <= {"dynamic-update-slice", "bitcast"}, fused_roots

    # who takes a whole cache as an operand, anywhere in the program
    made = {}
    for line in txt.splitlines():
        mo = _INSTRUCTION.match(line)
        if mo:
            made[mo.group(1)] = (mo.group(2), mo.group(3), mo.group(4), line)
    readers = {}
    for name, (_, op, operands, line) in made.items():
        whole = [a for a in re.findall(r"%([\w.\-]+)", operands)
                 if made.get(a, ("",))[0].startswith(f"f32[{dims}]")]
        if whole:
            readers.setdefault(op, []).append((len(whole), line))
    assert set(readers) <= _PLUMBING | {"dynamic-update-slice",
                                        "custom-call"}, sorted(readers)
    kernels = readers["custom-call"]
    assert all('custom_call_target="tpu_custom_call"' in line
               and n == 2 for n, line in kernels), kernels
    assert len(kernels) == layers       # one bounded read a layer a step
    assert len(readers["dynamic-update-slice"]) == 2 * layers * b

    cache_bytes = 4 * b * heads * hs * s
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * cache_bytes


# --- the hybrid decoder's compiled programs, for a v5e, without one ----------

_COMPUTATION = re.compile(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def _consumers(txt, shapes):
    """Who takes a whole array of one of ``shapes`` (``"f32[3,256,256]"``)
    as an operand, anywhere in the compiled text: ``{(opcode, share)}``,
    ``share`` ``"whole"`` where the operation's result holds more than a
    quarter of the array's elements and ``"part"`` where it holds less.
    A fusion is looked into (its parameter stands for the operand), a
    bitcast is followed (its result is the same buffer), plumbing is
    skipped."""
    made, params, comp = {}, {}, None
    for line in txt.splitlines():
        mo = _COMPUTATION.match(line)
        if mo:
            comp = mo.group(1)
            continue
        mo = _INSTRUCTION.match(line)
        if not mo:
            continue
        name, typ, op, operands = mo.groups()
        made[name] = (typ, op, re.findall(r"%([\w.\-]+)", operands), line)
        if op == "parameter":
            params[comp, int(operands)] = name
    n_whole = {s: int(np.prod([int(d) for d in re.findall(r"\d+",
               s[s.index("["):])])) for s in shapes}
    whole = {name: s for name, (typ, *_) in made.items()
             for s in shapes if typ.startswith(s)}
    grew = True
    while grew:             # through bitcasts and into fusions
        grew = False
        for name, (typ, op, operands, line) in made.items():
            for i, a in enumerate(operands):
                if a not in whole:
                    continue
                if op == "bitcast":
                    new = name
                elif op == "fusion":
                    new = params[re.search(r"calls=%?([\w.\-]+)",
                                           line).group(1), i]
                else:
                    continue
                if new not in whole:
                    whole[new] = whole[a]
                    grew = True
    found = set()
    for name, (typ, op, operands, line) in made.items():
        if op in _PLUMBING or op in ("fusion", "conditional", "call"):
            continue
        for a in operands:
            if a in whole:
                n = max(int(np.prod([int(d) for d in dims.split(",") if d]))
                        for dims in re.findall(r"\w\[([\d,]*)\]", typ))
                found.add((op, "whole" if 4 * n > n_whole[whole[a]]
                           else "part"))
    return found


def _compiled_programs(one_chip, zoo, b, s, tp):
    """Decode window, prompt and join of ``zoo``'s decoder (``b`` rows, a
    KV bucket of ``s``, a prompt bucket of ``tp``, one prompt a launch)
    compiled for the v5e from avals: ``{program: (text, {kind of state:
    {shape, ...}})}``."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(zoo.conf())
    net.params = jax.eval_shape(
        lambda: ComputationGraph(zoo.conf()).init().params)
    net.state, net.opt_state = {}, {}
    dec = zoo.decoder(net, max_batch=b, kv_bucket_min=s,
                      prompt_bucket_min=tp, join_bucket_max=1)
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    row = lambda dt, *tail: on_chip(  # noqa: E731
        jax.ShapeDtypeStruct((1,) + tail, dt))
    i32, req = row(jnp.int32), (row(jnp.int32), row(jnp.int32),
                                row(jnp.float32), row(jnp.uint32, 2))
    state, block = dec._struct_of(s), dec._kv_struct(1, tp)
    table = {
        "decode": (dec.decode_fn(s, 4), (net.params, state), state["caches"]),
        "prompt": (dec.prompt_fn(tp, 1),
                   (net.params, row(jnp.int32, tp), i32) + req, block),
        "join": (dec.join_fn(s, tp, 1),
                 (state, block, i32, i32, i32) + req + (row(jnp.bool_),),
                 state["caches"]),
    }
    short = {"bfloat16": "bf16", "float32": "f32"}
    out = {}
    for program, (step, args, leaves) in table.items():
        shapes = {}
        for name, layer_leaves in leaves.items():
            for leaf, a in layer_leaves.items():
                shapes.setdefault(dec._layer(name).cache_kinds[leaf], set()) \
                    .add(f"{short[str(a.dtype)]}"
                         f"[{','.join(map(str, a.shape))}]")
        txt = step.jit_fn.trace(*on_chip(args)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        out[program] = (_without_layout_constraints(txt), shapes)
    return out


@pytest.fixture(scope="module")
def hybrid_programs(one_chip):
    """Decode window, prompt and join of a two-layer ``HybridDecoderLM``
    (one block-sparse layer, one lightning layer) compiled for the v5e
    from avals: ``{program: (text, {kind of state: [shape, ...]})}``. The
    caches' widths are the published ones per head (KV heads 2 x 128, a
    recurrent state of 128 x 128 a head), so that they fill the (8, 128)
    tiles as the cell's do (a toy width is relaid by the compiler whatever
    the program says); the depth, the residual stream, the vocabulary and
    the sparse sizes are small, and three rows make a cache's shape no
    other array's."""
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    b, s, tp = 3, 4096, 1024
    zoo = HybridDecoderLM(
        vocab_size=512, hidden=384, ffn_dim=768,
        mixer_types=["minicpm4", "lightning-attn"], n_heads=4, head_dim=128,
        n_kv_heads=2, lightning_heads=3, lightning_head_dim=128,
        sparse={"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                "window_size": 256, "init_blocks": 1, "topk": 4,
                "dense_len": 512},
        max_len=s, weight_dtype="bfloat16", cache_dtype="bfloat16")
    return _compiled_programs(one_chip, zoo, b, s, tp)


# (program, kind of state) -> the operations that may take a whole array of
# that kind as an operand, and how much of it they may return
_WRITE = ("dynamic-update-slice", "whole")
_HYBRID_READERS = {
    # the step's token write; the reads are the compressed key's window
    # (a dynamic slice of 32 positions), the dense branch's prefix (a
    # slice, inside its conditional) and the selection's ONE kernel, which
    # takes the caches where they lie (PR 34: before it two window slices
    # and a gather of whole 256-lane rows a cache; a slice of a KV head's
    # lanes before the gather split the cache by head, and a vmapped
    # dynamic_slice relaid it, 7 ms a step each at 32 k)
    ("decode", "kv"): {_WRITE, ("dynamic-slice", "part"), ("slice", "part"),
                       ("custom-call", "part")},
    # the compressed key this token completes (read the old, write); the
    # selection scores EVERY live compressed key, in float32 at HIGHEST:
    # the convert and the maximum are the matrix unit's operand split
    ("decode", "compressed_keys"): {
        _WRITE, ("dynamic-slice", "part"), ("convolution", "part"),
        ("convert", "whole"), ("maximum", "whole")},
    # S' = decay * S + k^T v is the whole state by nature; q S reads it
    ("decode", "recurrent"): {("multiply", "whole"), ("add", "whole"),
                              ("reduce", "part")},
    # a prompt's blocks are results: keys and values are split by KV head
    # for the attention, the compressed keys scored, the recurrence carried
    ("prompt", "kv"): {("slice", "whole")},
    ("prompt", "compressed_keys"): {("slice", "whole"), ("multiply", "whole"),
                                    ("add", "whole")},
    ("prompt", "recurrent"): {("multiply", "whole"), ("add", "whole"),
                              ("select", "whole")},
    # a join writes the joining row alone (it reads that row back first:
    # a slot past the cache is padding and keeps what is there); a row is
    # a third of these three-row caches
    ("join", "kv"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "compressed_keys"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "recurrent"): {_WRITE, ("dynamic-slice", "whole")},
}


@pytest.mark.parametrize("program,kind", sorted(_HYBRID_READERS))
def test_hybrid_programs_touch_each_state_only_to_write_and_read_it(
        hybrid_programs, program, kind):
    """The text the TPU's compiler makes of the hybrid decoder's decode
    window, prompt and join: nothing but the in-place write and the
    layer's own read has a whole KV cache, compressed-key cache or
    recurrent state among its operands: no ``copy``, ``transpose``,
    ``pad`` or ``select`` of a cache, no read that returns more than a
    quarter of one where the layer reads a part."""
    txt, shapes = hybrid_programs[program]
    found = _consumers(txt, shapes[kind])
    assert found <= _HYBRID_READERS[program, kind], sorted(
        found - _HYBRID_READERS[program, kind])
    # the write and a read are really there to be seen
    assert found >= _HYBRID_READERS[program, kind] & {_WRITE} and found


def test_hybrid_decode_window_neither_sorts_nor_gathers(hybrid_programs):
    """The decode window compiled for the v5e chooses its blocks without a
    ``sort`` and reads them without a ``gather`` or a blocked view of a
    cache: the program's one Mosaic kernel is the selection's read, once
    in the step's body (a loop of K steps), and the test above says it
    takes the KV caches as they stand; ``top_k`` and the gathered read
    are what a CPU lowers to (``tests/test_hybrid_decoder.py``)."""
    txt, shapes = hybrid_programs["decode"]
    (kv,) = shapes["kv"]
    b, n, e = re.findall(r"\d+", kv[kv.index("["):])
    blocked = f"bf16[{b},{int(n) // 64},64,{e}]"    # the gathered read's view
    ops = {}
    for line in txt.splitlines():
        mo = _INSTRUCTION.match(line)
        if mo:
            ops.setdefault(mo.group(3), []).append(line)
    assert "sort" not in ops
    # the one gather left is the embedding's
    assert not [line for line in ops.get("gather", ())
                if kv in line or blocked in line]
    assert blocked not in txt
    assert not re.search(r"custom_call_target=\"TopK\"", txt)
    kernels = [line for line in txt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "sparse_read_attention" in kernels[0]
    # and the prompt walk keeps its top_k: a prompt's choice is not touched
    assert re.search(r"\bsort\(|TopK", hybrid_programs["prompt"][0])


# --- the routed-experts decoder: a bucket, a ring, grouped experts ----------

# three experts, two a token: a step's three rows give each expert two
# slots (every expert held), a prompt's 1,024 tokens 683 (grouped)
_ROUTED = {"tokens": 1024, "experts": 3, "expert_hidden": 128}


@pytest.fixture(scope="module")
def routed_programs(one_chip):
    """Decode window, prompt and join of a two-layer ``HybridDecoderLM``
    with a window layer over a dense feed-forward and a full layer over
    routed experts, compiled for the v5e from avals: ``{program: (text,
    {kind of state: [shape, ...]})}``. KV heads 2 x 128 fill the tiles as
    the cell's 4 x 128 do; three rows make a cache's shape no other
    array's; the ring holds 256 slots, the bucket 4,096."""
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    b, s, tp = 3, 4096, _ROUTED["tokens"]
    zoo = HybridDecoderLM(
        vocab_size=512, hidden=384, ffn_dim=768,
        mixer_types=["window-attn", "full-attn"], ffn_types=["dense", "moe"],
        moe={"n_experts": _ROUTED["experts"],
             "n_hidden": _ROUTED["expert_hidden"], "top_k": 2,
             "n_shared_hidden": 128, "route_scale": 2.826},
        post_norms=True, n_heads=4, head_dim=128, n_kv_heads=2, window=256,
        max_len=s, weight_dtype="bfloat16", cache_dtype="bfloat16")
    return _compiled_programs(one_chip, zoo, b, s, tp)


_ROUTED_READERS = {
    # the token's write, in place; a full layer's bucket is read by the
    # paged kernel (grouped KV heads), a ring by the two products of the
    # masked read
    ("decode", "kv"): {_WRITE, ("custom-call", "part")},
    ("decode", "kv_ring"): {_WRITE, ("convolution", "part")},
    # a join writes the joining row alone (a third of a three-row cache),
    # bucket and ring alike
    ("join", "kv"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "kv_ring"): {_WRITE, ("dynamic-slice", "whole")},
}


@pytest.mark.parametrize("program,kind", sorted(_ROUTED_READERS))
def test_routed_programs_touch_bucket_and_ring_only_to_write_and_read_them(
        routed_programs, program, kind):
    """No ``copy``, ``pad``, ``select`` or scatter's ``while`` has a whole
    bucket or a whole ring among its operands (PR 26's and PR 29's
    lesson), in the decode window or in the join."""
    txt, shapes = routed_programs[program]
    assert shapes == {"kv": {"bf16[3,4096,256]"},
                      "kv_ring": {"bf16[3,256,256]"}}
    found = _consumers(txt, shapes[kind])
    assert found <= _ROUTED_READERS[program, kind], sorted(
        found - _ROUTED_READERS[program, kind])
    assert _WRITE in found


def test_routed_prompt_walk_holds_no_tokens_by_experts_by_hidden_array(
        routed_programs):
    """The prompt walk groups: nowhere in its compiled text is an array of
    tokens x experts x hidden (the dense form, every token through every
    expert), nor a ``ragged-dot`` (what every other platform lowers a
    prompt's product to): its product is ONE Mosaic kernel over the slots
    in tiles of one expert, the three stacks among its operands as they
    lie; the decode window, whose three rows go through the touched
    experts' kernel, holds neither that nor the batched product's small
    one (PR 36: a CPU lowers to it, ``tests/test_routed_decoder.py``)."""
    t, e, h = (_ROUTED[k] for k in ("tokens", "experts", "expert_hidden"))
    d = 384
    dense = re.compile(
        rf"\[({t},{e},{h}|{e},{t},{h}|{2 * t},{e},{h}|{e},{2 * t},{h})\]")
    prompt, _ = routed_programs["prompt"]
    assert not dense.search(prompt)
    assert "ragged-dot" not in prompt
    kernels = [line for line in prompt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "grouped_experts_ffn" in kernels[0]
    types = {mo.group(1): mo.group(2) for mo in map(
        _INSTRUCTION.match, prompt.splitlines()) if mo}
    taken = [types[a].split("{")[0] for a in re.findall(
        r"%([\w.\-]+)", _INSTRUCTION.match(kernels[0]).group(4))]
    assert sorted(s for s in taken if s.startswith(f"bf16[{e},")) == [
        f"bf16[{e},{h},{d}]", f"bf16[{e},{d},{h}]", f"bf16[{e},{d},{h}]"]
    decode, _ = routed_programs["decode"]
    assert not re.search(rf"\[{e},3,{h}\]", decode)
    assert "ragged-dot" not in decode


def test_routed_decode_window_reads_the_expert_stacks_by_the_kernel_alone(
        routed_programs):
    """The decode window compiled for the v5e holds ONE more Mosaic kernel
    beside the full layer's paged read: the touched experts' product, once
    in the step's body, with the three stacks among its operands as they
    lie: no ``copy``, ``gather``, ``dot`` or ``convolution`` takes a
    stack, whole or in part. The prompt walk's stacks go to its grouped
    kernel and nowhere else, and neither the prompt nor the join holds
    the touched experts' kernel."""
    e, d, h = _ROUTED["experts"], 384, _ROUTED["expert_hidden"]
    stacks = {f"bf16[{e},{d},{h}]", f"bf16[{e},{h},{d}]"}
    decode, _ = routed_programs["decode"]
    assert _consumers(decode, stacks) == {("custom-call", "part")}
    kernels = [line for line in decode.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    ours = [line for line in kernels if "touched_experts_ffn" in line]
    assert len(kernels) == 2 and len(ours) == 1
    types = {mo.group(1): mo.group(2) for mo in map(
        _INSTRUCTION.match, decode.splitlines()) if mo}
    taken = [types[a].split("{")[0] for a in re.findall(
        r"%([\w.\-]+)", _INSTRUCTION.match(ours[0]).group(4))]
    assert sorted(t for t in taken if t in stacks) == [
        f"bf16[{e},{h},{d}]", f"bf16[{e},{d},{h}]", f"bf16[{e},{d},{h}]"]
    prompt, _ = routed_programs["prompt"]
    # (the grouped kernel, ``ops.routed_experts.grouped_experts_ffn``)
    assert _consumers(prompt, stacks) == {("custom-call", "whole")}
    assert len(re.findall(r"= f32\[\d+,\d+\]\S* custom-call\(.*"
                          r"op_name=\"[^\"]*/grouped_experts_ffn/", prompt)) == 1
    for program in ("prompt", "join"):
        assert "touched_experts_ffn" not in routed_programs[program][0]


# --- the state-space hybrid: a scan's state, a convolution's window, a bucket

_SSM = {"rows": 3, "tokens": 256, "channels": 1024, "state": 16}


@pytest.fixture(scope="module")
def ssm_programs(one_chip):
    """Decode window, prompt and join of a two-layer ``HybridDecoderLM``
    (a Mamba layer, a multi-query attention layer without positions, a
    tied head) compiled for the v5e from avals: ``{program: (text, {kind
    of state: [shape, ...]})}``. One block of 1,024 channels and a state
    of 16 fill the tiles as the cell's 5,120 x 16 do, and the prompt
    walk's scan is the kernel; three rows make a state's shape no other
    array's."""
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    b, s, tp = _SSM["rows"], 1024, _SSM["tokens"]
    zoo = HybridDecoderLM(
        vocab_size=512, hidden=384, ffn_dim=768,
        mixer_types=["mamba", "plain-attn"],
        mamba={"d_inner": _SSM["channels"], "d_state": _SSM["state"],
               "d_conv": 4, "dt_rank": 24},
        n_heads=3, head_dim=128, n_kv_heads=1, tie_head=True,
        depth_for_scale=1, max_len=s, weight_dtype="bfloat16",
        cache_dtype="bfloat16")
    return _compiled_programs(one_chip, zoo, b, s, tp)


_SSM_READERS = {
    # h' = exp(dt A) h + dt x B is the whole state by nature, elementwise
    # and in place; the read-out reduces it over the state's 16
    ("decode", "recurrent"): {("multiply", "whole"), ("add", "whole"),
                              ("exponential", "whole"), ("reduce", "part")},
    # the ring: the taps weigh every slot where it lies (a product, its
    # three lane slices summed), the token's input is selected into the
    # oldest slot; nothing shifts
    ("decode", "conv_window"): {("multiply", "whole"), ("add", "whole"),
                                ("select", "whole"), ("slice", "whole")},
    # the token's write, in place; the bucket is read by the paged kernel
    ("decode", "kv"): {_WRITE, ("custom-call", "part")},
    # a join writes the joining row alone (a third of a three-row state)
    ("join", "recurrent"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "conv_window"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "kv"): {_WRITE, ("dynamic-slice", "whole")},
}


@pytest.mark.parametrize("program,kind", sorted(_SSM_READERS))
def test_ssm_programs_touch_each_state_only_to_update_it_where_it_lies(
        ssm_programs, program, kind):
    """No ``copy``, ``pad``, ``concatenate`` or scatter's ``while`` has a
    scan's state, a convolution's window or a KV bucket among its
    operands, in the decode window or in the join (a window kept
    oldest-first was copied whole every step to be shifted)."""
    txt, shapes = ssm_programs[program]
    rows, d, n = _SSM["rows"], _SSM["channels"], _SSM["state"]
    assert shapes == {"recurrent": {f"f32[{rows},{n},{d}]"},
                      "conv_window": {f"f32[{rows},{3 * d}]"},
                      "kv": {f"bf16[{rows},1024,128]"}}
    found = _consumers(txt, shapes[kind])
    assert found <= _SSM_READERS[program, kind], sorted(
        found - _SSM_READERS[program, kind])
    assert found


def test_ssm_prompt_walk_is_the_kernel_and_keeps_no_state_history(
        ssm_programs):
    """The prompt walk compiled for the v5e scans with the one Mosaic
    kernel (``lax.scan`` over positions is what a CPU lowers to) and
    nowhere holds a state a position; the decode window's one kernel is
    the attention layer's paged read."""
    t, d, n = _SSM["tokens"], _SSM["channels"], _SSM["state"]
    prompt, _ = ssm_programs["prompt"]
    kernels = [line for line in prompt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "selective_scan" in kernels[0]
    history = re.compile(
        rf"\[(\d+,)?({t},{n},{d // 128},128|{t},{n},{d}|{n},{t},{d}|"
        rf"{t},1,{n},{d})\]")
    assert not history.search(prompt)
    assert f"f32[1,{n},{d // 128},128]" in prompt    # the kernel's state block
    decode, _ = ssm_programs["decode"]
    kernels = [line for line in decode.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "selective_scan" not in kernels[0]


# --- the delta-rule / latent hybrid: a delta state, a ring, a latent cache ----

_DELTA = {"rows": 3, "tokens": 256, "value_heads": 2, "head": 128,
          "latent": 384}


@pytest.fixture(scope="module")
def delta_programs(one_chip):
    """Decode window, prompt and join of a two-layer ``HybridDecoderLM`` (a
    gated delta-rule layer over a dense feed-forward, a latent attention
    layer over routed experts, zero-centred norms, clamped SwiGLUs)
    compiled for the v5e from avals: ``{program: (text, {kind of state:
    [shape, ...]})}``. Heads of 128 fill the tiles as the cell's do; the
    latent row of 256 + 64 lies in 384 lanes as the cell's 576 in 640;
    three rows make a state's shape no other array's."""
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    b, s, tp = _DELTA["rows"], 1024, _DELTA["tokens"]
    h, hv = _DELTA["head"], _DELTA["value_heads"]
    zoo = HybridDecoderLM(
        vocab_size=512, hidden=384, ffn_dim=768,
        mixer_types=["gated-deltanet", "mla"], ffn_types=["dense", "moe"],
        delta={"key_heads": 1, "value_heads": hv, "key_dim": h,
               "value_dim": h, "gate_scale": 2.0},
        mla={"n_heads": 2, "q_rank": 128, "kv_rank": 256, "nope_dim": 128,
             "rope_dim": 64, "value_dim": 128, "rope_theta": 100000.0,
             "yarn_factor": 8.0, "yarn_original": 32768},
        moe={"n_experts": 3, "n_hidden": 128, "top_k": 2,
             "n_shared_hidden": 128, "route_scale": 2.5},
        post_norms=True, zero_centred_norms=True, swiglu_limit=10.0,
        n_heads=2, head_dim=128, n_kv_heads=2, depth_for_scale=1, max_len=s,
        weight_dtype="bfloat16", cache_dtype="bfloat16")
    return _compiled_programs(one_chip, zoo, b, s, tp)


_DELTA_READERS = {
    # the delta rule's state goes whole into its one kernel and comes out
    # of it in place: the kernel reads and writes it, nothing else does
    ("decode", "recurrent"): {("custom-call", "whole")},
    # the ring, as the state-space layer's: the taps weigh every slot
    # where it lies, the token's input is selected into the oldest slot
    ("decode", "conv_window"): {("multiply", "whole"), ("add", "whole"),
                                ("select", "whole"), ("slice", "whole")},
    # the token's write, in place; the bucket is read by the paged kernel
    # (keys and values from one page)
    ("decode", "latent"): {_WRITE, ("custom-call", "part")},
    # a join writes the joining row alone (a third of a three-row state)
    ("join", "recurrent"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "conv_window"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "latent"): {_WRITE, ("dynamic-slice", "whole")},
}


@pytest.mark.parametrize("program,kind", sorted(_DELTA_READERS))
def test_delta_programs_touch_each_state_only_to_update_it_where_it_lies(
        delta_programs, program, kind):
    """No ``copy``, ``pad``, ``transpose`` or scatter's ``while`` has the
    delta rule's state, the convolution's ring or the latent cache among
    its operands, in the decode window or in the join (a latent row of 576
    lanes was kept in another layout inside the loop and copied whole in
    and out of every window: the cache's row fills whole lane tiles)."""
    txt, shapes = delta_programs[program]
    rows, hv, h = _DELTA["rows"], _DELTA["value_heads"], _DELTA["head"]
    assert shapes == {"recurrent": {f"f32[{rows},{hv},{h},{h}]"},
                      "conv_window": {f"f32[{rows},{3 * 4 * h}]"},
                      "latent": {f"bf16[{rows},1024,{_DELTA['latent']}]"}}
    found = _consumers(txt, shapes[kind])
    assert found <= _DELTA_READERS[program, kind], sorted(
        found - _DELTA_READERS[program, kind])
    assert found


def test_delta_decode_window_holds_the_three_kernels_once_each(
        delta_programs):
    """The decode window compiled for the v5e updates the delta rule's
    state by its kernel, reads the latent cache by the paged kernel and the
    experts by theirs, once each in the step's body; the prompt walk is the
    chunked form (no kernel of the delta rule) and holds no state a
    position."""
    decode, _ = delta_programs["decode"]
    kernels = [line for line in decode.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.search(r"%(\w+?)(\.\d+)? =", k).group(1)
                   for k in kernels)
    assert names == ["delta_rule_step_kernel", "paged_decode_attention",
                     "touched_experts_ffn"], names
    prompt, _ = delta_programs["prompt"]
    assert "delta_rule_step_kernel" not in prompt
    t, hv, h = _DELTA["tokens"], _DELTA["value_heads"], _DELTA["head"]
    assert not re.search(rf"\[\d*,?{t},{hv},{h},{h}\]", prompt)


# --- the short-convolution hybrid: a ring of two inputs beside a KV cache ---

_SHORTCONV = {"rows": 3, "tokens": 256, "hidden": 384}


@pytest.fixture(scope="module")
def shortconv_programs(one_chip):
    """Decode window, prompt and join of a two-layer ``HybridDecoderLM`` (a
    gated short convolution over a dense feed-forward, q/k-normed rotating
    attention over routed experts, a tied head) compiled for the v5e from
    avals: ``{program: (text, {kind of state: [shape, ...]})}``. A hidden
    width of 384 lays the ring's two inputs in whole lane tiles as the
    cell's 2 x 2,048 do; three rows and a feed-forward of 640 make a
    state's shape no other array's."""
    from deeplearning4j_tpu.zoo.graphs import HybridDecoderLM

    b, s, tp = _SHORTCONV["rows"], 1024, _SHORTCONV["tokens"]
    zoo = HybridDecoderLM(
        vocab_size=512, hidden=_SHORTCONV["hidden"], ffn_dim=640,
        mixer_types=["short-conv", "rope-attn"], ffn_types=["dense", "moe"],
        shortconv={"d_conv": 3},
        moe={"n_experts": 4, "n_hidden": 128, "top_k": 2,
             "route_eps": 1e-6},
        n_heads=6, head_dim=64, n_kv_heads=2, rope_theta=1e6, tie_head=True,
        depth_for_scale=1, max_len=s, weight_dtype="bfloat16",
        cache_dtype="bfloat16")
    return _compiled_programs(one_chip, zoo, b, s, tp)


_SHORTCONV_READERS = {
    # the ring, as the state-space layer's: the taps weigh every slot
    # where it lies, the token's input is selected into the oldest slot
    ("decode", "conv_window"): {("multiply", "whole"), ("add", "whole"),
                                ("select", "whole"), ("slice", "whole")},
    # the token's write, in place; the bucket is read by the paged kernel
    ("decode", "kv"): {_WRITE, ("custom-call", "part")},
    # a join writes the joining row alone (a third of a three-row state)
    ("join", "conv_window"): {_WRITE, ("dynamic-slice", "whole")},
    ("join", "kv"): {_WRITE, ("dynamic-slice", "whole")},
}


@pytest.mark.parametrize("program,kind", sorted(_SHORTCONV_READERS))
def test_shortconv_programs_touch_each_state_only_to_update_it_where_it_lies(
        shortconv_programs, program, kind):
    """No ``copy``, ``pad``, ``concatenate`` or scatter's ``while`` has the
    short convolution's ring or a KV bucket among its operands, in the
    decode window or in the join."""
    txt, shapes = shortconv_programs[program]
    rows, e = _SHORTCONV["rows"], _SHORTCONV["hidden"]
    assert shapes == {"conv_window": {f"f32[{rows},{2 * e}]"},
                      "kv": {f"bf16[{rows},1024,128]"}}
    found = _consumers(txt, shapes[kind])
    assert found <= _SHORTCONV_READERS[program, kind], sorted(
        found - _SHORTCONV_READERS[program, kind])
    assert found


# --- the scopes of the programs compiled for the v5e --------------------------
# (``telemetry.device_time``: what the device's time is filed under)

@pytest.fixture(scope="module")
def gpt2_programs(one_chip):
    """Decode window, prompt and join of a two-layer GPT-2-style decoder
    (heads of 128, three rows) compiled for the v5e from avals."""
    zoo = TransformerEncoder(vocab_size=512, embed_dim=256, n_heads=2,
                             n_layers=2, max_len=1024, causal=True,
                             lm_head=True, seed=0)
    return _compiled_programs(one_chip, zoo, 3, 1024, 128)


@pytest.fixture(scope="module")
def train_programs(one_chip):
    """The train step of a tiny residual ``ComputationGraph`` (two
    convolutions with BatchNorm around a skip, bfloat16 compute, Adam)
    compiled for the v5e from avals: ``{"train": (text, vertex names)}``."""
    import dataclasses

    from deeplearning4j_tpu.conf.activations import Activation
    from deeplearning4j_tpu.conf.graph import ElementWiseOp, ElementWiseVertex
    from deeplearning4j_tpu.conf.inputs import InputType
    from deeplearning4j_tpu.conf.layers import OutputLayer
    from deeplearning4j_tpu.conf.layers_cnn import (
        BatchNormalization,
        ConvolutionLayer,
        GlobalPoolingLayer,
    )
    from deeplearning4j_tpu.conf.multilayer import NeuralNetConfiguration
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    g = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-3))
         .graph_builder().add_inputs("in")
         .set_input_types(InputType.convolutional(32, 32, 16)))
    conv = lambda: ConvolutionLayer(  # noqa: E731
        n_out=16, kernel_size=(3, 3), padding=(1, 1),
        activation=Activation.IDENTITY)
    g.add_layer("stem_conv", conv(), "in")
    g.add_layer("stem_bn", BatchNormalization(activation=Activation.RELU),
                "stem_conv")
    g.add_layer("res_conv", conv(), "stem_bn")
    g.add_layer("res_bn", BatchNormalization(), "res_conv")
    g.add_vertex("res_add", ElementWiseVertex(op=ElementWiseOp.ADD),
                 "stem_bn", "res_bn")
    g.add_layer("pool", GlobalPoolingLayer(), "res_add")
    g.add_layer("output", OutputLayer(n_out=10), "pool")
    conf = dataclasses.replace(g.set_outputs("output").build(),
                               compute_dtype="bfloat16")
    net = ComputationGraph(conf)
    params, state = jax.eval_shape(lambda: (lambda n: (n.params, n.state))(
        ComputationGraph(conf).init()))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    opt = jax.tree_util.tree_map(lambda x: {"m": x, "v": x}, params)
    raw = net.train_step_fn()

    def step(params, state, opt, features, labels, it, ep, key):
        ones = (jnp.ones((labels[0].shape[0],), jnp.float32),)
        return raw(params, state, opt, features, labels, (None,), ones, it,
                   ep, key)

    txt = jax.jit(step, donate_argnums=(0, 1, 2)).trace(
        on_chip(params), on_chip(state), on_chip(opt),
        (sds((8, 32, 32, 16), jnp.float32),), (sds((8, 10), jnp.float32),),
        sds((), jnp.int32), sds((), jnp.int32), sds((2,), jnp.uint32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    return {"train": (txt, set(net._topo))}


_WALKED = {     # the classes of each decoder's plan entries
    "gpt2_programs": {"embed", "pos", "norm", "attn.mha", "residual", "ffn",
                      "head"},
    "hybrid_programs": {"embed", "norm", "attn.sparse", "attn.lightning",
                        "residual", "ffn", "head"},
    "routed_programs": {"embed", "norm", "attn.window", "attn.full",
                        "residual", "ffn", "moe", "head"},
    "ssm_programs": {"embed", "norm", "ssm", "attn.full", "residual", "ffn",
                     "head"},
    "delta_programs": {"embed", "norm", "attn.delta", "attn.latent",
                       "residual", "ffn", "moe", "head"},
    "shortconv_programs": {"embed", "norm", "mixer.shortconv", "attn.full",
                           "residual", "ffn", "moe", "head"},
}


@pytest.mark.parametrize("programs,program", [
    (d, p) for d in sorted(_WALKED) for p in ("decode", "prompt", "join")
] + [("train_programs", "train")])
def test_compiled_programs_carry_a_scope_on_what_does_work(
        request, programs, program):
    """In the text the TPU's compiler makes of each program, at least 95%
    of the instructions that run as operations and do work (no parameter,
    constant, tuple or bitcast, no loop: its body's are counted) lie
    under a scope of the vocabulary, their own or the one they take from
    the instruction they feed (``device_time.parse_scopes``); and every
    plan entry's class appears (a join holds the cached layers' alone; a
    residual sum lives inside its neighbour's fusion, so the classes are
    looked for in every ``op_name``)."""
    from deeplearning4j_tpu.telemetry import device_time as dt

    txt, vertices = request.getfixturevalue(programs)[program]
    work = [op for op in dt.parse_scopes(txt).values()
            if op.opcode not in dt.NO_WORK
            and op.opcode not in ("while", "call", "conditional")]
    if program == "train":
        known = set(dt.TRAIN_SCOPES) | vertices
        known |= {f"transpose({name})" for name in known}
        wanted = {"cast", "loss", "updater", "stem_conv", "res_bn",
                  "transpose(res_conv)", "transpose(stem_bn)"}
    else:
        known = set(dt.SCOPE_CLASSES + dt.PROGRAM_SCOPES)
        wanted = _WALKED[programs]
        if program == "join":
            wanted = {c for c in wanted if c.startswith(("attn.", "ssm"))} \
                | {"cache.write", "window.account"}
        else:
            wanted = wanted | {"sample"}
    named = [op for op in work if dt.group_of(op) in known]
    assert len(work) > 10 and len(named) >= 0.95 * len(work), (
        len(named), len(work), sorted({
            (op.opcode, op.scope) for op in work
            if dt.group_of(op) not in known})[:20])
    seen = set()
    for name in set(re.findall(r'op_name="([^"]*)"', txt)):
        scope, backward, _loop = dt.scope_of(name)
        seen.update(scope)
        if backward and scope:
            seen.add(f"transpose({scope[0]})")
    assert wanted <= seen, wanted - seen
    if (programs, program) == ("gpt2_programs", "decode"):
        # float32 matrices: the loop-invariant converts to bfloat16 stand
        # before the while, once a window
        assert any(op.opcode == "convert" and not op.in_while
                   and dt.group_of(op) == "window.prepare" for op in work)
