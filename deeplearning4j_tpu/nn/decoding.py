"""KV-cached autoregressive decode for causal Transformer graphs.

The serving engine (PR 5) batches at *request* granularity — fine for
one-shot classification, useless for autoregressive generation where a
request is a whole token-by-token loop. This module gives a causal
``zoo.TransformerEncoder(lm_head=True)`` graph (or any graph of the same
shape: embedding → position embedding → pre-LN causal-attention blocks →
LN → time-distributed output head) a decode path split into the two
phases every production LLM server uses:

- ``prefill``: the whole prompt in ONE launch — full causal attention,
  the projected keys/values of every layer captured in cache layout and
  scattered into the preallocated per-sequence KV buffers
  (``[max_batch, kv_bucket, heads * head_dim]`` + a per-sequence slot
  count; ``ops/attention.py`` says why that layout), the first output
  token sampled from the last valid position.
- ``decode_step``: one token per sequence per step against the cache —
  each step projects q/k/v for the new token only, writes k/v at the
  sequence's slot via ``dynamic_update_slice``, and attends the cached
  prefix. ``fused_steps=K`` of these are ``lax.scan``-ned into one host
  dispatch (PR 7's scan-per-dispatch shape) with in-graph EOS masking so
  sequences that finish inside the window become no-ops instead of
  forcing a dispatch boundary.

Every executable rides ``optimize/aot_cache`` with its bucket geometry in
the step-kind key — ``decode_step:s{kv_bucket}:k{K}``,
``prefill_join:s{S}:t{prompt_bucket}:b{join_bucket}``,
``gen_prompt:t{T}:b{B}`` — exactly like serving's power-of-two row
buckets, so after ``warmup()`` mixed-length traffic never recompiles.
The decode and join executables DONATE the state pytree (the KV buffers
dominate it); the PRG201 donation audit covers the ``decode_step*`` /
``prefill*`` kinds, so a regression that silently copies the cache every
token is a lint ERROR, not a memory mystery.

Per-row state is whatever the graph's layers keep: every layer with the
cache interface of ``conf/layers_hybrid.py`` (``cache_init``,
``cache_prefill``, ``cache_join``, ``cache_step``, ``cache_grow``,
``cache_release``) is served: KV buffers (``SelfAttentionLayer``), KV
buffers beside compressed keys (``BlockSparseAttentionLayer``), a
fixed-size recurrent state (``LightningAttentionLayer``), a scan's state
beside a convolution's window (``conf.layers_ssm.MambaMixerLayer``), a
delta rule's state beside a convolution's window
(``conf.layers_delta.GatedDeltaNetLayer``), one latent vector a position
(``conf.layers_delta.LatentAttentionLayer``), a short convolution's window
alone (``conf.layers_ssm.ShortConvLayer``), side by side in
the one donated state pytree, each in its own type. The suffix walk (the
prefix cache) needs ``prefill_suffix`` on every such layer and refuses a
graph that has a layer without it, by name.

Scheduling on top of this lives in ``parallel.generation`` — this module
is the pure model path plus :meth:`TransformerDecoder.generate`, the
sequential one-request-at-a-time reference the continuous-batching
engine is pinned bit-identical against (greedy token ids).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.conf.layers import (
    EmbeddingSequenceLayer,
    OutputLayer,
)
from deeplearning4j_tpu.conf.layers_cnn import GlobalPoolingLayer
from deeplearning4j_tpu.conf.layers_attention import (
    LearnedSelfAttentionLayer,
    RecurrentAttentionLayer,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.conf.layers_extra import PositionEmbeddingLayer
from deeplearning4j_tpu.optimize import aot_cache


def pow2_ladder(lo: int, hi: int) -> List[int]:
    """Power-of-two bucket ladder from ``lo`` up, capped at (and always
    including) ``hi`` — the KV-length / prompt-length twin of serving's
    ``bucket_ladder`` row buckets."""
    lo, hi = int(lo), int(hi)
    if lo >= hi:
        return [hi]
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


def bucket_for(n: int, ladder: List[int]) -> int:
    """Smallest ladder entry >= n (raises when n exceeds the ladder)."""
    for b in ladder:
        if b >= n:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {ladder[-1]}")


def _advance_rng(rng):
    """Split every per-sequence PRNG key: ``rng [B, 2] uint32`` →
    (step keys, carried keys). Per-sequence streams keep sampling
    deterministic per request no matter which co-tenants share the
    running batch — the continuous-vs-sequential bit-identity hinges on
    this."""
    ks = jax.vmap(jax.random.split)(rng.astype(jnp.uint32))
    return ks[:, 0], ks[:, 1]


def _sample_tokens(logits, step_keys, temps):
    """Greedy (temp == 0) or temperature sampling per row. The argmax
    and the categorical draw are both computed and selected with
    ``where`` so one executable serves mixed greedy/sampled batches."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(
        step_keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _first_token(logits, max_new, eos, temps, rng):
    """What a prefill samples from its last-position logits: the first
    token, the row's liveness (EOS on the first token / ``max_new == 1``
    rows are born retired) and the carried PRNG keys."""
    step_keys, rng_next = _advance_rng(rng)
    tok = _sample_tokens(logits, step_keys, temps)
    return tok, (tok != eos) & (max_new > 1), rng_next


def _seed_rows(state, caches, rows, tok, lengths, max_new, eos, temps, rng,
               active):
    """The state with a join group's caches and its per-row scheduler
    arrays written at ``rows`` (slots >= ``max_batch`` are padding, dropped
    by the scatter)."""
    at = lambda a, v: a.at[rows].set(v, mode="drop")  # noqa: E731
    with jax.named_scope("window.account"):
        return dict(
            state, caches=caches,
            tokens=at(state["tokens"], tok),
            positions=at(state["positions"], lengths),
            prompt_lens=at(state["prompt_lens"], jnp.maximum(lengths, 1)),
            max_new=at(state["max_new"], max_new),
            eos=at(state["eos"], eos),
            temps=at(state["temps"], temps),
            rng=at(state["rng"], rng),
            active=at(state["active"], active))


def _reject_types():
    # MoELayer's routing is cross-row (capacity is shared over the whole
    # batch), which breaks both decode-shape assumptions and the
    # row-independence the continuous-vs-sequential bit-identity pin
    # rests on — refuse rather than silently mis-route. The dropless
    # RoutedExpertsLayer has no capacity and is served (it is told the
    # live tokens: ``forward_live``)
    from deeplearning4j_tpu.conf.layers_moe import MoELayer

    return (GlobalPoolingLayer, LearnedSelfAttentionLayer,
            RecurrentAttentionLayer, MoELayer)


class TransformerDecoder:
    """KV-cached generation path over an initialized causal-LM
    ``ComputationGraph``.

    ``max_batch`` rows of KV cache are preallocated; the cache LENGTH is
    bucketed (``kv_bucket_min``, doubling to ``max_len``) and grows with
    the longest live sequence — each bucket is its own compiled
    executable, pre-built by ``warm_all``/engine ``warmup()``. State is
    one device-resident pytree (caches + per-row token/position/active/
    rng/temperature arrays) that every decode/join executable consumes
    donated and returns updated — the host never copies it.
    """

    def __init__(self, net, max_batch: int = 8, max_len: Optional[int] = None,
                 kv_bucket_min: int = 32, prompt_bucket_min: int = 8,
                 pad_id: int = 0, cache_dtype=None,
                 join_bucket_max: Optional[int] = None,
                 prompt_bucket_max: Optional[int] = None):
        self._net = net
        if net.params is None:
            net.init()
        self.max_batch = int(max_batch)
        self.pad_id = int(pad_id)
        self._dtype = net._dtype
        # the serving configuration's type for the caches; a layer's own
        # cache_dtype / state_dtype wins over it (conf/layers_hybrid.py)
        self._cache_dtype = (jnp.dtype(cache_dtype) if cache_dtype
                             else self._dtype)
        self._fns: Dict[str, object] = {}
        self.use_kernels = bool(getattr(net.conf, "use_kernels", False))
        conf = net.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError("KV-cached decode requires exactly one input "
                             "and one output vertex")
        self._input = conf.network_inputs[0]
        types = conf.vertex_output_types()
        self._plan = []
        # every layer that keeps per-row state (the cache interface of
        # conf/layers_hybrid.py): name -> n_in; _attn the KV-cached
        # SelfAttentionLayers among them (kernels/routing.py tunes those)
        self._cached: Dict[str, int] = {}
        self._attn: Dict[str, int] = {}
        derived_max = None
        reject = _reject_types()
        for name in net._topo:
            spec = net._vmap[name]
            layer = getattr(spec.vertex, "layer", None)
            cached = hasattr(layer, "cache_step")
            if isinstance(layer, reject) or (getattr(
                    spec.vertex, "has_carry", False) and not cached):
                raise ValueError(
                    f"vertex {name!r} ({type(layer or spec.vertex).__name__})"
                    " is not supported in the KV-cached decode path")
            if cached:
                if isinstance(layer, SelfAttentionLayer):
                    layer._decode_check()  # causal + projected, or raise
                src_t = types[spec.inputs[0]] if spec.inputs[0] in types \
                    else conf.input_types[0]
                self._cached[name] = src_t.size
                if isinstance(layer, SelfAttentionLayer):
                    self._attn[name] = src_t.size
                kind = "attn"
            elif isinstance(layer, PositionEmbeddingLayer):
                derived_max = layer.max_len if derived_max is None \
                    else min(derived_max, layer.max_len)
                kind = "pos"
            elif name in conf.network_outputs:
                if not isinstance(layer, OutputLayer):
                    raise ValueError("the output vertex must be an "
                                     "OutputLayer emitting vocab logits")
                kind = "head"
            else:
                kind = "gen"
            self._plan.append((kind, name, spec))
        if not self._cached:
            raise ValueError("graph has no causal SelfAttentionLayer (or "
                             "other layer with a decode cache) — nothing "
                             "to KV-cache")
        first = self._plan[0]
        if not (first[2].inputs == [self._input] or
                tuple(first[2].inputs) == (self._input,)) or \
                not isinstance(getattr(first[2].vertex, "layer", None),
                               EmbeddingSequenceLayer):
            raise ValueError("generation needs token-id inputs: the vertex "
                             "consuming the network input must be an "
                             "EmbeddingSequenceLayer (vocab_size > 0)")
        self.vocab_size = first[2].vertex.layer.n_in
        if max_len is None:
            max_len = derived_max
        if not max_len:
            raise ValueError("pass max_len= (no PositionEmbeddingLayer to "
                             "derive it from)")
        self.max_len = int(max_len if derived_max is None
                           else min(max_len, derived_max))
        self.kv_ladder = pow2_ladder(min(kv_bucket_min, self.max_len),
                                     self.max_len)
        # the longest prompt served (default max_len): a deployment whose
        # prompts are short beside its answers compiles no prompt or join
        # program for buckets no request of its reaches
        longest = min(int(prompt_bucket_max or self.max_len), self.max_len)
        self.prompt_ladder = pow2_ladder(min(prompt_bucket_min, longest),
                                         longest)
        self.join_ladder = pow2_ladder(
            1, min(int(join_bucket_max or self.max_batch), self.max_batch))
        # per-row counts the cached layers report at a step (summed over
        # the active rows of a decode window, returned beside its tokens)
        self._row_counters = sorted({
            k for name in self._cached
            for k in getattr(self._layer(name), "cache_counters", ())})
        # and the counts, one scalar a layer-step, of the layers without
        # state that are told the live tokens (``forward_live``)
        self._step_counters = sorted({
            k for _, name, _ in self._plan
            for k in getattr(self._layer(name), "live_counters", ())})
        self.counter_names = sorted(self._row_counters + self._step_counters)
        # any decode-state entry for a planned vertex would be silently
        # frozen at its init value — refuse rather than mis-serve
        stateful = [n for _, n, _ in self._plan if net.state.get(n)]
        if stateful:
            raise ValueError(f"stateful layers unsupported in decode: "
                             f"{stateful}")

    # --- state --------------------------------------------------------------
    def new_state(self, s: int) -> dict:
        """Fresh device-resident decode state at KV bucket ``s``: zeroed
        caches + per-row scheduler arrays (all rows inactive)."""
        b = self.max_batch
        caches = {name: self._layer(name).cache_init(b, s, n_in,
                                                     self._cache_dtype)
                  for name, n_in in self._cached.items()}
        return {
            "caches": caches,
            "tokens": jnp.zeros((b,), jnp.int32),
            "positions": jnp.zeros((b,), jnp.int32),
            "prompt_lens": jnp.ones((b,), jnp.int32),
            "max_new": jnp.ones((b,), jnp.int32),
            "eos": jnp.full((b,), -1, jnp.int32),
            "active": jnp.zeros((b,), bool),
            "rng": jnp.zeros((b, 2), jnp.uint32),
            "temps": jnp.zeros((b,), jnp.float32),
        }

    def _struct_of(self, s: int) -> dict:
        """ShapeDtypeStruct twin of :meth:`new_state` — lets ``warmup``
        compile every bucket without allocating a single cache buffer
        (``AotStep.warm`` only needs avals)."""
        b = self.max_batch
        sds = jax.ShapeDtypeStruct
        return {
            "caches": self._kv_struct(b, s),
            "tokens": sds((b,), jnp.int32),
            "positions": sds((b,), jnp.int32),
            "prompt_lens": sds((b,), jnp.int32),
            "max_new": sds((b,), jnp.int32),
            "eos": sds((b,), jnp.int32),
            "active": sds((b,), jnp.bool_),
            "rng": sds((b, 2), jnp.uint32),
            "temps": sds((b,), jnp.float32),
        }

    def _layer(self, name):
        return getattr(self._net._vmap[name].vertex, "layer", None)

    def state_bytes(self, s: int) -> Dict[str, int]:
        """Bytes the caches hold at KV bucket ``s``, by kind of state
        (``kv``, ``kv_ring``, ``compressed_keys``, ``recurrent``,
        ``conv_window``, ``latent``): what each layer's ``cache_kinds``
        calls its leaves."""
        out: Dict[str, int] = {}
        for name, leaves in self._kv_struct(self.max_batch, s).items():
            kinds = self._layer(name).cache_kinds
            for leaf, a in leaves.items():
                out[kinds[leaf]] = out.get(kinds[leaf], 0) + int(
                    np.prod(a.shape)) * a.dtype.itemsize
        return out

    def walks_missing(self, method: str) -> List[str]:
        """The cached vertices whose layer lacks ``method``
        (``prefill_suffix``: the prefix-cache walk), as
        ``name (LayerType)``."""
        return [f"{name!r} ({type(self._layer(name)).__name__})"
                for name in self._cached
                if not hasattr(self._layer(name), method)]

    def _need(self, method: str, walk: str):
        missing = self.walks_missing(method)
        if missing:
            raise NotImplementedError(
                f"{walk} needs {method}() on every cached layer; "
                f"{', '.join(missing)} keep state it cannot rebuild from "
                f"K/V pages")

    def _graph_key(self):
        return self._net._graph_key()

    def _ktag(self) -> str:
        """The ``:kern:<id>:<digest>`` token string folded into every
        step key (and ``_fns`` memo key): empty unless
        ``conf.use_kernels``, so pre-subsystem keys are untouched. Keyed
        off the tuning-cache epoch — a retune changes the digest, the
        next getter call misses the memo, and the re-trace bakes the new
        winner (a NEW executable, never a silently stale kernel)."""
        from deeplearning4j_tpu import kernels

        return kernels.cache_tag(self._net.conf)

    @property
    def net(self):
        """The wrapped ComputationGraph (shares live params — training
        the net between generations is visible immediately)."""
        return self._net

    @property
    def params(self):
        return self._net.params

    # --- the model walk ------------------------------------------------------
    def _walk(self, params, tokens, cached, positions=None, lengths=None,
              live=None, tally=None):
        """The one walk over the plan: ``tokens`` (``[B]`` for a decode
        step, ``[B, T]`` for a prompt or a suffix) → vocab logits. What
        differs between the walks is the caller's: ``cached(name, params,
        x) -> y`` runs a layer that keeps per-row state (and keeps that
        layer's new cache or KV block, and its counts, for the caller);
        ``positions`` is where a position vertex gathers its table
        (None: the layer's own forward, positions ``0..T-1``);
        ``lengths`` puts the head on each row's last valid position
        alone (None: on all of ``x``) — a whole ``[T, vocab]`` of logits
        is never needed, and at a 32k bucket of a 73k vocabulary would
        not fit. ``live`` (shaped like ``tokens``, true or above 0 where a
        token is real; None: all) is for a layer without state that groups the batch
        (``forward_live``: the routed experts); ``tally(counts)`` gets
        what such a layer counted."""
        acts = {self._input: tokens}
        logits = None
        for kind, name, spec in self._plan:
            xs = [acts[src] for src in spec.inputs]
            with self._scope(name):
                if kind == "attn":
                    y = cached(name, params[name], xs[0])
                elif kind == "pos" and positions is not None:
                    y = xs[0] + params[name]["P"][positions]
                elif kind == "head":
                    x = xs[0]
                    if lengths is not None:
                        idx = jnp.maximum(lengths - 1, 0)[:, None, None]
                        x = jnp.take_along_axis(x, idx, axis=1)[:, 0]
                    logits = self._layer(name).pre_output(
                        self._net._params_of(params, name), x)
                    continue
                elif hasattr(self._layer(name), "forward_live"):
                    y, own = self._layer(name).forward_live(
                        params[name], xs[0],
                        jnp.ones(tokens.shape, bool) if live is None
                        else live)
                    if tally is not None:
                        tally(own)
                else:
                    y, _ = spec.vertex.forward(params.get(name, {}), {}, xs,
                                               train=False, rng=None)
            acts[name] = y
        return logits

    @contextlib.contextmanager
    def _scope(self, name):
        """The two nested ``jax.named_scope``s of a plan entry, ``<class>``
        then ``<vertex name>``: what ``telemetry.device_time`` files the
        device's time under. The class is the layer type's ``scope_class``
        (``telemetry.device_time.SCOPE_CLASSES``). Metadata of the lowered
        program only: nothing at run time."""
        with jax.named_scope(self._net._vmap[name].vertex.scope_class), \
                jax.named_scope(name):
            yield

    def _run_token(self, params, tokens, positions, caches, active=None):
        """One token through the graph against the caches:
        ``tokens [B] int32`` → (vocab logits ``[B, V]``, new caches,
        the layers' counts summed over the layers: ``[B] int32`` each
        for a cached layer's, a scalar for a ``forward_live`` layer's)."""
        caches = dict(caches)
        counts: Dict[str, object] = {}

        def tally(own):
            for k, v in own.items():
                counts[k] = counts[k] + v if k in counts else v

        def step(name, p, x):
            y, caches[name], own = self._layer(name).cache_step(
                p, x, caches[name], positions, active=active)
            tally(own)
            return y

        logits = self._walk(params, tokens, step, positions=positions,
                            live=active, tally=tally)
        return logits, caches, counts

    def _run_prompt(self, params, prompts, lengths):
        """Whole-prompt prefill walk: ``prompts [Bp, Tp] int32`` →
        (last-valid-position logits ``[Bp, V]``, per-layer kv blocks in
        cache layout)."""
        with jax.named_scope("window.prepare"):
            key_mask = (jnp.arange(prompts.shape[1])[None, :]
                        < lengths[:, None]).astype(self._dtype)
        kv = {}

        def prefill(name, p, x):
            y, kv[name] = self._layer(name).cache_prefill(
                p, x, key_mask, dtype=self._cache_dtype,
                use_kernels=self.use_kernels)
            return y

        return self._walk(params, prompts, prefill, lengths=lengths,
                          live=key_mask), kv

    def _run_suffix(self, params, suffix, suf_lens, prefix_kv, prefix_lens):
        """Prompt-SUFFIX prefill walk against already-projected prefix
        KV pages: ``suffix [Bp, Ts] int32`` holds only the uncached tail
        of each prompt, ``prefix_kv[name]{k,v} [Bp, Tpre, heads * hd]``
        the shared pages (valid up to ``prefix_lens[b]``). Position
        embeddings are gathered at the suffix tokens' TRUE positions
        (``prefix_lens + i``), and each attention layer attends the
        ``[prefix ; suffix]`` concatenation — cold-prefill semantics
        minus re-projecting the prefix. Returns (last-valid-position
        logits ``[Bp, V]``, suffix-only kv blocks)."""
        self._need("prefill_suffix", "the prefix-cache suffix walk")
        ts = suffix.shape[1]
        tpre = next(iter(prefix_kv.values()))["k"].shape[1]
        with jax.named_scope("window.prepare"):
            key_mask = (jnp.arange(ts)[None, :]
                        < suf_lens[:, None]).astype(self._dtype)
            prefix_mask = (jnp.arange(tpre)[None, :]
                           < prefix_lens[:, None]).astype(self._dtype)
        kv = {}

        def prefill(name, p, x):
            y, k, v = self._layer(name).prefill_suffix(
                p, x, prefix_kv[name]["k"], prefix_kv[name]["v"],
                prefix_mask, key_mask, use_kernels=self.use_kernels)
            kv[name] = {"k": k, "v": v}
            return y

        with jax.named_scope("window.prepare"):
            at = jnp.clip(prefix_lens[:, None] + jnp.arange(ts),
                          0, self.max_len - 1)
        return self._walk(params, suffix, prefill, positions=at,
                          lengths=suf_lens, live=key_mask), kv

    # --- compiled executables (all through optimize/aot_cache) -------------
    def _exe(self, kind: str, fn, donate=()):
        """``fn`` jitted behind the AOT cache under the step kind ``kind``
        plus the kernel tag (:meth:`_ktag`), built once a kind: a retune
        changes the tag, and the next call traces a new executable."""
        kind += self._ktag()
        if kind not in self._fns:
            self._fns[kind] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=donate), self._graph_key(), kind)
        return self._fns[kind]

    def _each_cache(self, state, op):
        """``{name: op(layer, name, cache)}`` over the state's caches,
        each under its layer's scopes and ``cache.write``: a join, a grow
        or a release is a write of the cache and nothing else."""
        out = {}
        for name, c in state["caches"].items():
            with self._scope(name), jax.named_scope("cache.write"):
                out[name] = op(self._layer(name), name, c)
        return out

    def decode_fn(self, s: int, k: int):
        """K fused decode steps at KV bucket ``s``: ``lax.scan`` of the
        single-token walk, in-graph EOS/max-tokens masking (finished
        rows stop advancing, their rng/token/position freeze), state
        DONATED. Returns ``(state', tokens [K, B], emitted [K, B])`` —
        ``emitted[i, b]`` is True where row b was live going into step i
        (the host appends exactly those tokens)."""
        def fn(params, state):
            st, toks, emitted, counts = self._decode_window(
                params, state, k)
            if not self.counter_names:
                return st, toks, emitted
            # the layers' counters ride the window's own outputs: the
            # engine reads them with the tokens, no further sync
            with jax.named_scope("window.account"):
                return st, toks, emitted, jnp.stack(
                    [counts[n] for n in self.counter_names])

        return self._exe(f"decode_step:s{s}:k{k}", fn, donate=(1,))

    def _decode_window(self, params, state, k):
        """The fused K-step window body of :meth:`decode_fn`:
        ``lax.scan`` of the single-token walk with in-graph
        EOS/max-tokens masking."""
        def body(st, _):
            active = st["active"]
            logits, caches, counts = self._run_token(
                params, st["tokens"], st["positions"], st["caches"],
                active=active)
            with jax.named_scope("window.account"):
                counts = {**{n: jnp.sum(jnp.where(active, counts[n], 0))
                             for n in self._row_counters},
                          **{n: counts[n] for n in self._step_counters}}
            with jax.named_scope("sample"):
                step_keys, rng_next = _advance_rng(st["rng"])
                tok = _sample_tokens(logits, step_keys, st["temps"])
            with jax.named_scope("window.account"):
                tok = jnp.where(active, tok, st["tokens"])
                new_pos = st["positions"] + active.astype(jnp.int32)
                gen = new_pos - st["prompt_lens"] + 1
                nxt = active & (tok != st["eos"]) & (gen < st["max_new"])
                st = dict(st, caches=caches, tokens=tok,
                          positions=new_pos, active=nxt,
                          rng=jnp.where(active[:, None], rng_next,
                                        st["rng"]))
            return st, (tok, active, counts)

        st, (toks, emitted, counts) = jax.lax.scan(body, state, None,
                                                   length=k)
        with jax.named_scope("window.account"):
            counts = {n: jnp.sum(c, dtype=jnp.int32)
                      for n, c in counts.items()}
        return st, toks, emitted, counts

    def prompt_fn(self, tp: int, bp: int):
        """Prefill forward for a compact ``[bp, tp]`` group of joining
        prompts: kv blocks + sampled first token + in-graph liveness
        (:func:`_first_token`)."""
        def fn(params, prompts, lengths, max_new, eos, temps, rng):
            logits, kv = self._run_prompt(params, prompts, lengths)
            with jax.named_scope("sample"):
                return (kv,) + _first_token(logits, max_new, eos, temps,
                                            rng)

        return self._exe(f"gen_prompt:t{tp}:b{bp}", fn)

    def join_fn(self, s: int, tp: int, bp: int):
        """Scatter a prefilled group into the running state at given row
        indices (length-``bp``; slots >= ``max_batch`` are padding and
        dropped by the scatter). State DONATED — this is the ``prefill*``
        kind the PRG201 donation audit proves writes the KV cache in
        place."""
        def fn(state, kv, rows, tok, lengths, max_new, eos, temps,
               rng, active):
            caches = self._each_cache(
                state, lambda layer, name, c: layer.cache_join(
                    c, kv[name], rows, s))
            return _seed_rows(state, caches, rows, tok, lengths, max_new,
                              eos, temps, rng, active)

        return self._exe(f"prefill_join:s{s}:t{tp}:b{bp}", fn, donate=(0,))

    def grow_fn(self, s: int, s2: int):
        """Pad every cache from KV bucket ``s`` to ``s2`` (the bucket
        hop when the longest live sequence outgrows the current cache).
        Not donated: the cache shapes differ, so XLA could not alias
        them anyway — the old buffers free by refcount when the engine
        swaps states."""
        def fn(state):
            return dict(state, caches=self._each_cache(
                state, lambda layer, name, c: layer.cache_grow(c, s2)))

        return self._exe(f"kv_grow:s{s}:{s2}", fn)

    def release_fn(self, s: int):
        """Deactivate rows in-graph (deadline aborts, breaker resets):
        ``active &= keep``. State donated; everything else passes
        through aliased."""
        def fn(state, keep):
            caches = self._each_cache(
                state, lambda layer, name, c: layer.cache_release(c, keep))
            with jax.named_scope("window.account"):
                return dict(state, caches=caches,
                            active=state["active"] & keep)

        return self._exe(f"gen_release:s{s}", fn, donate=(0,))

    # --- prefix-cache executables ------------------------------------------
    def prefix_attach_fn(self, s: int, tpre: int, bp: int):
        """Scatter shared prefix KV pages into joining rows' caches —
        the ``prefill_join`` shape applied to cached pages instead of a
        fresh prefill: ``prefix_kv[name]{k,v} [bp, tpre, heads * hd]``
        lands at slots ``[0, tpre)`` of each row in ``rows`` (OOB slots
        are padding, dropped), ``positions`` is set to the per-row valid
        prefix length. State DONATED — the audit-visible in-place cache
        write that makes a hit O(pages copied), not O(prefix
        re-projected)."""
        def fn(state, prefix_kv, rows, prefix_lens):
            caches = self._each_cache(state, lambda layer, name, c: {
                "k": c["k"].at[rows, :tpre].set(
                    prefix_kv[name]["k"], mode="drop"),
                "v": c["v"].at[rows, :tpre].set(
                    prefix_kv[name]["v"], mode="drop"),
            })
            with jax.named_scope("window.account"):
                return dict(
                    state, caches=caches,
                    positions=state["positions"].at[rows].set(
                        prefix_lens, mode="drop"))

        return self._exe(f"prefix_attach:s{s}:t{tpre}:b{bp}", fn, donate=(0,))

    def suffix_prompt_fn(self, ts: int, tpre: int, bp: int):
        """Suffix-only prefill for a prefix-cache-hit join group: like
        :meth:`prompt_fn` but over ``[bp, ts]`` suffix tokens attending
        the shared prefix pages (see :meth:`_run_suffix`). NOT donated —
        the prefix pages are shared, refcounted buffers that other
        requests may attach concurrently."""
        def fn(params, suffix, suf_lens, prefix_kv, prefix_lens,
               max_new, eos, temps, rng):
            logits, kv = self._run_suffix(
                params, suffix, suf_lens, prefix_kv, prefix_lens)
            with jax.named_scope("sample"):
                return (kv,) + _first_token(logits, max_new, eos, temps,
                                            rng)

        return self._exe(f"gen_prompt_sfx:t{ts}:p{tpre}:b{bp}", fn)

    def suffix_join_fn(self, s: int, ts: int, bp: int):
        """Join a suffix-prefilled group behind its attached prefix: the
        suffix kv block lands at each row's PER-ROW offset
        (``prefix_lens[i]``, a traced ``dynamic_update_slice`` — the
        static join scatter cannot express a per-row start), and the row
        arrays are seeded exactly like :meth:`join_fn` with
        ``positions = prefix + suffix = full prompt length``. Padding
        group slots write back what the target row already holds (a
        gather/select no-op) because ``dynamic_update_slice`` clamps
        instead of dropping. State DONATED."""
        def fn(state, kv, rows, tok, prefix_lens, lengths, max_new,
               eos, temps, rng, active):
            b = self.max_batch
            with jax.named_scope("window.prepare"):
                valid = rows < b
                rc = jnp.minimum(rows, b - 1)
                off = jnp.clip(prefix_lens, 0, s - ts)

            def write(layer, name, c):
                ck, cv = c["k"], c["v"]
                for i in range(bp):
                    cur_k = jax.lax.dynamic_slice(
                        ck, (rc[i], off[i], 0),
                        (1,) + kv[name]["k"].shape[1:])
                    cur_v = jax.lax.dynamic_slice(
                        cv, (rc[i], off[i], 0),
                        (1,) + kv[name]["v"].shape[1:])
                    new_k = jnp.where(valid[i], kv[name]["k"][i][None],
                                      cur_k)
                    new_v = jnp.where(valid[i], kv[name]["v"][i][None],
                                      cur_v)
                    ck = jax.lax.dynamic_update_slice(
                        ck, new_k, (rc[i], off[i], 0))
                    cv = jax.lax.dynamic_update_slice(
                        cv, new_v, (rc[i], off[i], 0))
                return {"k": ck, "v": cv}

            return _seed_rows(state, self._each_cache(state, write), rows,
                              tok, lengths, max_new, eos, temps, rng,
                              active)

        return self._exe(f"prefix_join:s{s}:t{ts}:b{bp}", fn, donate=(0,))

    # --- warmup -------------------------------------------------------------
    def _kv_struct(self, bp: int, tp: int):
        """ShapeDtypeStruct pytree of the per-layer state of ``bp`` rows
        by ``tp`` positions (the caches themselves, a prefill's output,
        prefix pages): whatever each layer's ``cache_init`` returns."""
        return {name: jax.eval_shape(
            lambda layer=self._layer(name), n_in=n_in: layer.cache_init(
                bp, tp, n_in, self._cache_dtype))
            for name, n_in in self._cached.items()}

    def _ladder_floor(self, ladder: List[int], b: int) -> int:
        """Smallest real length that maps to bucket ``b`` (one past the
        previous ladder entry; 1 for the first)."""
        i = ladder.index(b)
        return 1 if i == 0 else ladder[i - 1] + 1

    def warm_all(self, fused_steps=(1,), prefix=False) -> dict:
        """Compile every (bucket, K) combination WITHOUT dispatching
        (``AotStep.warm`` on ShapeDtypeStructs): all KV buckets × K for
        decode, prompt × join buckets for prefill, every (S, T<=S, B)
        join, every upward grow hop, the release fn. ``prefix`` warms
        every feasible prefix-attach / suffix-prefill / suffix-join
        bucket combination (feasible = some real prefix and suffix
        lengths map to the pair without exceeding ``max_len``). After
        this, mixed prompt/output-length traffic — including mixed
        prefix hit/miss — is zero-recompile by construction (pinned in
        tests)."""
        sds = jax.ShapeDtypeStruct
        params = jax.tree_util.tree_map(
            lambda x: sds(jnp.shape(x), x.dtype), self._net.params)

        def rows(bp):
            """Avals of a join group of ``bp``: one int32 a row, and the
            request arrays every prefill takes (max_new, eos, temps,
            rng)."""
            i32 = sds((bp,), jnp.int32)
            return i32, (i32, i32, sds((bp,), jnp.float32),
                         sds((bp, 2), jnp.uint32)), sds((bp,), jnp.bool_)

        before = aot_cache.stats()
        for s in self.kv_ladder:
            st = self._struct_of(s)
            for k in fused_steps:
                self.decode_fn(s, int(k)).warm(params, st)
            self.release_fn(s).warm(st, sds((self.max_batch,), jnp.bool_))
            for s2 in self.kv_ladder:
                if s2 > s:
                    self.grow_fn(s, s2).warm(st)
        if prefix:
            # the suffix path always pads its join group to max_batch
            # (padding rows scatter out of bounds and drop) so the
            # prefix machinery compiles ONE join-width per shape — the
            # full join ladder here would multiply the warm set ~4x
            # for no measurable prefill win at these sizes
            bp = self.max_batch
            i32, req, live = rows(bp)
            for tpre in self.prompt_ladder:
                m_min = self._ladder_floor(self.prompt_ladder, tpre)
                for s in self.kv_ladder:
                    if tpre <= s:
                        self.prefix_attach_fn(s, tpre, bp).warm(
                            self._struct_of(s), self._kv_struct(bp, tpre),
                            i32, i32)
                for ts in self.prompt_ladder:
                    if m_min + self._ladder_floor(
                            self.prompt_ladder, ts) > self.max_len:
                        continue
                    self.suffix_prompt_fn(ts, tpre, bp).warm(
                        params, sds((bp, ts), jnp.int32), i32,
                        self._kv_struct(bp, tpre), i32, *req)
            for s in self.kv_ladder:
                for ts in self.prompt_ladder:
                    if ts <= s:
                        self.suffix_join_fn(s, ts, bp).warm(
                            self._struct_of(s), self._kv_struct(bp, ts),
                            i32, i32, i32, i32, *req, live)
        for tp in self.prompt_ladder:
            for bp in self.join_ladder:
                i32, req, live = rows(bp)
                self.prompt_fn(tp, bp).warm(
                    params, sds((bp, tp), jnp.int32), i32, *req)
                for s in self.kv_ladder:
                    if tp <= s:
                        self.join_fn(s, tp, bp).warm(
                            self._struct_of(s), self._kv_struct(bp, tp),
                            i32, i32, i32, *req, live)
        after = aot_cache.stats()
        return {
            "kv_buckets": list(self.kv_ladder),
            "prompt_buckets": list(self.prompt_ladder),
            "join_buckets": list(self.join_ladder),
            "fused_steps": [int(k) for k in fused_steps],
            "prefix": bool(prefix),
            "compiled": after["misses"] - before["misses"],
            "compile_seconds": round(
                after["compile_seconds"] - before["compile_seconds"], 3),
        }

    # --- sequential reference ----------------------------------------------
    def validate_request(self, tokens, max_new: int):
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if not toks:
            raise ValueError("prompt must contain at least one token")
        if any(t < 0 or t >= self.vocab_size for t in toks):
            raise ValueError(f"token ids must be in [0, {self.vocab_size})")
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(toks) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(toks)}) + max_new_tokens ({max_new}) "
                f"exceeds max_len={self.max_len}")
        if len(toks) > self.prompt_ladder[-1]:
            raise ValueError(
                f"prompt ({len(toks)}) exceeds the largest prompt bucket "
                f"{self.prompt_ladder[-1]}")
        return toks

    def generate(self, tokens, max_new: int, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 fused_steps: int = 1) -> List[int]:
        """Sequential single-request generation through the SAME compiled
        executables the continuous engine uses (one live row, the other
        ``max_batch - 1`` rows inactive). This is the unbatched
        reference: the engine's continuous schedule is pinned to produce
        token-identical greedy output."""
        toks = self.validate_request(tokens, max_new)
        ln = len(toks)
        tp = bucket_for(ln, self.prompt_ladder)
        # the KV bucket must cover the prompt bucket too: the join
        # scatter pads the [tp]-long prompt KV out to [s], and the
        # ladders need not be aligned (kv_bucket_min can sit below a
        # prompt bucket)
        s = bucket_for(max(min(ln + max_new, self.max_len), tp),
                       self.kv_ladder)
        state = self.new_state(s)
        prompts = np.full((1, tp), self.pad_id, np.int32)
        prompts[0, :ln] = toks
        rng = np.asarray(jax.random.PRNGKey(int(seed)),
                         np.uint32).reshape(1, 2)
        eos = np.asarray([-1 if eos_id is None else int(eos_id)], np.int32)
        lengths = np.asarray([ln], np.int32)
        mn = np.asarray([int(max_new)], np.int32)
        temps = np.asarray([float(temperature)], np.float32)
        kv, tok, active, rng2 = self.prompt_fn(tp, 1)(
            self._net.params, prompts, lengths, mn, eos, temps, rng)
        rows = np.asarray([0], np.int32)
        state = self.join_fn(s, tp, 1)(
            state, kv, rows, tok, lengths, mn, eos, temps, rng2, active)
        out = [int(np.asarray(tok)[0])]
        alive = bool(np.asarray(active)[0])
        step = self.decode_fn(s, int(fused_steps))
        while alive:
            state, toks_w, emitted = step(self._net.params, state)[:3]
            toks_w = np.asarray(toks_w)
            emitted = np.asarray(emitted)
            for i in range(toks_w.shape[0]):
                if not emitted[i, 0]:
                    alive = False
                    break
                t = int(toks_w[i, 0])
                out.append(t)
                if (eos_id is not None and t == eos_id) \
                        or len(out) >= max_new:
                    alive = False
                    break
        return out
