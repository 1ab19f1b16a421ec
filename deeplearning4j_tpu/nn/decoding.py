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
fixed-size recurrent state (``LightningAttentionLayer``), side by side in
the one donated state pytree, each in its own type. The chunk and suffix
walks (speculation, the prefix cache) need ``decode_chunk`` /
``prefill_suffix`` on every such layer and refuse a graph that has a
layer without them, by name.

Scheduling on top of this lives in ``parallel.generation`` — this module
is the pure model path plus :meth:`TransformerDecoder.generate`, the
sequential one-request-at-a-time reference the continuous-batching
engine is pinned bit-identical against (greedy token ids).
"""

from __future__ import annotations

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


def _reject_types():
    # MoE routing is cross-row (capacity is shared over the whole
    # batch), which breaks both decode-shape assumptions and the
    # row-independence the continuous-vs-sequential bit-identity pin
    # rests on — refuse rather than silently mis-route
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
                 join_bucket_max: Optional[int] = None):
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
        self._fns: Dict[tuple, object] = {}
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
        self.prompt_ladder = pow2_ladder(min(prompt_bucket_min, self.max_len),
                                         self.max_len)
        self.join_ladder = pow2_ladder(
            1, min(int(join_bucket_max or self.max_batch), self.max_batch))
        # per-row counts the cached layers report at a step (summed over
        # the active rows of a decode window, returned beside its tokens)
        self.counter_names = sorted({
            k for name in self._cached
            for k in getattr(self._layer(name), "cache_counters", ())})
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
        return self._net._vmap[name].vertex.layer

    def state_bytes(self, s: int) -> Dict[str, int]:
        """Bytes the caches hold at KV bucket ``s``, by kind of state
        (``kv``, ``compressed_keys``, ``recurrent``): what each layer's
        ``cache_kinds`` calls its leaves."""
        out: Dict[str, int] = {}
        for name, leaves in self._kv_struct(self.max_batch, s).items():
            kinds = self._layer(name).cache_kinds
            for leaf, a in leaves.items():
                out[kinds[leaf]] = out.get(kinds[leaf], 0) + int(
                    np.prod(a.shape)) * a.dtype.itemsize
        return out

    def walks_missing(self, method: str) -> List[str]:
        """The cached vertices whose layer lacks ``method`` (``decode_chunk``:
        the speculative verify walk; ``prefill_suffix``: the prefix-cache
        walk), as ``name (LayerType)``."""
        return [f"{name!r} ({type(self._layer(name)).__name__})"
                for name in self._cached
                if not hasattr(self._layer(name), method)]

    def _need(self, method: str, walk: str):
        missing = self.walks_missing(method)
        if missing:
            raise NotImplementedError(
                f"{walk} needs {method}() on every cached layer; "
                f"{', '.join(missing)} keep state it cannot rebuild from "
                f"K/V pages or roll back by a cursor")

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

    # --- pure model walks ---------------------------------------------------
    def _run_token(self, params, tokens, positions, caches, active=None):
        """One token through the graph against the caches:
        ``tokens [B] int32`` → (vocab logits ``[B, V]``, new caches,
        the layers' counts ``{name: [B] int32}`` summed over the
        layers)."""
        acts = {self._input: tokens}
        caches = dict(caches)
        logits = None
        counts: Dict[str, object] = {}
        for kind, name, spec in self._plan:
            xs = [acts[src] for src in spec.inputs]
            if kind == "attn":
                y, caches[name], own = self._layer(name).cache_step(
                    params[name], xs[0], caches[name], positions,
                    active=active, use_kernels=self.use_kernels)
                for k, v in own.items():
                    counts[k] = counts[k] + v if k in counts else v
            elif kind == "pos":
                y = xs[0] + params[name]["P"][positions]
            elif kind == "head":
                logits = self._layer(name).pre_output(params[name], xs[0])
                continue
            else:
                y, _ = spec.vertex.forward(params.get(name, {}), {}, xs,
                                           train=False, rng=None)
            acts[name] = y
        return logits, caches, counts

    def _run_prompt(self, params, prompts, lengths):
        """Whole-prompt prefill walk: ``prompts [Bp, Tp] int32`` →
        (last-valid-position logits ``[Bp, V]``, per-layer kv blocks in
        cache layout)."""
        tp = prompts.shape[1]
        key_mask = (jnp.arange(tp)[None, :]
                    < lengths[:, None]).astype(self._dtype)
        acts = {self._input: prompts}
        kv = {}
        logits = None
        for kind, name, spec in self._plan:
            xs = [acts[src] for src in spec.inputs]
            if kind == "attn":
                y, kv[name] = self._layer(name).cache_prefill(
                    params[name], xs[0], key_mask, dtype=self._cache_dtype,
                    use_kernels=self.use_kernels)
            elif kind == "head":
                # the head over the last valid position alone: a whole
                # [Tp, vocab] of logits is never needed (and at a 32k
                # bucket of a 73k vocabulary would not fit)
                idx = jnp.maximum(lengths - 1, 0)[:, None, None]
                last = jnp.take_along_axis(xs[0], idx, axis=1)[:, 0]
                logits = self._layer(name).pre_output(params[name], last)
                continue
            else:  # pos + generic both run the ordinary layer forward
                y, _ = spec.vertex.forward(params.get(name, {}), {}, xs,
                                           train=False, rng=None)
            acts[name] = y
        return logits, kv

    def _run_chunk(self, params, tokens, positions, caches):
        """A ``[B, T]`` window of tokens through the graph against the
        caches in ONE wide step (no scan): token ``i`` of row ``b`` sits
        at cache slot ``positions[b] + i``. Returns (full per-position
        logits ``[B, T, V]``, new caches) — the speculative verifier
        scores every drafted position from one launch of this walk."""
        self._need("decode_chunk", "the speculative verify walk")
        t = tokens.shape[1]
        acts = {self._input: tokens}
        caches = dict(caches)
        logits = None
        for kind, name, spec in self._plan:
            xs = [acts[src] for src in spec.inputs]
            if kind == "attn":
                y, caches[name] = self._layer(name).decode_chunk(
                    params[name], xs[0], caches[name], positions)
            elif kind == "pos":
                idx = jnp.clip(positions[:, None] + jnp.arange(t),
                               0, self.max_len - 1)
                y = xs[0] + params[name]["P"][idx]
            elif kind == "head":
                logits = self._layer(name).pre_output(params[name], xs[0])
                continue
            else:
                y, _ = spec.vertex.forward(params.get(name, {}), {}, xs,
                                           train=False, rng=None)
            acts[name] = y
        return logits, caches

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
        key_mask = (jnp.arange(ts)[None, :]
                    < suf_lens[:, None]).astype(self._dtype)
        prefix_mask = (jnp.arange(tpre)[None, :]
                       < prefix_lens[:, None]).astype(self._dtype)
        acts = {self._input: suffix}
        kv = {}
        logits = None
        for kind, name, spec in self._plan:
            xs = [acts[src] for src in spec.inputs]
            if kind == "attn":
                y, k, v = self._layer(name).prefill_suffix(
                    params[name], xs[0], prefix_kv[name]["k"],
                    prefix_kv[name]["v"], prefix_mask, key_mask,
                    use_kernels=self.use_kernels)
                kv[name] = {"k": k, "v": v}
            elif kind == "pos":
                idx = jnp.clip(prefix_lens[:, None] + jnp.arange(ts),
                               0, self.max_len - 1)
                y = xs[0] + params[name]["P"][idx]
            elif kind == "head":
                full = self._layer(name).pre_output(params[name], xs[0])
                idx = jnp.maximum(suf_lens - 1, 0)[:, None, None]
                logits = jnp.take_along_axis(full, idx, axis=1)[:, 0]
                continue
            else:
                y, _ = spec.vertex.forward(params.get(name, {}), {}, xs,
                                           train=False, rng=None)
            acts[name] = y
        return logits, kv

    # --- compiled executables (all through optimize/aot_cache) -------------
    def decode_fn(self, s: int, k: int):
        """K fused decode steps at KV bucket ``s``: ``lax.scan`` of the
        single-token walk, in-graph EOS/max-tokens masking (finished
        rows stop advancing, their rng/token/position freeze), state
        DONATED. Returns ``(state', tokens [K, B], emitted [K, B])`` —
        ``emitted[i, b]`` is True where row b was live going into step i
        (the host appends exactly those tokens)."""
        tag = self._ktag()
        key = ("decode", s, k, tag)
        if key not in self._fns:
            def fn(params, state):
                st, toks, emitted, counts = self._decode_window(
                    params, state, k)
                if not self.counter_names:
                    return st, toks, emitted
                # the layers' counters ride the window's own outputs: the
                # engine reads them with the tokens, no further sync
                return st, toks, emitted, jnp.stack(
                    [counts[n] for n in self.counter_names])

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(1,)), self._graph_key(),
                f"decode_step:s{s}:k{k}{tag}")
        return self._fns[key]

    def _decode_window(self, params, state, k):
        """The fused K-step window body shared by :meth:`decode_fn` and
        :meth:`spec_draft_fn`: ``lax.scan`` of the single-token walk
        with in-graph EOS/max-tokens masking."""
        def body(st, _):
            active = st["active"]
            logits, caches, counts = self._run_token(
                params, st["tokens"], st["positions"], st["caches"],
                active=active)
            counts = {n: jnp.sum(jnp.where(active, counts[n], 0))
                      for n in self.counter_names}
            step_keys, rng_next = _advance_rng(st["rng"])
            tok = _sample_tokens(logits, step_keys, st["temps"])
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
        return st, toks, emitted, {n: jnp.sum(c, dtype=jnp.int32)
                                   for n, c in counts.items()}

    def spec_draft_fn(self, s: int, k: int):
        """The DRAFT side of a speculative iteration in ONE launch:
        overwrite the draft's cursor with the target's (the spec_sync
        reconciliation — accepted slots already hold the right k/v, so
        it is pure bookkeeping) and run the fused K-step window from
        there. Folding the sync into the window halves the draft-side
        dispatches per iteration, which is most of speculation's cost
        on a dispatch-bound host. State DONATED; the cursor arrays come
        from the TARGET's state and are not."""
        tag = self._ktag()
        key = ("spec_draft", s, k, tag)
        if key not in self._fns:
            def fn(params, state, tokens, positions, active):
                st = dict(state, tokens=tokens, positions=positions,
                          active=active)
                return self._decode_window(params, st, k)[:3]

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(1,)), self._graph_key(),
                f"spec_draft:s{s}:k{k}{tag}")
        return self._fns[key]

    def prompt_fn(self, tp: int, bp: int):
        """Prefill forward for a compact ``[bp, tp]`` group of joining
        prompts: kv blocks + sampled first token + in-graph liveness
        (EOS-on-first-token / max_new == 1 rows are born retired)."""
        tag = self._ktag()
        key = ("prompt", tp, bp, tag)
        if key not in self._fns:
            def fn(params, prompts, lengths, max_new, eos, temps, rng):
                logits, kv = self._run_prompt(params, prompts, lengths)
                step_keys, rng_next = _advance_rng(rng)
                tok = _sample_tokens(logits, step_keys, temps)
                active = (tok != eos) & (max_new > 1)
                return kv, tok, active, rng_next

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn), self._graph_key(),
                f"gen_prompt:t{tp}:b{bp}{tag}")
        return self._fns[key]

    def join_fn(self, s: int, tp: int, bp: int):
        """Scatter a prefilled group into the running state at given row
        indices (length-``bp``; slots >= ``max_batch`` are padding and
        dropped by the scatter). State DONATED — this is the ``prefill*``
        kind the PRG201 donation audit proves writes the KV cache in
        place."""
        tag = self._ktag()
        key = ("join", s, tp, bp, tag)
        if key not in self._fns:
            def fn(state, kv, rows, tok, lengths, max_new, eos, temps,
                   rng, active):
                caches = {name: self._layer(name).cache_join(
                    c, kv[name], rows, s)
                    for name, c in state["caches"].items()}
                at = lambda a, v: a.at[rows].set(v, mode="drop")  # noqa: E731
                return dict(
                    state, caches=caches,
                    tokens=at(state["tokens"], tok),
                    positions=at(state["positions"], lengths),
                    prompt_lens=at(state["prompt_lens"],
                                   jnp.maximum(lengths, 1)),
                    max_new=at(state["max_new"], max_new),
                    eos=at(state["eos"], eos),
                    temps=at(state["temps"], temps),
                    rng=at(state["rng"], rng),
                    active=at(state["active"], active))

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(0,)), self._graph_key(),
                f"prefill_join:s{s}:t{tp}:b{bp}{tag}")
        return self._fns[key]

    def grow_fn(self, s: int, s2: int):
        """Pad every cache from KV bucket ``s`` to ``s2`` (the bucket
        hop when the longest live sequence outgrows the current cache).
        Not donated: the cache shapes differ, so XLA could not alias
        them anyway — the old buffers free by refcount when the engine
        swaps states."""
        tag = self._ktag()
        key = ("grow", s, s2, tag)
        if key not in self._fns:
            def fn(state):
                caches = {name: self._layer(name).cache_grow(c, s2)
                          for name, c in state["caches"].items()}
                return dict(state, caches=caches)

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn), self._graph_key(), f"kv_grow:s{s}:{s2}{tag}")
        return self._fns[key]

    def release_fn(self, s: int):
        """Deactivate rows in-graph (deadline aborts, breaker resets):
        ``active &= keep``. State donated; everything else passes
        through aliased."""
        tag = self._ktag()
        key = ("release", s, tag)
        if key not in self._fns:
            def fn(state, keep):
                caches = {name: self._layer(name).cache_release(c, keep)
                          for name, c in state["caches"].items()}
                return dict(state, caches=caches,
                            active=state["active"] & keep)

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(0,)), self._graph_key(),
                f"gen_release:s{s}{tag}")
        return self._fns[key]

    # --- speculative decoding (draft K, verify K+1 in one launch) ----------
    def spec_verify_fn(self, s: int, k: int):
        """Score a K-token drafted window in ONE wide launch — the
        speculative-decoding verifier. Input ``drafts [K, B]`` holds the
        draft model's proposals; the window fed through the graph is
        ``[current token ; drafts]`` (K+1 positions), scored by
        :meth:`_run_chunk` without a scan. Acceptance is resolved
        in-graph: position ``i`` emits the token the TARGET samples
        there (greedy argmax, or a categorical draw from the row's
        frozen PRNG stream — the SAME rule sequential decode applies),
        and emission continues only while the draft agreed at every
        earlier position, so the emitted stream is token-identical to
        non-speculative decode at ANY acceptance rate; drafts merely
        decide how many positions one launch may emit. Per-row rollback
        is the KV write cursor: all K+1 k/v blocks are written, but
        ``positions`` advances only by the emitted count and the row's
        PRNG stream consumes exactly that many draws — slots beyond the
        cursor are dead weight the attention mask never reads, and the
        next window overwrites them. State DONATED. Returns
        ``(state', tokens [K+1, B], emitted [K+1, B],
        accepted [B])`` — ``accepted`` counts the drafted tokens that
        survived (emitted minus the always-emitted first position)."""
        tag = self._ktag()
        key = ("spec_verify", s, k, tag)
        if key not in self._fns:
            w = k + 1

            def fn(params, state, drafts):
                active = state["active"]
                p0 = state["positions"]
                window = jnp.concatenate(
                    [state["tokens"][:, None],
                     jnp.transpose(drafts)], axis=1)  # [B, K+1]
                logits, caches = self._run_chunk(
                    params, window, p0, state["caches"])

                def split(carry, _):
                    ks = jax.vmap(jax.random.split)(carry)
                    return ks[:, 1], (ks[:, 0], ks[:, 1])

                rng0 = state["rng"].astype(jnp.uint32)
                _, (step_keys, chain) = jax.lax.scan(
                    split, rng0, None, length=w)
                tstar = jnp.stack([
                    _sample_tokens(logits[:, i], step_keys[i],
                                   state["temps"])
                    for i in range(w)])  # [K+1, B]
                match = jnp.cumprod(
                    (drafts == tstar[:k]).astype(jnp.int32), axis=0)
                a = match.sum(axis=0)  # accepted drafted prefix [B]
                emits = []
                emit = active
                for i in range(w):
                    if i > 0:
                        gen_prev = p0 + i + 1 - state["prompt_lens"]
                        emit = emit & (a >= i) \
                            & (tstar[i - 1] != state["eos"]) \
                            & (gen_prev < state["max_new"])
                    emits.append(emit)
                emitted = jnp.stack(emits)  # [K+1, B] bool
                e = emitted.astype(jnp.int32).sum(axis=0)
                positions_new = p0 + e
                last_i = jnp.maximum(e - 1, 0)
                last = jnp.take_along_axis(
                    tstar, last_i[None, :], axis=0)[0]
                tokens_new = jnp.where(e > 0, last, state["tokens"])
                rng_sel = jnp.take_along_axis(
                    chain, jnp.broadcast_to(
                        last_i[None, :, None], (1,) + chain.shape[1:]),
                    axis=0)[0]
                rng_new = jnp.where((e > 0)[:, None], rng_sel,
                                    state["rng"])
                gen_now = positions_new - state["prompt_lens"] + 1
                active_new = (e > 0) & (tokens_new != state["eos"]) \
                    & (gen_now < state["max_new"])
                accepted = jnp.maximum(e - 1, 0)
                st = dict(state, caches=caches, tokens=tokens_new,
                          positions=positions_new, active=active_new,
                          rng=rng_new)
                return st, tstar, emitted, accepted

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(1,)), self._graph_key(),
                f"spec_verify:s{s}:k{k}{tag}")
        return self._fns[key]

    def spec_sync_fn(self, s: int):
        """Roll the DRAFT state's cursor back onto the target's after a
        verify window: the draft speculated K steps ahead on its own
        chain, but its k/v for the accepted slots are already correct
        (accepted means the drafted token WAS the emitted token), so
        reconciliation is pure bookkeeping — set tokens/positions/active
        to the target's and let the mask strand the rejected tail. State
        DONATED; caches pass through aliased."""
        tag = self._ktag()
        key = ("spec_sync", s, tag)
        if key not in self._fns:
            def fn(state, tokens, positions, active):
                return dict(state, tokens=tokens, positions=positions,
                            active=active)

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(0,)), self._graph_key(),
                f"spec_sync:s{s}{tag}")
        return self._fns[key]

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
        tag = self._ktag()
        key = ("prefix_attach", s, tpre, bp, tag)
        if key not in self._fns:
            def fn(state, prefix_kv, rows, prefix_lens):
                caches = {}
                for name, c in state["caches"].items():
                    caches[name] = {
                        "k": c["k"].at[rows, :tpre].set(
                            prefix_kv[name]["k"], mode="drop"),
                        "v": c["v"].at[rows, :tpre].set(
                            prefix_kv[name]["v"], mode="drop"),
                    }
                return dict(
                    state, caches=caches,
                    positions=state["positions"].at[rows].set(
                        prefix_lens, mode="drop"))

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(0,)), self._graph_key(),
                f"prefix_attach:s{s}:t{tpre}:b{bp}{tag}")
        return self._fns[key]

    def suffix_prompt_fn(self, ts: int, tpre: int, bp: int):
        """Suffix-only prefill for a prefix-cache-hit join group: like
        :meth:`prompt_fn` but over ``[bp, ts]`` suffix tokens attending
        the shared prefix pages (see :meth:`_run_suffix`). NOT donated —
        the prefix pages are shared, refcounted buffers that other
        requests may attach concurrently."""
        tag = self._ktag()
        key = ("suffix_prompt", ts, tpre, bp, tag)
        if key not in self._fns:
            def fn(params, suffix, suf_lens, prefix_kv, prefix_lens,
                   max_new, eos, temps, rng):
                logits, kv = self._run_suffix(
                    params, suffix, suf_lens, prefix_kv, prefix_lens)
                step_keys, rng_next = _advance_rng(rng)
                tok = _sample_tokens(logits, step_keys, temps)
                active = (tok != eos) & (max_new > 1)
                return kv, tok, active, rng_next

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn), self._graph_key(),
                f"gen_prompt_sfx:t{ts}:p{tpre}:b{bp}{tag}")
        return self._fns[key]

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
        tag = self._ktag()
        key = ("suffix_join", s, ts, bp, tag)
        if key not in self._fns:
            def fn(state, kv, rows, tok, prefix_lens, lengths, max_new,
                   eos, temps, rng, active):
                b = self.max_batch
                valid = rows < b
                rc = jnp.minimum(rows, b - 1)
                off = jnp.clip(prefix_lens, 0, s - ts)
                caches = {}
                for name, c in state["caches"].items():
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
                    caches[name] = {"k": ck, "v": cv}
                at = lambda a, v: a.at[rows].set(v, mode="drop")  # noqa: E731
                return dict(
                    state, caches=caches,
                    tokens=at(state["tokens"], tok),
                    positions=at(state["positions"], lengths),
                    prompt_lens=at(state["prompt_lens"],
                                   jnp.maximum(lengths, 1)),
                    max_new=at(state["max_new"], max_new),
                    eos=at(state["eos"], eos),
                    temps=at(state["temps"], temps),
                    rng=at(state["rng"], rng),
                    active=at(state["active"], active))

            self._fns[key] = aot_cache.wrap(
                jax.jit(fn, donate_argnums=(0,)), self._graph_key(),
                f"prefix_join:s{s}:t{ts}:b{bp}{tag}")
        return self._fns[key]

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

    def warm_all(self, fused_steps=(1,), spec_steps=(), spec_sync=False,
                 spec_draft=(), prefix=False) -> dict:
        """Compile every (bucket, K) combination WITHOUT dispatching
        (``AotStep.warm`` on ShapeDtypeStructs): all KV buckets × K for
        decode, prompt × join buckets for prefill, every (S, T<=S, B)
        join, every upward grow hop, the release fn. ``spec_steps``
        additionally warms the ``spec_verify:s:k`` verifier (+ the sync
        op) per KV bucket; ``spec_sync`` warms just the draft-side sync;
        ``prefix`` warms every feasible prefix-attach / suffix-prefill /
        suffix-join bucket combination (feasible = some real prefix and
        suffix lengths map to the pair without exceeding ``max_len``).
        After this, mixed prompt/output-length traffic — including mixed
        prefix hit/miss and speculative accept/reject — is
        zero-recompile by construction (pinned in tests and reported by
        ``bench_decode.py``)."""
        sds = jax.ShapeDtypeStruct
        params = jax.tree_util.tree_map(
            lambda x: sds(jnp.shape(x), x.dtype), self._net.params)

        def row(shape, dt):
            return sds(shape, dt)

        nb = self.max_batch
        before = aot_cache.stats()
        for s in self.kv_ladder:
            st = self._struct_of(s)
            for k in fused_steps:
                self.decode_fn(s, int(k)).warm(params, st)
            for k in spec_steps:
                # the K+1-wide verify window cannot fit a bucket
                # shorter than it; the engine grows the bucket past
                # max_pos + K + 1 before ever dispatching a spec
                # window, so the small-bucket shapes are unreachable
                if s < int(k) + 1:
                    continue
                self.spec_verify_fn(s, int(k)).warm(
                    params, st, row((int(k), nb), jnp.int32))
            for k in spec_draft:
                self.spec_draft_fn(s, int(k)).warm(
                    params, st, row((nb,), jnp.int32),
                    row((nb,), jnp.int32), row((nb,), jnp.bool_))
            if spec_sync:
                self.spec_sync_fn(s).warm(
                    st, row((nb,), jnp.int32), row((nb,), jnp.int32),
                    row((nb,), jnp.bool_))
            self.release_fn(s).warm(st, row((self.max_batch,), jnp.bool_))
            for s2 in self.kv_ladder:
                if s2 > s:
                    self.grow_fn(s, s2).warm(st)
        if prefix:
            # the suffix path always pads its join group to max_batch
            # (padding rows scatter out of bounds and drop) so the
            # prefix machinery compiles ONE join-width per shape — the
            # full join ladder here would multiply the warm set ~4x
            # for no measurable prefill win at these sizes
            bp = nb
            for tpre in self.prompt_ladder:
                m_min = self._ladder_floor(self.prompt_ladder, tpre)
                for s in self.kv_ladder:
                    if tpre <= s:
                        self.prefix_attach_fn(s, tpre, bp).warm(
                            self._struct_of(s),
                            self._kv_struct(bp, tpre),
                            row((bp,), jnp.int32), row((bp,), jnp.int32))
                for ts in self.prompt_ladder:
                    if m_min + self._ladder_floor(
                            self.prompt_ladder, ts) > self.max_len:
                        continue
                    self.suffix_prompt_fn(ts, tpre, bp).warm(
                        params, row((bp, ts), jnp.int32),
                        row((bp,), jnp.int32),
                        self._kv_struct(bp, tpre),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.float32),
                        row((bp, 2), jnp.uint32))
            for s in self.kv_ladder:
                for ts in self.prompt_ladder:
                    if ts > s:
                        continue
                    self.suffix_join_fn(s, ts, bp).warm(
                        self._struct_of(s), self._kv_struct(bp, ts),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.float32),
                        row((bp, 2), jnp.uint32), row((bp,), jnp.bool_))
        for tp in self.prompt_ladder:
            for bp in self.join_ladder:
                args = (params, row((bp, tp), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.float32),
                        row((bp, 2), jnp.uint32))
                self.prompt_fn(tp, bp).warm(*args)
                for s in self.kv_ladder:
                    if tp > s:
                        continue
                    self.join_fn(s, tp, bp).warm(
                        self._struct_of(s), self._kv_struct(bp, tp),
                        row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.int32), row((bp,), jnp.int32),
                        row((bp,), jnp.float32), row((bp, 2), jnp.uint32),
                        row((bp,), jnp.bool_))
        after = aot_cache.stats()
        return {
            "kv_buckets": list(self.kv_ladder),
            "prompt_buckets": list(self.prompt_ladder),
            "join_buckets": list(self.join_ladder),
            "fused_steps": [int(k) for k in fused_steps],
            "spec_steps": [int(k) for k in spec_steps],
            "spec_draft": [int(k) for k in spec_draft],
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
        return toks

    def generate(self, tokens, max_new: int, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 fused_steps: int = 1) -> List[int]:
        """Sequential single-request generation through the SAME compiled
        executables the continuous engine uses (one live row, the other
        ``max_batch - 1`` rows inactive). This is the unbatched
        reference: the engine's continuous schedule is pinned to produce
        token-identical greedy output, and ``bench_decode.py``'s
        sequential baseline is this loop."""
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
