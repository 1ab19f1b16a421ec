"""Word2Vec.

Reference: ``org.deeplearning4j.models.word2vec.Word2Vec`` (Builder:
``layerSize/windowSize/minWordFrequency/negativeSample/iterations/
learningRate/sampling/seed``; elementsLearningAlgorithm SkipGram or CBOW;
objective hierarchical softmax — the reference default — or negative
sampling). All four combinations train here as ONE jitted batched step:

- input side: SkipGram feeds the center vector; CBOW the masked MEAN of
  the context window's vectors (gensim ``cbow_mean=1``).
- objective: negative sampling ``-log σ(v·u_t) - Σ_k log σ(-v·u_nk)``
  with in-step unigram^0.75 sampling, or hierarchical softmax over the
  word's Huffman path. Variable-length Huffman codes are padded to the
  tree's max depth with a mask — XLA keeps static shapes, masked path
  slots contribute zero loss/gradient, so the math is exactly the
  reference's per-node ``σ((1-2·code)·v·u_point)`` objective.

SkipGram pairs are generated by the native library (dynamic windows +
frequency subsampling, as word2vec.c does); CBOW windows are assembled on
the host per epoch.
"""

from __future__ import annotations

import functools
import heapq
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import VocabCache


@functools.partial(jax.jit, static_argnums=(10, 11), donate_argnums=(0, 1))
def _w2v_step_counter(w_in, w_out, src_ids, src_mask, targets, table,
                      codes, points, cmask, base_key, negative, hs,
                      stepc, lr):
    """Per-step rng derives IN-JIT from (base_key, step counter): an eager
    host-side jax.random.split would be one more dispatch per batch (see
    nn/io.py)."""
    rng = jax.random.fold_in(base_key, stepc)
    return _w2v_step(w_in, w_out, src_ids, src_mask, targets, table, codes,
                     points, cmask, rng, negative, hs, lr)


def _w2v_step(w_in, w_out, src_ids, src_mask, targets, table, codes,
              points, cmask, rng, negative, hs, lr):
    """One SGD step. ``src_ids [b, S]`` + ``src_mask [b, S]`` are the
    input-side words (S=1 for SkipGram; the context window for CBOW) and
    ``targets [b]`` the predicted words. ``hs`` selects the hierarchical-
    softmax objective over (codes, points, cmask); otherwise ``negative``
    samples come from the unigram^0.75 ``table``."""
    if not hs:
        idx = jax.random.randint(rng, (targets.shape[0], negative), 0,
                                 table.shape[0])
        neg = table[idx]

    def loss_fn(w_in, w_out):
        vs = w_in[src_ids]                               # [b, S, d]
        denom = jnp.maximum(jnp.sum(src_mask, -1, keepdims=True), 1.0)
        v = jnp.sum(vs * src_mask[..., None], 1) / denom  # [b, d]
        # SUM, not mean, across the batch: each example's embedding rows
        # get a full lr-scaled update, matching word2vec.c's per-pair SGD
        # semantics (mean would shrink per-row updates by the batch size)
        if hs:
            c = codes[targets]                           # [b, L]
            m = cmask[targets]
            u = w_out[points[targets]]                   # [b, L, d]
            z = jnp.einsum("bd,bld->bl", v, u)
            return -jnp.sum(m * jax.nn.log_sigmoid((1.0 - 2.0 * c) * z))
        u_pos = w_out[targets]                           # [b, d]
        u_neg = w_out[neg]                               # [b, k, d]
        pos = jnp.sum(v * u_pos, -1)
        negs = jnp.einsum("bd,bkd->bk", v, u_neg)
        return -(jnp.sum(jax.nn.log_sigmoid(pos))
                 + jnp.sum(jax.nn.log_sigmoid(-negs)))

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(w_in, w_out)
    w_in = w_in - lr * grads[0]
    w_out = w_out - lr * grads[1]
    return w_in, w_out, loss


@functools.partial(jax.jit, static_argnums=(8,), donate_argnums=(0, 1))
def _sgns_step_counter(w_in, w_out, centers, contexts, table, base_key,
                       stepc, lr, negative):
    """SkipGram/negative-sampling special case of :func:`_w2v_step` with
    the original (centers, contexts) signature — ParagraphVectors drives
    its doc-vector training through this entry point."""
    rng = jax.random.fold_in(base_key, stepc)
    src = centers[:, None]
    return _w2v_step(w_in, w_out, src, jnp.ones(src.shape, w_in.dtype),
                     contexts, table, None, None, None, rng, negative,
                     False, lr)


def build_huffman(counts: Sequence[float]) -> Tuple[np.ndarray, np.ndarray,
                                                    np.ndarray]:
    """Huffman tree over word frequencies (reference
    ``org.deeplearning4j.models.word2vec.Huffman``): returns per-word
    ``codes [V, L]`` (0/1 branch decisions), ``points [V, L]`` (inner-node
    row indices into the [V-1, D] output table), and ``mask [V, L]``
    (1 where the path is real, 0 padding). Frequent words get short codes.
    Node ids: leaves 0..V-1, inner nodes V..2V-2 (row = id - V)."""
    V = len(counts)
    if V < 2:
        z = np.zeros((V, 1))
        return z.astype(np.float32), z.astype(np.int32), z.astype(np.float32)
    heap = [(float(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    children = {}
    nxt = V
    while len(heap) > 1:
        c1, n1 = heapq.heappop(heap)
        c2, n2 = heapq.heappop(heap)
        children[nxt] = (n1, n2)
        heapq.heappush(heap, (c1 + c2, nxt))
        nxt += 1
    root = nxt - 1
    codes: List[List[int]] = [[] for _ in range(V)]
    points: List[List[int]] = [[] for _ in range(V)]
    stack = [(root, [], [])]
    while stack:
        node, code, path = stack.pop()
        if node < V:
            codes[node] = code
            points[node] = [p - V for p in path]
            continue
        left, right = children[node]
        stack.append((left, code + [0], path + [node]))
        stack.append((right, code + [1], path + [node]))
    L = max(len(c) for c in codes)
    C = np.zeros((V, L), np.float32)
    P = np.zeros((V, L), np.int32)
    M = np.zeros((V, L), np.float32)
    for i in range(V):
        n = len(codes[i])
        C[i, :n] = codes[i]
        P[i, :n] = points[i]
        M[i, :n] = 1.0
    return C, P, M


def _cbow_examples(sents: Sequence[np.ndarray], window: int,
                   rng: np.random.Generator):
    """CBOW training examples: (ctx [n, 2*window], ctx_mask, targets) with
    word2vec.c dynamic window shrinking. Host-side assembly (python loop —
    fine at the corpus sizes the tests/reference examples use)."""
    W = 2 * window
    ctxs, masks, tgts = [], [], []
    for sent in sents:
        n = len(sent)
        if n < 2:
            continue
        bs = rng.integers(1, window + 1, size=n)
        for i in range(n):
            lo, hi = max(0, i - int(bs[i])), min(n, i + int(bs[i]) + 1)
            ctx = np.concatenate([sent[lo:i], sent[i + 1:hi]])
            if ctx.size == 0:
                continue
            pad = W - ctx.size
            ctxs.append(np.pad(ctx, (0, pad)))
            masks.append(np.pad(np.ones(ctx.size, np.float32), (0, pad)))
            tgts.append(sent[i])
    if not ctxs:
        return (np.zeros((0, W), np.int32), np.zeros((0, W), np.float32),
                np.zeros((0,), np.int32))
    return (np.stack(ctxs).astype(np.int32), np.stack(masks),
            np.asarray(tgts, np.int32))


class Word2Vec:
    """Reference ``Word2Vec.Builder`` surface as keyword args; ``fit()``
    over an iterable of sentences (strings or token lists)."""

    def __init__(self, layer_size: int = 100, window_size: int = 5,
                 min_word_frequency: int = 5, negative: int = 5,
                 iterations: int = 1, epochs: int = 1,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 sampling: float = 0.0, batch_size: int = 512,
                 seed: int = 42,
                 tokenizer_factory: Optional[object] = None,
                 elements_learning_algorithm: str = "SkipGram",
                 use_hierarchic_softmax: Optional[bool] = None):
        """``use_hierarchic_softmax=None`` follows word2vec.c/reference
        semantics: HS when ``negative == 0``, negative sampling otherwise
        (the reference Builder's ``useHierarchicSoftmax`` default is true
        with negative=0)."""
        if elements_learning_algorithm not in ("SkipGram", "CBOW"):
            raise ValueError("elements_learning_algorithm must be SkipGram "
                             "or CBOW")
        self.layer_size = int(layer_size)
        self.window = int(window_size)
        self.min_word_frequency = int(min_word_frequency)
        self.negative = max(0, int(negative))
        self.hs = (bool(use_hierarchic_softmax)
                   if use_hierarchic_softmax is not None
                   else self.negative == 0)
        if not self.hs and self.negative == 0:
            raise ValueError("negative sampling needs negative >= 1 "
                             "(or enable use_hierarchic_softmax)")
        self.iterations = int(iterations)
        self.epochs = int(epochs)
        self.learning_rate = float(learning_rate)
        self.min_learning_rate = float(min_learning_rate)
        self.sampling = float(sampling)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.tokenizer = tokenizer_factory or DefaultTokenizerFactory()
        self.algorithm = elements_learning_algorithm
        self.vocab: Optional[VocabCache] = None
        self.syn0: Optional[np.ndarray] = None  # input vectors [V, D]
        # output vectors: [V, D] under negative sampling; the [V-1, D]
        # Huffman inner-node table under hierarchical softmax
        self.syn1: Optional[np.ndarray] = None

    # --- corpus handling ----------------------------------------------------
    def _tokenized(self, sentences) -> List[List[str]]:
        out = []
        for s in sentences:
            out.append(self.tokenizer.tokenize(s) if isinstance(s, str)
                       else list(s))
        return out

    def _encode(self, corpus: List[List[str]]) -> List[np.ndarray]:
        v = self.vocab
        return [np.asarray([v.index_of(t) for t in sent if t in v],
                           np.int32)
                for sent in corpus]

    def _subsampled(self, encoded: Sequence[np.ndarray],
                    rng: np.random.Generator) -> Sequence[np.ndarray]:
        """word2vec.c frequency subsampling (keep prob
        ``min(1, sqrt(t/f) + t/f)``); identity when sampling == 0."""
        if self.sampling <= 0:
            return encoded
        counts = np.asarray(self.vocab.counts(), np.float64)
        f = counts / counts.sum()
        keep_prob = np.minimum(
            1.0, np.sqrt(self.sampling / f) + self.sampling / f)
        return [sent[rng.random(len(sent)) < keep_prob[sent]]
                for sent in encoded if len(sent)]

    def _pairs(self, encoded: Sequence[np.ndarray],
               rng: np.random.Generator) -> np.ndarray:
        """All (center, context) pairs with word2vec.c dynamic windows and
        optional frequency subsampling. The pair walk runs in the native
        library (the role of the reference's nd4j SkipGram native op);
        subsampling filters host-side first."""
        from deeplearning4j_tpu import native

        return native.w2v_pairs(self._subsampled(encoded, rng), self.window,
                                seed=int(rng.integers(1, 2 ** 62)))

    # --- training -----------------------------------------------------------
    def _epoch_examples(self, encoded, rng):
        """-> (src_ids [n, S], src_mask [n, S], targets [n]) for one epoch
        under the configured elements algorithm."""
        if self.algorithm == "CBOW":
            ctx, mask, tgt = _cbow_examples(self._subsampled(encoded, rng),
                                            self.window, rng)
            order = rng.permutation(len(tgt))
            return ctx[order], mask[order], tgt[order]
        pairs = self._pairs(encoded, rng)
        rng.shuffle(pairs)
        src = np.ascontiguousarray(pairs[:, :1])
        return src, np.ones_like(src, np.float32), \
            np.ascontiguousarray(pairs[:, 1])

    def fit(self, sentences: Iterable) -> "Word2Vec":
        corpus = self._tokenized(sentences)
        self.vocab = VocabCache.build(iter(corpus), self.min_word_frequency)
        if len(self.vocab) < 2:
            raise ValueError("vocabulary has fewer than 2 words; lower "
                             "min_word_frequency or supply more text")
        V, D = len(self.vocab), self.layer_size
        rng = np.random.default_rng(self.seed)
        key = jax.random.PRNGKey(self.seed)
        w_in = jnp.asarray(
            (rng.random((V, D)) - 0.5) / D, jnp.float32)

        counts = np.asarray(self.vocab.counts(), np.float64)
        if self.hs:
            codes_np, points_np, cmask_np = build_huffman(counts)
            codes = jnp.asarray(codes_np)
            points = jnp.asarray(points_np)
            cmask = jnp.asarray(cmask_np)
            w_out = jnp.zeros((max(V - 1, 1), D), jnp.float32)
            table = jnp.zeros((1,), jnp.int32)  # unused under HS
        else:
            # unigram^0.75 negative table (word2vec.c construction)
            p75 = counts ** 0.75
            table = jnp.asarray(
                rng.choice(V, size=max(V * 8, 1 << 16), p=p75 / p75.sum()),
                jnp.int32)
            codes = points = cmask = jnp.zeros((V, 1), jnp.float32)
            points = jnp.zeros((V, 1), jnp.int32)
            w_out = jnp.zeros((V, D), jnp.float32)

        encoded = self._encode(corpus)
        total_steps = None
        step = 0
        for ep in range(self.epochs):
            src, mask, tgt = self._epoch_examples(encoded, rng)
            if total_steps is None:
                total_steps = max(
                    1, self.epochs * self.iterations
                    * (len(tgt) // self.batch_size + 1))
            for _ in range(self.iterations):
                for i in range(0, len(tgt), self.batch_size):
                    cs, cm, ct = (src[i:i + self.batch_size],
                                  mask[i:i + self.batch_size],
                                  tgt[i:i + self.batch_size])
                    if len(ct) == 0:
                        continue
                    if len(ct) < self.batch_size:  # static shapes: pad
                        reps = rng.integers(0, len(ct),
                                            self.batch_size - len(ct))
                        cs = np.concatenate([cs, cs[reps]])
                        cm = np.concatenate([cm, cm[reps]])
                        ct = np.concatenate([ct, ct[reps]])
                    frac = min(step / total_steps, 1.0)
                    lr = max(self.min_learning_rate,
                             self.learning_rate * (1.0 - frac))
                    # numpy args stage with the ONE dispatch; eager
                    # jnp.asarray/random.split would each round-trip
                    w_in, w_out, loss = _w2v_step_counter(
                        w_in, w_out, np.ascontiguousarray(cs),
                        np.ascontiguousarray(cm), np.ascontiguousarray(ct),
                        table, codes, points, cmask, key,
                        max(1, self.negative), self.hs,
                        np.int32(step), np.float32(lr))
                    step += 1
        self.syn0 = np.asarray(w_in)
        self.syn1 = np.asarray(w_out)
        return self

    # --- query API (reference WordVectors interface) ------------------------
    def has_word(self, word: str) -> bool:
        return self.vocab is not None and word in self.vocab

    def get_word_vector(self, word: str) -> np.ndarray:
        return self.syn0[self.vocab.index_of(word)]

    def get_word_vector_matrix(self) -> np.ndarray:
        return self.syn0

    def similarity(self, w1: str, w2: str) -> float:
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        denom = np.linalg.norm(a) * np.linalg.norm(b)
        return float(a @ b / denom) if denom > 0 else 0.0

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            vec = self.get_word_vector(word_or_vec)
            exclude = {word_or_vec}
        else:
            vec = np.asarray(word_or_vec)
            exclude = set()
        m = self.syn0
        sims = (m @ vec) / (np.linalg.norm(m, axis=1)
                            * max(np.linalg.norm(vec), 1e-9) + 1e-9)
        order = np.argsort(-sims)
        out = []
        for idx in order:
            w = self.vocab.word_at(int(idx))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out
