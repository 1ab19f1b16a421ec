"""ParagraphVectors / Doc2Vec.

Reference: ``org.deeplearning4j.models.paragraphvectors.ParagraphVectors``
(PV-DBOW sequence learning: each labelled document gets a vector trained to
predict its words — the reference's default ``DBOW`` sequence algorithm over
the same SkipGram machinery). Inference of an unseen document
(``inferVector``) runs gradient steps on a fresh doc vector with the word
matrices frozen, exactly as the reference does.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.word2vec import Word2Vec, _sgns_step_counter


@functools.partial(jax.jit, static_argnums=(6,))
def _infer_step(doc_vec, w_out, words, table, rng, lr, negative):
    idx = jax.random.randint(rng, (words.shape[0], negative), 0,
                             table.shape[0])
    neg = table[idx]

    def loss_fn(dv):
        u_pos = w_out[words]
        pos = u_pos @ dv
        negs = jnp.einsum("bkd,d->bk", w_out[neg], dv)
        return -(jnp.sum(jax.nn.log_sigmoid(pos))
                 + jnp.sum(jax.nn.log_sigmoid(-negs)))

    loss, g = jax.value_and_grad(loss_fn)(doc_vec)
    return doc_vec - lr * g, loss


class ParagraphVectors(Word2Vec):
    """PV-DBOW over labelled documents. ``fit(docs, labels)`` — each doc is
    a string or token list; labels default ``DOC_i``."""

    def __init__(self, **kwargs):
        kwargs.setdefault("min_word_frequency", 1)
        super().__init__(**kwargs)
        if self.hs:
            raise ValueError(
                "ParagraphVectors trains PV-DBOW with negative sampling "
                "only (its doc-vector phase reuses the SGNS step against "
                "the [V, D] word-output matrix; the HS inner-node table "
                "has V-1 rows) — use negative >= 1")
        self.doc_vectors: Optional[np.ndarray] = None
        self.labels: List[str] = []
        self._label_index: Dict[str, int] = {}
        self._table: Optional[jnp.ndarray] = None

    def fit(self, documents: Iterable, labels: Optional[Sequence[str]] = None
            ) -> "ParagraphVectors":
        corpus = self._tokenized(documents)
        self.labels = (list(labels) if labels is not None
                       else [f"DOC_{i}" for i in range(len(corpus))])
        if len(self.labels) != len(corpus):
            raise ValueError("labels/documents length mismatch")
        self._label_index = {l: i for i, l in enumerate(self.labels)}

        # train word vectors first (gives word matrix + vocab + table)
        super().fit(corpus)
        V, D = len(self.vocab), self.layer_size
        rng = np.random.default_rng(self.seed + 1)
        key = jax.random.PRNGKey(self.seed + 1)

        counts = np.asarray(self.vocab.counts(), np.float64) ** 0.75
        probs = counts / counts.sum()
        self._table = jnp.asarray(
            rng.choice(V, size=max(V * 8, 1 << 16), p=probs), jnp.int32)

        # PV-DBOW: doc-id "centers" predicting their words. Reuse the SGNS
        # step with doc vectors as the input matrix (offset indices).
        encoded = self._encode(corpus)
        pairs = []
        for di, sent in enumerate(encoded):
            for w in sent:
                pairs.append((di, w))
        pairs = np.asarray(pairs, np.int32)
        doc_vecs = jnp.asarray(
            (rng.random((len(corpus), D)) - 0.5) / D, jnp.float32)
        w_out = jnp.asarray(self.syn1)

        step, total = 0, max(1, self.epochs
                             * (len(pairs) // self.batch_size + 1))
        for ep in range(self.epochs):
            rng.shuffle(pairs)
            for i in range(0, len(pairs), self.batch_size):
                chunk = pairs[i:i + self.batch_size]
                if len(chunk) < self.batch_size:
                    reps = self.batch_size - len(chunk)
                    chunk = np.concatenate(
                        [chunk, chunk[rng.integers(0, len(chunk), reps)]])
                lr = max(self.min_learning_rate,
                         self.learning_rate * (1.0 - step / total))
                # numpy args stage with the one dispatch; the rng folds
                # in-jit from the step counter (one more dispatch per
                # eager op otherwise — see nn/io.py)
                doc_vecs, w_out, _ = _sgns_step_counter(
                    doc_vecs, w_out, np.ascontiguousarray(chunk[:, 0]),
                    np.ascontiguousarray(chunk[:, 1]), self._table, key,
                    np.int32(step), np.float32(lr), self.negative)
                step += 1
        self.doc_vectors = np.asarray(doc_vecs)
        self.syn1 = np.asarray(w_out)
        return self

    # --- query --------------------------------------------------------------
    def get_paragraph_vector(self, label: str) -> np.ndarray:
        return self.doc_vectors[self._label_index[label]]

    def infer_vector(self, text, steps: int = 50,
                     learning_rate: float = 0.05) -> np.ndarray:
        """Reference ``inferVector``: optimize a fresh doc vector against
        the FROZEN word matrix."""
        tokens = (self.tokenizer.tokenize(text) if isinstance(text, str)
                  else list(text))
        words = np.asarray([self.vocab.index_of(t) for t in tokens
                            if t in self.vocab], np.int32)
        if words.size == 0:
            return np.zeros(self.layer_size, np.float32)
        rng = np.random.default_rng(0)
        dv = jnp.asarray((rng.random(self.layer_size) - 0.5)
                         / self.layer_size, jnp.float32)
        w_out = jnp.asarray(self.syn1)
        key = jax.random.PRNGKey(7)
        for t in range(steps):
            key, sub = jax.random.split(key)
            dv, _ = _infer_step(dv, w_out, jnp.asarray(words), self._table,
                                sub, jnp.asarray(learning_rate, jnp.float32),
                                self.negative)
        return np.asarray(dv)

    def similarity_to_label(self, text, label: str) -> float:
        v = self.infer_vector(text)
        d = self.get_paragraph_vector(label)
        denom = np.linalg.norm(v) * np.linalg.norm(d)
        return float(v @ d / denom) if denom > 0 else 0.0

    def nearest_labels(self, text, top_n: int = 5) -> List[str]:
        v = self.infer_vector(text)
        m = self.doc_vectors
        sims = (m @ v) / (np.linalg.norm(m, axis=1)
                          * max(np.linalg.norm(v), 1e-9) + 1e-9)
        return [self.labels[i] for i in np.argsort(-sims)[:top_n]]
