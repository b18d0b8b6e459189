"""Distance-weighted KNN quality + output-length estimator (FAISS stand-in).

One lookup over the training split returns, for every candidate model, a
predicted quality in [0,1] and an expected output length (§4.2). The
interface is metric-agnostic: labels are whatever per-(prompt, model)
scores the operator supplies.

Backends:
  * numpy  — exact brute force (default off the hot path)
  * jax    — jitted matmul + lax.top_k (the batched hot path)
  * pallas — fused distance+top-k kernel (repro.kernels.knn_topk), used
             when available; validated against the jnp oracle in tests.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import numpy as np


def distance_weights(d2, eps: float, xp=np):
    """Inverse-distance weights over the k neighbors, normalized to sum
    to 1 along the trailing axis. The one definition shared by the
    numpy / jax / pallas backends and the fused hot path
    (`repro.core.hotpath`)."""
    w = 1.0 / (xp.sqrt(xp.maximum(d2, 0.0)) + eps)
    return w / w.sum(-1, keepdims=True)


def label_mix(picks, w):
    """The distance-weighted label mix: sum_j picks[j] * w[:, j] with
    picks[j] the (B, M) labels of each row's j-th neighbour and w the
    (B, k) `distance_weights`. Accumulated neighbour by neighbour in
    float32 — one definition of the rounding order for the numpy, fused
    and megakernel paths."""
    out = picks[0] * w[:, 0:1]
    for j in range(1, len(picks)):
        out = out + picks[j] * w[:, j:j + 1]
    return out


def topk_soft_lookup(q, x, xsq, quality, length, k: int, eps: float):
    """The jnp KNN query body: squared distances via the
    ||q-x||² = ||q||² - 2 q·x + ||x||² expansion, `lax.top_k`, then the
    distance-weighted label mix. One definition traced by both the
    staged jax backend and the fused hot path (exact-parity tests
    compare their outputs bitwise). All args are jnp arrays; returns
    (quality (B, M), length (B, M)).

    The cross term runs at HIGHEST precision: the TPU's default f32 dot
    takes bf16 passes, and the neighbour set must agree with the
    float32 numpy reference."""
    import jax
    import jax.numpy as jnp
    d2 = (xsq[None, :]
          - jnp.matmul(2.0 * q, x.T, precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(q * q, -1, keepdims=True))
    neg, idx = jax.lax.top_k(-d2, k)
    w = distance_weights(-neg, eps, jnp)
    return (label_mix([quality[idx[:, j]] for j in range(k)], w),
            label_mix([length[idx[:, j]] for j in range(k)], w))


class KNNEstimator:
    def __init__(self, k: int = 10, backend: str = "jax",
                 eps: float = 1e-6):
        self.k = k
        self.backend = backend
        self.eps = eps
        self._x: Optional[np.ndarray] = None          # (N, E)
        self._quality: Optional[np.ndarray] = None    # (N, M)
        self._length: Optional[np.ndarray] = None     # (N, M)
        self._jq = None

    # -- index build ---------------------------------------------------------
    def fit(self, embeddings: np.ndarray, quality: np.ndarray,
            lengths: np.ndarray):
        self._x = np.ascontiguousarray(embeddings, np.float32)
        self._quality = np.asarray(quality, np.float32)
        self._length = np.asarray(lengths, np.float32)
        self._sq = (self._x ** 2).sum(-1)
        self._jq = None
        return self

    @property
    def n_models(self) -> int:
        return self._quality.shape[1]

    def with_backend(self, backend: str) -> "KNNEstimator":
        """Copy sharing the fitted index but querying via `backend`
        (the compiled-query cache is backend-specific, so it resets)."""
        import copy
        knn = copy.copy(self)
        knn.backend = backend
        knn._jq = None
        return knn

    # -- query ----------------------------------------------------------------
    def query(self, q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """q: (B, E) -> (quality (B, M), length (B, M))."""
        if self.backend == "jax":
            return self._query_jax(q)
        if self.backend == "pallas":
            return self._query_pallas(q)
        return self._query_np(q)

    def _query_np(self, q):
        q = np.asarray(q, np.float32)
        d2 = self._sq[None, :] - 2.0 * q @ self._x.T \
            + (q ** 2).sum(-1, keepdims=True)
        idx = np.argpartition(d2, self.k, axis=1)[:, :self.k]
        d2k = np.take_along_axis(d2, idx, axis=1)
        order = np.argsort(d2k, axis=1)
        idx = np.take_along_axis(idx, order, axis=1)
        d2k = np.take_along_axis(d2k, order, axis=1)
        w = distance_weights(d2k, self.eps)
        return (label_mix([self._quality[idx[:, j]]
                           for j in range(self.k)], w),
                label_mix([self._length[idx[:, j]]
                           for j in range(self.k)], w))

    def _build_jax(self):
        import jax
        import jax.numpy as jnp
        x = jnp.asarray(self._x)
        sq = jnp.asarray(self._sq)
        qual = jnp.asarray(self._quality)
        leng = jnp.asarray(self._length)
        k, eps = self.k, self.eps

        @jax.jit
        def run(q):
            return topk_soft_lookup(q, x, sq, qual, leng, k, eps)
        return run

    def _query_jax(self, q):
        import jax.numpy as jnp
        if self._jq is None:
            self._jq = self._build_jax()
        # pow2-pad the batch to the same buckets the fused hot path
        # compiles at: XLA picks its dot kernel by shape (B=1 lowers to
        # a gemv whose f32 accumulation order differs from the gemm a
        # padded batch gets), so querying at the raw B would leave
        # staged-vs-fused bitwise parity to rounding luck on exactly
        # the batches retries produce. Bucketing makes it structural —
        # and caps the jit cache at O(log B) entries instead of one
        # per distinct batch size.
        q = np.asarray(q, np.float32)
        B = q.shape[0]
        Bb = max(1 << (B - 1).bit_length(), 8) if B else 8
        if Bb != B:
            q = np.concatenate(
                [q, np.zeros((Bb - B, q.shape[1]), np.float32)])
        qa, la = self._jq(jnp.asarray(q))
        return np.asarray(qa)[:B], np.asarray(la)[:B]

    def _query_pallas(self, q):
        from repro.kernels import knn_ops
        if self._jq is None:
            self._jq = knn_ops.build_query(
                self._x, self._quality, self._length, self.k, self.eps)
        qa, la = self._jq(np.asarray(q, np.float32))
        return np.asarray(qa), np.asarray(la)

    # -- diagnostics ----------------------------------------------------------
    def best_model_accuracy(self, q_emb, true_quality) -> float:
        qual, _ = self.query(q_emb)
        return float((qual.argmax(1)
                      == np.asarray(true_quality).argmax(1)).mean())
