"""Gradient-boosted regression trees — the XGBoost stand-in for the
per-(model, GPU)-tier TPOT heads (§4.2).

Training: numpy, histogram-based exact greedy on 256 bins, squared loss,
level-wise full binary trees. Inference: vectorized numpy (and a jnp
variant for in-graph use) walking the full tree arrays — one gather per
depth level, so a TPOT query stays O(depth) per row (the paper's ≈3 ms
booster contract is trivially met: ours measures in the tens of µs).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class _Tree:
    feature: np.ndarray      # (n_internal,) int32
    threshold: np.ndarray    # (n_internal,) float32
    leaf: np.ndarray         # (n_leaves,)  float32
    depth: int

    def leaves(self, X: np.ndarray) -> np.ndarray:
        """(n,) leaf index per row — the exact traversal result, used by
        the packed-parity tests."""
        idx = np.zeros(X.shape[0], np.int64)
        for _ in range(self.depth):
            f = self.feature[idx]
            t = self.threshold[idx]
            go_right = X[np.arange(X.shape[0]), f] > t
            idx = 2 * idx + 1 + go_right
        return idx - (2 ** self.depth - 1)

    def predict(self, X: np.ndarray) -> np.ndarray:
        return self.leaf[self.leaves(X)]


def _fit_tree(X, g, depth: int, n_bins: int, min_child: int,
              lam: float) -> _Tree:
    n, f = X.shape
    n_internal = 2 ** depth - 1
    n_leaves = 2 ** depth
    feature = np.zeros(n_internal, np.int32)
    threshold = np.full(n_internal, np.inf, np.float32)
    node = np.zeros(n, np.int64)           # current node per row

    # global quantile bins per feature
    qs = np.linspace(0, 100, n_bins + 1)[1:-1]
    bins = np.percentile(X, qs, axis=0)    # (n_bins-1, f)
    Xb = np.empty((n, f), np.int16)
    for j in range(f):
        Xb[:, j] = np.searchsorted(bins[:, j], X[:, j], side="right")

    for d in range(depth):
        level = range(2 ** d - 1, 2 ** (d + 1) - 1)
        for nd in level:
            rows = node == nd
            cnt = int(rows.sum())
            if cnt < 2 * min_child:
                feature[nd] = 0
                threshold[nd] = np.inf   # all go left
                continue
            gs = g[rows]
            xb = Xb[rows]
            best = (0.0, -1, -1)
            total = gs.sum()
            for j in range(f):
                sums = np.bincount(xb[:, j], weights=gs, minlength=n_bins)
                cnts = np.bincount(xb[:, j], minlength=n_bins)
                csum = np.cumsum(sums)[:-1]
                ccnt = np.cumsum(cnts)[:-1]
                ok = (ccnt >= min_child) & ((cnt - ccnt) >= min_child)
                if not ok.any():
                    continue
                gain = (csum ** 2 / (ccnt + lam)
                        + (total - csum) ** 2 / (cnt - ccnt + lam)
                        - total ** 2 / (cnt + lam))
                gain = np.where(ok, gain, -np.inf)
                b = int(np.argmax(gain))
                if gain[b] > best[0]:
                    best = (float(gain[b]), j, b)
            if best[1] >= 0:
                feature[nd] = best[1]
                threshold[nd] = (bins[best[2], best[1]]
                                 if best[2] < bins.shape[0]
                                 else np.inf)
        # route rows one level down
        f_nd = feature[node]
        t_nd = threshold[node]
        go_right = X[np.arange(n), f_nd] > t_nd
        node = 2 * node + 1 + go_right

    leaf_idx = node - n_internal
    leaf = np.zeros(n_leaves, np.float32)
    cnts = np.bincount(leaf_idx, minlength=n_leaves)
    sums = np.bincount(leaf_idx, weights=g, minlength=n_leaves)
    nzero = cnts > 0
    leaf[nzero] = (sums[nzero] / (cnts[nzero] + lam)).astype(np.float32)
    return _Tree(feature, threshold, leaf, depth)


class GradientBoostedRegressor:
    def __init__(self, n_trees: int = 80, depth: int = 4,
                 learning_rate: float = 0.15, n_bins: int = 64,
                 min_child: int = 8, lam: float = 1.0):
        self.n_trees = n_trees
        self.depth = depth
        self.lr = learning_rate
        self.n_bins = n_bins
        self.min_child = min_child
        self.lam = lam
        self.base = 0.0
        self.trees: List[_Tree] = []

    def fit(self, X: np.ndarray, y: np.ndarray,
            sample_weight: Optional[np.ndarray] = None):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        self.base = float(y.mean())
        pred = np.full(y.shape, self.base, np.float32)
        self.trees = []
        for _ in range(self.n_trees):
            resid = y - pred
            tree = _fit_tree(X, resid, self.depth, self.n_bins,
                             self.min_child, self.lam)
            upd = tree.predict(X)
            pred += self.lr * upd
            self.trees.append(tree)
        return self

    def predict(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X, np.float32)
        out = np.full(X.shape[0], self.base, np.float32)
        for t in self.trees:
            out += self.lr * t.predict(X)
        return out

    def leaf_indices(self, X: np.ndarray) -> np.ndarray:
        """(T, n) leaf index per (tree, row) — numpy reference for the
        packed traversal."""
        X = np.asarray(X, np.float32)
        return np.stack([t.leaves(X) for t in self.trees])

    # -- packed arrays for in-graph (jnp) inference -------------------------
    def pack(self):
        feat = np.stack([t.feature for t in self.trees])
        thr = np.stack([t.threshold for t in self.trees])
        leaf = np.stack([t.leaf for t in self.trees])
        return {"feature": feat, "threshold": thr, "leaf": leaf,
                "base": self.base, "lr": self.lr, "depth": self.depth}


def _packed_leaves(feat, thr, X, depth):
    """Shared packed traversal: one gather per depth level over all trees
    at once. feat/thr: (..., T, n_internal); X: matching (..., n, f);
    returns leaf idx (..., T, n)."""
    import jax.numpy as jnp
    idx = jnp.zeros(feat.shape[:-1] + (X.shape[-2],), jnp.int32)
    for _ in range(depth):
        f = jnp.take_along_axis(feat, idx, axis=-1)     # (..., T, n)
        t = jnp.take_along_axis(thr, idx, axis=-1)      # (..., T, n)
        # gather each row's split feature value: X[..., row, f]
        xv = jnp.take_along_axis(
            jnp.swapaxes(X, -1, -2)[..., None, :, :],   # (..., 1, f, n)
            f[..., None, :], axis=-2)[..., 0, :]        # (..., T, n)
        idx = 2 * idx + 1 + (xv > t).astype(jnp.int32)
    return idx - (2 ** depth - 1)


def _accumulate(base, lr, vals, xp):
    """base + sum_j lr * vals[..., j, :] accumulated tree-by-tree in
    float32 — the same rounding order as the numpy ensemble loop in
    `GradientBoostedRegressor.predict`, so packed inference is exactly
    (bitwise) the numpy prediction. The ONE definition of that rounding
    order: both packed entry points route through here. base may be a
    scalar or an array broadcastable to the output."""
    out = (xp.zeros(vals.shape[:-2] + vals.shape[-1:], np.float32)
           + xp.asarray(base, np.float32))
    for j in range(vals.shape[-2]):
        out = out + lr * vals[..., j, :]
    return out


def predict_packed(packed, X, return_leaves: bool = False):
    """jnp inference over packed trees, vectorized across trees.

    X: (n, f) -> (n,). One gather per depth level over all T trees at
    once; the per-tree accumulation mirrors the numpy loop bitwise.
    """
    import jax.numpy as jnp
    feat, thr, leaf = (jnp.asarray(packed["feature"]),
                       jnp.asarray(packed["threshold"]),
                       jnp.asarray(packed["leaf"]))
    X = jnp.asarray(X, jnp.float32)
    leaf_idx = _packed_leaves(feat, thr, X, packed["depth"])     # (T, n)
    vals = jnp.take_along_axis(leaf, leaf_idx, axis=1)           # (T, n)
    out = _accumulate(packed["base"], packed["lr"], vals, jnp)
    if return_leaves:
        return out, leaf_idx
    return out


def pack_ensemble(models: List["GradientBoostedRegressor"]):
    """Stack several same-shape boosters into one packed dict with a
    leading member axis — e.g. the per-tier TPOT heads fused into one
    device-resident gather for the single-dispatch hot path."""
    packs = [m.pack() for m in models]
    assert len({p["depth"] for p in packs}) == 1, "depth mismatch"
    assert len({p["lr"] for p in packs}) == 1, "learning-rate mismatch"
    assert len({p["feature"].shape for p in packs}) == 1, "tree-count mismatch"
    return {"feature": np.stack([p["feature"] for p in packs]),
            "threshold": np.stack([p["threshold"] for p in packs]),
            "leaf": np.stack([p["leaf"] for p in packs]),
            "base": np.array([p["base"] for p in packs], np.float32),
            "lr": packs[0]["lr"], "depth": packs[0]["depth"]}


def roster_tables(stacked, member):
    """Per-instance tree tables for `predict_roster`: each instance's
    member booster (`pack_ensemble` stack, ``member`` (I,) int) laid
    out node-major and instance-minor — feature/threshold
    (n_internal, T, I), leaf (n_leaves, T, I) — plus the (1, I) base.
    Built once per dispatch, outside any kernel."""
    import jax.numpy as jnp

    def plane(a):                             # (G, T, n) -> (n, T, I)
        return jnp.transpose(jnp.asarray(a)[member], (2, 1, 0))
    return {"feature": plane(stacked["feature"]),
            "threshold": plane(stacked["threshold"]),
            "leaf": plane(stacked["leaf"]),
            "base": jnp.asarray(stacked["base"], jnp.float32)[member][None]}


def predict_roster(tables, feats):
    """Per-instance member selection over a `pack_ensemble` stack: every
    instance walks its own member's trees — all per-tier TPOT heads in
    one pass, for the fused program and the megakernel alike. The walk
    is compare-select over the (T, I) planes of `roster_tables`
    (leading-axis indexing only, so it lowers inside a Mosaic kernel),
    then the shared `_accumulate` order, so each instance's value is
    bitwise its member's numpy prediction. ``tables`` may hold arrays
    or kernel refs, plus ``lr`` and ``depth``; ``feats`` is the list of
    (1, I) feature rows. Returns (1, I)."""
    import jax.numpy as jnp
    depth = tables["depth"]
    feat, thr, leaf = (tables["feature"], tables["threshold"],
                       tables["leaf"])
    shape = feat.shape[1:]                                  # (T, I)
    idx = jnp.zeros(shape, jnp.int32)
    for level in range(depth):
        f = jnp.zeros(shape, jnp.int32)
        t = jnp.zeros(shape, jnp.float32)
        for n in range(2 ** level - 1, 2 ** (level + 1) - 1):
            at = idx == n
            f = jnp.where(at, feat[n], f)
            t = jnp.where(at, thr[n], t)
        xv = jnp.zeros(shape, jnp.float32)
        for c, row in enumerate(feats):
            xv = jnp.where(f == c, row, xv)
        idx = 2 * idx + 1 + (xv > t).astype(jnp.int32)
    idx = idx - (2 ** depth - 1)
    vals = jnp.zeros(shape, jnp.float32)
    for n in range(2 ** depth):
        vals = jnp.where(idx == n, leaf[n], vals)
    return _accumulate(tables["base"], tables["lr"], vals[None], jnp)
