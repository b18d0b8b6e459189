"""Plain reference of one RouteBalance decision window, in numpy.

Written from the paper's description of the joint decision (§4) and
independent of the code under test: it imports nothing of `repro`.
Its inputs are data — the KNN index rows and labels, the fitted TPOT
trees as lists of arrays, the roster's prices and slot counts, and the
window's prompt embeddings, budgets and telemetry — never a table that
the decision program packed for itself.

One window, in four stages (3b only where the prefix-affinity weight is
above 0):

1. KNN: squared L2 distance of each prompt embedding to every index
   row, the k nearest (ties by row), inverse-distance weights, and the
   weighted quality and output-length labels per model.
2. TPOT: each instance walks its tier's boosted trees on
   (batch, pending, context, batch x context) and sums the leaves.
3. Eq. 2 admission: estimated cost within budget; a request that fits
   nowhere keeps its cheapest live instance.
3b. Prefix affinity: each request's prefix signatures (a rolling hash
   per 16-token block) against each live instance's prefix sketch; the
   leading run of matched blocks, in tokens and capped at the prompt's
   length, as a share of that length (`hit_fraction`).
4. LPT greedy: requests in descending order of their longest
   predicted output; each takes the highest Eq. 1 score (quality, cost
   and latency, normalised per request over its admitted instances,
   snapped to a 2^-13 grid, ties to the lowest instance; the latency
   first scaled by 1 - w_aff x hit where affinity is on), and the
   picked instance's pending work, batch and free slots are dead-
   reckoned forward.

`decide` is teacher-forced: at every step it records its own pick but
advances the dead-reckoned state with the program's pick and the
program's predicted length there, so one differing pick, or one
neighbour near-tie resolved the other way, is counted once rather than
cascading through the rest of the window. Everything is float32, as in
the program.

`precision="high"` computes the distance cross term the way a
three-pass bfloat16 matrix unit does (each float32 operand split into
a high and a low bfloat16 part, the low-by-low product dropped). That
is the control: the reference one precision step below the float32
(`HIGHEST`) distance that the configuration states.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import ml_dtypes
import numpy as np

F32 = np.float32
SCORE_GRID = F32(2.0 ** 13)
BLOCK = 16                       # tokens per prefix signature
SIG_COLS = 8                     # signatures per prompt: 128 tokens
HASH_MULT = np.uint32(2654435761)


@dataclasses.dataclass
class Trees:
    """One tier's boosted TPOT regressor: T full binary trees of one
    depth, as the fit left them."""
    feature: np.ndarray      # (T, 2**depth - 1) int
    threshold: np.ndarray    # (T, 2**depth - 1) float32
    leaf: np.ndarray         # (T, 2**depth) float32
    depth: int
    base: float
    lr: float


@dataclasses.dataclass
class Fleet:
    """What a decision reads besides the window itself."""
    index: np.ndarray        # (N, D) float32 KNN rows
    quality: np.ndarray      # (N, M) float32 labels
    length: np.ndarray       # (N, M) float32 labels
    k: int
    eps: float
    model_of: np.ndarray     # (I,) model column per instance
    tier_of: np.ndarray      # (I,) index into `trees`
    max_batch: np.ndarray    # (I,) float32
    price_in: np.ndarray     # (I,) float32, USD per 1M tokens
    price_out: np.ndarray    # (I,) float32
    trees: List[Trees]
    weights: Sequence[float]  # (w_quality, w_latency, w_cost)
    w_aff: float = 0.0       # prefix-affinity weight, in [0, 1]


@dataclasses.dataclass
class Window:
    """One decision window's inputs, as the program was handed them."""
    emb: np.ndarray          # (R, D) float32
    budget: np.ndarray       # (R,) USD, nan = none
    len_in: np.ndarray       # (R,) prompt tokens
    pending: np.ndarray      # (I,) telemetry at the decision
    batch: np.ndarray
    free: np.ndarray
    ctx: np.ndarray
    alive: np.ndarray        # (I,) bool
    # read only where the fleet's w_aff is above 0
    tokens: Optional[np.ndarray] = None   # (R, L) prompt tokens, 0-padded
    tok_len: Optional[np.ndarray] = None  # (R,) tokens per prompt
    sketch: Optional[np.ndarray] = None   # (I, S) cached prefix signatures


@dataclasses.dataclass
class Decision:
    """The reference's answer for one window, teacher-forced on `picks`."""
    pick: np.ndarray         # (R,) the reference's own choice per step
    length_at: np.ndarray    # (R,) predicted length at the program's pick
    latency_at: np.ndarray   # (R,) predicted latency at the program's
    #                          pick, after the affinity discount
    pending: np.ndarray      # (I,) dead-reckoned state after the window
    batch: np.ndarray
    free: np.ndarray
    hit_at: np.ndarray       # (R,) prefix hit share at the program's pick


def _bf16_split(a: np.ndarray):
    hi = a.astype(ml_dtypes.bfloat16).astype(F32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(F32)
    return hi, lo


def cross_term(q: np.ndarray, x: np.ndarray, precision: str) -> np.ndarray:
    """(2q) . x^T in float32, or as a three-pass bfloat16 unit does."""
    q2 = (F32(2.0) * q).astype(F32)
    if precision == "highest":
        return q2 @ x.T
    if precision != "high":
        raise ValueError(precision)
    qh, ql = _bf16_split(q2)
    xh, xl = _bf16_split(x)
    return (qh @ xl.T + ql @ xh.T) + qh @ xh.T


def knn_labels(emb: np.ndarray, fleet: Fleet, precision: str = "highest",
               block: int = 256):
    """(quality (R, M), length (R, M)) for every row of `emb`."""
    x = fleet.index
    xsq = (x * x).sum(-1, dtype=F32)
    k = fleet.k
    R = emb.shape[0]
    M = fleet.quality.shape[1]
    qual = np.empty((R, M), F32)
    leng = np.empty((R, M), F32)
    for s in range(0, R, block):
        q = emb[s:s + block].astype(F32)
        d2 = (xsq[None, :] - cross_term(q, x, precision)
              + (q * q).sum(-1, keepdims=True, dtype=F32)).astype(F32)
        near = _k_nearest(d2, k)
        dk = np.take_along_axis(d2, near, axis=1)
        w = F32(1.0) / (np.sqrt(np.maximum(dk, F32(0.0))) + F32(fleet.eps))
        w = w / w.sum(-1, keepdims=True)
        qm = fleet.quality[near[:, 0]] * w[:, :1]
        lm = fleet.length[near[:, 0]] * w[:, :1]
        for j in range(1, k):
            qm = qm + fleet.quality[near[:, j]] * w[:, j:j + 1]
            lm = lm + fleet.length[near[:, j]] * w[:, j:j + 1]
        qual[s:s + block] = qm
        leng[s:s + block] = lm
    return qual, leng


def _k_nearest(d2: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k smallest distances per row, nearest first, equal
    distances in row order."""
    part = np.argpartition(d2, k - 1, axis=1)[:, :k]
    kth = np.take_along_axis(d2, part, axis=1).max(axis=1)
    out = np.empty_like(part)
    for r in range(d2.shape[0]):
        cand = np.flatnonzero(d2[r] <= kth[r])
        order = np.lexsort((cand, d2[r, cand]))
        out[r] = cand[order[:k]]
    return out


def tpot(fleet: Fleet, w: Window) -> np.ndarray:
    """(I,) predicted seconds per output token at the window's state."""
    b = np.maximum(w.batch.astype(F32), F32(1.0))
    c = np.maximum(w.ctx.astype(F32), F32(64.0))
    feats = np.stack([b, w.pending.astype(F32), c, b * c], axis=1)
    out = np.empty(len(b), F32)
    for g, t in enumerate(fleet.trees):
        rows = np.flatnonzero(fleet.tier_of == g)
        if not len(rows):
            continue
        x = feats[rows]                                   # (n, 4)
        node = np.zeros((len(rows), t.feature.shape[0]), np.int64)
        trees = np.arange(t.feature.shape[0])[None, :]
        for _ in range(t.depth):
            f = t.feature[trees, node]                    # (n, T)
            right = np.take_along_axis(x, f, axis=1) > t.threshold[trees,
                                                                   node]
            node = 2 * node + 1 + right
        vals = t.leaf[trees, node - (2 ** t.depth - 1)]   # (n, T)
        acc = np.full(len(rows), F32(t.base), F32)
        for j in range(vals.shape[1]):                    # tree by tree
            acc = acc + F32(t.lr) * vals[:, j]
        out[rows] = np.maximum(acc, F32(1e-4))
    return out


def admission(fleet: Fleet, w: Window, length: np.ndarray):
    """Eq. 2 over (R, I): (allowed, estimated cost)."""
    cost = ((w.len_in.astype(F32)[:, None] * fleet.price_in[None, :]
             + length * fleet.price_out[None, :]) * F32(1e-6)).astype(F32)
    budget = w.budget.astype(F32)[:, None]
    fits = (np.isnan(budget) | (cost <= budget)) & w.alive[None, :]
    allowed = fits.copy()
    live_cost = np.where(w.alive[None, :], cost, np.inf)
    for r in np.flatnonzero(~fits.any(axis=1)):
        allowed[r, int(np.argmin(live_cost[r]))] = True
    return allowed, cost


def _score(q, c, t, allowed, weights):
    wq, wl, wc = (F32(v) for v in weights)
    cmax = max(F32(c[allowed].max()), F32(1e-12))
    tmax = max(F32(t[allowed].max()), F32(1e-12))
    s = wq * q + wc * (F32(1.0) - c / cmax) + wl * (F32(1.0) - t / tmax)
    s = np.round(s * SCORE_GRID) / SCORE_GRID
    return np.where(allowed, s, -np.inf)


def signatures(tokens: np.ndarray, tok_len: np.ndarray) -> np.ndarray:
    """(R, SIG_COLS) int32 prefix signatures. Column d is the rolling
    hash h <- h * 2654435761 + token + 1 (uint32, wrapping, from h = 0)
    over the first min(len, 16 (d + 1)) tokens, read as int32 with 0
    taken to 1; or 0 where the prompt does not reach block d (len <=
    16 d)."""
    lens = np.minimum(np.asarray(tok_len, np.int64), BLOCK * SIG_COLS)
    R = len(lens)
    width = int(lens.max(initial=0))
    h = np.zeros(R, np.uint32)
    upto = np.zeros((R, max(width, 1)), np.uint32)   # hash of [0, t]
    for t in range(width):
        h = h * HASH_MULT + np.asarray(tokens[:, t]).astype(np.uint32) \
            + np.uint32(1)
        upto[:, t] = h
    starts = BLOCK * np.arange(SIG_COLS)
    last = np.minimum(lens[:, None], starts + BLOCK) - 1
    reach = lens[:, None] > starts
    sig = np.take_along_axis(upto, np.where(reach, last, 0), axis=1)
    sig = sig.view(np.int32)
    sig = np.where(sig == 0, 1, sig)
    return np.where(reach, sig, 0).astype(np.int32)


def hit_fraction(req_sig: np.ndarray, len_in: np.ndarray,
                 sketch: np.ndarray, alive: np.ndarray) -> np.ndarray:
    """(R, I) float32: for each request and instance, the leading run
    of the request's signature columns present in the instance's
    sketch row (a 0 signature never matches), in tokens (x 16), capped
    at max(len_in, 1) and divided by it; 0 on dead instances."""
    R, I = len(req_sig), len(sketch)
    run = np.zeros((R, I), np.int64)
    for i in range(I):
        present = np.isin(req_sig, sketch[i]) & (req_sig != 0)
        run[:, i] = np.cumprod(present, axis=1).sum(axis=1)
    lenf = np.maximum(np.asarray(len_in, F32), F32(1.0))[:, None]
    hit = np.minimum((run * BLOCK).astype(F32), lenf) / lenf
    return np.where(np.asarray(alive, bool)[None, :], hit, F32(0.0))


def decide(fleet: Fleet, w: Window, qual: np.ndarray, leng: np.ndarray,
           picks: Optional[np.ndarray] = None,
           lengths: Optional[np.ndarray] = None) -> Decision:
    """One window. `qual`/`leng` are the window's rows of `knn_labels`;
    `picks` (R,) are the program's choices and `lengths` (R,) its
    predicted lengths at them (None: follow our own)."""
    R = w.emb.shape[0]
    q_inst = qual[:, fleet.model_of]
    l_inst = leng[:, fleet.model_of]
    per_token = tpot(fleet, w)
    allowed, cost = admission(fleet, w, l_inst)
    order = np.argsort(-leng.max(axis=1), kind="stable")
    d = w.pending.astype(F32).copy()
    b = np.maximum(w.batch.astype(F32), F32(1.0))
    free = w.free.astype(F32).copy()
    b0 = b.copy()
    hit = np.zeros((R, len(d)), F32)
    if fleet.w_aff > 0.0:
        hit = hit_fraction(signatures(w.tokens, w.tok_len), w.len_in,
                           w.sketch, w.alive)
        keep = F32(1.0) - F32(fleet.w_aff) * hit          # (R, I)
    mine = np.zeros(R, np.int64)
    length_at = np.zeros(R, F32)
    latency_at = np.zeros(R, F32)
    for r in order:
        wait = np.where(free > 0, F32(0.0), d / np.maximum(b, F32(1.0)))
        lat = per_token * np.maximum(b / b0, F32(1.0)) * (wait + l_inst[r])
        if fleet.w_aff > 0.0:
            lat = lat * keep[r]
        s = _score(q_inst[r], cost[r], lat, allowed[r], fleet.weights)
        mine[r] = int(np.argmax(s))
        p = mine[r] if picks is None else int(picks[r])
        length_at[r] = l_inst[r, p]
        latency_at[r] = lat[p]
        added = l_inst[r, p] if lengths is None else F32(lengths[r])
        d[p] = d[p] + added
        if free[p] > 0:
            free[p] = free[p] - F32(1.0)
            b[p] = min(b[p] + F32(1.0), fleet.max_batch[p])
    return Decision(mine, length_at, latency_at, d, b, free,
                    hit[np.arange(R), picks if picks is not None else mine])
