"""Equation 1: the fused quality-latency-cost score over request-instance
pairs, with per-request normalization of cost and latency by candidate
maxima (the batch supplies the reference set a point-at-a-time router
lacks; §4.1).

The math lives in one backend-agnostic function (`masked_score`) shared
by the numpy reference loop, the fused program's greedy scan
(`repro.core.decision_jax`, `repro.core.hotpath`) and the megakernel —
exact-parity differential tests depend on every backend evaluating the
identical expression in the identical operation order.

Scores are **epsilon-quantized** before they are returned: snapped to a
2^-13 grid (~1.2e-4 of the O(1) score scale). Two candidates whose
scores are equal in real arithmetic — same-tier replicas in identical
dead-reckoned state, the common case on a live cluster — used to come
back with a sub-1e-7 noise gap that the numpy loop's float64 resolved
and the device backends' float32 collapsed (or vice versa), flipping the
argmax between backends on unlucky worlds. Three coordinated choices
make the backends agree instead: the scheduler's numpy reference now
evaluates the decision arithmetic in float32 (`greedy_assign` follows
its input dtype, so the T/score chains are bitwise the device
backends'),
the cost scale is a reciprocal multiply rather than a division
(matching XLA's rewrite), and quantization absorbs the one residual
cross-backend difference — XLA's FMA contraction of the cost mul-add,
~1 ulp — by collapsing every sub-quantum gap to an exact tie in both
precisions (the pow2 scale makes the snap itself exact in either float
width). Ties break deterministically by candidate index in all
backends, so the three-way randomized soak holds on every seed with no
pinned exclusions (`tests/test_soak.py`). Gaps that matter — actual
quality/cost/latency differences, O(1e-3) and up — sit a thousand
quanta apart and are untouched.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

# pow2 quantum: s * 2^13 only shifts the exponent, so the snap is exact
# in both float32 and float64 and the two precisions land on the same
# grid point for any sub-quantum disagreement. 2^-13 ~ 1.2e-4 sits far
# below meaningful score signal (KNN quality noise is 0.14, TPOT heads
# carry ~3% error, weighted differences that matter are O(1e-2)) and
# ~1000x above float32 evaluation noise, so near-tie straddles of a
# grid boundary — the residual cross-precision flip mode — are rare
# enough that the randomized soak holds on every seed.
SCORE_QUANTUM = 2.0 ** -13
_INV_QUANTUM = 2.0 ** 13


def quantize_scores(s, xp=np):
    """Snap Eq. 1 scores to the shared epsilon grid (round half to even
    in both numpy and jax). -inf (masked candidates) passes through."""
    return xp.round(s * _INV_QUANTUM) * SCORE_QUANTUM


def masked_score(q, c, t, weights, mask, xp=np):
    """Eq. 1 over the trailing candidate axis, any leading batch shape.

    q/c/t/mask broadcastable arrays whose last axis enumerates the
    candidate instances; weights = (w_qual, w_lat, w_cost); xp is the
    array namespace (numpy or jax.numpy). Cost and latency are
    normalized per request by the max over *allowed* candidates;
    disallowed pairs come back -inf. Scores are epsilon-quantized (see
    module docstring) so float32 and float64 evaluations agree exactly.
    """
    wq, wl, wc = weights
    neg = -xp.inf
    cmax = xp.maximum(
        xp.max(xp.where(mask, c, neg), axis=-1, keepdims=True), 1e-12)
    tmax = xp.maximum(
        xp.max(xp.where(mask, t, neg), axis=-1, keepdims=True), 1e-12)
    s = wq * q + wc * (1.0 - c / cmax) + wl * (1.0 - t / tmax)
    return xp.where(mask, quantize_scores(s, xp), neg)


def affinity_discount(t, affinity, xp=np):
    """The prefix-affinity term (ROADMAP item 2): discount the
    predicted latency by the matched-prefix reuse score before Eq. 1
    normalizes it. `affinity` is `affinity_weight * hit_fraction`
    broadcastable to `t`'s shape (float32 in every backend); the
    multiplicative form keeps `affinity == 0` EXACTLY the legacy
    arithmetic (t * 1.0 is an IEEE identity), which is what lets the
    disabled path stay bitwise-identical across all three decision
    backends. Every backend must apply this before scoring AND use the
    discounted value for its est-latency/tie-break bookkeeping."""
    return t * (xp.float32(1.0) - affinity)


def score_matrix(q_hat: np.ndarray, c_hat: np.ndarray, t_hat: np.ndarray,
                 weights, allowed: Optional[np.ndarray] = None,
                 affinity: Optional[np.ndarray] = None) -> np.ndarray:
    """q_hat: (R, I) quality of instance's model per request in [0,1];
    c_hat, t_hat: (R, I) positive; weights = (w_qual, w_lat, w_cost);
    affinity: optional (R, I) prefix-reuse discount (weight x matched
    fraction) applied to t_hat. Returns (R, I) scores with disallowed
    pairs at -inf."""
    mask = np.ones(c_hat.shape, bool) if allowed is None else allowed
    if affinity is not None:
        t_hat = affinity_discount(t_hat, affinity, np)
    return masked_score(q_hat, c_hat, t_hat, weights, mask, np)


def score_row(q: np.ndarray, c: np.ndarray, t: np.ndarray, weights,
              allowed: Optional[np.ndarray] = None,
              affinity: Optional[np.ndarray] = None) -> np.ndarray:
    """Single-request variant used inside the greedy loop (t is
    state-dependent so it is recomputed per dispatch)."""
    mask = np.ones(c.shape, bool) if allowed is None else allowed
    if affinity is not None:
        t = affinity_discount(t, affinity, np)
    return masked_score(q, c, t, weights, mask, np)
