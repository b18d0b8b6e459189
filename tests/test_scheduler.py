"""Scheduler-core invariants: Eq.1 scoring, LPT, greedy dead reckoning,
budget safety, numpy-vs-jax greedy parity (the fused program's scan),
Hungarian validity."""
import numpy as np
import pytest

from repro.core import PRESETS, hungarian, score_matrix, validate
from repro.core.assignment import greedy_assign, lpt_order
from repro.core.budget import admission_mask, max_tokens_clamp
from repro.core.weights import sweep


def _rand_problem(rng, R=12, I=7):
    q = rng.uniform(0, 1, (R, I))
    c = rng.uniform(1e-6, 1e-4, (R, I))
    ln = rng.uniform(20, 500, (R, I))
    tpot = rng.uniform(0.005, 0.05, I)
    d = rng.uniform(0, 3000, I)
    b = rng.integers(1, 12, I).astype(float)
    free = rng.integers(0, 6, I).astype(float)
    maxb = np.full(I, 32.0)
    return q, c, ln, tpot, d, b, free, maxb


def test_weights_simplex():
    for w in sweep(16):
        validate(w)
    with pytest.raises(AssertionError):
        validate((0.5, 0.5, 0.5))


def test_score_matrix_bounds(rng):
    q, c, ln, tpot, d, b, free, maxb = _rand_problem(rng)
    T = tpot[None] * (d / np.maximum(b, 1) + ln)
    s = score_matrix(q, c, T, PRESETS["uniform"])
    assert np.all(s <= 1.0 + 1e-9)
    # best-cost candidate gets the full cost credit
    wq, wl, wc = PRESETS["uniform"]
    am = c.argmin(1)
    assert np.all(s[np.arange(len(am)), am] > -np.inf)


def test_lpt_order():
    ln = np.array([5.0, 100.0, 50.0])
    assert list(lpt_order(ln)) == [1, 2, 0]
    assert list(lpt_order(ln, enable=False)) == [0, 1, 2]


def test_greedy_dead_reckoning_avoids_herding(rng):
    """Identical requests must spread across identical instances."""
    R, I = 8, 4
    q = np.ones((R, I)) * 0.5
    c = np.ones((R, I)) * 1e-5
    ln = np.full((R, I), 100.0)
    tpot = np.full(I, 0.01)
    d = np.zeros(I)
    b = np.ones(I)
    free = np.full(I, 8.0)
    maxb = np.full(I, 32.0)
    choice, _ = greedy_assign(np.arange(R), q, c, ln, tpot, d, b, free,
                              maxb, (0.0, 1.0, 0.0))
    counts = np.bincount(choice, minlength=I)
    assert counts.max() - counts.min() <= 1, counts


def test_greedy_respects_allowed(rng):
    q, c, ln, tpot, d, b, free, maxb = _rand_problem(rng)
    allowed = rng.uniform(size=q.shape) < 0.4
    allowed[:, 0] = True  # every request keeps one candidate
    order = lpt_order(ln.max(1))
    choice, _ = greedy_assign(order, q, c, ln, tpot, d, b, free, maxb,
                              PRESETS["uniform"], allowed)
    assert all(allowed[r, choice[r]] for r in range(len(choice)))


def test_greedy_numpy_vs_jax(rng):
    q, c, ln, tpot, d, b, free, maxb = _rand_problem(rng, R=10, I=5)
    order = lpt_order(ln.max(1))
    ch_np, _ = greedy_assign(order, q, c, ln, tpot, d, b, free, maxb,
                             PRESETS["uniform"])
    import jax

    from repro.core.decision_jax import _greedy_scan
    scan = jax.jit(_greedy_scan, static_argnames=("weights",
                                                  "latency_mode"))
    f32 = np.float32
    ch_jx, _, _ = scan(order, f32(q), f32(c), f32(ln), f32(tpot), f32(tpot),
                       f32(d), f32(b), f32(free), f32(maxb),
                       PRESETS["uniform"], np.ones(q.shape, bool), "full")
    np.testing.assert_array_equal(ch_np, np.asarray(ch_jx))


def test_budget_admission_and_clamp():
    budgets = np.array([1e-5, np.nan, 1e-9])
    len_in = np.array([100.0, 100.0, 100.0])
    pred = np.array([[100.0, 400.0], [100.0, 400.0], [100.0, 400.0]])
    p_in = np.array([0.06, 0.40])
    p_out = np.array([0.06, 0.40])
    allowed, c_hat = admission_mask(budgets, len_in, pred, p_in, p_out)
    assert allowed[0, 0] and not allowed[0, 1]    # 72b too pricey
    assert allowed[1].all()                        # no budget
    assert allowed[2].sum() == 1                   # impossible budget ->
    assert allowed[2, c_hat[2].argmin()]           # cheapest kept
    mt = max_tokens_clamp(1e-5, 100, 0.06, 0.06)
    # worst case: len_in cost + mt * out price <= budget
    assert 100 * 0.06 / 1e6 + mt * 0.06 / 1e6 <= 1e-5 + 0.06 / 1e6


@pytest.mark.parametrize("threshold", [0.0, 0.35, 0.5, 1.0])
def test_bestroute_vectorized_matches_loop(threshold):
    """Regression pin: the one-argmax route() must reproduce the
    original per-request double loop over the price order."""
    from repro.core.routers import BestRouteRouter
    rng = np.random.default_rng(17)
    train = rng.normal(size=(300, 64)).astype(np.float32)
    Q = rng.uniform(size=(300, 4))
    L = rng.uniform(50, 500, (300, 4))
    prices = np.array([0.06, 0.07, 0.15, 0.40])
    br = BestRouteRouter(threshold=threshold).fit(train, Q, L, prices)
    emb = rng.normal(size=(80, 64)).astype(np.float32)
    got = br.route(emb)
    # reference: the pre-vectorization implementation
    q, _ = br._knn.query(emb)
    best = q.max(1, keepdims=True)
    spread = best - q.min(1, keepdims=True)
    ok = q >= best - (1.0 - br.t) * spread - 1e-12
    want = np.zeros(emb.shape[0], np.int64)
    for pos, r in enumerate(ok):
        for m in br.price_order:
            if r[m]:
                want[pos] = m
                break
    np.testing.assert_array_equal(got, want)


def test_hungarian_optimality_small():
    rng = np.random.default_rng(3)
    for _ in range(5):
        C = rng.uniform(0, 1, (4, 5))
        a = hungarian(C)
        best = None
        import itertools
        for p in itertools.permutations(range(5), 4):
            v = sum(C[i, p[i]] for i in range(4))
            best = v if best is None else min(best, v)
        got = sum(C[i, a[i]] for i in range(4))
        assert abs(got - best) < 1e-9
        assert len(set(a.tolist())) == 4   # injective
