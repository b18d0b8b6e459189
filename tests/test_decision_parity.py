"""Differential harness for the scheduler hot path: the numpy greedy
loop vs the greedy scan the fused program traces
(`decision_jax._greedy_scan`) must make the *same decisions*.

Decision-level: exact assignment parity at fixed seeds across all four
``latency_mode`` isolation arms x budget filter on/off x LPT on/off
(the scan carries float32 state while numpy runs float64, so parity is
exact away from argmax ties; the pinned seeds keep it deterministic).
The scan is jitted here with Eq. 2 admission in-graph
(`budget.admission_math` over jnp), the LPT order from `lpt_order`, and
the batch padded to the fused program's pow2 bucket with a `row_valid`
mask. System-level: a full ``ClusterSim`` run under the numpy and fused
backends lands on the same trajectory and metrics, and the numpy
backend fed by the Pallas ``knn_topk`` estimator lands on the jnp
feed's metrics.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PRESETS, EstimatorBundle, RBConfig, RouteBalance, \
    make_requests, run_cell
from repro.core.assignment import greedy_assign, lpt_order
from repro.core.budget import admission_mask, admission_math, cost_matrix
from repro.core.decision_jax import _greedy_scan, bucket_pow2
from repro.serving.workload import poisson_arrivals

MODES = ("full", "off_reactive", "off_predictive", "static_prior")
WEIGHTS = (PRESETS["uniform"], (0.55, 0.25, 0.2), PRESETS["cost"])


def _problem(seed, R=32, I=13):
    rng = np.random.default_rng(seed)
    return dict(
        q=rng.uniform(0, 1, (R, I)),
        ln=rng.uniform(20, 500, (R, I)),
        plm=rng.uniform(20, 600, R),          # LPT key (max over models)
        tpot=rng.uniform(0.005, 0.05, I),
        nominal=rng.uniform(0.005, 0.05, I),
        d=rng.uniform(0, 3000, I),
        b=rng.integers(1, 12, I).astype(float),
        free=rng.integers(0, 6, I).astype(float),
        maxb=np.full(I, 16.0),
        price_in=rng.uniform(0.05, 0.5, I),
        price_out=rng.uniform(0.05, 0.5, I),
        budgets=np.where(rng.uniform(size=R) < 0.5,
                         rng.uniform(1e-5, 3e-4, R), np.nan),
        len_in=rng.uniform(10, 500, R),
    )


def _numpy_decision(p, w, mode, budget_filter, lpt):
    R, I = p["q"].shape
    if budget_filter:
        allowed, c_hat = admission_mask(p["budgets"], p["len_in"],
                                        p["ln"], p["price_in"],
                                        p["price_out"])
    else:
        allowed = np.ones((R, I), bool)
        c_hat = (p["len_in"][:, None] * p["price_in"][None, :]
                 + p["ln"] * p["price_out"][None, :]) / 1e6
    order = lpt_order(p["plm"], enable=lpt)
    return greedy_assign(order, p["q"], c_hat, p["ln"], p["tpot"],
                         p["d"], p["b"], p["free"], p["maxb"], w,
                         allowed, latency_mode=mode,
                         nominal_tpot=p["nominal"])


_scan = jax.jit(_greedy_scan, static_argnames=("weights", "latency_mode"))


@functools.partial(jax.jit, static_argnames=("weights", "latency_mode",
                                             "budget_filter"))
def _admit_and_scan(order, q, ln, tpot, nominal, d, b, free, maxb,
                    budgets, len_in, price_in, price_out, row_valid, *,
                    weights, latency_mode, budget_filter):
    if budget_filter:
        allowed, c_hat = admission_math(budgets, len_in, ln, price_in,
                                        price_out, jnp)
    else:
        c_hat = cost_matrix(len_in, ln, price_in, price_out, jnp)
        allowed = jnp.ones(c_hat.shape, bool)
    choice, est_T, _ = _greedy_scan(
        order, q, c_hat, ln, tpot, nominal, d, b, free, maxb, weights,
        allowed, latency_mode, row_valid=row_valid)
    return choice, est_T


def _scan_decision(p, w, mode="full", budget_filter=True, lpt=True):
    """The scan's decision for problem `p`, float32 like the fused
    program, R padded to its pow2 bucket: pad rows take an LPT key below
    every real one (FIFO puts them last anyway) and are row_valid False,
    so they scan last and move no state. Returns the real rows'
    (choice, est_T)."""
    R = len(p["plm"])
    pad = bucket_pow2(R) - R
    f32 = np.float32

    def rows(x, fill=0.0):
        x = np.asarray(x, f32)
        return np.concatenate([x, np.full((pad,) + x.shape[1:], fill, f32)])
    order = lpt_order(rows(p["plm"], -1e30), enable=lpt)
    choice, est_T = _admit_and_scan(
        order, rows(p["q"]), rows(p["ln"]), f32(p["tpot"]),
        f32(p["nominal"]), f32(p["d"]), f32(p["b"]), f32(p["free"]),
        f32(p["maxb"]), rows(p["budgets"], np.nan), rows(p["len_in"]),
        f32(p["price_in"]), f32(p["price_out"]),
        np.arange(R + pad) < R, weights=tuple(float(x) for x in w),
        latency_mode=mode, budget_filter=budget_filter)
    return np.asarray(choice)[:R], np.asarray(est_T)[:R]


@pytest.mark.parametrize("lpt", [True, False], ids=["lpt", "fifo"])
@pytest.mark.parametrize("budget_filter", [True, False],
                         ids=["budget", "nobudget"])
@pytest.mark.parametrize("mode", MODES)
def test_exact_assignment_parity(mode, budget_filter, lpt):
    for seed, w in enumerate(WEIGHTS):
        p = _problem(seed)
        ch_np, info = _numpy_decision(p, w, mode, budget_filter, lpt)
        ch_jx, est = _scan_decision(p, w, mode, budget_filter, lpt)
        np.testing.assert_array_equal(ch_np, ch_jx)
        np.testing.assert_allclose(est, info["est_latency"],
                                   rtol=2e-4, atol=1e-7)


def test_parity_with_batch_padding():
    """The fused program pads R to a power of two; pad rows must not
    leak into real assignments (they scan strictly after every real
    request and move no state)."""
    for R in (1, 5, 13, 33, 63):
        p = _problem(7 + R, R=R)
        w = PRESETS["uniform"]
        ch_np, _ = _numpy_decision(p, w, "full", True, True)
        ch_jx, _ = _scan_decision(p, w)
        np.testing.assert_array_equal(ch_np, ch_jx)


def test_greedy_core_respects_allowed():
    p = _problem(42)
    R, I = p["q"].shape
    rng = np.random.default_rng(5)
    allowed = rng.uniform(size=(R, I)) < 0.3
    allowed[:, 2] = True
    order = lpt_order(p["plm"])
    c_hat = (p["len_in"][:, None] * p["price_in"][None, :]
             + p["ln"] * p["price_out"][None, :]) / 1e6
    f32 = np.float32
    choice, _, _ = _scan(
        order, f32(p["q"]), f32(c_hat), f32(p["ln"]), f32(p["tpot"]),
        f32(p["nominal"]), f32(p["d"]), f32(p["b"]), f32(p["free"]),
        f32(p["maxb"]), PRESETS["uniform"], allowed, "full")
    choice = np.asarray(choice)
    assert all(allowed[r, choice[r]] for r in range(R))


def test_admission_math_numpy_vs_jax():
    import jax.numpy as jnp
    from repro.core.budget import admission_math
    rng = np.random.default_rng(11)
    R, I = 24, 13
    budgets = np.where(rng.uniform(size=R) < 0.6,
                       rng.uniform(1e-6, 1e-4, R), np.nan)
    len_in = rng.uniform(10, 500, R)
    pred = rng.uniform(10, 800, (R, I))
    p_in = rng.uniform(0.01, 0.5, I)
    p_out = rng.uniform(0.01, 0.5, I)
    a_np, c_np = admission_math(budgets, len_in, pred, p_in, p_out, np)
    a_jx, c_jx = admission_math(
        jnp.asarray(budgets, jnp.float32), jnp.asarray(len_in, jnp.float32),
        jnp.asarray(pred, jnp.float32), jnp.asarray(p_in, jnp.float32),
        jnp.asarray(p_out, jnp.float32), jnp)
    np.testing.assert_array_equal(a_np, np.asarray(a_jx))
    np.testing.assert_allclose(c_np, np.asarray(c_jx), rtol=1e-5)


# -- system level -----------------------------------------------------------

def _run(ctx, cfg, n=80, lam=10.0, seed=3):
    arr = poisson_arrivals(lam, n, seed=seed)
    reqs = make_requests(ctx["ds"], "test", arr)
    rb = RouteBalance(cfg, ctx["bundle"], ctx["tiers"])
    return run_cell(rb, ctx["tiers"], ctx["names"], reqs)


@pytest.mark.parametrize("mode", ["full", "off_reactive"])
def test_e2e_cluster_metrics_parity(small_ctx, mode):
    """The numpy and fused backends land on the same request->instance
    trajectory, and so the same metrics, in each mode."""
    base = dict(charge_compute=False, latency_mode=mode)
    out = {}
    for be in ("numpy", "fused"):
        arr = poisson_arrivals(10.0, 80, seed=3)
        reqs = make_requests(small_ctx["ds"], "test", arr)
        rb = RouteBalance(RBConfig(decision_backend=be, **base),
                          small_ctx["bundle"], small_ctx["tiers"])
        m = run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs)
        out[be] = ([r.instance for r in reqs], m)
    assert out["numpy"][0] == out["fused"][0]
    for k in ("quality", "mean_e2e", "cost_per_req"):
        assert out["fused"][1][k] == pytest.approx(out["numpy"][1][k],
                                                   rel=1e-9)


def test_e2e_pallas_knn_feed(small_ctx):
    """The numpy backend fed by the Pallas knn_topk estimator (a bundle
    built with ``KNNEstimator(backend="pallas")``) lands on the same
    metrics as the jnp top_k feed."""
    from repro.estimators.knn import KNNEstimator
    b = small_ctx["bundle"]
    knn = KNNEstimator(k=b.knn.k, backend="pallas", eps=b.knn.eps).fit(
        b.knn._x, b.knn._quality, b.knn._length)
    pallas = EstimatorBundle(b.encoder, knn, b.heads, b.model_names)
    cfg = RBConfig(decision_backend="numpy", charge_compute=False)
    m_jnp = _run(small_ctx, cfg, n=40)
    m_pal = _run(dict(small_ctx, bundle=pallas), cfg, n=40)
    assert abs(m_jnp["quality"] - m_pal["quality"]) < 0.01
    assert m_pal["mean_e2e"] == pytest.approx(m_jnp["mean_e2e"], rel=0.05)
