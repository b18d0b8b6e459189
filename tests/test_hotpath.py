"""Differential harness for the single-dispatch fused hot path.

Decision-level: the fused device program (`repro.core.hotpath`) must
make exactly the staged numpy and staged jax backends' assignments at
fixed seeds across all four ``latency_mode`` arms x budget filter on/off
x LPT on/off. Estimator-level: packed GBM inference is bitwise the numpy
tree-ensemble prediction. Serving-level: the `ClusterSim` array-telemetry
view equals the dict snapshots, and a full cluster run under the fused
backend reproduces the staged trajectories request-for-request.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import PRESETS, RBConfig, RouteBalance, make_requests, \
    run_cell
from repro.core.decision_jax import bucket_pow2
from repro.serving.cluster import ClusterSim
from repro.serving.workload import poisson_arrivals

MODES = ("full", "off_reactive", "off_predictive", "static_prior")


def _loaded_sim(ctx, seed=9):
    """A sim whose telemetry arrays carry mid-run-looking load."""
    from repro.serving.scenarios import randomize_telemetry
    return randomize_telemetry(
        ClusterSim(ctx["tiers"], ctx["names"], seed=0), seed)


def _batch(ctx, R=24, seed=5, with_budgets=True):
    reqs = make_requests(ctx["ds"], "test", np.zeros(R))
    if with_budgets:
        rng = np.random.default_rng(seed)
        budgets = np.where(rng.uniform(size=R) < 0.5,
                           rng.uniform(1e-5, 3e-4, R), np.nan)
        for r, b in zip(reqs, budgets):
            r.budget = None if np.isnan(b) else float(b)
    return reqs


def _choices(ctx, backend, batch, **cfg_kw):
    rb = RouteBalance(RBConfig(decision_backend=backend, **cfg_kw),
                      ctx["bundle"], ctx["tiers"])
    rb.sim = _loaded_sim(ctx)
    instances, choice, l_chosen = rb._decide_core(batch)
    return [instances[int(i)].iid for i in choice], np.asarray(l_chosen)


@pytest.mark.parametrize("lpt", [True, False], ids=["lpt", "fifo"])
@pytest.mark.parametrize("budget_filter", [True, False],
                         ids=["budget", "nobudget"])
@pytest.mark.parametrize("mode", MODES)
def test_fused_exact_assignment_parity(small_ctx, mode, budget_filter,
                                       lpt):
    batch = _batch(small_ctx, with_budgets=budget_filter)
    kw = dict(latency_mode=mode, budget_filter=budget_filter, lpt=lpt)
    ids_np, l_np = _choices(small_ctx, "numpy", batch, **kw)
    ids_jx, l_jx = _choices(small_ctx, "jax", batch, **kw)
    ids_fu, l_fu = _choices(small_ctx, "fused", batch, **kw)
    assert ids_np == ids_jx == ids_fu
    np.testing.assert_allclose(l_fu, l_np, rtol=2e-4)
    np.testing.assert_array_equal(l_fu, l_jx)


def test_fused_batch_bucketing_parity(small_ctx):
    """R is bucketed to powers of two; pad rows must not leak into real
    assignments for any awkward batch size."""
    for R in (1, 3, 7, 13, 33):
        batch = _batch(small_ctx, R=R, seed=R)
        ids_np, _ = _choices(small_ctx, "numpy", batch)
        ids_fu, _ = _choices(small_ctx, "fused", batch)
        assert ids_np == ids_fu, f"R={R}"


def test_fused_carried_state_ignores_pad_rows(small_ctx):
    """R buckets to a power of two; the post-scan dead-reckoned device
    state must reflect only the real requests' dispatches, never the
    shape-padding rows'. The *carried* state (the telemetry mirror) must
    equal the host telemetry exactly — the delta path's reseed-per-batch
    contract."""
    R = 13                                    # buckets to 16 -> 3 pads
    batch = _batch(small_ctx, R=R, with_budgets=False)
    rb = RouteBalance(RBConfig(decision_backend="fused"),
                      small_ctx["bundle"], small_ctx["tiers"])
    rb.sim = _loaded_sim(small_ctx)
    tel = rb.sim.tel
    d0, free0 = tel.pending.sum(), tel.free.sum()
    _, choice, l_chosen = rb._decide_core(batch)
    d1, b1, f1 = (np.asarray(x, np.float64)
                  for x in rb._fused._post_state)
    # pending grew by exactly the real rows' predicted lengths
    np.testing.assert_allclose(d1.sum() - d0, l_chosen.sum(), rtol=1e-5)
    # at most R free slots were consumed
    assert free0 - f1.sum() <= R
    # the carried mirror is the (f32) telemetry, not the post-scan state
    I = len(rb.sim.instances)
    dm, bm, fm, cm = (np.asarray(x)[:I] for x in rb._fused._state)
    np.testing.assert_array_equal(dm, tel.pending.astype(np.float32))
    np.testing.assert_array_equal(fm, tel.free.astype(np.float32))


def test_fused_masks_dead_instances(small_ctx):
    """Failures flip the alive mask — the fused roster never assigns to
    a dead instance and stays in exact parity with the staged path."""
    batch = _batch(small_ctx, R=16)
    dead = None
    rbs = {}
    for be in ("numpy", "fused"):
        rb = RouteBalance(RBConfig(decision_backend=be),
                          small_ctx["bundle"], small_ctx["tiers"])
        rb.sim = _loaded_sim(small_ctx)
        if dead is None:
            dead = [i.iid for i in rb.sim.instances if "72b" in i.iid]
        for iid in dead:
            rb.sim.by_id[iid].fail()
        rbs[be] = rb
    out = {}
    for be, rb in rbs.items():
        instances, choice, _ = rb._decide_core(batch)
        out[be] = [instances[int(i)].iid for i in choice]
    assert out["numpy"] == out["fused"]
    assert not any(iid in dead for iid in out["fused"])


def test_fused_e2e_cluster_trajectory(small_ctx):
    """A full ClusterSim run lands on the identical request->instance
    trajectory (and therefore identical metrics) under all backends."""
    results = {}
    for be in ("numpy", "jax", "fused"):
        arr = poisson_arrivals(10.0, 60, seed=3)
        reqs = make_requests(small_ctx["ds"], "test", arr)
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   charge_compute=False),
                          small_ctx["bundle"], small_ctx["tiers"])
        m = run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs)
        results[be] = ([r.instance for r in reqs], m)
    assert results["numpy"][0] == results["fused"][0]
    assert results["jax"][0] == results["fused"][0]
    for k in ("quality", "mean_e2e", "cost_per_req"):
        assert results["fused"][1][k] == pytest.approx(
            results["numpy"][1][k], rel=1e-9)


# -- estimator-level ---------------------------------------------------------

def _toy_gbm(seed=0, n_trees=20, depth=3):
    from repro.estimators.gbm import GradientBoostedRegressor
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(300, 4)).astype(np.float32)
    y = (2 * X[:, 0] + np.sin(X[:, 1]) + 0.1 * rng.normal(size=300)
         ).astype(np.float32)
    return GradientBoostedRegressor(n_trees=n_trees, depth=depth).fit(X, y)


def test_predict_packed_bitwise_matches_numpy():
    from repro.estimators.gbm import predict_packed
    g = _toy_gbm()
    Xq = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32)
    out, leaves = predict_packed(g.pack(), Xq, return_leaves=True)
    np.testing.assert_array_equal(np.asarray(out), g.predict(Xq))
    np.testing.assert_array_equal(np.asarray(leaves), g.leaf_indices(Xq))


def test_pack_ensemble_gathered_matches_members():
    from repro.estimators.gbm import (pack_ensemble, predict_roster,
                                      roster_tables)
    models = [_toy_gbm(seed=s) for s in range(3)]
    stacked = pack_ensemble(models)
    rng = np.random.default_rng(2)
    Xq = rng.normal(size=(40, 4)).astype(np.float32)
    member = rng.integers(0, 3, 40)
    tables = dict(roster_tables(stacked, member), lr=stacked["lr"],
                  depth=stacked["depth"])
    got = np.asarray(predict_roster(tables, [Xq[None, :, f]
                                             for f in range(4)]))[0]
    ref = np.select([member == j for j in range(3)],
                    [m.predict(Xq) for m in models])
    np.testing.assert_array_equal(got, ref.astype(np.float32))


# -- serving-level -----------------------------------------------------------

def test_array_telemetry_matches_dict_snapshots(small_ctx):
    arr = poisson_arrivals(10.0, 50, seed=1)
    reqs = make_requests(small_ctx["ds"], "test", arr)
    rb = RouteBalance(RBConfig(charge_compute=False), small_ctx["bundle"],
                      small_ctx["tiers"])
    sim = ClusterSim(small_ctx["tiers"], small_ctx["names"], seed=0)
    snapshots = []

    def probe(t):
        for inst in sim.instances:
            s = inst.snapshot
            tel = sim.tel
            snapshots.append((
                s["pending_decode"] == tel.pending[inst.slot],
                s["batch_size"] == tel.batch[inst.slot],
                s["free_slots"] == tel.free[inst.slot],
                s["mean_ctx"] == tel.ctx[inst.slot],
                s["queue_depth"] == tel.queue[inst.slot]))
        if sim._events:
            sim.push(t + 0.25, probe)

    rb.expected = len(reqs)
    rb.attach(sim)
    for r in reqs:
        sim.push(r.arrival, lambda t, rr=r: rb.enqueue(rr, t))
    sim.push(0.1, probe)
    sim.run()
    assert snapshots and all(all(row) for row in snapshots)
    assert sim.tel.version > 0
    assert sim.tel.alive.all()


def test_telemetry_kill_marks_dead(small_ctx):
    sim = ClusterSim(small_ctx["tiers"], small_ctx["names"], seed=0)
    v0 = sim.tel.version
    sim.instances[0].fail()
    assert not sim.tel.alive[0] and sim.tel.alive[1:].all()
    assert sim.tel.version == v0 + 1


# -- plumbing ----------------------------------------------------------------

def test_fused_runner_cached_across_sims(small_ctx):
    """Repeated cells over the same bundle/roster/config reuse one
    compiled program (no per-sim recompile); carried state resets."""
    out = []
    for _ in range(2):
        arr = poisson_arrivals(10.0, 30, seed=4)
        reqs = make_requests(small_ctx["ds"], "test", arr)
        rb = RouteBalance(RBConfig(decision_backend="fused",
                                   charge_compute=False),
                          small_ctx["bundle"], small_ctx["tiers"])
        run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs)
        out.append((rb._fused, [r.instance for r in reqs]))
    assert out[0][0] is out[1][0]          # same compiled runner
    assert out[0][1] == out[1][1]          # identical trajectory


def test_fused_raises_on_dead_roster(small_ctx):
    rb = RouteBalance(RBConfig(decision_backend="fused"),
                      small_ctx["bundle"], small_ctx["tiers"])
    rb.sim = ClusterSim(small_ctx["tiers"], small_ctx["names"], seed=0)
    for inst in rb.sim.instances:
        inst.fail()
    with pytest.raises(RuntimeError, match="no alive instances"):
        rb._decide_core(_batch(small_ctx, R=4))


def test_default_backend_is_fused():
    """The fused single-dispatch program is the production default; the
    staged paths stay selectable under the parity harness."""
    assert RBConfig().decision_backend == "fused"


def test_bucket_pow2():
    assert [bucket_pow2(n) for n in (0, 1, 7, 8, 9, 63, 64, 65)] == \
        [8, 8, 8, 8, 16, 64, 64, 128]


def test_pad_tokens_vectorized_matches_loop():
    from repro.estimators.embedding import pad_tokens
    rng = np.random.default_rng(0)
    lists = [rng.integers(0, 4000, rng.integers(0, 40)).tolist()
             for _ in range(17)]
    lists[3] = []                                  # empty prompt
    lists[5] = rng.integers(0, 4000, 64).tolist()  # overlong
    for max_len in (1, 8, 32):
        ref = np.zeros((len(lists), max_len), np.int32)
        for i, t in enumerate(lists):
            n = min(len(t), max_len)
            ref[i, :n] = t[:n]
        np.testing.assert_array_equal(pad_tokens(lists, max_len), ref)
    assert pad_tokens([], 16).shape == (0, 16)
    assert pad_tokens([[], []], 16).shape == (2, 16)
