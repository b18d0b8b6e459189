"""Differential + structural harness for the Pallas decision
megakernel (`repro.kernels.decision_megakernel`).

Three layers, mirroring how the fused backend itself graduated:

  * kernel-level: `decision_call` against the pure-numpy full-pipeline
    oracle (`repro.kernels.ref.decision_ref`) on synthetic worlds —
    pad rows, dead instances, budgets, GBM on/off;
  * backend-level: ``decision_backend="megakernel"`` through
    `RouteBalance` must make bitwise the fused-XLA program's
    assignments (and l_chosen, and the post-scan dead-reckoned state)
    across the full mode grid, awkward batch sizes, dead rosters and
    the prefix-affinity arm;
  * plumbing-level: compile variants stay pinned at the pow2 buckets
    through roster churn, removed decision options are refused, and the
    kernel mode follows the platform.
"""
import numpy as np
import pytest

from repro.core import PRESETS, RBConfig, RouteBalance, make_requests, \
    run_cell
from repro.core.decision_jax import bucket_pow2
from repro.core.engine import BatchView
from repro.core.scheduler import RouteBalancePolicy
from repro.serving.cluster import ClusterSim

MODES = ("full", "off_reactive", "off_predictive", "static_prior")


def _loaded_sim(ctx, seed=9):
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
    return ([instances[int(i)].iid for i in choice],
            np.asarray(l_chosen), rb)


# -- backend-level: the 16-combo mode grid ------------------------------------

@pytest.mark.parametrize("lpt", [True, False], ids=["lpt", "fifo"])
@pytest.mark.parametrize("budget_filter", [True, False],
                         ids=["budget", "nobudget"])
@pytest.mark.parametrize("mode", MODES)
def test_megakernel_exact_assignment_parity(small_ctx, mode,
                                            budget_filter, lpt):
    """Every latency mode x budget filter x LPT combo: the megakernel
    makes bitwise the fused-XLA program's assignments AND l_chosen (both
    are float32 tracing the same shared stage math), and matches the
    float64 numpy reference loop's assignments exactly."""
    batch = _batch(small_ctx, with_budgets=budget_filter)
    kw = dict(latency_mode=mode, budget_filter=budget_filter, lpt=lpt)
    ids_np, _, _ = _choices(small_ctx, "numpy", batch, **kw)
    ids_fu, l_fu, _ = _choices(small_ctx, "fused", batch, **kw)
    ids_mk, l_mk, _ = _choices(small_ctx, "megakernel", batch, **kw)
    assert ids_mk == ids_fu == ids_np
    np.testing.assert_array_equal(l_mk, l_fu)


def test_megakernel_poststate_bitwise_matches_fused(small_ctx):
    """The in-kernel fori_loop's dead-reckoned carry (d1, b1, f1) must
    come back bitwise the fused lax.scan's — same greedy_step body,
    same float32 accumulation order — pow2 roster pads included."""
    batch = _batch(small_ctx, R=13)
    out = {}
    for be in ("fused", "megakernel"):
        _, _, rb = _choices(small_ctx, be, batch)
        out[be] = tuple(np.asarray(x) for x in rb._fused._post_state)
        # carried mirror too: both backends reseed from telemetry
        out[be + "_mirror"] = tuple(np.asarray(x)
                                    for x in rb._fused._state)
    for a, b in zip(out["fused"], out["megakernel"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(out["fused_mirror"], out["megakernel_mirror"]):
        np.testing.assert_array_equal(a, b)


def test_megakernel_batch_bucketing_parity(small_ctx):
    """Pad rows (R buckets to pow2) must not leak into real assignments
    for any awkward batch size."""
    for R in (1, 3, 7, 13, 33):
        batch = _batch(small_ctx, R=R, seed=R)
        ids_fu, l_fu, _ = _choices(small_ctx, "fused", batch)
        ids_mk, l_mk, _ = _choices(small_ctx, "megakernel", batch)
        assert ids_mk == ids_fu, f"R={R}"
        np.testing.assert_array_equal(l_mk, l_fu, err_msg=f"R={R}")


def test_megakernel_masks_dead_instances(small_ctx):
    batch = _batch(small_ctx, R=16)
    dead = None
    out = {}
    for be in ("fused", "megakernel"):
        rb = RouteBalance(RBConfig(decision_backend=be),
                          small_ctx["bundle"], small_ctx["tiers"])
        rb.sim = _loaded_sim(small_ctx)
        if dead is None:
            dead = [i.iid for i in rb.sim.instances if "72b" in i.iid]
        for iid in dead:
            rb.sim.by_id[iid].fail()
        instances, choice, _ = rb._decide_core(batch)
        out[be] = [instances[int(i)].iid for i in choice]
    assert out["megakernel"] == out["fused"]
    assert not any(iid in dead for iid in out["megakernel"])


def test_megakernel_affinity_parity(small_ctx):
    """Prefix-affinity live (w=0.35): warmed sketches, in-kernel
    hit_fraction must stay bitwise the fused program's."""
    from repro.serving.request import RequestColumns
    from repro.serving.scenarios import randomize_prefix_state
    batch = _batch(small_ctx, R=20, with_budgets=False)
    cols, _ = RequestColumns.for_batch(batch,
                                       small_ctx["bundle"].encoder)
    out = {}
    for be in ("fused", "megakernel"):
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   affinity_weight=0.35),
                          small_ctx["bundle"], small_ctx["tiers"])
        sim = _loaded_sim(small_ctx)
        randomize_prefix_state(sim, cols, 3)
        rb.sim = sim
        instances, choice, l_chosen = rb._decide_core(batch)
        out[be] = ([instances[int(i)].iid for i in choice],
                   np.asarray(l_chosen))
    assert out["megakernel"][0] == out["fused"][0]
    np.testing.assert_array_equal(out["megakernel"][1], out["fused"][1])


def test_megakernel_e2e_cluster_trajectory(small_ctx):
    """A full ClusterSim run lands on the identical request->instance
    trajectory under fused and megakernel."""
    from repro.serving.workload import poisson_arrivals
    results = {}
    for be in ("fused", "megakernel"):
        arr = poisson_arrivals(10.0, 40, seed=3)
        reqs = make_requests(small_ctx["ds"], "test", arr)
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   charge_compute=False),
                          small_ctx["bundle"], small_ctx["tiers"])
        m = run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs)
        results[be] = ([r.instance for r in reqs], m)
    assert results["megakernel"][0] == results["fused"][0]
    for k in ("quality", "mean_e2e", "cost_per_req"):
        assert results["megakernel"][1][k] == pytest.approx(
            results["fused"][1][k], rel=1e-12)


# -- plumbing: compile pinning + refused options ------------------------------

def _policy(ctx, sim, **cfg_kw):
    pol = RouteBalancePolicy(RBConfig(decision_backend="megakernel",
                                      **cfg_kw))
    pol.prepare(ctx["bundle"], ctx["tiers"])
    pol.on_attach(sim)
    return pol


@pytest.mark.parametrize("cfg_kw", [
    dict(decision_backend="jax"),
    dict(decision_backend="megakernel", window_coalesce=4),
    dict(decision_backend="fused", window_coalesce=4)],
    ids=["jax_backend", "coalesce_megakernel", "coalesce_fused"])
def test_removed_decision_options_refused(cfg_kw):
    """The staged jax backend and the multi-window dispatch are gone:
    a configuration that asks for either is refused, not served by
    something else."""
    with pytest.raises(ValueError):
        RouteBalancePolicy(RBConfig(**cfg_kw))


def test_megakernel_compile_variants_pinned(small_ctx):
    """Compile count stays one per pow2 R bucket through batch
    sizes, roster churn (fail/recover flips the alive mask, no
    recompile) and repeated dispatches. A non-default weights preset
    gives this test its own `for_bundle` cache slot — the session-scoped
    bundle shares compiled runners across tests, and jit caches survive
    `reset()` by design."""
    sim = _loaded_sim(small_ctx)
    pol = _policy(small_ctx, sim, weights=PRESETS["quality"])
    for R in (1, 3, 7, 13, 33, 13, 7):       # buckets: {8, 16, 64}
        pol.assign(BatchView(_batch(small_ctx, R=R, seed=R)),
                   sim).fetch()
    sim.instances[0].fail()                  # roster churn: alive mask
    pol.assign(BatchView(_batch(small_ctx, R=7)), sim).fetch()
    sim.instances[0].recover(t=1.0)
    pol.assign(BatchView(_batch(small_ctx, R=7)), sim).fetch()
    assert pol._fused.compile_count() == 3   # {8, 16, 64}


# -- kernel-level: decision_call vs the numpy oracle --------------------------

def _toy_world(seed=0, R=6, E=8, N=40, M=3, I=5, T=2, k=4):
    rng = np.random.default_rng(seed)
    f32 = np.float32
    emb = rng.normal(size=(R, E)).astype(f32)
    rv = np.ones(R, bool)
    rv[R - 1] = False                         # one pad row
    budgets = np.where(rng.uniform(size=R) < 0.5,
                       rng.uniform(1e-5, 3e-4, R), np.nan).astype(f32)
    len_in = rng.integers(8, 200, R).astype(f32)
    x = rng.normal(size=(N, E)).astype(f32)
    args = dict(
        emb=emb, row_valid=rv, budgets=budgets, len_in=len_in,
        psig=np.zeros((1, 1), np.int32),
        d=rng.uniform(0, 300, I).astype(f32),
        b=rng.integers(1, 6, I).astype(f32),
        free=rng.integers(0, 4, I).astype(f32),
        ctx=rng.uniform(64, 900, I).astype(f32),
        alive=np.array([True] * (I - 1) + [False]),
        x=x, xsq=(x * x).sum(1).astype(f32),
        qual=rng.uniform(0, 1, (N, M)).astype(f32),
        leng=rng.uniform(20, 400, (N, M)).astype(f32),
        m_of_i=rng.integers(0, M, I).astype(np.int32),
        tier_of_i=(np.arange(I) % T).astype(np.int32),
        maxb=np.full(I, 8.0, f32),
        price_in=rng.uniform(1e-7, 1e-6, I).astype(f32),
        price_out=rng.uniform(1e-6, 1e-5, I).astype(f32),
        nominal=rng.uniform(0.01, 0.06, I).astype(f32),
        sig_plane=np.zeros((1, 1), np.int32))
    statics = dict(k=k, eps=1e-3, weights=PRESETS["uniform"],
                   latency_mode="full", lpt=True, budget_filter=True,
                   w_aff=0.0)
    return args, statics


@pytest.mark.parametrize("use_gbm", [False, True], ids=["nominal", "gbm"])
def test_decision_call_matches_numpy_oracle(use_gbm):
    """The kernel pipeline (interpret mode) against the pure-numpy
    full-pipeline oracle: exact assignments, latencies and dead-reckoned
    state to float tolerance — pad rows, one dead
    instance, nan/finite budgets, GBM on and off."""
    from repro.kernels.ops import decision_megakernel as mk_op
    from repro.kernels.ref import decision_ref
    args, statics = _toy_world()
    if use_gbm:
        from repro.estimators.gbm import GradientBoostedRegressor, \
            pack_ensemble
        rng = np.random.default_rng(5)
        models = []
        for s in range(2):                    # T=2 tiers
            X = rng.uniform(0, 900, (200, 4)).astype(np.float32)
            y = (0.02 + 1e-5 * X[:, 1] + 1e-4 * X[:, 0]
                 ).astype(np.float32)
            models.append(GradientBoostedRegressor(
                n_trees=8, depth=2).fit(X, y))
        stacked = pack_ensemble(models)
        gbm_ref = stacked
        gfeat, gthr, gleaf, gbase = (stacked["feature"],
                                     stacked["threshold"],
                                     stacked["leaf"], stacked["base"])
        depth, lr = stacked["depth"], stacked["lr"]
    else:
        from repro.kernels.decision_megakernel import dummy_gbm
        gbm_ref = None
        gfeat, gthr, gleaf, gbase = dummy_gbm()
        depth, lr = 1, 0.1
    ref = decision_ref(*args.values(), gbm=gbm_ref, **statics)
    got = mk_op(*args.values(), gfeat, gthr, gleaf, gbase, **statics,
                use_gbm=use_gbm, depth=depth, lr=lr)
    np.testing.assert_array_equal(np.asarray(got[0]), ref[0])  # choice
    for g, r in zip(got[1:], ref[1:]):
        np.testing.assert_allclose(np.asarray(g), r, rtol=2e-5,
                                   atol=1e-7)
    # dead instance never chosen
    assert not np.any(np.asarray(got[0]) == len(args["d"]) - 1)


def test_decision_call_topk_modes_bitwise_equal():
    """The index streamed in tiles (``knn_tile=16``: three grid steps,
    the last one part padding — the compiled TPU form) and the whole
    index as one tile (the interpreter's default) must produce
    bitwise-identical decisions end to end — survivor set, order, and
    every downstream float32 sum."""
    from repro.kernels.ops import decision_megakernel as mk_op
    from repro.kernels.decision_megakernel import dummy_gbm
    args, statics = _toy_world(seed=3)
    gfeat, gthr, gleaf, gbase = dummy_gbm()
    out = {}
    for tile in (None, 16):
        out[tile] = mk_op(*args.values(), gfeat, gthr, gleaf, gbase,
                          **statics, use_gbm=False, depth=1, lr=0.1,
                          knn_tile=tile)
    for a, b in zip(out[None], out[16]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_topk_running_matches_lax_topk_order():
    """The kernel's streamed top-k (tile-by-tile merge, then survivor
    ordering) must reproduce lax.top_k's exact neighbor set and ORDER —
    stable by (distance, index) — and carry each survivor's labels: the
    label-mix sums are order-sensitive in float32. Exact ties sit both
    inside the top-k and on its boundary across tiles."""
    import sys

    import jax
    import jax.numpy as jnp
    import repro.kernels  # noqa: F401
    mk = sys.modules["repro.kernels.decision_megakernel"]
    rng = np.random.default_rng(0)
    R, N, k, tile = 32, 600, 10, 256
    d2 = rng.uniform(0, 10, (R, N)).astype(np.float32)
    d2[:, 100] = d2[:, 50]                   # ties anywhere
    d2[:, 401] = d2[:, 400]
    # boundary ties: tile 0 fills the buffer with two 0.5s as its worst,
    # tile 1's 0.3 must evict the higher index (30), tile 2's 0.5 must
    # not enter
    d2[:8] = np.maximum(d2[:8], 0.6)
    d2[:8, :8] = 0.1
    d2[:8, [20, 30, 520]] = 0.5
    d2[:8, 300] = 0.3
    labels = rng.uniform(0, 1, (2, N)).astype(np.float32)
    vals = jnp.full((R, k), mk.NEG, jnp.float32)
    idx = -1 - jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)
    labs = [jnp.zeros((R, k), jnp.float32)] * 2
    pad = np.full((R, (-N) % tile), mk.NEG, np.float32)
    dp = np.concatenate([d2, pad], 1)
    lp = np.concatenate([labels, np.zeros((2, pad.shape[1]), np.float32)],
                        1)
    for t in range(0, dp.shape[1], tile):
        vals, idx, labs = mk._merge_tile(
            vals, idx, labs, jnp.asarray(dp[:, t:t + tile]),
            [jnp.asarray(lp[c:c + 1, t:t + tile]) for c in range(2)], t, k)
    d2k, picks = mk._ordered_survivors(vals, idx, labs, k)
    neg, ridx = jax.lax.top_k(-jnp.asarray(d2), k)
    np.testing.assert_array_equal(np.asarray(d2k), np.asarray(-neg))
    ridx = np.asarray(ridx)
    for j in range(k):
        for c in range(2):
            np.testing.assert_array_equal(
                np.asarray(picks[j][c])[:, 0], labels[c][ridx[:, j]])


# -- execution mode -----------------------------------------------------------

def test_interpret_mode_follows_platform(monkeypatch, small_ctx):
    """Kernels compile with Mosaic on a TPU and interpret elsewhere; the
    mode is read from the platform when a runner is built."""
    import jax

    from repro.core.hotpath import FusedHotPath
    from repro.kernels.ops import interpret_mode
    assert interpret_mode() is True           # this CPU backend
    sim = _loaded_sim(small_ctx)
    cfg = RBConfig(decision_backend="megakernel")
    for platform, interpret in (("tpu", False), ("cpu", True),
                                ("gpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert interpret_mode() is interpret, platform
        runner = FusedHotPath(small_ctx["bundle"], sim.instances, cfg)
        assert runner._interpret is interpret, platform
