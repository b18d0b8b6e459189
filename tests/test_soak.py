"""Randomized differential soak harness for the fused decision backend.

Random serving worlds from `repro.serving.scenarios.random_scenario`
(rosters up to 16 tiers x 128 instances, composite multi-tenant traces,
scripted failure/recovery/straggler schedules) are fed identically to
the numpy reference loop and the fused single-dispatch program (and
the Pallas megakernel behind the same runner):

  * decision-level: exact fused == numpy assignment parity on
    randomized rosters and telemetry states, on every seed with no
    pinned exclusions (epsilon-quantized tie-break, PR 4 — the floor
    that justified flipping ``RBConfig.decision_backend`` to
    ``"fused"`` and keeping it there through the zero-allocation
    host-path rebuild);
  * serving-level: full `ClusterSim` runs land on identical
    request->instance trajectories and metrics under every backend,
    including through failure injection;
  * invariant-level: `TelemetryArrays` and the fused dead-reckoned
    device state stay physical under any perturbation schedule
    (free >= 0, batch <= capacity, dead slots never dispatched to,
    version strictly monotonic, columnar view == dict snapshots).

A seeded small-case subset runs in tier-1; the full soak (seeds x
128-instance rosters) is marked `slow` per the pytest.ini convention
and runs in the nightly CI job.
"""
import numpy as np
import pytest

from repro.core import RBConfig, RouteBalance, run_cell
from repro.serving.cluster import ClusterSim, Instance
from repro.serving.scenarios import (random_scenario,
                                     randomize_prefix_state,
                                     randomize_telemetry)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # tier-1 must collect without it
    HAVE_HYPOTHESIS = False

BACKENDS = ("numpy", "fused")
_RUNS = {}                              # (seed, scale) -> ScenarioRun


def _run_for(seed, max_tiers, max_instances, dataset_n=220):
    key = (seed, max_tiers, max_instances)
    if key not in _RUNS:
        sc = random_scenario(seed, max_tiers=max_tiers,
                             max_instances=max_instances)
        _RUNS[key] = sc.build(dataset_n=dataset_n)
        _RUNS[key].bundle()
    return _RUNS[key]


def _loaded_sim(run, seed, kill_frac=0.0):
    return randomize_telemetry(
        ClusterSim(run.tiers, run.names, seed=0), seed, kill_frac)


def _decision_parity(run, seed, R, kill_frac=0.0, affinity_weight=0.0,
                     backends=BACKENDS):
    reqs = run.requests(R, seed=seed)[:R]
    for r in reqs:
        r.arrival = 0.0
    out = {}
    for be in backends:
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   affinity_weight=affinity_weight),
                          run.bundle(), run.tiers)
        sim = _loaded_sim(run, seed, kill_frac)
        if affinity_weight:
            # warm a random subset of sketches through the live
            # dead-reckoning path (dead instances stay cold)
            randomize_prefix_state(sim, reqs[0].cols, seed)
        rb.sim = sim
        instances, choice, l_chosen = rb._decide_core(reqs)
        dead = {inst.iid for inst in rb.sim.instances if not inst.alive}
        picked = [instances[int(i)].iid for i in choice]
        assert not dead.intersection(picked), (be, dead & set(picked))
        out[be] = (picked, np.asarray(l_chosen, np.float64))
    anchor = "fused" if "fused" in out else backends[0]
    for be in backends:
        assert out[be][0] == out[anchor][0], (be, anchor)
        if be in (anchor, "numpy"):
            continue
        # every float32 backend (fused / megakernel) must agree
        # bitwise; the float64 numpy reference only to tolerance
        np.testing.assert_array_equal(out[be][1], out[anchor][1],
                                      err_msg=f"{be} vs {anchor}")
    if "numpy" in out and anchor != "numpy":
        np.testing.assert_allclose(out[anchor][1], out["numpy"][1],
                                   rtol=2e-4)


# -- decision-level soak ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_soak_decision_parity_small(seed):
    """Tier-1 subset: random rosters up to 32 instances."""
    run = _run_for(seed, max_tiers=6, max_instances=32)
    _decision_parity(run, seed, R=16)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10)))
@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
def test_soak_decision_parity_full(seed, kill_frac):
    """Full soak: rosters up to 16 tiers x 128 instances, with and
    without a quarter of the fleet dead. Exact numpy == fused parity on
    EVERY seed — the epsilon-quantized score tie-break
    (`repro.core.scoring`) collapses float32-vs-float64 argmax
    near-ties, so the grid no longer pins worlds away from same-tier
    replica flips."""
    run = _run_for(seed, max_tiers=16, max_instances=128)
    _decision_parity(run, seed, R=48, kill_frac=kill_frac)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_soak_decision_parity_affinity_small(seed):
    """Tier-1 subset with the prefix-affinity term live: warmed random
    sketches, exact numpy == fused parity including the quantized
    tie-break (the fourth term rides the same float32 arithmetic and
    epsilon-quantization as the other three)."""
    run = _run_for(seed, max_tiers=6, max_instances=32)
    _decision_parity(run, seed, R=16, affinity_weight=0.35)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10)))
@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
def test_soak_decision_parity_affinity_full(seed, kill_frac):
    """Full affinity soak: 16x128 worlds, warmed sketches, with and
    without a quarter of the fleet dead (killed AFTER warming in some
    orders via randomize_prefix_state's alive check — dead rows must
    never contribute affinity in any backend)."""
    run = _run_for(seed, max_tiers=16, max_instances=128)
    _decision_parity(run, seed, R=48, kill_frac=kill_frac,
                     affinity_weight=0.35)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10)))
@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
def test_soak_fused_matches_numpy_everywhere(seed, kill_frac):
    """The graduation guarantee behind decision_backend="fused": the
    fused program makes exactly the numpy reference's assignments on
    EVERY random world (l_chosen to the float32 tolerance), and no seed
    is excluded."""
    run = _run_for(seed, max_tiers=16, max_instances=128)
    reqs = run.requests(48, seed=seed)[:48]
    for r in reqs:
        r.arrival = 0.0
    out = {}
    for be in ("numpy", "fused"):
        rb = RouteBalance(RBConfig(decision_backend=be),
                          run.bundle(), run.tiers)
        rb.sim = _loaded_sim(run, seed, kill_frac)
        instances, choice, l_chosen = rb._decide_core(reqs)
        out[be] = ([instances[int(i)].iid for i in choice],
                   np.asarray(l_chosen, np.float64))
    assert out["numpy"][0] == out["fused"][0]
    np.testing.assert_allclose(out["fused"][1], out["numpy"][1],
                               rtol=2e-4)


@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_soak_decision_parity_megakernel_small(seed, kill_frac):
    """Tier-1 subset for the Pallas megakernel backend: exact assignment
    parity with the fused-XLA program (bitwise l_chosen included) and
    the staged references on random rosters, with and without a quarter
    of the fleet dead (alive-mask churn through the one-kernel path)."""
    run = _run_for(seed, max_tiers=6, max_instances=32)
    _decision_parity(run, seed, R=16, kill_frac=kill_frac,
                     backends=("numpy", "fused", "megakernel"))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_soak_decision_parity_megakernel_affinity_small(seed):
    """Megakernel with the prefix-affinity term live: the in-kernel
    integer sig compares + float32 discount must stay bitwise the fused
    program's on warmed random sketches."""
    run = _run_for(seed, max_tiers=6, max_instances=32)
    _decision_parity(run, seed, R=16, affinity_weight=0.35,
                     backends=("numpy", "fused", "megakernel"))


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10)))
@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
def test_soak_decision_parity_megakernel_full(seed, kill_frac):
    """Full megakernel soak: 16-tier x 128-instance worlds, exact
    three-way parity on every seed — the megakernel traces the SAME
    shared stage math as the fused program (greedy_step, admission_math,
    masked_score, packed GBM), so no tolerance is needed against it."""
    run = _run_for(seed, max_tiers=16, max_instances=128)
    _decision_parity(run, seed, R=48, kill_frac=kill_frac,
                     backends=("numpy", "fused", "megakernel"))


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(10)))
@pytest.mark.parametrize("kill_frac", [0.0, 0.25])
def test_soak_decision_parity_megakernel_affinity_full(seed, kill_frac):
    run = _run_for(seed, max_tiers=16, max_instances=128)
    _decision_parity(run, seed, R=48, kill_frac=kill_frac,
                     affinity_weight=0.35,
                     backends=("numpy", "fused", "megakernel"))


# -- serving-level soak -------------------------------------------------------

def _trajectory(run, be, reqs_seed, n):
    reqs = run.requests(n, seed=reqs_seed)
    rb = RouteBalance(RBConfig(decision_backend=be, charge_compute=False),
                      run.bundle(), run.tiers)
    m = run.run_cell(rb, reqs, seed=0)
    return [r.instance for r in reqs], m


@pytest.mark.parametrize("seed", [0, 2])
def test_soak_e2e_trajectory_small(seed):
    """A full cluster run through the scenario's own failure schedule
    lands on the identical trajectory under the numpy and fused
    backends."""
    run = _run_for(seed, max_tiers=5, max_instances=20)
    results = {be: _trajectory(run, be, seed, n=40) for be in BACKENDS}
    assert results["numpy"][0] == results["fused"][0]
    for k in ("quality", "mean_e2e", "cost_per_req", "goodput"):
        assert results["fused"][1][k] == pytest.approx(
            results["numpy"][1][k], rel=1e-9), k


@pytest.mark.parametrize("seed", [0, 2])
def test_soak_e2e_trajectory_megakernel(seed):
    """A full cluster run under the megakernel backend lands on the
    fused backend's trajectory request-for-request (same failure
    schedule, same metrics)."""
    run = _run_for(seed, max_tiers=5, max_instances=20)
    results = {be: _trajectory(run, be, seed, n=40)
               for be in ("fused", "megakernel")}
    assert results["megakernel"][0] == results["fused"][0]
    for k in ("quality", "mean_e2e", "cost_per_req", "goodput"):
        assert results["megakernel"][1][k] == pytest.approx(
            results["fused"][1][k], rel=1e-12), k


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(4)))
def test_soak_e2e_trajectory_full(seed):
    run = _run_for(seed, max_tiers=16, max_instances=128)
    results = {be: _trajectory(run, be, seed + 10, n=150)
               for be in BACKENDS}
    assert results["numpy"][0] == results["fused"][0]


# -- sharded-parity soak (hierarchical scheduling, PR 10) ---------------------

def _hier_fingerprint(reqs):
    return [(r.rid, r.instance, r.finish_time, r.tokens_out,
             bool(r.failed), bool(r.shed), r.attempt) for r in reqs]


def _cells_trajectory(run, n_cells, reqs_seed, n):
    """One full run under `n_cells` cells with the cell assignment
    pinned — span routing shards the scan of ONE logical controller,
    so placement is independent of the cell count by construction."""
    reqs = run.requests(n, seed=reqs_seed)
    rb = RouteBalance(
        RBConfig(charge_compute=False,
                 shard_cells=0 if n_cells == 1 else n_cells),
        run.bundle(), run.tiers)
    run.run_cell(rb, reqs, seed=0)
    return _hier_fingerprint(reqs)


@pytest.mark.parametrize("seed", [0, 2])
def test_soak_sharded_parity_small(seed):
    """Random scenarios under 1/2/4 cells land on identical per-request
    trajectories when the cell assignment is pinned (span routing: the
    sharded scan is bitwise the single controller), and the balanced
    hierarchy — where placement IS the cell count's decision — stays
    invariant-clean on the same worlds."""
    from repro.serving.hierarchy import HierarchyConfig, build_scheduler
    from repro.serving.metrics import check_terminal_states
    run = _run_for(seed, max_tiers=5, max_instances=20)
    trajs = {C: _cells_trajectory(run, C, seed + 5, n=40)
             for C in (1, 2, 4)}
    assert trajs[1] == trajs[2] == trajs[4]
    for C in (2, 3):
        reqs = run.requests(40, seed=seed + 6)
        sched = build_scheduler(
            RBConfig(charge_compute=False), run.bundle(), run.tiers,
            HierarchyConfig(n_cells=C, routing="balanced"))
        run.run_cell(sched, reqs, seed=0)
        check_terminal_states(reqs)
        assert sched.decisions + sched.shed_count == len(reqs)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(6)))
def test_soak_sharded_parity_full(seed):
    """Nightly-scale sharded parity: 16-tier x 128-instance random
    worlds, span trajectories identical across 1/2/4 cells through each
    scenario's own failure schedule, balanced runs invariant-clean at
    2/4 cells with every cell taking traffic."""
    from repro.serving.hierarchy import HierarchyConfig, build_scheduler
    from repro.serving.metrics import check_terminal_states
    run = _run_for(seed, max_tiers=16, max_instances=128)
    trajs = {C: _cells_trajectory(run, C, seed + 20, n=120)
             for C in (1, 2, 4)}
    assert trajs[1] == trajs[2] == trajs[4]
    for C in (2, 4):
        reqs = run.requests(120, seed=seed + 21)
        sched = build_scheduler(
            RBConfig(charge_compute=False), run.bundle(), run.tiers,
            HierarchyConfig(n_cells=C, routing="balanced"))
        run.run_cell(sched, reqs, seed=0)
        check_terminal_states(reqs)
        assert sched.decisions + sched.shed_count == len(reqs)
        assert all(sched.balancer.assigned_total[ci] > 0
                   for ci in range(C))


# -- invariant-level ----------------------------------------------------------

def _probe_invariants(sim, log):
    def probe(t):
        tel = sim.tel
        log.append(tel.version)
        assert np.all(tel.free >= 0)
        assert np.all(tel.free <= tel.max_batch)
        assert np.all(tel.batch <= tel.max_batch)
        assert np.all(tel.batch >= 0) and np.all(tel.pending >= 0)
        for inst in sim.instances:
            # the mirror masks quarantined rows (watchdog) as well as
            # dead ones; muted rows go stale by design, so snapshot
            # equality only holds for publishing rows
            assert bool(tel.alive[inst.slot]) == (
                inst.alive and not inst.quarantined)
            if inst.alive and not inst.tel_mute and not inst.quarantined:
                s = inst.snapshot
                assert s["pending_decode"] == tel.pending[inst.slot]
                assert s["batch_size"] == tel.batch[inst.slot]
                assert s["free_slots"] == tel.free[inst.slot]
                assert s["mean_ctx"] == tel.ctx[inst.slot]
                assert s["queue_depth"] == tel.queue[inst.slot]
        if sim._events:
            sim.push(t + 0.2, probe)
    sim.push(0.05, probe)


def _guard_dead_dispatch(monkeypatch):
    orig = Instance.submit

    def guarded(self, req, t, pred_len, max_tokens):
        assert self.alive, f"dispatched to dead instance {self.iid}"
        return orig(self, req, t, pred_len, max_tokens)

    monkeypatch.setattr(Instance, "submit", guarded)


@pytest.mark.parametrize("seed", [1, 3])
def test_telemetry_invariants_under_failures(seed, monkeypatch):
    """Property-check TelemetryArrays + dead-reckoned dispatch under the
    scenario's failure/recovery/straggler schedule: free >= 0, batch <=
    capacity, dead slots never dispatched to, version monotonic, and the
    columnar view always equals the per-instance dict snapshots."""
    _guard_dead_dispatch(monkeypatch)
    run = _run_for(seed, max_tiers=5, max_instances=20)
    reqs = run.requests(50, seed=seed)
    rb = RouteBalance(RBConfig(charge_compute=False), run.bundle(),
                      run.tiers)
    sim = run.sim(seed=0)
    rb.expected = len(reqs)
    rb.attach(sim)
    for r in reqs:
        sim.push(r.arrival, lambda t, rr=r: rb.enqueue(rr, t))
    versions = []
    _probe_invariants(sim, versions)
    sim.run()
    assert versions == sorted(versions)            # monotonic
    assert versions[-1] > versions[0]
    served = [r for r in reqs if r.finish_time is not None
              and not r.failed]
    assert served                                  # the cell made progress


def test_fused_carried_state_stays_physical(monkeypatch):
    """The fused backend's device-resident state must stay physical
    through an entire failure-perturbed run: the carried telemetry
    mirror (delta-synced, never fully re-uploaded in steady state) and
    the post-scan dead-reckoned view must respect d >= 0, free >= 0,
    b <= max_batch incl. the pow2 roster pads. (The mirror reflects the
    telemetry *as of the last sync* — the sim keeps writing telemetry
    after the final batch fires, so end-of-run exact equality is not an
    invariant; ``tests/test_hotpath.py`` asserts mirror == telemetry
    immediately after a sync, and ``tests/test_ingest.py`` asserts the
    delta path's assignment parity per batch.)"""
    _guard_dead_dispatch(monkeypatch)
    run = _run_for(4, max_tiers=6, max_instances=40)
    reqs = run.requests(60, seed=4)
    rb = RouteBalance(RBConfig(decision_backend="fused",
                               charge_compute=False),
                      run.bundle(), run.tiers)
    run.run_cell(rb, reqs, seed=0)
    assert rb._fused is not None
    # the delta path must have been the common case, not dead code
    st = rb._fused.stats
    assert st["delta_sync"] + st["carry"] > st["full_reseed"]
    d, b, free, ctx = (np.asarray(x, np.float64)
                       for x in rb._fused._state)
    maxb = np.asarray(rb._fused._maxb, np.float64)
    assert d.shape == b.shape == free.shape == maxb.shape
    assert len(d) >= run.n_instances               # pow2 roster bucket
    I = run.n_instances
    assert np.all(d >= 0) and np.all(free >= 0) and np.all(ctx >= 0)
    assert np.all(b[:I] <= maxb[:I] + 1e-6)        # mirror stays physical
    d1, b1, f1 = (np.asarray(x, np.float64)
                  for x in rb._fused._post_state)
    assert np.all(d1 >= 0) and np.all(f1 >= 0)
    assert np.all(b1 <= maxb + 1e-6)
    # pad columns accumulate no load (b carries the scan's max(b,1)
    # floor, nothing more)
    pad = slice(run.n_instances, None)
    assert np.all(d1[pad] == 0) and np.all(b1[pad] <= 1.0)


# -- fault-lifecycle soak (retry / hedge / watchdog, PR 7) --------------------

def _random_fault_schedule(seed, n_events=6, horizon=8.0):
    """A seeded random mix of every perturbation kind the lifecycle has
    to survive: crashes, recoveries, stragglers and telemetry
    blackouts. Target draws happen at fire time (apply_schedule), so
    the same tuple composes deterministically with whatever already
    failed."""
    from repro.serving.scenarios import FailureEvent
    rng = np.random.default_rng((seed, 0xC405))
    events = []
    for _ in range(n_events):
        kind = str(rng.choice(("fail", "recover", "straggle",
                               "mute", "unmute")))
        events.append(FailureEvent(
            t=float(rng.uniform(0.5, horizon)), kind=kind,
            frac=float(rng.uniform(0.2, 0.7)),
            factor=float(rng.uniform(2.0, 6.0))))
    return tuple(sorted(events, key=lambda ev: ev.t))


def _fault_cell(run, be, reqs_seed, n, schedule, cfg):
    """One manual cell with the recovery manager armed (run_cell is
    bypassed so the cached ScenarioRun's own schedule/recovery fields
    stay untouched for the other soak tests)."""
    from repro.serving.recovery import arm_recovery
    from repro.serving.scenarios import apply_schedule
    reqs = run.requests(n, seed=reqs_seed)
    rb = RouteBalance(RBConfig(decision_backend=be, charge_compute=False),
                      run.bundle(), run.tiers)
    sim = ClusterSim(run.tiers, run.names, seed=0)
    arm_recovery(sim, cfg)
    rb.expected = len(reqs)
    rb.attach(sim)
    for r in reqs:
        sim.push(r.arrival, lambda t, rr=r: rb.enqueue(rr, t))
    apply_schedule(sim, schedule, seed=reqs_seed)
    sim.run()
    return reqs, sim


def _lifecycle_fingerprint(reqs):
    return [(r.rid, r.instance, r.attempt, r.hedges, r.tokens_out,
             r.failed, r.shed) for r in reqs]


def _assert_exactly_once(reqs, sim, cfg):
    from repro.serving.metrics import check_terminal_states
    check_terminal_states(reqs)                     # no lost requests
    done = [r for r in sim.completed]
    assert len({id(r) for r in done}) == len(done)  # no duplicates
    assert len({r.rid for r in done}) == len(done)
    for r in reqs:                                  # attempt bound
        assert r.attempt < cfg.max_attempts, (r.rid, r.attempt)
        if r.failed:
            assert r.attempt == cfg.max_attempts - 1, \
                "gave up before exhausting attempts"


@pytest.mark.parametrize("seed", [0, 2])
def test_soak_exactly_once_under_random_faults(seed, monkeypatch):
    """Property soak over seeded random fault schedules with the
    recovery manager armed: every request reaches exactly one terminal
    state (served, failed-after-max-attempts, or shed — never lost,
    never duplicated), the retry bound holds, dead instances are never
    dispatched to, and the full lifecycle trajectory — including
    attempt counts and hedges — is identical under the numpy and fused
    decision backends."""
    from repro.serving.recovery import RecoveryConfig
    _guard_dead_dispatch(monkeypatch)
    run = _run_for(seed, max_tiers=5, max_instances=20)
    cfg = RecoveryConfig()
    schedule = _random_fault_schedule(seed)
    out = {}
    for be in BACKENDS:
        reqs, sim = _fault_cell(run, be, seed, 50, schedule, cfg)
        _assert_exactly_once(reqs, sim, cfg)
        served = [r for r in reqs if r.finish_time is not None
                  and not r.failed]
        assert served                               # progress under churn
        out[be] = (_lifecycle_fingerprint(reqs),
                   [r.finish_time or -1.0 for r in reqs])
    assert out["numpy"][0] == out["fused"][0]
    np.testing.assert_allclose(out["fused"][1], out["numpy"][1],
                               rtol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(6)))
def test_soak_exactly_once_under_random_faults_full(seed, monkeypatch):
    """Nightly-scale version: bigger rosters, longer schedules, tighter
    hedge deadlines so the hedge path actually fires across the seed
    sweep."""
    from repro.serving.recovery import RecoveryConfig
    _guard_dead_dispatch(monkeypatch)
    run = _run_for(seed, max_tiers=10, max_instances=64)
    cfg = RecoveryConfig(hedge_factor=2.5, hedge_slack_s=1.0)
    schedule = _random_fault_schedule(seed + 100, n_events=10,
                                      horizon=14.0)
    out = {}
    for be in BACKENDS:
        reqs, sim = _fault_cell(run, be, seed, 120, schedule, cfg)
        _assert_exactly_once(reqs, sim, cfg)
        out[be] = _lifecycle_fingerprint(reqs)
    assert out["numpy"] == out["fused"]


if HAVE_HYPOTHESIS:
    from repro.serving.scenarios import FailureEvent, apply_schedule
    from repro.serving.world import World, build_dataset
    from repro.serving.request import Request

    _TINY = {}

    def _tiny_world():
        if not _TINY:
            from repro.serving.scenarios import synthetic_pool
            tiers, names, world = synthetic_pool(3, 6, seed=11)
            _TINY["tiers"], _TINY["names"] = tiers, names
            _TINY["ds"] = build_dataset(world, n=120)
        return _TINY

    @settings(max_examples=25, deadline=None, derandomize=True)
    @given(st.integers(0, 10 ** 6))
    def test_hypothesis_scenario_generation_is_wellformed(seed):
        sc = random_scenario(seed, max_tiers=16, max_instances=128)
        assert sc.n_tiers <= sc.n_instances
        run_n = sum(1 for ev in sc.schedule if ev.kind == "recover")
        fails = sum(1 for ev in sc.schedule if ev.kind == "fail")
        assert run_n <= fails or run_n == 0
        assert 0 < sc.lam <= 30.0 + 1e-9       # max_lam is a real bound

    @settings(max_examples=15, deadline=None, derandomize=True)
    @given(st.integers(0, 10 ** 6))
    def test_hypothesis_telemetry_invariants(seed):
        """Random submissions + random fail/recover/straggle schedules
        never drive TelemetryArrays out of its physical envelope."""
        tiny = _tiny_world()
        rng = np.random.default_rng(seed)
        sim = ClusterSim(tiny["tiers"], tiny["names"], seed=0)
        prompts, Q, L = tiny["ds"].split("test")
        for i in range(int(rng.integers(5, 30))):
            j = int(rng.integers(0, len(prompts)))
            inst = sim.instances[int(rng.integers(0,
                                                  len(sim.instances)))]
            r = Request(rid=i, prompt=prompts[j],
                        arrival=float(rng.uniform(0, 3)),
                        true_quality=Q[j], true_length=L[j])
            sim.push(r.arrival,
                     lambda t, rr=r, ii=inst: ii.alive and ii.submit(
                         rr, t, float(rr.true_length[ii.model_idx]),
                         None))
        events = []
        for _ in range(int(rng.integers(0, 4))):
            kind = str(rng.choice(("fail", "recover", "straggle")))
            events.append(FailureEvent(
                t=float(rng.uniform(0, 4)), kind=kind,
                frac=float(rng.uniform(0.1, 0.9)),
                factor=float(rng.uniform(1.5, 8.0))))
        apply_schedule(sim, events, seed=seed)
        versions = []
        _probe_invariants(sim, versions)
        sim.run()
        assert versions == sorted(versions)
        assert np.all(sim.tel.free >= 0)
        assert np.all(sim.tel.batch <= sim.tel.max_batch)

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(st.integers(0, 10 ** 6))
    def test_hypothesis_exactly_once_with_recovery(seed):
        """Hypothesis sweep of the full fault-tolerant lifecycle on a
        tiny world: random fault schedules (incl. telemetry blackouts
        that trip the watchdog) never lose or duplicate a request, and
        the retry bound always holds."""
        from repro.serving.recovery import RecoveryConfig
        run = _run_for(seed % 3, max_tiers=4, max_instances=12)
        cfg = RecoveryConfig()
        schedule = _random_fault_schedule(seed, n_events=5)
        reqs, sim = _fault_cell(run, "fused", seed % 7, 30, schedule,
                                cfg)
        _assert_exactly_once(reqs, sim, cfg)
