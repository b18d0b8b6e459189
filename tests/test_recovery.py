"""Fault-tolerant request lifecycle (`repro.serving.recovery`):
retry/requeue with bounded attempts, hedged re-dispatch on deadline
expiry, the telemetry watchdog's quarantine/release/degraded cycle, the
fused hot path's zero-recompile contract through that churn, and
scheduler checkpoint/restore across a simulated controller crash."""
import dataclasses

import numpy as np
import pytest

from repro.core import (EngineConfig, RBConfig, RouteBalance,
                        ServingEngine, run_cell)
from repro.serving.cluster import ClusterSim
from repro.serving.faults import (CHAOS_SUITES, chaos_world, compose,
                                  correlated_failure, crash_storm,
                                  straggler_storm, telemetry_blackout)
from repro.serving.metrics import check_terminal_states
from repro.serving.recovery import (RecoveryConfig, RecoveryManager,
                                    arm_recovery, least_loaded_instance,
                                    simulate_controller_crash)
from repro.serving.request import Request
from repro.serving.scenarios import apply_schedule, synthetic_pool
from repro.serving.world import Prompt


def _mini_sim(n_tiers=2, n_instances=4, seed=0):
    tiers, names, _ = synthetic_pool(n_tiers, n_instances, seed=seed)
    return ClusterSim(tiers, names, seed=0)


def _req(rid=0, arrival=0.0):
    prompt = Prompt(pid=rid, topic=0, difficulty=0.5, verbosity=0.5,
                    tokens=np.zeros(4, np.int32), len_in=64)
    return Request(rid=rid, prompt=prompt, arrival=arrival,
                   true_quality=np.full(8, 0.5),
                   true_length=np.full(8, 40.0))


# -- satellite: stale-iterate epoch pin ---------------------------------------

def test_stale_iterate_epoch():
    """A pre-failure `_iterate` event firing after fail->recover is a
    no-op: `fail` bumps the instance's lifecycle epoch and the event
    carries the epoch it was scheduled under. The stale event must not
    touch `iter_scheduled`, generate tokens, or write telemetry — the
    behavioral pin that replaced the old comment in
    `Instance.recover`."""
    sim = _mini_sim(n_tiers=1, n_instances=1)
    inst = sim.instances[0]
    inst.busy_until = 1.0
    inst.submit(_req(0), 0.0, 10.0, None)        # _iterate queued @ t=1.0
    assert inst.epoch == 0 and inst.iter_scheduled
    sim.push(0.1, lambda t: inst.fail())
    sim.push(0.2, lambda t: inst.recover(t))
    sim.run(until=0.5)
    assert inst.epoch == 1
    assert not inst.iter_scheduled               # recover resets the flag
    v = sim.tel.version
    sim.run(until=1.5)                           # the stale event fires
    assert not inst.iter_scheduled               # ...and changed nothing
    assert sim.tel.version == v
    assert inst.running == [] and sim.completed[0].failed


# -- retry/requeue ------------------------------------------------------------

def test_requeue_resets_dispatch_state_keeps_first_arrival():
    r = _req(3, arrival=1.0)
    r.instance, r.model_idx, r.dispatch_time = "x#0", 2, 1.5
    r.pred_len, r.max_tokens, r.tokens_out = 80.0, 100, 17
    r.first_token_time = 1.8
    r.requeue(6.0)
    assert r.attempt == 1 and r.arrival == 6.0
    assert r.first_arrival == 1.0                # e2e keeps the true clock
    assert r.instance is None and r.model_idx is None
    assert r.dispatch_time is None and r.pred_len is None
    assert r.max_tokens is None and r.first_token_time is None
    assert r.tokens_out == 0 and not r.failed
    r.finish_time = 8.0
    assert r.e2e == pytest.approx(7.0)           # charged from t=1.0


def test_backoff_is_deterministic_and_bounded():
    """Backoff delays replay bitwise (counter-based jitter keyed on
    (seed, rid, attempt) — no RNG state) and stay within the configured
    jitter band around the exponential schedule."""
    cfg = RecoveryConfig(max_attempts=4)
    for attempt in (1, 2, 3):
        delays = set()
        for _ in range(3):
            sim = _mini_sim()
            mgr = arm_recovery(sim, cfg)
            r = _req(11)
            r.attempt = attempt - 1
            assert mgr.on_failure(r, sim.instances[0], 5, now=2.0)
            (_, due), = mgr._pending.values()
            delays.add(due)
            nominal = cfg.backoff_base_s * cfg.backoff_mult ** (attempt - 1)
            assert (2.0 + nominal * (1 - cfg.backoff_jitter)
                    <= due <= 2.0 + nominal * (1 + cfg.backoff_jitter))
        assert len(delays) == 1                  # bitwise replay


def test_attempt_bound_gives_up():
    cfg = RecoveryConfig(max_attempts=3)
    sim = _mini_sim()
    mgr = arm_recovery(sim, cfg)
    r = _req(5)
    assert mgr.on_failure(r, sim.instances[0], 0, 0.0)   # attempt 0 -> 1
    assert mgr.on_failure(r, sim.instances[0], 0, 1.0)   # attempt 1 -> 2
    assert not mgr.on_failure(r, sim.instances[0], 0, 2.0)  # exhausted
    assert r.attempt == 2 and mgr.gave_up == 1
    done = _req(6)
    done.finish_time = 1.0
    assert not mgr.on_failure(done, sim.instances[0], 0, 2.0)
    assert done.attempt == 0                     # terminal: untouched


def test_fail_routes_inflight_and_queued_through_recovery():
    """Instance.fail hands BOTH running and queued requests to the
    manager; requeued victims are not terminal, wasted tokens are
    charged for partial decodes."""
    from repro.serving.cluster import _Seq
    sim = _mini_sim(n_tiers=1, n_instances=2)
    mgr = arm_recovery(sim, RecoveryConfig())
    inst = sim.instances[0]
    a, b = _req(0), _req(1)
    a.instance = inst.iid                        # mid-decode in a batch
    inst.running.append(_Seq(req=a, target_tokens=40, max_tokens=10 ** 9,
                             budget_tokens=None, generated=7, ctx=71))
    b.instance = inst.iid
    inst.queue.append((b, 50.0))                 # still waiting to prefill
    inst.fail()
    assert mgr.retries == 2 and not a.failed and not b.failed
    assert a.finish_time is None and b.finish_time is None
    assert a.wasted_tokens == 7 and b.wasted_tokens == 0
    assert a.attempt == 1 and b.attempt == 1
    assert sim.completed == []                   # nothing terminal yet


# -- engine integration -------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_run():
    run = chaos_world().build(dataset_n=300)
    run.bundle()
    return run


def _cell(run, schedule, backend="fused", n=120,
          recovery=RecoveryConfig(), **rb_kw):
    run.recovery = recovery
    reqs = run.requests(n, seed=0)
    rb = RouteBalance(RBConfig(decision_backend=backend,
                               charge_compute=False, **rb_kw),
                      run.bundle(), run.tiers)
    m = run.run_cell(rb, _with_schedule(run, schedule, reqs), seed=0)
    return reqs, rb, m


def _with_schedule(run, schedule, reqs):
    # ScenarioRun.run_cell arms the SCENARIO's schedule; chaos cells
    # swap in a campaign by rebinding the (frozen) scenario
    run.scenario = dataclasses.replace(run.scenario, schedule=schedule)
    return reqs


def test_crash_storm_retries_everything(chaos_run):
    reqs, _, m = _cell(chaos_run, crash_storm(chaos_run.tiers))
    assert m["failed"] == 0 and m["n"] == len(reqs)
    assert m["retries"] > 0 and m["retried"] > 0
    assert m["wasted_tokens"] > 0                # partial decodes charged
    for r in reqs:
        if r.attempt > 0:
            assert r.arrival > r.first_arrival   # requeued later
            assert r.e2e == pytest.approx(r.finish_time - r.first_arrival)


def test_lost_work_without_recovery(chaos_run):
    reqs, _, m = _cell(chaos_run, crash_storm(chaos_run.tiers),
                       recovery=None)
    assert m["failed"] > 0                       # the arm retry beats
    assert "retries" not in m
    check_terminal_states(reqs)                  # failed, not lost


def test_correlated_failure_reroutes_heterogeneously(chaos_run):
    reqs, _, m = _cell(chaos_run,
                       correlated_failure(chaos_run.tiers))
    assert m["failed"] == 0 and m["n"] == len(reqs)
    assert m["retries"] > 0
    # victims moved to a DIFFERENT tier (the victim tier is fully dead)
    victim = max(chaos_run.tiers,
                 key=lambda t: (t.n_instances, t.name)).name
    moved = [r for r in reqs if r.attempt > 0
             and r.instance is not None]
    assert moved and any(not r.instance.startswith(victim)
                         for r in moved)


def test_straggler_storm_hedges(chaos_run):
    reqs, _, m = _cell(chaos_run,
                       straggler_storm(chaos_run.tiers, frac=0.7,
                                       factor=8.0, duration=10.0),
                       recovery=RecoveryConfig(hedge_factor=2.5,
                                               hedge_slack_s=1.0))
    assert m["failed"] == 0 and m["n"] == len(reqs)
    assert m["hedges"] > 0 and m["hedged"] > 0
    assert m["duplicate_tokens"] > 0             # loser's work charged
    hedged = [r for r in reqs if r.hedges > 0]
    assert all(r.finish_time is not None for r in hedged)


def test_watchdog_quarantine_release_zero_recompiles(chaos_run):
    """Partial telemetry blackout: stale rows are quarantined through
    the alive-mask path and released with a reseed when they publish
    again — with ZERO extra XLA recompiles (the same contract the
    autoscaler's roster churn pins). A distinct weight preset gets its
    own FusedHotPath (the runner is cached on the bundle per config),
    so the compile count is clean of the other cells in this module."""
    from repro.core import PRESETS
    reqs, rb, m = _cell(chaos_run,
                        telemetry_blackout(chaos_run.tiers, frac=0.5),
                        weights=PRESETS["quality"])
    assert m["failed"] == 0 and m["n"] == len(reqs)
    assert m["quarantines"] > 0
    assert m["degraded_decisions"] == 0          # half the fleet heard
    from repro.core.decision_jax import bucket_pow2
    buckets = {bucket_pow2(s) for s, _ in rb.compute_log}
    assert rb._fused.compile_count() == len(buckets)


def test_full_blackout_degrades_to_least_loaded(chaos_run):
    reqs, _, m = _cell(chaos_run,
                       telemetry_blackout(chaos_run.tiers, frac=1.0))
    assert m["failed"] == 0 and m["n"] == len(reqs)
    assert m["degraded_decisions"] > 0           # mirror went dark


def test_one_muted_row_among_idle_rows_is_quarantined():
    """Partial blackout at low load: one busy row stops publishing
    while every other live row has sat idle for longer than
    stale_after_s. The idle rows' mirrors still hold their workers'
    last state, so the watchdog quarantines the muted row alone and
    the controller does not go degraded."""
    sim = _mini_sim(n_tiers=1, n_instances=4)
    mgr = arm_recovery(sim, RecoveryConfig())
    busy, *idle = sim.instances
    for k, inst in enumerate(idle):              # short work, then idle
        inst.submit(_req(10 + k), 0.0, 40.0, None)
    long = _req(0)
    long.true_length = np.full(8, 5000.0)
    busy.tel_mute = True
    busy.submit(long, 0.0, 5000.0, None)
    sim.run(until=6.0)
    assert all(not i.running and not i.queue for i in idle)
    assert all(6.0 - sim.tel.t[i.slot] > 2.0 for i in idle)
    assert busy.running                          # still decoding, unseen
    mgr._watch(6.0)
    assert busy.quarantined and mgr.quarantines == 1
    assert not any(i.quarantined for i in idle)
    assert not mgr.degraded and mgr.degraded_entries == 0


# -- prefix-affinity under failure / quarantine (serving.affinity) -----------

def _pick_one(run, req, w, mutate=None):
    """The unanimous (numpy == fused) instance pick for a
    single-request decision at affinity weight `w`, on a fresh sim
    optionally perturbed by `mutate`."""
    picks = {}
    for be in ("numpy", "fused"):
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   affinity_weight=w),
                          run.bundle(), run.tiers)
        sim = ClusterSim(run.tiers, run.names, seed=0)
        if mutate is not None:
            mutate(sim)
        rb.sim = sim
        instances, choice, _ = rb._decide_core([req])
        picks[be] = instances[int(choice[0])].iid
    assert len(set(picks.values())) == 1, picks
    return picks["fused"]


def test_revived_instance_returns_cold(chaos_run):
    """The KV cache dies with the node: after fail() -> recover() the
    instance's sketch AND its scheduler-side mirror row are empty, and
    a re-dispatch of the very prompt it was serving scores a zero hit
    (a retry must never be credited against the cache its failed victim
    lost)."""
    from repro.serving.affinity import prompt_signatures
    sim = ClusterSim(chaos_run.tiers, chaos_run.names, seed=0)
    inst = sim.instances[0]
    p = Prompt(pid=0, topic=0, difficulty=0.5, verbosity=0.5,
               tokens=np.arange(1, 65, dtype=np.int32), len_in=64)
    r1, r2 = (_req(i) for i in (0, 1))
    r1.prompt = r2.prompt = p
    inst.submit(r1, 0.0, 10.0, None)
    assert len(inst.sketch) > 0
    assert sim.tel.prefix_sig[inst.slot].any()
    inst.fail()
    inst.recover(1.0)
    assert len(inst.sketch) == 0                 # revived cold
    assert not sim.tel.prefix_sig[inst.slot].any()
    assert inst.sketch.hit_tokens(prompt_signatures(p), 64) == 0
    inst.submit(r2, 1.1, 10.0, None)
    assert r2.prefix_hit == 0.0                  # the retry pays full prefill


def test_quarantined_row_never_scores_affinity(chaos_run):
    """The watchdog's quarantine masks a row out of the candidate
    roster; its (still-populated) prefix mirror must contribute NOTHING:
    decisions with a quarantined warm row are identical to decisions
    with that row quarantined and cold, in every backend — and the warm
    instance is never picked while masked."""
    from repro.serving.affinity import prompt_signatures
    run = chaos_run
    req = run.requests(4, seed=5)[0]
    req.arrival = 0.0
    sig = prompt_signatures(req.prompt)
    base = _pick_one(run, req, 0.9)
    slot = next(i.slot for i in
                ClusterSim(run.tiers, run.names, seed=0).instances
                if i.iid == base)

    def warm(sim):
        inst = sim.instances[slot]
        inst.sketch.insert(sig)
        sim.tel.write_prefix(slot, inst.sketch)

    def quarantine(sim):
        sim.instances[slot].quarantined = True
        sim.tel.quarantine(slot)

    def warm_quar(sim):
        warm(sim)
        quarantine(sim)

    assert _pick_one(run, req, 0.9, warm) == base    # warm: still best
    q_warm = _pick_one(run, req, 0.9, warm_quar)
    assert q_warm != base                            # masked row unpickable
    # stale prefix credit on a masked row is invisible to the score
    assert q_warm == _pick_one(run, req, 0.9, quarantine)


def test_parity_through_recovery_churn(chaos_run):
    """numpy == fused full-trajectory parity THROUGH retry,
    hedge and quarantine churn: every recovery decision is a
    deterministic function of the simulation trajectory, so the
    differential-soak contract extends to the fault-tolerant
    lifecycle."""
    campaign = compose(crash_storm(chaos_run.tiers, t0=2.0, waves=2),
                       straggler_storm(chaos_run.tiers, t0=6.0),
                       telemetry_blackout(chaos_run.tiers, t0=9.0,
                                          frac=0.5))
    out = {}
    for be in ("numpy", "fused"):
        reqs, _, m = _cell(chaos_run, campaign, backend=be)
        assert m["failed"] == 0
        out[be] = ([(r.rid, r.instance, r.model_idx, r.dispatch_time,
                     r.finish_time, r.tokens_out, r.attempt, r.hedges)
                    for r in reqs],
                   (m["retries"], m["hedges"], m["quarantines"]))
    assert out["numpy"] == out["fused"]


def test_retries_are_never_shed():
    """Admission control gates NEW work only: a retry re-entering
    `enqueue` bypasses the shed verdict even under declared
    overload."""
    from repro.serving.overload import OverloadConfig, arm_elastic

    class _Policy:
        budget_clamp = False
        name = "stub"

        def engine_overrides(self):
            return {}

        def prepare(self, bundle, tiers):
            self.bundle = bundle

        def on_attach(self, sim):
            pass

        def shed_verdict(self, req, ctl):
            return True                           # shed EVERYTHING

    tiers, names, _ = synthetic_pool(2, 4, seed=0)
    sim = ClusterSim(tiers, names, seed=0)
    arm_elastic(sim, OverloadConfig())

    class _Bundle:
        encoder = None
    eng = ServingEngine(_Policy(), _Bundle(), tiers, EngineConfig())
    eng.attach(sim)
    fresh, retry = _req(0), _req(1)
    retry.attempt = 1
    eng.enqueue(fresh, 0.0)
    eng.enqueue(retry, 0.0)
    assert fresh.shed and not retry.shed
    assert eng.waiting == [retry]


# -- checkpoint/restore across a controller crash -----------------------------

def _controlled_run(run, reqs, sched, crash_at=None):
    """One windowed cell with recovery armed; optionally crash the
    controller at `crash_at` and resume a FRESH engine from the
    checkpoint taken at the crash instant."""
    cfg = EngineConfig(charge_compute=False)
    rb_cfg = dict(decision_backend="fused", charge_compute=False)
    sim = ClusterSim(run.tiers, run.names, seed=0)
    arm_recovery(sim, RecoveryConfig())
    eng1 = RouteBalance(RBConfig(**rb_cfg), run.bundle(), run.tiers)
    eng1.expected = len(reqs)
    eng1.attach(sim)
    holder = {"eng": eng1}
    for r in reqs:
        sim.push(r.arrival, lambda t, rr=r: holder["eng"].enqueue(rr, t))
    apply_schedule(sim, sched, seed=1)
    if crash_at is not None:
        def crash(t):
            tree = holder["eng"].checkpoint_tree()
            dropped = simulate_controller_crash(sim, holder["eng"])
            assert dropped > 0                   # something actually died
            arm_recovery(sim, RecoveryConfig())
            eng2 = RouteBalance(RBConfig(**rb_cfg), run.bundle(),
                                run.tiers)
            eng2.resume(sim, tree, reqs)
            holder["eng"] = eng2
        sim.push(crash_at, crash)
    sim.run()
    check_terminal_states(reqs)
    return [(r.rid, r.finish_time, r.tokens_out, r.model_idx,
             r.instance, r.failed, r.attempt, r.hedges) for r in reqs]


def test_controller_crash_restore_bitwise_identical(chaos_run):
    """A controller crash + checkpoint restore mid-trace resumes to the
    BITWISE-identical completion set of an uninterrupted run: no lost
    requests, no duplicates, same assignments, same finish times —
    through an active crash-storm campaign, at multiple crash points
    (before, during and after the fault window)."""
    sched = crash_storm(chaos_run.tiers)
    reqs = chaos_run.requests(120, seed=0)
    ref = _controlled_run(chaos_run, reqs, sched)
    for crash_at in (2.0, 5.3, 9.1):
        reqs2 = chaos_run.requests(120, seed=0)
        got = _controlled_run(chaos_run, reqs2, sched, crash_at=crash_at)
        assert got == ref, f"divergence after crash at t={crash_at}"


def test_checkpoint_roundtrip_through_manager(chaos_run, tmp_path):
    """The engine tree survives the on-disk CheckpointManager: save at
    a live instant, restore into a template, resume — the arrays (and
    the completion trajectory) come back exactly."""
    from repro.distributed.checkpoint import CheckpointManager
    sched = crash_storm(chaos_run.tiers)
    reqs = chaos_run.requests(120, seed=0)
    ref = _controlled_run(chaos_run, reqs, sched)

    cfg = dict(decision_backend="fused", charge_compute=False)
    reqs2 = chaos_run.requests(120, seed=0)
    sim = ClusterSim(chaos_run.tiers, chaos_run.names, seed=0)
    arm_recovery(sim, RecoveryConfig())
    eng1 = RouteBalance(RBConfig(**cfg), chaos_run.bundle(),
                        chaos_run.tiers)
    eng1.expected = len(reqs2)
    eng1.attach(sim)
    holder = {"eng": eng1}
    for r in reqs2:
        sim.push(r.arrival, lambda t, rr=r: holder["eng"].enqueue(rr, t))
    apply_schedule(sim, sched, seed=1)
    ckpt = CheckpointManager(tmp_path / "ckpt")

    def crash(t):
        holder["eng"].save_checkpoint(ckpt, step=1)
        simulate_controller_crash(sim, holder["eng"])
        tree, step = ckpt.restore(ServingEngine._checkpoint_template())
        assert step == 1
        arm_recovery(sim, RecoveryConfig())
        eng2 = RouteBalance(RBConfig(**cfg), chaos_run.bundle(),
                            chaos_run.tiers)
        eng2.resume(sim, tree, reqs2)
        holder["eng"] = eng2
    sim.push(5.3, crash)
    sim.run()
    got = [(r.rid, r.finish_time, r.tokens_out, r.model_idx,
            r.instance, r.failed, r.attempt, r.hedges) for r in reqs2]
    assert got == ref


# -- terminal-state invariant -------------------------------------------------

def test_terminal_invariant_catches_lifecycle_bugs():
    lost = _req(0)                               # ingested, then vanished
    with pytest.raises(AssertionError, match="lost"):
        check_terminal_states([lost])
    dual = _req(1)
    dual.failed = dual.shed = True
    with pytest.raises(AssertionError, match="both"):
        check_terminal_states([dual])
    zombie = _req(2)                             # shed but "finished"
    zombie.shed = True
    zombie.finish_time = 3.0
    with pytest.raises(AssertionError, match="shed"):
        check_terminal_states([zombie])
    ghost = _req(3)                              # failed, no timestamp
    ghost.failed = True
    with pytest.raises(AssertionError, match="terminal timestamp"):
        check_terminal_states([ghost])
    ok_served, ok_failed, ok_shed = _req(4), _req(5), _req(6)
    ok_served.finish_time = 1.0
    ok_failed.failed = True
    ok_failed.finish_time = 1.0
    ok_shed.shed = True
    check_terminal_states([ok_served, ok_failed, ok_shed])


# -- degraded fallback details ------------------------------------------------

def test_least_loaded_prefers_unquarantined():
    sim = _mini_sim(n_tiers=1, n_instances=3)
    a, b, c = sim.instances
    a.quarantined = True
    b.queue.append((_req(0), 10.0))              # b is loaded
    pick = least_loaded_instance(sim)
    assert pick is c                             # idle, not quarantined
    pick = least_loaded_instance(sim, exclude=(c.iid,))
    assert pick is b                             # quarantine = last resort
    b.alive = c.alive = False
    assert least_loaded_instance(sim, exclude=(a.iid,)) is None


# -- arming from the deployment's configuration -------------------------------

def test_recovery_armed_from_rbconfig_binds_the_engine(chaos_run):
    cfg = RecoveryConfig(max_attempts=2)
    rb = RouteBalance(RBConfig(recovery=cfg), chaos_run.bundle(),
                      chaos_run.tiers)
    sim = ClusterSim(chaos_run.tiers, chaos_run.names)
    rb.attach(sim)
    assert isinstance(sim.recovery, RecoveryManager)
    assert sim.recovery.cfg == cfg and sim.recovery.engine is rb
    assert sim.recovery._watch_armed             # the watchdog started
    off = RouteBalance(RBConfig(), chaos_run.bundle(), chaos_run.tiers)
    bare = ClusterSim(chaos_run.tiers, chaos_run.names)
    off.attach(bare)
    assert off.ecfg.recovery is None
    assert getattr(bare, "recovery", None) is None   # as before: off


def test_a_recovery_block_becomes_a_recovery_config():
    cfg = RBConfig(recovery={"max_attempts": 2, "hedge": False})
    assert cfg.recovery == RecoveryConfig(max_attempts=2, hedge=False)
    hash(cfg.recovery)
    assert RBConfig().recovery is None
    with pytest.raises(TypeError):
        RBConfig(recovery={"no_such_knob": 1})


def test_scenario_and_engine_arm_through_one_function(chaos_run,
                                                      monkeypatch):
    """`ScenarioRun.arm` and `ServingEngine.attach` both build their
    manager in `arm_recovery`; an engine attached to a sim the scenario
    already armed binds that manager and arms no second one."""
    import repro.serving.recovery as recovery
    made = []

    class Spy(recovery.RecoveryManager):
        def __init__(self, sim, cfg):
            made.append(cfg)
            super().__init__(sim, cfg)

    monkeypatch.setattr(recovery, "RecoveryManager", Spy)
    by_scenario = RecoveryConfig(max_attempts=5)
    by_engine = RecoveryConfig(max_attempts=2)
    monkeypatch.setattr(chaos_run, "recovery", by_scenario)
    armed = chaos_run.arm(ClusterSim(chaos_run.tiers, chaos_run.names))
    rb = RouteBalance(RBConfig(recovery=by_engine), chaos_run.bundle(),
                      chaos_run.tiers)
    fresh = ClusterSim(chaos_run.tiers, chaos_run.names)
    rb.attach(fresh)
    assert type(armed.recovery) is Spy and type(fresh.recovery) is Spy
    assert made == [by_scenario, by_engine]
    rb.attach(armed)
    assert armed.recovery.cfg == by_scenario
    assert armed.recovery.engine is rb and len(made) == 2


def test_dead_cols_counts_the_masked_columns(chaos_run):
    """The runner's `dead_cols` adds the columns masked out, dead or
    quarantined, at each sync; fail, quarantine and recover flip the
    alive mask without a compile."""
    from repro.core import make_requests
    from repro.core.hotpath import FusedHotPath
    from repro.serving.request import batch_columns
    sim = ClusterSim(chaos_run.tiers, chaos_run.names)
    fp = FusedHotPath(chaos_run.bundle(), sim.instances,
                      RBConfig(decision_backend="fused"))
    cols, rows = batch_columns(make_requests(chaos_run.ds, "test",
                                             np.zeros(4)))
    cols.ensure_embeddings(chaos_run.bundle().encoder)

    def decide():
        fp.decide_cols(cols, rows, sim.tel).fetch()
        return fp.stats["dead_cols"]

    assert decide() == 0
    sim.instances[1].fail()
    sim.instances[3].fail()
    assert decide() == 2
    assert decide() == 4                         # counted at every sync
    sim.instances[5].quarantined = True
    sim.tel.quarantine(5)
    assert decide() == 7
    sim.instances[1].recover(1.0)
    sim.instances[3].recover(1.0)
    assert decide() == 8
    assert fp.stats["calls"] == 5 and fp.compile_count() == 1


def test_recovery_spans_in_the_trace(chaos_run, tmp_path):
    """`rb.recovery` spans open around the manager's work: inside the
    engine's `rb.submit` (the hedge timer armed per dispatch) and on the
    simulator's own events (a failure's victims, the watchdog)."""
    import jax
    from jax.profiler import ProfileData
    _cell(chaos_run, crash_storm(chaos_run.tiers), n=40)    # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, m = _cell(chaos_run, crash_storm(chaos_run.tiers), n=40)
    finally:
        jax.profiler.stop_trace()
    assert m["retries"] > 0
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("rb.")]
    rec = [s for s in spans if s[0] == "rb.recovery"]
    submits = [s for s in spans if s[0] == "rb.submit"]

    def inside(a, b):
        return b[1] <= a[1] and a[2] <= b[2]

    assert any(inside(r, s) for r in rec for s in submits)
    assert any(not any(inside(r, s) for s in submits) for r in rec)
