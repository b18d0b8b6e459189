"""Overload control + elastic roster: detector thresholds/hysteresis,
autoscaler scale-up lag + idle-only scale-down, SLO-aware priority
shedding, and the fused hot path's no-recompile contract under
autoscaler roster churn (with numpy==fused trajectory parity)."""
import numpy as np
import pytest

from repro.core import PRESETS, RBConfig, RouteBalance
from repro.core.decision_jax import bucket_pow2
from repro.serving.cluster import ClusterSim
from repro.serving.overload import (ElasticController, OverloadConfig,
                                    arm_elastic, load_score,
                                    provision_reserve)
from repro.serving.request import Request
from repro.serving.scenarios import get_scenario, synthetic_pool
from repro.serving.world import Prompt


def _mini_sim(n_tiers=2, n_instances=4, seed=0):
    tiers, names, _ = synthetic_pool(n_tiers, n_instances, seed=seed)
    return ClusterSim(tiers, names, seed=0)


def _req(rid=0, priority=0, arrival=0.0):
    prompt = Prompt(pid=rid, topic=0, difficulty=0.5, verbosity=0.5,
                    tokens=np.zeros(4, np.int32), len_in=64)
    return Request(rid=rid, prompt=prompt, arrival=arrival,
                   true_quality=np.full(8, 0.5), true_length=np.full(8, 40.0),
                   priority=priority)


# -- detector -----------------------------------------------------------------

def test_load_score_normalizes_by_alive_capacity():
    sim = _mini_sim()
    tel = sim.tel
    assert load_score(tel) == 0.0
    cap = float(tel.max_batch.sum())
    tel.batch[:] = tel.max_batch            # fleet exactly full
    assert load_score(tel) == pytest.approx(1.0)
    tel.queue[:] = tel.max_batch            # one fleet of backlog behind
    assert load_score(tel) == pytest.approx(2.0)
    # killing a row removes its capacity AND its contribution: the
    # remaining fleet is still exactly full-plus-one-fleet-queued
    sim.instances[0].fail()
    assert load_score(tel) == pytest.approx(2.0)
    for inst in sim.instances:
        inst.alive = False
    tel.alive[:] = False
    assert load_score(tel) == float("inf")


def test_detector_hysteresis_and_cooldown():
    """Scale-up needs `up_patience` consecutive hot checks; a non-hot
    check resets the streak; cooldown gates back-to-back events."""
    sim = _mini_sim(n_tiers=2, n_instances=6)
    reserve = [i.iid for i in sim.instances[-2:]]
    cfg = OverloadConfig(up_threshold=1.25, up_patience=2, cooldown_s=5.0,
                         scale_up_lag_s=0.5, max_step=1,
                         shed_enabled=False)
    ctl = ElasticController(sim, cfg, reserve).arm()
    tel = sim.tel

    def pressure(on):
        tel.queue[:] = tel.max_batch * (3.0 if on else 0.0)

    pressure(True)
    ctl._check(0.25)
    assert ctl._hot == 1 and ctl.scale_ups == 0     # patience not met
    pressure(False)
    ctl._check(0.50)
    assert ctl._hot == 0                            # streak reset
    pressure(True)
    ctl._check(0.75)
    ctl._check(1.00)
    assert ctl.scale_ups == 1                       # 2 consecutive hots
    ctl._check(1.25)
    ctl._check(1.50)
    assert ctl.scale_ups == 1                       # cooldown gates
    ctl._check(7.00)
    ctl._check(7.25)
    assert ctl.scale_ups == 2                       # cooldown expired


# -- autoscaler ---------------------------------------------------------------

def test_scale_up_pays_provisioning_lag():
    """A scale-up decision at t revives the reserve at exactly
    t + scale_up_lag_s (through the ordinary kill/revive path)."""
    sim = _mini_sim(n_tiers=2, n_instances=6)
    reserve = [i.iid for i in sim.instances[-2:]]
    cfg = OverloadConfig(up_patience=1, cooldown_s=0.0,
                         scale_up_lag_s=2.0, max_step=1,
                         shed_enabled=False)
    ctl = ElasticController(sim, cfg, reserve).arm()
    for iid in reserve:
        assert not sim.by_id[iid].alive             # armed cold
    sim.tel.queue[:] = sim.tel.max_batch * 5.0      # sustained pressure
    sim.push(10.0, lambda t: None)                  # keep the loop alive
    sim.run(until=1.0)
    ups = [(t, iid) for t, kind, iid in ctl.events if kind == "scale_up"]
    assert ups and ups[0][0] <= 1.0
    t_up, iid = ups[0]
    assert not sim.by_id[iid].alive                 # still provisioning
    sim.run(until=t_up + 2.0 + 1e-9)
    assert sim.by_id[iid].alive                     # ready after the lag
    ready = [(t, i) for t, kind, i in ctl.events if kind == "ready"]
    assert ready[0] == (pytest.approx(t_up + 2.0), iid)


def test_scale_down_retires_idle_reserves_only():
    sim = _mini_sim(n_tiers=2, n_instances=6)
    r0, r1 = sim.instances[-2], sim.instances[-1]
    cfg = OverloadConfig(shed_enabled=False)
    ctl = ElasticController(sim, cfg, [r0.iid, r1.iid])
    # both reserves alive (not armed cold): r0 has queued work
    r0.queue.append((_req(), 10.0))
    ctl._scale_down(1.0)
    assert r0.alive and not r1.alive                # idle one retired
    assert ctl.scale_downs == 1
    ctl._last_scale = -10.0
    ctl._scale_down(2.0)
    assert r0.alive                                 # busy: never revoked
    assert ctl.scale_downs == 1


# -- shedding -----------------------------------------------------------------

def test_shed_thresholds_are_priority_ordered():
    sim = _mini_sim()
    cfg = OverloadConfig(shed_thresholds=(6.0, 3.0, 1.8))
    ctl = ElasticController(sim, cfg, [])
    ctl.load = 2.0
    assert [ctl.wants_shed(p) for p in (0, 1, 2)] == [False, False, True]
    ctl.load = 4.0
    assert [ctl.wants_shed(p) for p in (0, 1, 2)] == [False, True, True]
    ctl.load = 7.0
    assert [ctl.wants_shed(p) for p in (0, 1, 2, 9)] == [True] * 4
    ctl.load = 7.0
    assert not ElasticController(
        sim, OverloadConfig(shed_enabled=False), []).wants_shed(2)


def test_policy_can_veto_shedding():
    """Shedding is policy-visible: RBConfig(shed=False) admits
    everything even when the controller wants to shed."""
    from repro.core.policies import RouterDispatchPolicy
    from repro.core.routers import PassthroughRouter
    from repro.core.dispatchers import RoundRobin
    from repro.core.scheduler import RouteBalancePolicy
    sim = _mini_sim()
    ctl = ElasticController(sim, OverloadConfig(), [])
    ctl.load = 100.0
    req = _req(priority=2)
    assert RouteBalancePolicy(RBConfig()).shed_verdict(req, ctl)
    assert not RouteBalancePolicy(
        RBConfig(shed=False)).shed_verdict(req, ctl)
    assert RouterDispatchPolicy(
        PassthroughRouter(), RoundRobin()).shed_verdict(req, ctl)
    assert not RouterDispatchPolicy(
        PassthroughRouter(), RoundRobin(), shed=False).shed_verdict(
            req, ctl)


# -- fail/recover edge-case pins (the machinery the autoscaler rides) ---------

def test_kill_does_not_stamp_last_write():
    """TelemetryArrays.kill bumps version + roster_version but NOT the
    row's last_write stamp: incremental readers must reseed via
    roster_version, never via dirty_rows (the fused mirror relies on
    this; pinned so the autoscaler can't regress it)."""
    sim = _mini_sim()
    tel = sim.tel
    inst = sim.instances[1]
    v0, r0 = tel.version, tel.roster_version
    inst.fail()
    assert tel.version > v0                      # write DID happen...
    assert inst.slot not in tel.dirty_rows(v0)   # ...but row not stamped
    assert tel.roster_version == r0 + 1          # reseed signal instead
    inst.recover(1.0)
    assert tel.roster_version == r0 + 2
    assert inst.slot in tel.dirty_rows(v0)       # revive DOES write


def test_recover_keeps_pending_iterate_single_chained():
    """Revive while a pre-failure `_iterate` event is still heap-pending
    must not start a second concurrent decode chain. Iterate events now
    carry the instance's lifecycle epoch (`fail` bumps it, stale events
    no-op on entry), so a revived instance always runs exactly ONE live
    chain — pinned by counting this instance's current-epoch _iterate
    events in the heap after a fail -> recover -> resubmit sequence.
    The stale-event no-op itself is pinned in
    tests/test_recovery.py::test_stale_iterate_epoch."""
    sim = _mini_sim(n_tiers=1, n_instances=1)
    inst = sim.instances[0]
    inst.busy_until = 1.0                        # pin the next iteration
    inst.submit(_req(0), 0.0, 10.0, None)        # _iterate queued @ t=1.0
    assert inst.iter_scheduled

    def live_iterates():
        n = 0
        for _, _, fn in sim._events:             # functools.partial events
            f = getattr(fn, "func", None)
            if (getattr(f, "__self__", None) is inst
                    and getattr(f, "__func__", None)
                    is type(inst)._iterate
                    and fn.keywords.get("epoch") == inst.epoch):
                n += 1
        return n

    assert live_iterates() == 1
    sim.push(0.1, lambda t: inst.fail())
    sim.push(0.2, lambda t: inst.recover(t))
    sim.push(0.3, lambda t: inst.submit(_req(1), t, 10.0, None))
    sim.run(until=0.5)                           # stale event NOT yet fired
    assert inst.alive and inst.iter_scheduled
    assert live_iterates() == 1                  # no second live chain
    sim.run()
    assert live_iterates() == 0
    done = [r for r in sim.completed if not r.failed]
    assert [r.rid for r in done] == [1]          # resubmit served once


# -- roster provisioning ------------------------------------------------------

def test_provision_reserve_expands_in_bucket():
    tiers, names, _ = synthetic_pool(4, 6, seed=5)
    out, reserve = provision_reserve(tiers, 2)
    assert sum(t.n_instances for t in out) == 8
    assert len(reserve) == 2
    assert bucket_pow2(6) == bucket_pow2(8) == 8  # same fused I bucket
    sim = ClusterSim(out, names, seed=0)
    for iid in reserve:
        assert iid in sim.by_id                   # trailing replicas exist
    same, none = provision_reserve(tiers, 0)
    assert [t.n_instances for t in same] == [t.n_instances for t in tiers]
    assert none == ()


# -- end-to-end: elastic scenario on the serving engine ------------------------

@pytest.fixture(scope="module")
def elastic_run():
    run = get_scenario("flashcrowd_elastic").build(dataset_n=300)
    run.bundle()
    return run


def _cell(run, backend, weights=PRESETS["uniform"], n=420, scale=4.0,
          shed=True):
    reqs = run.requests(n, lam_scale=scale, seed=3)
    rb = RouteBalance(RBConfig(weights=weights, decision_backend=backend,
                               charge_compute=False, shed=shed),
                      run.bundle(), run.tiers)
    m = run.run_cell(rb, reqs, seed=0)
    return reqs, rb, m


def _trajectory(reqs):
    return [(r.rid, r.instance, r.model_idx, r.dispatch_time,
             r.finish_time, r.tokens_out, bool(r.failed), bool(r.shed))
            for r in reqs]


def test_elastic_scenario_end_to_end(elastic_run):
    reqs, rb, m = _cell(elastic_run, "fused")
    assert m["scale_ups"] > 0                     # autoscaler fired
    assert m["peak_alive"] > (elastic_run.n_instances
                              - len(elastic_run.reserve_iids))
    assert m["shed"] > 0 and m["shed_rate"] > 0   # overload shed load
    assert m["n"] + m["shed"] + m["failed"] == len(reqs)
    prio = m["priorities"]
    # SLO-aware ordering: premium never sheds before the batch class
    assert prio[0]["shed"] <= prio[2]["shed"]
    assert prio[0]["slo_attainment"] >= prio[2]["slo_attainment"]
    # shed requests never reached an instance
    for r in reqs:
        if r.shed:
            assert r.instance is None and r.finish_time is None


def test_shed_disabled_policy_admits_everything(elastic_run):
    reqs, _, m = _cell(elastic_run, "fused", shed=False)
    assert m["shed"] == 0 and m["n"] + m["failed"] == len(reqs)


def test_elastic_parity_across_backends(elastic_run):
    """numpy == fused full-trajectory parity THROUGH autoscaler
    roster churn: controller decisions are deterministic functions of
    the telemetry trajectory, so identical assignments imply identical
    scale/shed timelines — the differential soak's contract extended to
    the elastic regime."""
    out = {}
    for be in ("numpy", "fused"):
        reqs, rb, m = _cell(elastic_run, be)
        assert m["scale_ups"] > 0 and m["shed"] > 0
        out[be] = (_trajectory(reqs),
                   (m["scale_ups"], m["scale_downs"], m["shed"]))
    assert out["numpy"] == out["fused"]


def test_no_recompile_on_autoscale_events(elastic_run):
    """Scale events flip the alive mask and reseed the device mirror
    (roster_reseed > 0) but must add ZERO XLA compiles: one program per
    pow2 R bucket, exactly."""
    # a distinct weight preset gets its own FusedHotPath (the runner is
    # cached on the bundle per config), so the compile count is clean
    reqs, rb, m = _cell(elastic_run, "fused", weights=PRESETS["quality"])
    assert m["scale_ups"] > 0
    st = rb._fused.stats
    assert st["roster_reseed"] > 0                # mask churn resynced
    buckets = {bucket_pow2(s) for s, _ in rb.compute_log}
    assert rb._fused.compile_count() == len(buckets)
