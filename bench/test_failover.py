"""The failover cell at a tiny size on the CPU: its process module's
cycle of failures, the readers of its four metrics, and whole runs of
the harness in which the decision prices dead columns out, every
request is served exactly once, and the check still fails a planted
fault."""
import dataclasses

import numpy as np
import pytest

from bench import arrivals, cell as cellmod, faults
from bench.conftest import SEED

PROCESS = arrivals.process("failover")
# one cycle of the cell's, compressed so that a 3 s window on the CPU
# holds the failure, its victims' retries and the restart even on a
# loaded host (a window covers the simulated seconds the host clock lets
# it); the next cycle would start past the horizon
SHORT = {"fill_s": 4.0, "horizon_s": 12.0, "first_s": 0.5,
         "cycle_s": 8.0, "recover_s": 1.5, "slow_from_s": 2.0,
         "slow_until_s": 3.0}


def _mix(**over):
    mix = cellmod.load_cell("paper_failover").mix
    params = dict(mix.params, **over)
    return dataclasses.replace(mix, fill_s=float(params["fill_s"]),
                               horizon_s=float(params["horizon_s"]),
                               params=params)


def test_the_cycle_rotates_and_never_takes_both_72b_replicas():
    mix = _mix()
    ev = PROCESS.events(mix, 13)
    assert [e[0] for e in ev] == sorted(e[0] for e in ev)
    fails = [e for e in ev if e[1] == "fail"]
    assert len(fails) == int((mix.horizon_s - mix.fill_s - 2.0) // 120) + 1
    tiers = cellmod.fleet_tiers(cellmod.load_cell("paper_failover").config)
    tier_of = [t.name for t in tiers for _ in range(t.n_instances)]
    big = {i for i, n in enumerate(tier_of) if n.startswith("qwen2.5-72b")}
    assert big == {0, 1}
    lost = set()
    for k, (t, _, pos) in enumerate(fails):
        assert t == mix.fill_s + 2.0 + 120.0 * k
        assert pos == tuple((k + 5 * j) % 13 for j in range(3))
        assert not big <= set(pos)
        lost |= {tier_of[p] for p in pos}
        if k == 2:
            assert lost == set(tier_of)   # each tier lost a replica
    by_kind = {kind: [e for e in ev if e[1] == kind]
               for kind in PROCESS.KINDS}
    # events past horizon_s are left out: the last cycle may be cut
    assert len(fails) - 1 <= len(by_kind["recover"]) <= len(fails)
    for k, rec in enumerate(by_kind["recover"]):
        t0, _, pos = fails[k]
        assert rec[0] == t0 + 30.0 and rec[2] == pos
        slow, speed = by_kind["slow"][k], by_kind["speed"][k]
        assert (slow[0], speed[0]) == (t0 + 40.0, t0 + 60.0)
        assert slow[2] == speed[2] == ((k + 2) % 13, (k + 7) % 13)
        assert not set(slow[2]) & set(pos)


def test_schedule_refuses_a_sim_without_recovery():
    from repro.serving.cluster import ClusterSim
    from repro.serving.tiers import paper_pool_tiers
    tiers = paper_pool_tiers()
    sim = ClusterSim(tiers, sorted({t.model for t in tiers}))
    with pytest.raises(RuntimeError, match="exactly once"):
        PROCESS.schedule(sim, _mix(), np.random.default_rng(0))
    assert not sim._events


def _record(**over):
    base = dict(setup_s=1.0, window_s=2.0, decides=[], requests=[],
                stats={}, compiles_in_window=0, roster=13, index_rows=1,
                dims={}, device_kind="cpu")
    return cellmod.Record(**dict(base, **over))


def _req(attempt=0, hedges=0, served=True):
    from types import SimpleNamespace
    return SimpleNamespace(attempt=attempt, hedges=hedges, shed=False,
                           failed=not served, finish_time=1.0)


def test_the_readers_on_a_hand_built_record(monkeypatch):
    import bench.stages
    reqs = ([_req()] * 6 + [_req(attempt=1), _req(hedges=1),
                            _req(attempt=2, hedges=1),
                            _req(attempt=1, served=False)])
    rec = _record(requests=reqs,
                  stats={"calls": 10.0, "dead_cols": 26.0},
                  trace={"decide_windows": 4})
    read = cellmod.load_reader
    assert read("retry_share")(rec) == pytest.approx(2 / 9)
    assert read("hedge_share")(rec) == pytest.approx(2 / 9)
    assert read("dead_col_share")(rec) == pytest.approx(26 / 130)
    spans = {"rb.recovery": {"count": 7, "host_s": 0.002, "idle_s": 0.0,
                             "self_idle_s": 0.0}}
    monkeypatch.setattr(bench.stages, "reduce_dir",
                        lambda *a: {"spans": spans})
    assert read("recovery_ms_per_window")(rec) == pytest.approx(0.5)
    # nothing to read: no trace, no span, a runner without the counter
    spans.clear()
    assert read("recovery_ms_per_window")(rec) is None
    assert read("recovery_ms_per_window")(_record()) is None
    assert read("dead_col_share")(_record(stats={"calls": 3.0})) is None
    assert read("retry_share")(_record()) is None
    assert read("hedge_share")(_record()) is None


@pytest.fixture(scope="module")
def failover(small):
    """(cell, setup, mix): paper_failover on the paper world, with its
    cycle compressed (`SHORT`)."""
    cell = cellmod.load_cell("paper_failover")
    setup = dataclasses.replace(small[1], config=cell.config)
    mix = _mix(**SHORT)
    return dataclasses.replace(cell, mix=mix), setup, mix


def _run(drive, failover, fault=None):
    """One whole run of the failover cell: (result, record, the windows
    the check was handed)."""
    captured = []
    compare = cellmod.checking.compare

    def keep(fleet, windows, *a, **kw):
        captured.extend(windows)
        return compare(fleet, windows, *a, **kw)

    cellmod.checking.compare = keep
    try:
        res, rec = drive(on=failover, seconds=3.0, fault=fault)
    finally:
        cellmod.checking.compare = compare
    return res, rec, captured


@pytest.fixture(scope="module")
def sound(failover, drive):
    return _run(drive, failover)


def test_failover_run_serves_every_request_once(sound):
    res, rec, _ = sound
    assert res["failed"] == 0 and res["attempted"] == len(rec.requests)
    assert len({id(r) for r in rec.requests}) == len(rec.requests)
    retried = [r for r in rec.requests if r.attempt > 0]
    assert retried
    for r in retried:
        assert r.arrival > r.first_arrival
        assert r.finish_time is not None and not r.failed
    # the failure flipped the alive mask (fail and restart without a
    # compile: tests/test_recovery.py)
    assert rec.stats["roster_reseed"] >= 1
    assert rec.stats["dead_cols"] > 0


def test_the_check_passes_windows_with_dead_columns(sound, failover):
    res, _, captured = sound
    assert res["correct"], res["checks"]
    dead = [c for c in captured if not c["tel"]["alive"].all()]
    assert dead and len(dead) < len(captured)
    fleet = cellmod.checking.fleet_of(failover[1].bundle, _roster(failover),
                                      failover[0].config)
    numbers = cellmod.checking.compare(fleet, dead,
                                       np.random.default_rng(SEED), 10 ** 6)
    verdict = cellmod.checking.judge(numbers,
                                     cellmod.checking.load_limits(
                                         "paper_failover"))
    assert numbers["compared"] > 0
    assert all(v["ok"] for v in verdict.values()), verdict


def test_the_check_fails_an_altered_answer_under_failures(failover, drive):
    res, _, captured = _run(drive, failover, fault=faults.answer_altered)
    assert any(not c["tel"]["alive"].all() for c in captured)
    assert not res["correct"]
    assert (res["checks"]["decide_p99"]["value"]
            > res["checks"]["decide_p99"]["limit"])


def _roster(failover):
    from repro.serving.cluster import ClusterSim
    _, setup, _ = failover
    return ClusterSim(setup.tiers, setup.names).instances
