"""The plain reference decision against the program, on the CPU.

On a short paper_13x4 segment with no decision time charged (so the
trajectory does not depend on the clock), the reference makes every
assignment that `decision_backend="numpy"` makes, window by window; and
through a whole run of the harness on the fused backend, its picks,
estimates and dead-reckoned state agree with the program's."""
import numpy as np
import pytest

from bench import check, reference as ref
from bench.cell import decision_config
from bench.conftest import SEED


def _numpy_windows(setup, mix, n_req=300):
    import dataclasses
    from repro.core import RouteBalance, make_requests, run_cell
    from bench import arrivals
    cfg = dataclasses.replace(decision_config(setup.config),
                              decision_backend="numpy",
                              charge_compute=False)
    rb = RouteBalance(cfg, setup.bundle, setup.tiers)
    rng = np.random.default_rng(SEED % 2 ** 32)
    t = arrivals.arrivals(mix, rng)[:n_req]
    reqs = make_requests(setup.dataset, "test", t,
                         budgets=arrivals.budgets(mix, len(t), rng),
                         encoder=setup.bundle.encoder)
    seen = []
    assign = rb.policy.assign

    def spy(view, sim):
        tel = sim.tel
        snap = {k: np.array(getattr(tel, k)) for k in
                ("pending", "batch", "free", "ctx", "alive")}
        res = assign(view, sim)
        seen.append({"cols": view.cols, "rows": np.array(view.rows),
                     "tel": snap, "choice": res.fetch()[0]})
        return res

    rb.policy.assign = spy
    m = run_cell(rb, setup.tiers, setup.names, reqs)
    assert m["failed"] == 0
    return rb, seen


def test_reference_makes_the_numpy_backends_assignments(small):
    cell, setup, mix = small
    rb, seen = _numpy_windows(setup, mix)
    fleet = check.fleet_of(setup.bundle, rb.sim.instances, setup.config)
    I = len(fleet.model_of)
    wins = [check.window_inputs(c, I) for c in seen]
    qual, leng = ref.knn_labels(np.concatenate([w.emb for w in wins]),
                                fleet)
    at = n = 0
    budgeted = 0
    for w, c in zip(wins, seen):
        R = w.emb.shape[0]
        dec = ref.decide(fleet, w, qual[at:at + R], leng[at:at + R])
        at += R
        n += R
        budgeted += int(np.isfinite(w.budget).sum())
        assert np.array_equal(dec.pick, c["choice"]), (w, dec.pick,
                                                        c["choice"])
    assert n == 300 and budgeted > 30 and len(wins) > 50


def test_reference_agrees_with_the_fused_program(drive):
    res, rec = drive(seconds=3.0)
    prog = res["checks"]
    assert res["attempted"] > 100 and res["failed"] == 0
    assert prog["decide_p99"]["value"] < 1e-5, prog
    assert prog["slot_miss"]["value"] == 0.0, prog
    assert prog["work_gap"]["value"] < 1e-5, prog


def test_teacher_forcing_counts_a_wrong_pick(small):
    """A window whose program picks differ in one place reads that
    miss; the scan goes on from the program's state, so the requests
    after it are judged against what the program saw."""
    cell, setup, mix = small
    rb, seen = _numpy_windows(setup, mix, n_req=120)
    fleet = check.fleet_of(setup.bundle, rb.sim.instances, setup.config)
    I = len(fleet.model_of)
    c = max(seen, key=lambda c: len(c["rows"]))
    w = check.window_inputs(c, I)
    qual, leng = ref.knn_labels(w.emb, fleet)
    picks = np.array(c["choice"])
    picks[0] = (picks[0] + 1) % I
    dec = ref.decide(fleet, w, qual, leng, picks)
    assert dec.pick[0] != picks[0]
    assert 1 <= int((dec.pick != picks).sum()) < len(picks)
    assert np.array_equal(ref.decide(fleet, w, qual, leng,
                                     np.array(c["choice"])).pick,
                          c["choice"])


@pytest.mark.parametrize("precision", ["highest", "high"])
def test_cross_term_precisions(precision):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(4, 128)).astype(np.float32)
    x = rng.normal(size=(64, 128)).astype(np.float32)
    exact = 2.0 * q.astype(np.float64) @ x.T.astype(np.float64)
    err = np.abs(ref.cross_term(q, x, precision) - exact).max()
    if precision == "highest":
        assert err < 1e-4
    else:
        assert 1e-4 < err < 1e-1
