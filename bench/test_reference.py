"""The plain reference decision against the program, on the CPU.

On a short paper_13x4 segment with no decision time charged (so the
trajectory does not depend on the clock), the reference makes every
assignment that `decision_backend="numpy"` makes, window by window; and
through a whole run of the harness on the fused backend, with and
without the prefix-affinity term, its picks, estimates and
dead-reckoned state agree with the program's."""
import json

import numpy as np
import pytest

from bench import check, reference as ref
from bench.cell import decision_config
from bench.conftest import SEED, W_AFF


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
    wins = [check.window_inputs(c, fleet) for c in seen]
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


@pytest.mark.parametrize("affinity_weight", [0.0, W_AFF])
def test_reference_agrees_with_the_fused_program(drive, sessions,
                                                 affinity_weight):
    """With the affinity term on, on a session mix, the reference also
    finds the prefix hits that the program's picks found."""
    lines = []
    res, rec = drive(seconds=3.0, on=sessions if affinity_weight else None,
                     info=lines.append)
    prog = res["checks"]
    numbers = json.loads(lines[-1])
    assert res["attempted"] > (60 if affinity_weight else 100)
    assert res["failed"] == 0
    assert prog["decide_p99"]["value"] < 1e-5, prog
    assert prog["slot_miss"]["value"] == 0.0, prog
    assert prog["work_gap"]["value"] < 1e-5, prog
    if affinity_weight:
        assert numbers["hit_share"] > 0.05, numbers
    else:
        assert "hit_share" not in numbers


def test_hit_fraction_on_hand_built_sketches():
    """The leading run of matched blocks, in tokens, capped at the
    prompt's length: a run stops at the first miss, 0 never matches
    (not even an empty sketch slot), and a dead instance reads 0."""
    sig = np.zeros((4, ref.SIG_COLS), np.int32)
    sig[0, :3] = [11, 12, 13]          # three blocks cached on instance 0
    sig[1, :3] = [11, 99, 13]          # run stops at the miss: one block
    sig[3, :2] = [11, 12]              # as row 0, on a longer prompt
    len_in = np.array([40, 64, 16, 200], np.float32)
    sketch = np.zeros((3, 64), np.int32)
    sketch[0, :3] = [13, 12, 11]       # order within a row is immaterial
    sketch[2, :3] = [11, 12, 13]
    alive = np.array([True, True, False])
    hit = ref.hit_fraction(sig, len_in, sketch, alive)
    assert hit.dtype == np.float32
    want = np.array([[1.0, 0.0, 0.0],          # 48 tokens cut to 40
                     [16 / 64, 0.0, 0.0],
                     [0.0, 0.0, 0.0],          # all-0 signatures
                     [32 / 200, 0.0, 0.0]], np.float32)
    assert np.array_equal(hit, want), hit
    assert ref.hit_fraction(sig, np.zeros(4, np.float32), sketch,
                            alive)[0, 0] == 1.0     # capped at max(len, 1)


def test_signatures_follow_the_rolling_hash():
    """Column d hashes the first min(len, 16 (d + 1)) tokens; 0 where
    the prompt stops short of block d; and the program's own signatures
    read the same."""
    from repro.serving.affinity import prefix_signatures
    rng = np.random.default_rng(3)
    lens = np.array([1, 15, 16, 17, 40, 128, 130, 0])
    toks = np.zeros((len(lens), 130), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = rng.integers(1, 4096, n)
    sig = ref.signatures(toks, lens)

    def plain(row, n):
        h = 0
        for tok in row[:n]:
            h = (h * 2654435761 + int(tok) + 1) % 2 ** 32
        h = h - 2 ** 32 if h >= 2 ** 31 else h
        return h or 1

    for r, n in enumerate(lens):
        for d in range(ref.SIG_COLS):
            want = plain(toks[r], min(n, 16 * (d + 1), 128)) \
                if n > 16 * d else 0
            assert sig[r, d] == want, (r, d)
    assert np.array_equal(sig, prefix_signatures(toks, lens))


def test_teacher_forcing_counts_a_wrong_pick(small):
    """A window whose program picks differ in one place reads that
    miss; the scan goes on from the program's state, so the requests
    after it are judged against what the program saw."""
    cell, setup, mix = small
    rb, seen = _numpy_windows(setup, mix, n_req=120)
    fleet = check.fleet_of(setup.bundle, rb.sim.instances, setup.config)
    I = len(fleet.model_of)
    c = max(seen, key=lambda c: len(c["rows"]))
    w = check.window_inputs(c, fleet)
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
