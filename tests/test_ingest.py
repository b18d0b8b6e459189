"""Zero-allocation host path: SoA ingest, staging-buffer reuse,
incremental telemetry deltas, and async double-buffered dispatch.

Five contracts of the host path:

  * **ingest** — `RequestColumns` mirrors the AoS request fields
    exactly (dtypes included), the memoized per-prompt embedding column
    is bitwise the per-batch encode it replaces, and `Request.budget`
    writes through to its column so post-ingest edits stay coherent;
  * **staging reuse** — the per-pow2(R)-bucket host staging buffers are
    double buffered: dispatching batch B must not corrupt batch A's
    still-unfetched `LazyDecision`, across same-bucket and
    cross-bucket sequences;
  * **delta telemetry** — `FusedHotPath._sync_state`'s dirty-row
    scatter must reproduce a from-scratch full reseed (the numpy
    reference's reseed-per-batch semantics) assignment-for-assignment,
    with the delta/carry arms the steady-state common case and full
    reseed reserved for roster-shape events and mostly-dirty batches;
  * **async dispatch** — deferring the result fetch to the dispatch
    point changes nothing observable: full cluster runs through an
    explicit fail/straggle/recover `FailureEvent` schedule land on the
    numpy reference's exact trajectories;
  * **fetch** — the runner starts one host transfer of each padded
    result at the launch and the fetch slices it in numpy: the same
    arrays as a device slice, bit for bit, and no program of its own.
"""
import numpy as np
import pytest

from repro.core import RBConfig, RouteBalance, make_requests, run_cell
from repro.core.hotpath import FusedHotPath
from repro.serving.cluster import ClusterSim
from repro.serving.request import RequestColumns, batch_columns
from repro.serving.scenarios import FailureEvent, randomize_telemetry
from repro.serving.workload import poisson_arrivals


def _loaded_sim(ctx, seed=9, kill_frac=0.0):
    return randomize_telemetry(
        ClusterSim(ctx["tiers"], ctx["names"], seed=0), seed, kill_frac)


def _batch(ctx, R=16, seed=5, with_budgets=True):
    reqs = make_requests(ctx["ds"], "test", np.zeros(R))
    if with_budgets:
        rng = np.random.default_rng(seed)
        budgets = np.where(rng.uniform(size=R) < 0.5,
                           rng.uniform(1e-5, 3e-4, R), np.nan)
        for r, b in zip(reqs, budgets):
            r.budget = None if np.isnan(b) else float(b)
    return reqs


def _runner(ctx, sim, **cfg_kw):
    """A private FusedHotPath (not the for_bundle cache — tests here
    need two independent runners against one telemetry view)."""
    return FusedHotPath(ctx["bundle"], sim.instances,
                        RBConfig(decision_backend="fused", **cfg_kw))


# -- SoA ingest ---------------------------------------------------------------

def test_request_columns_mirror_aos_fields(small_ctx):
    reqs = _batch(small_ctx, R=24, seed=3)
    cols = reqs[0].cols
    assert cols is not None and cols.n == 24
    for i, r in enumerate(reqs):
        assert r.cols is cols and r.row == i
        assert cols.len_in[i] == r.prompt.len_in
        if r.budget is None:
            assert np.isnan(cols.budget[i])
        else:
            assert cols.budget[i] == r.budget
        p = cols.prompt_row[i]
        n_tok = min(len(r.prompt.tokens), cols.tokens.shape[1])
        assert cols.tok_len[p] == n_tok
        np.testing.assert_array_equal(cols.tokens[p, :n_tok],
                                      r.prompt.tokens[:n_tok])
    # prompt deduplication: the token matrix has one row per unique
    # prompt object, not one per request
    assert len(cols.tokens) == len({id(r.prompt) for r in reqs})


def test_budget_edit_writes_through_to_column(small_ctx):
    reqs = _batch(small_ctx, R=4, with_budgets=False)
    cols = reqs[0].cols
    assert np.isnan(cols.budget[1])
    reqs[1].budget = 2.5e-4
    assert cols.budget[1] == 2.5e-4
    reqs[1].budget = None
    assert np.isnan(cols.budget[1])


def test_batch_columns_rejects_mixed_streams(small_ctx):
    s1 = _batch(small_ctx, R=6, with_budgets=False)
    s2 = _batch(small_ctx, R=6, with_budgets=False)
    cols, rows = batch_columns(s1[:3] + s2[:3])
    assert cols is None and rows is None
    cols, rows = batch_columns(s1[2:5])
    assert cols is s1[0].cols
    np.testing.assert_array_equal(rows, [2, 3, 4])
    assert batch_columns([]) == (None, None)


def test_ingest_embeddings_bitwise_match_batch_encode(small_ctx):
    from repro.estimators.embedding import pad_tokens
    enc = small_ctx["bundle"].encoder
    reqs = _batch(small_ctx, R=24, seed=7, with_budgets=False)
    cols = reqs[0].cols.ensure_embeddings(enc)
    toks = pad_tokens([r.prompt.tokens for r in reqs], enc.max_len)
    lens = np.array([min(len(r.prompt.tokens), enc.max_len)
                     for r in reqs])
    batch_emb = np.asarray(enc.encode(toks, lens))
    np.testing.assert_array_equal(cols.emb[cols.prompt_row], batch_emb)


def test_predict_prompts_gather_matches_encode_path(small_ctx):
    bundle = small_ctx["bundle"]
    reqs = _batch(small_ctx, R=12, with_budgets=False)
    Q1, L1 = bundle.predict_prompts(reqs)          # ingest gather path
    for r in reqs:                                 # strip -> legacy AoS
        r.cols, r.row = None, -1
    Q2, L2 = bundle.predict_prompts(reqs)
    np.testing.assert_array_equal(np.asarray(Q1), np.asarray(Q2))
    np.testing.assert_array_equal(np.asarray(L1), np.asarray(L2))


# -- staging-buffer reuse / async dispatch ------------------------------------

def test_staging_double_buffer_no_alias(small_ctx):
    """Write batch A, dispatch, overwrite the bucket with batch B (and a
    different bucket with C) while A is still in flight: every fetched
    result must equal an independent eager decide. R=13 and R=10 share
    the 16 bucket (forcing the flip); R=5 lands in the 8 bucket."""
    sim = _loaded_sim(small_ctx)
    fp = _runner(small_ctx, sim)
    ref = _runner(small_ctx, sim)
    enc = small_ctx["bundle"].encoder
    batches = [_batch(small_ctx, R=R, seed=R) for R in (13, 10, 5)]
    lazies = []
    for b in batches:                     # dispatch all, fetch nothing
        cols, rows = batch_columns(b)
        cols.ensure_embeddings(enc)
        lazies.append(fp.decide_cols(cols, rows, sim.tel))
    # telemetry never moved: first call reseeds, the rest carry
    assert fp.stats["full_reseed"] == 1 and fp.stats["carry"] == 2
    for b, lz in zip(batches, lazies):
        choice, l_chosen = lz.fetch()
        c_ref, l_ref = ref.decide(b, sim.tel)
        np.testing.assert_array_equal(choice, c_ref)
        np.testing.assert_array_equal(l_chosen, l_ref)
    # fetch is idempotent (diagnostics may re-read)
    again = lazies[0].fetch()
    np.testing.assert_array_equal(again[0], ref.decide(batches[0],
                                                       sim.tel)[0])


def test_async_dispatch_parity_through_failure_schedule(small_ctx):
    """Full cluster runs through an explicit fail -> straggle -> recover
    schedule: the async fused path (lazy fetch at the dispatch point)
    must land on the numpy reference's exact trajectories."""
    schedule = (FailureEvent(t=1.0, kind="fail", count=3),
                FailureEvent(t=2.5, kind="straggle", frac=0.25,
                             factor=3.0),
                FailureEvent(t=4.0, kind="recover", count=3))

    def cell(backend):
        reqs = make_requests(small_ctx["ds"], "test",
                             poisson_arrivals(12.0, 60, seed=11))
        rb = RouteBalance(RBConfig(decision_backend=backend,
                                   charge_compute=False),
                          small_ctx["bundle"], small_ctx["tiers"])
        m = run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs,
                     seed=0, schedule=schedule, schedule_seed=7)
        return [r.instance for r in reqs], m

    traj = {be: cell(be) for be in ("numpy", "fused")}
    assert traj["fused"][0] == traj["numpy"][0]
    for k in ("quality", "mean_e2e", "cost_per_req", "goodput"):
        assert traj["fused"][1][k] == pytest.approx(
            traj["numpy"][1][k], rel=1e-9), k


# -- incremental telemetry deltas ---------------------------------------------

def test_delta_scatter_reproduces_full_reseed(small_ctx):
    """After a handful of telemetry writes, the delta arm must make
    exactly the assignments a from-scratch full reseed makes (the
    numpy reference's reseed-per-batch contract)."""
    sim = _loaded_sim(small_ctx)
    tel = sim.tel
    fp = _runner(small_ctx, sim)
    fp.decide(_batch(small_ctx, R=16, seed=1), tel)   # seed the mirror
    assert fp.stats["full_reseed"] == 1
    for slot in (0, 3, 7):                            # a few dirty rows
        tel.write(slot, pending=123.0 + slot, batch=4, free=2,
                  ctx=900.0, queue=1, t=1.0)
    b2 = _batch(small_ctx, R=16, seed=2)
    c_delta, l_delta = fp.decide(b2, tel)
    assert fp.stats["delta_sync"] == 1
    assert fp.stats["delta_rows"] == 3
    c_ref, l_ref = _runner(small_ctx, sim).decide(b2, tel)
    np.testing.assert_array_equal(c_delta, c_ref)
    np.testing.assert_array_equal(l_delta, l_ref)


def test_delta_path_matches_staged_backends_per_batch(small_ctx):
    """Chained batches with telemetry churn between them: every fused
    decision off the delta-synced mirror equals the numpy decision off
    a fresh host read."""
    sim_f = _loaded_sim(small_ctx)
    rb_f = RouteBalance(RBConfig(decision_backend="fused"),
                        small_ctx["bundle"], small_ctx["tiers"])
    rb_f.sim = sim_f
    rb_n = RouteBalance(RBConfig(decision_backend="numpy"),
                        small_ctx["bundle"], small_ctx["tiers"])
    rb_n.sim = _loaded_sim(small_ctx)
    rng = np.random.default_rng(0)
    for step in range(4):
        batch = _batch(small_ctx, R=12, seed=100 + step)
        ids = {}
        for name, rb in (("fused", rb_f), ("numpy", rb_n)):
            instances, choice, _ = rb._decide_core(batch)
            ids[name] = [instances[int(i)].iid for i in choice]
        assert ids["fused"] == ids["numpy"], step
        slots = rng.choice(len(sim_f.instances), 4, replace=False)
        for sim in (sim_f, rb_n.sim):
            for slot in slots:                # same writes for every sim
                sim.tel.write(int(slot), pending=float(50 * step + slot),
                              batch=3, free=1, ctx=500.0, queue=0,
                              t=float(step))
    st = rb_f._fused.stats
    assert st["delta_sync"] >= 3              # the common case, not dead code
    assert st["full_reseed"] == 1


def test_roster_event_forces_full_reseed(small_ctx):
    """kill/revive bump `roster_version`; the mirror must full-reseed
    (the alive mask is device-resident) and keep avoiding dead slots."""
    sim = _loaded_sim(small_ctx)
    tel = sim.tel
    fp = _runner(small_ctx, sim)
    fp.decide(_batch(small_ctx, R=16, seed=1), tel)
    dead = sim.instances[2]
    dead.fail()
    assert not tel.alive[dead.slot]
    b2 = _batch(small_ctx, R=16, seed=2)
    choice, _ = fp.decide(b2, tel)
    assert fp.stats["full_reseed"] == 2 and fp.stats["delta_sync"] == 0
    assert dead.slot not in set(int(i) for i in choice)
    dead.recover(t=1.0)
    choice, _ = fp.decide(_batch(small_ctx, R=16, seed=3), tel)
    assert fp.stats["full_reseed"] == 3


def test_mostly_dirty_telemetry_reseeds_outright(small_ctx):
    """When more than half the roster is dirty the scatter would cost
    more than the re-upload — `_sync_state` reseeds instead."""
    sim = _loaded_sim(small_ctx)
    fp = _runner(small_ctx, sim)
    fp.decide(_batch(small_ctx, R=8, seed=1), sim.tel)
    sim.tel.mark_all_dirty()
    b = _batch(small_ctx, R=8, seed=2)
    c, _ = fp.decide(b, sim.tel)
    assert fp.stats["full_reseed"] == 2 and fp.stats["delta_sync"] == 0
    np.testing.assert_array_equal(
        c, _runner(small_ctx, sim).decide(b, sim.tel)[0])


def test_swapped_telemetry_object_forces_reseed(small_ctx):
    """Swapping in a different sim's TelemetryArrays (rb.sim = ... with
    no attach()) must full-reseed even though the new view's counters
    can look 'older' than the mirror's — freshness is keyed to the
    telemetry object's identity."""
    sim1 = _loaded_sim(small_ctx, seed=1)
    sim2 = _loaded_sim(small_ctx, seed=2)
    fp = _runner(small_ctx, sim1)
    b = _batch(small_ctx, R=8, seed=1)
    fp.decide(b, sim1.tel)
    c, _ = fp.decide(b, sim2.tel)             # same shapes, new object
    assert fp.stats["full_reseed"] == 2 and fp.stats["carry"] == 0
    np.testing.assert_array_equal(
        c, _runner(small_ctx, sim2).decide(b, sim2.tel)[0])


def test_reattach_with_queued_requests_falls_back_to_aos(small_ctx):
    """attach() clears the waiting queue's row ring; requests queued
    from before the re-attach have no rows in it, so the scheduler must
    marshal them AoS rather than pair them with the wrong columns."""
    rb = RouteBalance(RBConfig(), small_ctx["bundle"],
                      small_ctx["tiers"])
    rb.attach(_loaded_sim(small_ctx, seed=1))
    reqs = _batch(small_ctx, R=4, with_budgets=False)
    for r in reqs:
        rb.enqueue(r, 0.0)
    assert rb._wait_cols is reqs[0].cols
    rb.attach(_loaded_sim(small_ctx, seed=2))  # waiting is non-empty
    assert rb._wait_cols is False
    instances, choice, _ = rb._decide_core(reqs)   # still decides fine
    assert len(choice) == len(reqs)


def test_ephemeral_columns_do_not_restamp_stream_requests(small_ctx):
    """A mixed batch (stream + columnless requests) reaching the fused
    fallback builds ephemeral columns WITHOUT restamping the stream
    requests — their budget write-through target must stay the stream
    column."""
    stream = _batch(small_ctx, R=6, with_budgets=False)
    scols = stream[0].cols
    loner = _batch(small_ctx, R=1, with_budgets=False)[0]
    loner.cols, loner.row = None, -1
    sim = _loaded_sim(small_ctx)
    fp = _runner(small_ctx, sim)
    mixed = stream[:3] + [loner]
    choice, _ = fp.decide(mixed, sim.tel)
    assert len(choice) == 4
    assert all(r.cols is scols and r.row == i
               for i, r in enumerate(stream))
    stream[1].budget = 3e-4                    # write-through intact
    assert scols.budget[1] == 3e-4


def test_dirty_row_tracking(small_ctx):
    """TelemetryArrays stamps: dirty_rows(since) returns exactly the
    rows written after `since`, and mark_all_dirty stamps everything."""
    sim = ClusterSim(small_ctx["tiers"], small_ctx["names"], seed=0)
    tel = sim.tel
    v0 = tel.version
    assert len(tel.dirty_rows(v0)) == 0
    tel.write(5, pending=1.0, batch=1, free=1, ctx=10.0, queue=0, t=0.1)
    tel.write(2, pending=2.0, batch=1, free=1, ctx=10.0, queue=0, t=0.2)
    np.testing.assert_array_equal(tel.dirty_rows(v0), [2, 5])
    v1 = tel.version
    assert len(tel.dirty_rows(v1)) == 0
    r0 = tel.roster_version
    tel.kill(3)
    assert tel.roster_version == r0 + 1
    tel.revive(3, t=0.5)
    assert tel.roster_version == r0 + 2
    assert 3 in tel.dirty_rows(v1)                 # revive rewrites row 3
    tel.mark_all_dirty()
    assert len(tel.dirty_rows(v1)) == len(tel.alive)


# -- spans and counters of the runner -----------------------------------------

# host arrays a window hands the device: the four staging buffers (emb,
# row_valid, budgets, len_in) are numpy arguments of the jitted step;
# the affinity dummies are device-resident; a reseed or delta sync
# uploads one telemetry block, a carry none; the alive mask goes up
# again only on the first sync and when the roster moved
CARRY_UPLOADS = 4
DELTA_UPLOADS = CARRY_UPLOADS + 1
RESEED_UPLOADS = DELTA_UPLOADS
ALIVE_UPLOADS = 1


def test_host_seconds_are_stage_plus_telemetry(small_ctx):
    """`host_s` keeps its meaning (staging and telemetry sync): the sum
    of the two spans it is made of; the fetch's wait and copy land in
    `device_s` and `sync_s`."""
    sim = _loaded_sim(small_ctx)
    fp = _runner(small_ctx, sim)
    for R in (5, 9, 16):
        fp.decide(_batch(small_ctx, R=R, seed=R), sim.tel)
    st = fp.stats
    assert st["calls"] == 3
    for key in ("stage_s", "telemetry_s", "dispatch_s", "device_s",
                "sync_s"):
        assert st[key] > 0.0, key
    assert st["host_s"] == pytest.approx(st["stage_s"] + st["telemetry_s"],
                                         rel=1e-12)


def test_uploads_count_the_host_arrays_of_each_window(small_ctx):
    """A reseed or delta window uploads 5 host arrays, a carry window 4,
    and the first sync the alive mask besides; the step itself is
    handed the four staging buffers in every arm."""
    sim = _loaded_sim(small_ctx)
    tel = sim.tel
    fp = _runner(small_ctx, sim)
    handed = []
    step = fp._step

    def counting_step(*args):
        handed.append(sum(isinstance(a, np.ndarray) for a in args))
        return step(*args)

    fp._step = counting_step
    first = RESEED_UPLOADS + ALIVE_UPLOADS
    fp.decide(_batch(small_ctx, R=8, seed=1), tel)          # reseed
    assert fp.stats["uploads"] == first
    tel.write(4, pending=10.0, batch=2, free=1, ctx=300.0, queue=0, t=1.0)
    fp.decide(_batch(small_ctx, R=8, seed=2), tel)          # delta
    assert fp.stats["uploads"] == first + DELTA_UPLOADS
    fp.decide(_batch(small_ctx, R=3, seed=3), tel)          # carry
    assert fp.stats["uploads"] == first + DELTA_UPLOADS + CARRY_UPLOADS
    assert (fp.stats["full_reseed"], fp.stats["delta_sync"],
            fp.stats["carry"]) == (1, 1, 1)
    assert handed == [CARRY_UPLOADS] * 3


def test_dirty_rows_are_counted_before_the_mostly_dirty_rule(small_ctx):
    """Each sync that reads `tel.dirty_rows` counts once, with every
    dirty row it found, whether it then scatters or reseeds."""
    sim = _loaded_sim(small_ctx)
    tel = sim.tel
    n = len(sim.instances)
    fp = _runner(small_ctx, sim)
    b = _batch(small_ctx, R=8, seed=1)
    fp.decide(b, tel)                     # first sync: no read, reseed
    assert (fp.stats["dirty_checks"], fp.stats["dirty_rows_seen"]) == (0, 0)
    for slot in (1, 5, 6):
        tel.write(slot, pending=5.0, batch=1, free=1, ctx=100.0, queue=0,
                  t=1.0)
    fp.decide(b, tel)                     # 3 dirty rows: delta
    assert (fp.stats["dirty_checks"], fp.stats["dirty_rows_seen"]) == (1, 3)
    tel.mark_all_dirty()
    fp.decide(b, tel)                     # all dirty: reseed outright
    assert (fp.stats["dirty_checks"],
            fp.stats["dirty_rows_seen"]) == (2, 3 + n)
    fp.decide(b, tel)                     # nothing written: carry
    assert (fp.stats["dirty_checks"],
            fp.stats["dirty_rows_seen"]) == (3, 3 + n)
    assert (fp.stats["full_reseed"], fp.stats["delta_sync"],
            fp.stats["carry"]) == (2, 1, 1)


COUNTERS = ("full_reseed", "delta_sync", "delta_rows", "carry",
            "roster_reseed", "uploads", "dirty_checks", "dirty_rows_seen")


def _counter_sequence(ctx):
    """Reseed, carry, delta, roster event, mostly dirty: the counters
    after each decision."""
    sim = _loaded_sim(ctx)
    tel = sim.tel
    fp = _runner(ctx, sim)
    seen = []

    def go(seed):
        fp.decide(_batch(ctx, R=8, seed=seed), tel)
        seen.append(tuple(fp.stats[k] for k in COUNTERS))

    go(1)
    go(2)
    tel.write(3, pending=7.0, batch=1, free=1, ctx=50.0, queue=0, t=1.0)
    go(3)
    sim.instances[2].fail()
    go(4)
    tel.mark_all_dirty()
    go(5)
    return seen


@pytest.mark.parametrize("profiled", [False, True])
def test_spans_leave_the_counters_as_they_were(small_ctx, tmp_path,
                                               profiled):
    """With or without a profiler session the counters read the same,
    decision by decision, as the sync rules dictate."""
    import jax
    if profiled:
        jax.profiler.start_trace(str(tmp_path))
    try:
        seen = _counter_sequence(small_ctx)
    finally:
        if profiled:
            jax.profiler.stop_trace()
    n = len(_loaded_sim(small_ctx).instances)
    R, D, C, A = RESEED_UPLOADS, DELTA_UPLOADS, CARRY_UPLOADS, ALIVE_UPLOADS
    # (full, delta, rows, carry, roster, uploads, checks, seen)
    assert seen == [(1, 0, 0, 0, 0, R + A, 0, 0),
                    (1, 0, 0, 1, 0, R + A + C, 1, 0),
                    (1, 1, 1, 1, 0, R + A + C + D, 2, 1),
                    (2, 1, 1, 1, 1, 2 * (R + A) + C + D, 2, 1),
                    (3, 1, 1, 1, 1, 3 * R + 2 * A + C + D, 3, 1 + n)]


# -- the telemetry block: the mirror after each sync arm ----------------------

def _padded(fp, x, fill=0):
    n = len(x)
    out = np.full(fp._Itot, fill, np.asarray(x).dtype)
    out[:n] = x
    return out


def _arm_untouched(sim):
    pass


def _arm_delta(sim):
    for slot in (0, 4):
        sim.tel.write(slot, pending=77.0 + slot, batch=3, free=2, ctx=640.0,
                      queue=0, t=1.0)


def _arm_roster(sim):
    sim.instances[3].fail()


def _arm_all_dirty(sim):
    sim.tel.pending[:] += 1.5
    sim.tel.mark_all_dirty()


# (arm, touch the sim before the decision, counter it bumps, uploads)
ARMS = [("first", _arm_untouched, "full_reseed",
         RESEED_UPLOADS + ALIVE_UPLOADS),
        ("delta", _arm_delta, "delta_sync", DELTA_UPLOADS),
        ("carry", _arm_untouched, "carry", CARRY_UPLOADS),
        ("roster", _arm_roster, "roster_reseed",
         RESEED_UPLOADS + ALIVE_UPLOADS),
        ("all_dirty", _arm_all_dirty, "full_reseed", RESEED_UPLOADS)]


@pytest.mark.parametrize("arm,touch,counter,uploads", ARMS,
                         ids=[a[0] for a in ARMS])
def test_each_sync_arm_leaves_the_mirror_a_padded_host_read(
        small_ctx, arm, touch, counter, uploads):
    """After a decision in each sync arm, the donated mirror is the
    padded host read of `tel` bit for bit, the device alive mask is
    `tel.alive` padded with False, only the reseed and delta arms
    upload a telemetry block, and no arm compiles."""
    sim = _loaded_sim(small_ctx)
    tel = sim.tel
    fp = _runner(small_ctx, sim)
    blocks = []
    step = fp._step

    def keep(*args):
        blocks.append(args[9])
        return step(*args)

    keep._cache_size = step._cache_size      # compile_count reads it
    fp._step = keep
    if arm != "first":
        fp.decide(_batch(small_ctx, R=8, seed=1), tel)
    compiled = fp.compile_count()
    before = dict(fp.stats)
    touch(sim)
    fp.decide(_batch(small_ctx, R=8, seed=2), tel)
    assert fp.stats[counter] == before.get(counter, 0) + 1
    assert fp.stats["uploads"] - before.get("uploads", 0) == uploads
    for got, x in zip(fp._state, (tel.pending, tel.batch, tel.free,
                                  tel.ctx)):
        want = _padded(fp, np.asarray(x, np.float32))
        np.testing.assert_array_equal(_bits(np.asarray(got)), _bits(want))
    np.testing.assert_array_equal(np.asarray(fp._alive_dev),
                                  _padded(fp, tel.alive, fill=False))
    assert not isinstance(blocks[-1], np.ndarray)
    assert (blocks[-1] is fp._noop_block) == (arm == "carry")
    assert fp.compile_count() == max(compiled, 1) == 1


def test_step_keeps_the_argument_and_output_positions(small_ctx):
    """What the benchmark's planted faults read of `_step`: argument 1
    is the window's row_valid, argument 8 the alive mask, and the ten
    outputs come as (choice, est_T, l_chosen, d, b, free, ctx, d1, b1,
    f1)."""
    sim = _loaded_sim(small_ctx, kill_frac=0.25)
    tel = sim.tel
    fp = _runner(small_ctx, sim)
    R = 11
    cols, rows = batch_columns(_batch(small_ctx, R=R, seed=3))
    cols.ensure_embeddings(small_ctx["bundle"].encoder)
    seen = {}
    step = fp._step

    def keep(*args):
        seen["args"] = args
        seen["out"] = step(*args)
        return seen["out"]

    fp._step = keep
    choice, l_chosen = fp.decide_cols(cols, rows, tel).fetch()
    args, out = seen["args"], seen["out"]
    Rb = len(args[1])
    assert len(args) == 12 and len(out) == 10
    np.testing.assert_array_equal(np.asarray(args[1]),
                                  np.arange(Rb) < R)          # row_valid
    np.testing.assert_array_equal(np.asarray(args[8]),
                                  _padded(fp, tel.alive, fill=False))
    c, est, lc = (np.asarray(o) for o in out[:3])
    np.testing.assert_array_equal(c[:R], choice)
    np.testing.assert_array_equal(lc[:R].astype(np.float64), l_chosen)
    assert est.shape == (Rb,) and np.all(np.isfinite(est[:R]))
    assert np.all(est[:R] > 0.0)
    d, b, free, ctx = (np.asarray(o) for o in out[3:7])
    for got, x in zip((d, b, free, ctx), (tel.pending, tel.batch, tel.free,
                                          tel.ctx)):
        np.testing.assert_array_equal(got,
                                      _padded(fp, np.asarray(x, np.float32)))
    d1, b1, f1 = (np.asarray(o) for o in out[7:10])
    # dead reckoning: each pick adds its l_chosen to the instance's
    # pending work and takes a free slot where one was left
    np.testing.assert_allclose(
        d1 - d, np.bincount(choice, weights=l_chosen,
                            minlength=fp._Itot), rtol=1e-5, atol=1e-2)
    assert np.all(f1 <= free) and np.all(b1 >= b)
    np.testing.assert_array_equal(free - f1 > 0,
                                  (np.bincount(choice, minlength=fp._Itot)
                                   > 0) & (free > 0))


# -- the fetch: one padded transfer per result, sliced on the host ------------

def _decide_capturing(fp, cols, rows, tel):
    """`decide_cols`, also returning the step's padded outputs."""
    seen = {}
    step = fp._step

    def keep(*args):
        seen["out"] = step(*args)
        return seen["out"]

    fp._step = keep
    try:
        lz = fp.decide_cols(cols, rows, tel)
    finally:
        fp._step = step
    return lz, seen["out"]


def _bits(x):
    return np.ascontiguousarray(x).view(np.uint8)


@pytest.mark.parametrize("backend,R", [
    *(pytest.param("fused", R, id=str(R)) for R in (1, 5, 8, 13, 16)),
    *(pytest.param("megakernel", R, id=f"megakernel-{R}") for R in (5, 13))])
def test_fetch_is_the_device_slice_bit_for_bit(small_ctx, backend, R):
    """`fetch()` returns fresh, writable int64 choice and float64
    l_chosen equal bit for bit to slicing the step's outputs on the
    device; a second fetch returns the same objects; each window starts
    two device-to-host transfers. The megakernel runner fetches the
    same way."""
    sim = _loaded_sim(small_ctx)
    fp = FusedHotPath(small_ctx["bundle"], sim.instances,
                      RBConfig(decision_backend=backend))
    cols, rows = batch_columns(_batch(small_ctx, R=R, seed=R))
    cols.ensure_embeddings(small_ctx["bundle"].encoder)
    for window in (1, 2):
        lz, out = _decide_capturing(fp, cols, rows, sim.tel)
        assert fp.stats["d2h"] == 2 * window
        choice, l_chosen = lz.fetch()
        want_c = np.asarray(out[0][:R], np.int64)
        want_l = np.asarray(out[2][:R], np.float64)
        assert choice.dtype == np.int64 and choice.shape == (R,)
        assert l_chosen.dtype == np.float64 and l_chosen.shape == (R,)
        np.testing.assert_array_equal(_bits(choice), _bits(want_c))
        np.testing.assert_array_equal(_bits(l_chosen), _bits(want_l))
        for a in (choice, l_chosen):
            assert a.flags.writeable and a.flags.owndata
        again = lz.fetch()
        assert again[0] is choice and again[1] is l_chosen
        assert fp.stats["d2h"] == 2 * window


@pytest.mark.parametrize("warm,new", [(5, 7), (9, 11), (17, 27)])
def test_a_new_size_in_a_warm_bucket_compiles_nothing(small_ctx, warm,
                                                      new):
    """Once a pow2 bucket's step is compiled, deciding and fetching
    another batch size in that bucket compiles no program: the fetch
    slices on the host and launches nothing on the device."""
    import jax.monitoring as monitoring
    sim = _loaded_sim(small_ctx)
    fp = _runner(small_ctx, sim)
    fp.decide(_batch(small_ctx, R=warm, seed=warm), sim.tel)
    compiled = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(listen)
    try:
        choice, _ = fp.decide(_batch(small_ctx, R=new, seed=new), sim.tel)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert choice.shape == (new,)
    assert compiled == []
