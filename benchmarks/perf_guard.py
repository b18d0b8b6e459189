"""CI perf-regression guard for the fused hot path.

Re-runs the hotpath smoke grid (REPRO_HOTPATH_SMOKE=1 — a subset of the
full grid, so every smoke row has a committed counterpart) and fails if
any backend's ``us_per_call`` regresses more than the tolerance against
the committed ``BENCH_hotpath.json`` baseline:

  python -m benchmarks.perf_guard

Since the `SchedulingPolicy` redesign the timed cells resolve through
the policy/engine API (`RouteBalance._decide_core` ->
`RouteBalancePolicy.assign` on the shared `ServingEngine`), so the
committed PR-4 baselines gate the refactor itself: the API seam must
not cost more than the tolerance. `_assert_engine_api` pins that
wiring — a future change that detaches the bench from the production
decision path fails the guard loudly instead of gating a dead code
path.

Only the **fused** rows gate (the production hot path this guard
protects); staged numpy rows print informationally — their Python
loops are far noisier under co-tenant load, and a regression there
doesn't ship. A failing cell is re-timed once (min of the two runs)
before it counts, since even min-of-N timing jitters tens of percent on
a busy box.

The tolerance (default 1.25 = 25%) is multiplicative and env-tunable
via ``REPRO_PERF_GUARD_TOL`` — absolute wall-clock differs across
machines, so CI boxes that are systematically slower than the box that
produced the committed artifact should raise it rather than delete the
guard. Getting *faster* than baseline never fails; rows with no
committed counterpart are reported and skipped.

A second gate covers the fault-tolerant lifecycle (PR 7): a fault-free
cell is timed with the recovery manager armed and disarmed, and the
armed run must stay within the same tolerance — the retry/hedge/
watchdog hooks are only allowed to cost when faults actually fire.

A third gate covers the Pallas decision megakernel (PR 9): on every
smoke cell the megakernel row must land within the tolerance of the
fused-XLA row *from the same run* — a relative same-box comparison, so
machine speed cancels out. A megakernel more than 25% slower than
fused-XLA fails CI.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile

TOL = float(os.environ.get("REPRO_PERF_GUARD_TOL", "1.25"))
REPO = pathlib.Path(__file__).resolve().parent.parent


def _time_smoke_grid() -> dict:
    from benchmarks import common
    common.discard_rows()
    from benchmarks import hotpath
    hotpath.main()
    with tempfile.NamedTemporaryFile("r", suffix=".json") as tmp:
        common.flush_json("hotpath_guard", tmp.name)
        rows = json.load(open(tmp.name))["rows"]
    return {r["name"]: r["us_per_call"] for r in rows}


def _assert_engine_api():
    """The timed grid must exercise the policy/engine path the
    production scheduler serves through."""
    from benchmarks import common  # noqa: F401  (puts src on sys.path)
    from repro.core import (POLICIES, RouteBalance, RouteBalancePolicy,
                            ServingEngine, make_policy)
    assert issubclass(RouteBalance, ServingEngine), \
        "RouteBalance detached from ServingEngine — guard would gate a " \
        "dead path"
    assert "routebalance" in POLICIES
    assert isinstance(make_policy("routebalance"), RouteBalancePolicy)


def _recovery_overhead_guard() -> bool:
    """Fault-free cells must not pay for the recovery hooks: one small
    chaos-world cell, empty fault schedule, timed armed vs disarmed
    (min-of-3 each; the sim is a single-thread Python loop, so
    wall-clock is the honest cost of the extra per-dispatch bookkeeping
    and the watchdog's periodic scan)."""
    import dataclasses
    import time

    from repro.core import RBConfig, RouteBalance
    from repro.serving.faults import chaos_world
    from repro.serving.recovery import RecoveryConfig

    sc = chaos_world()
    run = sc.build(dataset_n=200)
    bundle = run.bundle()
    run.scenario = dataclasses.replace(run.scenario, schedule=())

    def cell(recovery):
        run.recovery = recovery
        reqs = run.requests(100, seed=0)
        rb = RouteBalance(RBConfig(charge_compute=False), bundle,
                          run.tiers)
        t0 = time.perf_counter()
        m = run.run_cell(rb, reqs, seed=0)
        assert m["failed"] == 0 and m.get("retries", 0) == 0
        return time.perf_counter() - t0

    cell(None)                          # warm-up: compiles and caches
    off = min(cell(None) for _ in range(3))
    on = min(cell(RecoveryConfig()) for _ in range(3))
    ratio = on / off
    verdict = "ok" if ratio <= TOL else "REGRESSED"
    print(f"recovery hooks (fault-free cell): armed {on * 1e3:.1f} ms "
          f"vs disarmed {off * 1e3:.1f} ms ({ratio:.2f}x, "
          f"tol {TOL:.2f}x) {verdict}")
    return ratio <= TOL


def _affinity_disabled_guard() -> bool:
    """The prefix-affinity term must be free when disabled (<5% of
    decide time). Structurally: ``affinity_weight=0`` compiles the term
    out of the fused program and stages NO signature data — the sig
    args are (1, 1) dummies and the per-bucket staging sets carry no
    ``psig`` buffer. By measurement: the disabled runner's decide time
    must not exceed the enabled runner's (which does strictly more
    work — sig gathers, plane upload, in-graph hit matching) by more
    than a 5% noise floor. Absolute regressions of the disabled path
    against history are the main BENCH_hotpath gate's job (those
    committed baselines predate the affinity term, so they gate it)."""
    import time

    from benchmarks import common  # noqa: F401  (puts src on sys.path)
    from repro.core import RBConfig, RouteBalance
    from repro.serving.cluster import ClusterSim
    from repro.serving.scenarios import (get_scenario,
                                         randomize_prefix_state)

    run = get_scenario("session_chat").build(dataset_n=200)
    bundle = run.bundle()
    reqs = run.requests(64, seed=0)
    for r in reqs:
        r.arrival = 0.0
    rbs = {}
    for w in (0.0, 0.35):
        rb = RouteBalance(RBConfig(decision_backend="fused",
                                   affinity_weight=w,
                                   charge_compute=False),
                          bundle, run.tiers)
        sim = ClusterSim(run.tiers, run.names, seed=0)
        if w:
            randomize_prefix_state(sim, reqs[0].cols, seed=0)
        rb.sim = sim
        rb._decide_core(reqs[:32])          # warm-up: compile the bucket
        rbs[w] = rb
    fused_off = rbs[0.0]._fused
    assert fused_off._w_aff == 0.0
    assert all("psig" not in bufset for pair in
               fused_off._stage.values() for bufset in pair), \
        "disabled affinity must stage no signature data"
    assert fused_off._dummy_psig.shape == (1, 1)

    def t_of(rb):
        best = float("inf")
        for _ in range(7):
            t0 = time.perf_counter()
            rb._decide_core(reqs[:32])
            best = min(best, time.perf_counter() - t0)
        return best

    ratio = t_of(rbs[0.0]) / t_of(rbs[0.35])
    if ratio > 1.05:                        # re-time once to shed noise
        ratio = min(ratio, t_of(rbs[0.0]) / t_of(rbs[0.35]))
    verdict = "ok" if ratio <= 1.05 else "REGRESSED"
    print(f"affinity term: disabled decide at {ratio:.2f}x the enabled "
          f"runner's (tol 1.05x) {verdict}")
    return ratio <= 1.05


def _hierarchy_guard() -> bool:
    """The hierarchical wrapper must be ~free at one cell: a 1-cell
    balanced `HierarchicalScheduler` (cell telemetry mirror, digest
    loop, recovery router — the whole control plane) runs the same
    trace as the plain fused controller and must stay within 1.10x of
    its wall-clock (min-of-3, same-box relative comparison; re-timed
    once before failing). This gates the PR-10 seam: the per-cell
    views may not tax the single-controller configuration everyone
    runs by default."""
    import time

    from repro.core import RBConfig, RouteBalance
    from repro.serving.hierarchy import HierarchyConfig, build_scheduler
    from repro.serving.scenarios import get_scenario

    tol = 1.10
    run = get_scenario("cluster").build(dataset_n=200)
    bundle = run.bundle()
    cfg = RBConfig(charge_compute=False)
    hcfg = HierarchyConfig(n_cells=1, routing="balanced")

    def cell(hier):
        reqs = run.requests(120, seed=0)
        sched = (build_scheduler(cfg, bundle, run.tiers, hcfg) if hier
                 else RouteBalance(cfg, bundle, run.tiers))
        t0 = time.perf_counter()
        m = run.run_cell(sched, reqs, seed=0)
        assert m["failed"] == 0
        return time.perf_counter() - t0

    cell(False), cell(True)             # warm-up: compiles and caches
    flat = min(cell(False) for _ in range(3))
    hier = min(cell(True) for _ in range(3))
    ratio = hier / flat
    if ratio > tol:                     # re-time once to shed noise
        ratio = min(ratio, min(cell(True) for _ in range(3))
                    / min(cell(False) for _ in range(3)))
    verdict = "ok" if ratio <= tol else "REGRESSED"
    print(f"hierarchy (1-cell balanced vs flat fused): "
          f"{hier * 1e3:.1f} ms vs {flat * 1e3:.1f} ms "
          f"({ratio:.2f}x, tol {tol:.2f}x) {verdict}")
    return ratio <= tol


def _megakernel_guard(fresh: dict) -> bool:
    """The one-kernel decision must hold parity-or-better against the
    fused-XLA pipeline: for every smoke cell, the megakernel row's
    us_per_call stays within TOL of the fused row's **from the same
    timed run** (both backends share ambient machine conditions, so the
    ratio is far more stable than any absolute baseline). A failing
    grid is re-timed once before it counts."""

    def ratios(rows):
        out = {}
        for name, us in rows.items():
            if name.startswith("hotpath/megakernel_"):
                cell = name.split("megakernel_", 1)[1]
                f = rows.get(f"hotpath/fused_{cell}")
                if f:
                    out[cell] = us / f
        return out

    r = ratios(fresh)
    assert r, "smoke grid lost its megakernel rows"
    if max(r.values()) > TOL:           # re-time once to shed noise
        print("# megakernel over tolerance: re-timing once")
        rerun = ratios(_time_smoke_grid())
        r = {c: min(v, rerun.get(c, v)) for c, v in r.items()}
    for cell, ratio in sorted(r.items()):
        verdict = "ok" if ratio <= TOL else "REGRESSED"
        print(f"megakernel vs fused @ {cell}: {ratio:.2f}x "
              f"(tol {TOL:.2f}x) {verdict}")
    return max(r.values()) <= TOL


def main() -> int:
    _assert_engine_api()
    os.environ["REPRO_HOTPATH_SMOKE"] = "1"
    baseline_doc = json.loads((REPO / "BENCH_hotpath.json").read_text())
    from benchmarks import common
    # the KNN index scales with the dataset, so timings are only
    # comparable at the baseline's dataset size — refuse a silent
    # apples-to-oranges gate (a paper-scale baseline would make every
    # default-scale run pass, a small-scale one would fail every run)
    base_n = baseline_doc.get("n_dataset")
    if base_n is not None and base_n != common.N_DATASET:
        print(f"perf guard: committed baseline was produced at "
              f"REPRO_BENCH_DATASET={base_n}, this run uses "
              f"{common.N_DATASET} — set REPRO_BENCH_DATASET={base_n} "
              f"(or regenerate the baseline) before gating")
        return 1
    baseline = {r["name"]: r["us_per_call"]
                for r in baseline_doc["rows"]}

    fresh = _time_smoke_grid()
    if any(name in baseline and us / baseline[name] > TOL
           and "fused" in name for name, us in fresh.items()):
        print("# possible regression: re-timing once to shed noise")
        rerun = _time_smoke_grid()
        fresh = {name: min(us, rerun.get(name, us))
                 for name, us in fresh.items()}

    failures, missing = [], []
    for name, us in fresh.items():
        base = baseline.get(name)
        if base is None:
            missing.append(name)
            continue
        ratio = us / base
        gates = "fused" in name
        verdict = ("ok" if ratio <= TOL else
                   "REGRESSED" if gates else "slow (informational)")
        print(f"{name}: {us:.0f} us vs baseline {base:.0f} us "
              f"({ratio:.2f}x, tol {TOL:.2f}x) {verdict}")
        if gates and ratio > TOL:
            failures.append((name, round(ratio, 2)))
    if missing:
        print(f"# no committed baseline for {missing} (new cells pass)")
    if not _megakernel_guard(fresh):
        failures.append(("megakernel_vs_fused", "regression"))
    if not _recovery_overhead_guard():
        failures.append(("recovery_hooks_fault_free", "overhead"))
    if not _affinity_disabled_guard():
        failures.append(("affinity_term_disabled", "overhead"))
    if not _hierarchy_guard():
        failures.append(("hierarchy_1cell_vs_flat", "overhead"))
    if failures:
        print(f"PERF REGRESSION: {failures}")
        return 1
    print(f"# perf guard ok: fused cells within {TOL:.2f}x of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
