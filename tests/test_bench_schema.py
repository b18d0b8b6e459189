"""Pin the BENCH_*.json artifact schema so perf trajectories stay
machine-comparable across PRs: `benchmarks.common.csv_row` /
`flush_json` produce {module, n_req_per_cell, rows[...]}, each row
{name, us_per_call, derived, <parsed k=v floats>}. The committed
BENCH_hotpath.json, BENCH_sweep.json, BENCH_frontier.json and
BENCH_ladder.json must conform — the sweep must cover the frontier
grid the fused-by-default graduation relied on, the frontier must
carry the policy/deployment/per-tenant columns of the SchedulingPolicy
redesign, and the ladder must show the §6.3 separation (serial
as-published deployments collapsing under load while the equalized
concurrent arms hold) through the one engine."""
import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

TOP_KEYS = {"module", "n_req_per_cell", "rows"}
ROW_KEYS = {"name", "us_per_call", "derived"}


def _load(name):
    p = REPO / name
    assert p.exists(), f"{name} not committed"
    return json.loads(p.read_text())


def _check_schema(doc, module):
    assert TOP_KEYS <= set(doc), doc.keys()
    assert doc["module"] == module
    assert isinstance(doc["n_req_per_cell"], int)
    assert doc["rows"], "no rows"
    for row in doc["rows"]:
        assert ROW_KEYS <= set(row), row
        assert isinstance(row["name"], str) and row["name"]
        assert isinstance(row["us_per_call"], float)
        assert row["us_per_call"] >= 0
        assert isinstance(row["derived"], str)
        # every k=v pair in derived must be surfaced as a parsed field
        for part in row["derived"].split(";"):
            if "=" in part:
                k = part.split("=", 1)[0].strip()
                assert k in row, f"unparsed derived field {k!r}"


def test_csv_row_flush_json_roundtrip(tmp_path, capsys):
    from benchmarks.common import csv_row, discard_rows, flush_json
    discard_rows()
    csv_row("unit/cell_a", 12.5, "speedup=2.00x;agree=1.000;note=hi")
    csv_row("unit/cell_b", 7.0, "p99_e2e=1.234")
    out = tmp_path / "BENCH_unit.json"
    flush_json("unit", str(out))
    doc = json.loads(out.read_text())
    _check_schema(doc, "unit")
    assert len(doc["rows"]) == 2
    a, b = doc["rows"]
    assert a["speedup"] == 2.0          # "x" suffix stripped to float
    assert a["agree"] == 1.0
    assert a["note"] == "hi"            # non-numeric kept verbatim
    assert b["p99_e2e"] == 1.234
    # buffer reset: a second flush writes nothing new
    flush_json("unit", str(out))
    assert json.loads(out.read_text())["rows"] == []


BREAKDOWN_COLS = ("host_us", "stage_us", "dispatch_us", "device_us",
                  "sync_us")


def test_bench_kernels_artifact_schema_and_headlines():
    """The kernel microbench artifact: the decision (R, I) grid carries
    megakernel / fused-XLA / staged columns at exact assignment
    agreement, the megakernel holds parity-or-better against fused-XLA
    (the perf_guard gate's committed counterpart) and clearly beats the
    staged pipeline."""
    doc = _load("BENCH_kernels.json")
    _check_schema(doc, "kernels")
    rows = doc["rows"]
    decision = [r for r in rows if r["name"].startswith("kernels/decision_R")]
    assert len(decision) >= 4, [r["name"] for r in decision]
    assert any(r["name"].endswith("_I128") for r in decision)
    for r in decision:
        for col in ("megakernel_us", "fused_us", "staged_us",
                    "per_req_us", "vs_fused", "vs_staged", "agree"):
            assert col in r, f"{r['name']} missing {col}"
        assert r["agree"] == 1.0, r["name"]
        # headline gate (mirrors perf_guard._megakernel_guard): the
        # one-kernel decision is no more than 25% slower than the
        # fused-XLA pipeline on any committed cell...
        assert r["megakernel_us"] <= 1.25 * r["fused_us"], r["name"]
        # ...and well ahead of the staged per-stage pipeline
        assert r["vs_staged"] >= 1.3, r["name"]
    # the historical hot-spot rows survived the rework
    names = {r["name"] for r in rows}
    assert {"kernels/scoring_loop_I13", "kernels/knn_topk_pallas",
            "kernels/embed_knn_B16"} <= names, names
    knn = next(r for r in rows if r["name"] == "kernels/knn_topk_pallas")
    assert knn["allclose_err"] <= 1e-4


def test_bench_hotpath_artifact_schema():
    doc = _load("BENCH_hotpath.json")
    _check_schema(doc, "hotpath")
    fused = [r for r in doc["rows"] if "fused" in r["name"]]
    assert fused, "hotpath artifact lost its fused rows"
    assert all(r.get("agree") == 1.0 for r in fused)
    for r in fused:
        # host/stage/device/sync timing breakdown (PR 4): present,
        # nonnegative, and the host side of a fused call stays under a
        # millisecond — the zero-allocation ingest contract
        for col in BREAKDOWN_COLS:
            assert col in r, f"{r['name']} missing {col}"
            assert r[col] >= 0
        assert r["host_us"] < 1000, \
            f"{r['name']}: host path {r['host_us']}us"
        # the paper cell: per-batch decision <= the paper's ~32 ms
        # headline at R<=64 on the 13-instance pool
        R = int(r["name"].split("_R")[1].split("_")[0])
        if R <= 64 and r["name"].endswith("_I13"):
            assert r["us_per_call"] <= 32_000, r["name"]
    # the Pallas decision megakernel rows: every fused cell has a
    # megakernel counterpart at exact agreement and parity-or-better
    # latency (the committed face of perf_guard's 1.25x gate)
    mega = {r["name"]: r for r in doc["rows"]
            if r["name"].startswith("hotpath/megakernel_")}
    assert mega, "hotpath artifact lost its megakernel rows"
    for f in fused:
        cell = f["name"].split("fused_", 1)[1]
        m = mega.get(f"hotpath/megakernel_{cell}")
        assert m is not None, f"no megakernel row for {cell}"
        assert m["agree"] == 1.0, cell
        assert "vs_fused" in m and m["vs_fused"] > 0
        assert m["us_per_call"] <= 1.25 * f["us_per_call"], cell


def test_bench_sweep_artifact_schema_and_grid():
    doc = _load("BENCH_sweep.json")
    _check_schema(doc, "sweep")
    rows = doc["rows"]
    scenes, weights, loads = set(), set(), set()
    for r in rows:
        # sweep/<scene>_<weight>_x<scale>
        body = r["name"].split("/", 1)[1]
        stem, scale = body.rsplit("_x", 1)
        scene, weight = stem.rsplit("_", 1)
        scenes.add(scene)
        weights.add(weight)
        loads.add(float(scale))
        for col in (("lam", "I", "q", "p50_e2e", "p99_e2e", "cost",
                     "tput", "goodput", "decide_ms_per_req",
                     "parity_np", "full_reseeds", "delta_syncs",
                     "carries") + BREAKDOWN_COLS):
            assert col in r, f"{r['name']} missing {col}"
        # scenario streams are tenant-stamped: per-TenantSpec SLO
        # columns (PR 5) ride on every cell row
        assert _tenant_names(r), f"{r['name']} lost tenant columns"
        # the numpy probe is an exact-parity guarantee since the
        # epsilon-quantized tie-break
        assert r["parity_np"] == pytest.approx(1.0)
        assert r["p99_e2e"] >= r["p50_e2e"] >= 0
        assert r["decide_ms_per_req"] >= 0
        # the zero-allocation host path keeps steady-state decision
        # cost at the paper cells well under the pre-rebuild 16-18.6
        # (x0.5) / 10.5-11.2 (x1.0) ms/req — gate at half
        if r["name"].startswith("sweep/paper_"):
            if r["name"].endswith("_x0.5"):
                assert r["decide_ms_per_req"] <= 8.0, r["name"]
            elif r["name"].endswith("_x1.0"):
                assert r["decide_ms_per_req"] <= 5.2, r["name"]
    # the graduation grid: >= 3 weight vectors x 3 loads x 2 scenarios
    # (the hyperscale family runs a deliberately smaller grid at
    # CI-nightly sizing, so it doesn't count toward the dense shape)
    assert len(weights) >= 3, weights
    assert len(loads) >= 3, loads
    dense = scenes - {"hyperscale"}
    assert len(dense) >= 2, scenes
    n_dense = sum(1 for r in rows
                  if not r["name"].startswith("sweep/hyperscale_"))
    assert n_dense >= len(weights) * len(loads) * len(dense)
    # the hyperscale family: 16-tier x 128-instance cells on the
    # megakernel backend, >= 2 weights x 2 loads, per-request decision
    # cost staying flat at the 128-instance scale point
    hyper = [r for r in rows if r["name"].startswith("sweep/hyperscale_")]
    assert len(hyper) >= 4, [r["name"] for r in hyper]
    for r in hyper:
        assert r["I"] == 128, r["name"]
        assert r["decide_ms_per_req"] <= 8.0, r["name"]
        assert r["device_us"] >= 0 and r["sync_us"] >= 0


def _tenant_names(row):
    """Tenant classes whose p50/p99/goodput triple is complete."""
    names = {k[len("t_"):-len("_p99")] for k in row
             if k.startswith("t_") and k.endswith("_p99")}
    for n in names:
        for suffix in ("p50", "p99", "goodput"):
            assert f"t_{n}_{suffix}" in row, (row["name"], n, suffix)
            assert row[f"t_{n}_{suffix}"] >= 0
    return names


def test_bench_frontier_artifact_schema_and_grid():
    """The equalized frontier: every cell row self-identifies its
    policy and deployment, carries per-tenant SLO columns, and the grid
    spans RouteBalance's weight family plus the decoupled baselines
    over >= 2 scenarios x >= 3 loads — all through the one engine."""
    doc = _load("BENCH_frontier.json")
    _check_schema(doc, "frontier")
    rows = doc["rows"]
    policies, deployments, scenes, loads = set(), set(), set(), set()
    for r in rows:
        for col in ("policy", "deployment", "lam", "q", "e2e",
                    "p99_e2e", "cost", "tput", "goodput", "failed"):
            assert col in r, f"{r['name']} missing {col}"
        assert r["p99_e2e"] >= 0 and r["tput"] >= 0
        policies.add(r["policy"])
        deployments.add(r["deployment"])
        # frontier/<scene>_<cell>_x<scale>
        body = r["name"].split("/", 1)[1]
        stem, scale = body.rsplit("_x", 1)
        scenes.add(stem.split("_", 1)[0])
        loads.add(float(scale))
        assert _tenant_names(r), f"{r['name']} lost tenant columns"
    assert "routebalance" in policies, policies
    assert len(policies - {"routebalance"}) >= 3, policies   # baselines
    # RouteBalance runs windowed; the baselines run the equalized
    # concurrent arm — one engine, two deployments on the same grid
    assert {"windowed", "concurrent"} <= deployments, deployments
    assert len(scenes) >= 2, scenes
    assert len(loads) >= 3, loads
    # the multitenant scenario really breaks out its tenant classes
    mt = [r for r in rows if r["name"].startswith("frontier/multitenant")]
    assert mt and all(len(_tenant_names(r)) >= 2 for r in mt)


def test_bench_ladder_artifact_schema_and_separation():
    """The §6.3 deployment ladder through the one engine: the
    as-published serial deployments degrade under load while the
    engineering-equalized concurrent variants hold with routing
    byte-identical, and the bounded-queue vLLM-SR arm fails requests
    at load."""
    doc = _load("BENCH_ladder.json")
    _check_schema(doc, "ladder")
    rows = {r["name"]: r for r in doc["rows"]}
    for r in rows.values():
        for col in ("policy", "deployment", "lam", "e2e", "resid",
                    "fail", "q", "goodput"):
            assert col in r, f"{r['name']} missing {col}"

    def cell(name, lam):
        return rows[f"ladder/{name}@{lam}"]

    for lam in (12, 24, 30):
        assert cell("bestroute_serial", lam)["deployment"] == \
            "serial_published"
        assert cell("bestroute_concurrent", lam)["deployment"] == \
            "concurrent"
        # routing is byte-identical across the ladder: same policy
        # family, same quality — only the serving arm moves
        assert cell("bestroute_serial", lam)["q"] == pytest.approx(
            cell("bestroute_concurrent", lam)["q"], abs=0.02)
    # serial-as-published collapses: the scoring station dominates e2e
    # (the paper's 23x-class separation) and grows with load
    s12, s30 = (cell("bestroute_serial", lam) for lam in (12, 30))
    c12, c30 = (cell("bestroute_concurrent", lam) for lam in (12, 30))
    assert s30["e2e"] > 10 * c30["e2e"], (s30["e2e"], c30["e2e"])
    assert s30["e2e"] > s12["e2e"]
    assert s30["resid"] > 10 * c30["resid"]
    assert s30["goodput"] < c30["goodput"] / 10
    # ...while the equalized concurrent arm holds under load
    assert c30["e2e"] <= 1.5 * c12["e2e"], (c12["e2e"], c30["e2e"])
    assert c30["goodput"] >= c12["goodput"]
    # avengers: the lighter scorer shows the same residual blow-up
    assert cell("avengers_serial", 30)["resid"] > \
        10 * cell("avengers_concurrent", 30)["resid"]
    # the bounded-queue external classifier drops requests at load
    assert cell("vllm_sr", 30)["fail"] > 0
    assert cell("vllm_sr", 12)["fail"] == 0
    # RouteBalance's amortized batch scoring meets the requirement by
    # construction: windowed deployment, sub-second residual
    for lam in (12, 24, 30):
        rb = cell("rb_uniform", lam)
        assert rb["deployment"] == "windowed"
        assert rb["resid"] < 1.0


def test_bench_elastic_artifact_schema_and_frontier():
    """The overload-control frontier: every cell row carries the new
    shed/autoscale axes plus per-priority SLO columns, the arm ladder
    (static / shed / elastic at each scale-up lag) is complete per
    scenario x load, and the no-recompile-on-scale contract is pinned
    in the committed artifact itself."""
    doc = _load("BENCH_elastic.json")
    _check_schema(doc, "elastic")
    rows = doc["rows"]
    scenes, arms, lags = set(), set(), set()
    for r in rows:
        for col in ("lam", "I_base", "I_max", "peak_alive", "shed_rate",
                    "shed", "scale_ups", "scale_downs", "scale_up_lag_s",
                    "p50_e2e", "p99_e2e", "goodput", "tput", "cost",
                    "failed", "roster_reseeds", "compiles", "r_buckets"):
            assert col in r, f"{r['name']} missing {col}"
        assert 0 <= r["shed_rate"] <= 1
        assert r["I_base"] < r["I_max"]
        assert r["I_base"] <= r["peak_alive"] <= r["I_max"]
        # elastic/<scene>_<arm>_x<scale>
        body = r["name"].split("/", 1)[1]
        stem, _ = body.rsplit("_x", 1)
        scene, arm = stem.split("_elastic_", 1)
        scenes.add(scene + "_elastic")
        arms.add(arm.split("_lag")[0] if "_lag" in arm else arm)
        if "_lag" in arm:
            lags.add(float(arm.split("_lag")[1]))
        # per-priority goodput/shed/SLO triples are complete
        prios = {k[len("prio"):-len("_shed")] for k in r
                 if k.startswith("prio") and k.endswith("_shed")}
        assert prios, f"{r['name']} lost priority columns"
        for p in prios:
            for suffix in ("goodput", "shed", "slo"):
                assert f"prio{p}_{suffix}" in r, (r["name"], p, suffix)
        assert "0" in prios          # the premium class always reported
        # the static arm never scales or sheds; the elastic arms did
        # scale up without adding a single XLA compile
        if arm == "static":
            assert r["scale_ups"] == 0 and r["shed"] == 0
        if arm.startswith("elastic"):
            assert r["scale_ups"] > 0
            assert r["roster_reseeds"] > 0
        assert r["compiles"] <= 5    # one program per warmed pow2 bucket
    assert scenes == {"diurnal_elastic", "flashcrowd_elastic"}, scenes
    assert arms == {"static", "shed", "elastic"}, arms
    assert len(lags) >= 3, lags
    # SLO-aware ordering: wherever anything was shed, the premium class
    # keeps a SLO attainment >= the best-effort class's
    for r in rows:
        if r["shed"] > 0 and "prio2_slo" in r:
            assert r["prio0_slo"] >= r["prio2_slo"], r["name"]


AFFINITY_BACKENDS = ("numpy", "fused")


def test_bench_affinity_artifact_schema_and_headline():
    """The prefix-affinity artifact: every backend x {on, off} cell is
    present with the cache/latency axes, the fused compile pin held
    through session churn, and the headline acceptance gate holds per
    backend — the affinity-on arm achieves a cache hit rate strictly
    above the off arm's incidental hits (and > 0) at mean TTFT no worse
    than affinity-off, at equal load. The backends agree on what
    affinity buys (the term is part of the exact-parity decision)."""
    doc = _load("BENCH_affinity.json")
    _check_schema(doc, "affinity")
    rows = {r["name"]: r for r in doc["rows"]}
    for be in AFFINITY_BACKENDS:
        for arm in ("on", "off"):
            r = rows[f"affinity/{be}_{arm}"]
            for col in ("cache_hit_rate", "mean_ttft", "p99_ttft",
                        "goodput", "mean_e2e", "served", "compiles",
                        "r_buckets"):
                assert col in r, f"{r['name']} missing {col}"
            assert 0 <= r["cache_hit_rate"] <= 1
            assert r["p99_ttft"] >= 0 and r["mean_ttft"] >= 0
            # session/retry churn never reaches XLA: one program per
            # pow2 R bucket, with or without the affinity term
            assert r["compiles"] <= r["r_buckets"], r["name"]
        on, off = rows[f"affinity/{be}_on"], rows[f"affinity/{be}_off"]
        assert on["cache_hit_rate"] > 0, be
        assert on["cache_hit_rate"] > off["cache_hit_rate"], be
        assert on["mean_ttft"] <= off["mean_ttft"] + 1e-12, be
        assert on["served"] == off["served"], be      # equal load
    for arm in ("on", "off"):
        hits = [rows[f"affinity/{be}_{arm}"]["cache_hit_rate"]
                for be in AFFINITY_BACKENDS]
        assert max(hits) - min(hits) < 1e-9, (arm, hits)


CHAOS_CAMPAIGNS = ("crash_storm", "correlated_failure",
                   "telemetry_blackout", "straggler_storm")
CHAOS_ARMS = ("lost", "retry", "retry_hedge")


def test_bench_chaos_artifact_schema_and_recovery():
    """The chaos harness artifact: every campaign x arm cell carries
    the lifecycle axes, fault churn added zero XLA compiles, the
    controller crash/restore came back bitwise identical, and the
    headline acceptance gate holds — under crash_storm the full
    retry+hedge stack recovers >= 90% of the goodput the lost-work arm
    gives up."""
    doc = _load("BENCH_chaos.json")
    _check_schema(doc, "chaos")
    rows = {r["name"]: r for r in doc["rows"]}
    assert "chaos/clean" in rows
    clean = rows["chaos/clean"]
    assert clean["failed"] == 0 and clean["retried"] == 0
    for camp in CHAOS_CAMPAIGNS:
        for arm in CHAOS_ARMS:
            r = rows[f"chaos/{camp}_{arm}"]
            for col in ("goodput", "tput", "p50_e2e", "p99_e2e",
                        "served", "failed", "retried", "gave_up",
                        "hedges", "duplicate_tokens", "wasted_tokens",
                        "quarantines", "degraded_decisions", "compiles",
                        "r_buckets"):
                assert col in r, f"{r['name']} missing {col}"
            assert r["p99_e2e"] >= r["p50_e2e"] >= 0
            # kill/revive/quarantine churn rides the alive-mask: one
            # compiled program per pow2 R bucket, never a recompile
            assert r["compiles"] <= r["r_buckets"], r["name"]
            if arm == "lost":
                # recovery disarmed: nothing retried, hedged or
                # quarantined — and the crash campaigns really lose work
                assert r["retried"] == 0 and r["hedges"] == 0
                assert r["quarantines"] == 0
                if camp in ("crash_storm", "correlated_failure"):
                    assert r["failed"] > 0, r["name"]
            else:
                # recovery armed: every victim is re-served to a
                # terminal success — zero lost requests
                assert r["failed"] == 0, r["name"]
                if camp in ("crash_storm", "correlated_failure"):
                    assert r["retried"] > 0, r["name"]
        rec = rows[f"chaos/{camp}_recovery"]
        for col in ("recovered_frac", "g_clean", "g_lost",
                    "g_retry_hedge"):
            assert col in rec, f"{rec['name']} missing {col}"
    # the watchdog and the hedger actually fired on their campaigns
    assert rows["chaos/telemetry_blackout_retry"]["quarantines"] > 0
    assert rows["chaos/straggler_storm_retry_hedge"]["hedges"] > 0
    # headline gate: g_rh >= g_lost + 0.9 * (g_clean - g_lost)
    storm = rows["chaos/crash_storm_recovery"]
    assert storm["g_retry_hedge"] >= storm["g_lost"] + 0.9 * (
        storm["g_clean"] - storm["g_lost"]) - 1e-9, storm
    assert storm["recovered_frac"] >= 0.9, storm
    # the scheduler process died mid-trace and resumed from its
    # checkpoint to the identical completion set
    cc = rows["chaos/controller_crash_restore"]
    assert cc["identical"] == 1
    assert cc["dropped_events"] > 0
    assert cc["served"] == cc["served_ref"] == cc["n"]


HIERARCHY_GRID_COLS = ("cells", "lam", "I", "decide_ms_per_req",
                       "digest_interval_s", "digest_stale_s",
                       "digest_mode", "digest_bytes_per_s", "digests",
                       "imbalance", "goodput", "p50_e2e", "p99_e2e",
                       "shed", "failed", "n")


def test_bench_hierarchy_artifact_schema_and_headlines():
    """The hierarchical-scheduling artifact: the exactness pins hold
    (span sharding and the 1-cell balanced hierarchy agree with the
    single fused controller on every request), every cells x load x
    digest grid cell carries the two-level axes with a clean run and a
    bounded inter-cell imbalance, and the headline acceptance gate
    holds — the 16-cell hierarchy decides the 10k-instance
    ``hyperfleet_10k`` world at <= 2.5 ms of controller compute per
    request."""
    doc = _load("BENCH_hierarchy.json")
    _check_schema(doc, "hierarchy")
    rows = {r["name"]: r for r in doc["rows"]}
    # exactness pins: sharded span scan at 2 and 4 cells, full-
    # trajectory parity for the 1-cell balanced hierarchy
    for name in ("hierarchy/parity_span_cells2",
                 "hierarchy/parity_span_cells4",
                 "hierarchy/parity_balanced_1cell"):
        assert rows[name]["agree"] == 1.0, name
    grid = [r for r in doc["rows"] if "/grid_" in r["name"]]
    assert grid, "no grid rows"
    for r in grid:
        for col in HIERARCHY_GRID_COLS:
            assert col in r, f"{r['name']} missing {col}"
        assert r["failed"] == 0, r["name"]
        assert r["decide_ms_per_req"] >= 0
        assert r["digest_bytes_per_s"] > 0
        assert 0 <= r["imbalance"] < 1.0, r["name"]
    assert {int(r["cells"]) for r in grid} >= {1, 2, 4}
    assert {r["digest_mode"] for r in grid} == {"exact", "int8"}
    # the 10k-instance headline: committed, clean, and under the
    # acceptance bar (cells run as parallel controllers; this is the
    # per-request decide compute on the controller that served it)
    fleet = rows["hierarchy/hyperfleet_10k_c16"]
    assert fleet["I"] == 10000
    assert fleet["failed"] == 0
    assert fleet["decide_ms_per_req"] <= 2.5, fleet["decide_ms_per_req"]
    # the single-controller comparison row rides along for the story
    assert rows["hierarchy/hyperfleet_10k_single"]["I"] == 10000
