"""Cluster-scale frontier sweep on the fused backend: (weight vector x
arrival rate x scenario) — the repo's reproduction of the paper's
three-way quality-cost-throughput frontier and its high-load separation
plots (§6.2/§6.5), run on worlds from `repro.serving.scenarios`.

Each cell is a full `ClusterSim` run under
``RBConfig(decision_backend="fused")``: one scenario (roster + composite
workload + perturbation schedule), one weight preset, one load multiple
of the scenario's nominal rate. Rows carry p50/p99 end-to-end latency,
per-request cost, measured decision time with a host/stage/device/sync
breakdown (mean us per fired batch, from ``FusedHotPath.stats`` — see
``benchmarks.hotpath`` for the column semantics) plus delta-telemetry
counters, goodput (SLO-bounded throughput) and a per-weight-config
parity probe — ``parity_np``, fused-vs-numpy agreement, an exact-1.0
guarantee since the epsilon-quantized tie-break (`repro.core.scoring`)
and gated at 1.0 in CI — landing in ``BENCH_sweep.json`` via
benchmarks.run.

The ``sweep/hyperscale_*`` family promotes the 16-tier x 128-instance
scenario into the committed artifact on the decision-megakernel backend
(`RBConfig(decision_backend="megakernel")`): a smaller weights x loads
grid at CI-nightly sizing, carrying the same decide_ms_per_req +
per-stage breakdown columns, with the parity probe anchored on the
megakernel.

Smoke mode for CI: REPRO_SWEEP_SMOKE=1 trims the grid (small rosters,
low n) to under a couple of minutes while keeping the full
3-weights x 3-loads x 2-scenarios shape (plus the hyperscale family)
so the artifact schema stays pinned.
"""
from __future__ import annotations

import os

import numpy as np

from .common import N_REQ, csv_row, tenant_cols
from repro.core import PRESETS, RBConfig, RouteBalance
from repro.serving.cluster import ClusterSim
from repro.serving.scenarios import get_scenario, randomize_telemetry

SMOKE = os.environ.get("REPRO_SWEEP_SMOKE", "") not in ("", "0")
WEIGHTS = (("quality", PRESETS["quality"]),
           ("uniform", PRESETS["uniform"]),
           ("cost", PRESETS["cost"]))
LOADS = (0.5, 1.0, 2.0)            # multiples of the scenario's rate
SCENES = ("paper", "multitenant") if SMOKE else ("paper", "cluster")
N_CELL = 48 if SMOKE else N_REQ
DATASET_N = 300 if SMOKE else 1500
# the hyperscale family: the 16-tier x 128-instance scenario on the
# decision megakernel backend — a smaller (weights x loads) grid at
# CI-nightly sizing, since each cell runs the full 128-instance sim
HYPER_WEIGHTS = (("uniform", PRESETS["uniform"]),
                 ("quality", PRESETS["quality"]))
HYPER_LOADS = (0.5, 1.0)
HYPER_N_CELL = 48 if SMOKE else 192
HYPER_DATASET_N = 300 if SMOKE else 800


def _parity_probe(run, bundle, weights, R=16, seed=7,
                  cell_backend="fused"):
    """Probe batch under THIS cell's weight vector on a randomly-loaded
    roster. Returns the cell backend's agreement with numpy, an
    exact-parity guarantee under the epsilon-quantized tie-break that
    gates the artifact at 1.0 (the hyperscale family anchors on the
    megakernel backend)."""
    reqs = run.requests(R, seed=seed)[:R]
    for r in reqs:
        r.arrival = 0.0
    picks = {}
    for be in ("numpy", cell_backend):
        rb = RouteBalance(
            RBConfig(weights=weights, decision_backend=be), bundle,
            run.tiers)
        rb.sim = randomize_telemetry(
            ClusterSim(run.tiers, run.names, seed=0), seed)
        instances, choice, _ = rb._decide_core(reqs)
        picks[be] = [instances[int(i)].iid for i in choice]
    return float(np.mean([a == b for a, b in
                          zip(picks["numpy"], picks[cell_backend])]))


def _cell_row(scene, run, sc, rb, m, wname, scale, parity_np):
    lam = sc.lam * scale
    # per-fired-batch decision breakdown over the whole cell
    # (FusedHotPath.stats is a per-cell window: for_bundle resets it
    # when the cell's scheduler first decides)
    st = rb._fused.stats if rb._fused is not None else {}
    calls = max(st.get("calls", 0), 1)
    bd = {k: st.get(k, 0.0) / calls * 1e6
          for k in ("host_s", "stage_s", "dispatch_s", "device_s",
                    "sync_s")}
    csv_row(
        f"sweep/{scene}_{wname}_x{scale}",
        m.get("measured_decide_ms_mean", 0.0) * 1e3,
        f"lam={lam:.1f}"
        f";I={run.n_instances}"
        f";q={m['quality']:.3f}"
        f";p50_e2e={m['p50_e2e']:.3f}"
        f";p99_e2e={m['p99_e2e']:.3f}"
        f";cost={m['cost_per_req']:.3e}"
        f";tput={m['throughput']:.2f}"
        f";goodput={m['goodput']:.2f}"
        f";failed={m['failed']}"
        f";decide_ms_per_req="
        f"{m.get('measured_decide_ms_per_req', 0.0):.3f}"
        f";host_us={bd['host_s']:.1f}"
        f";stage_us={bd['stage_s']:.1f}"
        f";dispatch_us={bd['dispatch_s']:.1f}"
        f";device_us={bd['device_s']:.1f}"
        f";sync_us={bd['sync_s']:.1f}"
        f";full_reseeds={st.get('full_reseed', 0)}"
        f";delta_syncs={st.get('delta_sync', 0)}"
        f";carries={st.get('carry', 0)}"
        f";parity_np={parity_np:.3f}"
        + tenant_cols(m))


def _hyperscale_cells():
    """The 16-tier x 128-instance scenario on the megakernel backend:
    the scale point where per-request decision cost must stay flat
    (amortized batched scoring) even with a 128-wide instance axis."""
    sc = get_scenario("hyperscale")
    run = sc.build(dataset_n=HYPER_DATASET_N)
    bundle = run.bundle()
    warm_reqs = run.requests(128, seed=99)
    for wname, w in HYPER_WEIGHTS:
        parity_np = _parity_probe(run, bundle, w,
                                  cell_backend="megakernel")
        warm = RouteBalance(
            RBConfig(weights=w, decision_backend="megakernel"),
            bundle, run.tiers)
        warm.sim = ClusterSim(run.tiers, run.names, seed=0)
        for R in (8, 16, 32, 64, 128):
            warm._decide_core(warm_reqs[:R])
        for scale in HYPER_LOADS:
            reqs = run.requests(HYPER_N_CELL, lam_scale=scale, seed=0)
            rb = RouteBalance(
                RBConfig(weights=w, decision_backend="megakernel"),
                bundle, run.tiers)
            m = run.run_cell(rb, reqs, seed=0)
            _cell_row("hyperscale", run, sc, rb, m, wname, scale,
                      parity_np)


def main():
    for scene in SCENES:
        sc = get_scenario(scene)
        run = sc.build(dataset_n=DATASET_N)
        bundle = run.bundle()
        warm_reqs = run.requests(128, seed=99)
        for wname, w in WEIGHTS:
            parity_np = _parity_probe(run, bundle, w)
            # deterministic warm-up: compile every pow2 R bucket the
            # overloaded cells can reach (backlog pushes batch sizes up
            # through 128) so XLA compiles land outside the measured
            # cells — the fused runner is cached on the bundle per
            # weight config, so the grid below reuses these programs
            warm = RouteBalance(
                RBConfig(weights=w, decision_backend="fused"),
                bundle, run.tiers)
            warm.sim = ClusterSim(run.tiers, run.names, seed=0)
            for R in (8, 16, 32, 64, 128):
                warm._decide_core(warm_reqs[:R])
            for scale in LOADS:
                reqs = run.requests(N_CELL, lam_scale=scale, seed=0)
                rb = RouteBalance(
                    RBConfig(weights=w, decision_backend="fused"),
                    bundle, run.tiers)
                m = run.run_cell(rb, reqs, seed=0)
                _cell_row(scene, run, sc, rb, m, wname, scale, parity_np)
    _hyperscale_cells()


if __name__ == "__main__":
    from .common import flush_json
    main()
    flush_json("sweep")
