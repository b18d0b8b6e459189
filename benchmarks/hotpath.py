"""Full hot-path latency: the staged numpy reference vs the fused
single-dispatch program vs the Pallas decision megakernel
(`repro.kernels.decision_megakernel` — one kernel for KNN top-k ->
packed GBM -> admission -> LPT greedy scan; megakernel rows carry
`vs_fused` + `agree` so perf_guard can gate parity-or-better).

One "decision" here is everything between batch formation and dispatch —
token padding, the sentence encoder, the batched KNN lookup, the
per-tier TPOT heads, Eq. 2 admission, LPT ordering and the dead-reckoned
greedy pass — i.e. exactly what `RouteBalance._decide_core` runs per
fired batch (the paper's ~32 ms/batch headline, §6.3). The staged
numpy backend pays a host round trip per stage; the fused
backend (`repro.core.hotpath`) pays one dispatch total with
device-resident constants and state.

Grid: (R, I) up to R=512, I=128 (instance pools are the paper's 4 tiers
proportionally scaled). Interleaved min-of-N timing so CPU drift doesn't
bias one backend. Rows land in BENCH_hotpath.json via the benchmarks.run
JSON emission (or the __main__ block when run directly). Smoke mode for
CI: REPRO_HOTPATH_SMOKE=1 trims the grid to seconds (a subset of the
full grid, so `benchmarks.perf_guard` can diff smoke rows against the
committed artifact).

Fused rows carry a host/stage/device/sync breakdown (mean us/call over
the timed reps, from `FusedHotPath.stats`): `stage_us` is the gather
into the preallocated staging buffers, `host_us` is all host-side work
up to dispatch (staging + telemetry delta assembly), `dispatch_us` is
the jitted-call dispatch, `device_us` is the wait on the device program
at fetch, and `sync_us` is the device->host result copy. Since the
host-path rebuild (SoA ingest + delta telemetry), `host_us` should be
microseconds — the paper's "router overhead" is all `device_us`.
"""
from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from .common import context, csv_row, make_requests
from repro.core import RBConfig, RouteBalance
from repro.serving.cluster import ClusterSim

SMOKE = os.environ.get("REPRO_HOTPATH_SMOKE", "") not in ("", "0")
GRID = (((8, 13), (16, 13)) if SMOKE else
        ((8, 13), (16, 13), (64, 13), (256, 13), (256, 52), (256, 128),
         (512, 128)))
BACKENDS = ("numpy", "fused", "megakernel")


def scaled_pool(tiers, I):
    """The paper's 4-tier pool proportionally scaled to I instances."""
    counts = np.array([t.n_instances for t in tiers], float)
    n = np.maximum(np.round(counts * I / counts.sum()).astype(int), 1)
    while n.sum() > I:
        n[np.argmax(n)] -= 1
    while n.sum() < I:
        n[np.argmin(n)] += 1
    return [dataclasses.replace(t, n_instances=int(k))
            for t, k in zip(tiers, n)]


def _bench_cell(ctx, R, I, reps):
    tiers = (ctx["tiers"] if I == sum(t.n_instances for t in ctx["tiers"])
             else scaled_pool(ctx["tiers"], I))
    batch = make_requests(ctx["ds"], "test", np.zeros(R))
    rng = np.random.default_rng(0)
    budgets = np.where(rng.uniform(size=R) < 0.5,
                       rng.uniform(1e-5, 3e-4, R), np.nan)
    for r, b in zip(batch, budgets):
        r.budget = None if np.isnan(b) else float(b)
    rbs = {}
    picks = {}
    from repro.serving.scenarios import randomize_telemetry
    for be in BACKENDS:
        # same load per backend (seeded shared fixture)
        sim = randomize_telemetry(ClusterSim(tiers, ctx["names"], seed=0),
                                  seed=1)
        tel = sim.tel
        rb = RouteBalance(RBConfig(decision_backend=be), ctx["bundle"],
                          tiers)
        rb.sim = sim
        rb._decide_core(batch)                  # compile + warm
        # repeated calls are parity-safe by construction now: the fused
        # runner's carried mirror equals a fresh host read of `tel`
        # (reseed-per-batch semantics; telemetry hasn't moved between
        # calls, so the carry arm is exact)
        instances, choice, _ = rb._decide_core(batch)
        picks[be] = [instances[int(i)].iid for i in choice]
        rbs[be] = rb
    # fraction of requests on which every backend picked the same
    # instance as the numpy reference
    agree = float(np.mean([
        all(picks[be][r] == picks["numpy"][r] for be in BACKENDS)
        for r in range(R)]))
    ts = {be: [] for be in BACKENDS}
    s0 = dict(rbs["fused"]._fused.stats)        # breakdown window start
    for _ in range(reps):                       # interleaved timing
        for be, rb in rbs.items():
            t0 = time.perf_counter()
            rb._decide_core(batch)
            ts[be].append(time.perf_counter() - t0)
    s1 = rbs["fused"]._fused.stats
    breakdown = {k: (s1[k] - s0[k]) / reps * 1e6
                 for k in ("host_s", "stage_s", "dispatch_s", "device_s",
                           "sync_s")}           # mean us/call over reps
    best = {be: min(v) for be, v in ts.items()}
    # per-rep paired differences share ambient (CPU-frequency, co-tenant)
    # conditions, so their median is far more noise-robust than the
    # difference of the mins
    paired = {be: float(np.median(np.array(ts["numpy"]) - np.array(v)))
              for be, v in ts.items()}
    return best, paired, agree, breakdown


def main():
    ctx = context()
    margins = {}
    for R, I in GRID:
        reps = 10 if R >= 256 else 16
        best, paired, agree, bd = _bench_cell(ctx, R, I, reps)
        margins[(R, I)] = paired["fused"] * 1e3
        for be in BACKENDS:
            extra = ""
            if be != "numpy":
                extra = f";speedup_vs_numpy={best['numpy']/best[be]:.2f}x"
            if be == "megakernel":
                # the one-kernel decision vs the fused-XLA pipeline:
                # the perf_guard parity-or-better gate's raw material
                extra += (f";vs_fused={best['fused']/best[be]:.2f}x"
                          f";agree={agree:.3f}")
            if be == "fused":
                extra += (f";margin_vs_numpy_ms={paired['fused']*1e3:.2f}"
                          f";agree={agree:.3f}"
                          f";host_us={bd['host_s']:.1f}"
                          f";stage_us={bd['stage_s']:.1f}"
                          f";dispatch_us={bd['dispatch_s']:.1f}"
                          f";device_us={bd['device_s']:.1f}"
                          f";sync_us={bd['sync_s']:.1f}")
            csv_row(f"hotpath/{be}_R{R}_I{I}", best[be] * 1e6,
                    f"per_req_us={best[be]/R*1e6:.1f}{extra}")
    if not SMOKE:
        print(f"# fused margin over staged numpy: "
              f"{margins.get((64, 13), 0):.1f} ms/batch at R=64,I=13 -> "
              f"{max(m for (R, _), m in margins.items() if R >= 256):.1f}"
              f" ms/batch at R>=256")


if __name__ == "__main__":
    from .common import flush_json
    main()
    flush_json("hotpath")
