"""Chip smoke test: drive the served RouteBalance decision path once on a
TPU, through the entry points a user calls (`RouteBalance`, `run_cell`,
`get_scenario`), at the paper's real size, and check what comes out.

    python chip_smoke.py             # one chip: phases 1-4
    python chip_smoke.py --chips 4   # four chips: the cell-sharded span
                                     # decision against one chip, only

Phases (one chip):
  1. device — refuse to run anywhere but a TPU;
  2. the paper cell (4 tiers x 13 instances) on the fused backend: the
     3,534-request trace from an 18,608-prompt world, whose
     14,886-row training split is the on-device KNN index;
  3. parity on the chip: numpy reference, fused and megakernel decide
     the same 600 requests (no compute charged, so the simulation is
     deterministic) and must make identical assignments; the megakernel
     must have compiled through Mosaic, not the interpreter;
  4. the hyperscale roster (16 tiers x 128 instances) on the fused
     backend, 600 requests.

One process, no child processes. Each phase prints one line with its
results and seconds; the last line of stdout is the JSON verdict
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_PROMPTS = 18608      # paper world: 14,886 train / 3,722 test prompts
PAPER_REQUESTS = 3534      # the paper's trace length
PAPER_RATE = 12.0          # req/s (Poisson), the paper cell's rate
PARITY_REQUESTS = 600
HYPER_PROMPTS = 6000       # as `repro.launch.serve --scenario` builds it
HYPER_REQUESTS = 600


def line(phase: str, seconds: float, **kv) -> None:
    cols = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {cols} seconds={seconds:.1f}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip smoke FAILED: {what}")


def device_phase(n_chips: int):
    import jax
    t0 = time.perf_counter()
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip smoke needs a TPU; JAX found {platform!r} "
                         f"({devs[0].device_kind}) — not running on it")
    check(len(devs) >= n_chips,
          f"--chips {n_chips} but JAX sees {len(devs)} device(s)")
    kind = devs[0].device_kind
    line("1 device", time.perf_counter() - t0, platform=platform,
         device_kind=repr(kind), count=len(devs))
    return {"platform": platform, "kind": kind, "count": len(devs)}


def runner_counters(rb) -> dict:
    """The fused runner's counters and its host split: seconds in each
    `rb.*` span (staging, telemetry sync, dispatch, the wait for the
    device, the copy), the host arrays it handed the device and the
    device-to-host transfers it started."""
    fused = rb._fused
    st = fused.stats
    return {"compile_count": fused.compile_count(),
            "full_reseed": st["full_reseed"],
            "roster_reseed": st["roster_reseed"],
            "delta_sync": st["delta_sync"], "carry": st["carry"],
            **{k: f"{st[k]:.3f}" for k in ("stage_s", "telemetry_s",
                                            "dispatch_s", "device_s",
                                            "sync_s")},
            "uploads": st["uploads"], "d2h": st["d2h"]}


def served_cell(phase: str, m: dict, rb, n: int, seconds: float,
                **extra) -> None:
    line(phase, seconds, **extra, n=m["n"], failed=m["failed"],
         quality=f"{m['quality']:.4f}", mean_e2e=f"{m['mean_e2e']:.3f}",
         p99_e2e=f"{m['p99_e2e']:.3f}",
         cost_per_req=f"{m['cost_per_req']:.3e}",
         decide_ms_mean=f"{m['measured_decide_ms_mean']:.3f}",
         decide_ms_per_req=f"{m['measured_decide_ms_per_req']:.4f}",
         **runner_counters(rb))
    check(m["failed"] == 0, f"{phase}: {m['failed']} requests failed")
    check(m["n"] == n, f"{phase}: served {m['n']} of {n} requests")
    for k in ("quality", "mean_e2e", "p99_e2e", "cost_per_req"):
        check(math.isfinite(m[k]), f"{phase}: {k} = {m[k]}")


def paper_world_bundle(n_prompts: int):
    from repro.core import EstimatorBundle
    from repro.serving.tiers import paper_pool_tiers
    from repro.serving.world import build_dataset, paper_world
    world, names = paper_world(seed=0)
    ds = build_dataset(world, n=n_prompts)
    tiers = paper_pool_tiers()
    return ds, tiers, names, EstimatorBundle.train(ds, tiers, names)


def paper_phase(ds, tiers, names, bundle, n_requests: int,
                setup_s: float) -> None:
    from repro.core import RBConfig, RouteBalance, make_requests, run_cell
    from repro.serving.workload import poisson_arrivals
    t0 = time.perf_counter()
    reqs = make_requests(ds, "test",
                         poisson_arrivals(PAPER_RATE, n_requests, seed=0))
    rb = RouteBalance(RBConfig(), bundle, tiers)
    m = run_cell(rb, tiers, names, reqs)
    served_cell("2 paper fused", m, rb, n_requests,
                setup_s + time.perf_counter() - t0,
                index_rows=bundle.knn._x.shape[0],
                setup_seconds=f"{setup_s:.1f}")


def parity_phase(ds, tiers, names, bundle, n_requests: int) -> None:
    from repro.core import RBConfig, RouteBalance, make_requests, run_cell
    from repro.serving.workload import poisson_arrivals
    t0 = time.perf_counter()
    picks, runners = {}, {}
    for backend in ("numpy", "fused", "megakernel"):
        reqs = make_requests(ds, "test",
                             poisson_arrivals(PAPER_RATE, n_requests, seed=0))
        rb = RouteBalance(RBConfig(decision_backend=backend,
                                   charge_compute=False), bundle, tiers)
        m = run_cell(rb, tiers, names, reqs)
        check(m["failed"] == 0, f"parity {backend}: {m['failed']} failed")
        picks[backend] = [r.instance for r in reqs]
        runners[backend] = rb._fused
    agree = {b: sum(a == r for a, r in zip(picks[b], picks["numpy"]))
             / n_requests for b in ("fused", "megakernel")}
    mosaic = runners["megakernel"]._interpret is False
    line("3 parity", time.perf_counter() - t0, n=n_requests,
         fused_vs_numpy=agree["fused"], megakernel_vs_numpy=agree["megakernel"],
         megakernel_mosaic=mosaic)
    check(mosaic, "the megakernel ran in the Pallas interpreter")
    for b, a in agree.items():
        check(a == 1.0, f"{b} agrees with numpy on {a:.4f} of assignments")


def hyperscale_phase(n_prompts: int, n_requests: int) -> None:
    from repro.core import RBConfig, RouteBalance
    from repro.serving.scenarios import get_scenario
    t0 = time.perf_counter()
    run = get_scenario("hyperscale").build(dataset_n=n_prompts)
    rb = RouteBalance(RBConfig(), run.bundle(), run.tiers)
    m = run.run_cell(rb, run.requests(n_requests, seed=0))
    served_cell("4 hyperscale fused", m, rb, n_requests,
                time.perf_counter() - t0)


def span_phase(n_prompts: int, n_requests: int) -> None:
    """The cell-sharded decision scan of one logical controller over a
    four-chip ("cell",) mesh, against the single-controller scan on one
    chip: same trace, identical assignments."""
    from repro.core import RBConfig, RouteBalance
    from repro.serving.scenarios import get_scenario
    t0 = time.perf_counter()
    run = get_scenario("hyperscale").build(dataset_n=n_prompts)
    picks = {}
    for cells in (0, 4):
        rb = RouteBalance(RBConfig(shard_cells=cells, charge_compute=False),
                          run.bundle(), run.tiers)
        reqs = run.requests(n_requests, seed=0)
        m = run.run_cell(rb, reqs)
        check(m["failed"] == 0, f"span cells={cells}: {m['failed']} failed")
        picks[cells] = [r.instance for r in reqs]
        if cells:
            mesh = rb._fused._cell_mesh
            check(mesh is not None, "no cell mesh: span ran as emulation")
            devs = {d.id for d in mesh.devices.flat}
            check(len(devs) == cells,
                  f"the cell mesh spans {len(devs)} device(s), not {cells}")
    agree = sum(a == b for a, b in zip(picks[4], picks[0])) / n_requests
    line("span 4 chips", time.perf_counter() - t0, n=n_requests,
         mesh_devices=len(devs), agree_vs_one_chip=agree)
    check(agree == 1.0, f"span agrees with one chip on {agree:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip span phase")
    args = ap.parse_args()

    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    device = device_phase(args.chips)
    if args.chips == 4:
        span_phase(HYPER_PROMPTS, HYPER_REQUESTS)
    else:
        t0 = time.perf_counter()
        ds, tiers, names, bundle = paper_world_bundle(PAPER_PROMPTS)
        paper_phase(ds, tiers, names, bundle, PAPER_REQUESTS,
                    time.perf_counter() - t0)
        parity_phase(ds, tiers, names, bundle, PARITY_REQUESTS)
        hyperscale_phase(HYPER_PROMPTS, HYPER_REQUESTS)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
