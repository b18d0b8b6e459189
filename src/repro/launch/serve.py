"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --preset uniform --lam 12
    PYTHONPATH=src python -m repro.launch.serve --pool zoo --preset quality
    PYTHONPATH=src python -m repro.launch.serve --scenario multitenant \
        --preset cost --lam-scale 2.0
    PYTHONPATH=src python -m repro.launch.serve --policy bestroute-sq \
        --deployment serial_published --lam 24

--scenario selects a named world from `repro.serving.scenarios`
(roster + composite multi-tenant workload + failure/recovery schedule);
it overrides --pool/--arrivals/--lam.

--policy selects any scheduler from the `repro.core.policies.POLICIES`
registry (RouteBalance plus the router x dispatcher baseline grid);
--deployment picks the engine's serving arm (windowed amortized batch
scoring, concurrent equalized worker-pool scoring, serial_published
one-call-per-request as-published, microbatch collector) — every
combination runs through the one `ServingEngine`.

--cells > 1 runs the hierarchical scheduler (`repro.serving.hierarchy`,
routebalance policy only): the roster is partitioned into cells, each
with its own RouteBalance engine, and a GlobalBalancer assigns arrivals
from compressed telemetry digests exchanged every --digest-interval
seconds (usable for --digest-stale seconds; --digest-mode picks the
exact float32 or lossy int8 wire codec). --cell-routing span instead
shards the fused instance-column scan of ONE logical controller over
the cells (bitwise-identical decisions at any cell count).
"""
from __future__ import annotations

import argparse
import json


def main():
    from repro.core.engine import DEPLOYMENTS
    from repro.core.policies import POLICIES

    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", choices=("paper", "zoo"), default="paper")
    ap.add_argument("--scenario", default="",
                    help="named scenario from repro.serving.scenarios "
                         "(overrides --pool/--arrivals/--lam)")
    ap.add_argument("--policy", default="routebalance",
                    choices=sorted(POLICIES),
                    help="scheduling policy from the POLICIES registry")
    ap.add_argument("--deployment", default="windowed",
                    choices=DEPLOYMENTS,
                    help="engine serving arm (§6.3 ladder axis)")
    ap.add_argument("--preset", default="uniform",
                    help="weight preset (routebalance policy only)")
    ap.add_argument("--weights", default="",
                    help="wq,wl,wc overriding --preset")
    ap.add_argument("--lam", type=float, default=12.0)
    ap.add_argument("--lam-scale", type=float, default=1.0,
                    help="scenario load multiplier (with --scenario)")
    ap.add_argument("--n", type=int, default=600)
    ap.add_argument("--arrivals", default="poisson",
                    choices=("poisson", "gamma", "square", "flash"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", type=int, default=1,
                    help="partition the roster into N scheduling cells "
                         "(hierarchical path; routebalance only)")
    ap.add_argument("--cell-routing", default="balanced",
                    choices=("span", "balanced"),
                    help="balanced: per-cell engines + digest-routed "
                         "GlobalBalancer; span: one logical decision "
                         "sharded across cells")
    ap.add_argument("--digest-interval", type=float, default=0.25,
                    help="seconds between per-cell telemetry digests")
    ap.add_argument("--digest-stale", type=float, default=1.0,
                    help="digest staleness bound (cell goes dark past "
                         "this age)")
    ap.add_argument("--digest-mode", default="exact",
                    choices=("exact", "int8"),
                    help="digest wire codec")
    args = ap.parse_args()

    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    from repro.core import (EngineConfig, EstimatorBundle, PRESETS,
                            ServingEngine, fit_policy, make_requests,
                            run_cell)
    from repro.serving.tiers import assigned_pool_tiers, paper_pool_tiers
    from repro.serving.workload import make_arrivals
    from repro.serving.world import World, build_dataset, paper_world

    w = PRESETS[args.preset]
    if args.weights:
        w = tuple(float(x) for x in args.weights.split(","))
    policy_kw = dict(weights=w) if args.policy == "routebalance" else {}

    def hier_sched(bundle, tiers):
        from repro.core import RBConfig
        from repro.serving.hierarchy import (HierarchyConfig,
                                             build_scheduler)
        assert args.policy == "routebalance", \
            "--cells > 1 requires the routebalance policy"
        return build_scheduler(
            RBConfig(weights=w), bundle, tiers,
            HierarchyConfig(n_cells=args.cells,
                            routing=args.cell_routing,
                            digest_interval_s=args.digest_interval,
                            digest_stale_s=args.digest_stale,
                            digest_mode=args.digest_mode))

    def hier_cols(m, eng):
        m["cells"] = args.cells
        m["cell_routing"] = args.cell_routing
        bal = getattr(eng, "balancer", None)
        if bal is not None:
            m["intercell_imbalance"] = round(bal.imbalance(), 4)
            m["digests"] = bal.digests_sent
            m["digest_bytes"] = bal.bytes_sent

    if args.scenario:
        from repro.serving.scenarios import get_scenario
        run = get_scenario(args.scenario).build(dataset_n=6000)
        reqs = run.requests(args.n, lam_scale=args.lam_scale,
                            seed=args.seed)
        if args.cells > 1:
            eng = hier_sched(run.bundle(), run.tiers)
        else:
            eng = run.engine(run.policy(args.policy, **policy_kw),
                             deployment=args.deployment)
        m = run.run_cell(eng, reqs, seed=args.seed)
        if args.cells > 1:
            hier_cols(m, eng)
        m["scenario"] = args.scenario
        m["n_instances"] = run.n_instances
    else:
        if args.pool == "paper":
            world, names = paper_world(seed=args.seed)
            tiers = paper_pool_tiers()
        else:
            from examples.zoo_serving import CAPS, VERB
            tiers = assigned_pool_tiers()
            names = [t.model for t in tiers]
            world = World([CAPS[m] for m in names],
                          [VERB[m] for m in names], seed=args.seed)
        ds = build_dataset(world, n=6000)
        bundle = EstimatorBundle.train(ds, tiers, names)
        reqs = make_requests(
            ds, "test", make_arrivals(args.arrivals, args.lam, args.n,
                                      seed=args.seed))
        if args.cells > 1:
            eng = hier_sched(bundle, tiers)
        else:
            policy = fit_policy(args.policy, bundle, tiers, names, ds,
                                **policy_kw)
            eng = ServingEngine(policy, bundle, tiers,
                                EngineConfig(deployment=args.deployment))
        m = run_cell(eng, tiers, names, reqs, seed=args.seed)
        if args.cells > 1:
            hier_cols(m, eng)
    print(json.dumps({k: v for k, v in m.items()
                      if not isinstance(v, tuple)}, indent=1,
                     default=str))


if __name__ == "__main__":
    main()
