"""Prefix-cache affinity benchmark: what the reuse term buys on a
multi-turn session workload.

One built ``session_chat`` world (multi-turn conversations sharing
growing prompt prefixes, `serving.scenarios.SessionSpec`) runs a 2x2
arm grid on one trained bundle and one request stream: affinity-on
(``RBConfig.affinity_weight > 0``) vs affinity-off, under the numpy and
fused decision backends. The sim's prefill-cache physics is identical
in every arm — `Instance._admit` discounts prefill by the matched
prefix whether or not the router scored for it — so the arms isolate
exactly what affinity-aware ROUTING adds: follow-up turns landing on
the instance that already holds the conversation's KV prefix.

Rows carry ``cache_hit_rate`` (mean matched-prefix fraction at
dispatch), TTFT, goodput and the fused compile pin (the sig planes ride
the existing programs: session churn must never add an XLA compile
beyond one program per pow2 R bucket).

Headline acceptance (asserted here, pinned again in
``tests/test_bench_schema.py``): per backend, the affinity-on arm gets
``cache_hit_rate`` strictly above the off arm's incidental hits, a hit
rate > 0, and mean TTFT no worse than affinity-off at equal load.

Smoke mode for CI: REPRO_AFFINITY_SMOKE=1 trims the cell size while
keeping every arm, so the artifact schema stays pinned.
"""
from __future__ import annotations

import os

from .common import csv_row
from repro.core import RBConfig, RouteBalance
from repro.core.decision_jax import bucket_pow2
from repro.serving.scenarios import get_scenario

SMOKE = os.environ.get("REPRO_AFFINITY_SMOKE", "") not in ("", "0")
N_CELL = 140 if SMOKE else 420
BACKENDS = ("numpy", "fused")
W_AFF = 0.35


def _cell(run, reqs, backend, w_aff):
    rb = RouteBalance(RBConfig(decision_backend=backend,
                               affinity_weight=w_aff,
                               charge_compute=False),
                      run.bundle(), run.tiers)
    m = run.run_cell(rb, reqs, seed=0)
    return m, rb


def _row(name, m, rb):
    compiles = r_buckets = 0
    if rb._fused is not None:
        compiles = rb._fused.compile_count()
        r_buckets = len({bucket_pow2(s) for s, _ in rb.compute_log})
        # session/retry churn must never reach XLA: one program per
        # pow2 R bucket, with or without the affinity term
        assert compiles <= r_buckets, (compiles, r_buckets)
    csv_row(
        name,
        m.get("measured_decide_ms_mean", 0.0) * 1e3,
        f"cache_hit_rate={m['cache_hit_rate']:.4f}"
        f";mean_ttft={m['mean_ttft']:.5f}"
        f";p99_ttft={m['p99_ttft']:.5f}"
        f";goodput={m['goodput']:.3f}"
        f";mean_e2e={m['mean_e2e']:.4f}"
        f";served={m['n']}"
        f";compiles={compiles}"
        f";r_buckets={r_buckets}")
    return m


def main():
    run = get_scenario("session_chat").build(
        dataset_n=300 if SMOKE else 600)
    run.bundle()
    reqs_by_arm = {}
    for be in BACKENDS:
        out = {}
        for arm, w in (("off", 0.0), ("on", W_AFF)):
            # a fresh stream per cell: requests are mutated by the run
            reqs = run.requests(N_CELL, seed=0)
            m, rb = _cell(run, reqs, be, w)
            out[arm] = _row(f"affinity/{be}_{arm}", m, rb)
        # the headline: scoring reuse must actually ROUTE for reuse —
        # strictly more cache hits than the off arm's incidental ones,
        # and no TTFT regression at equal load
        assert out["on"]["cache_hit_rate"] > 0.0, be
        assert out["on"]["cache_hit_rate"] > out["off"]["cache_hit_rate"], \
            (be, out["on"]["cache_hit_rate"], out["off"]["cache_hit_rate"])
        assert out["on"]["mean_ttft"] <= out["off"]["mean_ttft"] + 1e-12, \
            (be, out["on"]["mean_ttft"], out["off"]["mean_ttft"])
        reqs_by_arm[be] = out
    # all three backends agree on what affinity buys (same decisions)
    for arm in ("off", "on"):
        hits = {be: reqs_by_arm[be][arm]["cache_hit_rate"]
                for be in BACKENDS}
        assert max(hits.values()) - min(hits.values()) < 1e-9, (arm, hits)


if __name__ == "__main__":
    from .common import flush_json
    main()
    flush_json("affinity")
