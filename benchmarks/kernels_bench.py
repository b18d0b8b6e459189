"""Kernel-level microbenchmarks -> BENCH_kernels.json.

Two families:

  * the historical hot-spot rows — batched embed+KNN (the paper's
    ~27 ms term), greedy scoring-loop scaling (|I| = 13/100/500;
    paper: 12.8/14.3/22.5 us), knn_topk-vs-oracle;
  * the **decision megakernel grid**: per-batch decision µs over
    (R, I) cells with megakernel / fused-XLA / staged-numpy columns —
    the same `RouteBalance._decide_core` probe `benchmarks.hotpath`
    times, here centered on the kernel comparison (interleaved
    min-of-N so ambient CPU drift doesn't bias one backend). Off a
    TPU the megakernel runs in the Pallas interpreter, which executes
    as XLA; on a TPU the same kernel body compiles with Mosaic.

Smoke mode for CI: REPRO_KERNELS_SMOKE=1 trims the decision grid to the
small cells (a subset of the full grid, so perf_guard can gate smoke
rows against the committed artifact's shape).
"""
from __future__ import annotations

import os
import time

import numpy as np

from .common import context, csv_row, make_requests
from repro.core import PRESETS, RBConfig, RouteBalance
from repro.core.assignment import greedy_assign, lpt_order

FLUSH_AS = "kernels"     # artifact name: BENCH_kernels.json

SMOKE = os.environ.get("REPRO_KERNELS_SMOKE", "") not in ("", "0")
DECISION_GRID = (((8, 13), (16, 13)) if SMOKE else
                 ((8, 13), (16, 13), (64, 13), (64, 52), (256, 128)))


def _time(fn, n=20, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def _decision_cells(ctx):
    """The megakernel-vs-fused-vs-staged (R, I) grid."""
    from .hotpath import scaled_pool
    from repro.serving.cluster import ClusterSim
    from repro.serving.scenarios import randomize_telemetry
    backends = ("numpy", "fused", "megakernel")
    for R, I in DECISION_GRID:
        tiers = (ctx["tiers"]
                 if I == sum(t.n_instances for t in ctx["tiers"])
                 else scaled_pool(ctx["tiers"], I))
        batch = make_requests(ctx["ds"], "test", np.zeros(R))
        rbs, picks = {}, {}
        for be in backends:
            sim = randomize_telemetry(
                ClusterSim(tiers, ctx["names"], seed=0), seed=1)
            rb = RouteBalance(RBConfig(decision_backend=be),
                              ctx["bundle"], tiers)
            rb.sim = sim
            rb._decide_core(batch)              # compile + warm
            instances, choice, _ = rb._decide_core(batch)
            picks[be] = [instances[int(i)].iid for i in choice]
            rbs[be] = rb
        agree = float(np.mean([
            all(picks[be][r] == picks["megakernel"][r]
                for be in backends) for r in range(R)]))
        reps = 10 if R >= 256 else 16
        ts = {be: [] for be in backends}
        for _ in range(reps):                   # interleaved timing
            for be, rb in rbs.items():
                t0 = time.perf_counter()
                rb._decide_core(batch)
                ts[be].append(time.perf_counter() - t0)
        best = {be: min(v) * 1e6 for be, v in ts.items()}
        csv_row(
            f"kernels/decision_R{R}_I{I}", best["megakernel"],
            f"megakernel_us={best['megakernel']:.1f}"
            f";fused_us={best['fused']:.1f}"
            f";staged_us={best['numpy']:.1f}"
            f";per_req_us={best['megakernel']/R:.1f}"
            f";vs_fused={best['fused']/best['megakernel']:.2f}x"
            f";vs_staged={best['numpy']/best['megakernel']:.2f}x"
            f";agree={agree:.3f}")


def main():
    ctx = context()
    bundle = ctx["bundle"]
    rng = np.random.default_rng(0)
    # batched embed+KNN (the per-batch amortized decision compute)
    prompts, Q, L = ctx["ds"].split("test")
    for B in (1, 16, 64):
        reqs = [prompts[i] for i in range(B)]
        from repro.estimators.embedding import pad_tokens
        toks = pad_tokens([p.tokens for p in reqs], bundle.encoder.max_len)
        lens = np.array([min(len(p.tokens), 128) for p in reqs])
        dt_e = _time(lambda: bundle.encoder.encode(toks, lens))
        emb = bundle.encoder.encode(toks, lens)
        dt_k = _time(lambda: bundle.knn.query(emb))
        csv_row(f"kernels/embed_knn_B{B}", (dt_e + dt_k) * 1e6,
                f"embed_us={dt_e*1e6:.0f};knn_us={dt_k*1e6:.0f};"
                f"per_req_us={(dt_e+dt_k)/B*1e6:.0f}")
    # scoring-loop scaling with instance count (paper §4.2)
    for I in (13, 100, 500):
        R = 16
        q_inst = rng.uniform(0, 1, (R, I))
        c_hat = rng.uniform(1e-6, 1e-4, (R, I))
        l_inst = rng.uniform(50, 500, (R, I))
        tpot = rng.uniform(0.01, 0.05, I)
        d = rng.uniform(0, 2000, I)
        b = rng.integers(1, 16, I).astype(float)
        free = rng.integers(0, 8, I).astype(float)
        maxb = np.full(I, 48.0)
        order = lpt_order(l_inst.max(1))
        dt = _time(lambda: greedy_assign(
            order, q_inst, c_hat, l_inst, tpot, d, b, free, maxb,
            PRESETS["uniform"]), n=10)
        csv_row(f"kernels/scoring_loop_I{I}", dt / R * 1e6,
                f"per_req_us={dt/R*1e6:.1f}")
    # pallas kernels vs oracles (correctness timing, interpret mode)
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels import ref as kref
    q = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4096, 128)), jnp.float32)
    dv, di = ops.knn_topk(q, x, k=10)
    rv, ri = kref.knn_topk_ref(q, x, k=10)
    err = float(jnp.abs(dv - rv).max())
    dt_ref = _time(lambda: jax.block_until_ready(
        kref.knn_topk_ref(q, x, k=10)), n=10)
    csv_row("kernels/knn_topk_pallas", dt_ref * 1e6,
            f"allclose_err={err:.1e};jnp_oracle_us={dt_ref*1e6:.0f}")
    # the decision megakernel grid
    _decision_cells(ctx)
    return None


if __name__ == "__main__":
    from .common import flush_json
    main()
    flush_json(FLUSH_AS)
