"""RouteBalance: the fused routing + load-balancing policy (§4) on the
policy-agnostic `ServingEngine`.

Per fired batch: one batched embed+KNN call gives prompt-intrinsic Q̂/L̂
for every candidate model; per-tier TPOT heads + dead-reckoned instance
state give the state-dependent T̂; the LPT-ordered greedy pass maximizes
Eq. 1 per request, updating the local instance view after each dispatch.
Batch formation, SoA ingest, async dispatch and residual charging live
in `repro.core.engine.ServingEngine` (shared with every baseline
policy); this module holds the decision itself — `RouteBalancePolicy`
implementing the `SchedulingPolicy` protocol over the fused / staged
jax / numpy backends — plus the `RouteBalance` convenience class that
binds policy and engine the way the paper deploys them (windowed
amortized batch scoring).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.estimators.embedding import SentenceEncoder, pad_tokens
from repro.estimators.knn import KNNEstimator
from repro.estimators.latency import LatencyHead, tpot_features
from repro.serving.cluster import ClusterSim, Instance
from repro.serving.request import Request
from repro.serving.tiers import Tier

from .assignment import greedy_assign, lpt_order
from .budget import admission_mask, cost_matrix
from .decision_jax import LATENCY_MODES
from .engine import (AssignmentResult, BatchView, EngineConfig, Ready,
                     SchedulingPolicy, ServingEngine)
from .weights import PRESETS, Weights, validate

if TYPE_CHECKING:
    from repro.serving.recovery import RecoveryConfig


@dataclasses.dataclass
class RBConfig:
    weights: Weights = PRESETS["uniform"]
    base_window: float = 0.10          # batch formation window (s)
    adaptive: bool = True
    lpt: bool = True
    fixed_batch: Optional[int] = None  # fixed-size batching ablation
    budget_filter: bool = True
    latency_mode: str = "full"         # full|off_reactive|off_predictive|
    #                                    static_prior (§6.3 arms)
    learned_tpot: bool = True
    knn_k: int = 10
    charge_compute: bool = True        # charge measured decision time
    decision_backend: str = "fused"    # fused (single-dispatch hot
    #                                    path, the default since it
    #                                    soaked under tests/test_soak) |
    #                                    megakernel (the whole decision
    #                                    as ONE Pallas kernel —
    #                                    repro.kernels.decision_megakernel
    #                                    — behind the same host
    #                                    machinery as fused) |
    #                                    jax (staged jitted core) |
    #                                    numpy (reference loop)
    window_coalesce: int = 1           # megakernel only: up to K
    #                                    scheduler windows share one
    #                                    kernel dispatch (grid=(K,))
    #                                    via assign_windows. 1 = one
    #                                    dispatch per window (default;
    #                                    matches every other backend)
    knn_backend: Optional[str] = None  # override bundle's KNN backend
    #                                    (numpy | jax | pallas); staged
    #                                    backends only — fused has the
    #                                    estimator feed in-graph
    shed: bool = True                  # honor overload admission control
    #                                    when the sim carries an
    #                                    ElasticController (sim.overload)
    affinity_weight: float = 0.0       # prefix-cache affinity term
    #                                    (serving.affinity): predicted
    #                                    latency scales by (1 - weight x
    #                                    matched-prefix fraction) in
    #                                    every backend. 0 disables —
    #                                    the term is compiled out of the
    #                                    fused program and skipped by
    #                                    the staged paths. Kept OUTSIDE
    #                                    `weights`: that tuple is the
    #                                    Eq. 1 simplex (sums to 1);
    #                                    affinity is a discount on the
    #                                    latency term, not a 4th vertex.
    shard_cells: int = 0               # hierarchical "span" routing:
    #                                    > 1 splits the fused scan's
    #                                    pow2 instance-column axis into
    #                                    that many cells (pow2, fused
    #                                    backend only), combined with
    #                                    exact reductions — bitwise the
    #                                    single-controller decision on
    #                                    any cell count. 0/1 = the
    #                                    unsharded program verbatim.
    cell_tag: Optional[int] = None     # per-cell engine identity under
    #                                    serving.hierarchy "balanced"
    #                                    routing: keys the FusedHotPath
    #                                    cache so signature-identical
    #                                    cell rosters still get their
    #                                    own carried telemetry mirrors.
    recovery: Optional["RecoveryConfig"] = None
    #                                    fault-tolerant request lifecycle
    #                                    (serving.recovery): when set, the
    #                                    engine arms a RecoveryManager on
    #                                    every sim it attaches to. None =
    #                                    off. A dict (a configuration
    #                                    file's JSON block) is read as
    #                                    RecoveryConfig's fields.

    def __post_init__(self):
        if isinstance(self.recovery, dict):
            from repro.serving.recovery import RecoveryConfig
            self.recovery = RecoveryConfig(**self.recovery)


class EstimatorBundle:
    """The in-process predictor stack: encoder + KNN + per-tier heads."""

    def __init__(self, encoder: SentenceEncoder, knn: KNNEstimator,
                 heads: Dict[str, LatencyHead], model_names: List[str]):
        self.encoder = encoder
        self.knn = knn
        self.heads = heads
        self.model_names = model_names

    @staticmethod
    def train(dataset, tiers: Sequence[Tier], model_names: List[str],
              k: int = 10, backend: str = "jax",
              seed: int = 0) -> "EstimatorBundle":
        enc = SentenceEncoder(seed=7)
        prompts, Q, L = dataset.split("train")
        toks = pad_tokens([p.tokens for p in prompts], enc.max_len)
        lens = np.array([min(len(p.tokens), enc.max_len) for p in prompts])
        emb = []
        for i in range(0, len(prompts), 512):
            emb.append(enc.encode(toks[i:i + 512], lens[i:i + 512]))
        emb = np.concatenate(emb)
        knn = KNNEstimator(k=k, backend=backend).fit(emb, Q, L)
        heads = {}
        rng = np.random.default_rng(seed)
        for t in tiers:
            X, y = _tier_sweep(t, rng)
            heads[t.name] = LatencyHead(
                t.name, nominal_tpot=t.tpot(8, 500)).fit(X, y)
        return EstimatorBundle(enc, knn, heads, model_names)

    def predict_prompts(self, reqs: Sequence[Request], cols=None,
                        rows: Optional[np.ndarray] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched Q̂/L̂ for a request batch. When the batch is a slice
        of a SoA ingest stream (`repro.serving.request.RequestColumns`)
        the encoder is skipped entirely — the memoized per-prompt
        embedding column is gathered instead (bitwise the per-batch
        encode, which is padding-stable) — so the staged numpy/jax
        backends share the fused path's ingest win and the differential
        harness keeps comparing like for like."""
        if cols is None:
            from repro.serving.request import batch_columns
            cols, rows = batch_columns(reqs)
        if cols is not None:
            cols.ensure_embeddings(self.encoder)
            emb = cols.emb[cols.prompt_row[rows]]
        else:
            toks = pad_tokens([r.prompt.tokens for r in reqs],
                              self.encoder.max_len)
            lens = np.array([min(len(r.prompt.tokens),
                                 self.encoder.max_len) for r in reqs])
            emb = self.encoder.encode(toks, lens)
        return self.knn.query(emb)


def _tier_sweep(tier: Tier, rng) -> Tuple[np.ndarray, np.ndarray]:
    """Tier-local QPS sweep -> (features, true TPOT) training pairs."""
    rows, ys = [], []
    for _ in range(2000):
        b = rng.integers(1, tier.max_batch + 1)
        ctx = rng.uniform(32, 2048)
        pend = b * rng.uniform(8, 600)
        rows.append(tpot_features(b, pend, ctx))
        ys.append(tier.tpot(b, ctx) * np.exp(rng.normal(0, 0.03)))
    return np.stack(rows), np.asarray(ys, np.float32)


class RouteBalancePolicy(SchedulingPolicy):
    """The fused Eq.1/Eq.2 objective as a `SchedulingPolicy`: one
    batched decision over the full roster per fired batch, selectable
    across the fused single-dispatch program, the staged jitted core,
    and the numpy reference loop (`RBConfig.decision_backend`)."""

    name = "routebalance"
    # under the serial_published deployment ladder arm, charge the warm
    # per-batch decision estimate as the per-request service time — the
    # policy scores a batch in one call, so serial deployment is not
    # its natural habitat, but the axis stays orthogonal
    serial_scoring_s = 0.004
    budget_clamp = True

    def __init__(self, cfg: RBConfig):
        self.cfg = cfg
        validate(cfg.weights)
        assert cfg.decision_backend in ("numpy", "jax", "fused",
                                        "megakernel"), \
            cfg.decision_backend
        assert cfg.window_coalesce >= 1, cfg.window_coalesce
        assert (cfg.window_coalesce == 1
                or cfg.decision_backend == "megakernel"), \
            "window_coalesce > 1 needs decision_backend='megakernel'"
        assert cfg.knn_backend in (None, "numpy", "jax", "pallas"), \
            cfg.knn_backend
        assert cfg.latency_mode in LATENCY_MODES, cfg.latency_mode
        assert 0.0 <= cfg.affinity_weight <= 1.0, cfg.affinity_weight
        sc = int(cfg.shard_cells or 0)
        assert sc >= 0 and (sc & (sc - 1)) == 0, \
            f"shard_cells must be a power of two, got {cfg.shard_cells}"
        assert sc <= 1 or cfg.decision_backend == "fused", \
            "shard_cells > 1 requires decision_backend='fused'"
        self.bundle = None
        self._fused = None                    # lazily-built FusedHotPath

    def engine_overrides(self) -> dict:
        # batch formation belongs to RBConfig: honor it on ANY engine
        # this policy is mounted on (registry path included), not just
        # the RouteBalance convenience class
        cfg = self.cfg
        out = dict(base_window=cfg.base_window, adaptive=cfg.adaptive,
                   fixed_batch=cfg.fixed_batch,
                   charge_compute=cfg.charge_compute)
        if cfg.recovery is not None:
            out["recovery"] = cfg.recovery
        return out

    def prepare(self, bundle, tiers: Sequence[Tier]):
        cfg = self.cfg
        if (cfg.knn_backend is not None
                and cfg.knn_backend != bundle.knn.backend):
            # rebind the estimator feed (e.g. the Pallas knn_topk kernel)
            # on a copy so a shared bundle is not mutated across schedulers
            bundle = EstimatorBundle(bundle.encoder,
                                     bundle.knn.with_backend(
                                         cfg.knn_backend),
                                     bundle.heads, bundle.model_names)
        self.bundle = bundle

    def on_attach(self, sim: ClusterSim):
        self._fused = None                    # new sim -> new roster

    def shed_verdict(self, req: Request, controller) -> bool:
        # policy-visible admission control (RBConfig.shed): the
        # no-shedding ablation admits everything even under overload
        if not self.cfg.shed:
            return False
        return controller.wants_shed(req.priority)

    def assign(self, batch: BatchView, cluster: ClusterSim
               ) -> AssignmentResult:
        """Dispatch the per-batch decision; the fused backend's payload
        is a LazyDecision (device arrays, fetched later); the
        staged backends' is already numpy."""
        if self.cfg.decision_backend in ("fused", "megakernel"):
            instances, res = self._decide_fused(batch, cluster)
            return AssignmentResult(instances, res)
        instances, choice, l_chosen = self._decide_staged(batch, cluster)
        return AssignmentResult(instances, Ready(choice, l_chosen))

    def assign_windows(self, batches: List[BatchView],
                       cluster: ClusterSim) -> List[AssignmentResult]:
        """K scheduler windows as ONE device dispatch (megakernel only:
        `FusedHotPath.decide_cols_multi`, grid=(K,)). All K windows
        decide against the same telemetry snapshot — exactly what K
        back-to-back `assign` calls see when telemetry has not moved
        between them, so coalescing is assignment-exact there while
        paying one kernel launch for K windows. Falls back to per-window
        `assign` for every other backend (and for K == 1)."""
        if (self.cfg.decision_backend != "megakernel"
                or len(batches) <= 1):
            return [self.assign(bv, cluster) for bv in batches]
        runner = self._fused_runner(cluster)
        slices = [bv.columns(self.bundle.encoder) for bv in batches]
        lazies = runner.decide_cols_multi(slices, cluster.tel)
        return [AssignmentResult(cluster.instances, lz)
                for lz in lazies]

    def _fused_runner(self, sim: ClusterSim):
        """The lazily-built FusedHotPath over this sim's roster — THE
        seam hierarchical policies interpose on (a sharded runner, a
        per-cell runner), shared by `assign` and `assign_windows`."""
        if not sim.tel.alive.any():
            raise RuntimeError("no alive instances to schedule onto")
        if self._fused is None:
            from .hotpath import FusedHotPath
            self._fused = FusedHotPath.for_bundle(
                self.bundle, sim.instances, self.cfg)
        return self._fused

    def _decide_fused(self, batch: BatchView, sim: ClusterSim):
        """Single-dispatch path: one jitted device program per batch
        over the full instance roster (dead instances masked), staged
        from the SoA ingest columns."""
        runner = self._fused_runner(sim)
        # direct callers (tests, benches) arrive without a column
        # slice: derive one, building ephemeral columns if needed
        cols, rows = batch.columns(self.bundle.encoder)
        return sim.instances, runner.decide_cols(cols, rows, sim.tel)

    def _decide_staged(self, batch: BatchView, sim: ClusterSim):
        cfg = self.cfg
        reqs = batch.reqs
        # candidate roster = the SCHEDULER-VISIBLE rows: tel.alive, not
        # inst.alive — the telemetry watchdog quarantines stale rows by
        # masking them in the mirror while the worker stays up, and the
        # staged backends must see exactly the roster the fused backend
        # masks (slot k <-> sim.instances[k] by construction)
        tel = sim.tel
        alive_rows = np.flatnonzero(tel.alive)
        instances = [sim.instances[int(k)] for k in alive_rows]
        I = len(instances)
        R = len(reqs)
        m_of_i = np.array([inst.model_idx for inst in instances])
        tiers_of_i = [inst.tier for inst in instances]
        cols, rows = batch.cols, batch.rows
        if cols is None:
            from repro.serving.request import batch_columns
            cols, rows = batch_columns(reqs)

        # 1. batched prompt-intrinsic estimation (one call; the ingest
        # embedding column skips the encoder when available)
        Q, L = self.bundle.predict_prompts(reqs, cols=cols, rows=rows)
        q_inst = Q[:, m_of_i]                            # (R, I)
        l_inst = L[:, m_of_i]

        # 2. telemetry seed from the columnar view (non-blocking)
        d = tel.pending[alive_rows].copy()
        b = np.maximum(tel.batch[alive_rows], 1.0)
        free = tel.free[alive_rows].copy()
        ctx = np.maximum(tel.ctx[alive_rows], 64.0)
        maxb = tel.max_batch[alive_rows].copy()

        # 3. one TPOT-head call per TIER (not per instance)
        tpot = np.zeros(I)
        if cfg.latency_mode == "static_prior":
            tpot = np.array([self.bundle.heads[ti.name].nominal_tpot
                             for ti in tiers_of_i])
        else:
            by_tier: Dict[str, List[int]] = {}
            for i, ti in enumerate(tiers_of_i):
                by_tier.setdefault(ti.name, []).append(i)
            for tname, idxs in by_tier.items():
                feats = np.stack([
                    tpot_features(b[i], d[i], ctx[i]) for i in idxs])
                tpot[idxs] = self.bundle.heads[tname].tpot_batch(
                    feats, learned=cfg.learned_tpot)

        # 4+5. budget admission (Eq. 2) + LPT-ordered greedy with dead
        # reckoning — either the numpy loop or the jitted decision core
        price_in = np.array([ti.price_in for ti in tiers_of_i])
        price_out = np.array([ti.price_out for ti in tiers_of_i])
        if cols is not None:
            budgets = cols.budget[rows]
            len_in = cols.len_in[rows]
        else:
            budgets = np.array([np.nan if r.budget is None else r.budget
                                for r in reqs])
            len_in = np.array([r.prompt.len_in for r in reqs], float)
        nominal = np.array([self.bundle.heads[ti.name].nominal_tpot
                            for ti in tiers_of_i])
        # prefix-cache affinity (serving.affinity): both staged arms
        # compute the SAME host-side float32 discount matrix — the
        # fused backend evaluates the identical integer-compare +
        # float32 math in-graph, so all three backends score reuse
        # bit-identically
        aff = None
        if cfg.affinity_weight > 0.0:
            from repro.serving.affinity import (hit_fraction,
                                                prompt_signatures)
            if cols is not None:
                req_sig = cols.prefix_sig[cols.prompt_row[rows]]
            else:
                req_sig = np.stack([prompt_signatures(r.prompt)
                                    for r in reqs])
            hit = hit_fraction(req_sig, len_in.astype(np.float32),
                               tel.prefix_sig[alive_rows].T, np)
            aff = np.float32(cfg.affinity_weight) * hit
        if cfg.decision_backend == "jax":
            from . import decision_jax
            choice, _ = decision_jax.decide(
                q_inst, l_inst, L.max(axis=1), tpot, nominal, d, b, free,
                maxb, budgets, len_in, price_in, price_out, cfg.weights,
                latency_mode=cfg.latency_mode, lpt=cfg.lpt,
                budget_filter=cfg.budget_filter, affinity=aff)
        else:
            # the reference loop evaluates the decision arithmetic in
            # float32 — the jitted cores' precision — so the quantized
            # Eq. 1 tie groups are identical across all three backends
            # (greedy_assign follows the dtype of its inputs)
            f32 = np.float32
            budgets32, len_in32 = budgets.astype(f32), len_in.astype(f32)
            pi32, po32 = price_in.astype(f32), price_out.astype(f32)
            if cfg.budget_filter:
                allowed, c_hat = admission_mask(budgets32, len_in32,
                                                l_inst, pi32, po32)
            else:
                allowed = np.ones((R, I), bool)
                c_hat = cost_matrix(len_in32, l_inst, pi32, po32)
            order = lpt_order(L.max(axis=1), enable=cfg.lpt)
            choice, _ = greedy_assign(
                order, q_inst.astype(f32), c_hat, l_inst.astype(f32),
                tpot.astype(f32), d.astype(f32), b.astype(f32),
                free.astype(f32), maxb.astype(f32),
                cfg.weights, allowed, latency_mode=cfg.latency_mode,
                nominal_tpot=nominal.astype(f32), affinity=aff)
        l_chosen = l_inst[np.arange(R), choice]
        return instances, choice, l_chosen


class RouteBalance(ServingEngine):
    """The paper's deployment of the RouteBalance policy: windowed
    amortized batch scoring on the shared `ServingEngine`. Kept as a
    class so the historical constructor — ``RouteBalance(RBConfig(),
    bundle, tiers)`` — and the hot-path probes used by the benches and
    the differential harness (`_decide_core`, `_fused`) stay stable."""

    def __init__(self, cfg: RBConfig, bundle: EstimatorBundle,
                 tiers: Sequence[Tier]):
        # RBConfig's batch-formation knobs reach the engine through
        # RouteBalancePolicy.engine_overrides
        super().__init__(RouteBalancePolicy(cfg), bundle, tiers,
                         EngineConfig(deployment="windowed"))
        self.cfg = cfg

    @property
    def _fused(self):
        """The policy's lazily-built FusedHotPath (diagnostics)."""
        return self.policy._fused

    def _decide_core(self, batch: List[Request]
                     ) -> Tuple[List[Instance], np.ndarray, np.ndarray]:
        """The pure per-batch decision (no dispatch): returns the
        candidate roster plus (choice (R,) indices into it, l_chosen
        (R,) predicted length at the chosen instance). This is the hot
        path `benchmarks/hotpath.py` measures; it fetches eagerly —
        the engine's windowed dispatch defers the fetch to the
        dispatch point instead."""
        res = self.policy.assign(BatchView(batch), self.sim)
        choice, l_chosen = res.fetch()
        return res.instances, choice, l_chosen
