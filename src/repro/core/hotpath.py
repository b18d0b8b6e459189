"""Single-dispatch fused hot path: the whole per-batch RouteBalance
decision as ONE jitted device program (§4.2/§6.3), fed by the
zero-allocation SoA ingest layer.

After PR 2/3 the fused program was already one dispatch per batch, but
the steady-state host path around it still did per-request Python work
and fresh allocations every batch: four list comprehensions to marshal
tokens/budgets/lengths, a fresh (Rb, Lb) token matrix + mask, a full
host→device re-upload of the (I,)×5 telemetry state whenever
``TelemetryArrays.version`` moved (i.e. on every batch under real
traffic, so the dead-reckoned carry branch was dead code), a per-batch
encoder forward over the padded token matrix, and a blocking
``np.asarray`` on the result. This module removes all of it:

  * **SoA ingest** — token ids, lengths, ``len_in`` and budgets live in
    ``repro.serving.request.RequestColumns`` built once at
    workload-generation time, and the prompt embeddings are memoized
    there too (the masked-pooling encoder is bitwise stable under
    batch/length padding, so embedding a prompt once at ingest equals
    the per-batch encode bit for bit). A decision batch is a row-index
    slice into those columns;
  * **preallocated staging** — per-pow2(R)-bucket host buffers, double
    buffered so writing batch N+1 never aliases batch N's in-flight
    transfer; staging is a handful of vectorized ``np.take`` gathers
    with zero Python-level per-request work and zero steady-state
    allocation (the token/mask staging of earlier PRs disappears
    entirely: tokens stay at ingest, the program starts from
    embeddings);
  * **incremental device telemetry** — the (d, b, free, ctx) state is a
    device-resident mirror of ``TelemetryArrays``; each batch uploads
    one (5, I) block — a write mask, then the four planes — and the
    jitted step takes the lanes the mask marks: the rows written since
    the last sync (``tel.dirty_rows``), or every lane on a reseed
    (roster-shape events, tracked by ``tel.roster_version``, or most of
    the roster dirty); a window with nothing written uploads no
    telemetry at all. The refreshed mirror is bitwise the
    numpy reference's reseed-per-batch host read — untouched rows'
    telemetry has not moved — so carry-forward is now the common case
    AND exact-parity-safe (the PR-2 semantics, which carried post-scan
    dead-reckoned state, only matched numpy when nothing on the
    cluster moved; that branch almost never fired and silently diverged
    when it did not);
  * **async dispatch** — ``decide_cols`` returns a ``LazyDecision``
    whose host fetch is deferred to the scheduler's dispatch point, so
    residual accounting and next-batch staging overlap device
    execution. The host transfers of the padded choice and l_chosen
    start at the launch and queue behind the program; the fetch slices
    to R in numpy, so no program but the step runs per batch. The
    carried mirror chains batch-to-batch on device through donated
    buffers without a host round trip.

Batch size R and roster size I are still bucketed to powers of two
(`bucket_pow2`) for O(log R · log I) compile variants; roster pad
columns stay permanently dead and instance death is an ``alive`` mask
(no recompile after a failure). Eq. 1 scores are epsilon-quantized in
the shared scoring math (`repro.core.scoring`), so the fused program
makes exactly the numpy reference's assignments on
randomized worlds (``tests/test_soak.py``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.estimators.gbm import pack_ensemble, predict_roster, \
    roster_tables
from repro.estimators.knn import topk_soft_lookup
from repro.serving.affinity import SIG_WIDTH, SKETCH_SLOTS, hit_fraction

from .budget import admission_math, cost_matrix
from .decision_jax import _greedy_scan, bucket_pow2, sharded_greedy_scan
from .trace import span


def _new_stats() -> Dict:
    """Host seconds per `rb.*` span of the runner (`core/trace.py`):
    `stage_s` (rb.stage), `telemetry_s` (rb.telemetry), `host_s` (the
    two together), `dispatch_s` (rb.dispatch), `device_s` (rb.wait) and
    `sync_s` (rb.copy). Counters: `uploads`, the host arrays the runner
    hands to the device (each numpy argument of the jitted step, the
    telemetry block of a reseed or delta sync, and the alive mask when
    the roster moved); `d2h`, the device-to-host transfers
    it starts (two per dispatch: the padded choice and l_chosen);
    `dirty_checks` and `dirty_rows_seen`, the syncs that read
    `tel.dirty_rows` and the rows they found dirty, before the
    mostly-dirty rule decides; then how each sync ended; `dead_cols`,
    the roster columns masked out (dead or quarantined) at each sync,
    summed over the syncs."""
    return {"calls": 0, "host_s": 0.0, "stage_s": 0.0, "telemetry_s": 0.0,
            "dispatch_s": 0.0, "device_s": 0.0, "sync_s": 0.0,
            "uploads": 0, "d2h": 0, "dirty_checks": 0, "dirty_rows_seen": 0,
            "full_reseed": 0,
            "roster_reseed": 0,        # full reseeds caused by roster churn
            "delta_sync": 0, "delta_rows": 0, "carry": 0, "dead_cols": 0}


def _host_arrays(args) -> int:
    """How many of a jitted call's arguments are host numpy arrays, each
    transferred to the device before the launch."""
    return sum(type(a) is np.ndarray for a in args)


def _apply_block(d, b, free, ctx, block):
    """The telemetry block into the mirror, under the `telemetry` scope:
    each lane that row 0 (the write mask) marks takes rows 1-4 (pending,
    batch, free, ctx); every other lane keeps the carried value."""
    with jax.named_scope("telemetry"):
        write = block[0] != 0.0
        return tuple(jnp.where(write, block[k], x)
                     for k, x in enumerate((d, b, free, ctx), 1))


class LazyDecision:
    """An in-flight fused decision: the step's padded device outputs,
    whose host transfers the runner started at the launch, fetched only
    when the caller needs the values (the dispatch point). `fetch()`
    waits for the transfers, then slices off the shape-padding rows in
    numpy — no device program — and returns fresh arrays, idempotently,
    so diagnostics may re-fetch. This is the fused policy's
    `AssignmentResult` payload (`repro.core.engine`): the engine's
    windowed dispatch overlaps its host bookkeeping with the device
    program and fetches last."""

    __slots__ = ("_choice", "_l", "_R", "_stats", "_out")

    def __init__(self, choice, l_chosen, R: int, stats: Dict):
        self._choice = choice
        self._l = l_chosen
        self._R = R              # real rows; the rest are shape padding
        self._stats = stats
        self._out: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def fetch(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._out is None:
            st = self._stats
            with span("rb.fetch"):
                with span("rb.wait", st, "device_s"):
                    choice, l_chosen = jax.device_get((self._choice,
                                                       self._l))
                with span("rb.copy", st, "sync_s"):
                    self._out = (np.array(choice[:self._R], np.int64),
                                 np.array(l_chosen[:self._R], np.float64))
        return self._out


class FusedHotPath:
    """Compiled once per (bundle, roster signature, decision config);
    one call = one scheduler batch = one device dispatch."""

    @staticmethod
    def for_bundle(bundle, instances, cfg) -> "FusedHotPath":
        """Cached constructor: repeated cells over the same bundle with
        an equivalent roster and config (e.g. a sweep of run_cell calls)
        reuse one compiled program instead of paying a fresh XLA compile
        per sim. The cache lives on the bundle, so its lifetime — and
        the validity of the closed-over index/head arrays — tracks the
        bundle's. Carried state is reset on every cache hit."""
        roster = tuple((i.tier.name, i.model_idx, i.tier.max_batch,
                        i.tier.price_in, i.tier.price_out)
                       for i in instances)
        backend = ("megakernel"
                   if getattr(cfg, "decision_backend", "fused")
                   == "megakernel" else "fused")
        key = (roster, backend, cfg.latency_mode, bool(cfg.lpt),
               bool(cfg.budget_filter), bool(cfg.learned_tpot),
               tuple(float(w) for w in cfg.weights),
               float(getattr(cfg, "affinity_weight", 0.0)),
               # hierarchical scheduling: the cell-sharded scan compiles
               # a different program, and per-cell engines (cell_tag)
               # each need their own carried telemetry mirror even when
               # their rosters happen to be signature-identical
               int(getattr(cfg, "shard_cells", 0) or 0),
               getattr(cfg, "cell_tag", None))
        cache = bundle.__dict__.setdefault("_fused_cache", {})
        runner = cache.get(key)
        if runner is None:
            runner = cache[key] = FusedHotPath(bundle, instances, cfg)
        else:
            runner.reset()
        return runner

    def __init__(self, bundle, instances, cfg):
        self._encoder = bundle.encoder      # ingest-time embedding only
        # "megakernel" swaps the traced stage pipeline for the single
        # Pallas dispatch (repro.kernels.decision_megakernel); every
        # other backend value (the default "fused" included) keeps the
        # staged-XLA body. All host machinery — staging, delta sync,
        # LazyDecision, pow2 bucketing — is shared, so the two traced
        # bodies differ ONLY inside _step_impl.
        self._backend = ("megakernel"
                         if getattr(cfg, "decision_backend", "fused")
                         == "megakernel" else "fused")
        if self._backend == "megakernel":
            from repro.kernels.ops import interpret_mode
            self._interpret = interpret_mode()
        knn = bundle.knn
        self._E = bundle.encoder.dim
        self._k = knn.k
        self._eps = knn.eps
        self._x = jnp.asarray(knn._x)
        self._xsq = jnp.asarray(knn._sq)
        self._qual = jnp.asarray(knn._quality)
        self._leng = jnp.asarray(knn._length)

        tier_names: List[str] = []
        for inst in instances:
            if inst.tier.name not in tier_names:
                tier_names.append(inst.tier.name)
        heads = [bundle.heads[t] for t in tier_names]
        # roster size is bucketed to a power of two, like R: pad columns
        # are permanently dead (never admitted, never scored), so
        # rosters of 65..128 instances share one compiled I=128 shape
        # and the scan geometry stays uniform across scenario sweeps
        I = len(instances)
        self._n_real = I
        self._Itot = bucket_pow2(I)
        self._Ipad = self._Itot - I
        tier_of_i = self._pad_i(np.array(
            [tier_names.index(i.tier.name) for i in instances],
            np.int32))
        self._tier_of_i = jnp.asarray(tier_of_i)
        self._m_of_i = jnp.asarray(self._pad_i(
            np.array([i.model_idx for i in instances], np.int32)))
        self._maxb = jnp.asarray(self._pad_i(
            np.array([i.tier.max_batch for i in instances], np.float32),
            fill=1.0))
        self._price_in = jnp.asarray(self._pad_i(
            np.array([i.tier.price_in for i in instances], np.float32)))
        self._price_out = jnp.asarray(self._pad_i(
            np.array([i.tier.price_out for i in instances], np.float32)))
        self._nominal = jnp.asarray(
            np.array([h.nominal_tpot for h in heads],
                     np.float32)[tier_of_i])

        self._mode = cfg.latency_mode
        self._lpt = bool(cfg.lpt)
        self._budget_filter = bool(cfg.budget_filter)
        self._weights = tuple(float(w) for w in cfg.weights)
        # cell-sharded scan (hierarchical scheduling): the pow2 column
        # axis splits into shard_cells contiguous blocks, combined with
        # exact max/argmax reductions — bitwise the single-controller
        # scan (see decision_jax.sharded_greedy_scan). The mesh comes
        # from the active shardctx when the launcher pinned one with a
        # matching "cell" axis, else launch.mesh.make_cell_mesh (which
        # degrades to None -> single-program emulation on hosts without
        # the devices).
        self._shard_cells = int(getattr(cfg, "shard_cells", 0) or 0)
        self._cell_mesh = None
        if self._shard_cells > 1:
            assert self._backend == "fused", \
                "shard_cells requires the fused backend (the megakernel" \
                " scan is a single monolithic dispatch)"
            assert self._Itot % self._shard_cells == 0, \
                (self._Itot, self._shard_cells)
            from repro.distributed.shardctx import current as _shardctx
            mesh, _ = _shardctx()
            if (mesh is not None and "cell" in mesh.axis_names
                    and mesh.shape["cell"] == self._shard_cells):
                self._cell_mesh = mesh
            else:
                from repro.launch.mesh import make_cell_mesh
                self._cell_mesh = make_cell_mesh(self._shard_cells)
        # prefix-affinity term: compiled in only when the weight is
        # nonzero — the disabled program is the pre-affinity program
        # verbatim (the dummy sig args below are dead inputs XLA drops),
        # so turning the feature off cannot perturb existing parity or
        # decide-time (perf-guarded in benchmarks/perf_guard.py)
        self._w_aff = float(getattr(cfg, "affinity_weight", 0.0))
        if self._w_aff > 0.0:
            # per-call upload of the instance sig plane: (Itot, 64)
            # int32 ≈ 32 KB at I=128 — double buffered like the other
            # staged inputs so a host write never aliases the previous
            # batch's in-flight transfer. Signatures ride their own
            # `tel.prefix_version` counter (sketch writes must not look
            # like telemetry heartbeats), so the plane is re-staged
            # every call rather than through the delta machinery.
            self._pstage = [
                np.zeros((self._Itot, SKETCH_SLOTS), np.int32),
                np.zeros((self._Itot, SKETCH_SLOTS), np.int32)]
            self._pflip = 0
        # device-resident, so the dead affinity inputs are no host
        # array of the call (`uploads` counts the host arrays)
        self._dummy_psig = jnp.zeros((1, 1), jnp.int32)
        self._dummy_plane = jnp.zeros((1, 1), jnp.int32)
        self._use_gbm = (cfg.latency_mode != "static_prior"
                         and cfg.learned_tpot)
        if self._use_gbm:
            # partial fits would silently diverge from the numpy
            # per-tier learned/nominal fallback — refuse instead
            assert all(h.model is not None for h in heads), \
                "fused backend needs every TPOT head fitted (or " \
                "learned_tpot=False): unfitted " + \
                str([t for t, h in zip(tier_names, heads)
                     if h.model is None])
            stacked = pack_ensemble([h.model for h in heads])
            self._gbm = stacked
            self._gbm_tables = dict(
                roster_tables(stacked, tier_of_i), lr=stacked["lr"],
                depth=stacked["depth"])
        # the telemetry mirror (d, b, free, ctx) is donated in and the
        # refreshed (pre-scan) mirror comes back out, so it chains
        # batch-to-batch on device; alive is read-only (re-uploaded on
        # roster events). args: emb 0, row_valid 1, budgets 2, len_in 3,
        # d 4, b 5, free 6, ctx 7, alive 8, telemetry block 9, psig 10,
        # sig_plane 11 (appended so donate indices stay fixed)
        self._step = jax.jit(self._step_impl, donate_argnums=(4, 5, 6, 7))
        # the telemetry block is one (5, Itot) shape in every sync arm
        # (the arms differ only in its write mask), so reseed, delta and
        # carry share one compiled program per R bucket and warming the
        # R buckets warms everything. Every arm hands the step a device
        # array there: jit keys its dispatch cache on host-vs-device
        # arguments, so a numpy block beside the carry's device block
        # would double the cache entries. Double buffered like staging;
        # pad lanes stay 0 (only real rows are ever written).
        self._tstage = [np.zeros((5, self._Itot), np.float32)
                        for _ in range(2)]
        self._tflip = 0
        self._noop_block = jnp.zeros((5, self._Itot), jnp.float32)
        self._stage: Dict[int, list] = {}    # Rb -> [bufset, bufset]
        self._sflip: Dict[int, int] = {}
        self.reset()                         # also installs fresh stats

    def _pad_i(self, x: np.ndarray, fill=0) -> np.ndarray:
        """Pad an (I,) per-instance vector out to the pow2 roster
        bucket."""
        if self._Ipad == 0:
            return x
        return np.concatenate(
            [x, np.full(self._Ipad, fill, x.dtype)])

    # -- traced bodies ------------------------------------------------------
    def _mega_stages(self, emb, row_valid, budgets, len_in,
                     d, b, free, ctx, alive, psig, sig_plane):
        """Stages 1–4 as the single Pallas megakernel dispatch. Returns
        (choice, est_T, l_chosen, d1, b1, f1)."""
        from repro.kernels.decision_megakernel import (decision_call,
                                                       dummy_gbm)
        if self._use_gbm:
            gf, gt, gl, gb = (self._gbm["feature"],
                              self._gbm["threshold"],
                              self._gbm["leaf"], self._gbm["base"])
            depth, lr = self._gbm["depth"], self._gbm["lr"]
        else:
            gf, gt, gl, gb = dummy_gbm()
            depth, lr = 1, 0.1
        with jax.named_scope("megakernel"):
            return decision_call(
                emb, row_valid, budgets, len_in, psig,
                d, b, free, ctx, alive,
                self._x, self._xsq, self._qual, self._leng,
                self._m_of_i, self._tier_of_i, self._maxb, self._price_in,
                self._price_out, self._nominal, sig_plane, gf, gt, gl, gb,
                k=self._k, eps=self._eps, weights=self._weights,
                latency_mode=self._mode, lpt=self._lpt,
                budget_filter=self._budget_filter, w_aff=self._w_aff,
                use_gbm=self._use_gbm, depth=depth, lr=lr,
                interpret=self._interpret)

    def _step_impl(self, emb, row_valid, budgets, len_in,
                   d, b, free, ctx, alive, block, psig, sig_plane):
        # 0. incremental telemetry: the lanes the block's mask marks
        # into the donated device mirror. The refreshed mirror is
        # bitwise a full host re-read — untouched rows' telemetry has
        # not moved since they were last synced — so every arm
        # preserves the numpy reference's reseed-per-batch semantics.
        d, b, free, ctx = _apply_block(d, b, free, ctx, block)

        if self._backend == "megakernel":
            # stages 1–4 fused into one Pallas dispatch; the refreshed
            # pre-scan mirror still carries forward exactly as below
            choice, est_T, l_chosen, d1, b1, f1 = self._mega_stages(
                emb, row_valid, budgets, len_in, d, b, free, ctx, alive,
                psig, sig_plane)
            return (choice, est_T, l_chosen, d, b, free, ctx,
                    d1, b1, f1)

        # each stage under a named scope (op metadata only: the
        # compiled program is the same), so a device trace splits the
        # program by stage

        # 1. prompt-intrinsic estimation: KNN top-k over the ingest
        # embedding column, all models at once
        with jax.named_scope("knn"):
            qual, leng = topk_soft_lookup(emb, self._x, self._xsq,
                                          self._qual, self._leng,
                                          self._k, self._eps)  # (R, M)
            q_inst = qual[:, self._m_of_i]                     # (R, I)
            l_inst = leng[:, self._m_of_i]
            # pad rows order strictly after every real request
            pred_len_max = jnp.where(row_valid, leng.max(axis=1), -1e30)

        # 2. state-dependent TPOT: all per-tier heads in one packed gather
        with jax.named_scope("tpot"):
            b_eff = jnp.maximum(b, 1.0)
            ctx_eff = jnp.maximum(ctx, 64.0)
            if self._use_gbm:
                tpot = jnp.maximum(
                    predict_roster(self._gbm_tables,
                                   [b_eff, d, ctx_eff, b_eff * ctx_eff])[0],
                    1e-4)
            else:
                tpot = self._nominal

        # 3. Eq. 2 admission over the alive roster
        with jax.named_scope("admission"):
            budgets = budgets.astype(jnp.float32)
            len_in = len_in.astype(jnp.float32)
            if self._budget_filter:
                allowed, c_hat = admission_math(
                    budgets, len_in, l_inst, self._price_in,
                    self._price_out, jnp, valid=alive)
            else:
                c_hat = cost_matrix(len_in, l_inst, self._price_in,
                                    self._price_out, jnp)
                allowed = jnp.broadcast_to(alive[None, :], c_hat.shape)

            # 3b. prefix-affinity: matched-fraction hit against the
            # mirrored per-instance sig planes, zeroed for
            # dead/quarantined columns (alive is the same mask Eq. 2
            # admission uses, so a quarantined instance can neither be
            # picked NOR attract affinity credit). Python-level branch:
            # w_aff == 0 compiles the term out and the dummy
            # psig/sig_plane inputs are dead.
            if self._w_aff > 0.0:
                hit = hit_fraction(psig, len_in, sig_plane.T, jnp)
                hit = jnp.where(alive[None, :], hit, jnp.float32(0.0))
                aff = jnp.float32(self._w_aff) * hit
            else:
                aff = None

        # 4. LPT order + dead-reckoned greedy scan (Eq. 1 per request)
        with jax.named_scope("scan"):
            if self._lpt:
                order = jnp.argsort(-pred_len_max, stable=True)
            else:
                order = jnp.arange(q_inst.shape[0])
            choice, est_T, (d1, b1, f1) = self._scan(
                order, q_inst, c_hat, l_inst, tpot, d, b_eff, free,
                allowed, row_valid, aff)
            l_chosen = jnp.take_along_axis(l_inst, choice[:, None],
                                           axis=1)[:, 0]
        # the refreshed pre-scan mirror (d, b, free, ctx) is the carried
        # state; (d1, b1, f1) is the post-scan dead-reckoned view, kept
        # for diagnostics/invariant checks only — the next batch reseeds
        # from telemetry just like the numpy reference
        return (choice, est_T, l_chosen, d, b, free, ctx, d1, b1, f1)

    def _scan(self, order, q_inst, c_hat, l_inst, tpot, d, b_eff, free,
              allowed, row_valid, aff):
        """Stage-4 greedy scan, factored so the scan strategy is the
        one seam hierarchical runners interpose on: the
        single-controller program traces `_greedy_scan`; with
        ``shard_cells > 1`` the bitwise-identical cell-sharded
        decomposition runs instead (single-program emulation or
        shard_map over the cell mesh)."""
        if self._shard_cells > 1:
            return sharded_greedy_scan(
                order, q_inst, c_hat, l_inst, tpot, self._nominal,
                d, b_eff, free, self._maxb, self._weights, allowed,
                self._mode, row_valid=row_valid, affinity=aff,
                n_cells=self._shard_cells, mesh=self._cell_mesh)
        return _greedy_scan(
            order, q_inst, c_hat, l_inst, tpot, self._nominal,
            d, b_eff, free, self._maxb, self._weights, allowed,
            self._mode, row_valid=row_valid, affinity=aff)

    # -- host side ----------------------------------------------------------
    def reset(self):
        """Forget carried device state (new sim / fresh roster) and
        start a fresh stats window, so `stats` reads as per-cell
        counters rather than accumulating across cache-hit reuses. A
        `LazyDecision` still in flight keeps a reference to the old
        window and is unaffected."""
        # the (d, b, free, ctx) mirror starts device-resident, so the
        # first sync is an ordinary all-lanes block
        self._state: Tuple = tuple(jnp.zeros(self._Itot, jnp.float32)
                                   for _ in range(4))
        self._post_state: Optional[Tuple] = None   # post-scan (d, b, free)
        self._alive_dev = None
        self._dead = 0                        # masked columns in the mirror
        self._seen_tel = None                 # identity of the synced view
        self._seen_version = -1
        self._seen_roster = -1
        self.stats = _new_stats()

    def compile_count(self) -> int:
        """Number of XLA programs compiled for the fused step — one per
        pow2 R bucket seen. Roster events
        (fail/recover/autoscale) flip the alive mask and reseed the
        mirror but must NOT add entries here: that is the
        no-recompile-on-scale contract the elastic soak asserts
        (`compile_count() == len(distinct R buckets)`)."""
        return int(self._step._cache_size())

    def _stage_buffers(self, Rb: int) -> Dict[str, np.ndarray]:
        """The preallocated host staging set for the pow2 batch bucket.
        Two sets alternate per bucket so writing batch N+1 can never
        alias batch N's still-in-flight transfer (the async-dispatch
        window is one batch deep)."""
        pair = self._stage.get(Rb)
        if pair is None:
            def mk():
                buf = {"emb": np.zeros((Rb, self._E), np.float32),
                       "prow": np.zeros(Rb, np.int32),
                       "budgets": np.full(Rb, np.nan, np.float32),
                       "len_in": np.zeros(Rb, np.float32),
                       "rv": np.zeros(Rb, bool)}
                if self._w_aff > 0.0:
                    buf["psig"] = np.zeros((Rb, SIG_WIDTH), np.int32)
                return buf
            pair = self._stage[Rb] = [mk(), mk()]
            self._sflip[Rb] = 0
        self._sflip[Rb] ^= 1
        return pair[self._sflip[Rb]]

    def _telemetry_block(self, tel, rows) -> jax.Array:
        """Fill the next host block from `tel` and upload it: every lane
        marked when `rows` is None (a reseed: pad lanes take 0), else
        only `rows`. The four planes are copied whole either way, so
        the arms differ only in the mask."""
        self._tflip ^= 1
        blk = self._tstage[self._tflip]
        n = self._n_real
        blk[1, :n] = tel.pending
        blk[2, :n] = tel.batch
        blk[3, :n] = tel.free
        blk[4, :n] = tel.ctx
        if rows is None:
            blk[0] = 1.0
        else:
            blk[0] = 0.0
            blk[0, rows] = 1.0
        self.stats["uploads"] += 1
        return jax.device_put(blk)

    def _sync_state(self, tel) -> Tuple:
        """Assemble the telemetry-state args for `_step`: the carried
        device mirror, the alive mask and the telemetry block.

        Full reseed (every lane marked) happens only on the first batch,
        after `reset()`, on roster-shape events (`tel.roster_version`
        moved: a fail or recover flipped the alive mask), or when most
        of the roster is dirty. The common steady-state case is the
        delta arm: only rows with ``tel.last_write > seen_version`` are
        marked; with none, the carry arm uploads nothing and hands over
        a device-resident block that writes nothing. Alive is uploaded
        only when the roster or the telemetry object changed. Either
        way the state handed to the scan equals the numpy reference's
        fresh host read of `tel` bit for bit — which is what keeps the
        fused backend in exact assignment parity (regression-tested in
        ``tests/test_ingest.py``; the PR-2 semantics of carrying
        post-scan dead-reckoned state across batches did NOT have this
        property and is gone)."""
        st = self.stats
        rows = None
        # freshness is keyed to the telemetry OBJECT, not just its
        # counters: a caller that swaps in a new sim's TelemetryArrays
        # (rb.sim = ClusterSim(...) without attach()) must reseed — the
        # new view's counters can look "older" than the mirror's and
        # would otherwise silently carry the previous cluster's state
        if tel is self._seen_tel:
            if tel.roster_version == self._seen_roster:
                rows = tel.dirty_rows(self._seen_version)
                st["dirty_checks"] += 1
                st["dirty_rows_seen"] += len(rows)
                if 2 * len(rows) > self._n_real:
                    rows = None              # mostly dirty: reseed outright
            else:
                # fail/recover/autoscale flipped the alive mask: the
                # reseed is roster-caused — kill() deliberately does not
                # stamp last_write, so a delta read would miss the dead
                # row; this counter is what lets the elastic soak assert
                # scale events resync WITHOUT recompiling
                st["roster_reseed"] += 1
        self._seen_version = tel.version
        if (tel is not self._seen_tel
                or tel.roster_version != self._seen_roster):
            # the mask changes only with roster_version
            self._seen_tel = tel
            self._seen_roster = tel.roster_version
            self._alive_dev = jnp.asarray(
                self._pad_i(np.asarray(tel.alive), fill=False))
            self._dead = self._n_real - int(np.count_nonzero(tel.alive))
            st["uploads"] += 1
        st["dead_cols"] += self._dead
        if rows is None:
            st["full_reseed"] += 1
            block = self._telemetry_block(tel, None)
        elif len(rows) == 0:
            st["carry"] += 1
            block = self._noop_block
        else:
            st["delta_sync"] += 1
            st["delta_rows"] += len(rows)
            block = self._telemetry_block(tel, rows)
        return self._state + (self._alive_dev, block)

    def decide_cols(self, cols, rows: np.ndarray, tel) -> LazyDecision:
        """One scheduler batch as a row slice into the SoA ingest
        columns: stage via vectorized gathers into the preallocated
        double-buffered host set, sync the device telemetry mirror
        (the dirty rows in the common case), dispatch the single fused
        program, and hand back a `LazyDecision` so the caller's host
        work overlaps device execution."""
        assert cols.emb is not None, \
            "RequestColumns.ensure_embeddings must run before decide"
        st = self.stats
        st["calls"] += 1
        with span("rb.stage", st, "stage_s") as staged:
            R = len(rows)
            s = self._stage_buffers(bucket_pow2(R))
            np.take(cols.prompt_row, rows, out=s["prow"][:R])
            np.take(cols.emb, s["prow"][:R], axis=0, out=s["emb"][:R])
            s["emb"][R:] = 0.0
            s["budgets"][:R] = cols.budget[rows]
            s["budgets"][R:] = np.nan
            s["len_in"][:R] = cols.len_in[rows]
            s["len_in"][R:] = 0.0
            s["rv"][:R] = True
            s["rv"][R:] = False
            if self._w_aff > 0.0:
                np.take(cols.prefix_sig, s["prow"][:R], axis=0,
                        out=s["psig"][:R])
                s["psig"][R:] = 0
                self._pflip ^= 1
                plane = self._pstage[self._pflip]
                plane[:self._n_real] = tel.prefix_sig
                psig = s["psig"]
            else:
                psig, plane = self._dummy_psig, self._dummy_plane
        with span("rb.telemetry", st, "telemetry_s") as synced:
            state_args = self._sync_state(tel)
        st["host_s"] += staged.seconds + synced.seconds
        args = (s["emb"], s["rv"], s["budgets"], s["len_in"],
                *state_args, psig, plane)
        st["uploads"] += _host_arrays(args)
        with span("rb.dispatch", st, "dispatch_s"):
            out = self._step(*args)
            self._state = out[3:7]           # refreshed pre-scan mirror
            # the fetch's transfers queue behind the program now, so
            # they overlap each other and the host's work until the fetch
            out[0].copy_to_host_async()
            out[2].copy_to_host_async()
            st["d2h"] += 2
        self._post_state = out[7:10]         # post-scan (diagnostics)
        return LazyDecision(out[0], out[2], R, st)

    def decide(self, batch, tel) -> Tuple[np.ndarray, np.ndarray]:
        """Legacy AoS entry (direct callers, tests): derive the column
        slice from the request list — ephemeral non-stamping columns if
        the batch has no shared stream — then fetch eagerly. Returns
        (choice (R,) int64 indexing the FULL instance roster, l_chosen
        (R,))."""
        from repro.serving.request import RequestColumns
        cols, rows = RequestColumns.for_batch(batch, self._encoder)
        return self.decide_cols(cols, rows, tel).fetch()
