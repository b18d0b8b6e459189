"""Jitted full-parity decision core: the whole per-batch RouteBalance
decision as one array program (§4).

The numpy production loop (`assignment.greedy_assign`) walks the batch
request-by-request in Python; this module runs the identical math —
Eq. 1 scoring with per-request normalization (`scoring.masked_score`),
Eq. 2 budget admission (`budget.admission_math`), all four
``latency_mode`` isolation arms, LPT ordering and the dead-reckoned
state updates — as a single jitted ``lax.scan``, selectable in
production via ``RBConfig(decision_backend="jax")``.

Two jitted entry points:

  * ``greedy_core``  — the scan alone (order/mask precomputed), the
    drop-in twin of ``greedy_assign``; ``greedy_assign_jax`` delegates
    here.
  * ``decide_batch`` — the full per-batch pipeline (LPT order + Eq. 2
    admission + scan) traced end-to-end; ``decide`` is the numpy-in /
    numpy-out wrapper the scheduler calls.

The estimator step that feeds this core (batched KNN over prompt
embeddings) runs through the Pallas ``knn_topk`` kernel when the bundle
is built with ``KNNEstimator(backend="pallas")`` or the scheduler is
configured with ``RBConfig(knn_backend="pallas")``.

Differential parity with the numpy loop is asserted in
``tests/test_decision_parity.py`` across every mode arm. The math here
is float32 (the jit default) while numpy runs float64; the shared
scoring math epsilon-quantizes Eq. 1 scores (`repro.core.scoring`), so
sub-quantum float noise collapses to exact, identically-broken ties in
both precisions and the randomized soak asserts three-way assignment
parity on every seed with no pinned exclusions (``tests/test_soak.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .budget import admission_math, cost_matrix
from .scoring import affinity_discount, masked_score, quantize_scores

LATENCY_MODES = ("full", "off_reactive", "off_predictive", "static_prior")


def bucket_pow2(n: int, lo: int = 8) -> int:
    """Round a dynamic size up to the next power of two (floor `lo`) so
    jitted programs compile O(log) shape variants instead of one per
    size."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def first_index(hit):
    """Column of the first True along the trailing axis, keepdims (the
    tie order of argmax/argmin); all-False gives the axis length.
    Written as compare + min so it lowers inside a Mosaic kernel."""
    iota = jax.lax.broadcasted_iota(jnp.int32, hit.shape, hit.ndim - 1)
    return jnp.min(jnp.where(hit, iota, hit.shape[-1]), axis=-1,
                   keepdims=True)


def greedy_step(q_r, c_r, l_r, allowed_r, aff_r, valid_r, d, b, free, *,
                tpot, nominal_tpot, b0, max_batch, weights,
                latency_mode):
    """One greedy-scan step: Eq. 1 score for one request over the live
    dead-reckoned state, the pick, and the state update. THE one
    definition of the per-step arithmetic — `_greedy_scan`'s lax.scan
    (staged-jax and fused-XLA backends) and the Pallas megakernel's
    in-kernel fori_loop (`repro.kernels.decision_megakernel`) both
    trace this body, which is what makes their dead-reckoned carries
    bitwise identical by construction rather than by luck.

    The request's row of each (R, I) plane comes in already selected
    (``q_r``/``c_r``/``l_r``/``allowed_r``/``aff_r``, ``aff_r`` None
    when affinity is off) with the same shape as the state ``d``/``b``/
    ``free`` — (I,) in the scan, (1, I) in the kernel; ``valid_r`` is
    the request's row-valid flag. The pick and the scatter-updates are
    spelled as compare/select over the candidate axis (no gather, no
    scatter), which is bitwise the indexed form and lowers in Mosaic.

    Returns (d, b, free, i (int32 pick), est (float32 latency)), i and
    est with a kept trailing axis of size 1."""
    wq, wl, wc = weights
    wait = jnp.where(free > 0, 0.0, d / jnp.maximum(b, 1.0))
    tpot_eff = tpot * jnp.maximum(b / b0, 1.0)
    if latency_mode == "static_prior":
        T = nominal_tpot * l_r
    else:
        T = tpot_eff * (wait + l_r)
    if aff_r is not None:
        T = affinity_discount(T, aff_r, jnp)
    if latency_mode in ("off_reactive", "off_predictive"):
        s = masked_score(q_r, c_r, T, (wq, 0.0, wc), allowed_r, jnp)
        # model score is instance-blind: tie-break within winner
        # model. The numpy loop subtracts 1e-9 * normalized tie in
        # float64; that term is below float32 eps for O(1) scores,
        # so realize the same order explicitly — least tie metric
        # among the score-tied candidates. Scores arrive
        # epsilon-quantized from masked_score, so the tie groups
        # are identical across float32/float64 backends.
        tie = (d + b) if latency_mode == "off_reactive" else T
        tn = tie / jnp.maximum(jnp.max(tie, axis=-1, keepdims=True), 1e-9)
        v = jnp.where(s >= jnp.max(s, axis=-1, keepdims=True), tn, jnp.inf)
        i = first_index(v == jnp.min(v, axis=-1, keepdims=True))
    else:
        s = masked_score(q_r, c_r, T, (wq, wl, wc), allowed_r, jnp)
        i = first_index(s == jnp.max(s, axis=-1, keepdims=True))
    pick = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1) == i
    est = jnp.max(jnp.where(pick, T, -jnp.inf), axis=-1, keepdims=True)
    # dead reckoning: the chosen instance's pending work grows by L̂
    d = jnp.where(pick, d + jnp.where(valid_r, l_r, 0.0), d)
    has_free = (free > 0) & valid_r
    free = jnp.where(pick, free + -jnp.where(has_free, 1.0, 0.0), free)
    b = jnp.where(pick & has_free, jnp.minimum(b + 1.0, max_batch), b)
    return d, b, free, i, est


def _greedy_scan(order, q_inst, c_hat, l_inst, tpot, nominal_tpot,
                 d, b, free, max_batch, weights, allowed,
                 latency_mode: str, row_valid=None, affinity=None):
    """Traced body shared by both entry points. Mirrors
    ``assignment.greedy_assign`` operation-for-operation.

    ``row_valid`` (R,) optionally marks shape-padding rows: invalid rows
    still pick (their choices are dropped by the caller) but apply NO
    dead-reckoning update, so callers that carry the post-scan state
    across batches (the fused hot path) don't accumulate phantom load.
    Defaults to all-valid, which is bitwise the original behavior.

    ``affinity`` (R, I) optionally carries the prefix-reuse discount
    (affinity_weight x matched-prefix fraction): T scales by
    (1 - affinity) before scoring/tie-break, identically to the numpy
    loop. None compiles the term out entirely."""
    b0 = jnp.maximum(b, 1.0)            # snapshot batch (TPOT reference)
    if row_valid is None:
        row_valid = jnp.ones(q_inst.shape[0], bool)

    def step(state, r):
        d, b, free = state
        d, b, free, i, est = greedy_step(
            q_inst[r], c_hat[r], l_inst[r], allowed[r],
            None if affinity is None else affinity[r], row_valid[r],
            d, b, free, tpot=tpot, nominal_tpot=nominal_tpot, b0=b0,
            max_batch=max_batch, weights=weights,
            latency_mode=latency_mode)
        return (d, b, free), (i[0], est[0])

    init = (d, b, free)
    (d, b, free), (picks, ests) = jax.lax.scan(step, init, order)
    # scan emits in LPT order; scatter back to request order
    choice = jnp.zeros_like(picks).at[order].set(picks)
    est_T = jnp.zeros_like(ests).at[order].set(ests)
    return choice, est_T, (d, b, free)


def _f(x):
    return jnp.asarray(x, jnp.float32)


@functools.partial(jax.jit, static_argnames=("latency_mode",))
def greedy_core(order, q_inst, c_hat, l_inst, tpot, nominal_tpot,
                d, b, free, max_batch, weights, allowed,
                latency_mode: str = "full", affinity=None):
    """Jitted greedy pass over a precomputed order + admission mask."""
    choice, est_T, state = _greedy_scan(
        jnp.asarray(order), _f(q_inst), _f(c_hat), _f(l_inst), _f(tpot),
        _f(nominal_tpot), _f(d), _f(b), _f(free), _f(max_batch),
        weights, jnp.asarray(allowed, bool), latency_mode,
        affinity=None if affinity is None else _f(affinity))
    return choice, est_T


@functools.partial(jax.jit, static_argnames=("latency_mode", "lpt",
                                             "budget_filter"))
def decide_batch(q_inst, l_inst, pred_len_max, tpot, nominal_tpot,
                 d, b, free, max_batch, budgets, len_in,
                 price_in, price_out, weights,
                 latency_mode: str = "full", lpt: bool = True,
                 budget_filter: bool = True, affinity=None):
    """The whole per-batch decision, traced end-to-end.

    q_inst/l_inst: (R, I) per-instance quality / predicted length;
    pred_len_max: (R,) max predicted length over *models* (LPT key);
    tpot/nominal_tpot/d/b/free/max_batch: (I,) instance state;
    budgets (R,) with nan = unconstrained; len_in (R,);
    price_in/price_out (I,); affinity optionally (R, I) prefix-reuse
    discount. Returns (choice (R,), est_T (R,), c_hat (R, I),
    allowed (R, I)).
    """
    q_inst, l_inst = _f(q_inst), _f(l_inst)
    budgets, len_in = _f(budgets), _f(len_in)
    price_in, price_out = _f(price_in), _f(price_out)
    R = q_inst.shape[0]
    if lpt:
        order = jnp.argsort(-_f(pred_len_max), stable=True)
    else:
        order = jnp.arange(R)
    if budget_filter:
        allowed, c_hat = admission_math(budgets, len_in, l_inst,
                                        price_in, price_out, jnp)
    else:
        c_hat = cost_matrix(len_in, l_inst, price_in, price_out, jnp)
        allowed = jnp.ones(c_hat.shape, bool)
    choice, est_T, _ = _greedy_scan(
        order, q_inst, c_hat, l_inst, _f(tpot), _f(nominal_tpot),
        _f(d), _f(b), _f(free), _f(max_batch), weights, allowed,
        latency_mode,
        affinity=None if affinity is None else _f(affinity))
    return choice, est_T, c_hat, allowed


def decide(q_inst: np.ndarray, l_inst: np.ndarray,
           pred_len_max: np.ndarray, tpot: np.ndarray,
           nominal_tpot: np.ndarray, d: np.ndarray, b: np.ndarray,
           free: np.ndarray, max_batch: np.ndarray,
           budgets: np.ndarray, len_in: np.ndarray,
           price_in: np.ndarray, price_out: np.ndarray, weights,
           latency_mode: str = "full", lpt: bool = True,
           budget_filter: bool = True,
           affinity: Optional[np.ndarray] = None
           ) -> Tuple[np.ndarray, np.ndarray]:
    """numpy-in / numpy-out wrapper for the scheduler hot path.

    Batches are padded up to the next power of two so the jit cache sees
    O(log R) distinct shapes instead of one per batch size. Padding is
    parity-safe: pad rows carry a -inf LPT key so they scan strictly
    after every real request — their dead-reckoning updates can only
    affect later (i.e. other pad) steps — and their choices are dropped.
    """
    R = q_inst.shape[0]
    Rp = bucket_pow2(R)
    if Rp != R:
        pad = Rp - R
        q_inst = np.pad(np.asarray(q_inst, float), ((0, pad), (0, 0)))
        l_inst = np.pad(np.asarray(l_inst, float), ((0, pad), (0, 0)))
        pred_len_max = np.concatenate(
            [np.asarray(pred_len_max, float), np.full(pad, -1e30)])
        budgets = np.concatenate(
            [np.asarray(budgets, float), np.full(pad, np.nan)])
        len_in = np.concatenate(
            [np.asarray(len_in, float), np.zeros(pad)])
        if affinity is not None:
            affinity = np.pad(np.asarray(affinity, np.float32),
                              ((0, pad), (0, 0)))
    weights = tuple(float(w) for w in weights)
    choice, est_T, _, _ = decide_batch(
        q_inst, l_inst, pred_len_max, tpot, nominal_tpot, d, b, free,
        max_batch, budgets, len_in, price_in, price_out, weights,
        latency_mode=latency_mode, lpt=lpt, budget_filter=budget_filter,
        affinity=affinity)
    return (np.asarray(choice[:R], np.int64),
            np.asarray(est_T[:R], np.float64))


# ---------------------------------------------------------------------------
# Cell-sharded greedy scan (hierarchical scheduling, ROADMAP item 1)
# ---------------------------------------------------------------------------
#
# `sharded_greedy_scan` is the cell-partitioned twin of `_greedy_scan`:
# the padded instance axis splits into `n_cells` contiguous blocks
# ("cells") and each step runs the per-instance arithmetic per block,
# combining across blocks with exact reductions only. The decomposition
# is bitwise-exact by construction, not by tolerance:
#
#   * every cross-instance reduction in `greedy_step` is a max / argmax
#     / argmin (the Eq. 1 normalizers cmax/tmax, s.max(), tie.max());
#     a max over the full axis equals the max of per-block maxima, with
#     no reassociation of additions anywhere;
#   * first-index argmax semantics survive the split: each block that
#     attains the global max contributes `block_offset + local_argmax`
#     (its own first attaining column) and the global winner is the
#     minimum of those, i.e. the globally-first attaining column;
#   * the per-step elementwise chain (wait/tpot_eff/T/score) is the
#     identical expression in the identical operation order as
#     `greedy_step`, evaluated on each block's slice of the same
#     float32 inputs; scores pass through the shared epsilon
#     quantization, which is what already makes numpy == jax == fused
#     exact across program boundaries;
#   * dead-reckoning updates land via drop-mode scatters so non-winner
#     cells are untouched bit-for-bit (no +0.0 writes that could flip a
#     -0.0).
#
# Two execution strategies share one step definition
# (`cell_greedy_step`), differing only in how the cross-cell reductions
# are spelled:
#
#   * mesh=None: single-program emulation — the cell axis is an array
#     dimension ((R, I) -> (R, C, Ic)) and the combines are reductions
#     over it. Runs anywhere, any cell count.
#   * mesh with a "cell" axis (see `repro.launch.mesh.make_cell_mesh`):
#     the same body under `shard_map`, one block per device, combines
#     as pmax/pmin/psum collectives (`repro.launch.sharding.cell_specs`
#     pins the layout). This is the arm that lets one logical decision
#     span cells when the mesh has the devices.


def _local_max(x):
    return jnp.max(x, axis=-1, keepdims=True)


def cell_greedy_step(r, d, b, free, *, q_inst, c_hat, l_inst, tpot,
                     nominal_tpot, b0, max_batch, weights, allowed,
                     latency_mode, row_valid, affinity, offs,
                     gmax, gmin, gsum):
    """One greedy step over cell-sharded state. All per-instance arrays
    carry a leading cell axis: (C, Ic) state, (R, C, Ic) per-request
    planes (C is the local cell count — `n_cells` in the single-program
    emulation, 1 per device under shard_map). `offs` (C, 1) int32 is
    each block's global column offset; gmax/gmin/gsum reduce a (C, 1)
    per-cell scalar across ALL cells (array reduction or collective).

    Mirrors `greedy_step` operation-for-operation; returns
    (d, b, free, i (int32 GLOBAL pick), est (float32))."""
    wq, wl, wc = weights
    Ic = d.shape[-1]
    rows = jnp.arange(d.shape[0])
    wait = jnp.where(free > 0, 0.0, d / jnp.maximum(b, 1.0))
    tpot_eff = tpot * jnp.maximum(b / b0, 1.0)
    if latency_mode == "static_prior":
        T = nominal_tpot * l_inst[r]
    else:
        T = tpot_eff * (wait + l_inst[r])
    if affinity is not None:
        T = affinity_discount(T, affinity[r], jnp)
    mask = allowed[r]
    q_r, c_r = q_inst[r], c_hat[r]
    neg = -jnp.inf
    # masked_score with GLOBAL normalizers: per-cell max of the masked
    # plane, cross-cell max, then the same maximum(., eps) clamp — the
    # identical value masked_score computes over the full axis.
    cmax = jnp.maximum(gmax(_local_max(jnp.where(mask, c_r, neg))), 1e-12)
    if latency_mode in ("off_reactive", "off_predictive"):
        sw_l = 0.0
    else:
        sw_l = wl
    tmax = jnp.maximum(gmax(_local_max(jnp.where(mask, T, neg))), 1e-12)
    s = wq * q_r + wc * (1.0 - c_r / cmax) + sw_l * (1.0 - T / tmax)
    s = jnp.where(mask, quantize_scores(s, jnp), neg)
    big = jnp.int32(2 ** 30)
    if latency_mode in ("off_reactive", "off_predictive"):
        # instance-blind model score: tie-break by least normalized tie
        # metric among the score-tied candidates (see greedy_step)
        tie = (d + b) if latency_mode == "off_reactive" else T
        tn = tie / jnp.maximum(gmax(_local_max(tie)), 1e-9)
        smax = gmax(_local_max(s))
        v = jnp.where(s >= smax, tn, jnp.inf)
        vloc = jnp.min(v, axis=-1, keepdims=True)
        aloc = jnp.argmin(v, axis=-1).astype(jnp.int32)
        vglob = gmin(vloc)
        cand = jnp.where(vloc == vglob, offs + aloc[:, None], big)
    else:
        sloc = _local_max(s)
        aloc = jnp.argmax(s, axis=-1).astype(jnp.int32)
        smax = gmax(sloc)
        cand = jnp.where(sloc == smax, offs + aloc[:, None], big)
    i = gmin(cand)[0, 0]                      # global first attaining col
    li = jnp.clip(i - offs[:, 0], 0, Ic - 1)  # winner's local column
    in_cell = (i >= offs[:, 0]) & (i < offs[:, 0] + Ic)
    # est = T at the winner: exactly one cell contributes, rest add 0.0
    est = gsum(jnp.where(in_cell, T[rows, li], 0.0)[:, None])[0, 0]
    # dead reckoning on the winner cell only; drop-mode scatters keep
    # every other cell's state bit-identical
    upd = in_cell & row_valid[r]
    sc = jnp.where(upd, li, Ic)               # Ic = out of range -> drop
    d = d.at[rows, sc].add(l_inst[r][rows, li], mode="drop")
    has_free = (free[rows, li] > 0) & upd
    scf = jnp.where(has_free, li, Ic)
    free = free.at[rows, scf].add(-1.0, mode="drop")
    b = b.at[rows, scf].set(
        jnp.minimum(b[rows, li] + 1.0, max_batch[rows, li]), mode="drop")
    return d, b, free, i.astype(jnp.int32), est


def cell_greedy_scan(order, q_inst, c_hat, l_inst, tpot, nominal_tpot,
                     d, b, free, max_batch, weights, allowed,
                     latency_mode: str, row_valid=None, affinity=None,
                     *, offs, gmax, gmin, gsum):
    """`_greedy_scan` over cell-sharded arrays (see `cell_greedy_step`
    for shapes). Returns (choice (R,) GLOBAL columns, est_T (R,),
    (d, b, free) still cell-sharded)."""
    b0 = jnp.maximum(b, 1.0)            # snapshot batch (TPOT reference)
    if row_valid is None:
        row_valid = jnp.ones(q_inst.shape[0], bool)

    def step(state, r):
        d, b, free = state
        d, b, free, i, est = cell_greedy_step(
            r, d, b, free, q_inst=q_inst, c_hat=c_hat, l_inst=l_inst,
            tpot=tpot, nominal_tpot=nominal_tpot, b0=b0,
            max_batch=max_batch, weights=weights, allowed=allowed,
            latency_mode=latency_mode, row_valid=row_valid,
            affinity=affinity, offs=offs, gmax=gmax, gmin=gmin,
            gsum=gsum)
        return (d, b, free), (i, est)

    init = (d, b, free)
    (d, b, free), (picks, ests) = jax.lax.scan(step, init, order)
    choice = jnp.zeros_like(picks).at[order].set(picks)
    est_T = jnp.zeros_like(ests).at[order].set(ests)
    return choice, est_T, (d, b, free)


def sharded_greedy_scan(order, q_inst, c_hat, l_inst, tpot,
                        nominal_tpot, d, b, free, max_batch, weights,
                        allowed, latency_mode: str, row_valid=None,
                        affinity=None, *, n_cells: int, mesh=None):
    """Drop-in cell-sharded replacement for `_greedy_scan`: same
    flat-array signature in and out, bitwise-identical results (see the
    section comment for the exactness argument). The padded instance
    axis must divide evenly into `n_cells` contiguous blocks — callers
    pass pow2 cell counts against the pow2-bucketed column axis.

    mesh=None runs the single-program emulation; a mesh carrying a
    "cell" axis of size `n_cells` runs one block per device under
    shard_map with pmax/pmin/psum combines."""
    I = q_inst.shape[-1]
    C = int(n_cells)
    if C <= 1:
        return _greedy_scan(order, q_inst, c_hat, l_inst, tpot,
                            nominal_tpot, d, b, free, max_batch,
                            weights, allowed, latency_mode,
                            row_valid=row_valid, affinity=affinity)
    assert I % C == 0, (I, C)
    Ic = I // C

    def r2(x):                                    # (R, I) -> (R, C, Ic)
        return x.reshape(x.shape[0], C, Ic)

    def r1(x):                                    # (I,)   -> (C, Ic)
        return x.reshape(C, Ic)

    q3, c3, l3, al3 = r2(q_inst), r2(c_hat), r2(l_inst), r2(allowed)
    tp2, nm2 = r1(tpot), r1(nominal_tpot)
    d2, b2, f2, mb2 = r1(d), r1(b), r1(free), r1(max_batch)
    a3 = None if affinity is None else r2(affinity)

    if mesh is None:
        offs = (jnp.arange(C, dtype=jnp.int32) * Ic)[:, None]
        choice, est_T, (d2, b2, f2) = cell_greedy_scan(
            order, q3, c3, l3, tp2, nm2, d2, b2, f2, mb2, weights,
            al3, latency_mode, row_valid=row_valid, affinity=a3,
            offs=offs,
            gmax=lambda x: jnp.max(x, axis=0, keepdims=True),
            gmin=lambda x: jnp.min(x, axis=0, keepdims=True),
            gsum=lambda x: jnp.sum(x, axis=0, keepdims=True))
        return choice, est_T, (d2.reshape(I), b2.reshape(I),
                               f2.reshape(I))

    from ..launch.sharding import cell_specs
    pr, pi, pn = cell_specs()
    if row_valid is None:
        row_valid = jnp.ones(q_inst.shape[0], bool)
    has_aff = a3 is not None

    def body(order, q3, c3, l3, tp2, nm2, d2, b2, f2, mb2, al3, rv,
             *rest):
        idx = jax.lax.axis_index("cell").astype(jnp.int32)
        offs = (idx * Ic).reshape(1, 1)
        choice, est_T, state = cell_greedy_scan(
            order, q3, c3, l3, tp2, nm2, d2, b2, f2, mb2, weights,
            al3, latency_mode, row_valid=rv,
            affinity=rest[0] if has_aff else None, offs=offs,
            gmax=lambda x: jax.lax.pmax(
                jnp.max(x, axis=0, keepdims=True), "cell"),
            gmin=lambda x: jax.lax.pmin(
                jnp.min(x, axis=0, keepdims=True), "cell"),
            gsum=lambda x: jax.lax.psum(
                jnp.sum(x, axis=0, keepdims=True), "cell"))
        return choice, est_T, state

    in_specs = [pn, pr, pr, pr, pi, pi, pi, pi, pi, pi, pr, pn]
    args = [order, q3, c3, l3, tp2, nm2, d2, b2, f2, mb2, al3,
            row_valid]
    if has_aff:
        in_specs.append(pr)
        args.append(a3)
    choice, est_T, (d2, b2, f2) = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(pn, pn, (pi, pi, pi)), check_vma=False)(*args)
    return choice, est_T, (d2.reshape(I), b2.reshape(I), f2.reshape(I))
