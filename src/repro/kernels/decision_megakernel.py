"""Pallas decision megakernel: the whole RouteBalance per-batch
decision — KNN top-k, packed-GBM TPOT heads, Eq. 2 admission,
prefix-affinity and the LPT greedy scan — as ONE kernel dispatch.

The fused XLA backend (`repro.core.hotpath`) already runs the decision
as a single jitted program, but XLA still materializes every stage
boundary (the (R, N) distance matrix, the (R, M) label mixes, the
(R, I) admission/affinity planes) as separate HBM buffers between
fusions. This kernel keeps the pipeline in VMEM instead. Every stage is
written in the forms Mosaic lowers — 2-D blocks, compare/select,
min/max reductions, static slices — with no sort and no gather:

  * **stage 1 — KNN top-k**, streamed over the index: the grid walks
    the training index in tiles (the `knn_topk` idiom), so the (R, N)
    distance plane never exists whole. Per tile, k extract-min rounds
    merge the tile into a running (R, k) buffer that carries each
    survivor's distance, index and labels (a label is picked by
    compare-select on the extracted column). After the last tile, k
    extract-min rounds over the buffer order the survivors by
    (distance, index) — `lax.top_k`'s order, which the order-sensitive
    float32 label mix needs;
  * **stage 2 — packed GBM**: the per-tier TPOT heads walk their trees
    through `predict_roster` (compare-select over per-instance tree
    tables) and the shared `_accumulate` rounding order;
  * **stage 3 — Eq. 2 admission + affinity**: `admission_math` and
    `hit_fraction` over the same alive mask the fused program uses;
  * **stage 4 — LPT greedy scan**: the LPT order is a rank per request
    from pairwise comparison; a fori_loop over ranks selects each step's
    request row by compare-select and runs the shared
    `repro.core.decision_jax.greedy_step` body, with the dead-reckoned
    (d, b, free) carry in the loop state.

Execution mode follows the platform (`repro.kernels.ops.interpret_mode`):
Mosaic on a TPU, the Pallas interpreter elsewhere. Under the
interpreter the whole index is one tile, so the distance matmul has the
fused program's shape and the decision is bitwise the fused one
(``tests/test_megakernel.py`` and the randomized soak). The numpy
oracle is `repro.kernels.ref.decision_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = 3.4e38          # +inf stand-in for f32 distance masking (knn_topk.NEG)
BIG = 2 ** 30         # index sentinel above any real index
KNN_TILE = 512        # index rows per grid step when compiled
ROWS = 8              # request rows per in-kernel block (one sublane tile)
VMEM_LIMIT = 64 << 20   # survivor label buffers grow as R x labels


def _pick(sel, plane, axis: int):
    """The value of ``plane`` where ``sel`` holds, reduced along
    ``axis`` (keepdims): an exact gather as compare-select + max."""
    return jnp.max(jnp.where(sel, plane, -jnp.inf), axis=axis,
                   keepdims=True)


def _merge_tile(vals, idx, labs, d, lab_rows, base, k: int):
    """Merge one distance tile ``d`` (R, T) into the running top-k
    buffer: ``vals``/``idx`` (R, k) and ``labs``, one (R, k) plane per
    label row. Each of k rounds extracts the tile's least (distance,
    column) and evicts the buffer's greatest (distance, index) when the
    new distance is strictly less — so equal distances keep the lower
    index, as `lax.top_k` does. ``lab_rows`` are the tile's (1, T)
    label rows; ``base`` is the tile's first global index."""
    from repro.core.decision_jax import first_index
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    for _ in range(k):
        m = jnp.min(d, axis=1, keepdims=True)                  # (R, 1)
        am = first_index(d == m)
        sel = col == am
        worst = jnp.max(vals, axis=1, keepdims=True)
        wid = jnp.max(jnp.where(vals == worst, idx, -BIG), axis=1,
                      keepdims=True)
        take = (vals == worst) & (idx == wid) & (m < worst)
        vals = jnp.where(take, m, vals)
        idx = jnp.where(take, am + base, idx)
        labs = [jnp.where(take, _pick(sel, row, 1), lab)
                for row, lab in zip(lab_rows, labs)]
        d = jnp.where(sel, NEG, d)
    return vals, idx, labs


def _ordered_survivors(vals, idx, labs, k: int):
    """The buffer's k survivors in (distance, index) order: returns the
    ordered distances (R, k) and, per rank j, the (R, 1) label columns
    of the j-th survivor."""
    lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    d2k = jnp.zeros_like(vals)
    picks = []
    for j in range(k):
        m = jnp.min(vals, axis=1, keepdims=True)
        jid = jnp.min(jnp.where(vals == m, idx, BIG), axis=1,
                      keepdims=True)
        sel = idx == jid
        d2k = jnp.where(lane == j, m, d2k)
        picks.append([_pick(sel, lab, 1) for lab in labs])
        vals = jnp.where(sel, jnp.inf, vals)
    return d2k, picks


def _lpt_rank(key, key_row, row0):
    """LPT positions of one block of requests: each request's place in
    ``argsort(-key, stable=True)`` by pairwise comparison with every
    key. ``key`` (B, 1) holds requests [row0, row0 + B), ``key_row``
    (1, R) all keys; returns (B, 1) int32."""
    shape = (key.shape[0], key_row.shape[1])
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    before = (key_row > key) | ((key_row == key) & (lane < sub))
    return jnp.sum(before.astype(jnp.int32), axis=1, keepdims=True)


def _row_blocks(R: int, body, init):
    """fori_loop over the (R, .) planes in blocks of `ROWS` rows:
    ``body(rows, row0, carry)`` with ``rows`` the block's ref slice.
    Blocks keep every live value a few vregs wide, so Mosaic neither
    unrolls R-sized code nor spills it."""
    def step(blk, carry):
        row0 = pl.multiple_of(blk * ROWS, ROWS)
        return body(pl.ds(row0, ROWS), row0, carry)
    return jax.lax.fori_loop(0, R // ROWS, step, init)


def _by_model(cols, m_of_i):
    """(R, I) plane of each instance's model column: cols[m] (R, 1)."""
    out = jnp.zeros((cols[0].shape[0], m_of_i.shape[1]), jnp.float32)
    for m, c in enumerate(cols):
        out = jnp.where(m_of_i == m, c, out)
    return out


def _kernel(emb_ref, rv_ref, bud_ref, lin_ref, psig_ref,
            d_ref, b_ref, free_ref, ctx_ref, alive_ref,
            x_ref, xsq_ref, lab_ref,
            m_of_i_ref, maxb_ref, price_in_ref, price_out_ref,
            nominal_ref, sig_ref, gfeat_ref, gthr_ref, gleaf_ref,
            gbase_ref,
            choice_ref, est_ref, lchosen_ref, d1_ref, b1_ref, f1_ref,
            vals_ref, idx_ref, labs_ref, d2_ref, plane_ref, key_ref,
            rank_ref,
            *, k: int, eps: float, weights, latency_mode: str,
            lpt: bool, budget_filter: bool, w_aff: float,
            use_gbm: bool, depth: int, lr: float, n_index: int):
    # deferred: repro.core imports repro.kernels-adjacent modules at
    # package-init time; the kernel body only traces after everything
    # is importable, so the shared one-definition math is pulled in here
    from repro.core.budget import admission_math, cost_matrix
    from repro.core.decision_jax import greedy_step
    from repro.estimators.gbm import predict_roster
    from repro.estimators.knn import distance_weights, label_mix
    from repro.serving.affinity import hit_fraction

    t = pl.program_id(0)
    R = emb_ref.shape[0]
    tile = x_ref.shape[0]
    n_lab = lab_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, NEG, jnp.float32)
        # distinct negative placeholder indices: one slot evicts at a time
        idx_ref[...] = -1 - jax.lax.broadcasted_iota(
            jnp.int32, idx_ref.shape, 1)
        labs_ref[...] = jnp.zeros(labs_ref.shape, jnp.float32)

    # -- stage 1: one index tile into the running top-k --------------------
    # the distance expansion is spelled as topk_soft_lookup's — same op
    # order, HIGHEST precision — so the survivors' distances are the
    # fused program's
    emb = emb_ref[...]                                      # (R, E)
    d2 = (xsq_ref[...]
          - jnp.matmul(2.0 * emb, x_ref[...].T,
                       precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(emb * emb, -1, keepdims=True))          # (R, tile)
    if n_index % tile:
        col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + t * tile
        d2 = jnp.where(col < n_index, d2, NEG)
    d2_ref[...] = d2

    def merge(rows, row0, carry):
        vals, idx, labs = _merge_tile(
            vals_ref[rows, :], idx_ref[rows, :],
            [labs_ref[c, rows, :] for c in range(n_lab)], d2_ref[rows, :],
            [lab_ref[c:c + 1, :] for c in range(n_lab)], t * tile, k)
        vals_ref[rows, :] = vals
        idx_ref[rows, :] = idx
        for c in range(n_lab):
            labs_ref[c, rows, :] = labs[c]
        return carry
    _row_blocks(R, merge, 0)

    @pl.when(t == pl.num_programs(0) - 1)
    def _decide():
        d = d_ref[...]                                      # (1, I)
        b = b_ref[...]
        free = free_ref[...]
        ctx = ctx_ref[...]
        alive = alive_ref[...] > 0
        nominal = nominal_ref[...]
        m_of_i = m_of_i_ref[...]
        M = n_lab // 2

        # -- stage 2: packed-GBM TPOT heads --------------------------------
        b_eff = jnp.maximum(b, 1.0)
        ctx_eff = jnp.maximum(ctx, 64.0)
        if use_gbm:
            tables = {"feature": gfeat_ref, "threshold": gthr_ref,
                      "leaf": gleaf_ref, "base": gbase_ref[...],
                      "lr": lr, "depth": depth}
            tpot = jnp.maximum(
                predict_roster(tables,
                               [b_eff, d, ctx_eff, b_eff * ctx_eff]),
                1e-4)
        else:
            tpot = nominal

        # -- stage 1 tail + stage 3, per row block: ordered survivors ->
        # distance-weighted label mix -> Eq. 2 admission + affinity
        def estimate(rows, row0, carry):
            d2k, picks = _ordered_survivors(
                vals_ref[rows, :], idx_ref[rows, :],
                [labs_ref[c, rows, :] for c in range(n_lab)], k)
            w = distance_weights(d2k, eps, jnp)
            mix = [label_mix([p[c] for p in picks], w)
                   for c in range(n_lab)]
            qual, leng = mix[:M], mix[M:]
            l_inst = _by_model(leng, m_of_i)                # (ROWS, I)
            key_ref[rows, :] = jnp.where(
                rv_ref[rows, :] > 0, functools.reduce(jnp.maximum, leng),
                -1e30)
            len_in = lin_ref[rows, :]
            if budget_filter:
                allowed, c_hat = admission_math(
                    bud_ref[rows, :], len_in, l_inst, price_in_ref[...],
                    price_out_ref[...], jnp, valid=alive)
            else:
                c_hat = cost_matrix(len_in, l_inst, price_in_ref[...],
                                    price_out_ref[...], jnp)
                allowed = jnp.broadcast_to(alive, c_hat.shape)
            plane_ref[0, rows, :] = _by_model(qual, m_of_i)
            plane_ref[1, rows, :] = c_hat
            plane_ref[2, rows, :] = l_inst
            plane_ref[3, rows, :] = allowed.astype(jnp.float32)
            if w_aff > 0.0:
                hit = hit_fraction(psig_ref[rows, :], len_in,
                                        sig_ref[...], jnp)
                hit = jnp.where(alive, hit, jnp.float32(0.0))
                plane_ref[4, rows, :] = jnp.float32(w_aff) * hit
            return carry
        _row_blocks(R, estimate, 0)

        # -- stage 4: LPT rank + dead-reckoned greedy scan -----------------
        if lpt:
            def gather_keys(rows, row0, key_row):
                sub = jax.lax.broadcasted_iota(jnp.int32, (ROWS, R), 0)
                lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, R), 1)
                return jnp.maximum(
                    key_row, _pick(sub + row0 == lane, key_ref[rows, :], 0))
            key_row = _row_blocks(R, gather_keys,
                                  jnp.full((1, R), -jnp.inf, jnp.float32))

            def rank(rows, row0, carry):
                rank_ref[rows, :] = _lpt_rank(key_ref[rows, :], key_row,
                                              row0)
                return carry
            _row_blocks(R, rank, 0)
        else:
            rank_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        b0 = jnp.maximum(b_eff, 1.0)
        maxb = maxb_ref[...]

        def body(s, carry):
            dc, bc, fc, choice, est = carry
            sel = rank_ref[...] == s                        # (R, 1)

            def row(j):
                return _pick(sel, plane_ref[j], 0)          # (1, I)
            valid_r = _pick(sel, rv_ref[...], 0) > 0        # (1, 1)
            dc, bc, fc, i, e = greedy_step(
                row(0), row(1), row(2), row(3) > 0.5,
                row(4) if w_aff > 0.0 else None, valid_r, dc, bc, fc,
                tpot=tpot, nominal_tpot=nominal, b0=b0, max_batch=maxb,
                weights=weights, latency_mode=latency_mode)
            return (dc, bc, fc, jnp.where(sel, i, choice),
                    jnp.where(sel, e, est))

        d1, b1, f1, choice, est = jax.lax.fori_loop(
            0, R, body, (d, b_eff, free, jnp.zeros((R, 1), jnp.int32),
                         jnp.zeros((R, 1), jnp.float32)))
        choice_ref[...] = choice
        est_ref[...] = est

        def chosen_len(rows, row0, carry):
            l_inst = plane_ref[2, rows, :]
            lane = jax.lax.broadcasted_iota(jnp.int32, l_inst.shape, 1)
            lchosen_ref[rows, :] = _pick(lane == choice_ref[rows, :],
                                         l_inst, 1)
            return carry
        _row_blocks(R, chosen_len, 0)
        d1_ref[...] = d1
        b1_ref[...] = b1
        f1_ref[...] = f1


def decision_call(emb, row_valid, budgets, len_in, psig,
                  d, b, free, ctx, alive,
                  x, xsq, qual, leng,
                  m_of_i, tier_of_i, maxb, price_in, price_out, nominal,
                  sig_plane, gfeat, gthr, gleaf, gbase, *,
                  k: int, eps: float, weights, latency_mode: str,
                  lpt: bool, budget_filter: bool, w_aff: float,
                  use_gbm: bool, depth: int, lr: float,
                  knn_tile: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """The megakernel dispatch (traceable; jit at the call site).

    One scheduler window: emb (R, E), row_valid (R,) bool,
    budgets/len_in (R,), psig (R, SIG_WIDTH) int32 (any (1, 1) dummy
    when ``w_aff == 0``); telemetry mirror d/b/free/ctx (I,) f32 +
    alive (I,) bool; sig_plane is (I, SKETCH_SLOTS)
    (any dummy when ``w_aff == 0``); gfeat/gthr/gleaf/gbase are the
    `pack_ensemble` stack (1-element dummies when ``use_gbm`` is
    False). ``knn_tile`` is the index rows per grid step: by default
    the whole index under the interpreter and `KNN_TILE` compiled.
    ``interpret`` defaults to the platform's mode. Returns
    (choice (R,) i32, est_T (R,) f32, l_chosen (R,) f32,
    d1/b1/f1 (I,) f32 post-scan dead-reckoned views).

    Layout work happens here, outside the kernel: per-request vectors
    become (R, 1) columns, per-instance vectors (1, I) rows, bools
    int32, the label tables one (2M, N) row block, the sketch plane and
    GBM tables instance-minor (`roster_tables`).
    """
    from repro.estimators.gbm import roster_tables

    from .ops import interpret_mode
    if interpret is None:
        interpret = interpret_mode()
    R0, E = emb.shape
    R = -(-R0 // ROWS) * ROWS          # pad rows: invalid, scanned last
    I = d.shape[0]
    N, M = qual.shape
    tile = knn_tile or (N if interpret else KNN_TILE)
    Np = -(-N // tile) * tile
    f32 = jnp.float32

    def pad_rows(a, fill=0):
        a = jnp.asarray(a)
        return jnp.pad(a, ((0, R - R0),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill)

    def col(a, dtype=f32, fill=0):
        return pad_rows(jnp.asarray(a).astype(dtype), fill).reshape(R, 1)

    def row(a, dtype=f32):
        return jnp.asarray(a).astype(dtype).reshape(1, I)

    labels = jnp.concatenate([jnp.asarray(qual, f32).T,
                              jnp.asarray(leng, f32).T])      # (2M, N)
    x = jnp.asarray(x, f32)
    xsq = jnp.asarray(xsq, f32).reshape(1, N)
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
        xsq = jnp.pad(xsq, ((0, 0), (0, Np - N)))
        labels = jnp.pad(labels, ((0, 0), (0, Np - N)))
    sig_t = (jnp.asarray(sig_plane, jnp.int32).T if w_aff > 0.0
             else jnp.zeros((1, 1), jnp.int32))
    if use_gbm:
        tables = roster_tables({"feature": gfeat, "threshold": gthr,
                                "leaf": gleaf, "base": gbase},
                               jnp.asarray(tier_of_i))
        gbm = (tables["feature"], tables["threshold"], tables["leaf"],
               tables["base"])
    else:
        gbm = (jnp.zeros((1, 1, 1), jnp.int32),
               jnp.zeros((1, 1, 1), f32), jnp.zeros((1, 1, 1), f32),
               jnp.zeros((1, 1), f32))
    n_planes = 5 if w_aff > 0.0 else 4
    psig = jnp.asarray(psig, jnp.int32)
    if w_aff > 0.0:
        psig = pad_rows(psig)

    def whole(*block):
        return pl.BlockSpec(block, lambda t: (0,) * len(block))

    kern = functools.partial(
        _kernel, k=k, eps=eps, weights=tuple(weights),
        latency_mode=latency_mode, lpt=lpt, budget_filter=budget_filter,
        w_aff=w_aff, use_gbm=use_gbm, depth=depth, lr=lr, n_index=N)
    outs = pl.pallas_call(
        kern,
        grid=(Np // tile,),
        in_specs=[
            whole(R, E),               # emb
            whole(R, 1),               # row_valid
            whole(R, 1),               # budgets
            whole(R, 1),               # len_in
            whole(*psig.shape),        # psig
            whole(1, I), whole(1, I), whole(1, I), whole(1, I),
            whole(1, I),               # alive
            pl.BlockSpec((tile, E), lambda t: (t, 0)),           # x
            pl.BlockSpec((1, tile), lambda t: (0, t)),           # xsq
            pl.BlockSpec((2 * M, tile), lambda t: (0, t)),       # labels
            whole(1, I),               # m_of_i
            whole(1, I),               # maxb
            whole(1, I),               # price_in
            whole(1, I),               # price_out
            whole(1, I),               # nominal
            whole(*sig_t.shape),       # sketch plane (slots, I)
            *(whole(*g.shape) for g in gbm),
        ],
        out_specs=[whole(R, 1), whole(R, 1), whole(R, 1),
                   whole(1, I), whole(1, I), whole(1, I)],
        out_shape=[
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
            jax.ShapeDtypeStruct((R, 1), f32),
            jax.ShapeDtypeStruct((R, 1), f32),
            jax.ShapeDtypeStruct((1, I), f32),
            jax.ShapeDtypeStruct((1, I), f32),
            jax.ShapeDtypeStruct((1, I), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, k), f32),                # survivor distances
            pltpu.VMEM((R, k), jnp.int32),          # survivor indices
            pltpu.VMEM((2 * M, R, k), f32),         # survivor labels
            pltpu.VMEM((R, tile), f32),             # tile distances
            pltpu.VMEM((n_planes, R, I), f32),      # scan planes
            pltpu.VMEM((R, 1), f32),                # LPT key
            pltpu.VMEM((R, 1), jnp.int32),          # LPT rank
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(pad_rows(emb), col(row_valid, jnp.int32), col(budgets, fill=np.nan),
      col(len_in), psig,
      row(d), row(b), row(free), row(ctx), row(alive, jnp.int32),
      x, xsq, labels,
      row(m_of_i, jnp.int32), row(maxb), row(price_in), row(price_out),
      row(nominal), sig_t, *gbm)
    return (outs[0][:R0, 0], outs[1][:R0, 0], outs[2][:R0, 0],
            outs[3][0], outs[4][0], outs[5][0])


@functools.partial(
    jax.jit,
    static_argnames=("k", "eps", "weights", "latency_mode", "lpt",
                     "budget_filter", "w_aff", "use_gbm", "depth", "lr",
                     "knn_tile", "interpret"))
def decision_megakernel(emb, row_valid, budgets, len_in, psig,
                        d, b, free, ctx, alive,
                        x, xsq, qual, leng,
                        m_of_i, tier_of_i, maxb, price_in, price_out,
                        nominal, sig_plane, gfeat, gthr, gleaf, gbase,
                        *, k, eps, weights, latency_mode, lpt,
                        budget_filter, w_aff, use_gbm, depth, lr,
                        knn_tile: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Jitted standalone entry for tests/benches; production goes
    through `FusedHotPath` (decision_backend="megakernel"), which
    traces `decision_call` inside its own donated-buffer step."""
    return decision_call(
        emb, row_valid, budgets, len_in, psig, d, b, free, ctx, alive,
        x, xsq, qual, leng, m_of_i, tier_of_i, maxb, price_in,
        price_out, nominal, sig_plane, gfeat, gthr, gleaf, gbase,
        k=k, eps=eps, weights=weights, latency_mode=latency_mode,
        lpt=lpt, budget_filter=budget_filter, w_aff=w_aff,
        use_gbm=use_gbm, depth=depth, lr=lr, knn_tile=knn_tile,
        interpret=interpret)


def dummy_gbm() -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """1-element placeholder GBM operands for ``use_gbm=False`` calls
    (the static flag keeps the kernel from ever reading them)."""
    return (np.zeros((1, 1, 1), np.int32),
            np.zeros((1, 1, 1), np.float32),
            np.zeros((1, 1, 1), np.float32),
            np.zeros(1, np.float32))
