"""Pure-jnp/numpy oracles for every kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def knn_topk_ref(q, x, k: int = 10):
    """Exact top-k smallest squared L2 distances. -> (d2 (B,k), idx)."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    d2 = (jnp.sum(q * q, 1, keepdims=True)
          + jnp.sum(x * x, 1)[None, :]
          - 2.0 * q @ x.T)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)


def decision_ref(emb, row_valid, budgets, len_in, psig,
                 d, b, free, ctx, alive,
                 x, xsq, qual, leng,
                 m_of_i, tier_of_i, maxb, price_in, price_out, nominal,
                 sig_plane, gbm=None, *, k: int, eps: float, weights,
                 latency_mode: str = "full", lpt: bool = True,
                 budget_filter: bool = True, w_aff: float = 0.0):
    """Pure-numpy oracle for the decision megakernel
    (`repro.kernels.decision_megakernel.decision_call`): the same
    KNN -> GBM -> admission -> affinity -> greedy-scan pipeline, one
    Python loop per request, float32 throughout like the device
    backends. Args mirror `decision_call` (`gbm` is the
    `pack_ensemble` dict or None for nominal-TPOT mode). Returns the
    same six outputs.

    This is a *logical* oracle (assignment-exact on the tested worlds,
    latencies to float tolerance), not the bitwise contract — that is
    the fused backend, asserted in ``tests/test_megakernel.py``."""
    from repro.core.budget import admission_math, cost_matrix
    from repro.core.scoring import affinity_discount, masked_score
    from repro.estimators.gbm import _accumulate
    from repro.estimators.knn import distance_weights
    from repro.serving.affinity import hit_fraction

    f32 = np.float32
    q = np.asarray(emb, f32)
    R = q.shape[0]
    d0, b_tel, free0, ctx0 = (np.asarray(a, f32)
                              for a in (d, b, free, ctx))
    alive = np.asarray(alive, bool)
    x = np.asarray(x, f32)
    xsq = np.asarray(xsq, f32)
    qual_lbl = np.asarray(qual, f32)
    leng_lbl = np.asarray(leng, f32)
    m_of_i = np.asarray(m_of_i)
    maxb = np.asarray(maxb, f32)
    price_in = np.asarray(price_in, f32)
    price_out = np.asarray(price_out, f32)
    nominal = np.asarray(nominal, f32)
    I = d0.shape[0]
    wq, wl, wc = (f32(w) for w in weights)

    b_eff = np.maximum(b_tel, f32(1.0))
    ctx_eff = np.maximum(ctx0, f32(64.0))
    if gbm is not None:
        feats = np.stack([b_eff, d0, ctx_eff, b_eff * ctx_eff],
                         axis=1).astype(f32)
        feat_m = np.asarray(gbm["feature"])[tier_of_i]   # (I, T, n_int)
        thr_m = np.asarray(gbm["threshold"], f32)[tier_of_i]
        leaf_m = np.asarray(gbm["leaf"], f32)[tier_of_i]
        idx = np.zeros((I, feat_m.shape[1]), np.int32)
        for _ in range(gbm["depth"]):
            fsel = np.take_along_axis(feat_m, idx[:, :, None],
                                      axis=2)[..., 0]
            tsel = np.take_along_axis(thr_m, idx[:, :, None],
                                      axis=2)[..., 0]
            xv = np.take_along_axis(feats, fsel, axis=1)
            idx = 2 * idx + 1 + (xv > tsel).astype(np.int32)
        leaf_idx = idx - (2 ** gbm["depth"] - 1)
        vals = np.take_along_axis(leaf_m, leaf_idx[:, :, None],
                                  axis=2)[..., 0]        # (I, T)
        base = np.asarray(gbm["base"], f32)[tier_of_i]
        tpot = np.maximum(
            _accumulate(base, gbm["lr"], vals.T, np), f32(1e-4))
    else:
        tpot = nominal

    rv = np.asarray(row_valid, bool)
    bud = np.asarray(budgets, f32)
    lin = np.asarray(len_in, f32)
    # stage 1: exact KNN (sorted ascending by (distance, index))
    d2 = (xsq[None, :] - 2.0 * q @ x.T
          + (q * q).sum(-1, keepdims=True)).astype(f32)
    nidx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    d2k = np.take_along_axis(d2, nidx, axis=1)
    w = distance_weights(d2k, eps, np).astype(f32)
    qmix = (qual_lbl[nidx] * w[..., None]).sum(1)        # (R, M)
    lmix = (leng_lbl[nidx] * w[..., None]).sum(1)
    q_inst = qmix[:, m_of_i]
    l_inst = lmix[:, m_of_i]
    pred_len_max = np.where(rv, lmix.max(axis=1), -1e30)
    # stage 3: admission + affinity
    if budget_filter:
        allowed, c_hat = admission_math(
            bud, lin, l_inst, price_in, price_out, np, valid=alive)
    else:
        c_hat = cost_matrix(lin, l_inst, price_in, price_out, np)
        allowed = np.broadcast_to(alive[None, :], c_hat.shape)
    if w_aff > 0.0:
        hit = hit_fraction(np.asarray(psig), lin,
                           np.asarray(sig_plane).T, np)
        aff = f32(w_aff) * np.where(alive[None, :], hit, f32(0.0))
    else:
        aff = None
    # stage 4: LPT order + greedy scan (mirrors greedy_step)
    order = (np.argsort(-pred_len_max, kind="stable") if lpt
             else np.arange(R))
    choice = np.zeros(R, np.int32)
    est_T = np.zeros(R, f32)
    l_chosen = np.zeros(R, f32)
    dc, bc, fc = d0.copy(), b_eff.copy(), free0.copy()
    b0 = np.maximum(b_eff, f32(1.0))
    for r in order:
        wait = np.where(fc > 0, f32(0.0),
                        dc / np.maximum(bc, f32(1.0)))
        tpot_eff = tpot * np.maximum(bc / b0, f32(1.0))
        if latency_mode == "static_prior":
            T = nominal * l_inst[r]
        else:
            T = tpot_eff * (wait + l_inst[r])
        if aff is not None:
            T = affinity_discount(T, aff[r], np)
        if latency_mode in ("off_reactive", "off_predictive"):
            s = masked_score(q_inst[r], c_hat[r], T, (wq, 0.0, wc),
                             allowed[r], np)
            tie = (dc + bc) if latency_mode == "off_reactive" else T
            tn = tie / np.maximum(tie.max(), f32(1e-9))
            i = int(np.argmin(np.where(s >= s.max(), tn, np.inf)))
        else:
            s = masked_score(q_inst[r], c_hat[r], T, (wq, wl, wc),
                             allowed[r], np)
            i = int(np.argmax(s))
        choice[r] = i
        est_T[r] = T[i]
        l_chosen[r] = l_inst[r, i]
        if rv[r]:
            dc[i] += l_inst[r, i]
            if fc[i] > 0:
                fc[i] -= 1.0
                bc[i] = min(bc[i] + 1.0, maxb[i])
    return choice, est_T, l_chosen, dc, bc, fc


def decode_attention_ref(q, k_cache, v_cache, cache_positions, pos,
                         window: int = 0):
    """GQA decode attention; mirrors models.attention.decode_attention
    but takes q (B, H, d) and returns (B, H, d)."""
    from repro.models.attention import decode_attention
    o = decode_attention(q[:, None], k_cache, v_cache, cache_positions,
                         pos, window=window)
    return o[:, 0]


def ssd_scan_ref(xh, Bm, Cm, dt, A, chunk: int):
    """Chunked SSD (mamba2) oracle; mirrors models.blocks._ssd_chunked
    with heads already expanded. Returns (y, final_state)."""
    from repro.models.blocks import _ssd_chunked
    B, S, nh, P = xh.shape
    init = jnp.zeros((B, nh, P, Bm.shape[-1]), jnp.float32)
    # _ssd_chunked expects group dim; here Bm/Cm are (B, S, G, N)
    return _ssd_chunked(xh, Bm, Cm, dt, A, chunk, init)


def ssd_recurrent_ref(xh, Bm, Cm, dt, A):
    """Token-by-token linear recurrence (the SSD ground truth):
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ; y_t = C_t . h_t.
    xh: (B,S,nh,P); Bm/Cm: (B,S,nh,N); dt: (B,S,nh); A: (nh,)."""
    B, S, nh, P = xh.shape
    N = Bm.shape[-1]

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp
        dA = jnp.exp(dt_t * A)[..., None, None]          # (B,nh,1,1)
        h = h * dA + jnp.einsum("bhp,bhn,bh->bhpn",
                                x_t.astype(jnp.float32), B_t, dt_t)
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t)
        return h, y

    xs = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(Bm, 1, 0),
          jnp.moveaxis(Cm, 1, 0), jnp.moveaxis(dt, 1, 0))
    h0 = jnp.zeros((B, nh, P, N), jnp.float32)
    hT, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), hT
