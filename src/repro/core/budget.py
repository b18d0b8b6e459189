"""Equation 2 budget control: average-case admission filter at scoring
time, worst-case enforcement at dispatch (max_tokens clamp) plus the
engine's streaming early-stop (§4.1, §6.4).

`admission_math` is backend-agnostic (numpy or jax.numpy) so the numpy
reference loop, the fused program (`repro.core.hotpath`) and the
megakernel evaluate one shared definition of Eq. 2 — no fancy indexing, only
where/min, so it traces under jit and inside the Mosaic kernel unchanged.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def _col(v):
    """Per-request operand as an (R, 1) column ((R,) or already (R, 1))."""
    return v if v.ndim == 2 else v[:, None]


def _row(v):
    """Per-instance operand as a (1, I) row ((I,) or already (1, I))."""
    return v if v.ndim == 2 else v[None, :]


def cost_matrix(len_in, pred_len, price_in, price_out, xp=np):
    """Ĉ(r,i) = (ℓ_in c_in + L̂ c_out) · 1e-6 over (R, I).

    Per-request args may come as (R,) or (R, 1), per-instance ones as
    (I,) or (1, I) (the Mosaic kernel keeps everything 2-D).

    The per-token scale is applied as a reciprocal multiply, not a
    division: XLA rewrites division by a constant into multiplication by
    its (correctly rounded) reciprocal, so spelling the multiply out
    keeps the numpy float32 evaluation on the jitted backends' exact
    arithmetic (the sole remaining cross-backend difference is FMA
    contraction of the mul-add, ~1 ulp, which the epsilon-quantized
    scoring grid absorbs)."""
    return (_col(len_in) * _row(price_in)
            + pred_len * _row(price_out)) * 1e-6


def admission_math(budgets, len_in, pred_len, price_in, price_out, xp=np,
                   valid=None):
    """Shared Eq. 2 body; see `admission_mask` for semantics. Returns
    (allowed (R, I) bool, c_hat (R, I)). Operand shapes as in
    `cost_matrix`.

    `valid` (I,) bool optionally restricts the candidate set (the fused
    hot path schedules over the full instance roster with dead instances
    masked instead of recompiling after a failure): disallowed columns
    never admit and never win the cheapest-candidate fallback."""
    c_hat = cost_matrix(len_in, pred_len, price_in, price_out, xp)
    budgets = _col(budgets)
    # boolean algebra rather than selects over bools: Mosaic lowers the
    # former only
    constrained = xp.isnan(budgets) | (c_hat <= budgets)
    c_sel = c_hat
    if valid is not None:
        constrained = constrained & _row(valid)
        c_sel = xp.where(_row(valid), c_hat, xp.inf)
    none_fit = constrained.astype(xp.int32).max(axis=1, keepdims=True) == 0
    # one-hot fallback: the first cheapest candidate (argmin tie order)
    col = xp.arange(c_sel.shape[1])[None, :]
    first = xp.where(c_sel == c_sel.min(axis=1, keepdims=True), col,
                     c_sel.shape[1]).min(axis=1, keepdims=True)
    allowed = (none_fit & (col == first)) | (~none_fit & constrained)
    return allowed, c_hat


def admission_mask(budgets: np.ndarray, len_in: np.ndarray,
                   pred_len: np.ndarray, price_in: np.ndarray,
                   price_out: np.ndarray) -> np.ndarray:
    """(R,) budgets (nan = none), (R,) len_in, (R, I) pred_len per
    instance's model, (I,) prices -> (R, I) allowed mask.

    Ĉ(r,i) = ℓ_in c_in + L̂ c_out <= b_r. Requests whose budget excludes
    every candidate keep their single cheapest candidate (the system still
    serves every request; §6.2)."""
    return admission_math(budgets, len_in, pred_len, price_in, price_out,
                          np)


def max_tokens_clamp(budget: Optional[float], len_in: int,
                     price_in: float, price_out: float) -> Optional[int]:
    """Worst-case enforcement at dispatch: the response may not exceed the
    remaining budget at the chosen model's output price."""
    if budget is None or np.isnan(budget):
        return None
    rem = budget - len_in * price_in / 1e6
    return max(int(rem / (price_out / 1e6 + 1e-30)), 1)
