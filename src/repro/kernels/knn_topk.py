"""Pallas TPU kernel: fused L2-distance + running top-k over a KNN index.

The paper's dominant hot-path term is the batched MiniLM+KNN estimator
(~27 ms/batch on their CPU; §6.3). TPU-native re-think (DESIGN.md §3):
the index lives in HBM and is streamed through VMEM tiles; per tile the
(B, E) x (E, T) distance cross-term runs on the MXU via the
||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2 expansion, and a running top-k
(k ~ 10) is maintained in the output VMEM buffers across the sequential
grid (the index-tile axis is a reduction axis: output index_map is
constant along it, so the buffers persist).

Top-k merge per tile: k rounds of (min, argmin, mask) over the (B, T)
tile distances — O(k*T) vector ops, no sort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = 3.4e38  # +inf stand-in for f32 distance masking


def _kernel(q_ref, qsq_ref, x_ref, xsq_ref, vals_ref, idx_ref, *,
            k: int, tile: int, n_total: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    q = q_ref[...]                     # (B, E)
    x = x_ref[...]                     # (T, E)
    xsq = xsq_ref[...]                 # (1, T)
    qsq = qsq_ref[...]                 # (B, 1)
    # (B, T) squared distances on the MXU, at full f32 precision (the
    # TPU's default f32 dot takes bf16 passes)
    d = qsq + xsq - 2.0 * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    base = t * tile
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1) + base
    d = jnp.where(col < n_total, d, NEG)

    vals = vals_ref[...]               # (B, k) current best (distances)
    idx = idx_ref[...]                 # (B, k)
    # merge: k rounds of extract-min from the tile
    for j in range(k):
        m = jnp.min(d, axis=1, keepdims=True)            # (B, 1)
        am = jnp.argmin(d, axis=1)                       # (B,)
        gidx = am.astype(jnp.int32) + base
        worst = jnp.max(vals, axis=1, keepdims=True)     # (B, 1)
        wslot = jnp.argmax(vals, axis=1)                 # (B,)
        better = m < worst                               # (B, 1)
        onehot_w = (jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
                    == wslot[:, None])
        take = onehot_w & better
        vals = jnp.where(take, m, vals)
        idx = jnp.where(take, gidx[:, None], idx)
        onehot_d = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
                    == am[:, None])
        d = jnp.where(onehot_d, NEG, d)
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def knn_topk(q, x, *, k: int = 10, tile: int = 512,
             interpret: bool = True):
    """q: (B, E) queries; x: (N, E) index. Returns (d2 (B,k), idx (B,k)),
    sorted ascending by distance."""
    B, E = q.shape
    N = x.shape[0]
    n_pad = (-N) % tile
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    Np = x.shape[0]
    qsq = jnp.sum(q * q, axis=1, keepdims=True)          # (B, 1)
    xsq = jnp.sum(x * x, axis=1)[None, :]                # (1, Np)
    grid = (Np // tile,)
    vals, idx = pl.pallas_call(
        functools.partial(_kernel, k=k, tile=tile, n_total=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, E), lambda t: (0, 0)),
            pl.BlockSpec((B, 1), lambda t: (0, 0)),
            pl.BlockSpec((tile, E), lambda t: (t, 0)),
            pl.BlockSpec((1, tile), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda t: (0, 0)),
            pl.BlockSpec((B, k), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        interpret=interpret,
    )(q.astype(jnp.float32), qsq.astype(jnp.float32),
      x.astype(jnp.float32), xsq.astype(jnp.float32))
    # final ascending sort of the k survivors
    order = jnp.argsort(vals, axis=1)
    return (jnp.take_along_axis(vals, order, axis=1),
            jnp.take_along_axis(idx, order, axis=1))
