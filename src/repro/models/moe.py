"""Mixture-of-Experts layer with scatter-based (sort-free) dispatch.

Capacity-based token dispatch via cumsum positions + scatter-add into
per-expert buffers, batched expert matmuls, and gather-combine. This avoids
the (T, E, C) one-hot dispatch einsum of GShard-style MoE, whose memory is
prohibitive at train_4k token counts. Expert weights are TP-shardable on
the d_ff axis (works for any expert count, incl. E=8 and E=40 which do not
divide a 16-wide model axis); the dispatch itself stays data-local, so no
cross-data-shard token routing is required at lowering time. True EP with
all-to-all is an optimization explored in §Perf.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .layers import apply_act, dense_init


def moe_params(key, d: int, f: int, n_experts: int, glu: bool,
               dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, n_experts), dtype=jnp.float32),
        "up": dense_init(ks[1], (n_experts, d, f), dtype=dtype),
        "down": dense_init(ks[2], (n_experts, f, d), dtype=dtype),
    }
    if glu:
        p["gate"] = dense_init(ks[3], (n_experts, d, f), dtype=dtype)
    return p


def moe_layer(x, p: Dict, *, top_k: int, capacity_factor: float,
              act: str = "silu", glu: bool = True, no_drop: bool = False):
    """x: (..., D) -> (out (..., D), aux load-balance loss).

    no_drop=True sets capacity C=T (each token fits every expert it picks —
    used at decode where per-shard token counts are tiny and capacity drops
    would perturb served quality).
    """
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    E = p["router"].shape[1]
    k = top_k

    logits = (x2.astype(jnp.float32) @ p["router"])            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                           # (T, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * P_e.
    me = probs.mean(axis=0)                                    # (T,E)->(E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        jnp.ones((T * k,), jnp.float32)) / (T * k)
    aux = E * jnp.sum(me * ce)

    C = T if no_drop else max(1, int(capacity_factor * k * T / E))
    flat_e = idx.reshape(-1)                                   # (T*k,)
    flat_w = w.reshape(-1)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (T*k, E)
    pos = jnp.cumsum(oh, axis=0) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]
    keep = (pos_in_e < C).astype(x2.dtype)
    slot = jnp.clip(pos_in_e, 0, C - 1)

    x_rep = jnp.repeat(x2, k, axis=0)                          # (T*k, D)
    buf = jnp.zeros((E, C, D), x2.dtype).at[flat_e, slot].add(
        x_rep * keep[:, None])

    up = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    if glu:
        h = apply_act(jnp.einsum("ecd,edf->ecf", buf, p["gate"]), act) * up
    else:
        h = apply_act(up, act)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["down"])          # (E, C, D)

    y = out_buf[flat_e, slot] * (keep * flat_w.astype(x2.dtype))[:, None]
    out = y.reshape(T, k, D).sum(axis=1)
    return out.reshape(orig_shape), aux


def moe_layer_sharded(x, p: Dict, *, top_k: int, capacity_factor: float,
                      act: str = "silu", glu: bool = True,
                      no_drop: bool = False):
    """Data-local MoE under an active sharding context.

    shard_map keeps the dispatch (cumsum/scatter/gather) entirely within
    each data shard — no cross-shard token routing at lowering time — while
    expert FFN weights stay TP-sharded on d_ff over "model". This is what
    prevents GSPMD from materializing replicated (E, C, D) buffers with
    cross-data psums. The capacity C is computed from the LOCAL token count
    (shapes inside shard_map are per-shard).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.shardctx import batch_axes, current
    mesh, _ = current()
    if mesh is None:
        return moe_layer(x, p, top_k=top_k, capacity_factor=capacity_factor,
                         act=act, glu=glu, no_drop=no_drop)
    ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    if not ba or x.shape[0] % nb != 0:
        return moe_layer(x, p, top_k=top_k, capacity_factor=capacity_factor,
                         act=act, glu=glu, no_drop=no_drop)

    def local(xl, pl):
        out, aux = moe_layer(xl, pl, top_k=top_k,
                             capacity_factor=capacity_factor, act=act,
                             glu=glu, no_drop=no_drop)
        # expert down-proj contracted over the TP-sharded d_ff: finish it
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, ba)
        return out, aux

    p_specs = {
        "router": P(),
        "up": P(None, None, "model"),
        "down": P(None, "model", None),
    }
    if glu:
        p_specs["gate"] = P(None, None, "model")
    x_spec = P(ba, *([None] * (x.ndim - 1)))
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(x_spec, p_specs),
                       out_specs=(x_spec, P()),
                       check_vma=False)
    return fn(x, p)
