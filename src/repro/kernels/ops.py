"""Jitted public wrappers over the Pallas kernels.

Execution mode follows the platform: on a TPU every kernel compiles
with Mosaic, anywhere else it runs through the Pallas interpreter
(`interpret_mode`). The mode is decided when a wrapper is called or a
runner is built, never at import. The kernel functions themselves keep
an explicit ``interpret=`` argument, so tests can compile them for a
described chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .decode_attention import decode_attention as decode_attention_kernel
from .knn_topk import knn_topk as knn_topk_kernel
from .ssd_scan import ssd_scan as ssd_scan_kernel


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU: Pallas kernels
    compile with Mosaic there and run interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def knn_topk(q, x, k: int = 10, tile: int = 512):
    return knn_topk_kernel(q, x, k=k, tile=tile, interpret=interpret_mode())


def decode_attention(q, k_cache, v_cache, cache_positions, pos,
                     window: int = 0, tile: int = 512):
    return decode_attention_kernel(q, k_cache, v_cache, cache_positions,
                                   pos, window=window, tile=tile,
                                   interpret=interpret_mode())


def ssd_scan(xh, Bm, Cm, dt, A, chunk: int = 128, head_tile: int = 8):
    return ssd_scan_kernel(xh, Bm, Cm, dt, A, chunk=chunk,
                           head_tile=head_tile, interpret=interpret_mode())


def decision_megakernel(*args, **kwargs):
    """The fused-decision megakernel at the platform's mode (see
    `repro.kernels.decision_megakernel` for the signature). Production
    reaches the kernel through `FusedHotPath`; this wrapper is the
    direct kernel-level entry for tests and benches."""
    from .decision_megakernel import decision_megakernel as _mk
    kwargs.setdefault("interpret", interpret_mode())
    return _mk(*args, **kwargs)


# -- KNN estimator backend ---------------------------------------------------

def build_query(x: np.ndarray, quality: np.ndarray, lengths: np.ndarray,
                k: int, eps: float):
    """Returns a callable (B, E) -> (quality (B, M), length (B, M)) using
    the fused Pallas distance+top-k kernel."""
    xj = jnp.asarray(x, jnp.float32)
    qualj = jnp.asarray(quality, jnp.float32)
    lenj = jnp.asarray(lengths, jnp.float32)
    interpret = interpret_mode()

    from repro.estimators.knn import distance_weights, label_mix

    @jax.jit
    def run(q):
        d2, idx = knn_topk_kernel(q, xj, k=k, interpret=interpret)
        w = distance_weights(d2, eps, jnp)
        return (label_mix([qualj[idx[:, j]] for j in range(k)], w),
                label_mix([lenj[idx[:, j]] for j in range(k)], w))
    return run
