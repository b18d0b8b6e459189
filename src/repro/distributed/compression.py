"""Compression for everything that crosses the (simulated) network:
gradients and telemetry digests.

``compress_decompress`` is the pure single-program gradient form (int8
quantization with EF-SGD error feedback): under GSPMD the data-axis
psum of the quantized tensor is what crosses the network (8-bit payload
instead of 16/32), and the local quantization error is carried to the
next step, preserving convergence. ``shardmap_allreduce`` is the
explicit-collective variant (int8 payload, int32 accumulation) for
meshes where the launcher wants the collective pinned.

``TelemetryDigest`` + ``encode_digest``/``decode_digest`` are the
hierarchical scheduler's control plane (`repro.serving.hierarchy`):
each cell summarizes its dead-reckoned telemetry into per-tier
occupancy/depth/free vectors, the digest is serialized to wire bytes
(exact float32, or the same int8 scale-quantization the gradient path
uses), and the `GlobalBalancer` routes ONLY from what survived the
round trip — so the lossy mode's routing error is exactly the codec's
quantization error, nothing hidden. Digests carry the sending cell's
sim-clock timestamp; `digest_fresh` is the staleness contract: a
balancer may use a digest only while ``now - digest.t <= stale_s``,
otherwise the cell must be treated as dark (the same discipline the
telemetry watchdog applies to instance rows).
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _quantize(x, scale):
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def compress_decompress(grads, error_state=None
                        ) -> Tuple[Any, Any, Dict[str, jax.Array]]:
    """Per-tensor int8 quantize(+error feedback) -> dequantize.

    Returns (grads_hat, new_error_state, metrics). grads_hat replaces the
    raw grads in the optimizer update; the psum over data happens on the
    int8-scaled values downstream (GSPMD)."""
    if error_state is None:
        error_state = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        gf = g.astype(jnp.float32) + e
        scale = jnp.maximum(jnp.max(jnp.abs(gf)) / 127.0, 1e-12)
        q = _quantize(gf, scale)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), gf - deq

    flat_g = jax.tree.leaves(grads)
    flat_e = jax.tree.leaves(error_state)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    treedef = jax.tree.structure(grads)
    ghat = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_e = jax.tree.unflatten(treedef, [o[1] for o in outs])
    err_norm = sum(jnp.sum(jnp.square(o[1])) for o in outs)
    return ghat, new_e, {"compression_err_sq": err_norm}


def shardmap_allreduce(x, mesh, axes=("data",)):
    """Explicit int8-payload all-reduce over the data axes: quantize
    locally, psum int32 accumulators, dequantize with the max scale."""
    from jax.sharding import PartitionSpec as P

    def local(xl):
        scale = jnp.maximum(jnp.max(jnp.abs(xl)) / 127.0, 1e-12)
        scale = jax.lax.pmax(scale, axes)          # shared scale
        q = _quantize(xl, scale).astype(jnp.int32)
        s = jax.lax.psum(q, axes)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return (s.astype(jnp.float32) * scale / n).astype(xl.dtype)

    spec = P(*([None] * x.ndim))
    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


# ---------------------------------------------------------------------------
# Telemetry digests (hierarchical scheduling control plane)
# ---------------------------------------------------------------------------

_DIGEST_MAGIC = b"RBTD"
_DIGEST_VERSION = 1
_DIGEST_MODES = ("exact", "int8")
# magic, version, mode, cell, seq, t, n_alive, n_total, n_tiers
_HEADER = struct.Struct("<4sBBiidiii")


@dataclasses.dataclass
class TelemetryDigest:
    """One cell's compressed telemetry summary: per-tier occupancy
    (batch fill fraction of the alive capacity), queue depth
    (pending + queued work) and free decode slots, plus the alive
    roster count and the cell's sim-clock send time."""
    cell: int
    seq: int
    t: float
    n_alive: int
    n_total: int
    tier_occupancy: np.ndarray          # (T,) float32
    tier_depth: np.ndarray              # (T,) float32
    tier_free: np.ndarray               # (T,) float32

    @property
    def depth_total(self) -> float:
        return float(self.tier_depth.sum())

    @property
    def free_total(self) -> float:
        return float(self.tier_free.sum())

    def age(self, now: float) -> float:
        return now - self.t


def digest_fresh(d: TelemetryDigest, now: float, stale_s: float) -> bool:
    """The staleness-bound contract: a digest is usable while its age
    is within ``stale_s`` of the observer's clock; past that the cell
    is dark and a balancer must route around it (or fall back to blind
    round-robin when every cell is dark)."""
    return d.age(now) <= stale_s


def digest_from_telemetry(tel, tier_of_slot: np.ndarray, n_tiers: int,
                          cell: int, seq: int, t: float
                          ) -> TelemetryDigest:
    """Summarize a TelemetryArrays view (a cell mirror or the full
    array) into per-tier vectors. ``tier_of_slot`` (n,) int maps each
    telemetry row to its tier index; quarantined/dead rows contribute
    nothing (the balancer must not route toward capacity the watchdog
    masked)."""
    alive = np.asarray(tel.alive, bool)
    tos = np.asarray(tier_of_slot)
    wsum = lambda w: np.bincount(  # noqa: E731 - tiny local reducer
        tos[alive], weights=np.asarray(w, np.float64)[alive],
        minlength=n_tiers).astype(np.float32)
    cap = wsum(tel.max_batch)
    occ = wsum(tel.batch) / np.maximum(cap, 1.0)
    depth = wsum(np.asarray(tel.pending) + np.asarray(tel.queue))
    free = wsum(tel.free)
    return TelemetryDigest(cell=int(cell), seq=int(seq), t=float(t),
                           n_alive=int(alive.sum()), n_total=len(alive),
                           tier_occupancy=occ, tier_depth=depth,
                           tier_free=free)


def _encode_plane(x: np.ndarray, mode: str) -> bytes:
    x = np.asarray(x, np.float32)
    if mode == "exact":
        return x.tobytes()
    # int8: the gradient codec's scale-quantization, one scale per plane
    scale = np.float32(max(float(np.abs(x).max()) / 127.0, 1e-12))
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return struct.pack("<f", scale) + q.tobytes()


def _decode_plane(buf: bytes, off: int, n: int, mode: str
                  ) -> Tuple[np.ndarray, int]:
    if mode == "exact":
        end = off + 4 * n
        return np.frombuffer(buf[off:end], np.float32).copy(), end
    (scale,) = struct.unpack_from("<f", buf, off)
    off += 4
    end = off + n
    q = np.frombuffer(buf[off:end], np.int8)
    return q.astype(np.float32) * np.float32(scale), end


def encode_digest(d: TelemetryDigest, mode: str = "exact") -> bytes:
    """Serialize a digest to wire bytes. ``exact`` ships raw float32
    planes (bitwise round trip); ``int8`` ships one float32 scale + an
    int8 payload per plane (the `_quantize` semantics), cutting the
    plane payload 4x at <= scale/2 absolute error per entry."""
    assert mode in _DIGEST_MODES, mode
    head = _HEADER.pack(_DIGEST_MAGIC, _DIGEST_VERSION,
                        _DIGEST_MODES.index(mode), d.cell, d.seq, d.t,
                        d.n_alive, d.n_total, len(d.tier_depth))
    return head + b"".join(
        _encode_plane(p, mode)
        for p in (d.tier_occupancy, d.tier_depth, d.tier_free))


def decode_digest(buf: bytes) -> TelemetryDigest:
    magic, ver, mode_i, cell, seq, t, n_alive, n_total, T = \
        _HEADER.unpack_from(buf, 0)
    assert magic == _DIGEST_MAGIC and ver == _DIGEST_VERSION, \
        (magic, ver)
    mode = _DIGEST_MODES[mode_i]
    off = _HEADER.size
    occ, off = _decode_plane(buf, off, T, mode)
    depth, off = _decode_plane(buf, off, T, mode)
    free, off = _decode_plane(buf, off, T, mode)
    return TelemetryDigest(cell=cell, seq=seq, t=t, n_alive=n_alive,
                           n_total=n_total, tier_occupancy=occ,
                           tier_depth=depth, tier_free=free)
