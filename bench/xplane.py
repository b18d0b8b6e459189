"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes `<dir>/plugins/profile/<run>/*.xplane.pb`.
`load` reads it with `jax.profiler.ProfileData` into plain interval
lists; `reduce` works on those lists alone, so it is tested on
synthetic intervals whose answers are known.

From the trace:

- device ops: events of each device plane's "XLA Ops" line (all of the
  plane's lines where it has none); busy is their union;
- decision program: events of the "XLA Modules" line whose name holds
  `PROGRAM` (the jitted step of the fused runner, whatever backend
  runs inside it);
- host spans: the benchmark's own `bench.*` annotations (bench.sim
  around simulator slices, bench.decide from the call into the policy
  to the end of the fetch, bench.assign, bench.sync, bench.dispatch,
  bench.fetch inside it).

The traced window runs from the first `bench.*` span's start to the
last one's end.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

PROGRAM = "_step_impl"
SPAN_PREFIX = "bench."
NAME_CHARS = 120            # device op names are HLO text: keep the head


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None
                                   or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best[0] if best else "outside bench spans"


def reduce(devices: Sequence[Sequence[Event]], modules: Sequence[Event],
           spans: Sequence[Event]) -> Optional[Dict]:
    """devices: per device plane, its op events; modules: program-level
    events of all planes; spans: host `bench.*` spans. None when the
    trace holds no host span or no device op."""
    if not spans or not any(devices):
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    planes = [clip(union([(s, e) for _, s, e in ops]), lo, hi)
              for ops in devices if ops]
    busy = sum(length(p) for p in planes) / len(planes)
    decide = union([(s, e) for n, s, e in spans if n == "bench.decide"])
    decide = clip(decide, lo, hi)
    in_decide = sum(overlap(p, decide) for p in planes) / len(planes)
    program = sum(min(e, hi) - max(s, lo) for n, s, e in modules
                  if PROGRAM in n and min(e, hi) > max(s, lo))
    per_op: Dict[str, float] = {}
    for ops in devices:
        for n, s, e in ops:
            if lo <= s < hi:
                n = n[:NAME_CHARS]
                per_op[n] = per_op.get(n, 0.0) + (e - s)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps(planes[0], lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "device_idle_share": 1.0 - busy / (hi - lo),
        "decide_s": length(decide) * 1e-9,
        "decide_idle_share": (1.0 - in_decide / length(decide)
                              if length(decide) > 0 else None),
        "program_s": program * 1e-9 / len(planes),
        "decide_windows": sum(1 for n, _, _ in spans if n == "bench.decide"),
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in top_ops],
            "idle_gaps": [[innermost(spans, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in idle]},
    }


def load(path: str):
    """(devices, modules, spans) from one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, spans = [], [], []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops_lines = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ln in ops_lines for ev in ln.events]
            devices.append(ops)
            modules.extend((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ln in lines if ln.name == "XLA Modules"
                           for ev in ln.events)
        else:
            spans.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ln in lines for ev in ln.events
                         if ev.name.startswith(SPAN_PREFIX))
    return devices, modules, spans


def reduce_dir(trace_dir: Path, trace_s: float) -> Dict:
    """Reduce the newest trace under `trace_dir`. Always returns a dict
    with `window_s` and `busy_s` (the latter 0 without a device plane)."""
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    out = reduce(*load(files[-1])) if files else None
    if out is None:
        return {"window_s": trace_s, "busy_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    return out
