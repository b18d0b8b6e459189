"""Reduction of a profiler trace by stage: the decision program's device
time under each of its named scopes, and the program's host spans
(`rb.*`) with the device's idle time inside each.

It reads the `.xplane.pb` that `bench/xplane.py` reads (the newest under
`.bench_out/trace`) and uses its interval helpers. From the trace:

- device ops: events of each device plane's "XLA Ops" line. An op that
  runs inside another op (a loop body inside its `while`) counts within
  the outer op only. An op is the decision program's when it starts
  inside one of the program's "XLA Modules" events (`xplane.PROGRAM`);
  its stage is the first component of its op name (the `OP_NAME` stat
  of the event's metadata: the op's `op_name`) that names a scope of the
  program (`SCOPES`, put there by `core/hotpath.py`), else `other`; an
  outer op with no stage of its own takes the first stage named inside
  it (a `while` has no op name, its body's ops have);
- host spans: the program's `rb.*` spans (`core/trace.py`) and the
  benchmark's `bench.*` spans. The traced window runs from the first
  `bench.*` span's start to the last one's end, as in `bench/xplane.py`
  (from the `rb.*` spans in a trace without `bench.*` spans), and a
  decision window is a `bench.decide` span (else an `rb.window` span).

Each `rb.*` span name gets its host time, the device-idle time inside
it, and the idle time where it is the innermost `rb.*` span open: the
last sums, over the names and `NO_SPAN`, to the window's idle time.

    python3 -m bench.stages .bench_out/trace    # prints the table
    python3 -m bench.stages <file>.xplane.pb
"""
from __future__ import annotations

import glob
import math
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from .xplane import PROGRAM, clip, gaps, length, overlap, union

SCOPES = ("telemetry", "knn", "tpot", "admission", "scan", "megakernel")
OTHER = "other"
SPAN_PREFIX = "rb."
NO_SPAN = "outside rb spans"
OP_NAME = "tf_op"
# below this share of the program under named stages, the executables
# that ran carry no scopes (loaded from a compile cache entry that a
# tree without them wrote: the cache key leaves op metadata out)
MIN_NAMED = 0.9
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"

Span = Tuple[str, float, float]               # (name, start_ns, end_ns)
Op = Tuple[float, float, str]                 # (start_ns, end_ns, op_name)
Plane = Tuple[Sequence[Op], Sequence[Span]]   # (ops, module events)


def stage_of(op_name: str) -> str:
    """The first scope of the program named in an op name, else
    `other`."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return OTHER


def outermost(ops: Sequence[Op]) -> List[Tuple[float, float, str]]:
    """(start, end, stage) of the ops not inside another op, in time
    order. The stage is the op's own, or, where its own names none (a
    `while` carries no op name; its body's ops do), the first stage that
    an op inside it names."""
    out: List[list] = []
    end = -math.inf
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        if s >= end:
            out.append([s, e, stage_of(name)])
            end = e
        elif out[-1][2] == OTHER:
            out[-1][2] = stage_of(name)
    return [tuple(op) for op in out]


def innermost_segments(spans: Sequence[Span]) -> List[Span]:
    """Cut the time the spans cover into (name, start, end) pieces, each
    named by the innermost span open there (spans nest, as one thread's
    do)."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []        # (name, end)
    t = -math.inf

    def unwind(upto: float):
        nonlocal t
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        unwind(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        t = s
        if stack:
            e = min(e, stack[-1][1])
        stack.append((name, e))
    unwind(math.inf)
    return out


def reduce(planes: Sequence[Plane], spans: Sequence[Span]) -> Optional[Dict]:
    """planes: per device plane, its ops and its "XLA Modules" events;
    spans: host spans, `rb.*` and `bench.*`. None without an `rb.*` span
    or a device op."""
    rb = [sp for sp in spans if sp[0].startswith(SPAN_PREFIX)]
    planes = [(ops, mods) for ops, mods in planes if ops]
    if not rb or not planes:
        return None
    bounds = [sp for sp in spans if not sp[0].startswith(SPAN_PREFIX)] or rb
    lo = min(s for _, s, _ in bounds)
    hi = max(e for _, _, e in bounds)
    windows = (sum(1 for sp in spans if sp[0] == "bench.decide")
               or sum(1 for sp in rb if sp[0] == "rb.window"))
    stage_ns: Dict[str, float] = {}
    program_ns = 0.0
    busy = []
    for ops, mods in planes:
        program = clip(union([(s, e) for n, s, e in mods if PROGRAM in n]),
                       lo, hi)
        program_ns += length(program)
        busy.append(clip(union([(s, e) for s, e, _ in ops]), lo, hi))
        i = 0
        for s, e, st in outermost(ops):
            if not lo <= s < hi:
                continue
            while i < len(program) and program[i][1] <= s:
                i += 1
            if i < len(program) and program[i][0] <= s:
                stage_ns[st] = stage_ns.get(st, 0.0) + min(e, hi) - s
    n = len(planes)

    def idle_s(merged) -> float:
        """Seconds of `merged` (sorted, disjoint) the device is idle."""
        return (length(merged) - sum(overlap(b, merged) for b in busy) / n
                ) * 1e-9

    rb = [(name, max(s, lo), min(e, hi)) for name, s, e in rb
          if min(e, hi) > max(s, lo)]
    segments = innermost_segments(rb)
    per_span: Dict[str, Dict[str, float]] = {}
    for name in sorted({sp[0] for sp in rb}):
        own = union([(s, e) for nm, s, e in rb if nm == name])
        per_span[name] = {
            "count": sum(1 for sp in rb if sp[0] == name),
            "host_s": length(own) * 1e-9, "idle_s": idle_s(own),
            "self_idle_s": idle_s([(s, e) for nm, s, e in segments
                                   if nm == name])}
    outside = gaps(union([(s, e) for _, s, e in rb]), lo, hi)
    per_span[NO_SPAN] = {"count": 0, "host_s": length(outside) * 1e-9,
                         "idle_s": idle_s(outside),
                         "self_idle_s": idle_s(outside)}
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": idle_s([(lo, hi)]),
        "decide_windows": windows,
        "program_s": program_ns * 1e-9 / n,
        "stage_s": {k: v * 1e-9 / n for k, v in stage_ns.items()},
        "spans": per_span,
    }


# The subset of the profiler's XSpace schema (tsl/profiler/protobuf/
# xplane.proto) read here: `jax.profiler.ProfileData` does not expose an
# op's metadata stats, where the op name is kept. Fields by number;
# a map is its repeated entry message on the wire.
_SCHEMA = {
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent*")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat*")],
    "XStatMetadata": [("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine*"),
               ("event_metadata", 4, "EventMetadataEntry*"),
               ("stat_metadata", 5, "StatMetadataEntry*")],
    "XSpace": [("planes", 1, "XPlane*")],
}


def _xspace():
    """The XSpace message class, built from `_SCHEMA`."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_stages_xplane.proto", package="bench_stages",
        syntax="proto3")
    for name, fields in _SCHEMA.items():
        m = f.message_type.add(name=name)
        for field, number, kind in fields:
            many = kind.endswith("*")
            kind = kind.rstrip("*")
            fd = m.field.add(name=field, number=number,
                             label=F.LABEL_REPEATED if many
                             else F.LABEL_OPTIONAL)
            if kind in _SCHEMA:
                fd.type, fd.type_name = F.TYPE_MESSAGE, f".bench_stages.{kind}"
            else:
                fd.type = getattr(F, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_stages.XSpace"))


def load(path: str) -> Tuple[List[Plane], List[Span]]:
    """(planes, spans) from one `.xplane.pb` file."""
    space = _xspace().FromString(Path(path).read_bytes())
    planes: List[Plane] = []
    spans: List[Span] = []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}

        def events(line):
            """(metadata id, start ns, end ns) of the line's events."""
            t0 = line.timestamp_ns
            return [(ev.metadata_id, t0 + ev.offset_ps * 1e-3,
                     t0 + (ev.offset_ps + ev.duration_ps) * 1e-3)
                    for ev in line.events]

        if plane.name.startswith("/device:"):
            key = next((e.key for e in plane.stat_metadata
                        if e.value.name == OP_NAME), None)
            op_name = {e.key: next((st.str_value for st in e.value.stats
                                    if st.metadata_id == key), "")
                       for e in plane.event_metadata}
            lines = {line.name: events(line) for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            planes.append(([(s, e, op_name[i])
                            for i, s, e in lines.get("XLA Ops", [])],
                           [(names[i], s, e)
                            for i, s, e in lines.get("XLA Modules", [])]))
        else:
            spans += [(names[i], s, e) for line in plane.lines
                      for i, s, e in events(line)
                      if names[i].startswith((SPAN_PREFIX, "bench."))]
    return planes, spans


def newest(trace_dir: Path = TRACE_DIR) -> Optional[str]:
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(trace_dir: Path = TRACE_DIR) -> Optional[Dict]:
    """The reduction of the newest trace under `trace_dir` (None without
    one)."""
    path = newest(trace_dir)
    return reduce(*load(path)) if path else None


def named_share(out: Dict) -> float:
    """Share of the program's device time under its named stages."""
    total = sum(out["stage_s"].values())
    return 1.0 - out["stage_s"].get(OTHER, 0.0) / total if total else 0.0


def device_ms_per_window(rec, stage: str,
                         trace_dir: Path = TRACE_DIR) -> Optional[float]:
    """Device ms of the program's ops under `stage` per decision window
    traced (`rec.trace["decide_windows"]`, as
    `program_device_ms_per_window` divides); None for an untraced run, a
    trace with no op under that stage, or one whose program ran with
    less than `MIN_NAMED` of its time under named stages."""
    if not rec.trace or not rec.trace.get("decide_windows"):
        return None
    out = reduce_dir(trace_dir)
    if (out is None or stage not in out["stage_s"]
            or named_share(out) < MIN_NAMED):
        return None
    return out["stage_s"][stage] / rec.trace["decide_windows"] * 1e3


def table(out: Dict) -> str:
    """The reduction as two markdown tables, per decision window."""
    w = max(out["decide_windows"], 1)
    program = out["program_s"]
    rows = [f"{out['decide_windows']} decision windows in "
            f"{out['window_s']:.3f} s traced; program "
            f"{program / w * 1e3:.4f} ms per window, "
            f"{named_share(out):.1%} of it under named stages", "",
            "| Stage | device ms/window | share of program |",
            "| --- | --- | --- |"]
    for st in SCOPES + (OTHER,):
        if st in out["stage_s"]:
            v = out["stage_s"][st]
            rows.append(f"| {st} | {v / w * 1e3:.4f} | "
                        f"{v / program if program else 0.0:.3f} |")
    rows += ["", "| Span | count | host ms/window | idle ms/window | "
             "innermost idle ms/window |", "| --- | --- | --- | --- | --- |"]
    for name, v in out["spans"].items():
        rows.append(f"| {name} | {v['count']} | {v['host_s'] / w * 1e3:.4f}"
                    f" | {v['idle_s'] / w * 1e3:.4f} | "
                    f"{v['self_idle_s'] / w * 1e3:.4f} |")
    return "\n".join(rows)


def main(argv: Sequence[str]) -> int:
    """Print the table of a trace directory's newest trace, or of one
    `.xplane.pb` file."""
    where = Path(argv[0]) if argv else TRACE_DIR
    out = reduce(*load(str(where))) if where.is_file() else reduce_dir(where)
    if out is None:
        print("no trace with rb.* spans and device ops", file=sys.stderr)
        return 1
    print(table(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
