"""The reduction by stage on synthetic op events and spans whose answers
are known, its reader on a real (host-only) CPU trace, and the readers
of the stage metrics on records without their input."""
import types

import pytest

from bench import cell as cellmod
from bench import stages

MS = 1_000_000  # ns
STEP = "jit(_step_impl)"

# window 0..100 ms (bench spans); two decision windows
SPANS = [("bench.sim", 0, 100 * MS),
         ("bench.decide", 10 * MS, 30 * MS), ("bench.decide", 60 * MS, 80 * MS),
         ("rb.window", 10 * MS, 30 * MS), ("rb.assign", 10 * MS, 20 * MS),
         ("rb.stage", 10 * MS, 12 * MS), ("rb.telemetry", 12 * MS, 14 * MS),
         ("rb.dispatch", 14 * MS, 16 * MS), ("rb.fetch", 20 * MS, 25 * MS),
         ("rb.wait", 20 * MS, 24 * MS), ("rb.copy", 24 * MS, 25 * MS),
         ("rb.submit", 25 * MS, 30 * MS),
         ("rb.window", 60 * MS, 80 * MS), ("rb.assign", 60 * MS, 70 * MS)]
MODULES = [("jit__step_impl(7)", 15 * MS, 23 * MS),
           ("jit_dynamic_slice(2)", 24 * MS, 25 * MS),
           ("jit__step_impl(7)", 65 * MS, 70 * MS)]
OPS = [(15 * MS, 16 * MS, f"{STEP}/telemetry/scatter"),
       (16 * MS, 19 * MS, f"{STEP}/knn/top_k"),
       (19 * MS, 22 * MS, f"{STEP}/scan/while"),
       # inside the while: counted within it only, whatever its name
       (19.5 * MS, 21 * MS, f"{STEP}/knn/while/body/add"),
       (22 * MS, 23 * MS, ""),                       # unscoped: other
       (24 * MS, 25 * MS, "jit(dynamic_slice)/dynamic_slice"),  # not ours
       (65 * MS, 67 * MS, f"{STEP}/tpot/mul"),
       (67 * MS, 70 * MS, f"{STEP}/admission/and")]


def test_stage_of_reads_the_outermost_scope():
    assert stages.stage_of(f"{STEP}/knn/jit(take)/gather") == "knn"
    assert stages.stage_of(f"{STEP}/megakernel/pallas_call") == "megakernel"
    assert stages.stage_of("jit(f)/while/body/add") == "other"
    assert stages.stage_of("") == "other"


def test_outermost_drops_nested_ops():
    ops = [(0, 10, f"{STEP}/knn/a"), (2, 5, f"{STEP}/scan/b"),
           (5, 10, "c"), (10, 12, "d")]
    assert stages.outermost(ops) == [(0, 10, "knn"), (10, 12, "other")]


def test_an_unnamed_loop_takes_its_body_stage():
    """A `while` carries no op name on the TPU; its body's ops do."""
    ops = [(0, 10, ""), (1, 2, ""), (2, 3, f"{STEP}/scan/while/body/add"),
           (3, 4, f"{STEP}/knn/x"), (10, 11, "")]
    assert stages.outermost(ops) == [(0, 10, "scan"), (10, 11, "other")]


def test_innermost_segments_split_nested_spans():
    spans = [("p", 0, 10), ("c1", 2, 5), ("c2", 6, 8), ("q", 12, 14)]
    assert stages.innermost_segments(spans) == [
        ("p", 0, 2), ("c1", 2, 5), ("p", 5, 6), ("c2", 6, 8), ("p", 8, 10),
        ("q", 12, 14)]


def test_reduce_known_answers():
    out = stages.reduce([(OPS, MODULES)], SPANS)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["decide_windows"] == 2
    assert out["program_s"] == pytest.approx(0.013)
    got = {k: v * 1e3 for k, v in out["stage_s"].items()}
    assert got == pytest.approx({"telemetry": 1.0, "knn": 3.0, "scan": 3.0,
                                 "other": 1.0, "tpot": 2.0,
                                 "admission": 3.0})
    # the named stages and other make up the program's device time
    assert sum(out["stage_s"].values()) == pytest.approx(out["program_s"])
    assert stages.named_share(out) == pytest.approx(12 / 13)
    # device busy 15..23, 24..25, 65..70: 14 ms of 100
    assert out["idle_s"] == pytest.approx(0.086)
    want = {  # name: (count, host ms, idle ms, innermost idle ms)
        "rb.window": (2, 40, 26, 10), "rb.assign": (2, 20, 10, 5),
        "rb.stage": (1, 2, 2, 2), "rb.telemetry": (1, 2, 2, 2),
        "rb.dispatch": (1, 2, 1, 1), "rb.fetch": (1, 5, 1, 0),
        "rb.wait": (1, 4, 1, 1), "rb.copy": (1, 1, 0, 0),
        "rb.submit": (1, 5, 5, 5), stages.NO_SPAN: (0, 60, 60, 60)}
    assert set(out["spans"]) == set(want)
    for name, (count, host, idle, own) in want.items():
        v = out["spans"][name]
        assert v["count"] == count, name
        assert (v["host_s"] * 1e3, v["idle_s"] * 1e3,
                v["self_idle_s"] * 1e3) == pytest.approx((host, idle, own)), \
            name
    # innermost idle attributes every idle nanosecond once
    assert sum(v["self_idle_s"] for v in out["spans"].values()) == \
        pytest.approx(out["idle_s"])
    text = stages.table(out)
    assert "| knn | 1.5000 |" in text and "| rb.wait | 1 |" in text


def test_window_from_program_spans_without_bench_spans():
    rb_only = [sp for sp in SPANS if sp[0].startswith("rb.")]
    out = stages.reduce([(OPS, MODULES)], rb_only)
    assert out["window_s"] == pytest.approx(0.070)       # 10..80 ms
    assert out["decide_windows"] == 2                    # rb.window spans
    assert stages.NO_SPAN in out["spans"]


def test_nothing_to_reduce():
    assert stages.reduce([(OPS, MODULES)], SPANS[:3]) is None   # no rb.*
    assert stages.reduce([([], MODULES)], SPANS) is None        # no ops


def test_load_reads_op_names_from_event_metadata(tmp_path):
    """A device plane as the TPU profiler writes it: the op name is a
    stat of the event's metadata, times are line start plus offsets."""
    space = stages._xspace()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=7, value={"name": "tf_op"})
    dev.stat_metadata.add(key=8, value={"name": "flops"})
    dev.event_metadata.add(key=1, value={
        "name": "%sort = ...", "stats": [{"metadata_id": 8},
                                         {"metadata_id": 7,
                                          "str_value": f"{STEP}/knn/top_k:"}]})
    dev.event_metadata.add(key=2, value={"name": "%copy-start = ..."})
    dev.event_metadata.add(key=3, value={"name": "jit__step_impl(5)"})
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=500_000)
    ops.events.add(metadata_id=2, offset_ps=3_000_000, duration_ps=1_000)
    dev.lines.add(name="XLA Modules", timestamp_ns=1000).events.add(
        metadata_id=3, offset_ps=1_000_000, duration_ps=3_000_000)
    dev.lines.add(name="Async XLA Ops", timestamp_ns=0).events.add(
        metadata_id=2, offset_ps=0, duration_ps=1)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1, value={"name": "rb.wait"})
    host.event_metadata.add(key=2, value={"name": "other.event"})
    py = host.lines.add(name="python3", timestamp_ns=500)
    py.events.add(metadata_id=1, offset_ps=0, duration_ps=5_000_000)
    py.events.add(metadata_id=2, offset_ps=0, duration_ps=1)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    planes, spans = stages.load(str(path))
    assert planes == [([(3000.0, 3500.0, f"{STEP}/knn/top_k:"),
                        (4000.0, 4001.0, "")],
                       [("jit__step_impl(5)", 2000.0, 5000.0)])]
    assert spans == [("rb.wait", 500.0, 5500.0)]


def test_cpu_trace_has_spans_but_no_device(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    from repro.core.trace import span
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with span("rb.window"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    planes, spans = stages.load(stages.newest(tmp_path))
    assert [n for n, _, _ in spans] == ["rb.window"]
    assert stages.reduce(planes, spans) is None
    assert stages.reduce_dir(tmp_path) is None
    assert stages.main([str(tmp_path)]) == 1
    assert stages.reduce_dir(tmp_path / "none") is None


# -- the readers ---------------------------------------------------------------

STATS_METRICS = {
    "stage_ms_per_window": ({"calls": 4, "stage_s": 0.002}, 0.5),
    "telemetry_ms_per_window": ({"calls": 4, "telemetry_s": 0.004}, 1.0),
    "dispatch_ms_per_window": ({"calls": 4, "dispatch_s": 0.006}, 1.5),
    "fetch_ms_per_window": ({"calls": 4, "device_s": 0.001,
                             "sync_s": 0.003}, 1.0),
    "uploads_per_window": ({"calls": 4, "uploads": 46}, 11.5),
    "dirty_row_share": ({"dirty_checks": 4, "dirty_rows_seen": 26}, 0.5),
}


def _rec(stats=None, trace=None):
    return types.SimpleNamespace(stats=stats or {}, trace=trace, roster=13)


@pytest.mark.parametrize("name", sorted(STATS_METRICS))
def test_stats_readers(name):
    read = cellmod.load_reader(name)
    stats, want = STATS_METRICS[name]
    assert read(_rec(stats)) == pytest.approx(want)
    assert read(_rec()) is None                        # no such counters
    empty = {k: 0 for k in stats}
    assert read(_rec(empty)) is None                   # nothing counted


@pytest.mark.parametrize("name,stage", [("knn_device_ms_per_window", "knn"),
                                        ("scan_device_ms_per_window",
                                         "scan")])
def test_device_stage_readers(name, stage, monkeypatch):
    read = cellmod.load_reader(name)
    traced = {"decide_windows": 2}
    monkeypatch.setattr(stages, "reduce_dir", lambda *a: None)
    assert read(_rec()) is None                        # untraced run
    assert read(_rec(trace=traced)) is None            # no trace file
    out = stages.reduce([(OPS, MODULES)], SPANS)
    monkeypatch.setattr(stages, "reduce_dir", lambda *a: out)
    assert read(_rec(trace=traced)) == pytest.approx(1.5)
    assert read(_rec(trace={"decide_windows": 0})) is None
    mega = dict(out, stage_s={"megakernel": 0.01})     # no such scope
    monkeypatch.setattr(stages, "reduce_dir", lambda *a: mega)
    assert read(_rec(trace=traced)) is None
    # executables without scopes (an older tree's compile, from the
    # cache): a partial split is no reading
    stale = dict(out, stage_s=dict(out["stage_s"], other=0.002))
    assert stages.named_share(stale) == pytest.approx(12 / 14)
    monkeypatch.setattr(stages, "reduce_dir", lambda *a: stale)
    assert read(_rec(trace=traced)) is None
