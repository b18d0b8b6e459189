"""The trace reduction on synthetic intervals whose answers are known,
and the reader on a real (host-only) CPU trace."""
import pytest

from bench import xplane

MS = 1_000_000  # ns


def test_union_overlap_and_gaps():
    u = xplane.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert u == [(0, 3), (5, 9)]
    assert xplane.length(u) == 7
    assert xplane.overlap(u, [(2, 6)]) == 2
    assert xplane.gaps(u, 0, 10) == [(3, 5), (9, 10)]
    assert xplane.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_reduce_known_answers():
    # window 0..100 ms (host spans), device busy 10..20 and 50..70 ms
    spans = [("bench.sim", 0, 100 * MS),
             ("bench.decide", 10 * MS, 30 * MS),
             ("bench.decide", 60 * MS, 80 * MS),
             ("bench.fetch", 25 * MS, 30 * MS)]
    ops = [("fusion.1", 10 * MS, 15 * MS), ("dot.2", 14 * MS, 20 * MS),
           ("fusion.1", 50 * MS, 70 * MS)]
    modules = [("jit__step_impl(3)", 10 * MS, 20 * MS),
               ("jit__step_impl(3)", 60 * MS, 70 * MS),
               ("jit_other(1)", 50 * MS, 60 * MS)]
    out = xplane.reduce([ops], modules, spans)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["device_idle_share"] == pytest.approx(0.7)
    # decide spans cover 40 ms; busy inside them: 10..20 and 60..70
    assert out["decide_idle_share"] == pytest.approx(0.5)
    assert out["program_s"] == pytest.approx(0.020)
    assert out["decide_windows"] == 2
    assert out["breakdown"]["device_ops"][0] == ["fusion.1",
                                                 pytest.approx(0.025)]
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.sim", pytest.approx(0.030)]      # 20..50
    assert gaps[1] == ["bench.sim", pytest.approx(0.030)]      # 70..100
    assert gaps[2] == ["bench.sim", pytest.approx(0.010)]      # 0..10


def test_gap_named_by_innermost_span():
    spans = [("bench.sim", 0, 100), ("bench.decide", 10, 60),
             ("bench.fetch", 40, 60)]
    assert xplane.innermost(spans, 50) == "bench.fetch"
    assert xplane.innermost(spans, 20) == "bench.decide"
    assert xplane.innermost(spans, 200) == "outside bench spans"


def test_no_device_no_numbers(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.decide"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    devices, modules, spans = xplane.load(
        next(tmp_path.glob("plugins/profile/*/*.xplane.pb")).as_posix())
    assert [n for n, _, _ in spans] == ["bench.decide"]
    assert xplane.reduce(devices, modules, spans) is None
    out = xplane.reduce_dir(tmp_path, 1.5)
    assert out["busy_s"] == 0.0 and out["window_s"] == 1.5
