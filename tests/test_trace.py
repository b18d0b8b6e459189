"""The program's own spans and device-stage names.

`repro.core.trace.span` is one mechanism for the runner's host-clock
totals and the profiler's trace: with a profiler session on, the
decision runner's and the engine's `rb.*` spans land in the session's
`.xplane.pb`, nested and in order; the fused step's stages carry named
scopes in their op metadata.
"""
import numpy as np
import pytest

from repro.core import RBConfig, RouteBalance, make_requests, run_cell
from repro.core.hotpath import FusedHotPath
from repro.core.trace import span
from repro.serving.cluster import ClusterSim
from repro.serving.request import batch_columns
from repro.serving.workload import poisson_arrivals

RUNNER_SPANS = ("rb.stage", "rb.telemetry", "rb.dispatch", "rb.fetch",
                "rb.wait", "rb.copy")
STAGES = ("telemetry", "knn", "tpot", "admission", "scan")


def _spans(trace_dir):
    """(name, start_ns, end_ns) of every host `rb.*` event, by start."""
    from jax.profiler import ProfileData
    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    out = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in data.planes if not plane.name.startswith("/device:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("rb.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _runner(ctx):
    sim = ClusterSim(ctx["tiers"], ctx["names"], seed=0)
    fp = FusedHotPath(ctx["bundle"], sim.instances,
                      RBConfig(decision_backend="fused"))
    reqs = make_requests(ctx["ds"], "test", np.zeros(6))
    cols, rows = batch_columns(reqs)
    cols.ensure_embeddings(ctx["bundle"].encoder)
    return fp, sim, cols, rows


def test_span_adds_its_seconds_to_its_key():
    st = {"x_s": 1.0}
    with span("rb.test", st, "x_s") as sp:
        pass
    assert sp.seconds > 0.0
    assert st["x_s"] == 1.0 + sp.seconds
    with span("rb.test") as bare:          # no key: nothing to add to
        pass
    assert bare.seconds > 0.0 and st["x_s"] == 1.0 + sp.seconds


def test_runner_spans_in_the_trace_nested_and_ordered(small_ctx, tmp_path):
    import jax
    fp, sim, cols, rows = _runner(small_ctx)
    fp.decide_cols(cols, rows, sim.tel).fetch()      # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        fp.decide_cols(cols, rows, sim.tel).fetch()
    finally:
        jax.profiler.stop_trace()
    ev = {name: (name, s, e) for name, s, e in _spans(tmp_path)}
    assert tuple(n for n, _, _ in _spans(tmp_path)) == RUNNER_SPANS
    order = [ev[n] for n in ("rb.stage", "rb.telemetry", "rb.dispatch",
                             "rb.fetch")]
    for a, b in zip(order, order[1:]):
        assert a[2] <= b[1], (a, b)              # one after the other
    assert _inside(ev["rb.wait"], ev["rb.fetch"])
    assert _inside(ev["rb.copy"], ev["rb.fetch"])
    assert ev["rb.wait"][2] <= ev["rb.copy"][1]


def test_engine_spans_wrap_the_runner_spans(small_ctx, tmp_path):
    """rb.window holds rb.assign (and the runner's staging inside it),
    the fetch, then rb.submit."""
    import jax
    reqs = make_requests(small_ctx["ds"], "test",
                         poisson_arrivals(20.0, 12, seed=4))
    rb = RouteBalance(RBConfig(decision_backend="fused",
                               charge_compute=False),
                      small_ctx["bundle"], small_ctx["tiers"])
    run_cell(rb, small_ctx["tiers"], small_ctx["names"],
             make_requests(small_ctx["ds"], "test",
                           poisson_arrivals(20.0, 12, seed=5)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_cell(rb, small_ctx["tiers"], small_ctx["names"], reqs)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    windows = [s for s in spans if s[0] == "rb.window"]
    assert windows and len(windows) == rb._fused.stats["calls"]
    for w in windows:
        inner = [s for s in spans if s is not w and _inside(s, w)]
        names = [s[0] for s in inner]
        assert names[0] == "rb.assign" and names[-1] == "rb.submit"
        assert set(RUNNER_SPANS) <= set(names)
        assign = inner[0]
        for s in inner:
            if s[0] in ("rb.stage", "rb.telemetry", "rb.dispatch"):
                assert _inside(s, assign)
        fetch = next(s for s in inner if s[0] == "rb.fetch")
        assert fetch[2] <= inner[-1][1]          # submit after the fetch


@pytest.fixture(scope="module")
def step_program(small_ctx):
    """The optimized text of the fused step the runner dispatched."""
    fp, sim, cols, rows = _runner(small_ctx)
    handed = {}
    step = fp._step

    def keep(*args):
        handed["args"] = args
        return step(*args)

    fp._step = keep
    fp.decide_cols(cols, rows, sim.tel).fetch()
    return step.lower(*handed["args"]).compile().as_text()


@pytest.mark.parametrize("scope", STAGES)
def test_step_stages_carry_named_scopes(step_program, scope):
    """Each stage of the fused step, the scatter of the dirty rows
    too, keeps ops under its scope in the optimized program's op
    metadata, so a device trace can time it."""
    assert f'op_name="jit(_step_impl)/{scope}/' in step_program

