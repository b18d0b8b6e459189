"""One benchmark cell: set-up, the measured window, and the check.

A cell is one entry of `workloads` in BENCHMARK.json: a fleet
configuration (`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<mix>.json`). Everything here is general; what belongs
to one configuration, mix or metric lives in its own file.

Set-up (timed as `setup_s`): the configuration's world, dataset and
estimator bundle; the RouteBalance engine on the decision backend the
configuration names (else the one the code selects); the requests,
drawn from the seed (`requests_for`): the mix's one-shot stream and,
where the mix has a `sessions` block, its multi-turn chat stream
(`bench/sessions.py`), merged by arrival time; one decision at every
batch size the trace can put in a window, so that every program the
window runs (the step of each pow2 batch bucket; the fetch slices its
result on the host) compiles or loads from the compile cache; any
fleet events the mix's process schedules; and `fill_s` simulated
seconds of the trace replayed, unmeasured, so the window starts on a
loaded fleet.

Window: the open-loop trace keeps arriving (simulated clock) while the
host clock is under `--seconds`; then arrivals stop and what has
arrived drains. Every decision of the window counts, and so does every
request that arrived in it. Every backend compile in the window, of
any program, is counted (`compiles_in_window`).

The timed path is observed from outside: the policy's `assign` and the
result's `fetch` are wrapped with the benchmark's own clock (decision
latency), and the fused runner's `decide_cols`/`_step` are wrapped to
keep each window's inputs and the program's outputs for the check
(with the affinity term on, also the instances' prefix sketches).
The harness reads `_step`'s outputs by position: (choice, est_T,
l_chosen, d, b, free, ctx, d1, b1, f1).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import arrivals as traffic
from . import check as checking
from . import sessions

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
# the engine's adaptive window never exceeds this (core/engine.py)
WINDOW_MAX_S = 0.30
SLICE_S = 0.25            # simulated seconds between looks at the clock
TRACE_S = 3.0             # host seconds of the window the profiler sees
REF_REQUESTS = 6000       # requests compared against the reference
# JAX's monitoring event around each backend compile (or persistent
# cache load) of a program missing from the in-memory cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    mix: traffic.Mix
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics: List[dict], name: str) -> List[dict]:
    return [m for m in metrics
            if "workloads" not in m or name in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` as BENCHMARK.json and its files describe it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = traffic.Mix.load(root / "bench" / "traffic"
                           / f"{w['traffic']}.json")
    return Cell(name, w["config"], config, mix, int(w["chips"]),
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name))


def traffic_seed(seed: int) -> int:
    """The traffic's seed from the run's `--seed` (any integer)."""
    return int(np.random.SeedSequence(int(seed) % (1 << 64))
               .generate_state(1)[0])


def fleet_tiers(config: dict):
    """The configuration's tiers: the program's pool, with each tier's
    replica count from the file and every other number checked
    against it."""
    import repro.serving.tiers as tiers_mod
    pool = {t.name: t for t in getattr(tiers_mod, config["pool"])()}
    out = []
    for spec in config["tiers"]:
        t = pool[spec["name"]]
        held = {"model": t.model, "chips": t.n_chips,
                "price_in": t.price_in, "price_out": t.price_out,
                "bw_eff": t.bw_eff, "max_batch": t.max_batch}
        for key, val in held.items():
            if spec[key] != val:
                raise SystemExit(f"config {config['name']}: tier "
                                 f"{t.name} {key} is {val} in the program,"
                                 f" {spec[key]} in the file")
        out.append(dataclasses.replace(t, n_instances=int(spec["instances"])))
    return out


# the decision settings `bench/reference.py` implements (besides any
# affinity_weight in [0, 1])
COVERED = {"latency_mode": ("full",), "lpt": (True,), "budget_filter": (True,),
           "learned_tpot": (True,), "shard_cells": (0, 1),
           "window_coalesce": (1,),
           "decision_backend": ("fused", "megakernel")}


def decision_config(config: dict):
    """RBConfig from the configuration's "decision" block: each key that
    names an RBConfig field sets it (the others describe), the rest keep
    the code's defaults. Refuses a setting the reference does not
    implement."""
    from repro.core import RBConfig
    fields = {f.name for f in dataclasses.fields(RBConfig)}
    cfg = RBConfig(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in config["decision"].items() if k in fields})
    for key, allowed in COVERED.items():
        if getattr(cfg, key) not in allowed:
            raise SystemExit(f"RBConfig.{key} = {getattr(cfg, key)!r}; the "
                             f"reference implements {allowed!r}")
    if not 0.0 <= cfg.affinity_weight <= 1.0:
        raise SystemExit(f"RBConfig.affinity_weight = "
                         f"{cfg.affinity_weight!r}; the reference "
                         f"implements [0.0, 1.0]")
    return cfg


def requests_for(mix: traffic.Mix, ds, encoder,
                 rng: np.random.Generator):
    """(arrival times, requests) of a run, drawn from `rng`: the
    one-shot stream (the seed deals the test prompts to its arrivals in
    turn and draws a budget per request) and the chat stream's turns of
    a `sessions` block (none without one) with a budget each, merged by
    arrival time. Without sessions these are `make_requests`'s
    requests for the one-shot stream, from the same draws."""
    from repro.serving.request import Request, RequestColumns
    t = traffic.arrivals(mix, rng)
    order = rng.permutation(len(ds.test_idx))
    dealt = dataclasses.replace(ds, test_idx=ds.test_idx[order])
    budgets = traffic.budgets(mix, len(t), rng)
    prompts, Q, L = dealt.split("test")
    at, _, base, tokens = sessions.chat_turns(mix, prompts, rng)
    budgets = np.concatenate([budgets, traffic.budgets(mix, len(at), rng)])
    one_shot = [(prompts[i % len(prompts)], i % len(prompts))
                for i in range(len(t))]
    turns = [(dataclasses.replace(prompts[j], tokens=toks,
                                  len_in=int(toks.size)), j)
             for j, toks in zip(base, tokens)]
    entries = one_shot + turns
    t_all = np.concatenate([t, at])
    merged = np.argsort(t_all, kind="stable")
    reqs = []
    for rid, e in enumerate(merged):
        prompt, j = entries[e]
        reqs.append(Request(
            rid=rid, prompt=prompt, arrival=float(t_all[e]),
            true_quality=Q[j], true_length=L[j],
            budget=None if np.isnan(budgets[e]) else float(budgets[e])))
    cols = RequestColumns.from_requests(reqs)
    if encoder is not None:
        cols.ensure_embeddings(encoder)
    return t_all[merged], reqs


@contextlib.contextmanager
def counting_compiles():
    """The names of the programs compiled (or loaded from the persistent
    cache) while open: a list that fills as they come."""
    import jax.monitoring as monitoring
    seen: List[str] = []

    def listen(event, duration, **kw):
        if event == BACKEND_COMPILE:
            seen.append(str(kw.get("fun_name")))

    monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def _span(name: str, on: bool):
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


# host telemetry each window's check reads, as the program was handed it
TELEMETRY = ("pending", "batch", "free", "ctx", "alive")


class Probe:
    """Wraps the timed path from outside. While `on`: the benchmark's
    own host clock from the engine's call into the policy to the end of
    the fetch, per decision window; each window's inputs and the
    program's outputs for the check (where the configuration weighs
    prefix affinity, also the instances' prefix sketch rows; the
    window's token rows stay in its columns); and, when `trace`, host
    spans."""

    def __init__(self, rb, runner, trace: bool,
                 fault: Optional[Callable] = None):
        self.on = False
        self.trace = trace
        self.decides: List[tuple] = []     # (R, seconds, host start)
        self.captured: List[dict] = []
        self._pending: Optional[dict] = None
        self._restore: List[tuple] = []
        from repro.core import AssignmentResult
        policy = rb.policy
        assign = policy.assign
        probe = self

        class Timed:
            __slots__ = ("res", "t0", "R", "span", "done")

            def __init__(self, res, t0, R, span):
                self.res, self.t0, self.R, self.span = res, t0, R, span
                self.done = False

            def fetch(self):
                with _span("bench.fetch", probe.trace):
                    out = self.res.fetch()
                if not self.done:
                    self.done = True
                    probe.decides.append(
                        (self.R, time.perf_counter() - self.t0, self.t0))
                    if self.span is not None:
                        self.span.__exit__(None, None, None)
                return out

        def timed_assign(view, sim):
            if not probe.on:
                return assign(view, sim)
            t0 = time.perf_counter()
            span = None
            if probe.trace:
                import jax
                span = jax.profiler.TraceAnnotation("bench.decide")
                span.__enter__()
            with _span("bench.assign", probe.trace):
                res = assign(view, sim)
            return AssignmentResult(res.instances,
                                    Timed(res, t0, len(view), span))

        self._wrap(policy, "assign", timed_assign)

        decide_cols = runner.decide_cols
        kept = TELEMETRY + (("prefix_sig",)
                            if rb.cfg.affinity_weight > 0.0 else ())

        def captured_decide_cols(cols, rows, tel):
            if probe.on:
                probe._pending = {
                    "cols": cols, "rows": np.array(rows, np.int64),
                    "tel": {k: np.array(getattr(tel, k)) for k in kept}}
            return decide_cols(cols, rows, tel)

        self._wrap(runner, "decide_cols", captured_decide_cols)

        step = runner._step

        def captured_step(*args):
            with _span("bench.dispatch", probe.trace):
                out = step(*args)
            if fault is not None:
                out = fault(args, out)
            if probe.on and probe._pending is not None:
                probe._pending["out"] = (out[0], out[1], out[2],
                                         out[7], out[8], out[9])
                probe.captured.append(probe._pending)
                probe._pending = None
            return out

        self._wrap(runner, "_step", captured_step)

        if trace:
            sync = runner._sync_state

            def spanned_sync(tel):
                with _span("bench.sync", True):
                    return sync(tel)

            self._wrap(runner, "_sync_state", spanned_sync)

    def _wrap(self, obj, name: str, fn):
        self._restore.append((obj, name, obj.__dict__.get(name)))
        setattr(obj, name, fn)

    def remove(self):
        """Put back what the wrappers replaced."""
        for obj, name, orig in reversed(self._restore):
            if orig is None:
                del obj.__dict__[name]
            else:
                setattr(obj, name, orig)
        self._restore = []


@dataclasses.dataclass
class Record:
    """What the metric readers read (`bench/metrics/<name>.py`)."""
    setup_s: float
    window_s: float
    decides: List[tuple]           # (R, seconds, host start) per window
    requests: list                 # requests that arrived in the window
    stats: Dict[str, float]        # fused-runner counters, window delta
    compiles_in_window: int
    roster: int                    # instances I
    index_rows: int                # KNN rows N
    dims: Dict[str, int]           # D, M, k, trees, depth, tiers
    device_kind: str
    backlog: tuple = (0, 0)        # unfinished at window open, at stop
    trace: Optional[dict] = None   # bench/xplane.py reduction


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: List[dict], rec: Record) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = load_reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _counters(runner) -> Dict[str, float]:
    return {k: float(v) for k, v in runner.stats.items()
            if isinstance(v, (int, float))}


@dataclasses.dataclass
class Setup:
    """The world of a configuration: dataset, tiers and estimator
    bundle. It is fixed by the configuration's own seeds, as the paper's
    dataset is fixed, so every run compiles the same programs and the
    compile cache serves all runs after the first; `--seed` draws the
    traffic."""
    config: dict
    dataset: object
    tiers: list
    names: List[str]
    bundle: object


def build(cell: Cell) -> Setup:
    """The configuration's world: dataset, tiers, estimator bundle."""
    from repro.core import EstimatorBundle
    from repro.serving.world import build_dataset, paper_world
    config = cell.config
    wc = config["world"]
    world, names = paper_world(seed=int(wc["seed"]))
    ds = build_dataset(world, n=int(wc["prompts"]),
                       train_frac=float(wc["train_frac"]),
                       seed=int(wc["split_seed"]))
    tiers = fleet_tiers(config)
    bundle = EstimatorBundle.train(ds, tiers, names, k=int(config["knn_k"]),
                                   seed=int(wc["tpot_seed"]))
    return Setup(config, ds, tiers, names, bundle)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict, fault: Optional[Callable] = None,
        info=print,
        setup: Optional[Setup] = None, mix: Optional[traffic.Mix] = None,
        check: bool = True, control: Optional[str] = None):
    """Set up, measure and check one run. Returns (result, record):
    the result object of the last output line, and what the metric
    readers read. `setup`/`mix` reuse a world and replace the cell's
    mix (the knee sweep); `control`, a name of `check.CONTROLS`, also
    returns that control's numbers under result["control"]."""
    import jax
    from repro.core import BatchView, RouteBalance
    from repro.serving.cluster import ClusterSim

    marks = {"start": time.perf_counter()}
    if setup is None:
        setup = build(cell)
    marks["world"] = time.perf_counter()
    config, ds = setup.config, setup.dataset
    tiers, names, bundle = setup.tiers, setup.names, setup.bundle
    rb = RouteBalance(decision_config(config), bundle, tiers)

    tseed = traffic_seed(seed)
    mix = mix or cell.mix
    t, reqs = requests_for(mix, ds, bundle.encoder,
                           np.random.default_rng(tseed))
    cols = reqs[0].cols
    marks["requests"] = time.perf_counter()

    sim = ClusterSim(tiers, names)
    rb.attach(sim)
    # every batch size the trace can put in a window, once, before the
    # fill, so that each pow2 bucket's step is compiled or loaded
    r_max = min(max(traffic.peak_window_count(t, WINDOW_MAX_S), 1),
                len(reqs))
    for R in range(1, r_max + 1):
        view = BatchView(reqs[:R], cols, np.arange(R, dtype=np.int64), 0.0)
        rb.policy.assign(view, sim).fetch()
    runner = rb._fused
    marks["warm"] = time.perf_counter()
    schedule = getattr(traffic.process(mix.process), "schedule", None)
    if schedule is not None:
        schedule(sim, mix, np.random.default_rng(tseed + 2))
    probe = Probe(rb, runner, trace, fault)
    compiled = contextlib.ExitStack()
    try:
        n_fill = int(np.searchsorted(t, mix.fill_s, side="right"))
        for r in reqs[:n_fill]:
            sim.push(r.arrival, lambda tt, rr=r: rb.enqueue(rr, tt))
        sim.run(until=mix.fill_s)

        # -- the measured window --------------------------------------------
        in_window = compiled.enter_context(counting_compiles())
        stats0 = _counters(runner)
        backlog0 = sum(1 for r in reqs[:n_fill] if r.finish_time is None)
        w0 = marks["fill"] = time.perf_counter()
        setup_s = w0 - t_start
        trace_dir = None
        if trace:
            trace_dir = ROOT / ".bench_out" / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        tracing = trace
        probe.on = True
        i, t_sim = n_fill, mix.fill_s
        exhausted = False
        while time.perf_counter() - w0 < seconds:
            if i >= len(reqs):
                exhausted = True
                break
            t_sim += SLICE_S
            j = int(np.searchsorted(t, t_sim, side="right"))
            for r in reqs[i:j]:
                sim.push(r.arrival, lambda tt, rr=r: rb.enqueue(rr, tt))
            i = j
            with _span("bench.sim", trace):
                sim.run(until=t_sim)
            if tracing and time.perf_counter() - w0 >= TRACE_S:
                jax.profiler.stop_trace()
                trace_s = time.perf_counter() - w0
                tracing = False
        backlog = sum(1 for r in reqs[:i] if r.finish_time is None)
        rb.expected = i
        with _span("bench.sim", trace):
            sim.run()
        probe.on = False
        window_s = time.perf_counter() - w0
        if tracing:
            jax.profiler.stop_trace()
            trace_s = time.perf_counter() - w0
    finally:
        compiled.close()
        probe.remove()
    compiles = len(in_window)
    stats = {k: v - stats0.get(k, 0.0) for k, v in _counters(runner).items()}

    window_reqs = reqs[n_fill:i]
    served = [r for r in window_reqs
              if r.finish_time is not None and not r.failed and not r.shed]
    knn = bundle.knn
    heads = [bundle.heads[tt.name].model for tt in tiers]
    rec = Record(
        setup_s=setup_s, window_s=window_s, decides=probe.decides,
        requests=window_reqs, stats=stats, compiles_in_window=compiles,
        roster=len(sim.instances), index_rows=int(knn._x.shape[0]),
        dims={"D": int(knn._x.shape[1]), "M": int(knn._quality.shape[1]),
              "k": int(knn.k), "trees": len(heads[0].trees),
              "depth": int(heads[0].depth), "tiers": len(tiers)},
        device_kind=device["kind"], backlog=(backlog0, backlog))
    if trace:
        from . import xplane
        rec.trace = xplane.reduce_dir(trace_dir, trace_s)
    decide_s = sum(d[1] for d in probe.decides)
    info(json.dumps({
        "cell": cell.name, "seed": seed, "window_s": window_s,
        "decide_share": decide_s / window_s if window_s else None,
        "compiles_in_window": compiles,
        "compiled_in_window": sorted(set(in_window)),
        "windows": len(probe.decides),
        "requests": len(window_reqs), "served": len(served),
        "sim_s": t_sim - mix.fill_s,
        "sim_s_per_wall_s": (t_sim - mix.fill_s) / window_s,
        "trace_exhausted": exhausted, "setup_s": setup_s,
        "setup_parts_s": {
            "imports": marks["start"] - t_start,
            **{k: marks[k] - marks[p] for p, k in zip(
                ("start", "world", "requests", "warm"),
                ("world", "requests", "warm", "fill"))}},
        "sizes_warmed": r_max,
        "backlog": [backlog0, backlog]}))

    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, rec)
    memory = None
    stats_fn = getattr(jax.devices()[0], "memory_stats", None)
    if stats_fn is not None and stats_fn():
        memory = int(stats_fn().get("peak_bytes_in_use", 0))

    # -- the check, after the window and the memory reading ----------------
    result = {
        "correct": False,
        "attempted": len(window_reqs),
        "failed": len(window_reqs) - len(served),
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=memory),
    }
    if trace:
        result["device"]["busy_s"] = rec.trace["busy_s"]
        result["device"]["window_s"] = rec.trace["window_s"]
        result["breakdown"] = rec.trace["breakdown"]
    if not check:
        return result, rec
    fleet = checking.fleet_of(bundle, sim.instances, config)
    c0 = time.perf_counter()
    numbers = checking.compare(
        fleet, probe.captured, np.random.default_rng(tseed + 1),
        REF_REQUESTS)
    checks = checking.judge(numbers, checking.load_limits(cell.name))
    info(json.dumps(dict(numbers, check_s=time.perf_counter() - c0)))
    result["correct"] = all(c["ok"] for c in checks.values())
    if control:
        result["control"] = checking.compare(
            fleet, probe.captured, np.random.default_rng(tseed + 1),
            REF_REQUESTS, outputs=checking.CONTROLS[control])
        result["program"] = numbers
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result, rec
