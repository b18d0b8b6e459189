"""The one traffic generator: a mix file's parameters and a seed in,
an open-loop trace out.

A mix (`bench/traffic/<name>.json`) holds:

    process       the arrival process, `bench/processes/<process>.py`
    rate_rps      mean arrival rate on the simulated clock
    budget_share  share of requests that carry a USD budget
    budget_usd    [lo, hi] of the log-uniform budget draw
    fill_s        simulated seconds replayed before the window opens
    horizon_s     simulated seconds of trace generated (the window
                  stops early, and says so, if it reaches the end)

and whatever parameters its process reads (`cv` for `gamma`): that is
the one-shot stream, one test prompt per arrival. A mix may also hold a
`sessions` block, a stream of multi-turn chat conversations whose turns
share growing prefixes (`bench/sessions.py` gives its keys and law);
the run merges the two streams by arrival time. A process
module exposes `arrivals(mix, rng)`, the arrival times up to
`horizon_s`, and may expose `schedule(sim, mix, rng)`, which pushes
fleet events (failures, stragglers, recoveries) onto the simulator
before the fill. A new process is a new file there.

The budget law is that of `repro.serving.workload.sample_budgets`,
copied here so that the yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

import numpy as np

PROCESS_DIR = Path(__file__).resolve().parent / "processes"


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    process: str
    rate_rps: float
    budget_share: float
    budget_usd: tuple
    fill_s: float
    horizon_s: float
    params: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def load(path: Path) -> "Mix":
        raw = json.loads(Path(path).read_text())
        mix = Mix(name=Path(path).stem, process=raw["process"],
                  rate_rps=float(raw["rate_rps"]),
                  budget_share=float(raw["budget_share"]),
                  budget_usd=tuple(float(v) for v in raw["budget_usd"]),
                  fill_s=float(raw["fill_s"]),
                  horizon_s=float(raw["horizon_s"]), params=raw)
        process(mix.process)
        if "sessions" in raw:
            from .sessions import validate
            validate(raw["sessions"], str(path))
        if not 0.0 < mix.fill_s < mix.horizon_s:
            raise ValueError(f"{path}: need 0 < fill_s < horizon_s")
        return mix


def process(name: str):
    """The arrival process `name`, from its own file."""
    path = PROCESS_DIR / f"{name}.py"
    if not path.exists():
        raise ValueError(f"no arrival process {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_process_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arrivals(mix: Mix, rng: np.random.Generator) -> np.ndarray:
    """Arrival times (s) on the simulated clock, up to `horizon_s`."""
    return process(mix.process).arrivals(mix, rng)


def renewal(mix: Mix, draw: Callable[[int], np.ndarray]) -> np.ndarray:
    """Arrival times of a renewal process whose gaps `draw(n)` gives."""
    n = int(np.ceil(mix.rate_rps * mix.horizon_s * 1.2)) + 64
    t = np.cumsum(draw(n))
    return t[t <= mix.horizon_s]


def budgets(mix: Mix, n: int, rng: np.random.Generator) -> np.ndarray:
    """(n,) USD budgets, nan where a request carries none."""
    lo, hi = mix.budget_usd
    has = rng.uniform(size=n) < mix.budget_share
    vals = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
    return np.where(has, vals, np.nan)


def peak_window_count(t: np.ndarray, span_s: float) -> int:
    """Most arrivals in any interval of `span_s` seconds: the largest
    batch one decision window of that length can hold."""
    if not len(t):
        return 0
    ends = np.searchsorted(t, t + span_s, side="left")
    return int((ends - np.arange(len(t))).max())
