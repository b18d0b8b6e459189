"""Shared set-up for the benchmark's CPU tests: the paper cell's
configuration and world at their real size (the 14,886-row index: the
control's readings depend on how dense the index is), a mix with a
short fill, the same fleet with the prefix-affinity term on under a
small session mix, and a way to drive a whole run of the harness
without the chip."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold
W_AFF = 0.35
# one-shot Poisson 4 req/s and 5-turn chat sessions at 10 req/s
SESSION_MIX = {
    "process": "poisson", "rate_rps": 4.0, "budget_share": 0.25,
    "budget_usd": [2e-5, 4e-4], "fill_s": 4.0, "horizon_s": 20.0,
    "sessions": {"process": "gamma", "cv": 2.0, "rate_rps": 10.0,
                 "turns": 5, "base_len": 48, "extend": [12, 28],
                 "think_s": 2.0}}


@pytest.fixture(scope="session")
def small():
    """(cell, setup, mix): paper_steady, its fill cut to 4 s."""
    from bench.cell import build, load_cell
    cell = load_cell("paper_steady")
    cell.mix = dataclasses.replace(cell.mix, fill_s=4.0, horizon_s=20.0)
    return cell, build(cell), cell.mix


@pytest.fixture(scope="session")
def sessions(small, tmp_path_factory):
    """(cell, setup, mix): paper_steady's fleet with the prefix-affinity
    weight W_AFF, under SESSION_MIX loaded from a file."""
    from bench.arrivals import Mix
    cell, setup, _ = small
    config = dict(setup.config, decision=dict(setup.config["decision"],
                                              affinity_weight=W_AFF))
    path = tmp_path_factory.mktemp("traffic") / "session_small.json"
    path.write_text(json.dumps(SESSION_MIX))
    mix = Mix.load(path)
    return (dataclasses.replace(cell, config=config, mix=mix),
            dataclasses.replace(setup, config=config), mix)


@pytest.fixture(scope="session")
def drive(small):
    """drive(fault=None, control=None, seconds=1.0, mix=None, on=None,
    info=None) -> (result, rec): one whole run of the harness on the
    CPU, the chip check skipped; `on` is another (cell, setup, mix) than
    `small`, `info` takes the lines the run prints."""
    from bench.cell import run

    def go(fault=None, control=None, seconds=1.0, trace=False, mix=None,
           on=None, info=None):
        cell, setup, cell_mix = on or small
        return run(cell, SEED, seconds, trace, time.perf_counter(),
                   dict(FAKE_DEVICE), fault=fault, setup=setup,
                   mix=mix or cell_mix, control=control,
                   info=info or (lambda s: None))
    return go
