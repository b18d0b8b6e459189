"""Shared set-up for the benchmark's CPU tests: the paper cell's
configuration and world at their real size (the 14,886-row index: the
control's readings depend on how dense the index is), a mix with a
short fill, and a way to drive a whole run of the harness without the
chip."""
import dataclasses
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


@pytest.fixture(scope="session")
def small():
    """(cell, setup, mix): paper_steady, its fill cut to 4 s."""
    from bench.cell import build, load_cell
    cell = load_cell("paper_steady")
    cell.mix = dataclasses.replace(cell.mix, fill_s=4.0, horizon_s=20.0)
    return cell, build(cell), cell.mix


@pytest.fixture(scope="session")
def drive(small):
    """drive(fault=None, control=False, seconds=1.0, mix=None) ->
    (result, rec): one whole run of the harness on the CPU, the chip
    check skipped."""
    from bench.cell import run
    cell, setup, small_mix = small

    def go(fault=None, control=False, seconds=1.0, trace=False, mix=None):
        return run(cell, SEED, seconds, trace, time.perf_counter(),
                   dict(FAKE_DEVICE), fault=fault, setup=setup,
                   mix=mix or small_mix, control=control,
                   info=lambda s: None)
    return go
