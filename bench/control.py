"""Readings for the limits of the check: the program and the control
on many seeds, in one process, at the cell's own size and load.

    python bench/control.py --workload paper_steady --seeds 1,2,3 \
        --seconds 6

For each seed, one run of the cell (one world for all) with a short
window: the numbers of the program against the reference, and those of
the control against the reference. The control, `--control <name>` of
`bench/check.py`'s CONTROLS, is the reference's own answers put in the
program's place: `knn_high` (the default) with its KNN distance one
precision step down; `affinity_off` with the prefix-affinity term
dropped. One JSON line per seed on standard output. With `--fault
<name>` the program runs with that fault of `bench/faults.py` planted
in its outputs instead, and the line holds the numbers it reads. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--control", default="knn_high")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.cell import build, load_cell, run
    from bench.check import CONTROLS
    from bench.faults import FAULTS
    from bench.run import device_or_exit, place_cache
    if args.control not in CONTROLS:
        ap.error(f"--control: one of {sorted(CONTROLS)}")
    place_cache()
    cell = load_cell(args.workload)
    device = device_or_exit(cell.chips)
    setup = build(cell)
    fault = FAULTS[args.fault] if args.fault else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res, _ = run(cell, seed, args.seconds, False, time.perf_counter(),
                     device, setup=setup,
                     control=args.control if fault is None else None,
                     fault=fault, info=lambda s: None)
        line = {"workload": args.workload, "seed": seed,
                "correct": res["correct"]}
        if fault is None:
            line.update(program=res["program"], control=res["control"],
                        control_name=args.control)
        else:
            line.update(fault=args.fault, checks=res["checks"])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
