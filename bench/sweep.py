"""Knee sweep of one cell's fleet: the cell's mix at several Poisson
rates, one short window each, one process and one world.

    python bench/sweep.py --workload paper_steady --rates 12,24,36 \
        --seconds 8 --seed 5

One JSON line per rate on standard output. The knee is the highest
rate at which the backlog (requests arrived but unfinished) does not
grow over the window and the TTFT tail has not turned up; the cells'
rates are fixed from it once (PERF.md keeps the sweeps).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.cell import build, load_cell, run
    from bench.run import device_or_exit, place_cache
    from bench.stats import pct
    place_cache()
    cell = load_cell(args.workload)
    device = device_or_exit(cell.chips)
    setup = build(cell)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dataclasses.replace(cell.mix, process="poisson",
                                  rate_rps=rate)
        _, rec = run(cell, args.seed, args.seconds, False,
                     time.perf_counter(), device, setup=setup, mix=mix,
                     check=False, info=lambda s: None)
        served = [r for r in rec.requests if r.first_token_time is not None]
        ttft = [r.first_token_time - r.arrival for r in served]
        half = len(served) // 2
        dec = [d[1] for d in rec.decides]
        print(json.dumps({
            "workload": args.workload, "rate_rps": rate,
            "attempted": len(rec.requests), "served": len(served),
            "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
            "ttft_p95_first_half_s": pct(ttft[:half], 95),
            "ttft_p95_second_half_s": pct(ttft[half:], 95),
            "backlog_open_stop": list(rec.backlog),
            "decide_ms_p95": pct(dec, 95) * 1e3,
            "decide_ms_per_req": sum(dec) / max(
                sum(d[0] for d in rec.decides), 1) * 1e3,
            "reqs_per_window": sum(d[0] for d in rec.decides)
            / max(len(rec.decides), 1),
            "window_s": rec.window_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
