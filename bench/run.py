"""RouteBalance chip benchmark: one cell, one run, one result line.

    python bench/run.py --workload paper_steady --seed 7 --seconds 20 \
        --trace 0

Runs from the root of a checkout on a machine with a TPU. Refuses to
run, and prints no result, anywhere else. The cell (configuration and
traffic) comes from BENCHMARK.json; see bench/cell.py for set-up, the
window and the check. The last line of standard output is the result
object; with `--trace 1` its metrics are the cell's per-layer metrics.
The numbers the check compared close standard error, each beside its
limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def place_cache() -> None:
    """JAX's persistent compilation cache where the program's entry
    points keep it (`repro.launch.cache`), keeping every program however
    fast it compiled, so that only a cell's first run compiles."""
    import jax
    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_or_exit(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {d.platform!r} "
                 f"({d.device_kind}) - not running")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chip(s), JAX sees "
                 f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.cell import load_cell, run
    cell = load_cell(args.workload)
    place_cache()
    device = device_or_exit(cell.chips)

    def info(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    result, _ = run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                 device, info=info)
    for name, c in result["checks"].items():
        info(f"check {name} {c['value']!r} limit {c['limit']!r}")
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
