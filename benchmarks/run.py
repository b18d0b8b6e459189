"""Benchmark harness entry point: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows and writes the same rows
machine-readably to ``BENCH_<module>.json`` (the accumulating perf
trajectory).

  python -m benchmarks.run            # full suite
  python -m benchmarks.run frontier   # one module
Sizes scale with REPRO_BENCH_N (default 600 requests/cell; the paper's
cells are 3,534)."""
from __future__ import annotations

import sys
import time
import traceback

MODULES = ("predictors", "kernels_bench", "hotpath", "sweep", "replay",
           "frontier", "residual", "isolation", "batching", "budget",
           "tier_loss", "ladder", "tails", "roofline", "elastic", "chaos",
           "affinity", "hierarchy")


def main() -> None:
    from benchmarks import common  # noqa: F401  (puts src/ on sys.path)
    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    failures = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"\n### {name}")
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            from benchmarks import common
            common.flush_json(getattr(mod, "FLUSH_AS", name))
            print(f"### {name} done in {time.time()-t0:.0f}s")
        except Exception:
            failures.append(name)
            from benchmarks import common
            common.discard_rows()
            print(f"### {name} FAILED:\n{traceback.format_exc()[-2000:]}")
    if failures:
        print("\nFAILED MODULES:", failures)
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
