"""Least work of one decision window, from its shapes alone.

The count is of the algorithm, not of any implementation: whatever
runs the decision has at least this to do. Shapes are the real ones:
R requests, I instances, an index of N rows of D floats with M labels
per row, k neighbours, and per tier a boosted regressor of `trees`
trees of `depth` levels.

Operations:
- distances: the R x N x D cross term (2 each) plus 3 per distance;
- top-k: one comparison per distance;
- label mix: k weights and 2 x k x M multiply-adds per request;
- TPOT: I x trees x (depth comparisons + 1 add);
- admission: 6 per (request, instance);
- greedy scan: 20 per (request, instance) for the Eq. 1 score, wait
  and dead reckoning.

Bytes: the index rows and their squared norms read once, the 2 x k x M
labels of each request's neighbours, the R embeddings and budgets in,
the I-wide telemetry and the trees of the tiers in, and the R picks
and estimates and I-wide state out; all float32 or int32.

`least_seconds` is the larger of operations over the chip's peak and
bytes over its memory bandwidth, from `bench/peaks.json` keyed by the
device kind; a kind that is not there is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"
WORD = 4


def decision_work(R: int, I: int, N: int, D: int, M: int, k: int,
                  tiers: int, trees: int, depth: int) -> Tuple[float, float]:
    """(operations, bytes) of one window."""
    ops = (2.0 * R * N * D + 3.0 * R * N      # distances
           + 1.0 * R * N                      # top-k selection
           + R * k * (4.0 + 4.0 * M)          # weights and label mix
           + I * trees * (depth + 1.0)        # TPOT trees
           + 6.0 * R * I                      # admission
           + 20.0 * R * I)                    # greedy scan
    tree_words = tiers * trees * (2 * (2 ** depth - 1) + 2 ** depth)
    words = (N * D + N                        # index rows and norms
             + 2 * R * k * M                  # neighbours' labels
             + R * (D + 2)                    # embeddings, budget, len_in
             + 5 * I + tree_words             # telemetry, trees
             + 3 * R + 3 * I)                 # picks, estimates, state
    return ops, float(words * WORD)


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(ops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
