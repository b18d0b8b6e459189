"""The percentile the metrics use, kept with the benchmark so that the
program cannot move it: the linear-interpolation percentile of
`repro.serving.metrics._pct`."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def pct(values: Sequence[float], p: float) -> float:
    """p-th percentile, linear between order statistics; nan if empty."""
    x = np.asarray(values, np.float64)
    return float(np.percentile(x, p)) if len(x) else float("nan")

