"""Device time of the decision program's ops under the `knn` scope (the
KNN top-k lookup and its gathers), per decision window traced, ms
(`bench/stages.py`)."""
from bench.stages import device_ms_per_window


def read(rec):
    return device_ms_per_window(rec, "knn")
