"""Device time of the decision program's ops under the `scan` scope (the
LPT order, the greedy scan and the pick's length), per decision window
traced, ms (`bench/stages.py`)."""
from bench.stages import device_ms_per_window


def read(rec):
    return device_ms_per_window(rec, "scan")
