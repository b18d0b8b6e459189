"""95th percentile over all decision windows of the window of the host
clock from the engine's call into the policy to the end of the fetched
result, in ms."""
from bench.stats import pct


def read(rec):
    if not rec.decides:
        return None
    return pct([d[1] for d in rec.decides], 95) * 1e3
