"""95th percentile of time to first token over every request that
arrived in the window and was served, from its due arrival time, on the
fleet's simulated clock (which charges the measured decision time)."""
from bench.stats import pct


def read(rec):
    ttft = [r.first_token_time - r.arrival for r in rec.requests
            if r.first_token_time is not None and not r.failed
            and not r.shed]
    return pct(ttft, 95) if ttft else None
