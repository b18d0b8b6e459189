"""The same idle share, within the host's bench.decide spans only."""


def read(rec):
    t = rec.trace
    if not t or not t.get("busy_s") or t.get("decide_idle_share") is None:
        return None
    return t["decide_idle_share"]
