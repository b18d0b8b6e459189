"""Host-to-device transfers the runner starts per decision window: each
host array handed to the jitted step and each array a reseed uploads
(`FusedHotPath.stats` `uploads`), over its calls."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "uploads" not in s:
        return None
    return s["uploads"] / s["calls"]
