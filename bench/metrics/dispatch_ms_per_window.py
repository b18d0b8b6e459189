"""Host time of the runner's dispatch per decision window, ms: the
jitted step's call, its argument transfers and the launch (span
`rb.dispatch`, `FusedHotPath.stats` `dispatch_s`), over its calls."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "dispatch_s" not in s:
        return None
    return s["dispatch_s"] / s["calls"] * 1e3
