"""Host time of the runner's staging per decision window, ms: the
gathers into the staging buffers (span `rb.stage`, `FusedHotPath.stats`
`stage_s`), over its calls in the window."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "stage_s" not in s:
        return None
    return s["stage_s"] / s["calls"] * 1e3
