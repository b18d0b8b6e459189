"""Host time of the runner's telemetry sync per decision window, ms: the
dirty-row read, then the reseed uploads or the delta fill (span
`rb.telemetry`, `FusedHotPath.stats` `telemetry_s`), over its calls."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "telemetry_s" not in s:
        return None
    return s["telemetry_s"] / s["calls"] * 1e3
