"""Host time of the result's fetch per decision window, ms: the wait for
the device (span `rb.wait`, `device_s`) and the slice and copy once
ready (span `rb.copy`, `sync_s`), from `FusedHotPath.stats`, over its
calls."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "device_s" not in s or "sync_s" not in s:
        return None
    return (s["device_s"] + s["sync_s"]) / s["calls"] * 1e3
