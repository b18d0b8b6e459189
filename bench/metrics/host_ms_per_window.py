"""Host time of the fused runner per decision window, ms: staging and
telemetry sync (`host_s`), dispatch (`dispatch_s`) and the copy of the
result once ready (`sync_s`), from `FusedHotPath.stats`, over its calls
in the window. The wait for the device (`device_s`) is left out."""


def read(rec):
    s = rec.stats
    calls = s.get("calls", 0)
    if not calls:
        return None
    host = s.get("host_s", 0) + s.get("dispatch_s", 0) + s.get("sync_s", 0)
    return host / calls * 1e3
