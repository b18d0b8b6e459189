"""Share of telemetry syncs in the window that re-uploaded the whole
roster (`full_reseed`) rather than the dirty rows (`delta_sync`)."""


def read(rec):
    full = rec.stats.get("full_reseed", 0)
    delta = rec.stats.get("delta_sync", 0)
    return full / (full + delta) if full + delta else None
