"""Share of the roster found dirty at each telemetry sync that read the
dirty rows (`FusedHotPath.stats` `dirty_rows_seen` over `dirty_checks`
times the instances), counted before the mostly-dirty rule picks a
reseed or a delta."""


def read(rec):
    s = rec.stats
    if not s.get("dirty_checks") or "dirty_rows_seen" not in s:
        return None
    return s["dirty_rows_seen"] / (s["dirty_checks"] * rec.roster)
