"""Share of the roster's columns the decision masked out, dead or
quarantined: the runner's counter `dead_cols` (`FusedHotPath.stats`,
summed over its telemetry syncs) over calls x instances, in the
window."""


def read(rec):
    s = rec.stats
    if not s.get("calls") or "dead_cols" not in s:
        return None
    return s["dead_cols"] / (s["calls"] * rec.roster)
