"""Host time of the recovery stack per decision window traced, ms: the
program's `rb.recovery` spans (`serving/recovery.py`: the requeue of a
failure's victims, the hedge timer armed at each dispatch, the hedge
check, the watchdog and its releases) in the traced window, from
`bench/stages.py`'s reduction (its `per_span`), over the decision
windows traced, as `program_device_ms_per_window` divides. None for an
untraced run or a trace without the span."""
from bench.stages import reduce_dir

SPAN = "rb.recovery"


def read(rec):
    if not rec.trace or not rec.trace.get("decide_windows"):
        return None
    out = reduce_dir()
    if out is None or SPAN not in out["spans"]:
        return None
    return out["spans"][SPAN]["host_s"] / rec.trace["decide_windows"] * 1e3
