"""The decision program's share of its roofline, %: the least time of
the traced windows' work (`bench/work.py`, real R, I, N and D; the
larger of operations over peak and bytes over bandwidth, peaks from
`bench/peaks.json` by device kind) over the program's device time in
the trace."""
from bench.work import decision_work, least_seconds


def read(rec):
    t = rec.trace
    if not t or not t.get("program_s") or not t.get("decide_windows"):
        return None
    d = rec.dims
    least = sum(least_seconds(*decision_work(
        R, rec.roster, rec.index_rows, d["D"], d["M"], d["k"], d["tiers"],
        d["trees"], d["depth"]), rec.device_kind)
        for R, _, _ in rec.decides[:t["decide_windows"]])
    return least / t["program_s"] * 100.0
