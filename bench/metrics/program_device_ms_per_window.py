"""Device time of the decision program (trace: "XLA Modules" events of
the runner's jitted step) over the decision windows traced, ms."""


def read(rec):
    t = rec.trace
    if not t or not t.get("program_s") or not t.get("decide_windows"):
        return None
    return t["program_s"] / t["decide_windows"] * 1e3
