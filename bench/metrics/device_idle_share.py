"""1 - (union of device op intervals) / (traced window)."""


def read(rec):
    t = rec.trace
    if not t or not t.get("busy_s"):
        return None
    return t["device_idle_share"]
