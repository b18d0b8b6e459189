"""All decision time of the window over all requests decided in it, ms."""


def read(rec):
    n = sum(d[0] for d in rec.decides)
    if not n:
        return None
    return sum(d[1] for d in rec.decides) / n * 1e3
