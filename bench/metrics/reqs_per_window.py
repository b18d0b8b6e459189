"""Mean requests per decision window."""


def read(rec):
    if not rec.decides:
        return None
    return sum(d[0] for d in rec.decides) / len(rec.decides)
