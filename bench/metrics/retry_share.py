"""Share of the window's served requests that were retried after an
instance failure (`Request.attempt` > 0, `serving/recovery.py`)."""


def read(rec):
    served = [r for r in rec.requests
              if r.finish_time is not None and not r.failed and not r.shed]
    if not served:
        return None
    return sum(1 for r in served if r.attempt > 0) / len(served)
