"""Mean wait of a window's requests for their decision window to fire
(`Request.sched_batch_wait`, simulated clock), ms."""


def read(rec):
    w = [r.sched_batch_wait for r in rec.requests
         if r.dispatch_time is not None]
    return sum(w) / len(w) * 1e3 if w else None
