"""Mean quality label, drawn with the world from the seed, of the model
each served request of the window was routed to."""


def read(rec):
    q = [float(r.true_quality[r.model_idx]) for r in rec.requests
         if r.finish_time is not None and not r.failed and not r.shed]
    return sum(q) / len(q) if q else None
