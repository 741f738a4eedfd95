"""90th percentile (nearest rank) of the time from a request's due time to
its admission into a slot, as the engine stamped it (the end of the
request's ``serve.queue`` span, ``engine_spans.admitted_at``), over every
request due in the window.  One never admitted ranks above every other;
where the percentile falls on one, there is no reading."""
from record import nearest_rank

import engine_spans


def read(run):
    admitted = engine_spans.admitted_at(run)
    sub = run.window.in_window()
    if admitted is None or not sub:
        return None
    wait = [admitted[s.id] - s.due if s.id in admitted else float("inf")
            for s in sub]
    p = nearest_rank(wait, 0.90)
    return 1e3 * p if p != float("inf") else None
