"""90th percentile (nearest rank) of the time from a request's due time to
the start of the step that admitted it into a slot, over every request due
in the window.  One never admitted by the end of the drain ranks above
every other; where the percentile falls on one, there is no reading."""
from record import nearest_rank


def read(run):
    sub = run.window.in_window()
    if not sub:
        return None
    wait = [s.admitted - s.due if s.admitted is not None else float("inf")
            for s in sub]
    p = nearest_rank(wait, 0.90)
    return 1e3 * p if p != float("inf") else None
