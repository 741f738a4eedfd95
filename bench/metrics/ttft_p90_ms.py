"""90th percentile (nearest rank) of the time from a request's due time to
its first token, over every request due in the window.  A request with no
first token by the end of the drain ranks above every other; where the
percentile falls on one, there is no reading."""
from record import nearest_rank


def read(run):
    sub = run.window.in_window()
    if not sub:
        return None
    late = [s.first - s.due if s.first is not None else float("inf")
            for s in sub]
    p = nearest_rank(late, 0.90)
    return 1e3 * p if p != float("inf") else None
