"""Mean share of the page pool in use (reserved by live requests) after
each step of the window, from ``PagedCache.memory_stats``."""


def read(run):
    st = [s for s in run.steps_in_window() if s.pages_total]
    if not st:
        return None
    return 100.0 * sum(s.pages_in_use / s.pages_total for s in st) / len(st)
