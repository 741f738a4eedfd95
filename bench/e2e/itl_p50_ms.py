"""Median (nearest rank) of the gaps between consecutive tokens of one
request, both inside the window, over all requests."""
from record import nearest_rank, token_gaps


def read(run):
    g = token_gaps(run)
    return 1e3 * nearest_rank(g, 0.50) if g else None
