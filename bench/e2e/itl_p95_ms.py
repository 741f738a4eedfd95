"""95th percentile (nearest rank) of the same gaps as ``itl_p50_ms``."""
from record import nearest_rank, token_gaps


def read(run):
    g = token_gaps(run)
    return 1e3 * nearest_rank(g, 0.95) if g else None
