"""Mean host gap of the window's decode steps that ran a prefill chunk
first, the chunk's wait taken out (``engine_spans.host_gaps``).  Beside
``decode_step_ms`` and ``prefill_chunk_ms`` it should account for
``itl_p95_ms``."""
from statistics import fmean

import engine_spans


def read(run):
    gaps = engine_spans.host_gaps(run)
    if not gaps or not gaps[1]:
        return None
    return 1e3 * fmean(gaps[1])
