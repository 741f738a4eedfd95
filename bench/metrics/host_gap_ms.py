"""Mean host gap of the window's decode steps that ran no prefill chunk:
host time from the previous step's tokens reaching the host to this
step's decode dispatch returning, from the engine's own spans over the
whole window (``engine_spans.host_gaps``).  Beside ``decode_step_ms`` it
should account for ``itl_p50_ms``."""
from statistics import fmean

import engine_spans


def read(run):
    gaps = engine_spans.host_gaps(run)
    if not gaps or not gaps[0]:
        return None
    return 1e3 * fmean(gaps[0])
