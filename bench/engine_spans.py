"""The engine's own host spans (``ServeEngine.spans``, see
``docs/telemetry.md``), read over the whole measured window: the host gap
of each decode step, and the moment each request was admitted.

Span times are ``time.perf_counter()`` seconds, the clock the window is
opened on (``Window.t0``).  A step belongs to the window when its
``serve.step`` span lies between the window's open and its close; warm-up
and drain steps do not.  An engine without a span log gives no reading:
each function returns None for it and raises nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

STEP, DISPATCH, WAIT = "serve.step", "serve.decode_dispatch", \
    "serve.decode_wait"
CHUNK_WAIT, QUEUE = "serve.prefill_wait", "serve.queue"


def span_log(run):
    """The engine's SpanLog, or None."""
    return getattr(run.window.eng, "spans", None)


def host_gaps(run) -> Optional[Tuple[List[float], List[float]]]:
    """(plain, chunk): seconds of host time before each decode step of the
    window whose previous step decoded too, for the steps that ran no
    prefill chunk and for those that ran one or more.

    A step's host gap runs from the end of the previous step's
    ``serve.decode_wait`` (its tokens back on the host) to the end of its
    own ``serve.decode_dispatch`` (the next decode step enqueued), less
    the ``serve.prefill_wait`` time in between, in which the device runs
    a chunk.  It holds the harness loop's time between steps as well
    as the engine's.  In a traced run the one gap that holds the profiler's start
    is left out: that time is the harness's."""
    log = span_log(run)
    if log is None:
        return None
    win = run.window
    lo, hi = win.t0, win.t0 + win.close
    spans = log.spans()
    steps = sorted((s for s in spans if s.name == STEP
                    and lo <= s.start and s.end <= hi),
                   key=lambda s: s.start)
    phase: Dict[int, Dict[str, float]] = {}
    for s in spans:
        if s.name in (DISPATCH, WAIT):
            phase.setdefault(s.parent_id, {})[s.name] = s.end
    chunk_waits = sorted((s.start, s.end) for s in spans
                         if s.name == CHUNK_WAIT)
    a = run.traced[0]
    profiler_start = (win.t0 + win.steps[a].start
                      if 0 < a < len(win.steps) else None)
    plain: List[float] = []
    chunk: List[float] = []
    for prev, cur in zip(steps, steps[1:]):
        p, c = phase.get(prev.id, {}), phase.get(cur.id, {})
        if WAIT not in p or DISPATCH not in c:
            continue
        begin, end = p[WAIT], c[DISPATCH]
        if profiler_start is not None and begin < profiler_start < end:
            continue
        waits = [min(t1, end) - max(t0, begin) for t0, t1 in chunk_waits
                 if t0 < end and t1 > begin]
        (chunk if waits else plain).append(end - begin - sum(waits))
    return plain, chunk


def admitted_at(run) -> Optional[Dict[int, float]]:
    """Request id -> its first admission (the end of its ``serve.queue``
    span), in seconds since the window opened; None without a span
    log."""
    log = span_log(run)
    if log is None:
        return None
    out: Dict[int, float] = {}
    for s in log.spans(QUEUE):
        t = s.end - run.window.t0
        if s.request_id not in out or t < out[s.request_id]:
            out[s.request_id] = t
    return out
