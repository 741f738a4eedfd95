"""The profiler trace of the benchmark's own process, reduced to events.

``Capture`` runs the profiler over a stretch of the run and keeps, from the
``.xplane.pb`` it writes, the device planes' events (line, name, start,
duration) and the host spans the harness opened (``bench.*``), all in
nanoseconds on the trace's clock.  ``Trace`` is that reduction: it can be
saved as JSON, and the tests read a small one recorded on the chip.

A reduction that looks for an event by name and finds none raises: a
missing event is a broken name or a broken trace, never a reading of 0.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
import shutil
import tempfile
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, List, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = HOST_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 160          # op names carry their HLO text; keep the head

Event = Tuple[str, str, float, float]       # line, name, start_ns, dur_ns
Span = Tuple[str, float, float]             # name, start_ns, dur_ns


class MissingEvent(LookupError):
    pass


@dataclass
class Trace:
    devices: Dict[str, List[Event]]
    host: List[Span]

    # ------------------------------------------------------------ io ----
    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["host"]])

    @classmethod
    def load(cls, path) -> "Trace":
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "rt") as f:
            return cls.from_json(json.load(f))

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)

    def clipped(self, seconds: float) -> "Trace":
        """The first ``seconds`` of the window, as a trace of its own."""
        lo, _ = self.window()
        hi = lo + seconds * 1e9
        keep = lambda t: lo <= t < hi                     # noqa: E731
        devices = {k: [e for e in v if keep(e[2])]
                   for k, v in self.devices.items()}
        host = [(n, t, min(d, hi - t)) for n, t, d in self.host
                if keep(t) and n != WINDOW_SPAN]
        return Trace(devices, [(WINDOW_SPAN, lo, hi - lo)] + host)

    # ----------------------------------------------------------- reads ----
    def window(self) -> Tuple[float, float]:
        spans = [s for s in self.host if s[0] == WINDOW_SPAN]
        if not spans:
            raise MissingEvent(f"no {WINDOW_SPAN!r} span in the trace")
        _, t, d = spans[0]
        return t, t + d

    def spans(self, name: str) -> List[Tuple[float, float]]:
        out = [(t, t + d) for n, t, d in self.host
               if n == HOST_PREFIX + name]
        if not out:
            raise MissingEvent(f"no host span {HOST_PREFIX + name!r}")
        return out

    def modules(self, jit_name: str, device: str = "0") -> List[float]:
        """Device durations (ns) of the executions of the jitted function
        ``jit_name``, in order, from the device's module line."""
        return [b - a for a, b in self.module_spans(jit_name, device)]

    def module_spans(self, jit_name: str, device: str = "0"
                     ) -> List[Tuple[float, float]]:
        """(start, end) in ns of each execution of ``jit_name`` that
        starts inside the window, in order."""
        pat = re.compile(rf"^jit_{re.escape(jit_name)}(\b|[.(\[]|$)")
        lo, hi = self.window()
        out = [(t, t + d) for line, name, t, d in self.devices[device]
               if line == MODULES_LINE and pat.match(name) and lo <= t <= hi]
        if not out:
            names = sorted({n for ln, n, _, _ in self.devices[device]
                            if ln == MODULES_LINE})
            raise MissingEvent(f"no module jit_{jit_name} on device "
                               f"{device}; modules: {names[:20]}")
        return out

    def busy_intervals(self, device: str) -> List[Tuple[float, float]]:
        """Union of the op intervals on ``device`` inside the window."""
        lo, hi = self.window()
        evs = [(max(t, lo), min(t + d, hi))
               for line, _, t, d in self.devices[device]
               if line == OPS_LINE and t + d > lo and t < hi]
        if not evs:
            raise MissingEvent(f"no {OPS_LINE!r} events on device {device}")
        evs.sort()
        merged = [list(evs[0])]
        for a, b in evs[1:]:
            if a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_ns(self) -> float:
        """Busy time inside the window, averaged over the devices."""
        tot = [sum(b - a for a, b in self.busy_intervals(dev))
               for dev in self.devices]
        return sum(tot) / len(tot)

    def busy_in(self, spans: List[Tuple[float, float]],
                device: str = "0") -> float:
        """Nanoseconds of ``spans`` during which ``device`` ran an op."""
        iv = self.busy_intervals(device)
        total, j = 0.0, 0
        for a, b in sorted(spans):
            while j < len(iv) and iv[j][1] <= a:
                j += 1
            k = j
            while k < len(iv) and iv[k][0] < b:
                total += min(b, iv[k][1]) - max(a, iv[k][0])
                k += 1
        return total

    def top_ops(self, n: int = 10, device: str = "0") -> List[list]:
        """Self time (seconds) by op name inside the window: an op that
        encloses others on the line (a ``while`` over the layers) counts
        only the time none of them covers."""
        lo, hi = self.window()
        evs = sorted(((t, t + d, name) for line, name, t, d
                      in self.devices[device]
                      if line == OPS_LINE and lo <= t <= hi),
                     key=lambda e: (e[0], -e[1]))
        tot: Dict[str, float] = defaultdict(float)
        stack: List[list] = []          # [end, name] of earlier open ops
        for a, b, name in evs:
            while stack and stack[-1][0] <= a:
                stack.pop()
            # the innermost op that holds this one whole (async copies
            # overlap others without holding them)
            parent = next((e for e in reversed(stack) if e[0] >= b), None)
            if parent is not None:
                tot[parent[1]] -= b - a
            tot[name] += b - a
            stack.append([b, name])
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10, device: str = "0") -> List[list]:
        """Device idle time inside the window, summed by the innermost
        host span open at each gap's midpoint."""
        lo, hi = self.window()
        iv = self.busy_intervals(device)
        gaps, prev = [], lo
        for a, b in iv:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if hi > prev:
            gaps.append((prev, hi))
        spans = sorted(((d, nm, t) for nm, t, d in self.host
                        if nm != WINDOW_SPAN), key=lambda x: x[0])
        tot: Dict[str, float] = defaultdict(float)
        for a, b in gaps:
            mid = (a + b) / 2
            name = next((nm for d, nm, t in spans if t <= mid <= t + d),
                        "host:outside any span")
            tot[name.removeprefix(HOST_PREFIX)] += b - a
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


def _reduce(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            devices[m.group(1)] = [
                (line.name, ev.name[:NAME_CHARS], float(ev.start_ns),
                 float(ev.duration_ns))
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            host += [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(HOST_PREFIX)]
    return Trace(devices, host)


def _options():
    """Device and host events without the Python call tracer, which would
    record every Python call of the engine loop and slow it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class Capture:
    """The profiler around a stretch of the run, opened and closed between
    engine steps, inside a ``bench.window`` span.  The raw trace goes to a
    fresh temporary directory, is reduced by ``read`` and then removed."""

    def __init__(self):
        self.dir = None
        self._ann = None
        self.start_s = self.stop_s = 0.0      # what starting and stopping took

    def warm(self) -> None:
        """Start and stop the profiler once, in set-up: its first start
        takes seconds, which inside the window would stall the loop."""
        import jax
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tmp, profiler_options=_options())
            jax.profiler.stop_trace()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def start(self) -> None:
        import jax
        t = time.perf_counter()
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir, profiler_options=_options())
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.start_s = time.perf_counter() - t

    def stop(self) -> None:
        import jax
        t = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t

    def read(self, summary_path=None) -> Trace:
        try:
            files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if len(files) != 1:
                raise MissingEvent(f"expected one .xplane.pb, found {files}")
            if summary_path is not None:
                with open(summary_path, "w") as f:
                    json.dump(raw_summary(files[0]), f, indent=1)
            return _reduce(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(HOST_PREFIX + name):
        yield


def raw_summary(path: str) -> dict:
    """Planes, lines and the commonest event names of a raw trace: what to
    look at by hand before writing a reduction against a new chip."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = defaultdict(int)
            first = None
            for ev in line.events:
                names[ev.name] += 1
                first = first if first is not None else ev.start_ns
            lines[line.name] = {"first_ns": first, "names": sorted(
                names.items(), key=lambda kv: -kv[1])[:15]}
        out[plane.name] = lines
    return out
