"""The open loop around ``ServeEngine``: submit what is due, step, read.

The engine is a synchronous ``submit``/``step`` loop, so the harness drives
it from outside.  Each pass submits every request whose due time has come,
runs one ``step()`` (which blocks on the step's sampled tokens) and reads
the new tokens of each request from ``Request.out_tokens``.  Every time is
on the host clock, in seconds since the window opened: a request is timed
from its due time, so a stall delays the requests behind it too.

Requests a mix marks as due in set-up (its ``sessions``) are submitted by
``prime`` before the window opens, and the engine is stepped until each
has its first token: their prompts are in the cache when the window
opens, and what set-up did stays on the window's clock at negative times.

Per step the driver records what the step did, as the per-layer readers
need it: the decode tokens and the contexts they attended, the prefill
chunks and their positions, and (when ``pages`` is on) the pool's pages in
use.  All of it is read from the engine's public state between steps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from repro.serve import Request

PRIME_STEPS = 100_000      # set-up steps before a session is given up on


@dataclass
class Served:
    """One request as the client saw it."""
    id: int
    due: float
    prompt_len: int
    max_new: int
    req: Optional[Request] = None
    submitted: Optional[float] = None
    admitted: Optional[float] = None     # start of the step that admitted it
    token_times: List[float] = field(default_factory=list)
    prefilled: int = 0                   # prompt positions landed so far
    setup: bool = False                  # submitted in set-up (a session)

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.status != "pending"


@dataclass
class Step:
    start: float
    end: float
    decode_tokens: int = 0
    decode_ctx: int = 0                  # sum of positions attended
    chunks: List[Tuple[int, int]] = field(default_factory=list)  # (start, n)
    pages_in_use: int = 0
    pages_total: int = 0


class Window:
    """One measured window over ``eng`` and the due list ``due``."""

    def __init__(self, eng, due, pages: bool = False, span=None):
        self.eng = eng
        self.served = [Served(d.id, d.due, len(d.prompt), d.max_new,
                              setup=d.setup) for d in due]
        self._prompts = {d.id: d.prompt for d in due}
        self.steps: List[Step] = []
        self.pages = pages
        self.span = span           # context-manager factory for host spans
        self._queued: List[Served] = []
        self._live: List[Served] = []
        self.t0 = 0.0
        self.close = 0.0           # window end, seconds after t0
        self.drained = 0.0         # end of the drain, seconds after t0

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _submit(self, s: Served) -> None:
        s.req = Request(s.id, self._prompts.pop(s.id),
                        max_new_tokens=s.max_new)
        self.eng.submit(s.req)
        s.submitted = self.now()
        self._queued.append(s)

    def _busy(self) -> bool:
        return bool(self.eng.queue) or any(
            r is not None for r in self.eng.slot_req)

    def step(self) -> None:
        eng = self.eng
        start = self.now()
        if self.span is None:
            eng.step()
        else:
            with self.span("step"):
                eng.step()
        end = self.now()
        rec = Step(start, end)
        queued = {id(r) for r in eng.queue}
        still = []
        for s in self._queued:
            if id(s.req) in queued:
                still.append(s)
            else:
                s.admitted = start
                self._live.append(s)
        self._queued = still
        now_pref = {st.req.id: st.done for st in eng.prefilling.values()}
        live = []
        for s in self._live:
            r = s.req
            # an admitted request is mid-prefill or has landed its prompt
            landed = now_pref.get(r.id, s.prompt_len)
            if landed > s.prefilled:
                rec.chunks.append((s.prefilled, landed - s.prefilled))
                s.prefilled = landed
            new = len(r.out_tokens) - len(s.token_times)
            if new:
                # one token per step: it was decoded at the position after
                # the prompt and the tokens before it
                rec.decode_tokens += new
                rec.decode_ctx += s.prompt_len + len(r.out_tokens)
                s.token_times.extend([end] * new)
            if not s.done:
                live.append(s)
        self._live = live
        if self.pages:
            st = eng.kv.memory_stats()
            rec.pages_in_use, rec.pages_total = st.pages_in_use, \
                st.pages_total
        self.steps.append(rec)

    def prime(self) -> None:
        """Set-up: submit every request due in set-up and step the engine
        until each has its first token.  None of these steps is the
        window's; no-op for a mix with no such request."""
        first = [s for s in self.served if s.setup]
        if not first:
            return
        self.t0 = time.perf_counter()
        for s in first:
            self._submit(s)
            s.due = s.submitted
        while any(s.first is None and not s.done for s in first):
            if len(self.steps) >= PRIME_STEPS:
                raise RuntimeError(f"set-up: {PRIME_STEPS} steps and not "
                                   f"every session has its first token")
            self.step()
        self.steps.clear()

    def _reclock(self, t0: float) -> None:
        """Put what was recorded since ``self.t0`` on a clock that starts
        at ``t0``."""
        dt = t0 - self.t0
        for s in self.served:
            if s.submitted is not None:
                s.due -= dt
                s.submitted -= dt
                if s.admitted is not None:
                    s.admitted -= dt
                s.token_times = [t - dt for t in s.token_times]
        self.t0 = t0

    def run(self, seconds: float, drain_s: float, on_step=None) -> None:
        """Open the window, drive it for ``seconds``, then step on for at
        most ``drain_s`` more until every request due in the window has
        its first token or has ended (none are submitted after the
        close)."""
        pending = sorted((s for s in self.served if s.submitted is None),
                         key=lambda s: s.due)
        i = 0
        self._reclock(time.perf_counter())
        self.close = seconds
        while True:
            now = self.now()
            if now >= seconds:
                break
            while i < len(pending) and pending[i].due <= now:
                self._submit(pending[i])
                i += 1
            if self._busy():
                self.step()
                if on_step is not None:
                    on_step(self)
            else:
                nxt = pending[i].due if i < len(pending) else seconds
                time.sleep(max(0.0, min(nxt, seconds) - self.now()))
        while i < len(pending) and pending[i].due < seconds:
            self._submit(pending[i])
            i += 1
        while (drain_s > 0 and self.now() < seconds + drain_s
               and any(s.first is None and not s.done
                       for s in self.in_window())):
            self.step()
        self.drained = self.now()

    def in_window(self) -> List[Served]:
        return [s for s in self.served if s.submitted is not None]
