"""What a run hands to the metric readers, and the statistics they share.

Each reader is ``read(run: Run) -> float | None`` in
``bench/e2e/<metric>.py`` or ``bench/metrics/<metric>.py``.  It returns
None where the run holds nothing for it to read (a traced metric in an
untraced run), and never a 0 in place of a reading it could not make.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flops
from driver import Step, Window
from spec import Cell
from devtrace import Trace


@dataclass
class Run:
    cell: Cell
    seconds: float               # the measured window
    window: Window               # requests as served and steps as recorded
    setup_s: float               # process start to window open
    compile_s: float             # backend compile + cache loads in set-up
    peaks: Dict[str, Any]        # bench/peaks.json row of this device,
    #                              held summed over the cell's chips
    trace: Optional[Trace] = None
    traced: Tuple[int, int] = (0, 0)   # window.steps[a:b] ran under trace

    def __post_init__(self):
        # the readers hold the work against every chip of the cell
        self.peaks = flops.summed(self.peaks, self.cell.chips)

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def slots(self) -> int:
        return int(self.cell.engine["max_batch"])

    def steps_in_window(self) -> List[Step]:
        return [s for s in self.window.steps if s.end <= self.seconds]

    def traced_steps(self) -> List[Step]:
        a, b = self.traced
        return self.window.steps[a:b]


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile by nearest rank: a value that was observed."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def token_gaps(run: Run) -> List[float]:
    """Seconds between consecutive tokens of one request, both emitted
    inside the window, over all requests."""
    out: List[float] = []
    for s in run.window.served:
        t = [x for x in s.token_times if 0 <= x <= run.seconds]
        out += [b - a for a, b in zip(t, t[1:])]
    return out
