"""Cross-chip merge time the decode step leaves exposed, per decode step:
on device 0, inside each ``jit_fused`` execution of the traced stretch,
the time during which a collective op (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, or the start or done of
one) runs and no other op runs beside it.  An op that holds a collective
whole (the layer loop's ``while``) is not beside it, nor is one that a
collective holds.  In the sharded paged pool (``parallel/pagedkv.py``)
the collectives are the softmax merge's pmax and psums, every layer of
every step.

A trace with no collective in those executions raises: the cell's merge
was not seen, which is never a reading of 0."""
import bisect
import re

import numpy as np

from devtrace import OPS_LINE, MissingEvent

COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)\b")


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def exposed(ops):
    """(exposed ns, collectives seen) among one execution's ``ops``, each
    (start, end, name)."""
    coll = np.array([(a, b) for a, b, n in ops if COLLECTIVE.match(n)],
                    float).reshape(-1, 2)
    rest = np.array([(a, b) for a, b, n in ops if not COLLECTIVE.match(n)],
                    float).reshape(-1, 2)
    if not len(coll):
        return 0.0, 0
    (a, b), (c, d) = rest.T[:, :, None], coll.T[:, None, :]
    holds = ((a <= c) & (d <= b)).any(axis=1)
    held = ((c <= a) & (b <= d)).any(axis=1)
    beside = _union(rest[~holds & ~held].tolist())
    total = 0.0
    for lo, hi in _union(coll.tolist()):
        total += hi - lo
        for x, y in beside:
            if x < hi and y > lo:
                total -= min(hi, y) - max(lo, x)
    return total, len(coll)


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.module_spans("fused")
    ops = sorted((t, t + d, name) for line, name, t, d
                 in run.trace.devices["0"] if line == OPS_LINE)
    starts = [o[0] for o in ops]
    total, seen = 0.0, 0
    for lo, hi in spans:
        ns, n = exposed(ops[bisect.bisect_left(starts, lo):
                            bisect.bisect_left(starts, hi)])
        total += ns
        seen += n
    if not seen:
        raise MissingEvent(f"no collective op in {len(spans)} jit_fused "
                           f"executions on device 0")
    return total / len(spans) * 1e-6
