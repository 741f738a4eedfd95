"""Share of the time inside ``ServeEngine.step`` (the harness's
``bench.step`` spans in the traced stretch) during which device 0 ran no
operation: the host holding the chip back."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans("step")
    lo, hi = run.trace.window()
    spans = [(a, b) for a, b in spans if lo <= a and b <= hi]
    total = sum(b - a for a, b in spans)
    return 100.0 * (1.0 - run.trace.busy_in(spans) / total)
