"""The readers of the engine's own spans (``engine_spans`` and the metrics
``host_gap_ms``, ``chunk_host_gap_ms``, ``admit_wait_p90_ms``): exact
values on a hand-made span log, no reading and no error for an engine
without one, and sound readings of a real window at CPU size."""
from types import SimpleNamespace as NS

import pytest

import spec
from devtrace import Trace
from driver import Served, Step
from repro.core.telemetry import Span

READERS = ("host_gap_ms", "chunk_host_gap_ms", "admit_wait_p90_ms")
T0 = 100.0                  # the window opens at perf_counter() 100 s


class Log:
    def __init__(self, spans):
        self._spans = spans

    def spans(self, name=None):
        return [s for s in self._spans if name is None or s.name == name]


def step_spans(sid, start, dispatch_end, wait_end, end, chunk=None):
    """One decode step as the engine logs it (ids sid .. sid+4), times in
    seconds after the window's open; ``chunk`` is a (start, end) wait."""
    at = lambda t: T0 + t                          # noqa: E731
    out = [Span(sid, "serve.step", at(start), at(end), None, None),
           Span(sid + 1, "serve.decode_dispatch", at(dispatch_end - 0.001),
                at(dispatch_end), sid, None),
           Span(sid + 2, "serve.decode_wait", at(dispatch_end),
                at(wait_end), sid, None)]
    if chunk is not None:
        out += [Span(sid + 3, "serve.prefill", at(start), at(chunk[1]), sid,
                     None),
                Span(sid + 4, "serve.prefill_wait", at(chunk[0]),
                     at(chunk[1]), sid + 3, None)]
    return out


def hand_run(spans, served=(), traced=(0, 0), steps=()):
    win = NS(eng=NS(spans=Log(spans)), t0=T0, close=10.0, steps=list(steps),
             in_window=lambda: list(served))
    return NS(window=win, traced=traced)


def read(name, run):
    return spec.reader("metrics", name)(run)


def decode_log():
    # a warm-up step before the window opens, then four steps of 100 ms
    # (waits end at 0.1, 0.2, ...); the third runs a 60-ms chunk first
    spans = step_spans(0, -5.0, -4.9, -4.8, -4.79)
    spans += step_spans(10, 0.000, 0.004, 0.100, 0.101)
    spans += step_spans(20, 0.102, 0.105, 0.200, 0.201)
    spans += step_spans(30, 0.202, 0.275, 0.380, 0.381,
                        chunk=(0.203, 0.263))
    spans += step_spans(40, 0.382, 0.386, 0.480, 0.481)
    # a drain step after the close is left out
    spans += step_spans(50, 10.0, 10.004, 10.1, 10.101)
    return spans


def test_host_gaps_exact_on_a_hand_made_log():
    run = hand_run(decode_log())
    # plain: 0.105 - 0.100 and 0.386 - 0.380; chunk: 0.275 - 0.200 - 0.060
    assert read("host_gap_ms", run) == pytest.approx(5.5)
    assert read("chunk_host_gap_ms", run) == pytest.approx(15.0)


def test_the_gap_that_holds_the_profiler_start_is_left_out():
    steps = [Step(0.0, 0.101), Step(0.102, 0.201), Step(0.202, 0.381),
             Step(0.382, 0.481)]
    run = hand_run(decode_log(), traced=(3, 4), steps=steps)
    assert read("host_gap_ms", run) == pytest.approx(5.0)
    assert read("chunk_host_gap_ms", run) == pytest.approx(15.0)


def test_no_gap_without_a_previous_decode():
    # one step in the window: nothing to pair it with
    run = hand_run(step_spans(10, 0.0, 0.004, 0.1, 0.101))
    assert read("host_gap_ms", run) is None
    assert read("chunk_host_gap_ms", run) is None


def test_admit_wait_exact_and_missing_ranks_last():
    def window(n_missing):
        sub, spans = [], []
        for i in range(20):
            sub.append(Served(i, float(i) / 10, 8, 4))
            # admitted 10*(i+1) ms after its due time; a resumed request's
            # later admission does not count
            t = T0 + i / 10 + 0.01 * (i + 1)
            spans.append(Span(i, "serve.queue", T0, t, None, i))
            spans.append(Span(100 + i, "serve.queue", T0, t + 5, None, i))
        spans = [s for s in spans if s.request_id >= n_missing]
        return hand_run(spans, served=sub)

    # nearest rank 18 of 20: 180 ms
    assert read("admit_wait_p90_ms", window(0)) == pytest.approx(180.0)
    assert read("admit_wait_p90_ms", window(2)) == pytest.approx(200.0)
    assert read("admit_wait_p90_ms", window(3)) is None


@pytest.mark.parametrize("name", READERS)
def test_no_reading_for_an_engine_without_spans(name):
    sub = [Served(0, 0.0, 8, 4)]
    run = NS(window=NS(eng=NS(), t0=T0, close=10.0, steps=[],
                       in_window=lambda: sub), traced=(0, 0))
    assert read(name, run) is None


def test_idle_gaps_attribute_to_the_innermost_engine_phase():
    # device busy 10-30 and 50-60 in a window of 0-100; the engine's
    # phases nest in the harness's step span
    ops = "XLA Ops"
    dev = [(ops, "fusion.1", 10, 20), (ops, "fusion.2", 50, 10)]
    host = [("bench.window", 0, 100), ("bench.step", 2, 66),
            ("serve.step", 3, 64), ("serve.decode_dispatch", 4, 8),
            ("serve.decode_wait", 12, 38), ("serve.emit", 62, 4)]
    gaps = dict(Trace({"0": dev}, host).idle_gaps())
    # 0-10: mid 5 in the dispatch; 30-50: mid 40 in the wait; 60-100:
    # mid 80 outside every span
    assert gaps == pytest.approx({"serve.decode_dispatch": 10e-9,
                                  "serve.decode_wait": 20e-9,
                                  "host:outside any span": 40e-9})


def test_real_window_at_cpu_size():
    """The readers over a window served by the tiny cell's engine: every
    decode step after the first has a gap, admission comes no earlier
    than the start of the step that admitted, and no reading is None."""
    import driver
    import record
    import run as bench_run
    import tiny
    import traffic

    cell, seed, seconds = tiny.cell(), 2**31 + 23, 2.0
    vocab = cell.config["vocab_size"]
    _, _, eng = bench_run.build(cell, seed)
    bench_run.warm(eng, vocab)
    win = driver.Window(eng, traffic.generate(cell.traffic, seed, seconds,
                                              vocab))
    win.run(seconds, 60.0)
    r = record.Run(cell=cell, seconds=seconds, window=win, setup_s=0.0,
                   compile_s=0.0, peaks=tiny.PEAKS)
    import engine_spans
    plain, chunk = engine_spans.host_gaps(r)
    in_window = [s for s in win.steps if s.end <= win.close]
    decoding = [s for s in in_window if s.decode_tokens]
    assert 0 < len(plain) + len(chunk) <= len(decoding) - 1
    assert all(g > 0 for g in plain + chunk)
    vals = {n: read(n, r) for n in READERS}
    assert all(v is not None and v > 0 for v in vals.values()), vals
    assert vals["admit_wait_p90_ms"] >= read("queue_wait_p90_ms", r)
