"""``merge_exposed_ms``: the collective time of the decode step that no
other op hides, on a hand-made trace, and a raise where no collective
ran."""
from types import SimpleNamespace as NS

import pytest

import spec
from devtrace import MissingEvent, Trace

MOD, OPS = "XLA Modules", "XLA Ops"


def trace(with_collectives=True):
    # two decode steps of 100 ns, each a layer loop (while) holding
    # compute and, in the first, an all-reduce overlapped by a copy for
    # 5 ns and a pmax-like all-reduce-start/done pair with nothing beside
    dev = [(MOD, "jit_fused(1)", 0, 100), (OPS, "%while.1 = (f32[", 0, 100),
           (OPS, "%fusion.1 = bf16[8]", 10, 20),
           (OPS, "%copy.3 = bf16[8]", 45, 10),
           (MOD, "jit_fused(1)", 200, 100), (OPS, "%while.1 = (f32[", 200, 100),
           (OPS, "%fusion.1 = bf16[8]", 210, 60),
           (MOD, "jit_run(2)", 400, 50), (OPS, "%all-reduce.9 = f32", 400, 50)]
    if with_collectives:
        dev += [(OPS, "%all-reduce.5 = f32[8,8]", 40, 10),
                (OPS, "%all-reduce-start.2 = f32[8]", 60, 2),
                (OPS, "%all-reduce-done.2 = f32[8]", 62, 8),
                (OPS, "%all-gather.1 = f32[8]", 280, 10)]
    return Trace({"0": dev, "1": []}, [("bench.window", 0, 500)])


def read(t):
    return spec.reader("metrics", "merge_exposed_ms")(NS(trace=t))


def test_exposed_collective_time_per_decode_step():
    # step 1: 40-50 less the copy's 45-50, then 60-70: 15 ns; step 2: 10
    # ns; the chunk's all-reduce (jit_run) is not the decode step's
    assert read(trace()) == pytest.approx((15 + 10) / 2 * 1e-6)


def test_no_collective_raises_and_untraced_reads_nothing():
    with pytest.raises(MissingEvent):
        read(trace(with_collectives=False))
    assert read(None) is None


# bench/tests/data/trace_longctx.json.gz: device 0 over the first 0.285 s
# of a --trace 1 run's traced stretch of qwen3-4b.longctx on a 2x2 v5e
# (three whole decode steps), reduced by devtrace and clipped (op names
# cut to 48 characters).

def test_recorded_four_chip_merge():
    from test_trace import DATA
    t = Trace.load(DATA / "trace_longctx.json.gz")
    steps = t.modules("fused")
    assert len(steps) == 3 and all(85e6 < d < 86e6 for d in steps)
    coll = [n for line, n, _, _ in t.devices["0"]
            if line == OPS and n.startswith("%all-reduce")]
    assert len(coll) == 3 * 36            # one merge a layer, every step
    # the merges run with nothing beside them: ~7.3 us each, all exposed
    assert 0.26 < read(t) < 0.27
