"""The trace-to-metric reduction, on a hand-made trace and on one
recorded on a v5e by ``bench/run.py --trace 1 --dump-trace``."""
import pytest

from devtrace import MissingEvent, Trace

MOD, OPS = "XLA Modules", "XLA Ops"


def synthetic():
    # window 0..100; two steps; device busy 10-30 (fused) and 50-60 (run)
    dev = [(MOD, "jit_fused(7)", 10, 20), (OPS, "fusion.1", 10, 12),
           (OPS, "dot.2", 20, 10), (MOD, "jit_run", 50, 10),
           (OPS, "fusion.1", 50, 10), (MOD, "jit_fused_other", 70, 5)]
    host = [("bench.window", 0, 100), ("bench.step", 5, 40),
            ("bench.engine.dispatch_fused", 8, 3), ("bench.step", 45, 20)]
    return Trace({"0": dev}, host)


def test_busy_union_and_idle():
    t = synthetic()
    assert t.busy_intervals("0") == [(10, 30), (50, 60)]
    assert t.busy_ns() == 30
    # step 1 (5-45) holds 20 busy, step 2 (45-65) holds 10
    assert t.busy_in(t.spans("step")) == 30


def test_modules_by_jit_name():
    t = synthetic()
    assert t.modules("fused") == [20]          # not jit_fused_other
    assert t.modules("run") == [10]
    with pytest.raises(MissingEvent):
        t.modules("prefill")


def test_gaps_attributed_to_innermost_span():
    gaps = dict(synthetic().idle_gaps())
    # 0-10: mid 5.0 in step 1 (5-45); 30-50: mid 40 in step 1;
    # 60-100: mid 80 outside any span
    assert gaps["step"] == pytest.approx(30e-9)
    assert gaps["host:outside any span"] == pytest.approx(40e-9)


def test_top_ops_sum_by_name():
    ops = dict(synthetic().top_ops())
    assert ops["fusion.1"] == pytest.approx(22e-9)


def test_top_ops_take_self_time_of_enclosing_ops():
    # a while op (10-40) encloses two body ops; the body's time is theirs
    dev = [(OPS, "while.5", 10, 30), (OPS, "dot.1", 12, 10),
           (OPS, "fusion.2", 25, 10), (OPS, "copy.3", 50, 5)]
    t = Trace({"0": dev}, [("bench.window", 0, 100)])
    ops = dict(t.top_ops())
    assert ops == pytest.approx({"while.5": 10e-9, "dot.1": 10e-9,
                                 "fusion.2": 10e-9, "copy.3": 5e-9})


def test_no_window_span_is_an_error():
    t = synthetic()
    t.host = [s for s in t.host if s[0] != "bench.window"]
    with pytest.raises(MissingEvent):
        t.window()


# ------------------------------------------------ recorded on the chip ----
# bench/tests/data/trace_*.json.gz: the first 0.36 s (chat) and 0.16 s
# (batch) of a --trace 1 run's traced stretch on one v5e, reduced by
# devtrace and clipped (op names cut to 48 characters).

from types import SimpleNamespace  # noqa: E402
from pathlib import Path  # noqa: E402

import spec  # noqa: E402
from driver import Step  # noqa: E402

DATA = Path(__file__).parent / "data"


def recorded(name):
    return Trace.load(DATA / f"trace_{name}.json.gz")


def test_recorded_chat_modules_and_busy():
    t = recorded("chat")
    fused, chunk = t.modules("fused"), t.modules("run")
    assert len(fused) == 2 and len(chunk) == 2
    assert all(101e6 < d < 102e6 for d in fused)
    assert all(64e6 < d < 66e6 for d in chunk)
    lo, hi = t.window()
    assert 0 < t.busy_ns() < hi - lo
    names = {n for n, _ in t.idle_gaps()}
    assert names <= {"step", "engine.run_prefill_chunks", "engine.admit",
                     "engine.dispatch_fused", "host:outside any span"}


def test_recorded_readers():
    run = SimpleNamespace(trace=recorded("chat"))
    ms = spec.reader("metrics", "decode_step_ms")(run)
    assert 101.2 < ms < 101.3
    assert 64.8 < spec.reader("metrics", "prefill_chunk_ms")(run) < 65.0
    idle = spec.reader("metrics", "step_idle_pct")(run)
    assert 0 < idle < 15


def test_recorded_decode_roofline():
    import flops
    t = recorded("batch")
    conf = spec.load_json(spec.BENCH / "configs" / "granite-8b-pp2.json")
    peaks = flops.peaks("TPU v5 lite")
    steps = [Step(0, 0, 32, 32 * 900) for _ in range(3)]
    run = SimpleNamespace(trace=t, config=conf, peaks=peaks,
                          traced_steps=lambda: steps)
    pct = spec.reader("metrics", "decode_roofline")(run)
    f, b = flops.decode_step(conf, 32, 32 * 900)
    need = 3 * max(f / 197e12, b / 819e9)
    assert pct == pytest.approx(100 * need / (sum(t.modules("fused")) * 1e-9))
    assert 0 < pct < 100
    # a traced stretch with a decode step the trace lacks is refused
    run.traced_steps = lambda: steps + steps[:1]
    with pytest.raises(MissingEvent):
        spec.reader("metrics", "decode_roofline")(run)
