"""A whole run of the harness at CPU size, the chip check skipped: a sound
engine is judged correct, and an engine broken underneath the timed path
is judged not correct, once for each fault a serving cell can have."""
import jax
import jax.numpy as jnp
import pytest

import run
import tiny


def serve(monkeypatch, fault=None, seed=2**31 + 11):
    real = run.build

    def build(cell, s):
        lm, params, eng = real(cell, s)
        if fault is not None:
            fault(eng)
        return lm, params, eng

    monkeypatch.setattr(run, "build", build)
    return run.run_cell(tiny.cell(), seed, 2.0, False, peaks=tiny.PEAKS)


def altered_token(eng):
    """Every decode step after warm-up hands back each slot's sampled token
    plus one: a token altered where it is produced."""
    inner, n = eng._dispatch_fused, [0]

    def bad(*a):
        sampled, layers = inner(*a)
        n[0] += 1
        if n[0] > 8:
            sampled = jnp.where(sampled > 0, (sampled + 1)
                                % tiny.CONFIG["vocab_size"], sampled)
        return sampled, layers
    eng._dispatch_fused = bad


def state_unchanged(eng):
    """The decode step returns the cache it was given: no new position's
    K/V is ever written."""
    fused = eng._make_fused()
    eng._fused = jax.jit(lambda *a: (fused(*a)[0], a[2]),
                         static_argnums=(12,))


def test_sound_run_is_correct(monkeypatch):
    out = serve(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 5
    assert out["checks"]["logit_gap"]["value"] <= 1e-3
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p95_ms"}
    assert list(out)[-1] == "checks"


def test_sound_run_with_biases_and_tied_head_is_correct():
    """Granite Code's settings (a bias on every projection, tied head)
    through the chunk and decode programs."""
    from test_reference import GRANITE
    out = run.run_cell(tiny.cell(config=GRANITE), 2**31 + 17, 2.0, False,
                       peaks=tiny.PEAKS)
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] <= 1e-3


def test_control_is_judged_not_correct():
    """The float8 control, put in the program's place, goes through the
    same comparison and limits as the program and comes out not
    correct; the program's run stays correct."""
    out = run.run_cell(tiny.cell(), 2**31 + 13, 2.0, False,
                       peaks=tiny.PEAKS, control=True)
    assert out["correct"], out["checks"]
    ctrl = out["control"]
    assert not ctrl["correct"]
    gap = ctrl["checks"]["logit_gap"]
    assert gap["limit"] == out["checks"]["logit_gap"]["limit"]
    assert gap["value"] > gap["limit"]


def test_control_command_fails_unless_every_control_fails(monkeypatch):
    import control

    def fake(correct, ctrl_correct):
        def run_cell(cell, seed, *a, **kw):
            c = run.judge({"logit_gap": 1.0, "tokens_checked": 1},
                          0.1 if correct else 2.0, 5, 0)
            k = run.judge({"logit_gap": 1.0, "tokens_checked": 1},
                          0.5 if ctrl_correct else 3.0, 5, 0)
            return {"correct": run.holds(c), "checks": c,
                    "control": {"correct": run.holds(k), "checks": k}}
        return run_cell

    from repro.launch import compile_cache
    monkeypatch.setattr(run, "require_chips", lambda n: None)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    argv = ["--workload", "qwen3-4b.chat", "--seeds", "1,2",
            "--seconds", "1"]
    monkeypatch.setattr(run, "run_cell", fake(True, False))
    control.main(argv)
    for bad in (fake(True, True), fake(False, False)):
        monkeypatch.setattr(run, "run_cell", bad)
        with pytest.raises(SystemExit):
            control.main(argv)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_broken_engine_is_not_correct(monkeypatch, fault):
    out = serve(monkeypatch, fault)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] \
        > out["checks"]["logit_gap"]["limit"]


def test_outcomes_count_missing_and_bad_endings():
    from types import SimpleNamespace as NS
    from driver import Served

    def served(status, first, admitted):
        s = Served(0, 0.0, 8, 4, req=NS(status=status), admitted=admitted)
        s.token_times = [first] if first is not None else []
        return s

    chat = {"arrivals": {"kind": "poisson"}}
    batch = {"arrivals": {"kind": "at_zero"}}
    ok, late = served("completed", 1.0, 0.5), served("pending", 9.0, 8.0)
    lost, dead = served("pending", None, None), served("dead_letter", 2, 1)
    owed, failed = run.outcomes([ok, late, lost, dead], chat, 10.0)
    assert len(owed) == 4
    assert [id(x) for x in failed] == [id(lost), id(dead)]
    cut = served("pending", None, 11.0)        # admitted after the close
    owed, failed = run.outcomes([ok, late, cut, dead], batch, 10.0)
    assert [id(x) for x in owed] == [id(ok), id(late), id(dead)]
    assert [id(x) for x in failed] == [id(dead)]


def test_first_token_and_admission_tails_rank_the_missing_last():
    from types import SimpleNamespace as NS
    from driver import Served
    import spec

    def window(n_missing):
        sub = []
        for i in range(20):
            s = Served(i, float(i), 8, 4, admitted=i + 0.01 * (i + 1))
            s.token_times = [i + 0.1 * (i + 1)]
            sub.append(s)
        for s in sub[:n_missing]:
            s.admitted, s.token_times = None, []
        return NS(window=NS(in_window=lambda: sub))

    ttft = spec.reader("metrics", "ttft_p90_ms")
    wait = spec.reader("metrics", "queue_wait_p90_ms")
    # nearest rank 18 of 20: the 18th smallest of 0.1 .. 2.0 s
    assert abs(ttft(window(0)) - 1800.0) < 1e-6
    assert abs(wait(window(0)) - 180.0) < 1e-6
    # two missing rank above the rest: the 18th is still a reading
    assert abs(ttft(window(2)) - 2000.0) < 1e-6
    assert ttft(window(3)) is None and wait(window(3)) is None
