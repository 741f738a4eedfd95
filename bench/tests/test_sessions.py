"""The ``sessions`` arrivals kind: sessions drawn the same for a seed,
submitted and brought to their first token in set-up, the window opening
only after that; and the sample and outcomes of the other kinds as they
were."""
from types import SimpleNamespace as NS

import numpy as np

import driver
import run
import spec
import tiny
import traffic
from driver import Served

LONGCTX = spec.load_json(spec.BENCH / "traffic" / "longctx.json")
SESSIONS = {"arrivals": {"kind": "sessions", "sessions": 4},
            "prompt": {"median": 40, "sigma": 0.3, "min": 24, "max": 60},
            "output": {"fill": 96, "min": 36, "max": 72, "checked": 24},
            "block": 4, "drain_s": 0}


def test_sessions_are_deterministic_per_seed():
    a = traffic.generate(LONGCTX, 2**31 + 5, 51, 1000)
    b = traffic.generate(LONGCTX, 2**31 + 5, 51, 1000)
    assert [(r.due, r.max_new, r.setup) for r in a] == \
        [(r.due, r.max_new, r.setup) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.generate(LONGCTX, 7, 51, 1000)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert len(a) == 8 and all(r.setup and r.due == 0.0 for r in a)
    fill = LONGCTX["output"]["fill"]
    assert all(len(r.prompt) + r.max_new == fill for r in a)
    assert traffic.checked(LONGCTX) == 256
    # the other kinds submit nothing in set-up, and check whole answers
    chat = spec.load_json(spec.BENCH / "traffic" / "chat.json")
    assert not any(r.setup for r in traffic.generate(chat, 1, 10, 100))
    assert traffic.checked(chat) == chat["output"]["max"]


def test_window_opens_after_every_first_token():
    cell = tiny.cell(traffic=SESSIONS)
    _, _, eng = run.build(cell, 2**31 + 3)
    run.warm(eng, cell.config["vocab_size"])
    due = traffic.generate(SESSIONS, 2**31 + 3, 1.0,
                           cell.config["vocab_size"])
    win = driver.Window(eng, due)
    win.prime()
    assert all(s.first is not None and s.submitted is not None
               for s in win.served)
    assert not win.steps             # set-up's steps are not the window's
    win.run(1.0, 0.0)
    assert win.steps and all(s.start >= 0 for s in win.steps)
    for s in win.served:
        # set-up happened before the window opened, on the window's clock
        assert s.submitted <= s.due + 1e-9 and s.first < 0 < s.token_times[-1]
        assert s.admitted < 0
    r = NS(window=win, seconds=1.0)
    gaps = run.spec.reader("e2e", "itl_p50_ms")(r)
    assert gaps is not None and gaps > 0
    owed, failed = run.outcomes(win.in_window(), SESSIONS, 1.0)
    assert len(owed) == 4 and not failed
    picked = run.sample(win, 2**31 + 3, traffic.checked(SESSIONS))
    assert picked and all(s.setup for s in picked)


def parent_sample(window, seed):
    """``run.sample`` as it was before sessions: completed requests only,
    the longest, then others drawn from the seed."""
    done = [s for s in window.served
            if s.req is not None and s.req.status == "completed"]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 13])
    longest = max(done, key=lambda s: len(s.req.out_tokens))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    picked, total = [longest], len(longest.req.out_tokens)
    for s in rest:
        if total >= run.SAMPLE_TOKENS or len(picked) >= run.SAMPLE_REQUESTS:
            break
        picked.append(s)
        total += len(s.req.out_tokens)
    return picked


def window_of(n, seed):
    rng = np.random.default_rng(seed)
    served = []
    for i in range(n):
        status = rng.choice(["completed", "pending", "dead_letter"],
                            p=[0.7, 0.2, 0.1])
        out = list(rng.integers(0, 50, int(rng.integers(1, 120))))
        s = Served(i, float(i), 8, 120, req=NS(status=status,
                                               out_tokens=out, prompt=[1]))
        s.token_times = [float(i)] * len(out)
        served.append(s)
    return NS(served=served)


def test_sample_and_outcomes_of_other_kinds_unchanged():
    for seed in range(20):
        win = window_of(40, seed)
        for mix in ("chat", "batch"):
            m = spec.load_json(spec.BENCH / "traffic" / f"{mix}.json")
            got = run.sample(win, seed, traffic.checked(m))
            assert [s.id for s in got] == \
                [s.id for s in parent_sample(win, seed)]
    # a pending request of those kinds is never checked; a session is
    win = window_of(10, 3)
    for s in win.served:
        s.req.status = "pending"
    assert run.sample(win, 3, 512) == []
    for s in win.served:
        s.setup = True
    assert run.sample(win, 3, 512)
    # the last ``rows`` served tokens are checked, after the rest
    req = NS(prompt=np.arange(5, dtype=np.int32), out_tokens=list(range(9)))
    prompt, out = run.checked_part(req, 4)
    assert list(prompt) == [0, 1, 2, 3, 4, 0, 1, 2, 3, 4] and out == [5, 6,
                                                                     7, 8]
    prompt, out = run.checked_part(req, 16)
    assert list(prompt) == list(range(5)) and out == list(range(9))
    # a session decoding at the close is owed and not failed
    live = Served(0, -3.0, 8, 4, req=NS(status="pending"), setup=True)
    live.token_times = [-1.0, 0.5]
    owed, failed = run.outcomes([live], {"arrivals": {"kind": "sessions"}},
                                10.0)
    assert owed == [live] and not failed
