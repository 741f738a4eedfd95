"""The serve engine's host spans (``ServeEngine.spans``, a
``telemetry.SpanLog``): one set of phase spans per engine iteration, each
inside the ``serve.step`` that ran it; a ``serve.prefill_wait`` for every
prefill dispatch; one ``serve.queue`` per admission, from submit to
admission; a ring that stays bounded; and token streams the spans leave
alone."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.core.telemetry import Span, SpanLog
from repro.models import LM
from repro.serve import Request, ServeEngine

PHASES = ("serve.decode_dispatch", "serve.decode_wait", "serve.emit")


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(CONFIGS["llama3.2-3b"].reduced(),
                              dtype="float32", num_layers=1)
    lm = LM(cfg)
    return cfg, lm, lm.init(jax.random.key(0))


def serve(tiny, spans=None, **kw):
    """Drain four requests (prompts of 3-13 tokens, so of one to four
    chunks of 4) through two slots; the engine and its requests."""
    cfg, lm, params = tiny
    eng = ServeEngine(lm, params, max_batch=2, max_seq=32,
                      cache_backend="paged", page_size=4, spans=spans, **kw)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=3)
            for i, n in enumerate((3, 13, 6, 9))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, reqs


def inside(child: Span, parent: Span) -> bool:
    return (child.parent_id == parent.id
            and parent.start <= child.start <= child.end <= parent.end)


def test_each_decode_iteration_has_one_of_each_phase_in_its_step(tiny):
    eng, _ = serve(tiny, prefill_chunk=4)
    log = eng.spans.spans()
    steps = {s.id: s for s in log if s.name == "serve.step"}
    assert len(steps) == eng._iter
    kids = {}
    for s in log:
        if s.name in PHASES:
            kids.setdefault(s.parent_id, []).append(s)
    decoded = 0
    for sid, step in steps.items():
        mine = kids.get(sid, [])
        if not mine:
            continue
        decoded += 1
        assert sorted(s.name for s in mine) == sorted(PHASES)
        assert all(inside(s, step) for s in mine)
        by = {s.name: s for s in mine}
        assert (by["serve.decode_dispatch"].end <= by["serve.decode_wait"].start
                and by["serve.decode_wait"].end <= by["serve.emit"].start)
    # every phase span has a step as its parent
    assert sum(len(v) for v in kids.values()) == 3 * decoded
    assert set(kids) <= set(steps)
    assert decoded == eng.reg.counter("serve_decode_dispatches_total").get()
    assert decoded > 0


def test_every_chunk_round_waits_inside_its_prefill(tiny):
    eng, _ = serve(tiny, prefill_chunk=4)
    log = eng.spans.spans()
    by_id = {s.id: s for s in log}
    waits = [s for s in log if s.name == "serve.prefill_wait"]
    assert len(waits) == \
        eng.reg.counter("serve_prefill_dispatches_total").get() > 0
    for w in waits:
        pre = by_id[w.parent_id]
        assert pre.name == "serve.prefill" and inside(w, pre)
        assert by_id[pre.parent_id].name == "serve.step"


def test_whole_prompt_prefill_waits_inside_admission(tiny):
    eng, _ = serve(tiny)
    log = eng.spans.spans()
    by_id = {s.id: s for s in log}
    waits = [s for s in log if s.name == "serve.prefill_wait"]
    assert len(waits) == \
        eng.reg.counter("serve_prefill_dispatches_total").get() > 0
    for w in waits:
        assert by_id[w.parent_id].name == "serve.admit"
        assert inside(w, by_id[w.parent_id])
    assert not eng.spans.spans("serve.prefill")


@pytest.mark.parametrize("chunk", [0, 4])
def test_each_admission_records_its_queue_wait(tiny, chunk):
    eng, reqs = serve(tiny, prefill_chunk=chunk)
    by_id = {s.id: s for s in eng.spans.spans()}
    queue = eng.spans.spans("serve.queue")
    assert sorted(s.request_id for s in queue) == [r.id for r in reqs]
    for r in reqs:
        (q,) = [s for s in queue if s.request_id == r.id]
        assert q.start == r.submitted_at <= q.end <= r.first_token_at
        admit = by_id[q.parent_id]
        assert admit.name == "serve.admit"
        assert admit.start <= q.end <= admit.end


def test_ring_stays_bounded_and_streams_are_unchanged(tiny, monkeypatch):
    eng, _ = serve(tiny, prefill_chunk=4)
    total = len(eng.spans)
    monkeypatch.setattr(SpanLog, "CAPACITY", 16)
    shared = SpanLog()
    small, _ = serve(tiny, spans=shared, prefill_chunk=4)
    assert small.spans is shared and total > 16 == len(shared)
    # the ring keeps the newest: the last step's spans end it
    assert shared.spans()[-1].name == "serve.step"
    whole, _ = serve(tiny)

    def streams(e):
        return sorted((r.id, tuple(r.out_tokens)) for r in e.finished)
    assert streams(eng) == streams(small) == streams(whole)
    assert all(len(r.out_tokens) == 3 for r in eng.finished)


def test_span_log_nests_and_records():
    log = SpanLog()
    with log.span("a"):
        with log.span("b", request_id=7):
            pass
        log.record("q", 1.0, 2.0, request_id=3)
    b, q, a = log.spans()
    assert (a.name, a.parent_id) == ("a", None)
    assert (b.parent_id, b.request_id) == (a.id, 7)
    assert (q.start, q.end, q.parent_id, q.request_id) == (1.0, 2.0, a.id, 3)
    assert a.start <= b.start <= b.end <= a.end
    assert log.spans("b") == [b]
    with pytest.raises(ValueError):
        with log.span("c"):
            raise ValueError
    # a span left by an exception is kept, and closes its parent's stack
    assert log.spans()[-1].name == "c" and log.spans()[-1].parent_id is None
