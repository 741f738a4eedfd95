"""Fault-tolerant serving: injection at every seam, watchdog/guard
detection, and bitwise recompute-on-resume recovery.

The correctness bar is the one the recovery design is built around: a
recovered stream re-draws its discarded sample *at the same stream step*
(the per-request sampling fold keys on ``len(out_tokens)``), so after any
recoverable fault — non-finite logits out of the fused dispatch, a
poisoned KV page, a stalled prefill chunk, a transient dispatch error, a
whole failed chip — every stream that completes must be **bitwise
identical** to the same workload on a fault-free engine, for greedy and
seeded sampling alike, whole-prompt and chunked prefill alike.  Faults
that cannot be recovered degrade predictably: bounded retries dead-letter
the victim without perturbing its neighbours, and a wedged engine names
its wedged slots instead of returning silently."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.models import LM
from repro.serve import (CacheInvariantError, EngineStuckError, FaultEvent,
                         FaultPlan, PrefixStore, PriorityClass, Request,
                         SamplingParams, ServeEngine, TenancyConfig,
                         TenantSpec, TransientDispatchError)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(CONFIGS["llama3.2-3b"].reduced(),
                              dtype="float32", num_layers=2)
    lm = LM(cfg)
    return cfg, lm, lm.init(jax.random.key(0))


def _requests(cfg, n=6, max_new=6, seed=0, tenant=None):
    """Mixed sampling workload: even ids greedy, odd ids seeded top-p —
    both must survive recovery bitwise."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    6 + (i % 5)).astype(np.int32),
                    max_new_tokens=max_new, tenant=tenant,
                    sampling=SamplingParams(
                        temperature=0.0 if i % 2 == 0 else 0.8, seed=i))
            for i in range(n)]


def _drain(eng, reqs, max_iters=2000):
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained(max_iters=max_iters)
    return {r.id: tuple(r.out_tokens) for r in done}


def _engine(lm, params, *, chunked=False, **kw):
    kw.setdefault("num_pages", 33)
    if chunked:
        kw.setdefault("prefill_chunk", 8)
    return ServeEngine(lm, params, max_batch=4, max_seq=64,
                       cache_backend="paged", page_size=4, **kw)


# --------------------------------------------- bitwise resume parity ----

@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole_prompt", "chunked"])
@pytest.mark.parametrize("kind", ["nan_logits", "poison_page"])
def test_recovered_streams_bitwise_identical(model, kind, chunked):
    """The tentpole assertion: inject a corruption fault mid-decode, let
    detection (the in-dispatch non-finite guard) and recovery (evict +
    re-queue + recompute-on-resume) run, and require every stream —
    including the recovered victim — bitwise equal to a fault-free run,
    across greedy and seeded sampling and both prefill modes."""
    cfg, lm, params = model
    base = _drain(_engine(lm, params, chunked=chunked), _requests(cfg))
    plan = FaultPlan([FaultEvent(2, kind), FaultEvent(6, kind)])
    eng = _engine(lm, params, chunked=chunked, fault_plan=plan,
                  watchdog_iters=16, verify_cache=True)
    out = _drain(eng, _requests(cfg))
    assert out == base
    assert eng.reg.counter("serve_faults_injected_total").get(
        {"kind": kind}) == 2
    assert eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "nonfinite_logits"}) >= 1
    assert eng.reg.histogram("serve_recovery_iters").recent(10)
    assert eng.reg.gauge("serve_streams_quarantined").get() == 0
    eng.kv.verify()


def test_recovery_bitwise_under_tenancy(model):
    """Recovery composes with the multi-tenant scheduler: the re-queued
    victim keeps its tenant, re-admits under quota/priority, and still
    resumes bitwise."""
    cfg, lm, params = model

    def tenancy():
        return TenancyConfig(
            tenants=[TenantSpec("chat", "interactive"),
                     TenantSpec("bulk", "batch", page_quota=20)],
            classes={"interactive": PriorityClass("interactive", 100,
                                                  preemptible=False),
                     "batch": PriorityClass("batch", 0, preemptible=True)})

    def reqs():
        out = _requests(cfg)
        for r in out:
            r.tenant = "chat" if r.id % 2 else "bulk"
        return out

    base = _drain(_engine(lm, params, tenancy=tenancy()), reqs())
    plan = FaultPlan([FaultEvent(2, "nan_logits"),
                      FaultEvent(5, "poison_page")])
    eng = _engine(lm, params, tenancy=tenancy(), fault_plan=plan,
                  verify_cache=True)
    assert _drain(eng, reqs()) == base
    eng.kv.verify()


def test_stalled_chunk_recovered_by_watchdog_bitwise(model):
    """A prefill chunk stalled past the watchdog window (a stuck allocator
    grant) is detected by the per-stream progress watchdog and recovered;
    the resumed stream — and its untouched neighbours — stay bitwise."""
    cfg, lm, params = model
    base = _drain(_engine(lm, params, chunked=True), _requests(cfg))
    plan = FaultPlan([FaultEvent(1, "stall_chunk", duration=50)])
    eng = _engine(lm, params, chunked=True, fault_plan=plan,
                  watchdog_iters=6, verify_cache=True)
    assert _drain(eng, _requests(cfg)) == base
    assert eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "watchdog"}) >= 1
    assert eng.reg.counter("serve_prefill_chunk_stalls_total").get() >= 1


def test_transient_dispatch_error_retried_bitwise(model):
    """A transient dispatch failure raises *before* the fused call touches
    its donated buffers, so the in-place retry is idempotent: the run
    completes bitwise with only the retry counter showing the hiccup."""
    cfg, lm, params = model
    base = _drain(_engine(lm, params), _requests(cfg))
    plan = FaultPlan([FaultEvent(3, "dispatch_error", duration=2)])
    eng = _engine(lm, params, fault_plan=plan)
    assert _drain(eng, _requests(cfg)) == base
    assert eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "dispatch_error"}) == 2


def test_persistent_dispatch_error_is_engine_fatal(model):
    """``max_retries`` consecutive dispatch failures re-raise: a dead
    dispatch path is an engine outage, not a per-stream fault."""
    cfg, lm, params = model
    plan = FaultPlan([FaultEvent(2, "dispatch_error", duration=10)])
    eng = _engine(lm, params, fault_plan=plan, max_retries=2)
    for r in _requests(cfg, n=2):
        eng.submit(r)
    with pytest.raises(TransientDispatchError):
        eng.run_until_drained(max_iters=100)


# ----------------------------------------------- bounded retries ----

def test_retry_exhaustion_dead_letters_without_poisoning_neighbors(model):
    """A persistent per-stream fault (nan_logits re-firing on the same
    slot every time its victim resumes) exhausts the retry budget and
    dead-letters that one request — with the error surfaced on it — while
    its neighbour completes bitwise and the engine drains clean."""
    cfg, lm, params = model

    def reqs():
        return _requests(cfg, n=2)

    base = _drain(ServeEngine(lm, params, max_batch=2, max_seq=64,
                              cache_backend="paged", page_size=4,
                              num_pages=33), reqs())
    plan = FaultPlan([FaultEvent(1, "nan_logits", slot=0),
                      FaultEvent(3, "nan_logits", slot=0)])
    eng = ServeEngine(lm, params, max_batch=2, max_seq=64,
                      cache_backend="paged", page_size=4, num_pages=33,
                      fault_plan=plan, max_retries=1, verify_cache=True)
    for r in reqs():
        eng.submit(r)
    done = {r.id: r for r in eng.run_until_drained(max_iters=2000)}
    assert done[0].status == "dead_letter"
    assert done[0].retries == 2
    assert "dead-lettered" in done[0].error
    assert "nonfinite_logits" in done[0].error
    assert done[1].status == "completed"
    assert tuple(done[1].out_tokens) == base[1]
    assert eng.reg.counter("serve_dead_letter_total").get(
        {"reason": "nonfinite_logits"}) == 1
    assert eng.reg.gauge("serve_streams_quarantined").get() == 0
    eng.kv.verify()


# ------------------------------------------------- chip failure ----

def test_chip_failure_drains_victims_and_resumes_bitwise(model):
    """One chip of a 2-chip page pool fails mid-flight: capacity degrades
    to the surviving chip's pages, only streams holding pages there are
    recovered, and every completed stream matches the 2-chip clean run
    bitwise."""
    cfg, lm, params = model

    def engine(**kw):
        return ServeEngine(lm, params, max_batch=4, max_seq=64,
                           cache_backend="paged", page_size=4,
                           num_pages=24, locality_chips=2, **kw)

    base = _drain(engine(), _requests(cfg, n=8))
    plan = FaultPlan([FaultEvent(3, "chip_failure", chip=1)])
    eng = engine(fault_plan=plan, watchdog_iters=16, verify_cache=True)
    for r in _requests(cfg, n=8):
        eng.submit(r)
    done = eng.run_until_drained(max_iters=2000)
    victims = eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "chip_failure"})
    assert victims >= 1
    assert eng.reg.counter("serve_faults_injected_total").get(
        {"kind": "chip_failure"}) == 1
    for r in done:
        if r.status == "completed":
            assert tuple(r.out_tokens) == base[r.id]
    # the surviving pool: chip 0's pages minus the scratch page
    assert eng.kv.usable_pages() == eng.kv.pages_per_chip - 1
    assert eng.kv.memory_stats().chips_failed == 1
    eng.kv.verify()


def test_chip_failure_dead_letters_unservable_footprints(model):
    """After the failure, a queued request whose footprint can never fit
    the degraded pool dead-letters immediately (reason ``capacity_lost``)
    instead of deferring forever."""
    cfg, lm, params = model
    eng = ServeEngine(lm, params, max_batch=2, max_seq=64,
                      cache_backend="paged", page_size=4, num_pages=12,
                      locality_chips=2,
                      fault_plan=FaultPlan([FaultEvent(2, "chip_failure",
                                                       chip=1)]))
    rng = np.random.default_rng(0)
    # footprint ceil((14+10)/4) = 6 pages > the 5 that survive chip 0
    big = Request(0, rng.integers(0, cfg.vocab_size, 14).astype(np.int32),
                  max_new_tokens=10)
    small = Request(1, rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                    max_new_tokens=4)
    third = Request(2, rng.integers(0, cfg.vocab_size, 14).astype(np.int32),
                    max_new_tokens=10)
    for r in (small, big, third):
        eng.submit(r)
    done = {r.id: r for r in eng.run_until_drained(max_iters=2000)}
    assert done[1].status == "completed"
    dead = [r for r in done.values() if r.status == "dead_letter"]
    assert dead and all("capacity_lost" in r.error for r in dead)
    assert eng.reg.counter("serve_dead_letter_total").get(
        {"reason": "capacity_lost"}) == len(dead)


# ---------------------------------------------------- random soak ----

def test_random_fault_soak_always_drains(model):
    """~200-step soak under a seeded random plan firing every recoverable
    kind, with requests trickling in mid-flight: the engine must drain,
    every request must reach a terminal status, and the pool sanitizer
    must come back clean."""
    cfg, lm, params = model
    rng = np.random.default_rng(11)
    arrivals = {}
    for i in range(16):
        arrivals.setdefault(int(rng.integers(0, 60)), []).append(
            Request(i, rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(2, 12))).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 7)),
                    sampling=SamplingParams(
                        temperature=0.0 if i % 2 == 0 else 0.8, seed=i)))
    eng = _engine(lm, params, chunked=True,
                  fault_plan=FaultPlan.random(10, 150, seed=3),
                  watchdog_iters=16, max_retries=8, verify_cache=True)
    it = 0
    while it < 200 or eng.queue or any(r is not None for r in eng.slot_req):
        for r in arrivals.get(it, []):
            eng.submit(r)
        eng.step()
        it += 1
        assert it < 1000, "fault soak did not drain"
    assert len(eng.finished) == 16
    assert all(r.status in ("completed", "dead_letter")
               for r in eng.finished)
    injected = sum(v for _, v in eng.reg.counter(
        "serve_faults_injected_total").labels_values())
    assert injected >= 1
    assert eng.reg.gauge("serve_streams_quarantined").get() == 0
    eng.kv.verify()


# ------------------------------------------------- host-tier faults ----

def test_host_poisoned_page_quarantined_at_prefetch(model):
    """Corruption in the warm tier surfaces as recompute, never as a
    poisoned stream: poison a host-resident prefix page, and the next
    hash-hitting admission's prefetch finite-check catches it *before*
    the page is registered as landed — the entry is quarantined, the
    prefix recomputes, and the stream stays bitwise identical to a
    tier-less engine."""
    cfg, lm, params = model
    prefix = np.arange(100, 108, dtype=np.int32)    # 2 full pages @ page=4

    def reqs(ids):
        return [Request(i, np.concatenate(
                    [prefix, np.asarray([(i * 7 + 3) % cfg.vocab_size],
                                        np.int32)]),
                        max_new_tokens=5) for i in ids]

    base = _drain(_engine(lm, params), reqs(range(4)))
    eng = _engine(lm, params, host_pages=16, verify_cache=True)
    # wave 1: sharers complete and free -> prefix pages offload to host
    out1 = _drain(eng, reqs([0, 1]))
    assert out1[0] == base[0] and out1[1] == base[1]
    eng.kv.drain_offloads()
    keys = [eng.kv._key(prefix, i) for i in range(2)]
    assert all(eng.kv.store.has(k) for k in keys)
    for k in keys:
        assert eng.kv.store.poison(k)
    # wave 2: the hash hit prefetches, trips the finite check, quarantines
    out2 = _drain(eng, reqs([2, 3]))
    assert out2[2] == base[2] and out2[3] == base[3]
    stats = eng.kv.store.stats()
    assert stats["poisoned"] >= 1
    # the poisoned entry was dropped; the wave-2 recompute re-offloaded
    # the prefix, so the key is resident again — with clean bytes
    got = eng.kv.store.lookup(keys[0])
    assert got is not None
    assert all(np.isfinite(np.asarray(v, np.float32)).all()
               for v in got.values()
               if np.issubdtype(v.dtype, np.floating))
    assert eng.reg.gauge("serve_streams_quarantined").get() == 0
    eng.kv.verify()


def test_prefix_store_digest_collision_is_miss_never_crosstalk(model):
    """The store indexes by a short digest but verifies the full prefix
    key on every lookup: force *every* digest to collide and the store
    must degrade to misses/replacements — another prefix's KV bytes are
    never served — while engine streams stay bitwise clean."""
    cfg, lm, params = model

    class CollidingStore(PrefixStore):
        def _digest(self, key):
            return b"\x00"                    # all keys collide

    # unit pin: collision on lookup is a miss, on put a replacement
    store = CollidingStore(8)
    store.bind({"x": ((2,), np.float32)})
    a, b = b"prefix-a", b"prefix-b"
    store.put(a, {"x": np.ones(2, np.float32)})
    assert store.lookup(b) is None            # full-key mismatch: miss
    assert store.stats()["collisions"] == 1
    store.put(b, {"x": np.full(2, 2.0, np.float32)})   # replaces a
    assert store.lookup(a) is None
    got = store.lookup(b)
    np.testing.assert_array_equal(got["x"], np.full(2, 2.0, np.float32))
    store.verify()

    # engine pin: a fully-colliding store never changes any stream
    def reqs():
        out = _requests(cfg)
        for r in out:       # two recurring prefixes so offloads collide
            r.prompt = np.concatenate(
                [np.arange(8, dtype=np.int32) + (r.id % 2) * 50,
                 r.prompt[:2]])
        return out

    base = _drain(_engine(lm, params), reqs())
    eng = _engine(lm, params, prefix_store=CollidingStore(16),
                  verify_cache=True)
    assert _drain(eng, reqs()) == base
    assert eng.kv.store.stats()["collisions"] >= 1
    assert eng.kv.store.pages_in_use() <= 1   # one digest -> one entry
    eng.kv.verify()


def test_evict_while_shared_never_offloads_live_pages(model):
    """The evict-while-shared race: preempting one sharer of a prefix
    while another still decodes from it must NOT offload the pages (their
    refcount is still positive — offload of a live page would let the
    host copy go stale).  Offload happens only when the *last* sharer
    frees; a later admission then prefetches the pages back."""
    cfg, lm, params = model
    kv = lm.init_cache(4, 32, dtype=jnp.float32, backend="paged",
                       page_size=4, num_pages=16, host_pages=8)
    prompt = np.arange(8, dtype=np.int32)
    _, _, pc = lm.forward(params, {"tokens": jnp.asarray(prompt[None])},
                          collect_cache=True)
    assert kv.alloc(0, 12, prefix=prompt) == 0
    kv.write_prefill(0, pc["layers"])
    assert kv.alloc(1, 12, prefix=prompt) == 8       # shares both pages
    shared = list(kv._slot_pages[1][:2])
    kv.evict(0)                     # preemption while slot 1 still shares
    kv.drain_offloads()
    assert kv.store.pages_in_use() == 0, \
        "evicting one sharer offloaded pages another slot still reads"
    assert all(kv._ref[p] == 1 for p in shared)
    assert all(p in kv._page_to_hash for p in shared)   # still registered
    kv.verify()
    kv.free(1)                      # last reference: NOW they offload
    kv.drain_offloads()
    assert kv.store.pages_in_use() == 2
    assert kv.store.stats()["offloads"] == 2
    assert kv.alloc(2, 12, prefix=prompt) == 8       # host prefetch hit
    assert kv.store.stats()["hits"] == 2
    kv.verify()


# --------------------------------------------- stuck-stream surfacing ----

def test_run_until_drained_raises_naming_wedged_slots(model):
    """Exhausting ``max_iters`` with work in flight is an error, not a
    silent return: the raise carries the wedged requests, each flagged
    ``stuck`` with its slot and last-progress iteration."""
    cfg, lm, params = model
    eng = _engine(lm, params)
    for r in _requests(cfg, n=2):
        eng.submit(r)
    with pytest.raises(EngineStuckError) as ei:
        eng.run_until_drained(max_iters=2)
    assert ei.value.stuck and all(r.status == "stuck"
                                  for r in ei.value.stuck)
    assert "slot" in ei.value.stuck[0].error
    assert "iteration" in ei.value.stuck[0].error


def test_run_until_drained_status_mode_returns_stuck_streams(model):
    """``on_stuck="status"`` reports instead of raising: the return value
    includes the wedged requests with their partial output intact."""
    cfg, lm, params = model
    eng = _engine(lm, params)
    for r in _requests(cfg, n=2):
        eng.submit(r)
    done = eng.run_until_drained(max_iters=3, on_stuck="status")
    stuck = [r for r in done if r.status == "stuck"]
    assert stuck and all(r.error for r in stuck)


# ----------------------------------------------- sanitizer + plan API ----

def test_verify_detects_corrupted_bookkeeping(model):
    """The sanitizer actually bites: hand-corrupt the allocator state and
    ``verify()`` must raise ``CacheInvariantError`` naming the drift."""
    cfg, lm, params = model
    kv = lm.init_cache(2, 32, dtype=jnp.float32, backend="paged",
                       page_size=4, num_pages=9)
    prompt = np.arange(5, dtype=np.int32)
    assert kv.alloc(0, 8, prefix=prompt) == 0
    kv.verify()
    kv._ref[kv._slot_pages[0][0]] += 1
    with pytest.raises(CacheInvariantError, match="refcounts"):
        kv.verify()


def test_fault_plan_parse_round_trip():
    plan = FaultPlan.parse("nan_logits@5,poison_page@9:slot=2,"
                           "chip_failure@12:chip=1,"
                           "stall_chunk@3:slot=0:dur=8,"
                           "dispatch_error@7:dur=2")
    assert [(e.kind, e.iteration) for e in plan.events] == [
        ("stall_chunk", 3), ("nan_logits", 5), ("dispatch_error", 7),
        ("poison_page", 9), ("chip_failure", 12)]
    assert plan.events_at(3)[0].duration == 8
    assert plan.events_at(9)[0].slot == 2
    assert plan.events_at(12)[0].chip == 1
    for bad in ("typo_kind@3", "nan_logits", "nan_logits@2:bogus=1"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)
    with pytest.raises(ValueError):
        FaultEvent(-1, "nan_logits")
    with pytest.raises(ValueError):
        FaultEvent(1, "nan_logits", duration=0)


def test_paged_only_fault_kinds_rejected_on_contiguous(model):
    """Plans with page/chip-level kinds cannot target the contiguous
    backend — rejected at construction, not at fire time."""
    cfg, lm, params = model
    plan = FaultPlan([FaultEvent(1, "poison_page")])
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(lm, params, max_batch=2, max_seq=64,
                    cache_backend="contiguous", fault_plan=plan)


def test_nan_guard_recovers_on_contiguous_backend(model):
    """The dispatch guard and recompute-on-resume don't depend on paging:
    nan_logits recovery holds bitwise on the contiguous backend too."""
    cfg, lm, params = model

    def engine(**kw):
        return ServeEngine(lm, params, max_batch=4, max_seq=64,
                           cache_backend="contiguous", **kw)

    base = _drain(engine(), _requests(cfg))
    eng = engine(fault_plan=FaultPlan([FaultEvent(2, "nan_logits")]))
    assert _drain(eng, _requests(cfg)) == base
    assert eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "nonfinite_logits"}) >= 1


def test_serve_cli_exits_nonzero_when_a_request_does_not_complete(
        capsys, monkeypatch):
    """The serving entry point reports per-stream outcomes, then fails the
    process when any request ended short of ``completed``."""
    from repro.launch import serve

    # leave this worker's JAX compile-cache settings as they were
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as e:
        serve.main(["--reduced", "--requests", "3", "--max-batch", "2",
                    "--max-seq", "64", "--new-tokens", "6",
                    "--fault-plan", "nan_logits@2:slot=0",
                    "--max-retries", "0"])
    assert "did not complete: 0 [dead_letter]" in str(e.value.code)
    assert "request 0 [dead_letter]" in capsys.readouterr().out
