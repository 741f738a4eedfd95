"""Ragged continuous batching over a pluggable KV cache: one fused
decode+sample dispatch per iteration.

watsonx.ai-style inference — the paper's clusters are "constantly moved
between training and inferencing", so the same model stack must serve inside
whatever HBM training leaves, and per-step overheads must stay in the <5%
regime of Figs 5/6/8.  Design:

* **B fixed cache slots**, each holding one in-flight request at its own
  depth.  ``decode_step`` takes a per-slot position vector ``(B,)`` (per-slot
  RoPE, scatter-writes, causal masks), so an arbitrarily ragged batch costs
  exactly **one jitted device call per engine iteration**.
* **Pluggable KV cache** (``repro.serve.kvcache``): the engine talks to a
  backend through ``alloc`` / ``decode_view`` / ``free`` / ``memory_stats``.
  ``PagedCache`` (the default) reserves only the pages a request actually
  needs — ``ceil((prompt + max_new_tokens) / page)`` — behind a (B, M) page
  table that rides into the fused dispatch as one more int32 input, with
  hash-based prefix sharing so identical prompt prefixes pin physical pages
  once.  When the page pool is exhausted, **admission is deferred** (the
  request stays queued) instead of the engine OOMing.  ``ContiguousCache``
  is the seed dense layout behind the same API.  ``decode_impl`` picks how
  the paged table is resolved per step: ``"gather"`` (XLA fallback,
  O(B·M·page) transient) or ``"pallas"`` (the page-table-walking
  flash-decode kernel, O(page) transient — ``repro.kernels.paged_decode``).
* **Sharded paged serving** (``mesh=``): the page pools carry the
  ``kv_pages`` logical axis and shard P/n over the ``kv_axis`` mesh axis,
  so pinned pool HBM scales *down* with the inference mesh.  The fused
  dispatch stays one device call: inside it, each layer's scatter-write +
  paged attention runs under shard_map — every chip owns the page-id range
  ``[chip*P/n, (chip+1)*P/n)``, treats non-local pages exactly like dead
  pages, and the per-chip online-softmax partials merge with one
  pmax + two psums (``repro.parallel.pagedkv``).  ``PagedCache.alloc``'s
  free list is locality-aware (prefers one chip per request) without ever
  changing admission decisions.
* **Batched bucketed prefill**: admitted prompts are grouped by power-of-two
  length bucket and each group runs as a *single* ``lm.forward`` call whose
  K/V block is scatter-written into every admitted slot's cache rows/pages
  in the same device call (one dispatch per group, not per request).
* **Chunked prefill** (``prefill_chunk=C``): instead of prefilling a whole
  prompt in one bucketed call — which stalls every in-flight decode stream
  for the prompt's full forward — admitted prompts split into fixed-size
  C-token chunks that interleave with the fused decode steps: each engine
  iteration spends at most ``prefill_budget`` tokens (default: one chunk)
  on prefill before running decode, so no decode iteration ever waits on
  more than one chunk of prefill compute.  Chunk *k* attends causally over
  the pages written by chunks ``0..k-1`` at a position offset
  (``lm.prefill_chunk``), pages are claimed chunk-by-chunk
  (``PagedCache.alloc_chunked``/``extend`` — banker-safe, so a long prompt
  admits into a pool whose free pages cover only its first chunk, and a
  mid-prefill stall defers the chunk rather than deadlocking), and
  mid-prefill slots are excluded from the fused dispatch's ``active`` mask
  with their page-table rows shielded to scratch until the last chunk
  lands.  Page-aware by construction: paged backend only, single-device
  (the sharded pool's per-chip chunk scatter is a ROADMAP follow-on), and
  dense-FFN families only — MoE capacity routing depends on the forwarded
  group shape, so chunk-at-a-time routing would break stream parity.
* **Multi-tenant SLO scheduling** (``tenancy=``, ``repro.serve.tenancy``):
  requests carry a tenant id; each tenant has a priority class
  (``interactive`` / ``batch``, extensible) and an optional KV **page
  quota** the paged banker enforces (a quota deny skips just that request
  — other tenants keep admitting — while a pool deny still stops
  admission in order).  Admission is priority-ordered (stable FIFO within
  a class), chunked prefill schedules TTFT-sensitive classes first with
  optional per-class token budgets, and under slot/page pressure the
  engine **preempts** the lowest-priority preemptible running decode:
  its pages are evicted and the request re-queued for
  recompute-on-resume prefill (prompt + generated-so-far tokens re-enter
  as one prefill, re-sharing still-registered prefix pages, with the
  sampling step index resumed so non-greedy streams stay reproducible).
* **On-device sampling**: greedy / temperature / top-k / top-p run as a
  vectorized kernel (``repro.serve.sampling``) fused into the decode
  dispatch.  The only host transfer per iteration is the (B,) vector of
  sampled token ids.
* **Scratch-routed inactive writes**: masked (free) slots still participate
  in the fused scatter, but their write position is routed to a scratch
  location — row 0 of their own slot (contiguous: always rewritten by the
  next prefill before it can be attended) or the scratch page (paged: a
  freed slot's page-table row points at physical page 0), so a freed slot
  can never deposit stale-position K/V into pages that have since been
  reallocated to another request.
* **Fault tolerance** (``repro.serve.faults``): a seedable ``FaultPlan``
  injects faults at named seams — non-finite logits inside the fused
  dispatch, poisoned KV pages, a failed chip of the sharded pool, stalled
  prefill chunks, transient dispatch exceptions.  Detection is in-band
  and cheap: the fused step's non-finite guard maps a bad logit row to a
  ``-1`` token sentinel riding the existing single (B,) host transfer, a
  per-stream progress watchdog (``watchdog_iters``) catches wedged slots,
  and ``PagedCache.verify()`` re-checks the allocator invariants every
  iteration in debug mode (``verify_cache=True``).  Recovery reuses the
  preemption machinery so it stays **bitwise**: the suspect slot's pages
  are dropped from the prefix registry (corrupt content must never
  re-share) and evicted, and the request re-queues for
  recompute-on-resume prefill with its sampling step indices intact —
  greedy AND seeded streams continue bit-identically — under bounded
  retries with exponential backoff.  A stream out of retries (or whose
  footprint can never fit again after a chip loss) dead-letters:
  ``status="dead_letter"`` with the error on the ``Request``, neighbours
  untouched.  Chip failure (``PagedCache.fail_chip``) drains the lost
  chip's free pages so capacity degrades from P to P·(n-1)/n and only
  the streams actually holding pages there are recovered.

Finished slots (EOS or max_len) free their cache reservation and are
refilled from the queue — the 'continuous batching' part.  Dispatch and
memory accounting are exported through the metrics registry
(``serve_decode_dispatches_total`` / ``serve_iterations_total`` /
``serve_prefill_dispatches_total`` / ``serve_prefill_batch_size`` /
``serve_kv_pages_in_use`` / ``serve_kv_bytes_reserved`` /
``serve_decode_transient_bytes``) so the one-call-per-iteration and
paged-memory invariants are observable, not asserted.  The phases of each
``step()`` — prefill rounds and their waits, admission, each request's
queue wait, the decode dispatch, the wait on its sampled tokens, the emit
— are timed as host spans (``serve.*``) in the engine's ``SpanLog``
(``spans=``), on ``time.perf_counter()`` and in any profiler trace; the
table is in ``docs/telemetry.md``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ForwardOpts, LM
from repro.core.telemetry import MetricsRegistry, SpanLog
from repro.serve.faults import FaultEvent, FaultPlan, TransientDispatchError
from repro.serve.sampling import sample_batch
from repro.serve.tenancy import TenancyConfig, Victim, next_victim


@dataclass
class SamplingParams:
    temperature: float = 0.0         # 0 => greedy
    top_k: int = 0                   # 0 => no top-k filter
    top_p: float = 1.0               # nucleus
    seed: int = 0


@dataclass
class _PrefillState:
    """A slot mid-chunked-prefill: resumable across engine iterations."""
    req: Request
    done: int = 0            # prefill positions landed so far
    shared: int = 0          # leading positions backed by shared pages
    # the token array being prefilled: the prompt, or — after a preemption
    # — prompt + generated-so-far (recompute-on-resume)
    tokens: Optional[np.ndarray] = None


@dataclass
class Request:
    id: int
    prompt: np.ndarray               # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                 # -1: never stops early
    sampling: SamplingParams = field(default_factory=SamplingParams)
    img_embeds: Optional[np.ndarray] = None   # (num_image_tokens, d) for vlm
    out_tokens: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    tenant: str = "default"          # tenancy key (ignored without tenancy=)
    preemptions: int = 0             # times this request lost its slot
    last_token_at: Optional[float] = None     # for inter-token latency
    retries: int = 0                 # fault/watchdog recoveries consumed
    error: Optional[str] = None      # dead-letter / stuck diagnostic
    status: str = "pending"          # terminal: completed|dead_letter|stuck
    _seq: int = 0                    # submit order — the FIFO tiebreak
    _resume_after: int = 0           # recovery backoff: earliest readmit iter


class EngineStuckError(RuntimeError):
    """``run_until_drained`` exhausted ``max_iters`` with work still in
    flight.  ``.stuck`` holds the wedged requests, each flagged
    ``status="stuck"`` with the diagnostic on ``Request.error``."""

    def __init__(self, message: str, stuck: List["Request"]):
        super().__init__(message)
        self.stuck = stuck


def _filtered_probs_np(logits: np.ndarray, params: SamplingParams) -> np.ndarray:
    """The (float64 numpy) filtered distribution ``sample_token`` draws from —
    the per-row reference the vectorized device sampler is tested against."""
    x = logits.astype(np.float64) / params.temperature
    if params.top_k > 0:
        kth = np.partition(x, -params.top_k)[-params.top_k]
        x = np.where(x < kth, -np.inf, x)
    p = np.exp(x - np.max(x))
    p /= p.sum()
    if params.top_p < 1.0:
        order = np.argsort(-p)
        cum = np.cumsum(p[order])
        cut = np.searchsorted(cum, params.top_p) + 1
        mask = np.zeros_like(p)
        mask[order[:cut]] = 1.0
        p = p * mask
        p /= p.sum()
    return p


def sample_token(logits: np.ndarray, params: SamplingParams,
                 step: int) -> int:
    """Greedy / temperature / top-k / top-p sampling over a 1-D logit row
    (host-side reference implementation; the engine samples on device)."""
    if params.temperature <= 0.0:
        return int(np.argmax(logits))
    p = _filtered_probs_np(logits, params)
    rng = np.random.default_rng((params.seed, step))
    return int(rng.choice(len(p), p=p))


class ServeEngine:
    def __init__(self, lm: LM, params, max_batch: int, max_seq: int,
                 opts: ForwardOpts = ForwardOpts(attn_impl="dense",
                                                 remat="none"),
                 registry: Optional[MetricsRegistry] = None,
                 greedy: bool = True,
                 cache_backend: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_sharing: bool = True,
                 decode_impl: str = "gather",
                 mesh=None, kv_axis: str = "model", dp_axis=None,
                 prefill_chunk: int = 0, prefill_budget: int = 0,
                 kv_dtype: str = "native",
                 tenancy: Optional[TenancyConfig] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 watchdog_iters: int = 0, max_retries: int = 3,
                 verify_cache: bool = False, alerts=None,
                 health_every: int = 16,
                 locality_chips: Optional[int] = None,
                 host_pages: int = 0, prefix_store=None,
                 spans: Optional[SpanLog] = None):
        # per-slot positions rely on masked-then-overwritten cache writes,
        # which holds for attention KV caches but not recurrent state
        assert lm.cfg.family in ("dense", "moe", "vlm"), (
            "ServeEngine supports attention-cache families; recurrent archs "
            "serve via a synchronized full-batch decode loop")
        self.lm = lm
        self.params = params
        self.B = max_batch
        self.S = max_seq
        self.finished: List[Request] = []
        self.opts = opts
        self.reg = registry or MetricsRegistry()
        self.spans = spans if spans is not None else SpanLog()
        self._span = self.spans.span
        self.greedy = greedy
        self.img_len = (lm.cfg.num_image_tokens
                        if lm.cfg.family == "vlm" else 0)
        dt = jnp.float32 if lm.cfg.dtype == "float32" else jnp.bfloat16
        self.kv = lm.init_cache(max_batch, max_seq, dtype=dt,
                                backend=cache_backend, page_size=page_size,
                                num_pages=num_pages,
                                prefix_sharing=prefix_sharing,
                                decode_impl=decode_impl, mesh=mesh,
                                kv_axis=kv_axis, dp_axis=dp_axis,
                                kv_dtype=kv_dtype,
                                locality_chips=locality_chips,
                                host_pages=host_pages,
                                prefix_store=prefix_store)
        # host-tier counter sync: the PrefixStore keeps monotonic totals;
        # _export_memory publishes them as counter increments by delta
        self._host_synced: Dict[str, int] = {}
        # fault injection + detection + recovery (repro.serve.faults): the
        # plan is polled once per step; all detection state is host-side
        self.fault_plan = fault_plan
        self.watchdog_iters = int(watchdog_iters)
        self.max_retries = int(max_retries)
        self.verify_cache = bool(verify_cache)
        self.alerts = alerts
        self.health_every = int(health_every)
        if fault_plan is not None and type(self.kv).backend != "paged":
            bad = ({e.kind for e in fault_plan.events}
                   & {"poison_page", "chip_failure", "stall_chunk"})
            if bad:
                raise ValueError(
                    f"fault kinds {sorted(bad)} target the paged allocator "
                    "(physical pages / chips / chunked grants); use "
                    "cache_backend='paged'")
        self._iter = 0                      # step() clock (faults, watchdog)
        self._pending_faults: List[FaultEvent] = []  # carried until firable
        self._poison_slots: set = set()     # nan_logits victims this step
        self._stall_until: Dict[int, int] = {}   # slot -> stall expiry iter
        self._dispatch_fail_left = 0        # queued transient dispatch raises
        self._last_progress: Dict[int, int] = {}  # slot -> last progress it.
        self._quarantined: Dict[int, int] = {}    # req.id -> recovery start
        # chunked prefill: C-token chunks interleaved with decode, at most
        # `budget` prefill tokens per engine iteration (0 = whole-prompt)
        self.chunk = int(prefill_chunk)
        self.budget = int(prefill_budget) or self.chunk
        if prefill_budget and not self.chunk:
            raise ValueError(
                "prefill_budget bounds *chunked* prefill per iteration; "
                "without prefill_chunk the whole prompt lands in one "
                "dispatch and no budget applies (set prefill_chunk)")
        if self.chunk:
            if cache_backend != "paged":
                raise ValueError(
                    "chunked prefill is page-aware: chunks claim pages "
                    "incrementally and mid-prefill slots shield their table "
                    "rows from decode (use cache_backend='paged')")
            if self.img_len:
                raise ValueError(
                    "chunked prefill covers token prompts; VLM image-embed "
                    "prefixes prefill whole-prompt")
            if lm.cfg.family == "moe":
                raise ValueError(
                    "chunked prefill would change MoE expert-capacity "
                    "routing: moe_ffn computes capacity and token dropping "
                    "per forwarded sequence, so a (1, C) chunk routes "
                    "differently than the whole bucketed prompt and the "
                    "bitwise stream-parity contract breaks; MoE prompts "
                    "prefill whole-prompt")
            if self.chunk < 1 or self.budget < self.chunk:
                raise ValueError(
                    f"prefill budget {self.budget} below one chunk "
                    f"({self.chunk}): no chunk could ever dispatch")
        # multi-tenant SLO scheduling: priority-ordered admission, per-tenant
        # page quotas (enforced by the paged banker), preemptive eviction
        self.tenancy = tenancy
        self._submit_seq = 0
        if tenancy is not None:
            if tenancy.has_quotas():
                if type(self.kv).backend != "paged":
                    raise ValueError(
                        "per-tenant page quotas are enforced inside the "
                        "paged backend's banker-style safety check; the "
                        "contiguous layout has no pages to meter (use "
                        "cache_backend='paged' or drop the quotas)")
                for spec in tenancy.tenants.values():
                    if spec.page_quota is not None:
                        self.kv.set_quota(spec.name, spec.page_quota)
            if tenancy.preemption and type(self.kv).backend != "paged":
                raise ValueError(
                    "preemption evicts a victim's KV pages back to the "
                    "pool; the contiguous layout pre-reserves every slot so "
                    "there is nothing to reclaim (use cache_backend='paged' "
                    "or TenancyConfig(..., preemption=False))")
            if self.chunk:
                for spec in tenancy.tenants.values():
                    cap = tenancy.classes[spec.cls].prefill_budget
                    if cap is not None and cap < self.chunk:
                        raise ValueError(
                            f"class {spec.cls!r} prefill_budget {cap} below "
                            f"one chunk ({self.chunk}): its tenants could "
                            "never finish a prefill")
        self.prefilling: dict = {}           # slot -> _PrefillState (FIFO)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)   # next write index
        self.queue: List[Request] = []
        # per-slot device-call state: the pending (sampled, not yet emitted)
        # token plus the sampling params, mirrored as flat arrays so the
        # fused dispatch takes plain (B,) tensors
        self.next_token = np.zeros(max_batch, np.int32)
        self.active = np.zeros(max_batch, bool)
        self.temps = np.zeros(max_batch, np.float32)
        self.top_ks = np.zeros(max_batch, np.int32)
        self.top_ps = np.ones(max_batch, np.float32)
        self.seeds = np.zeros(max_batch, np.int32)
        # the pool/rows argument is donated so XLA can update the cache in
        # place instead of double-buffering it per dispatch (live HBM stays
        # ~bytes_total, not 2x).  The page table is a separate, NON-donated
        # input: its device copy is cached across steps by PagedCache.
        self._fused = jax.jit(self._make_fused(), static_argnums=(12,),
                              donate_argnums=(2,))
        self._prefill = jax.jit(self._make_prefill(), donate_argnums=(3,))
        if self.chunk:
            self._chunk_step = jax.jit(self._make_chunk(),
                                       donate_argnums=(2,))
        self._declare_metrics()
        if type(self.kv).backend == "paged":
            # per-step transient of the paged KV read path (byte math, one
            # layer): the gather fallback scales with B·M·page, the pallas
            # kernel with the page block only — dense rows gather nothing
            from repro.serve.kvcache import decode_transient_bytes
            self.reg.gauge("serve_decode_transient_bytes").set(
                decode_transient_bytes(
                    lm.cfg, self.B, self.kv.max_pages, self.kv.page,
                    self.kv.dtype, self.kv.decode_impl,
                    kv_dtype=self.kv.kv_dtype))
        if tenancy is not None:
            for spec in tenancy.tenants.values():
                if spec.page_quota is not None:
                    self.reg.gauge("serve_tenant_quota_pages").set(
                        spec.page_quota, {"tenant": spec.name})

    def _declare_metrics(self):
        """Eagerly register every metric the engine can emit, with help
        text, so the observability surface is complete from iteration zero
        (dashboards see zero-valued series instead of gaps) and
        ``docs/telemetry.md`` can be verified against the registry by a test
        (tests/test_docs.py) rather than by hand."""
        c, g, h = self.reg.counter, self.reg.gauge, self.reg.histogram
        c("serve_requests_total", "requests accepted by submit()")
        c("serve_admission_deferred_total",
          "admissions deferred by page-pool admission control; the "
          "'reason' label splits pool_exhausted vs quota_denied (the "
          "unlabeled series counts both)")
        c("serve_quota_denied_total",
          "admissions denied by a per-tenant page quota (the tenant's "
          "request is skipped; lower-priority tenants still admit)")
        c("serve_preemptions_total",
          "running decodes preempted under pressure: pages evicted, "
          "request re-queued for recompute-on-resume prefill")
        c("serve_prefill_dispatches_total",
          "prefill device dispatches (bucketed groups + chunks)")
        c("serve_prefill_tokens_total", "prompt tokens prefilled")
        c("serve_prefill_chunks_total", "chunked-prefill chunk dispatches")
        c("serve_prefill_chunk_stalls_total",
          "prefill chunks deferred because a page grant was not banker-safe")
        c("serve_decode_stall_iters",
          "iterations where live decode streams waited on prefill work "
          "exceeding the per-iteration budget")
        c("serve_decode_dispatches_total", "fused decode+sample dispatches")
        c("serve_iterations_total", "engine iterations")
        c("serve_tokens_total", "tokens emitted by finished requests")
        h("serve_ttft_seconds", "submit-to-first-token latency")
        h("serve_latency_seconds", "submit-to-completion latency")
        h("serve_class_ttft_seconds",
          "submit-to-first-token latency by priority class ('class' label; "
          "populated when tenancy is configured)")
        h("serve_class_itl_seconds",
          "inter-token latency by priority class ('class' label; a "
          "preempted stream's requeue gap counts — that is the SLO cost "
          "of preemption)")
        h("serve_prefill_batch_size",
          "requests covered by one bucketed prefill dispatch",
          buckets=(1, 2, 4, 8, 16, 32, 64, float("inf")))
        g("serve_kv_pages_in_use", "physical KV pages reserved by live slots")
        g("serve_kv_bytes_reserved", "cache bytes reserved by live slots")
        g("serve_kv_pages_shared", "pages with refcount > 1 (prefix sharing)")
        g("serve_kv_bytes_per_chip", "pinned cache bytes per mesh chip")
        g("serve_decode_transient_bytes",
          "per-step transient of the paged KV read path, one layer")
        g("serve_kv_quant_enabled",
          "1 when the cache stores int8 quantized KV pages")
        g("serve_kv_quant_scale_bytes",
          "HBM pinned by the int8 page format's fp32 scale arrays")
        g("serve_kv_quant_bytes_saved",
          "pool bytes saved by int8 pages vs the compute-dtype pool")
        g("serve_tenant_pages_in_use",
          "footprint pages charged to each tenant ('tenant' label)")
        g("serve_tenant_quota_pages",
          "configured per-tenant page quota ('tenant' label)")
        c("serve_faults_injected_total",
          "injected faults that fired, by seam ('kind' label)")
        c("serve_stream_retries_total",
          "stream recoveries (quarantine + evict + re-queue) and transient "
          "dispatch retries, by detection ('reason' label)")
        c("serve_dead_letter_total",
          "requests terminally failed: recovery retries exhausted or "
          "footprint unfittable after a chip loss ('reason' label)")
        h("serve_recovery_iters",
          "engine iterations from a stream's quarantine to its next "
          "emitted token",
          buckets=(1, 2, 4, 8, 16, 32, 64, float("inf")))
        g("serve_streams_quarantined",
          "streams currently re-queued by fault recovery (awaiting resume)")
        c("serve_prefill_chunks_skipped_total",
          "prefill chunks whose forward was skipped because every position "
          "was already backed by landed shared pages (device-shared or "
          "prefetched from the host tier)")
        c("serve_prefix_store_hits_total",
          "prefix-store page lookups served from the host tier")
        c("serve_prefix_store_misses_total",
          "prefix-store page lookups that missed (cold, evicted, digest "
          "collision, or quarantined-poisoned) and recomputed prefill")
        c("serve_host_evictions_total",
          "host-tier pages LRU-evicted to make room for newer prefixes")
        c("serve_host_offload_bytes_total",
          "wire bytes copied device->host by cold-prefix offload")
        c("serve_host_prefetch_bytes_total",
          "wire bytes copied host->device by prefix-hit prefetch")
        g("serve_host_pages_in_use",
          "prefix pages resident in the host-RAM tier's pinned buffers")

    # ---------------------------------------------------------- jit builds ----
    def _make_fused(self):
        """One device call: decode all B slots at their own positions, then
        sample the next token for every slot, vectorized.  Returns the (B,)
        sampled ids (zeros on inactive slots) and the new cache view.

        ``all_greedy`` is static: the common all-greedy batch compiles to a
        bare argmax, skipping the top-k/top-p sort machinery entirely (at
        most two jit cache entries)."""
        lm, vocab = self.lm, self.lm.cfg.vocab_size
        decode_impl = self.kv.decode_impl   # fixed per engine (kvcache config)
        mesh, kv_axis = self.kv.mesh, self.kv.kv_axis
        dp_axis = self.kv.dp_axis

        def fused(params, tokens, layers, page_table, positions, active,
                  temps, top_ks, top_ps, seeds, steps, poison, all_greedy):
            cache = {"layers": layers}
            if page_table is not None:
                cache["page_table"] = page_table
            logits, cache = lm.decode_step(params, tokens, cache, positions,
                                           decode_impl=decode_impl,
                                           mesh=mesh, kv_axis=kv_axis,
                                           dp_axis=dp_axis)
            rows = logits[:, -1, :vocab].astype(jnp.float32)
            # nan_logits fault seam: a traced (B,) mask NaNs the victim's
            # row *inside* the dispatch, so detection exercises the real
            # guard (all-False on healthy iterations — same trace)
            rows = jnp.where(poison[:, None], jnp.nan, rows)
            if all_greedy:
                tok = jnp.argmax(rows, axis=-1).astype(jnp.int32)
            else:
                tok = sample_batch(rows, temps, top_ks, top_ps, seeds, steps)
            # non-finite guard: a row with any NaN/Inf yields the -1
            # sentinel in place of a token id — detection rides the
            # existing single (B,) host transfer instead of adding one
            tok = jnp.where(jnp.isfinite(rows).all(axis=-1), tok, -1)
            return jnp.where(active, tok, 0), cache["layers"]

        return fused

    def _make_prefill(self):
        """Batched whole-prompt prefill: one forward with cache collection
        for ``n`` same-bucket prompts, scatter the K/V blocks into every
        admitted slot's storage (rows for contiguous, page-table-resolved
        flat indices for paged), and sample each request's first token on
        device — all in one dispatch.  jit caches one trace per
        (group size, prompt bucket) pair.

        ``steps`` is each request's per-stream sampling index: 0 for a
        fresh prompt, ``len(out_tokens)`` for a preempted request being
        recompute-resumed — the token it re-samples is that deep in its
        stream, so a seeded non-greedy stream draws the same value it
        would have drawn without the preemption."""
        lm, opts, vocab = self.lm, self.opts, self.lm.cfg.vocab_size
        has_img = self.img_len > 0
        writer = self.kv.staged_write_prefill

        def prefill(params, tokens, img_embeds, layers, write_spec, last_idx,
                    temps, top_ks, top_ps, seeds, steps):
            batch = {"tokens": tokens}
            if has_img:
                batch["img_embeds"] = img_embeds
            logits, _, pcache = lm.forward(params, batch, opts,
                                           collect_cache=True)
            layers = writer(layers, pcache["layers"], write_spec)
            n = tokens.shape[0]
            rows = logits[jnp.arange(n), last_idx, :vocab].astype(jnp.float32)
            toks = sample_batch(rows, temps, top_ks, top_ps, seeds, steps)
            # same non-finite sentinel as the fused dispatch
            toks = jnp.where(jnp.isfinite(rows).all(axis=-1), toks, -1)
            return toks, layers

        return prefill

    def _make_chunk(self):
        """One chunked-prefill device call: forward a stacked (n, C) round
        of chunks — at most one chunk per slot — against their pages
        (``lm.prefill_chunk`` — scatter + prior-cache attention), and
        sample a would-be first token from each chunk's last valid row.
        A sampled token is consumed only when that chunk was its prompt's
        final one; computing it unconditionally keeps traces cheap.  jit
        caches one trace per round group size (same shape discipline as
        ``_prefill_group``); every chunk is padded to the fixed length.

        Under ``mesh=`` the chunk forward routes through the sharded
        write/attend primitive (per-chip ``mode="drop"`` scatters + the
        partial-softmax merge) — the captured mesh/axes mirror
        ``_make_fused``."""
        lm, vocab = self.lm, self.lm.cfg.vocab_size
        mesh, kv_axis = self.kv.mesh, self.kv.kv_axis
        dp_axis = self.kv.dp_axis

        def run(params, tokens, layers, page_row, dest, start_pos, last_pos,
                temps, top_ks, top_ps, seeds, steps):
            cache = {"layers": layers, "page_table": page_row}
            logits, cache = lm.prefill_chunk(params, tokens, cache,
                                             start_pos, dest, last_pos,
                                             mesh=mesh, kv_axis=kv_axis,
                                             dp_axis=dp_axis)
            rows = logits[:, -1, :vocab].astype(jnp.float32)
            toks = sample_batch(rows, temps, top_ks, top_ps, seeds, steps)
            # the chunk attends prior pages: a poisoned page surfaces here
            toks = jnp.where(jnp.isfinite(rows).all(axis=-1), toks, -1)
            return toks, cache["layers"]

        return run

    # ------------------------------------------------------------- intake ----
    def submit(self, req: Request):
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.id}: empty prompt "
                             "(nothing to prefill or sample from)")
        if len(req.prompt) + self.img_len >= self.S:
            raise ValueError(
                f"request {req.id}: prompt length {len(req.prompt)} "
                f"(+{self.img_len} image tokens) leaves no room to decode "
                f"in a max_seq={self.S} cache")
        if not self.kv.can_ever_fit(self._footprint(req)):
            raise ValueError(
                f"request {req.id}: footprint of {self._footprint(req)} "
                f"positions can never fit the {type(self.kv).backend} cache "
                "pool (shrink the prompt/max_new_tokens or grow num_pages)")
        if self.tenancy is not None:
            self.tenancy.spec(req.tenant)    # raises on unknown tenant
        req.submitted_at = time.perf_counter()
        req._seq = self._submit_seq
        self._submit_seq += 1
        self.queue.append(req)
        self.reg.counter("serve_requests_total").inc()

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    # ----------------------------------------------------------- tenancy ----
    def _prio(self, req: Request) -> int:
        return self.tenancy.priority_of(req.tenant) if self.tenancy else 0

    def _class_name(self, req: Request) -> str:
        return self.tenancy.spec(req.tenant).cls if self.tenancy else "none"

    def _tenant(self, req: Request) -> Optional[str]:
        return req.tenant if self.tenancy is not None else None

    def _admission_order(self) -> List[Request]:
        """Queue snapshot in admission order: priority class first, then
        submit order.  Without tenancy the sort is a no-op (all priority 0,
        stable by ``_seq``) — plain FIFO, bit-identical to the untenanted
        engine.  A request preempted *during* the current admission pass
        re-enters ``self.queue`` but not this snapshot, so one pass can
        never preempt-and-readmit the same request.  A recovering request
        stays invisible until its ``_resume_after`` backoff horizon."""
        ready = [r for r in self.queue if r._resume_after <= self._iter]
        return sorted(ready, key=lambda r: (-self._prio(r), r._seq))

    def _prefill_tokens(self, req: Request) -> np.ndarray:
        """What prefill must land for this request: the prompt — plus, for
        a preempted request, every token it had already generated
        (recompute-on-resume: the whole history re-enters as one prefill,
        re-sharing any of its pages still in the prefix registry)."""
        if req.out_tokens:
            return np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.out_tokens, np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _count_deferral(self, reason: str) -> None:
        c = self.reg.counter("serve_admission_deferred_total")
        c.inc()                       # unlabeled total (both causes)
        c.inc(1, {"reason": reason})
        if reason == "quota_denied":
            self.reg.counter("serve_quota_denied_total").inc()

    def _preempt_for(self, req: Request) -> Optional[int]:
        """Evict the best victim so ``req`` can take its slot/pages.

        Victims are running decode slots only — strictly lower priority,
        preemptible class; mid-chunked-prefill slots are excluded (their
        banker need is in flight).  Returns the freed slot, or ``None``
        when nothing is eligible (equal priority never preempts: two batch
        tenants cannot livelock evicting each other)."""
        if (self.tenancy is None or not self.tenancy.preemption
                or type(self.kv).backend != "paged"):
            return None
        cands = [Victim(i, self._prio(r),
                        self.tenancy.class_of(r.tenant).preemptible,
                        self.kv.slot_freeable(i))
                 for i, r in enumerate(self.slot_req)
                 if r is not None and i not in self.prefilling]
        victim = next_victim(cands, self._prio(req))
        if victim is None:
            return None
        self._preempt(victim.slot)
        return victim.slot

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s pages and re-queue its request.  The pending
        (sampled, not yet emitted) token is discarded — the resume prefill
        re-samples it at the same stream step, so a greedy or seeded
        stream continues bit-identically."""
        req = self.slot_req[slot]
        self.kv.evict(slot)
        self.slot_req[slot] = None
        self.active[slot] = False
        self.slot_pos[slot] = 0
        self.next_token[slot] = 0
        req.preemptions += 1
        self.queue.append(req)   # keeps _seq: resumes ahead of later peers
        self.reg.counter("serve_preemptions_total").inc()

    def _footprint(self, req: Request) -> int:
        """Cache positions a request can ever occupy — the number ``submit``
        validates against ``can_ever_fit`` and ``_admit`` reserves via
        ``kv.alloc``; keeping both on this one formula is what guarantees an
        admitted request can never stall waiting for pages that cannot
        exist."""
        return min(self.img_len + len(req.prompt) + req.max_new_tokens,
                   self.S)

    # ----------------------------------------------------- fault recovery ----
    def _apply_faults(self) -> None:
        """Fire every pending fault whose preconditions hold; the rest
        carry to the next iteration (a plan never silently drops a fault
        it could eventually fire)."""
        self._pending_faults = [
            e for e in self._pending_faults if not self._fire(e)]

    def _victim_slot(self, want: Optional[int]) -> Optional[int]:
        """Deterministic victim resolution: the requested slot if it is
        live and decodable, else the lowest active decoding slot."""
        slots = [i for i in range(self.B)
                 if self.active[i] and i not in self.prefilling]
        if want is not None:
            return want if want in slots else None
        return slots[0] if slots else None

    def _fire(self, e: FaultEvent) -> bool:
        """Apply one fault event; returns ``False`` to carry it forward."""
        if e.kind == "dispatch_error":
            self._dispatch_fail_left += e.duration
        elif e.kind == "nan_logits":
            slot = self._victim_slot(e.slot)
            if slot is None:
                return False
            self._poison_slots.add(slot)
        elif e.kind == "poison_page":
            pid = e.page
            if pid is None:
                slot = self._victim_slot(e.slot)
                if slot is None or self.slot_pos[slot] <= 0:
                    return False
                # the page backing the victim's most recent position: read
                # by its very next decode step, so detection is immediate
                pid = int(self.kv.page_table[
                    slot, (int(self.slot_pos[slot]) - 1) // self.kv.page])
            if pid <= 0:
                return False
            self.kv.poison_page(pid)
        elif e.kind == "stall_chunk":
            if not self.chunk:
                return True         # impossible by config: never fires
            slots = sorted(self.prefilling)
            if e.slot is not None:
                slots = [s for s in slots if s == e.slot]
            if not slots:
                return False
            self._stall_until[slots[0]] = self._iter + e.duration
        else:
            assert e.kind == "chip_failure", e.kind
            chip = e.chip if e.chip is not None \
                else getattr(self.kv, "chips", 1) - 1
            for s in self.kv.fail_chip(chip):
                self._recover(s, "chip_failure")
            # queued requests whose footprint can no longer ever fit the
            # degraded pool would defer forever: dead-letter them now
            for r in [r for r in self.queue
                      if not self.kv.can_ever_fit(self._footprint(r))]:
                self.queue.remove(r)
                self._dead_letter(r, "capacity_lost")
        self.reg.counter("serve_faults_injected_total").inc(
            1, {"kind": e.kind})
        return True

    def _recover(self, slot: int, reason: str) -> None:
        """Quarantine ``slot``'s stream and re-queue it for bitwise
        recompute-on-resume — the preemption path under a retry budget.
        The slot's pages are dropped from the prefix registry first
        (suspect content must never re-share into a resume prefill), then
        evicted; the request re-enters the queue keeping its ``_seq``,
        gated by an exponential-backoff resume horizon.  Out of retries —
        or with a footprint the post-chip-failure pool can never hold
        again — the request dead-letters instead of looping."""
        req = self.slot_req[slot]
        self.prefilling.pop(slot, None)
        if type(self.kv).backend == "paged":
            self.kv.unregister_pages(list(self.kv._slot_pages[slot]))
            self.kv.evict(slot)
        else:
            self.kv.free(slot)
        self.slot_req[slot] = None
        self.active[slot] = False
        self.slot_pos[slot] = 0
        self.next_token[slot] = 0
        self._stall_until.pop(slot, None)
        self._last_progress.pop(slot, None)
        req.retries += 1
        self.reg.counter("serve_stream_retries_total").inc(
            1, {"reason": reason})
        if req.retries > self.max_retries:
            self._dead_letter(req, reason)
        elif not self.kv.can_ever_fit(self._footprint(req)):
            self._dead_letter(req, "capacity_lost")
        else:
            # keep the original quarantine iteration across re-faults so
            # serve_recovery_iters measures fault-to-resumption end to end
            self._quarantined.setdefault(req.id, self._iter)
            req._resume_after = self._iter + (1 << (req.retries - 1))
            self.queue.append(req)   # keeps _seq: resumes ahead of peers
            self.reg.gauge("serve_streams_quarantined").set(
                len(self._quarantined))
        self._export_memory()

    def _dead_letter(self, req: Request, reason: str) -> None:
        """Terminal failure: surface the error on the request and finish
        it un-served (``status="dead_letter"``).  Neighbour streams are
        untouched — the slot and pages were already released."""
        req.status = "dead_letter"
        req.error = (f"dead-lettered after {req.retries} recoveries "
                     f"(reason: {reason})")
        req.done_at = time.perf_counter()
        self._quarantined.pop(req.id, None)
        self.reg.gauge("serve_streams_quarantined").set(
            len(self._quarantined))
        self.reg.counter("serve_dead_letter_total").inc(1, {"reason": reason})
        self.finished.append(req)

    def _watchdog(self) -> None:
        """Recover every live slot that made no progress — no token
        emitted, no chunk landed, no admission — for ``watchdog_iters``
        engine iterations (a stalled allocator grant, a need stranded by a
        chip failure, or any future wedge)."""
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            last = self._last_progress.get(slot, self._iter)
            if self._iter - last >= self.watchdog_iters:
                self._recover(slot, "watchdog")

    def lower_decode(self, abstract=None):
        """Lower the all-greedy fused decode+sample step at this engine's
        shapes, to inspect what the device runs (``.compile().as_text()``
        names the paged kernel as a ``tpu_custom_call`` on the TPU).
        ``abstract`` maps every argument leaf to a stand-in, e.g. a
        ShapeDtypeStruct placed on a described device, before lowering."""
        view = self.kv.decode_view()
        z = lambda dt: jnp.zeros(self.B, dt)                  # noqa: E731
        args = (self.params, jnp.zeros((self.B, 1), jnp.int32),
                view["layers"], view.get("page_table"), z(jnp.int32),
                z(bool), z(jnp.float32), z(jnp.int32),
                jnp.ones(self.B, jnp.float32), z(jnp.int32), z(jnp.int32),
                z(bool))
        if abstract is not None:
            args = jax.tree.map(abstract, args)
        return self._fused.lower(*args, True)

    def _dispatch_fused(self, *args):
        """The fused dispatch behind the transient-fault retry loop.  An
        injected :class:`TransientDispatchError` raises *before* the real
        call, so the donated buffers are untouched and the retry is
        idempotent; ``max_retries`` consecutive failures re-raise — a
        permanently dead dispatch path is engine-fatal, not per-stream."""
        attempt = 0
        while True:
            try:
                if self._dispatch_fail_left > 0:
                    self._dispatch_fail_left -= 1
                    raise TransientDispatchError(
                        f"injected dispatch failure (iteration {self._iter})")
                return self._fused(*args)
            except TransientDispatchError:
                attempt += 1
                self.reg.counter("serve_stream_retries_total").inc(
                    1, {"reason": "dispatch_error"})
                if attempt > self.max_retries:
                    raise
                time.sleep(min(0.001 * (1 << (attempt - 1)), 0.05))

    # ------------------------------------------------------------ prefill ----
    def _admit(self):
        """Admit queued requests into free slots under admission control,
        then prefill them — one forward dispatch per power-of-two prompt
        bucket, each covering every same-bucket request admitted this
        iteration.

        Admission is FIFO within a priority class (priority-ordered across
        classes under tenancy): a request reserves its full cache footprint
        (prompt + max_new_tokens) via ``kv.alloc`` before its slot is
        committed.  A **pool** deny stops admission in order (no
        head-of-line skipping inside a class) — after preemption, if
        enabled, has run out of lower-priority victims to evict.  A
        **quota** deny skips just that request: its tenant is at cap, but
        other tenants' requests behind it must still admit."""
        if self.chunk:
            self._admit_chunked()
            return
        free = self._free_slots()
        admitted = []                 # (slot, req, bucket, shared_len, toks)
        stop = False
        for req in self._admission_order():
            if stop:
                break
            toks = self._prefill_tokens(req)
            # image positions are embeddings, not tokens — no hash identity,
            # so VLM requests skip prefix sharing
            prefix = toks if self.img_len == 0 else None
            while True:
                if not free:
                    slot = self._preempt_for(req)
                    if slot is None:
                        self._count_deferral("pool_exhausted")
                        stop = True
                        break
                    free.append(slot)
                shared = self.kv.alloc(free[0], self._footprint(req),
                                       prefix=prefix,
                                       tenant=self._tenant(req))
                if shared is not None:
                    slot = free.pop(0)
                    self.queue.remove(req)
                    self.spans.record("serve.queue", req.submitted_at,
                                      time.perf_counter(), req.id)
                    plen = len(toks)
                    bucket = 1 << (plen - 1).bit_length()  # next power of two
                    bucket = min(bucket, self.S - self.img_len)
                    admitted.append((slot, req, bucket, shared, toks))
                    break
                if getattr(self.kv, "last_deny", None) == "quota":
                    self._count_deferral("quota_denied")
                    break             # skip this request, keep admitting
                slot = self._preempt_for(req)
                if slot is None:
                    self._count_deferral("pool_exhausted")
                    stop = True
                    break
                free.append(slot)
        # group same-bucket admissions into single forward dispatches
        for bucket in sorted({b for _, _, b, _, _ in admitted}):
            self._prefill_group(
                bucket, [a for a in admitted if a[2] == bucket])
        if admitted:
            self._export_memory()

    def _admit_chunked(self):
        """Chunked admission (priority-ordered under tenancy, FIFO within a
        class): an admitted request claims only its first chunk's pages
        (``kv.alloc_chunked`` — banker-safe incremental allocation, full
        footprint charged against its tenant's quota up front so later
        ``extend``s never quota-stall), takes a slot with the decode shield
        up, and joins ``self.prefilling``; its chunks dispatch from
        ``_run_prefill_chunks`` starting this same iteration.  Deny
        handling mirrors whole-prompt ``_admit``: pool denies preempt then
        stop, quota denies skip just the capped tenant's request."""
        free = self._free_slots()
        admitted = False
        stop = False
        for req in self._admission_order():
            if stop:
                break
            toks = self._prefill_tokens(req)
            first = min(self.chunk, len(toks))
            while True:
                if not free:
                    slot = self._preempt_for(req)
                    if slot is None:
                        self._count_deferral("pool_exhausted")
                        stop = True
                        break
                    free.append(slot)
                shared = self.kv.alloc_chunked(free[0], self._footprint(req),
                                               first, prefix=toks,
                                               tenant=self._tenant(req))
                if shared is not None:
                    slot = free.pop(0)
                    self.queue.remove(req)
                    self.spans.record("serve.queue", req.submitted_at,
                                      time.perf_counter(), req.id)
                    self.slot_req[slot] = req
                    self.active[slot] = False    # not decodable yet
                    self.kv.set_decode_shield(slot, True)
                    self.prefilling[slot] = _PrefillState(
                        req=req, shared=shared, tokens=toks)
                    self._last_progress[slot] = self._iter
                    admitted = True
                    break
                if getattr(self.kv, "last_deny", None) == "quota":
                    self._count_deferral("quota_denied")
                    break
                slot = self._preempt_for(req)
                if slot is None:
                    self._count_deferral("pool_exhausted")
                    stop = True
                    break
                free.append(slot)
        if admitted:
            self._export_memory()

    def _run_prefill_chunks(self, budget: int, skip=(), cls_spent=None):
        """Dispatch up to ``budget`` tokens of prefill chunks in stacked
        rounds: each round collects at most one chunk per mid-prefill slot
        (same-slot chunks are sequentially dependent — chunk k attends the
        pages chunk k-1 wrote) and forwards them as ONE (n, C)
        ``_chunk_step`` dispatch, the chunk-time mirror of
        ``_prefill_group``'s stacked whole-prompt dispatch.  Rounds repeat
        while budget remains and slots still have chunks, so a lone long
        prompt drains its budget exactly as the per-slot loop did.

        Collection order is admission order without tenancy (dict order);
        with tenancy, TTFT-sensitive classes collect first (priority
        order, ``_seq`` tiebreak) and a class's per-iteration token cap
        (``PriorityClass.prefill_budget``, tracked across both
        same-iteration passes via ``cls_spent``) stops batch-class prompts
        from monopolizing the global budget.  Each collected chunk first
        ``extend``s the slot's pages to cover its end — the *final* chunk
        extends to the full footprint, claiming the decode tail — and a
        chunk whose grant is not banker-safe stalls (the slot resumes in a
        later iteration once completions free pages; the round dispatches
        without it).  When a slot's last chunk lands it is unshielded,
        marked active with the sampled first token pending, and decodes in
        this same iteration's fused dispatch.  Returns (budget tokens
        consumed, slots that stalled) — ``skip`` lets the second
        same-iteration pass avoid re-stalling slots the first already
        counted."""
        landed = spent = 0
        stalled: set = set()
        cls_spent: Dict[str, int] = \
            cls_spent if cls_spent is not None else {}
        if not self.prefilling:
            return spent, stalled
        done_slots: set = set(skip)     # no further chunks this call
        while budget >= self.chunk and self.prefilling:
            order = [s for s in self.prefilling if s not in done_slots]
            if self.tenancy is not None:
                order.sort(
                    key=lambda s: (-self._prio(self.prefilling[s].req),
                                   self.prefilling[s].req._seq))
            group = []      # (slot, st, req, ptoks, end, final, dest)
            for slot in order:
                if (len(group) + 1) * self.chunk > budget:
                    break
                st = self.prefilling[slot]
                req = st.req
                ptoks = st.tokens if st.tokens is not None else req.prompt
                plen = len(ptoks)
                cname = self._class_name(req)
                cap = (self.tenancy.classes[cname].prefill_budget
                       if self.tenancy is not None else None)
                if (cap is not None
                        and cls_spent.get(cname, 0) + self.chunk > cap):
                    done_slots.add(slot)
                    continue
                if self._iter < self._stall_until.get(slot, 0):
                    # injected stall_chunk fault: behaves exactly like a
                    # banker-unsafe grant until the stall expires
                    self.reg.counter(
                        "serve_prefill_chunk_stalls_total").inc()
                    stalled.add(slot)
                    done_slots.add(slot)
                    continue
                # fully-landed shared chunks skip their forward entirely:
                # every position below st.shared is backed by pages whose
                # content already landed (device prefix sharing, or a
                # host-tier prefetch at admission), the chunk's writes
                # would all scratch-route, and its logits are consumed
                # only on the FINAL chunk — so a covered non-final chunk
                # costs zero dispatches, zero budget.  This is where the
                # prefix-hit TTFT win comes from: a fully warm prompt
                # fast-forwards to its last chunk in one pass.
                skipped = 0
                while (st.done + self.chunk < plen
                       and st.done + self.chunk <= st.shared):
                    st.done += self.chunk
                    skipped += 1
                if skipped:
                    self.reg.counter(
                        "serve_prefill_chunks_skipped_total").inc(skipped)
                    self._last_progress[slot] = self._iter
                end = min(st.done + self.chunk, plen)
                final = end == plen
                cover = self._footprint(req) if final else end
                if not self.kv.extend(slot, cover):
                    self.reg.counter(
                        "serve_prefill_chunk_stalls_total").inc()
                    stalled.add(slot)
                    done_slots.add(slot)
                    continue             # defer-and-resume, not deadlock
                dest = self.kv.chunk_dest(slot, st.done, end, self.chunk,
                                          st.shared)
                cls_spent[cname] = cls_spent.get(cname, 0) + self.chunk
                group.append((slot, st, req, ptoks, end, final, dest))
            if not group:
                break
            n = len(group)
            tokens = np.zeros((n, self.chunk), np.int32)
            dests = np.zeros((n, self.chunk), np.int32)
            rows = np.zeros((n,) + self.kv.table_row(group[0][0]).shape,
                            np.int32)
            starts = np.zeros(n, np.int32)
            lasts = np.zeros(n, np.int32)
            temps = np.zeros(n, np.float32)
            top_ks = np.zeros(n, np.int32)
            top_ps = np.ones(n, np.float32)
            seeds = np.zeros(n, np.int32)
            steps = np.zeros(n, np.int32)
            for j, (slot, st, req, ptoks, end, final, dest) in \
                    enumerate(group):
                tokens[j, :end - st.done] = ptoks[st.done:end]
                dests[j] = dest
                rows[j] = self.kv.table_row(slot)
                starts[j] = st.done
                lasts[j] = end - 1
                sp = req.sampling
                temps[j] = sp.temperature
                top_ks[j] = sp.top_k
                top_ps[j] = sp.top_p
                seeds[j] = sp.seed
                steps[j] = len(req.out_tokens)
            toks, new_layers = self._chunk_step(
                self.params, jnp.asarray(tokens),
                self.kv.state["layers"], jnp.asarray(rows),
                jnp.asarray(dests), jnp.asarray(starts),
                jnp.asarray(lasts), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
                jnp.asarray(seeds), jnp.asarray(steps))
            self.kv.update({**self.kv.state, "layers": new_layers})
            self.reg.counter("serve_prefill_chunks_total").inc(n)
            self.reg.counter("serve_prefill_dispatches_total").inc()
            with self._span("serve.prefill_wait"):
                toks = np.asarray(toks)
            for j, (slot, st, req, ptoks, end, final, dest) in \
                    enumerate(group):
                self.reg.counter("serve_prefill_tokens_total").inc(
                    end - st.done)
                budget -= self.chunk
                spent += self.chunk
                tok0 = int(toks[j])
                if tok0 == -1:
                    # the chunk attended non-finite content (a poisoned
                    # page): quarantine before the landed pages can enter
                    # the prefix registry and re-share the corruption
                    self._recover(slot, "nonfinite_logits")
                    done_slots.add(slot)
                    continue
                self.kv.register_landed(slot, ptoks, end)
                landed += end - st.done
                st.done = end
                self._last_progress[slot] = self._iter
                if final:
                    del self.prefilling[slot]
                    self.kv.set_decode_shield(slot, False)
                    sp = req.sampling
                    self.slot_pos[slot] = self.img_len + len(ptoks)
                    self.next_token[slot] = tok0
                    self.active[slot] = True
                    self.temps[slot] = sp.temperature
                    self.top_ks[slot] = sp.top_k
                    self.top_ps[slot] = sp.top_p
                    self.seeds[slot] = sp.seed
        if landed:
            self._export_memory()
        return spent, stalled

    def _prefill_group(self, bucket: int, group):
        """One ``lm.forward`` dispatch for every admitted request in this
        prefill bucket: stacked (n, bucket) tokens in, per-request first
        tokens and the updated K/V storage out.  A recompute-resumed
        request's token array is prompt + generated-so-far; its first
        token re-samples at stream step ``len(out_tokens)``."""
        n = len(group)
        paged = type(self.kv).backend == "paged"
        tokens = np.zeros((n, bucket), np.int32)
        last_idx = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        top_ps = np.ones(n, np.float32)
        seeds = np.zeros(n, np.int32)
        steps = np.zeros(n, np.int32)
        imgs = np.zeros((n, self.img_len, self.lm.cfg.d_model), np.float32) \
            if self.img_len else None
        block_len = self.img_len + bucket
        write_spec = (np.zeros((n, block_len), np.int32) if paged
                      else np.zeros(n, np.int32))
        for j, (slot, req, _, shared, ptoks) in enumerate(group):
            plen = len(ptoks)
            tokens[j, :plen] = ptoks
            last_idx[j] = self.img_len + plen - 1
            sp = req.sampling
            temps[j], top_ks[j] = sp.temperature, sp.top_k
            top_ps[j], seeds[j] = sp.top_p, sp.seed
            steps[j] = len(req.out_tokens)
            if self.img_len and req.img_embeds is not None:
                imgs[j] = req.img_embeds
            if paged:
                write_spec[j] = self.kv.prefill_dest(
                    slot, block_len, self.img_len + plen, shared)
            else:
                write_spec[j] = slot
        img = (jnp.asarray(imgs, jax.tree.leaves(self.kv.state)[0].dtype)
               if self.img_len else None)
        toks, new_layers = self._prefill(
            self.params, jnp.asarray(tokens), img, self.kv.state["layers"],
            jnp.asarray(write_spec), jnp.asarray(last_idx),
            jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
            jnp.asarray(seeds), jnp.asarray(steps))
        self.kv.update({**self.kv.state, "layers": new_layers})
        with self._span("serve.prefill_wait"):
            toks = np.asarray(toks)
        for j, (slot, req, _, _, ptoks) in enumerate(group):
            sp = req.sampling
            self.slot_req[slot] = req
            self.slot_pos[slot] = self.img_len + len(ptoks)
            self.next_token[slot] = int(toks[j])
            self.active[slot] = True
            self.temps[slot] = sp.temperature
            self.top_ks[slot] = sp.top_k
            self.top_ps[slot] = sp.top_p
            self.seeds[slot] = sp.seed
            self._last_progress[slot] = self._iter
            self.reg.counter("serve_prefill_tokens_total").inc(len(ptoks))
        self.reg.counter("serve_prefill_dispatches_total").inc()
        # buckets fixed by the eager _declare_metrics registration
        self.reg.histogram("serve_prefill_batch_size").observe(n)
        for j, (slot, _, _, _, _) in enumerate(group):
            if int(toks[j]) == -1:
                # non-finite logits out of the prefill forward itself:
                # quarantine this slot before it can decode
                self._recover(slot, "nonfinite_logits")

    # ------------------------------------------------------------- decode ----
    def step(self) -> bool:
        """One engine iteration (``_step``), wrapped with the fault clock:
        scheduled faults fire first (events whose preconditions are not
        met yet carry forward), the watchdog then recovers any stream that
        made no progress for ``watchdog_iters`` iterations, debug mode
        re-verifies the allocator invariants, and — when an
        ``AlertManager`` is wired in — the serve-path light health checks
        and alert rules run every ``health_every`` iterations."""
        with self._span("serve.step"):
            if self.fault_plan is not None:
                self._pending_faults.extend(
                    self.fault_plan.events_at(self._iter))
                if self._pending_faults:
                    self._apply_faults()
            live = self._step()
            if self.watchdog_iters:
                self._watchdog()
            if self.verify_cache and hasattr(self.kv, "verify"):
                self.kv.verify()
            if (self.alerts is not None
                    and self._iter % self.health_every == 0):
                from repro.core.health import serve_light_checks
                serve_light_checks(self)
                self.alerts.evaluate()
            self._iter += 1
            return live

    def _step(self):
        """One engine iteration: admit (+ up to one budget's worth of
        prefill chunks), then **one** fused decode+sample dispatch for all
        active slots at their own positions.

        ``serve_decode_stall_iters`` counts iterations where live decode
        streams waited on more prefill tokens than the per-iteration budget
        allows — zero by construction with chunking on; in whole-prompt mode
        there is no budget, so every prefill dispatched alongside live
        decode streams counts as a stall."""
        streams_waiting = bool(np.any(self.active))
        pf0 = self.reg.counter("serve_prefill_tokens_total").get()
        if self.chunk:
            # resume in-flight chunked prefills BEFORE admitting new work:
            # a stalled slot gets first claim on pages freed since last
            # iteration, so sustained short-request traffic can slow a
            # mid-prefill long prompt but never starve it
            cls_spent: Dict[str, int] = {}
            with self._span("serve.prefill"):
                spent, stalled = self._run_prefill_chunks(
                    self.budget, cls_spent=cls_spent)
            with self._span("serve.admit"):
                self._admit()
            if spent < self.budget:
                # leftover budget covers a fresh admission's first chunk in
                # the same iteration (skip already-stalled slots: the pages
                # they need did not appear mid-iteration; per-class caps
                # carry over via cls_spent)
                with self._span("serve.prefill"):
                    self._run_prefill_chunks(self.budget - spent,
                                             skip=stalled,
                                             cls_spent=cls_spent)
        else:
            with self._span("serve.admit"):
                self._admit()
        pf_tokens = self.reg.counter("serve_prefill_tokens_total").get() - pf0
        if streams_waiting and pf_tokens > (self.budget if self.chunk else 0):
            self.reg.counter("serve_decode_stall_iters").inc()
        active_idx = [i for i, r in enumerate(self.slot_req)
                      if r is not None and i not in self.prefilling]
        if not active_idx:
            # mid-prefill slots are still work in flight
            return bool(self.prefilling)
        with self._span("serve.decode_dispatch"):
            # per-slot sample-step index: the token being sampled now is
            # out_tokens[len]+1 deep in the request's stream (the pending
            # token, sampled earlier, is #len and gets emitted this
            # iteration)
            steps = np.zeros(self.B, np.int32)
            for i in active_idx:
                steps[i] = len(self.slot_req[i].out_tokens) + 1
            # inactive slots decode at scratch position 0: their masked
            # scatter lands in storage the next prefill rewrites (contiguous
            # row 0) or in the scratch page (paged), never in live data
            positions = np.where(self.active,
                                 np.minimum(self.slot_pos, self.S - 1), 0)
            all_greedy = bool(np.all(self.temps[self.active] <= 0.0))
            view = self.kv.decode_view()
            poison = np.zeros(self.B, bool)
            if self._poison_slots:
                poison[sorted(self._poison_slots)] = True
                self._poison_slots.clear()
            sampled, new_layers = self._dispatch_fused(
                self.params, jnp.asarray(self.next_token[:, None]),
                view["layers"], view.get("page_table"),
                jnp.asarray(positions), jnp.asarray(self.active),
                jnp.asarray(self.temps), jnp.asarray(self.top_ks),
                jnp.asarray(self.top_ps), jnp.asarray(self.seeds),
                jnp.asarray(steps), jnp.asarray(poison), all_greedy)
            self.kv.update({**view, "layers": new_layers})
        self.reg.counter("serve_decode_dispatches_total").inc()
        self.reg.counter("serve_iterations_total").inc()
        with self._span("serve.decode_wait"):
            sampled = np.asarray(sampled)     # the one (B,) host transfer
        with self._span("serve.emit"):
            self._emit(active_idx, sampled)
        return True

    def _emit(self, active_idx: List[int], sampled: np.ndarray) -> None:
        """Emit each decoding slot's pending token, finish and free the
        requests that are done, and hold this step's ``sampled`` tokens as
        the next pending ones (a ``-1`` sentinel recovers its slot)."""
        now = time.perf_counter()
        freed = False
        for i in active_idx:
            req = self.slot_req[i]
            tok = int(self.next_token[i])
            req.out_tokens.append(tok)
            if req.first_token_at is None:
                req.first_token_at = now
                self.reg.histogram("serve_ttft_seconds").observe(
                    now - req.submitted_at)
                if self.tenancy is not None:
                    self.reg.histogram("serve_class_ttft_seconds").observe(
                        now - req.submitted_at,
                        {"class": self._class_name(req)})
            elif self.tenancy is not None and req.last_token_at is not None:
                self.reg.histogram("serve_class_itl_seconds").observe(
                    now - req.last_token_at, {"class": self._class_name(req)})
            req.last_token_at = now
            self.slot_pos[i] += 1
            self._last_progress[i] = self._iter
            if req.id in self._quarantined:
                # the recovered stream resumed emitting: recovery complete
                self.reg.histogram("serve_recovery_iters").observe(
                    self._iter - self._quarantined.pop(req.id))
                self.reg.gauge("serve_streams_quarantined").set(
                    len(self._quarantined))
            done = (len(req.out_tokens) >= req.max_new_tokens
                    or tok == req.eos_id
                    or self.slot_pos[i] >= self.S)
            if done:
                req.done_at = now
                req.status = "completed"
                self.reg.counter("serve_tokens_total").inc(
                    len(req.out_tokens))
                self.reg.histogram("serve_latency_seconds").observe(
                    now - req.submitted_at)
                self.finished.append(req)
                self.slot_req[i] = None
                self.active[i] = False
                self.kv.free(i)
                freed = True
            elif int(sampled[i]) == -1:
                # the non-finite guard tripped on THIS step's logits
                # (injected NaN or a poisoned page read).  The pending
                # token just emitted came from last step's clean logits;
                # the corrupt sample is discarded and re-drawn at the same
                # stream step by the resume prefill — bitwise either way.
                self._recover(i, "nonfinite_logits")
            else:
                self.next_token[i] = sampled[i]
        if freed:
            self._export_memory()

    def _export_memory(self):
        st = self.kv.memory_stats()
        if self.tenancy is not None:
            for name in self.tenancy.tenants:
                self.reg.gauge("serve_tenant_pages_in_use").set(
                    st.tenant_pages.get(name, 0), {"tenant": name})
        self.reg.gauge("serve_kv_pages_in_use").set(st.pages_in_use)
        self.reg.gauge("serve_kv_bytes_reserved").set(st.bytes_reserved)
        self.reg.gauge("serve_kv_pages_shared").set(st.pages_shared)
        self.reg.gauge("serve_kv_bytes_per_chip").set(st.bytes_per_chip)
        quant = st.kv_dtype == "int8"
        self.reg.gauge("serve_kv_quant_enabled").set(int(quant))
        self.reg.gauge("serve_kv_quant_scale_bytes").set(st.bytes_scales)
        saved = 0
        if quant:
            from repro.serve.kvcache import page_kv_bytes
            dense_total = page_kv_bytes(
                self.lm.cfg, st.page_size, self.kv.dtype) \
                * (st.pages_total + 1)
            saved = dense_total - st.bytes_total
        self.reg.gauge("serve_kv_quant_bytes_saved").set(saved)
        # host-RAM page tier: publish the store's monotonic totals as
        # counter deltas (counters are engine-owned; the store may be
        # shared across engines, so each engine syncs from its own mark)
        self.reg.gauge("serve_host_pages_in_use").set(st.host_pages_in_use)
        store = getattr(self.kv, "store", None)
        if store is not None:
            totals = store.stats()
            for metric, key in (
                    ("serve_prefix_store_hits_total", "hits"),
                    ("serve_prefix_store_misses_total", "misses"),
                    ("serve_host_evictions_total", "evictions"),
                    ("serve_host_offload_bytes_total", "offload_bytes"),
                    ("serve_host_prefetch_bytes_total", "prefetch_bytes")):
                delta = totals[key] - self._host_synced.get(key, 0)
                if delta:
                    self.reg.counter(metric).inc(delta)
                self._host_synced[key] = totals[key]

    def run_until_drained(self, max_iters: int = 10_000,
                          on_stuck: str = "raise") -> List[Request]:
        """Step until every submitted request reaches a terminal state
        (completed or dead-lettered).

        Exhausting ``max_iters`` with work still in flight no longer
        returns silently: every surviving request is flagged
        ``status="stuck"`` with its diagnostic on ``Request.error``, and
        ``on_stuck="raise"`` (default) raises :class:`EngineStuckError`
        naming the wedged slots and their last-progress iteration, while
        ``on_stuck="status"`` returns the survivors appended to
        ``finished`` so drivers can report per-stream outcomes."""
        assert on_stuck in ("raise", "status"), on_stuck
        for _ in range(max_iters):
            if not self.step() and not self.queue:
                return self.finished
        if not self.queue and all(r is None for r in self.slot_req):
            return self.finished
        stuck: List[Request] = []
        what: List[str] = []
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            last = self._last_progress.get(slot)
            lp = f"iteration {last}" if last is not None else "never"
            stuck.append(req)
            what.append(f"request {req.id} wedged in slot {slot} "
                        f"(last progress: {lp})")
        for req in self.queue:
            stuck.append(req)
            what.append(f"request {req.id} still queued")
        for req, w in zip(stuck, what):
            req.status = "stuck"
            req.error = f"undrained after {max_iters} iterations ({w})"
        if on_stuck == "status":
            return self.finished + stuck
        raise EngineStuckError(
            f"engine not drained after {max_iters} iterations: "
            + "; ".join(what), stuck)
