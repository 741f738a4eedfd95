"""Batched serving driver (watsonx.ai-style inference cluster role).

Drives the fused ragged continuous-batching engine: one jitted
decode+sample dispatch per iteration regardless of slot positions, batched
group prefill, on-device sampling, and a pluggable KV cache — paged
(page-table indirection + prefix sharing + admission control) by default,
contiguous dense rows via ``--cache-backend contiguous``.

    python -m repro.launch.serve --arch qwen3-4b --reduced --requests 16
    python -m repro.launch.serve --max-seq 1024   # published widths, bf16
    python -m repro.launch.serve --cache-backend paged --page-size 8 \
        --num-pages 48   # tight pool: watch admissions defer, not OOM
    python -m repro.launch.serve --decode-impl pallas   # page-table-walking
        # flash-decode kernel: no gathered dense KV transient per step
    python -m repro.launch.serve --prefill-chunk 16     # chunked prefill:
        # long prompts interleave with decode, no stream ever stalls on
        # more than one chunk of prefill compute
    python -m repro.launch.serve --kv-dtype int8        # int8 KV pages:
        # quantize-on-write, dequant-on-read — same pool HBM holds ~2x
        # the concurrent streams (vs bf16; ~3.8x vs fp32)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.serve --mesh 4   # sharded paged serving:
        # pools pinned P/4 pages per chip, partial-softmax merged reads
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.serve --mesh dp=2,model=4   # 2-D mesh:
        # pool shards P/4 over the model axis, dispatch batch dims shard
        # over 2 DP replicas, merge runs per replica
    python -m repro.launch.serve \
        --tenants chat=interactive,bulk=batch --quota bulk=24 \
        # multi-tenant SLO serving: priority-ordered admission, per-tenant
        # page quotas in the banker check, preemptive page eviction —
        # interactive traffic admits ahead of (and can preempt) batch
    python -m repro.launch.serve --fault-plan nan_logits@5,poison_page@9 \
        --watchdog-iters 8 --verify-cache   # fault-tolerant serving:
        # injected faults are detected by the fused step's non-finite
        # guard, quarantined streams resume *bitwise* via recompute-on-
        # resume prefill, and the summary reports per-stream outcomes
    python -m repro.launch.serve --host-pages 64 --prefix-store \
        --prefill-chunk 16   # hierarchical KV: cold shared prefixes
        # spill to a 64-page host-RAM tier on their last free and
        # prefetch back on a hash-hit instead of recomputing prefill;
        # --prefix-store runs a warmup pass through a second engine
        # sharing one persistent store, so the reported pass serves warm
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import CONFIGS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM
from repro.serve import Request, SamplingParams, ServeEngine


def _parse_mesh(spec: str):
    """Parse --mesh: 'N' -> (0, N) 1-D pool mesh; 'DxM' / 'D,M' /
    'dp=D,model=M' -> (D, M) 2-D batch x pages mesh.  '0' -> (0, 0)."""
    spec = spec.strip().lower()
    if "=" in spec:
        kv = dict(part.split("=", 1) for part in spec.split(","))
        unknown = set(kv) - {"dp", "model"}
        if unknown:
            raise SystemExit(f"--mesh: unknown axes {sorted(unknown)} "
                             "(expected dp=D,model=M)")
        return int(kv.get("dp", 1)), int(kv["model"])
    for sep in ("x", ","):
        if sep in spec:
            d, m = spec.split(sep, 1)
            return int(d), int(m)
    return 0, int(spec)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=sorted(CONFIGS))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's reduced() twin in float32 "
                         "(CPU-sized); default: the published widths with "
                         "weights in the compute dtype")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 => greedy; sampling runs on device either way")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--cache-backend", default="paged",
                    choices=["paged", "contiguous"])
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical page pool size (default: dense-equivalent"
                         " capacity); smaller pools defer admissions")
    ap.add_argument("--no-prefix-sharing", action="store_true")
    ap.add_argument("--decode-impl", default="gather",
                    choices=["gather", "pallas"],
                    help="paged page-table resolution per decode step: "
                         "'gather' (XLA fallback — materializes a "
                         "dense-equivalent KV view, transient grows with "
                         "batch x pages) or 'pallas' (page-table-walking "
                         "flash-decode kernel, O(page) transient; interpret "
                         "mode on CPU, Mosaic on TPU).  Ignored by "
                         "--cache-backend contiguous")
    ap.add_argument("--kv-dtype", default="native",
                    choices=["native", "int8"],
                    help="page-pool storage format: 'native' (the model "
                         "dtype) or 'int8' — pages stored int8 with "
                         "per-row fp32 scales, quantized on write and "
                         "dequantized on read (in-register inside the "
                         "pallas kernel; in the gathered view under "
                         "'gather').  Requires --cache-backend paged")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="C",
                    help="chunked prefill: split admitted prompts into "
                         "C-token chunks interleaved with fused decode "
                         "steps (chunk k attends the pages chunks 0..k-1 "
                         "wrote), claiming pages chunk-by-chunk so a long "
                         "prompt admits into a pool whose free pages cover "
                         "only its first chunk.  0 = whole-prompt prefill.  "
                         "Requires --cache-backend paged; composes with "
                         "--mesh (chunks route through the unified sharded "
                         "write/attend primitive)")
    ap.add_argument("--prefill-budget", type=int, default=0, metavar="T",
                    help="max prefill tokens per engine iteration "
                         "(>= one chunk; default: exactly one chunk) — the "
                         "bound on how long any decode iteration can wait "
                         "on prefill compute")
    ap.add_argument("--mesh", default="0", metavar="N|DxM",
                    help="sharded paged serving over an inference mesh.  "
                         "'N': the page pool's kv_pages dim shards P/N "
                         "pages per chip (pool HBM scales down with N) and "
                         "every dispatch — fused decode, whole-prompt "
                         "prefill writes, chunked prefill — runs under the "
                         "unified shard_map primitive: per-chip "
                         "mode='drop' local pool writes, local-window "
                         "attention partials, one psum-style partial-"
                         "softmax merge.  'DxM' / 'D,M' / 'dp=D,model=M': "
                         "a 2-D batch x pages mesh — the pool shards P/M "
                         "over the model axis (replicated across DP), "
                         "dispatch batch dims shard over D replicas, and "
                         "the merge runs per DP replica.  Requires D*M "
                         "visible devices (on CPU: XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=K) and "
                         "--cache-backend paged.  0 = single-device")
    ap.add_argument("--mesh-axis", default="model",
                    help="mesh axis name the kv_pages dim maps onto "
                         "(default: model, matching the kv_pages sharding "
                         "rule in repro.parallel.sharding)")
    ap.add_argument("--tenants", default="", metavar="N=CLS,...",
                    help="multi-tenant SLO serving: comma-separated "
                         "name=class tenant table (classes: interactive — "
                         "admitted first, never preempted — and batch; "
                         "class defaults to batch when omitted).  Requests "
                         "round-robin over the tenants.  Empty = "
                         "single-tenant FIFO engine")
    ap.add_argument("--quota", default="", metavar="N=PAGES,...",
                    help="per-tenant KV page quotas (name=pages,...): a "
                         "tenant at cap has its admissions quota-denied — "
                         "skipped, not queue-blocking — until its slots "
                         "free pages.  Requires --tenants and the paged "
                         "backend")
    ap.add_argument("--priority", dest="priority", action="store_true",
                    default=True,
                    help="preempt lowest-priority running decodes when a "
                         "higher class cannot admit (pages evicted, request "
                         "re-queued for recompute-on-resume prefill; "
                         "default on)")
    ap.add_argument("--no-priority", dest="priority", action="store_false",
                    help="disable preemption: quotas and priority-ordered "
                         "admission only")
    ap.add_argument("--fault-plan", default="", metavar="PLAN",
                    help="deterministic fault injection: comma-separated "
                         "kind@iteration[:slot=N][:chip=N][:page=N][:dur=N] "
                         "events (kinds: nan_logits, poison_page, "
                         "chip_failure, stall_chunk, dispatch_error), e.g. "
                         "'nan_logits@5,chip_failure@12:chip=1'.  Faulted "
                         "streams are quarantined and resume bitwise via "
                         "recompute-on-resume prefill")
    ap.add_argument("--watchdog-iters", type=int, default=0, metavar="N",
                    help="per-stream progress watchdog: recover any live "
                         "slot that emits no token / lands no chunk for N "
                         "engine iterations (0 = off)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="recoveries a stream may consume before it "
                         "dead-letters (error surfaced on the request; "
                         "neighbour streams unaffected)")
    ap.add_argument("--verify-cache", action="store_true",
                    help="debug mode: run the PagedCache.verify() "
                         "invariant sanitizer (refcounts, free lists, page "
                         "tables, quotas) after every engine iteration")
    ap.add_argument("--host-pages", type=int, default=0, metavar="N",
                    help="host-RAM page tier: cold shared prefix pages "
                         "spill to N pinned host page buffers when their "
                         "last device reference drops, and admissions that "
                         "hash-hit the stored prefix prefetch the pages "
                         "back instead of recomputing prefill (with "
                         "--prefill-chunk, fully-covered chunks skip their "
                         "forward entirely).  Requires --cache-backend "
                         "paged and prefix sharing.  0 = off")
    ap.add_argument("--prefix-store", action="store_true",
                    help="persistent prefix store demo: serve the workload "
                         "through a warmup engine first, then rebuild the "
                         "engine REUSING the same store — the reported "
                         "pass admits against a warm host tier, showing "
                         "cross-engine prefix persistence.  Requires "
                         "--host-pages")
    return ap


def build_lm(args) -> LM:
    """The model for ``--arch``.  Serving holds no master weights, so
    parameters live in the compute dtype (bf16 at published widths: 8 GB
    for qwen3-4b, which fits one 16 GB chip with room for KV pages);
    ``--reduced`` serves the float32 reduced() twin."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    return LM(dataclasses.replace(cfg, param_dtype=cfg.dtype))


def build_model(args, mesh=None):
    """(cfg, lm, params) with random weights from a fixed seed.  Under a
    ``mesh`` the weights are made replicated on every chip of it, so no
    dispatch copies them."""
    lm = build_lm(args)
    init = jax.jit(lm.init, out_shardings=(
        None if mesh is None else NamedSharding(mesh, PartitionSpec())))
    return lm.cfg, lm, init(jax.random.key(0))


def build_mesh(args, devices=None):
    """(mesh, dp_axis) for ``--mesh``; (None, None) when single-device."""
    dp, nkv = _parse_mesh(args.mesh)
    if not nkv:
        return None, None
    from repro.parallel.mesh import make_mesh
    if dp:
        # 2-D batch x pages mesh: dp axis named 'data' (matching the
        # batch sharding rule in repro.parallel.sharding)
        return make_mesh((dp, nkv), ("data", args.mesh_axis),
                         devices), "data"
    return make_mesh((nkv,), (args.mesh_axis,), devices), None


def build_engine(lm, params, args, *, mesh=None, dp_axis=None,
                 registry=None, tenancy=None, fault_plan=None, alerts=None,
                 prefix_store=None) -> ServeEngine:
    """The ServeEngine the command line describes."""
    return ServeEngine(lm, params, args.max_batch, args.max_seq,
                       registry=registry,
                       cache_backend=args.cache_backend,
                       page_size=args.page_size,
                       num_pages=args.num_pages,
                       prefix_sharing=not args.no_prefix_sharing,
                       decode_impl=args.decode_impl, mesh=mesh,
                       kv_axis=args.mesh_axis, dp_axis=dp_axis,
                       prefill_chunk=args.prefill_chunk,
                       prefill_budget=args.prefill_budget,
                       kv_dtype=args.kv_dtype, tenancy=tenancy,
                       fault_plan=fault_plan,
                       watchdog_iters=args.watchdog_iters,
                       max_retries=args.max_retries,
                       verify_cache=args.verify_cache,
                       alerts=alerts, prefix_store=prefix_store)


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.prefix_store and not args.host_pages:
        raise SystemExit("--prefix-store persists the host tier across "
                         "engines; size it with --host-pages N")
    enable_compile_cache()
    mesh, dp_axis = build_mesh(args)
    cfg, lm, params = build_model(args, mesh)
    tenancy = None
    if args.tenants:
        from repro.serve import TenancyConfig
        tenancy = TenancyConfig.parse(args.tenants, args.quota,
                                      preemption=args.priority)
    elif args.quota:
        raise SystemExit("--quota requires --tenants")
    fault_plan = None
    if args.fault_plan:
        from repro.serve import FaultPlan
        fault_plan = FaultPlan.parse(args.fault_plan)
    from repro.core.alerts import (AlertManager, DEFAULT_RULES, LogSink,
                                   SERVE_RULES, SlackSink)
    from repro.core.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    slack = SlackSink()
    alerts = AlertManager(reg, sinks=[slack, LogSink()],
                          rules=DEFAULT_RULES + SERVE_RULES)
    store = None
    if args.host_pages:
        from repro.serve import PrefixStore
        store = PrefixStore(args.host_pages)

    def engine_for(registry, with_alerts=True):
        return build_engine(lm, params, args, mesh=mesh, dp_axis=dp_axis,
                            registry=registry, tenancy=tenancy,
                            fault_plan=fault_plan,
                            alerts=alerts if with_alerts else None,
                            prefix_store=store)

    tenant_names = sorted(tenancy.tenants) if tenancy else []

    def submit_all(engine):
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size,
                                  rng.integers(4, 12)).astype(np.int32)
            engine.submit(Request(
                i, prompt, max_new_tokens=args.new_tokens,
                tenant=(tenant_names[i % len(tenant_names)]
                        if tenant_names else "default"),
                sampling=SamplingParams(
                    temperature=args.temperature,
                    top_k=args.top_k, top_p=args.top_p, seed=i)))

    if args.prefix_store:
        # warmup engine: same workload, own registry, SAME store — its
        # freed prefixes offload to host and survive the engine teardown
        warm = engine_for(MetricsRegistry(), with_alerts=False)
        t0 = time.perf_counter()
        submit_all(warm)
        warm.run_until_drained(on_stuck="status")
        cold_ttft = warm.reg.histogram(
            "serve_ttft_seconds").quantile(0.5) * 1e3
        print(f"warmup pass: {time.perf_counter()-t0:.1f}s, TTFT p50 "
              f"{cold_ttft:.0f}ms, {store.pages_in_use()} prefix pages "
              f"now host-resident; rebuilding engine on the warm store")
        del warm

    eng = engine_for(reg)
    t0 = time.perf_counter()
    submit_all(eng)
    done = eng.run_until_drained(on_stuck="status")
    wall = time.perf_counter() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    iters = eng.reg.counter("serve_iterations_total").get()
    decode = eng.reg.counter("serve_decode_dispatches_total").get()
    prefill = eng.reg.counter("serve_prefill_dispatches_total").get()
    by_status = {}
    for r in done:
        by_status.setdefault(r.status, []).append(r)
    outcome = ", ".join(f"{len(rs)} {s}" for s, rs in sorted(by_status.items()))
    print(f"served {len(done)} requests ({outcome}), {total_tokens} tokens "
          f"in {wall:.1f}s ({total_tokens/wall:.1f} tok/s)")
    print(f"device calls: {decode:.0f} fused decode+sample "
          f"({decode/max(iters,1):.2f}/iteration) + {prefill:.0f} prefill")
    print(f"TTFT p50 {eng.reg.histogram('serve_ttft_seconds').quantile(0.5)*1e3:.0f}ms "
          f"p95 {eng.reg.histogram('serve_ttft_seconds').quantile(0.95)*1e3:.0f}ms")
    print(f"latency p50 "
          f"{eng.reg.histogram('serve_latency_seconds').quantile(0.5):.2f}s")
    st = eng.kv.memory_stats()
    deferred = eng.reg.counter("serve_admission_deferred_total").get()
    pf_h = eng.reg.histogram("serve_prefill_batch_size")
    print(f"kv cache [{st.backend}]: {st.bytes_total/1e6:.2f} MB pinned"
          + (f", {st.pages_total} pages of {st.page_size}"
             if st.backend == "paged" else "")
          + (f", sharded over {st.mesh_chips} chips "
             f"({st.bytes_per_chip/1e6:.2f} MB/chip)"
             if st.mesh_chips > 1 else "")
          + f"; admissions deferred={deferred:.0f}; "
          f"prefill batch p50={pf_h.quantile(0.5):.0f}")
    if st.backend == "paged":
        transient = eng.reg.gauge("serve_decode_transient_bytes").get()
        print(f"decode impl [{eng.kv.decode_impl}]: per-step KV read "
              f"transient {transient/1e3:.1f} kB/layer")
    if st.backend == "paged" and st.kv_dtype == "int8":
        saved = eng.reg.gauge("serve_kv_quant_bytes_saved").get()
        per_chip = (f" ({st.bytes_scales_per_chip/1e3:.1f} kB/chip)"
                    if st.mesh_chips > 1 else "")
        print(f"kv quant [int8]: {st.bytes_scales/1e3:.1f} kB scales"
              f"{per_chip}, "
              f"{saved/1e6:.2f} MB saved vs {np.dtype(eng.kv.dtype).name} "
              f"pages "
              f"({(st.bytes_total + saved)/max(st.bytes_total, 1):.2f}x "
              f"positions per byte)")
    if args.host_pages:
        hits = eng.reg.counter("serve_prefix_store_hits_total").get()
        misses = eng.reg.counter("serve_prefix_store_misses_total").get()
        off_b = eng.reg.counter("serve_host_offload_bytes_total").get()
        pre_b = eng.reg.counter("serve_host_prefetch_bytes_total").get()
        print(f"host tier [{args.host_pages} pages"
              + (", persistent store" if args.prefix_store else "")
              + f"]: {st.host_pages_in_use} resident "
              f"({st.host_bytes/1e6:.2f} MB host RAM), "
              f"{hits:.0f} page hits / {misses:.0f} misses, "
              f"{off_b/1e6:.2f} MB offloaded, {pre_b/1e6:.2f} MB "
              f"prefetched")
        if args.prefill_chunk:
            skipped = eng.reg.counter(
                "serve_prefill_chunks_skipped_total").get()
            print(f"  {skipped:.0f} fully-shared chunks skipped their "
                  f"forward")
    if args.prefill_chunk:
        chunks = eng.reg.counter("serve_prefill_chunks_total").get()
        stalls = eng.reg.counter("serve_prefill_chunk_stalls_total").get()
        stall_it = eng.reg.counter("serve_decode_stall_iters").get()
        print(f"chunked prefill [{args.prefill_chunk} tok/chunk, budget "
              f"{eng.budget}]: {chunks:.0f} chunks, {stalls:.0f} page-grant "
              f"stalls, decode stall iters={stall_it:.0f}")
    if tenancy is not None:
        preempt = eng.reg.counter("serve_preemptions_total").get()
        qdeny = eng.reg.counter("serve_quota_denied_total").get()
        print(f"tenancy [{len(tenancy.tenants)} tenants, preemption "
              f"{'on' if tenancy.preemption else 'off'}]: "
              f"{preempt:.0f} preemptions, {qdeny:.0f} quota denies")
        for name in tenant_names:
            spec = tenancy.spec(name)
            peak = eng.reg.gauge("serve_tenant_pages_in_use").get(
                {"tenant": name})
            quota = (f"/{spec.page_quota}" if spec.page_quota is not None
                     else "")
            print(f"  tenant {name} [{spec.cls}]: pages {peak:.0f}{quota}")
        for cls in sorted({t.cls for t in tenancy.tenants.values()}):
            h = eng.reg.histogram("serve_class_ttft_seconds")
            if h.count({"class": cls}):
                print(f"  class {cls}: TTFT p50 "
                      f"{h.quantile(0.5, {'class': cls})*1e3:.0f}ms p99 "
                      f"{h.quantile(0.99, {'class': cls})*1e3:.0f}ms")
    injected = sum(v for _, v in eng.reg.counter(
        "serve_faults_injected_total").labels_values())
    recovered = sum(v for _, v in eng.reg.counter(
        "serve_stream_retries_total").labels_values())
    dead_total = sum(v for _, v in eng.reg.counter(
        "serve_dead_letter_total").labels_values())
    if args.fault_plan or args.watchdog_iters or injected or recovered:
        rec_h = eng.reg.histogram("serve_recovery_iters")
        rec_p50 = (f", recovery p50 {rec_h.quantile(0.5):.0f} iters"
                   if rec_h.count() else "")
        print(f"faults: {injected:.0f} injected, {recovered:.0f} stream "
              f"retries, {dead_total:.0f} dead-lettered{rec_p50}")
    # per-stream terminal outcomes: operators see recovery results without
    # scraping metrics — dead-letter/stuck always shown, retried streams too
    for r in sorted(done, key=lambda r: r.id):
        if r.status != "completed" or r.retries or r.preemptions:
            detail = f"  request {r.id} [{r.status}]: " \
                     f"{len(r.out_tokens)} tokens, {r.retries} retries, " \
                     f"{r.preemptions} preemptions"
            if r.error:
                detail += f" — {r.error}"
            print(detail)
    if slack.messages:
        print("alerts fired:")
        for m in slack.messages:
            print(f"  {m}")
    short = [r for r in done if r.status != "completed"]
    if short:
        raise SystemExit(f"{len(short)} of {len(done)} requests did not "
                         f"complete: " + ", ".join(
                             f"{r.id} [{r.status}]" for r in short))


if __name__ == "__main__":
    main()
