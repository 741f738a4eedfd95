#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU.

    python chip_smoke.py             # one chip: qwen3-4b at published widths
    python chip_smoke.py --chips 4   # sharded paged serving on four chips

One process, no children.  It builds qwen3-4b (36 layers, d_model 2560,
32 heads / 8 KV heads, vocab 151936, bf16 weights drawn from a seed) through
the same builders as ``python -m repro.launch.serve`` and serves 8 seeded
requests: prompts of 129-512 tokens, 32 new tokens each, greedy sampling,
the paged cache, 4 slots of 1024 positions.

One chip runs three phases:
  a  the default engine: gather decode, whole-prompt prefill;
  b  ``--decode-impl pallas``, whose compiled decode step must hold the
     paged kernel as a ``tpu_custom_call``;
  c  ``--prefill-chunk 128``.
``--chips 4`` runs a single-device engine on ``jax.devices()[0]``, then
``--mesh 4`` and ``--mesh dp=2,model=2`` on the same requests, and nothing
else.

Checks, each fatal: every request completes with 32 tokens; no logits row
was non-finite (the engine's in-dispatch guard would have retried it);
every sampled token of every phase, the first included, is the argmax of a
plain ``lm.forward`` over the prompt and the stream so far, unless the
top logits tie within 4 units in the last place of bf16 (the logits'
dtype), where the token must be one of the tied; and each sharded pool
holds 1/N of its pages per chip, N the chips of its pages axis.  Where a
phase's streams diverge from the first phase's, the step is reported.

It needs a TPU and exits non-zero without one.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed check prints no such line.  Tok/s figures include compilation:
they are smoke readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
N_REQUESTS = 8
NEW_TOKENS = 32
MAX_BATCH = 4
MAX_SEQ = 1024
MAX_PROMPT = 512
PREFILL_CHUNK = 128
# Two correct paths through 36 bf16 layers round differently, and the
# logits are bf16: a sampled token may lose to the reference argmax by a
# few units in the last place of the top logit, never by more.
TIE_ULPS = 4


def require_tpu() -> None:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX backend is "
                         f"{backend!r}); this smoke run needs the chip and "
                         "has no CPU fallback")


# ----------------------------------------------------------- compile clock --

class CompileClock:
    """Seconds of backend compilation and persistent-cache hits while the
    ``with`` block runs, read from JAX's own monitoring events."""

    def __init__(self):
        self.seconds, self.hits = 0.0, 0

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def mark(self):
        return self.seconds, self.hits


# ------------------------------------------------------------------ phases --

def serve_args(*extra: str, reduced: bool = False, max_seq: int = MAX_SEQ,
               new_tokens: int = NEW_TOKENS):
    """The ``launch/serve.py`` namespace for these flags."""
    from repro.launch import serve
    argv = ["--arch", ARCH, "--max-batch", str(MAX_BATCH), "--max-seq",
            str(max_seq), "--new-tokens", str(new_tokens), *extra]
    return serve.build_parser().parse_args(
        argv + (["--reduced"] if reduced else []))


def make_prompts(vocab: int, max_prompt: int = MAX_PROMPT,
                 n: int = N_REQUESTS, seed: int = 0) -> List[np.ndarray]:
    """Seeded prompts: the first half of (max/2, max] tokens, the second of
    (max/4, max/2].  Each admission wave of 4 then fills one prefill bucket,
    which bounds the number of programs compiled at full depth."""
    rng = np.random.default_rng(seed)
    hi = max_prompt
    lens = [int(rng.integers(hi // 2 + 1, hi + 1)) for _ in range(n // 2)]
    lens += [int(rng.integers(hi // 4 + 1, hi // 2 + 1))
             for _ in range(n - n // 2)]
    return [rng.integers(0, vocab, ln).astype(np.int32) for ln in lens]


@dataclass
class Phase:
    name: str
    streams: Dict[int, List[int]]
    statuses: Dict[int, str]
    nonfinite: float
    compile_s: float
    cache_hits: int
    wall_s: float
    peak_bytes: Optional[int]
    params_bytes: int
    pool_bytes: int
    pool_bytes_per_chip: int
    pool_chips: int
    custom_call: Optional[bool] = None
    failures: List[str] = field(default_factory=list)

    def report(self, cache_dir: str) -> str:
        tokens = sum(len(s) for s in self.streams.values())
        done = sum(s == "completed" for s in self.statuses.values())
        peak = ("n/a" if self.peak_bytes is None
                else f"{self.peak_bytes}")
        kernel = ("" if self.custom_call is None else
                  f"; decode step tpu_custom_call={self.custom_call}")
        return (f"phase {self.name}: {done}/{len(self.statuses)} completed, "
                f"{tokens} tokens; compile {self.compile_s:.1f} s "
                f"({self.cache_hits} cache hits, cache {cache_dir}); "
                f"peak_bytes_in_use {peak}; params {self.params_bytes} B; "
                f"kv pool {self.pool_bytes} B ({self.pool_bytes_per_chip} B "
                f"per chip over {self.pool_chips}); wall {self.wall_s:.1f} s, "
                f"{tokens / self.wall_s:.1f} tok/s (smoke reading incl. "
                f"compile, not a benchmark){kernel}")


def run_phase(name, lm, params, args, prompts, clock, *, mesh=None,
              dp_axis=None, want_kernel=False) -> Phase:
    """Serve ``prompts`` on a fresh engine built as ``launch/serve.py``
    builds it, and read back streams, outcomes and memory."""
    import jax
    from repro.launch import serve
    from repro.serve import Request, SamplingParams

    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    eng = serve.build_engine(lm, params, args, mesh=mesh, dp_axis=dp_axis)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=args.new_tokens,
                           sampling=SamplingParams(seed=i)))
    done = eng.run_until_drained(on_stuck="status")
    wall = time.perf_counter() - t0
    custom = None
    if want_kernel:
        custom = "tpu_custom_call" in eng.lower_decode().compile().as_text()
    c1, h1 = clock.mark()
    nonfinite = eng.reg.counter("serve_stream_retries_total").get(
        {"reason": "nonfinite_logits"})
    pool = jax.tree.leaves(eng.kv.state["layers"])
    per_chip = sum(x.addressable_shards[0].data.nbytes for x in pool)
    stats = jax.devices()[0].memory_stats() or {}
    phase = Phase(
        name=name, streams={r.id: list(r.out_tokens) for r in done},
        statuses={r.id: r.status for r in done}, nonfinite=nonfinite,
        compile_s=c1 - c0, cache_hits=h1 - h0, wall_s=wall,
        peak_bytes=stats.get("peak_bytes_in_use"),
        params_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
        pool_bytes=sum(x.nbytes for x in pool), pool_bytes_per_chip=per_chip,
        pool_chips=len(pool[0].sharding.device_set), custom_call=custom)
    for i in range(len(prompts)):
        st, n = phase.statuses.get(i, "missing"), len(phase.streams.get(i, ()))
        if st != "completed" or n != args.new_tokens:
            phase.failures.append(f"phase {name}: request {i} ended "
                                  f"{st} with {n}/{args.new_tokens} tokens")
    if nonfinite:
        phase.failures.append(f"phase {name}: {nonfinite:.0f} non-finite "
                              "logits rows")
    del eng, done
    gc.collect()
    return phase


def reference(lm, params, prompts, streams, new_tokens):
    """Teacher-forced plain ``lm.forward`` (the engine's prefill options)
    over each prompt plus its own stream.  Per request and step: how far
    the sampled token's logit lies below the top logit (0 when it is the
    argmax), the gap between the top two logits, and the tolerance,
    ``TIE_ULPS`` units in the last place of the top logit in the logits'
    dtype.  Rows are padded to one width, so this compiles once."""
    import jax
    import jax.numpy as jnp
    from repro.models import ForwardOpts

    vocab = lm.cfg.vocab_size
    opts = ForwardOpts(attn_impl="dense", remat="none")
    width = max(len(p) for p in prompts) + new_tokens
    width = -(-width // 64) * 64

    @jax.jit
    def score(params, tokens, start, sampled):
        logits = lm.forward(params, {"tokens": tokens}, opts)[0][0, :, :vocab]
        ulp = jnp.finfo(logits.dtype).eps
        rows = jax.lax.dynamic_slice_in_dim(
            logits, start, new_tokens).astype(jnp.float32)
        top2 = jax.lax.top_k(rows, 2)[0]
        picked = jnp.take_along_axis(rows, sampled[:, None], axis=1)[:, 0]
        tol = TIE_ULPS * ulp * jnp.exp2(jnp.floor(jnp.log2(
            jnp.abs(top2[:, 0]))))
        return top2[:, 0] - picked, top2[:, 0] - top2[:, 1], tol

    out = []
    for i, p in enumerate(prompts):
        stream = np.asarray(streams[i], np.int32)
        seq = np.concatenate([p, stream[:-1]])
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        out.append(tuple(np.asarray(x) for x in score(
            params, jnp.asarray(toks), jnp.int32(len(p) - 1),
            jnp.asarray(stream))))
    return out


def check_streams(lm, params, prompts, phases: List[Phase]):
    """Every token of every phase against the teacher-forced reference of
    its own stream: the sampled token's reference logit must lie within
    the tolerance of the top one, so argmax flips are allowed only where
    the top two logits tie to within bf16 rounding.  Where later phases
    diverge from the first, the step is reported.  Returns (lines,
    failures)."""
    lines, failures = [], []
    for ph in phases:
        if ph.failures:          # a stream cut short has no full reference
            continue
        ref = reference(lm, params, prompts, ph.streams,
                        len(ph.streams[0]))
        agree = ties = 0
        worst = 0.0
        for i, (deficit, gap, tol) in enumerate(ref):
            agree += int((deficit == 0).sum())
            ties += int((gap <= tol).sum())
            worst = max(worst, float((deficit / tol).max()))
            for t in np.flatnonzero(deficit > tol):
                failures.append(
                    f"phase {ph.name}: request {i} step {t} sampled a token "
                    f"{deficit[t]:.4f} below the reference argmax "
                    f"(tolerance {tol[t]:.4f})")
        total = sum(len(s) for s in ph.streams.values())
        lines.append(f"reference: phase {ph.name} samples the teacher-forced "
                     f"argmax on {agree}/{total} tokens ({ties} steps tie "
                     f"within {TIE_ULPS} ulp); worst deficit "
                     f"{worst:.2f} of tolerance")
    base = phases[0]
    for ph in phases[1:]:
        steps = []
        for i in range(len(prompts)):
            a, b = base.streams.get(i, []), ph.streams.get(i, [])
            t = next((t for t, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
            if t is not None:
                steps.append(f"request {i} at step {t}")
        lines.append(f"phase {ph.name} vs {base.name}: "
                     + ("identical streams" if not steps else
                        "diverge " + ", ".join(steps)))
    return lines + [f"FAIL {f}" for f in failures], failures


def one_chip(reduced=False, max_seq=MAX_SEQ, max_prompt=MAX_PROMPT,
             new_tokens=NEW_TOKENS, chunk=PREFILL_CHUNK):
    """Phases a, b and c on the default device.  Returns (lines, failures,
    phases)."""
    from repro.launch import serve
    kw = dict(reduced=reduced, max_seq=max_seq, new_tokens=new_tokens)
    args = serve_args(**kw)
    cfg, lm, params = serve.build_model(args)
    prompts = make_prompts(cfg.vocab_size, max_prompt)
    with CompileClock() as clock:
        a = run_phase("a [gather decode, whole-prompt prefill]", lm, params,
                      args, prompts, clock)
        b = run_phase("b [pallas decode]", lm, params,
                      serve_args("--decode-impl", "pallas", **kw), prompts,
                      clock, want_kernel=True)
        c = run_phase(f"c [prefill chunk {chunk}]", lm, params,
                      serve_args("--prefill-chunk", str(chunk), **kw),
                      prompts, clock)
    phases = [a, b, c]
    lines, failures = check_streams(lm, params, prompts, phases)
    for ph in phases:
        failures += ph.failures
    return lines, failures, phases


def four_chips(reduced=False, max_seq=MAX_SEQ, max_prompt=MAX_PROMPT,
               new_tokens=NEW_TOKENS):
    """A single-device engine on ``jax.devices()[0]``, then ``--mesh 4``
    and ``--mesh dp=2,model=2`` engines, all on one copy of the weights:
    replicated over the four chips, the single-device engine reading
    device 0's copy in place."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import serve

    kw = dict(reduced=reduced, max_seq=max_seq, new_tokens=new_tokens)
    one_d = serve_args("--mesh", "4", **kw)
    two_d = serve_args("--mesh", "dp=2,model=2", **kw)
    mesh1, _ = serve.build_mesh(one_d)
    mesh2, dp_axis = serve.build_mesh(two_d)
    cfg, lm, params = serve.build_model(one_d, mesh1)
    dev0 = jax.devices()[0]
    local = jax.tree.map(lambda x: next(s.data for s in x.addressable_shards
                                        if s.device == dev0), params)
    rep2 = NamedSharding(mesh2, PartitionSpec())
    params2 = jax.tree.map(
        lambda x: jax.make_array_from_single_device_arrays(
            x.shape, rep2, [s.data for s in x.addressable_shards]), params)
    prompts = make_prompts(cfg.vocab_size, max_prompt)
    with CompileClock() as clock:
        single = run_phase("single [devices()[0]]", lm, local,
                           serve_args(**kw), prompts, clock)
        m1 = run_phase("mesh (4,)", lm, params, one_d, prompts, clock,
                       mesh=mesh1)
        m2 = run_phase("mesh dp=2,model=2", lm, params2, two_d, prompts,
                       clock, mesh=mesh2, dp_axis=dp_axis)
    phases = [single, m1, m2]
    lines, failures = check_streams(lm, local, prompts, phases)
    for ph, chips in ((m1, 4), (m2, 2)):
        # the pool's pages shard over the pages axis only: 1/4 of the pool
        # per chip on (4,), 1/2 on dp=2,model=2 (replicated across dp)
        if ph.pool_bytes_per_chip * chips != ph.pool_bytes:
            failures.append(f"phase {ph.name}: {ph.pool_bytes_per_chip} "
                            f"pool bytes per chip, expected 1/{chips} of "
                            f"{ph.pool_bytes}")
    for ph in phases:
        failures += ph.failures
    return lines, failures, phases


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    require_tpu()
    import jax
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke: run from a checkout of the repo "
                         f"(src/repro not importable: {e})")
    cache_dir = enable_compile_cache()
    if args.chips == 4 and len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke --chips 4: {len(jax.devices())} "
                         "devices visible")
    run = one_chip if args.chips == 1 else four_chips
    lines, failures, phases = run()
    for ph in phases:
        print(ph.report(cache_dir), flush=True)
    for ln in lines:
        print(ln, flush=True)
    b = next((ph for ph in phases if ph.custom_call is not None), None)
    if b is not None and not b.custom_call:
        failures.append(f"phase {b.name}: compiled decode step holds no "
                        "tpu_custom_call (the paged kernel is missing)")
    if failures:
        raise SystemExit("chip_smoke FAILED:\n  " + "\n  ".join(failures))
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
