#!/usr/bin/env python3
"""One run of one cell of the serving benchmark on the chip.

    python3 bench/run.py --workload qwen3-4b.chat --seed 7 --seconds 30 \\
        --trace 0

Set-up builds the cell's model and engine through the server's own
builders (``repro.launch.serve``), with weights drawn from ``--seed`` on
the device, and warms the two step programs the window uses (the prefill
chunk and the fused decode) through the engine itself; a mix's sessions
are then submitted and prefilled to their first token.  The window then
drives the cell's traffic open-loop for ``--seconds``.  Afterwards the
engine is freed, the plain reference checks a seeded sample of the served
tokens, and the last line of stdout is one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a stretch of the window.
The numbers compared for ``correct`` close standard error and the JSON.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import spec  # noqa: E402

SAMPLE_TOKENS = 300        # served tokens the reference checks, at least
SAMPLE_REQUESTS = 12       # ... in at most this many requests
TRACE_SECONDS = 6.0        # traced stretch: the window's last seconds
DUMP_SECONDS = 0.5         # --dump-trace keeps the traced stretch's start


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="PATH",
                    help="also save the reduced trace (PATH) and a summary "
                         "of the raw one (PATH.raw.json)")
    return ap.parse_args(argv)


def require_chips(n: int) -> None:
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench: no TPU (JAX backend is "
                         f"{jax.default_backend()!r}); no CPU fallback")
    if len(jax.devices()) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, "
                         f"{len(jax.devices())} visible")


class CompileClock:
    """Seconds spent obtaining executables (backend compiles and loads
    from the persistent cache) and how many were obtained, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def _on_duration(self, event, secs, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.seconds += secs
            self.count += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)


def serve_args(engine: dict):
    """``launch/serve.py``'s namespace for the cell's engine settings."""
    from repro.launch import serve
    argv = []
    for key, val in engine.items():
        flag = "--" + key.replace("_", "-")
        argv += [flag] if val is True else [flag, str(val)]
    return serve.build_parser().parse_args(argv)


def build(cell: spec.Cell, seed: int):
    """(lm, params, engine) for the cell, through the server's builders.
    The engine, and with it the cache pool, comes first and the weights
    after: the pool of a mesh is made whole on its first chip and then
    split, which does not fit beside the weights at a long-context pool's
    size.  The engine is built without weights and given them before its
    first dispatch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import serve
    from repro.models import LM
    import weights

    args = serve_args(cell.engine)
    mesh, dp_axis = serve.build_mesh(args)
    lm = LM(spec.program_config(cell.config))
    eng = serve.build_engine(lm, None, args, mesh=mesh, dp_axis=dp_axis)
    params = weights.make_program_params(
        seed, cell.config, lm,
        None if mesh is None else NamedSharding(mesh, PartitionSpec()))
    jax.block_until_ready(params)
    eng.params = params
    return lm, params, eng


def warm(eng, vocab: int) -> None:
    """Run the window's programs once through the engine: a prompt of one
    chunk and one token more (two chunk dispatches) and a few decode
    steps."""
    from repro.serve import Request
    n = (eng.chunk or 16) + 1
    rng = np.random.default_rng(0)
    eng.submit(Request(-1, rng.integers(0, vocab, n).astype(np.int32),
                       max_new_tokens=3))
    eng.run_until_drained()
    eng.finished.clear()


def wrap_spans(eng, span) -> None:
    """Host spans around the engine's phases, for the traced run only."""
    for name in ("_run_prefill_chunks", "_admit", "_dispatch_fused"):
        inner = getattr(eng, name)

        def wrapped(*a, _inner=inner, _name=name, **kw):
            with span("engine." + _name.lstrip("_")):
                return _inner(*a, **kw)
        setattr(eng, name, wrapped)


def sample(window, seed: int, rows: int):
    """The requests the reference checks: the one with the most served
    tokens, then others drawn from the seed, until ``SAMPLE_TOKENS``
    checked, each on at most its last ``rows`` served tokens.  Requests
    count once completed; a session (submitted in set-up) also while it
    decodes, on the tokens it emitted by the close."""
    done = [s for s in window.served
            if s.req is not None and (s.req.status == "completed"
                                      or s.setup and s.req.out_tokens)]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 13])
    longest = max(done, key=lambda s: len(s.req.out_tokens))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    picked, total = [longest], min(len(longest.req.out_tokens), rows)
    for s in rest:
        if total >= SAMPLE_TOKENS or len(picked) >= SAMPLE_REQUESTS:
            break
        picked.append(s)
        total += min(len(s.req.out_tokens), rows)
    return picked


def checked_part(req, rows: int):
    """(prompt, served) for the reference: the request's last ``rows``
    served tokens, after its prompt and the tokens before them."""
    out = list(req.out_tokens)
    return np.concatenate([np.asarray(req.prompt),
                           np.asarray(out[:-rows], np.int32)]), out[-rows:]


def check(cell: spec.Cell, seed: int, picked, control: bool = False
          ) -> dict:
    """The widest gap by which a served token's logit lies below the
    plain reference's best, over the sampled requests.  With ``control``
    also the control's reading: at the same positions, the gap of the
    token that the reference computed in float8 puts first."""
    import jax.numpy as jnp
    import reference
    import traffic
    import weights
    w = weights.make_canonical(seed, cell.config)
    seq = int(cell.engine["max_seq"])
    rows = traffic.checked(cell.traffic)
    worst, tokens, ctrl = 0.0, 0, 0.0
    for s in picked:
        prompt, out = checked_part(s.req, rows)
        gap, _, logits = reference.teacher_forced(
            w, cell.config, prompt, out, seq, rows)
        worst = max(worst, float(gap.max()))
        tokens += len(gap)
        if control:
            _, top8, _ = reference.teacher_forced(
                w, cell.config, prompt, out, seq, rows, mode="fp8")
            pick = jnp.take_along_axis(logits, jnp.asarray(top8)[:, None],
                                       axis=1)[:, 0]
            ctrl = max(ctrl, float((logits.max(axis=1) - pick).max()))
    out = {"logit_gap": worst, "tokens_checked": tokens}
    if control:
        out["control_logit_gap"] = ctrl
    return out


def judge(limits: dict, gap, tokens: int, failed: int) -> dict:
    """Each number compared for ``correct``, beside its limit."""
    return {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"],
                      "holds": "<="},
        "tokens_checked": {"value": tokens,
                           "limit": limits["tokens_checked"],
                           "holds": ">="},
        "failed_requests": {"value": failed, "limit": 0, "holds": "<="},
    }


def holds(compared: dict) -> bool:
    """Every number within its limit; a number not read fails."""
    return all(c["value"] is not None and (
        c["value"] <= c["limit"] if c["holds"] == "<="
        else c["value"] >= c["limit"]) for c in compared.values())


def outcomes(inw, mix: dict, seconds: float):
    """(owed, failed) among the requests submitted in the window.  Owed:
    in an open loop each one due in the window, which must have started
    answering by the drain's end; in a queue due at once, each one
    admitted before the close; of sessions, every session (each has its
    first token before the window opens).  Failed: owed and no first
    token, or ended other than completed.  One still decoding is late, not
    failed; a session decodes through the window by design."""
    def ended_badly(s):
        return s.req.status not in ("pending", "completed")

    if mix["arrivals"]["kind"] == "at_zero":
        owed = [s for s in inw
                if s.admitted is not None and s.admitted <= seconds]
        return owed, [s for s in owed if ended_badly(s)]
    return inw, [s for s in inw if s.first is None or ended_badly(s)]


def diagnose(win, seconds: float) -> str:
    """Where a run lost time on the host clock, as its longest steps in
    the window; and, as an untraced run's line leaves it out, the time to
    first token by quantile."""
    from record import nearest_rank
    st = [s for s in win.steps if s.end <= seconds]
    if not st:
        return "bench: no step in the window"
    dur = sorted(((s.end - s.start, s.start) for s in st), reverse=True)
    ttft = [s.first - s.due for s in win.in_window() if s.first is not None]
    q = ", ".join(f"p{int(p * 100)} {1e3 * nearest_rank(ttft, p):.0f}"
                  for p in (0.5, 0.75, 0.85, 0.9) if ttft)
    return (f"bench: steps median {1e3 * dur[len(dur) // 2][0]:.1f} ms, "
            f"longest " + ", ".join(f"{1e3 * d:.0f} ms at {t:.1f} s"
                                    for d, t in dur[:3])
            + f"; ttft ms {q}")


def breakdown(trace) -> dict:
    return {"device_ops": trace.top_ops(), "idle_gaps": trace.idle_gaps()}


def main(argv=None) -> None:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    try:
        import jax  # noqa: F401
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"bench: run from a checkout of the repo ({e})")
    require_chips(cell.chips)
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   dump=args.dump_trace)
    print(json.dumps(out), flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             dump=None, peaks=None, control: bool = False) -> dict:
    """Set-up, window, readings and check of one run; returns the result
    line's object and prints the checks to standard error."""
    import jax
    import driver
    import flops
    import traffic
    from record import Run
    from devtrace import Capture, MissingEvent, span

    dev = jax.devices()
    peaks = peaks or flops.peaks(dev[0].device_kind)
    due = traffic.generate(cell.traffic, seed, seconds,
                           cell.config["vocab_size"])
    with CompileClock() as clock:
        lm, params, eng = build(cell, seed)
        warm(eng, cell.config["vocab_size"])
        cap, marks = Capture(), {}
        if traced:
            wrap_spans(eng, span)
            cap.warm()
        win = driver.Window(eng, due, pages=traced,
                            span=span if traced else None)
        # a mix's sessions prefill and take their first token in set-up
        win.prime()
    compile_s = clock.seconds
    # the profiler stops only after the drain: stopping writes the trace,
    # which takes seconds that would stall the loop inside the window
    t_a = seconds - min(TRACE_SECONDS, seconds / 2)

    def on_step(w):
        if traced and "a" not in marks and w.now() >= t_a:
            cap.start()
            marks["a"] = len(w.steps)

    with CompileClock() as in_window:
        setup_s = time.perf_counter() - T_START
        win.run(seconds, float(cell.traffic.get("drain_s", 0)),
                on_step=on_step)
    if "a" in marks:
        cap.stop()
        marks["b"] = len(win.steps)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev)

    trace = None
    if traced:
        trace = cap.read(f"{dump}.raw.json" if dump else None)
        if dump:
            trace.clipped(DUMP_SECONDS).save(dump)
    run = Run(cell=cell, seconds=seconds, window=win, setup_s=setup_s,
              compile_s=compile_s, peaks=peaks, trace=trace,
              traced=(marks.get("a", 0), marks.get("b", 0)))

    metrics, missing = {}, []
    entries = cell.per_layer if traced else cell.end_to_end
    for m in entries:
        kind = "metrics" if traced else "e2e"
        try:
            val = spec.reader(kind, m["name"])(run)
        except MissingEvent as e:
            val = None
            print(f"bench: {m['name']}: {e}", file=sys.stderr)
        if val is None:
            missing.append(m["name"])
            continue
        metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    inw = win.in_window()
    owed, failed = outcomes(inw, cell.traffic, seconds)
    late = [s.submitted - s.due for s in inw]

    picked = sample(win, seed, traffic.checked(cell.traffic))
    eng = params = win.eng = None
    gc.collect()
    t_ref = time.perf_counter()
    # nothing served to check: no gap to read, and the check fails
    checks = check(cell, seed, picked, control) if picked else \
        {"logit_gap": None, "tokens_checked": 0}
    t_ref = time.perf_counter() - t_ref
    compared = judge(cell.limits, checks["logit_gap"],
                     checks["tokens_checked"], len(failed))

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(peak)}
    out = {"correct": holds(compared), "attempted": len(owed),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace is not None:
        lo, hi = trace.window()
        device["busy_s"] = trace.busy_ns() * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = breakdown(trace)
    if control:
        # the control in the program's place, judged by the same limits
        ctrl = judge(cell.limits, checks.get("control_logit_gap"),
                     checks["tokens_checked"], len(failed))
        out["control"] = {"correct": holds(ctrl), "checks": ctrl}
    out["checks"] = compared

    print(diagnose(win, seconds), file=sys.stderr)
    print(f"bench: {cell.name} seed {seed}: {len(inw)} requests "
          f"submitted, {len(owed)} owed, {len(failed)} failed; "
          f"{len(win.steps)} steps; generator late by at most "
          f"{max(late, default=0.0) * 1e3:.1f} ms; set-up {setup_s:.2f} s "
          f"(executables {compile_s:.2f} s); drain "
          f"{win.drained - seconds:.1f} s; reference {t_ref:.1f} s; "
          f"programs obtained in the window: {in_window.count}; profiler "
          f"start {cap.start_s:.2f} s, stop {cap.stop_s:.2f} s; missing "
          f"metrics: {missing}",
          file=sys.stderr)
    if control:
        for name, c in out["control"]["checks"].items():
            print(f"control check {name}: {c['value']} {c['holds']} "
                  f"{c['limit']}", file=sys.stderr)
    for name, c in compared.items():
        print(f"check {name}: {c['value']} {c['holds']} {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return out


if __name__ == "__main__":
    main()
