#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: serve its traffic
at each of a few fixed rates, one window each, and report whether the
queue grows over the window.

    python3 bench/sweep.py --workload qwen3-4b.chat --rates 1,2,3,4 \\
        --seconds 30

One process: the weights are drawn once, and each rate gets a fresh
engine (its programs come from the compile cache).  Per rate it prints
one JSON line: requests due, admitted and finished in the window, the
waiting queue (not yet admitted) at each quarter of the window, TTFT
p50/p90 of the requests that got a first token, and the median gap
between tokens.  The knee is the highest rate whose queue does not grow
over the window.  The benchmark's cells run at a fixed rate written into
their traffic file; this script only finds it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import driver
    import run
    import spec
    import traffic
    import weights
    from record import nearest_rank
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import LM

    cell = spec.load_cell(a.workload)
    run.require_chips(cell.chips)
    enable_compile_cache()
    args = run.serve_args(cell.engine)
    mesh, dp_axis = serve.build_mesh(args)
    lm = LM(spec.program_config(cell.config))
    params = weights.make_program_params(
        a.seed, cell.config, lm,
        None if mesh is None else NamedSharding(mesh, PartitionSpec()))
    vocab = cell.config["vocab_size"]
    for rate in [float(r) for r in a.rates.split(",")]:
        mix = dict(cell.traffic, arrivals={"kind": "poisson",
                                           "rate_per_s": rate})
        eng = serve.build_engine(lm, params, args, mesh=mesh,
                                 dp_axis=dp_axis)
        run.warm(eng, vocab)
        due = traffic.generate(mix, a.seed, a.seconds, vocab)
        win = driver.Window(eng, due)
        quarters = {}

        def on_step(w):
            q = int(4 * w.now() / a.seconds)
            if 1 <= q <= 4 and q not in quarters:
                quarters[q] = len(w.eng.queue)

        win.run(a.seconds, 0.0, on_step=on_step)
        quarters.setdefault(4, len(eng.queue))
        sub = win.in_window()
        ttft = [s.first - s.due for s in sub if s.first is not None]
        gaps = [b - c for s in sub for c, b in
                zip(s.token_times, s.token_times[1:])]
        print(json.dumps({
            "rate_per_s": rate, "due": len(sub),
            "admitted": sum(s.admitted is not None for s in sub),
            "finished": sum(s.done for s in sub),
            "queue_by_quarter": [quarters.get(q) for q in (1, 2, 3, 4)],
            "ttft_p50_ms": 1e3 * nearest_rank(ttft, 0.5) if ttft else None,
            "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.9) if ttft else None,
            "itl_p50_ms": 1e3 * nearest_rank(gaps, 0.5) if gaps else None,
            "steps": len(win.steps)}), flush=True)
        win.eng = eng = None
        gc.collect()
    print(json.dumps({"device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
