#!/usr/bin/env python3
"""The control of a cell's correctness check, read on the chip.

    python3 bench/control.py --workload qwen3-4b.chat --seeds 11,12,13 \\
        --seconds 45

For each seed, one run of the cell as ``bench/run.py --trace 0`` makes it,
whose sampled requests are then read twice: the program's reading
(``logit_gap``, the widest gap by which a served token's logit lies below
the float32 reference's best) and the control's (the widest gap of the
token that the same reference computed in float8 puts first, at the same
positions of the same prompts and tokens).  The harness judges each by the
cell's limits in ``bench/cells/<cell>.json``.  One JSON line per seed; the
command fails unless every program run is correct and every control run
is not.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    run.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for seed in [int(x) for x in a.seeds.split(",")]:
        out = run.run_cell(cell, seed, a.seconds, False, control=True)
        rows.append(summary(seed, out))
        print(json.dumps(rows[-1]), flush=True)
    verdict(rows)


def summary(seed: int, out: dict) -> dict:
    ctrl = out["control"]
    return {"seed": seed, "correct": out["correct"],
            "control_correct": ctrl["correct"],
            "logit_gap": out["checks"]["logit_gap"]["value"],
            "control_logit_gap": ctrl["checks"]["logit_gap"]["value"],
            "limit": out["checks"]["logit_gap"]["limit"],
            "tokens": out["checks"]["tokens_checked"]["value"]}


def verdict(rows) -> None:
    """Exit non-zero unless the program passed and the control failed on
    every seed."""
    bad = [r["seed"] for r in rows
           if not r["correct"] or r["control_correct"]]
    if bad:
        raise SystemExit(f"control: seeds {bad} do not separate the "
                         f"program (correct) from the control (not "
                         f"correct)")
    print(f"control: the control is not correct on all {len(rows)} seeds, "
          f"the program correct", file=sys.stderr)

if __name__ == "__main__":
    main()
