"""The one traffic generator; a mix is a data file of its parameters.

``bench/traffic/<mix>.json``::

    {"arrivals": {"kind": "poisson", "rate_per_s": 2.4}
                 | {"kind": "at_zero", "requests": 512}
                 | {"kind": "sessions", "sessions": 8},
     "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1536},
     "output": {"median": 96, "sigma": 0.9, "min": 8, "max": 512}
               | {"fill": 12288, "min": 2048, "max": 6144, "checked": 256},
     "block": 32, "drain_s": 60}

Lengths are lognormal (median, sigma) clipped to [min, max]; arrivals are
an open-loop Poisson process at a fixed rate, a queue that is all due at
t = 0, or sessions: requests submitted in set-up, each prefilled and given
its first token before the window opens, so the window decodes over their
whole contexts.  An answer with ``fill`` runs until prompt and answer fill
that many positions (``min`` and ``max`` state the answers that gives), so
a session does not end inside the window.  ``checked`` caps the served
tokens of one request the reference reads (the last ones; default: the
largest answer, ``max``).

Every seed gets the same set of sizes and of gaps between arrivals, in
another order: the sizes are the quantiles of their distribution at an even
grid, dealt out in blocks of ``block`` consecutive requests so that every
block holds one size from each stratum, and the seed permutes the order
inside each block, the order of the blocks and the gaps.  So a run's work
does not change with its seed, only its order and the tokens; and any run
of whole blocks from the front of a queue sees the whole distribution.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Due:
    id: int
    due: float                # seconds after the window opens
    prompt: np.ndarray        # (len,) int32 token ids
    max_new: int
    setup: bool = False       # submitted in set-up, before the window


def count(mix: Dict[str, Any], seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, round(arr["rate_per_s"] * seconds))
    if arr["kind"] == "at_zero":
        return int(arr["requests"])
    if arr["kind"] == "sessions":
        return int(arr["sessions"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def strata(n: int, block: int) -> List[np.ndarray]:
    """Quantile levels in (0, 1), one array per block of ``block``
    requests: the even grid of ``n`` levels dealt so that block ``b`` takes
    the ``b``-th level of every stratum (a final short block takes evenly
    spread strata)."""
    nb = math.ceil(n / block)
    grid = (np.arange(block * nb) + 0.5) / (block * nb)
    per = grid.reshape(block, nb)            # row j: stratum j
    out = []
    for b in range(nb):
        r = min(block, n - b * block)
        js = np.floor((np.arange(r) + 0.5) * block / r).astype(int)
        out.append(per[js, b])
    return out


def lognormal(levels: np.ndarray, p: Dict[str, Any]) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(u)) for u in levels])
    x = np.round(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(x, p["min"], p["max"]).astype(int)


def answers(levels: np.ndarray, prompts: np.ndarray,
            p: Dict[str, Any]) -> np.ndarray:
    """Answer lengths: lognormal at ``levels``, or, with ``fill``, what
    fills that many positions after each prompt."""
    if "fill" in p:
        return int(p["fill"]) - prompts
    return lognormal(levels, p)


def checked(mix: Dict[str, Any]) -> int:
    """The most served tokens of one request the reference reads."""
    out = mix["output"]
    return int(out.get("checked", out["max"]))


def generate(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Due]:
    """The requests of one run, in due order."""
    rng = np.random.default_rng([int(seed), 7])
    n = count(mix, seconds)
    blocks = strata(n, int(mix["block"]))
    order = rng.permutation(len(blocks))
    plens, olens = [], []
    for b in order:
        lv = blocks[b]
        plens.append(lognormal(lv[rng.permutation(len(lv))], mix["prompt"]))
        olens.append(answers(lv[rng.permutation(len(lv))], plens[-1],
                             mix["output"]))
    plens, olens = np.concatenate(plens), np.concatenate(olens)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / arr["rate_per_s"]
        gaps = gaps[rng.permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = np.zeros(n)
    setup = arr["kind"] == "sessions"
    return [Due(i, float(due[i]),
                rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                int(olens[i]), setup) for i in range(n)]
