"""Operations and bytes a serving step needs, from the configuration's
shapes alone, and the chip's peaks to hold them against.

"Needs" is the work of the step whatever path implements it: every weight
read once, the cache of the live positions read, the new cache written,
and the matrix products of the real tokens.  Padding, inactive slots and
whatever a gather reads beyond the live positions are not counted, so a
path that stops doing them shows as a gain in roofline share.  The counts
are the architecture module's (``bench/arch/<model_type>.py``); the peaks
are one chip's, and a reading over a cell's chips sums them (``summed``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

import spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
PER_CHIP = ("bf16_flops_per_s", "hbm_bytes_per_s")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS.name} (has {sorted(table)})")
    return table[device_kind]


def summed(chip: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """The peak rates of ``chips`` chips of one kind: work counted once,
    against every chip the cell holds."""
    return dict(chip, **{k: chip[k] * chips for k in PER_CHIP})


def _arch(c: Dict[str, Any]):
    return spec.arch(c["model_type"])


def decode_step(c: Dict[str, Any], tokens: int, ctx_sum: int
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) one fused decode step needs for ``tokens`` live slots
    whose contexts (positions attended, the new one included) sum to
    ``ctx_sum``."""
    return _arch(c).decode_step(c, tokens, ctx_sum)


def chunk(c: Dict[str, Any], start: int, n: int) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill chunk of ``n`` prompt tokens at positions
    ``start .. start+n-1`` needs, the head for its last row only."""
    return _arch(c).chunk(c, start, n)


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of weights one step reads: a decode step of no token."""
    return decode_step(c, 0, 0)[1]


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    """Cache bytes of one position, no per-slot state."""
    return _arch(c).cache_bytes(c, 1, 0)


def layer_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of one layer, for an architecture whose layers
    are all alike (its module's ``layer_params``)."""
    return _arch(c).layer_params(c)


def attention_flops(c: Dict[str, Any], ctx: int) -> int:
    """Attention FLOPs of one query row over ``ctx`` positions, all layers
    (its module's ``attention_flops``)."""
    return _arch(c).attention_flops(c, ctx)
