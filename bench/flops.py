"""Operations and bytes a serving step needs, from the configuration's
shapes alone, and the chip's peaks to hold them against.

"Needs" is the work of the step whatever path implements it: every weight
read once, the KV of the live positions read, the new KV written, and the
matrix products of the real tokens.  Padding, inactive slots and whatever
a gather reads beyond the live positions are not counted, so a path that
stops doing them shows as a gain in roofline share.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS.name} (has {sorted(table)})")
    return table[device_kind]


def _dims(c: Dict[str, Any]):
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"], hd,
            c["intermediate_size"], c["vocab_size"])


def layer_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of one decoder layer (q, k, v, o, SwiGLU)."""
    L, d, H, KV, hd, F, V = _dims(c)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def small_params(c: Dict[str, Any]) -> int:
    """Norm scales and biases of the whole model."""
    L, d, H, KV, hd, F, V = _dims(c)
    qk = 2 * hd if c["model_type"] == "qwen3" else 0
    attn_b = (H * hd + 2 * KV * hd + d) if c.get("attention_bias") else 0
    mlp_b = (2 * F + d) if c.get("mlp_bias") else 0
    return L * (2 * d + qk + attn_b + mlp_b) + d


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of weights one step reads: every layer, the norms, biases and
    the output head (the tied embedding table is the head; an untied embedding
    table is read only at the step's own rows, which is left out)."""
    L, d, H, KV, hd, F, V = _dims(c)
    n = L * layer_params(c) + small_params(c) + V * d
    return n * DTYPE_BYTES[c["torch_dtype"]]


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    L, d, H, KV, hd, F, V = _dims(c)
    return L * 2 * KV * hd * DTYPE_BYTES[c["torch_dtype"]]


def attention_flops(c: Dict[str, Any], ctx: int) -> int:
    """Scores and weighted sum of one query row over ``ctx`` positions,
    all layers."""
    L, d, H, KV, hd, F, V = _dims(c)
    return 4 * L * H * hd * ctx


def decode_step(c: Dict[str, Any], tokens: int, ctx_sum: int
                ) -> Tuple[int, int]:
    """(FLOPs, bytes) one fused decode step needs for ``tokens`` live slots
    whose contexts (positions attended, the new one included) sum to
    ``ctx_sum``: one row through every layer and the head per slot."""
    L, d, H, KV, hd, F, V = _dims(c)
    flops = 2 * (L * layer_params(c) + d * V) * tokens \
        + attention_flops(c, ctx_sum)
    kvb = kv_bytes_per_position(c)
    return flops, weight_bytes(c) + kvb * ctx_sum + kvb * tokens


def chunk(c: Dict[str, Any], start: int, n: int) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill chunk of ``n`` prompt tokens at positions
    ``start .. start+n-1`` needs: every row through every layer, causal
    attention over the prior positions and the chunk, and the head for
    the last row only (the one the first token is sampled from)."""
    L, d, H, KV, hd, F, V = _dims(c)
    ctx = n * start + n * (n + 1) // 2       # sum of (p + 1) over the chunk
    flops = 2 * L * layer_params(c) * n + 2 * d * V \
        + attention_flops(c, ctx)
    kvb = kv_bytes_per_position(c)
    return flops, weight_bytes(c) + kvb * start + kvb * n
