"""The dense GQA decoder of the Llama family (``model_type`` "llama"):
Llama, and Granite Code, which puts a bias on every projection of
attention and of the MLP.

RMSNorm, rotary embedding on the two halves of each head, causal softmax
attention with grouped KV heads, SwiGLU, a final RMSNorm and the output
head (the embedding table when tied).  ``bench/arch/qwen3.py`` is this
decoder with an RMSNorm over each head of q and k: every function that
depends on it takes ``qk_norm``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import flops
import weights
from reference import F32, _attend, _rms, _rope

# the source's config keys, as the program's ModelConfig names them
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "use_bias",
    "hidden_act": "act",
}


def model_fields(config: Dict[str, Any], qk_norm: bool = False
                 ) -> Dict[str, Any]:
    out = {FIELDS[k]: v for k, v in config.items() if k in FIELDS}
    # the program has one switch for the biases of attention and of the MLP
    if bool(config.get("mlp_bias")) != bool(config["attention_bias"]):
        raise SystemExit("the program cannot run attention_bias "
                         f"{config['attention_bias']} with mlp_bias "
                         f"{config.get('mlp_bias')}")
    out["qk_norm"] = qk_norm
    return out


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    c = config
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                hd=c.get("head_dim") or c["hidden_size"]
                // c["num_attention_heads"],
                F=c["intermediate_size"], V=c["vocab_size"])


# ------------------------------------------------------------ weights ----
def leaves(config: Dict[str, Any], qk_norm: bool = False
           ) -> Dict[str, Tuple[tuple, Any]]:
    """Canonical leaf -> (shape, std); std None marks a norm scale."""
    s = sizes(config)
    L, d, H, KV, hd, F, V = (s[k] for k in ("L", "d", "H", "KV", "hd", "F",
                                            "V"))
    std = weights.STD
    out_std = std / math.sqrt(2 * L)
    out = {
        "embed": ((V, d), std),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, H * hd), std),
        "wk": ((L, d, KV * hd), std),
        "wv": ((L, d, KV * hd), std),
        "wo": ((L, H * hd, d), out_std),
        "w_gate": ((L, d, F), std),
        "w_up": ((L, d, F), std),
        "w_down": ((L, F, d), out_std),
    }
    if config.get("attention_bias"):
        for name, width in (("bq", H * hd), ("bk", KV * hd), ("bv", KV * hd),
                            ("bo", d)):
            out[name] = ((L, width), weights.BIAS_STD)
    if config.get("mlp_bias"):
        for name, width in (("b_gate", F), ("b_up", F), ("b_down", d)):
            out[name] = ((L, width), weights.BIAS_STD)
    if qk_norm:
        out["q_norm"] = ((L, hd), None)
        out["k_norm"] = ((L, hd), None)
    if not config["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), std)
    return out


def to_program(w: Dict[str, jax.Array], config: Dict[str, Any],
               padded_vocab: int) -> Dict[str, Any]:
    """The program's parameter tree (``repro.models.transformer``'s spec)
    holding the canonical weights ``w``."""
    s = sizes(config)
    L, d, H, KV, hd = s["L"], s["d"], s["H"], s["KV"], s["hd"]
    pad = padded_vocab - s["V"]
    attn = {"wq": {"kernel": w["wq"].reshape(L, d, H, hd)},
            "wk": {"kernel": w["wk"].reshape(L, d, KV, hd)},
            "wv": {"kernel": w["wv"].reshape(L, d, KV, hd)},
            "wo": {"kernel": w["wo"].reshape(L, H, hd, d)}}
    if "bq" in w:
        attn["wq"]["bias"] = w["bq"].reshape(L, H, hd)
        attn["wk"]["bias"] = w["bk"].reshape(L, KV, hd)
        attn["wv"]["bias"] = w["bv"].reshape(L, KV, hd)
        attn["wo"]["bias"] = w["bo"]
    mlp = {"wg": {"kernel": w["w_gate"]}, "wi": {"kernel": w["w_up"]},
           "wo": {"kernel": w["w_down"]}}
    if "b_gate" in w:
        mlp["wg"]["bias"], mlp["wi"]["bias"], mlp["wo"]["bias"] = \
            w["b_gate"], w["b_up"], w["b_down"]
    if "q_norm" in w:
        attn["q_norm"] = {"scale": w["q_norm"]}
        attn["k_norm"] = {"scale": w["k_norm"]}
    tree = {
        "embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {"ln1": {"scale": w["ln1"]}, "attn": attn,
                   "ln2": {"scale": w["ln2"]},
                   # SwiGLU as the program writes it: act(wg) * wi, then wo
                   "mlp": mlp},
    }
    if "lm_head" in w:
        tree["lm_head"] = {"kernel": jnp.pad(w["lm_head"], ((0, 0), (0, pad)))}
    return tree


# ---------------------------------------------------------- reference ----
def logits(w, config: Dict[str, Any], tokens, rows, mm):
    """Float32 logits at ``rows`` of the causal forward over ``tokens``;
    every matrix product through ``mm(eq, a, b, a_axis, b_axis)``."""
    c = config
    s = sizes(c)
    L, H, KV, hd = s["L"], s["H"], s["KV"], s["hd"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    h = w["embed"][tokens].astype(F32)
    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down", "q_norm",
                                "k_norm", "bq", "bk", "bv", "bo", "b_gate",
                                "b_up", "b_down") if k in w}

    def proj(x, lw, name, bias):
        y = mm("sd,de->se", x, lw[name], -1, 0)
        return y + lw[bias].astype(F32) if bias in lw else y

    def layer(h, lw):
        x = _rms(h, lw["ln1"], eps)
        q = proj(x, lw, "wq", "bq").reshape(S, H, hd)
        k = proj(x, lw, "wk", "bk").reshape(S, KV, hd)
        v = proj(x, lw, "wv", "bv").reshape(S, KV, hd)
        if "q_norm" in lw:
            q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attend(q.reshape(S, KV, H // KV, hd), k, v, mm)
        h = h + proj(o.reshape(S, H * hd), lw, "wo", "bo")
        x = _rms(h, lw["ln2"], eps)
        g = proj(x, lw, "w_gate", "b_gate")
        u = proj(x, lw, "w_up", "b_up")
        return h + proj(jax.nn.silu(g) * u, lw, "w_down", "b_down"), None

    h, _ = jax.lax.scan(layer, h, layers)
    x = _rms(h[rows], w["final_norm"], eps)
    if "lm_head" in w:
        return mm("rd,dv->rv", x, w["lm_head"], -1, 0)
    return mm("rd,vd->rv", x, w["embed"], -1, -1)


# --------------------------------------------------------------- work ----
def _dims(c: Dict[str, Any]):
    s = sizes(c)
    return tuple(s[k] for k in ("L", "d", "H", "KV", "hd", "F", "V"))


def layer_params(c: Dict[str, Any]) -> int:
    """Matrix parameters of one decoder layer (q, k, v, o, SwiGLU)."""
    L, d, H, KV, hd, F, V = _dims(c)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def small_params(c: Dict[str, Any], qk_norm: bool = False) -> int:
    """Norm scales and biases of the whole model."""
    L, d, H, KV, hd, F, V = _dims(c)
    qk = 2 * hd if qk_norm else 0
    attn_b = (H * hd + 2 * KV * hd + d) if c.get("attention_bias") else 0
    mlp_b = (2 * F + d) if c.get("mlp_bias") else 0
    return L * (2 * d + qk + attn_b + mlp_b) + d


def weight_bytes(c: Dict[str, Any], qk_norm: bool = False) -> int:
    """Bytes of weights one step reads: every layer, the norms, biases and
    the output head (the tied embedding table is the head; an untied embedding
    table is read only at the step's own rows, which is left out)."""
    L, d, H, KV, hd, F, V = _dims(c)
    n = L * layer_params(c) + small_params(c, qk_norm) + V * d
    return n * flops.DTYPE_BYTES[c["torch_dtype"]]


def kv_bytes_per_position(c: Dict[str, Any]) -> int:
    L, d, H, KV, hd, F, V = _dims(c)
    return L * 2 * KV * hd * flops.DTYPE_BYTES[c["torch_dtype"]]


def attention_flops(c: Dict[str, Any], ctx: int) -> int:
    """Scores and weighted sum of one query row over ``ctx`` positions,
    all layers."""
    L, d, H, KV, hd, F, V = _dims(c)
    return 4 * L * H * hd * ctx


def decode_step(c: Dict[str, Any], tokens: int, ctx_sum: int,
                qk_norm: bool = False) -> Tuple[int, int]:
    """(FLOPs, bytes) one fused decode step needs for ``tokens`` live slots
    whose contexts (positions attended, the new one included) sum to
    ``ctx_sum``: one row through every layer and the head per slot."""
    L, d, H, KV, hd, F, V = _dims(c)
    f = 2 * (L * layer_params(c) + d * V) * tokens \
        + attention_flops(c, ctx_sum)
    kvb = kv_bytes_per_position(c)
    return f, weight_bytes(c, qk_norm) + kvb * ctx_sum + kvb * tokens


def chunk(c: Dict[str, Any], start: int, n: int, qk_norm: bool = False
          ) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill chunk of ``n`` prompt tokens at positions
    ``start .. start+n-1`` needs: every row through every layer, causal
    attention over the prior positions and the chunk, and the head for
    the last row only (the one the first token is sampled from)."""
    L, d, H, KV, hd, F, V = _dims(c)
    ctx = n * start + n * (n + 1) // 2       # sum of (p + 1) over the chunk
    f = 2 * L * layer_params(c) * n + 2 * d * V + attention_flops(c, ctx)
    kvb = kv_bytes_per_position(c)
    return f, weight_bytes(c, qk_norm) + kvb * start + kvb * n


# ------------------------------------------------------------- sizing ----
def cache_bytes(c: Dict[str, Any], positions: int, slots: int) -> int:
    """Cache bytes of ``positions`` KV positions; a dense decoder keeps no
    state per slot."""
    return kv_bytes_per_position(c) * positions


def one_layer(c: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration cut to one layer, every width kept."""
    return dict(c, num_hidden_layers=1)
