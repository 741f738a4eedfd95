"""The plain reference: a dense GQA decoder in float32 ``jax.numpy``.

It follows the published description of the configurations it serves
(Llama-style decoder; Qwen3 adds an RMSNorm over each head of q and k
before the rotary embedding; Granite Code adds a bias to every projection
of attention and of the MLP): RMSNorm, rotary embedding on the two halves
of each head, causal softmax attention with grouped KV heads, SwiGLU, a
final RMSNorm and the output head (the embedding table when tied).  No
cache, no batching, no kernels, and nothing imported from the program: the
weights are the canonical tree of ``weights.py``, drawn again from the
seed.  Every matrix product runs at ``Precision.HIGHEST``.

``mode="fp8"`` is the control: the same forward with every operand of
every matrix product rounded to float8 e4m3 (weights by output channel,
activations by row), the precision below the bfloat16 the configurations
state.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from weights import sizes

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 1024           # query rows per block of attention scores


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(eq, a, b, mode, a_axis, b_axis):
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(F32)


def _rope(x, pos, theta):
    """x (S, heads, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(F32) * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, mode):
    """Causal attention; q (S, KV, G, hd), k/v (S, KV, hd)."""
    S, hd = q.shape[0], q.shape[-1]
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = _mm("qkgd,skd->kgqs", qb, k, mode, -1, -1) / math.sqrt(hd)
        qpos = jnp.arange(lo, lo + qb.shape[0])
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("kgqs,skd->qkgd", p, v, mode, -1, 0))
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnums=(1, 5))
def _logits(w, config_items, tokens, rows, served, mode):
    """(gap of the served token below the best, argmax) at each row."""
    c = dict(config_items)
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
        y = _mm("sd,de->se", x, lw[name], mode, -1, 0)
        return y + lw[bias].astype(F32) if bias in lw else y

    def layer(h, lw):
        x = _rms(h, lw["ln1"], eps)
        q = proj(x, lw, "wq", "bq").reshape(S, H, hd)
        k = proj(x, lw, "wk", "bk").reshape(S, KV, hd)
        v = proj(x, lw, "wv", "bv").reshape(S, KV, hd)
        if "q_norm" in lw:
            q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attend(q.reshape(S, KV, H // KV, hd), k, v, mode)
        h = h + proj(o.reshape(S, H * hd), lw, "wo", "bo")
        x = _rms(h, lw["ln2"], eps)
        g = proj(x, lw, "w_gate", "b_gate")
        u = proj(x, lw, "w_up", "b_up")
        return h + proj(jax.nn.silu(g) * u, lw, "w_down", "b_down"), None

    h, _ = jax.lax.scan(layer, h, layers)
    x = _rms(h[rows], w["final_norm"], eps)
    if "lm_head" in w:
        logits = _mm("rd,dv->rv", x, w["lm_head"], mode, -1, 0)
    else:
        logits = _mm("rd,vd->rv", x, w["embed"], mode, -1, -1)
    best = logits.max(axis=-1)
    picked = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return best - picked, jnp.argmax(logits, axis=-1).astype(jnp.int32), \
        logits


def teacher_forced(w, config: Dict[str, Any], prompt, out_tokens,
                   seq_len: int, max_rows: int, mode: str = "f32"):
    """Teacher-force ``prompt`` + ``out_tokens[:-1]`` and read, at each
    position that produced a served token, the gap by which the served
    token's logit lies below the best logit, and the argmax.  Sequences
    are padded to ``seq_len`` and rows to ``max_rows``, so each cell
    compiles this once.  Returns (gaps, argmax, logits) for the real rows,
    as host arrays (logits stay on the device)."""
    prompt, out = np.asarray(prompt), np.asarray(out_tokens)
    n = len(out)
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    tokens = np.zeros(seq_len, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(max_rows, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    served = np.zeros(max_rows, np.int32)
    served[:n] = out
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta")
    gap, top, logits = _logits(w, tuple((k, config.get(k)) for k in keys),
                               jnp.asarray(tokens), jnp.asarray(rows),
                               jnp.asarray(served), mode)
    return np.asarray(gap)[:n], np.asarray(top)[:n], logits[:n]
