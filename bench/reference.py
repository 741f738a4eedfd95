"""The plain reference: a float32 ``jax.numpy`` forward of the
configuration's architecture, and the comparison it is read through.

Each architecture's forward is ``logits`` of its module,
``bench/arch/<model_type>.py``, which follows the published description of
the configurations it serves; ``_rms``, ``_rope`` and ``_attend`` here are
its shared parts.  No cache, no batching, no kernels, and nothing imported
from the program: the weights are the canonical tree of ``weights.py``,
drawn again from the seed.  Every matrix product goes through ``_mm`` at
``Precision.HIGHEST``.

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

import spec

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 1024           # query rows per block of attention scores, at most
SCORE_BYTES = 1 << 30    # ... and float32 scores per block, at most


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


def _attend(q, k, v, mm):
    """Causal attention; q (S, KV, G, hd), k/v (S, KV, hd); the products
    through ``mm`` (``logits``' argument)."""
    S, hd = q.shape[0], q.shape[-1]
    heads = q.shape[1] * q.shape[2]
    block = Q_BLOCK
    while block > 1 and block * heads * S * 4 > SCORE_BYTES:
        block //= 2             # long sequences: fewer rows a block
    outs = []
    for lo in range(0, S, block):
        qb = q[lo:lo + block]
        s = mm("qkgd,skd->kgqs", qb, k, -1, -1) / math.sqrt(hd)
        qpos = jnp.arange(lo, lo + qb.shape[0])
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(mm("kgqs,skd->qkgd", p, v, -1, 0))
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnums=(1, 5))
def _logits(w, config_items, tokens, rows, served, mode):
    """(gap of the served token below the best, argmax, logits) at each
    row."""
    c = dict(config_items)

    def mm(eq, a, b, a_axis, b_axis):
        return _mm(eq, a, b, mode, a_axis, b_axis)

    logits = spec.arch(c["model_type"]).logits(w, c, tokens, rows, mm)
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
    # the configuration's scalar keys, static: the forward's shapes
    scalars = tuple(sorted((k, v) for k, v in config.items()
                           if v is None or isinstance(v, (bool, int, float,
                                                          str))))
    gap, top, logits = _logits(w, scalars, jnp.asarray(tokens),
                               jnp.asarray(rows), jnp.asarray(served), mode)
    return np.asarray(gap)[:n], np.asarray(top)[:n], logits[:n]
