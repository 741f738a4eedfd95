"""Sequence-sharded self-attention via shard_map (§Perf).

For architectures whose head count does not divide the ``model`` mesh axis
(arctic 56H, llama3.2/starcoder2 24H, granite-13b 40H) the pure-GSPMD
fallback replicates the whole attention block 16× on the model axis.  This
shard_map path shards the *query sequence* over the model axis instead:

    q, k, v sharded (B, S/16, ...);  K/V all-gathered (tiled) inside;
    each shard computes its query rows against the full K/V with causal
    masking from its global offset (axis_index-based, traced).

Compute and score-memory drop ~16×; the cost is the K/V all-gather
(2·S·KV·D bf16 per layer, tiny for GQA) and losing the static causal skip
(block masks applied everywhere → ≤2× upper-triangle waste, still ~8× net).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_context

NEG_INF = -1e30


def _blockwise_dyn_offset(q, k, v, q_offset, q_chunk: int, kv_chunk: int):
    """Blockwise online-softmax attention with a *traced* query offset.
    q: (B, Sq, KV, G, D); k, v: (B, Skv, KV, D)."""
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0
    q = q * (1.0 / math.sqrt(hd))
    n_kv = skv // kv_chunk
    k_b = k.reshape(b, n_kv, kv_chunk, kvh, hd).swapaxes(0, 1)
    v_b = v.reshape(b, n_kv, kv_chunk, kvh, hd).swapaxes(0, 1)
    kpos = (jnp.arange(n_kv)[:, None] * kv_chunk
            + jnp.arange(kv_chunk)[None, :])          # (n_kv, kvc)
    outs = []
    for i in range(sq // q_chunk):
        q_blk = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)   # traced offset

        def step(carry, xs):
            kj, vj, kp = xs
            m_prev, l_prev, acc = carry
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk,
                           kj).astype(jnp.float32)
            s = jnp.where(qpos[None, None, None, :, None] >= kp[None, :],
                          s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, q_chunk), jnp.float32),
                jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(step, init, (k_b, v_b, kpos))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(0, 3, 1, 2, 4).astype(q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def seq_sharded_attention(q, k, v, *, axis: str = "model",
                          q_chunk: int = 512, kv_chunk: int = 512):
    """q: (B,S,KV,G,D); k, v: (B,S,KV,D), all logically unsharded on entry.
    Runs under the active sharding context; no-op fallback without one."""
    ctx = current_context()
    if ctx is None:
        return _blockwise_dyn_offset(q, k, v, jnp.int32(0), q_chunk, kv_chunk)
    mesh, rules = ctx
    if axis not in mesh.shape or q.shape[1] % mesh.shape[axis] != 0:
        return _blockwise_dyn_offset(q, k, v, jnp.int32(0), q_chunk, kv_chunk)
    n_shards = mesh.shape[axis]
    s_loc = q.shape[1] // n_shards
    batch_axes = rules.get("batch")

    qspec = P(batch_axes, axis, None, None, None)
    kvspec = P(batch_axes, axis, None, None)

    def body(ql, kl, vl):
        kf = jax.lax.all_gather(kl, axis, axis=1, tiled=True)
        vf = jax.lax.all_gather(vl, axis, axis=1, tiled=True)
        offset = jax.lax.axis_index(axis) * s_loc
        return _blockwise_dyn_offset(ql, kf, vf, offset,
                                     min(q_chunk, s_loc), kv_chunk)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                       out_specs=qspec, check_vma=False)
    return fn(q, k, v)
