"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels run with interpret=True — the kernel body
executes in python for correctness validation; on TPU they compile to Mosaic.
Model code calls these through ``ForwardOpts(attn_impl="pallas")`` etc.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_ssd as _ssd
from repro.kernels import rmsnorm as _rn
from repro.kernels import rwkv6_wkv as _wkv


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """Model-layout wrapper.  q: (B, S, KV, G, D); k, v: (B, S, KV, D)."""
    b, s, kv, g, d = q.shape
    q2 = q.transpose(0, 2, 3, 1, 4).reshape(b * kv * g, s, d)
    k2 = k.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
    v2 = v.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
    o = _fa.flash_attention_bhsd(q2, k2, v2, causal=causal,
                                 block_q=min(block_q, s),
                                 block_k=min(block_k, s),
                                 interpret=_interpret())
    return o.reshape(b, kv, g, s, d).transpose(0, 3, 1, 2, 4)


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, page_table, positions,
                           k_scale=None, v_scale=None):
    """Model-layout wrapper for the page-table-walking flash-decode kernel.

    q: (B, 1, KV, G, D) — one query token per slot; k/v pools:
    (P, page, KV, D); page_table: (B, M) int32; positions: (B,) int32.
    Returns (B, 1, KV, G, D).  No gathered dense KV view is materialized:
    each slot's program streams one physical page at a time
    (``repro.kernels.paged_decode``).  ``k_scale``/``v_scale``
    ((P, page, KV) fp32, int8 pools): dequantized in-register inside the
    kernel, each page's scales loaded through the same page walk."""
    from repro.kernels import paged_decode as _pd
    b, s, kv, g, d = q.shape
    assert s == 1, q.shape
    o = _pd.paged_flash_decode(q[:, 0], k_pool, v_pool, page_table,
                               positions, k_scale=k_scale, v_scale=v_scale,
                               interpret=_interpret())
    return o[:, None]


def paged_decode_partials(q, k_pool, v_pool, page_table, positions,
                          page_offset, k_scale=None, v_scale=None):
    """Per-chip partial paged decode for sharded serving
    (``repro.parallel.pagedkv``): the pool argument is one chip's
    (P/n, page, KV, D) shard, ``page_offset`` its first global page id, and
    the page table keeps GLOBAL ids — non-local pages are skipped exactly
    like dead pages.  q: (B, 1, KV, G, D).  Returns the raw fp32
    online-softmax triple ``(acc (B,1,KV,G,D), l (B,KV,G), m (B,KV,G))``
    whose cross-chip psum-style merge reconstructs the full softmax.
    ``k_scale``/``v_scale``: the chip's local (P/n, page, KV) scale shards
    (int8 pools).  Not jitted here: it only runs inside a shard_map body
    that is already staged by the engine's fused dispatch."""
    from repro.kernels import paged_decode as _pd
    b, s, kv, g, d = q.shape
    assert s == 1, q.shape
    acc, l, m = _pd.paged_flash_decode(q[:, 0], k_pool, v_pool, page_table,
                                       positions, page_offset=page_offset,
                                       k_scale=k_scale, v_scale=v_scale,
                                       partials=True, interpret=_interpret())
    return acc[:, None], l, m


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 128):
    """x: (..., d)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    n = x2.shape[0]
    br = block_rows
    while n % br:
        br //= 2
    o = _rn.rmsnorm_rows(x2, scale, eps=eps, block_rows=max(br, 1),
                         interpret=_interpret())
    return o.reshape(shape)


@partial(jax.jit, static_argnames=("chunk",))
def mamba2_ssd(x, da, b, c, *, chunk: int = 128):
    """Model layout: x: (B, S, H, P) pre-scaled; da: (B, S, H);
    b, c: (B, S, N)."""
    bb, s, h, p = x.shape
    x2 = x.transpose(0, 2, 1, 3).reshape(bb * h, s, p)
    da2 = da.transpose(0, 2, 1).reshape(bb * h, s)
    o = _ssd.ssd_scan_bhsd(x2, da2, b, c, chunk=chunk,
                           interpret=_interpret())
    return o.reshape(bb, h, s, p).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("lr", "beta1", "beta2", "eps",
                                   "weight_decay", "step", "block"))
def adamw_fused(g, m, v, master, *, lr, beta1=0.9, beta2=0.95, eps=1e-8,
                weight_decay=0.0, step=1, block=4096):
    from repro.kernels import adamw_update as _aw
    return _aw.adamw_fused(g, m, v, master, lr=lr, beta1=beta1, beta2=beta2,
                           eps=eps, weight_decay=weight_decay, step=step,
                           block=block, interpret=_interpret())


@partial(jax.jit, static_argnames=("vocab", "block_rows"))
def softmax_xent(logits, labels, *, vocab: int = 0, block_rows: int = 8):
    from repro.kernels import softmax_xent as _sx
    return _sx.softmax_xent(logits, labels, vocab=vocab,
                            block_rows=block_rows, interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_wkv(r, k, v, lw, u, *, chunk: int = 32):
    """Model layout: r, k, lw: (B, S, H, K); v: (B, S, H, V); u: (H, K)."""
    b, s, h, kd = r.shape
    vd = v.shape[-1]
    def fold(t, last):
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, last)
    o = _wkv.wkv6_scan_bhsd(fold(r, kd), fold(k, kd), fold(v, vd),
                            fold(lw, kd), u, chunk=chunk,
                            interpret=_interpret())
    return o.reshape(b, h, s, vd).transpose(0, 2, 1, 3)
