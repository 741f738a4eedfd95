"""GQA attention: train/prefill (dense or blockwise-flash) + decode with KV cache.

Three implementations share one set of weights:

* ``dense``      — materializes (Sq, Skv) scores; only for tiny smoke tests.
* ``blockwise``  — FlashAttention expressed in pure XLA: python-unrolled loop over
  query chunks, ``lax.scan`` over the causally-required KV chunks with an online
  softmax.  Causal-FLOP-optimal (no wasted upper-triangle work), O(chunk) memory,
  GSPMD-partitionable — this is the dry-run / production XLA path.
* ``pallas``     — the TPU kernel in ``repro.kernels.flash_attention`` (interpret
  mode on CPU); selected via ``impl="pallas"``.

Decode is a single-token attention over a KV cache; the cache index is either
a shared scalar or a (B,) per-slot position vector, so a ragged continuous
batch decodes in a single call.  Two cache layouts share the decode math:

* **contiguous** — (B, Smax, KV, D) dense rows per slot (train/dry-run).
* **paged**      — a (P, page, KV, D) physical page pool plus a (B, M) int32
  page table; slot positions resolve through the table (pos -> page
  ``table[b, pos // page]``, row ``pos % page``), so slots only pin the pages
  they actually use and identical prompt prefixes can share physical pages
  (``repro.serve.kvcache``).  Physical page 0 is a scratch sink: freed slots'
  table rows point at it, so masked/inactive decode writes land in garbage
  space instead of pages that may since belong to another request.

Paged decode resolves the table one of two ways (``decode_impl``):
``"gather"`` — XLA gather into a dense-equivalent per-step view (default,
runs anywhere, O(B·M·page) transient) — or ``"pallas"`` — the
``repro.kernels.paged_decode`` flash kernel that walks the table
block-by-block with O(page) transient (interpret mode on CPU).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import P, apply_rope, dense_spec, norm_spec, rms_norm
from repro.parallel.sharding import constrain

NEG_INF = -1e30


def attention_spec(cfg, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    depth_scale = 0.02 / math.sqrt(2 * max(cfg.num_layers, 1))
    spec = {
        "wq": dense_spec(d, 0, ("embed", "heads", "head_dim"), cfg.use_bias,
                         shape=(d, h, hd)),
        "wk": dense_spec(d, 0, ("embed", "kv_heads", "head_dim"), cfg.use_bias,
                         shape=(d, kv, hd)),
        "wv": dense_spec(d, 0, ("embed", "kv_heads", "head_dim"), cfg.use_bias,
                         shape=(d, kv, hd)),
        "wo": {"kernel": P((h, hd, d), ("heads", "head_dim", "embed"),
                           scale=depth_scale)},
    }
    if cfg.use_bias:
        spec["wo"]["bias"] = P((d,), ("embed",), init="zeros")
    if cfg.qk_norm and not cross:
        spec["q_norm"] = {"scale": P((hd,), ("head_dim",), init="ones", )}
        spec["k_norm"] = {"scale": P((hd,), ("head_dim",), init="ones")}
    return spec


def _proj(p, x, dtype, tp_shardmap: bool = False):
    k = p["kernel"]
    bias = p["bias"].reshape(k.shape[1:]) if "bias" in p else None
    if tp_shardmap:
        from repro.parallel.tpmm import col_proj_tp
        return col_proj_tp(x, k, bias)
    y = jnp.einsum("bsd,dhe->bshe", x, k.astype(dtype))
    if bias is not None:
        y = y + bias.astype(dtype)
    return y


def project_qkv(p, cfg, xq, xkv, q_positions, kv_positions, rope: bool = True,
                flat_heads: bool = False, tp_shardmap: bool = False):
    """Returns q: (B,Sq,KV,G,D) grouped for GQA; k, v: (B,Skv,KV,D).

    flat_heads (train/prefill): KV is repeated to H so q/k/v are all
    (B,S,H,D) reshaped to KV=H, G=1 — the flat head axis then shards over the
    ``model`` mesh axis whenever H divides it (e.g. llama3-405b H=128,
    qwen3 H=32), instead of falling back to fully-replicated attention when
    the *grouped* dims (KV, G) don't divide.  Per-chip repeated-KV bytes
    equal the per-chip q bytes, so nothing blows up.  Decode keeps the
    grouped layout (a repeated KV *cache* would be a real memory hit).
    """
    dtype = xq.dtype
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = _proj(p["wq"], xq, dtype, tp_shardmap)         # (B,Sq,H,D)
    k = _proj(p["wk"], xkv, dtype, tp_shardmap)        # (B,Skv,KV,D)
    v = _proj(p["wv"], xkv, dtype, tp_shardmap)
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, q_positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    if flat_heads and h != kv:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        kv = h
    q = q.reshape(q.shape[0], q.shape[1], kv, h // kv, hd)
    if flat_heads:
        q = constrain(q, ("batch", "seq", "heads", None, None))
        k = constrain(k, ("batch", "seq", "heads", None))
        v = constrain(v, ("batch", "seq", "heads", None))
    else:
        q = constrain(q, ("batch", "seq", "kv_heads", "q_group", None))
        k = constrain(k, ("batch", "seq", "kv_heads", None))
        v = constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def output_proj(p, cfg, y, tp_shardmap: bool = False):
    """y: (B,S,KV,G,D) -> (B,S,d)."""
    dtype = y.dtype
    b, s = y.shape[:2]
    y = y.reshape(b, s, cfg.num_heads, cfg.resolved_head_dim)
    if tp_shardmap:
        from repro.parallel.tpmm import o_proj_tp
        return o_proj_tp(y, p["wo"]["kernel"], p["wo"].get("bias"))
    out = jnp.einsum("bshe,hed->bsd", y, p["wo"]["kernel"].astype(dtype))
    if "bias" in p["wo"]:
        out = out + p["wo"]["bias"].astype(dtype)
    return out


# ------------------------------------------------------------- dense variant --

def dense_attention(q, k, v, causal: bool, q_offset: int = 0):
    """q: (B,Sq,KV,G,D); k,v: (B,Skv,KV,D)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if causal:
        sq, skv = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq) + q_offset
        mask = qpos[:, None] >= jnp.arange(skv)[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


# --------------------------------------------------------- blockwise variant --

def _online_block(carry, kv_blk, q_blk, bias=None):
    """One online-softmax step.  q_blk: (B,Qc,KV,G,D) pre-scaled;
    kv_blk: (k, v).  bias: optional (Qc, kvc) additive mask — only the
    diagonal block pays for masking."""
    m_prev, l_prev, acc = carry
    k_blk, v_blk = kv_blk
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk).astype(jnp.float32)
    if bias is not None:
        s = s + bias
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk)
    acc = acc * corr[..., None] + pv.astype(jnp.float32)
    return (m_new, l_new, acc), None


def blockwise_attention(q, k, v, causal: bool, q_chunk: int = 1024,
                        kv_chunk: int = 1024, q_offset: int = 0):
    """Flash attention in pure XLA.  Causal-FLOP-optimal: query chunk i only
    visits KV chunks 0..ceil((q_offset+(i+1)*qc)/kvc)-1 (static per unrolled
    iteration).  Memory-lean: the softmax scale is folded into q before the
    matmul (d-sized instead of S²), and masking is an additive bias that is
    exactly zero on fully-visible blocks (fuses away) rather than a `where`
    pass over every score block."""
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, q_chunk, skv, kv_chunk)
    q = q * (1.0 / math.sqrt(hd))            # folded scale (d-sized, not S²)
    n_q = sq // q_chunk
    outs = []
    for i in range(n_q):
        q_blk = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        q_end = q_offset + (i + 1) * q_chunk if causal else skv
        n_kv = -(-min(q_end, skv) // kv_chunk)        # ceil
        kv_len = n_kv * kv_chunk
        k_i = jax.lax.dynamic_slice_in_dim(k, 0, kv_len, axis=1)
        v_i = jax.lax.dynamic_slice_in_dim(v, 0, kv_len, axis=1)
        # (n_kv, B, kvc, KV, D) scan layout.  NOTE (§Perf llama405 it0,
        # refuted): splitting masked diagonal blocks out of the scan to skip
        # the mask op on visible blocks INCREASED bytes-accessed by 12% —
        # the uniform scan fuses better; keep the single-scan structure.
        k_i = k_i.reshape(b, n_kv, kv_chunk, kvh, hd).swapaxes(0, 1)
        v_i = v_i.reshape(b, n_kv, kv_chunk, kvh, hd).swapaxes(0, 1)
        if causal:
            qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            kpos = (jnp.arange(n_kv)[:, None] * kv_chunk
                    + jnp.arange(kv_chunk)[None, :])      # (n_kv, kvc)
            bias = jnp.where(qpos[None, :, None] >= kpos[:, None, :],
                             0.0, NEG_INF).astype(jnp.float32)
            bias = bias[:, None, None, None, :, :]
        else:
            bias = jnp.zeros((n_kv, 1, 1, 1, 1, 1), jnp.float32)
        init = (jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, q_chunk), jnp.float32),
                jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            partial(_online_block_bias, q_blk=q_blk),
            init, (k_i, v_i, bias))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(0, 3, 1, 2, 4).astype(q.dtype))  # (B,Qc,KV,G,D)
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def _online_block_bias(carry, kv_blk, q_blk):
    k_blk, v_blk, bias = kv_blk
    return _online_block(carry, (k_blk, v_blk), q_blk, bias=bias)


# ----------------------------------------------------------------- decode ----

def decode_positions(cache_index, batch: int):
    """Normalize a decode cache index to a (B,) per-slot position vector.

    ``cache_index`` is either a scalar (synchronized batch: every sequence at
    the same depth — the train/dry-run calling convention) or already a (B,)
    vector of per-slot positions (ragged continuous batching)."""
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.full((batch,), idx)
    assert idx.shape == (batch,), (idx.shape, batch)
    return idx


def gather_pages(pool, page_table, positions=None):
    """Resolve a page pool into per-slot logical KV rows.

    pool: (P, page, KV, D) physical pages; page_table: (B, M) int32 page ids
    in logical order.  Returns (B, M*page, KV, D) where row ``pos`` of slot
    ``b`` is ``pool[page_table[b, pos // page], pos % page]``.

    ``positions`` (B,), when given, redirects table rows for logical pages
    past ``ceil((pos+1)/page)`` — allocated for the request's future decode
    but holding nothing attendable yet — to the scratch page (physical page
    0).  Every row those pages would contribute is masked to NEG_INF by the
    caller anyway, so logits are bitwise unchanged, but the gather's HBM
    reads for a short sequence shrink from the slot's full reservation to
    the pages it has actually written (repeated scratch-page reads hit the
    same lines).

    Only the pool persists in HBM; the gathered view is a per-step
    temporary — but it IS materialized at dense-equivalent size for the
    current batch, so transient decode memory grows with the (paged-enlarged)
    concurrent batch even though pinned memory does not.  The paged
    flash-decode kernel (``decode_attention(..., impl="pallas")``) walks the
    table block-by-block instead and never materializes this view.

    The gather clamps its ids (``mode="clip"``) and never changes one: every
    id a table holds is in ``[0, P)`` — ``PagedCache`` writes only page ids
    it allocated or the scratch page 0, and the dead-page redirect above
    (and the off-chip redirect of the sharded twins, which gather through
    here too) writes 0.  The default
    ``mode="fill"`` would add an in-bounds mask and a NaN select over the
    whole (B, M, page, KV, D) result, re-writing the gathered view every
    layer for a fill that never fires.  Every pool and scale gather on the
    paged read path uses the same mode."""
    b, m = page_table.shape
    page = pool.shape[1]
    if positions is not None:
        live = jnp.arange(m)[None, :] <= positions[:, None] // page  # (B, M)
        page_table = jnp.where(live, page_table, 0)
    k = jnp.take(pool, page_table, axis=0, mode="clip")  # (B, M, page, KV, D)
    return k.reshape(b, m * page, *pool.shape[2:])


def dequant_gathered(gathered, scale_pool, lt_or_table, b, rows, dtype):
    """Dequantize a gathered int8 KV view: ``gathered`` (B, S, KV, D) int8,
    ``scale_pool`` (P, page, KV) fp32, ``lt_or_table`` the (B, M) table the
    int8 view was gathered through — the scales resolve through the *same*
    indirection, so row and scale can never come from different pages."""
    sg = jnp.take(scale_pool, lt_or_table, axis=0, mode="clip").reshape(
        b, rows, scale_pool.shape[2])
    return (gathered.astype(jnp.float32) * sg[..., None]).astype(dtype)


def paged_gather_partials(q, k_pool, v_pool, page_table, positions,
                          page_offset, k_scale=None, v_scale=None):
    """Per-chip partial paged decode by XLA gather — the sharded-serving
    counterpart of the plain gather path, so gather/pallas parity holds on
    every backend (the Pallas twin is ``kernels.ops.paged_decode_partials``).

    q: (B, 1, KV, G, D); pools: one chip's LOCAL (P/n, page, KV, D) shard;
    page_table: (B, M) GLOBAL page ids; page_offset: global id of the local
    shard's first page.  Table entries outside ``[offset, offset + P/n)``
    are masked exactly like dead pages: their gather rows redirect to local
    page 0 and their scores to NEG_INF, so each chip materializes only its
    own dense-equivalent view and attends only to rows it owns.

    Returns the raw fp32 online-softmax triple ``(acc (B,1,KV,G,D),
    l (B,KV,G), m (B,KV,G))``; ``merge_paged_partials`` combines chips.  A
    chip owning no live page of a slot returns the identity element
    (acc=0, l=0, m=NEG_INF) — note the explicit ``where`` on p below: with
    every score at NEG_INF the naive ``exp(s - max)`` would be exp(0)=1.

    ``k_scale``/``v_scale`` (int8 pools): the local (P/n, page, KV) fp32
    scale shards — gathered rows dequantize through the same redirected
    table before the score/accumulate einsums."""
    hd = q.shape[-1]
    b, m = page_table.shape
    pn, page = k_pool.shape[:2]
    live = jnp.arange(m)[None, :] <= positions[:, None] // page    # (B, M)
    local = page_table - page_offset
    ok = live & (local >= 0) & (local < pn)
    lt = jnp.where(ok, local, 0)
    kg = gather_pages(k_pool, lt)
    vg = gather_pages(v_pool, lt)
    if k_scale is not None:
        kg = dequant_gathered(kg, k_scale, lt, b, m * page, jnp.float32)
        vg = dequant_gathered(vg, v_scale, lt, b, m * page, jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", q[:, 0], kg).astype(jnp.float32)
    s = s / math.sqrt(hd)
    rows = jnp.arange(m * page)[None, :]
    valid = (rows <= positions[:, None]) \
        & jnp.repeat(ok, page, axis=1)                             # (B, S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1)                                       # (B,KV,G)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - mx[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, vg.astype(jnp.float32))
    return acc[:, None], l, mx


def merge_paged_partials(acc, l, m, axis_name: str):
    """Cross-chip online-softmax merge (inside shard_map): combine per-chip
    raw triples into the full softmax with one pmax + two psums.

    acc: (B, 1, KV, G, D) unnormalized; l, m: (B, KV, G).  Chips with no
    live pages carry m = NEG_INF, so their weight exp(m - m*) is exactly 0.
    The denominator can only vanish if *no* chip saw a live row, which the
    scratch-page convention rules out (logical page 0 is live at every
    position >= 0, and freed slots' tables point at physical page 0)."""
    gm = jax.lax.pmax(m, axis_name)
    w = jnp.exp(m - gm)                                            # (B,KV,G)
    num = jax.lax.psum(acc * w[:, None, :, :, None], axis_name)
    den = jax.lax.psum(l * w, axis_name)
    return num / jnp.maximum(den, 1e-30)[:, None, :, :, None]


def decode_attention(q, k_cache, v_cache, cache_index, page_table=None,
                     impl: str = "gather", k_scale=None, v_scale=None):
    """q: (B,1,KV,G,D); attends to positions <= index.

    ``cache_index``: scalar or (B,) per-slot positions — each slot gets its
    own causal mask, so a ragged batch decodes in one call.

    caches: (B,Smax,KV,D) contiguous rows, or — when ``page_table`` (B, M)
    is given — (P,page,KV,D) pools resolved per slot through the table.  The
    gathered view preserves logical row order, so the masked softmax below is
    identical math to the contiguous path (bit-for-bit when M*page == Smax).

    ``impl`` selects the paged resolution strategy: ``"gather"`` (the XLA
    fallback — materializes the dense-equivalent view per step) or
    ``"pallas"`` (the ``repro.kernels.paged_decode`` flash kernel — walks
    the page table block-by-block, O(page) transient, matching this masked
    softmax within fp32 online-softmax tolerance).  Contiguous caches
    ignore ``impl``.

    ``k_scale``/``v_scale`` (paged int8 pools only): (P, page, KV) fp32
    absmax scales — the gather path dequantizes the gathered int8 view,
    the pallas path dequantizes in-register inside the kernel.
    """
    hd = q.shape[-1]
    pos = decode_positions(cache_index, q.shape[0])
    assert k_scale is None or page_table is not None, (
        "KV scales ride on the paged int8 page format")
    if page_table is not None:
        if impl == "pallas":
            from repro.kernels import ops as kops
            return kops.paged_decode_attention(q, k_cache, v_cache,
                                               page_table, pos,
                                               k_scale=k_scale,
                                               v_scale=v_scale)
        assert impl == "gather", impl
        b, m = page_table.shape
        page = k_cache.shape[1]
        k_cache = gather_pages(k_cache, page_table, pos)
        v_cache = gather_pages(v_cache, page_table, pos)
        if k_scale is not None:
            # dequantize through the same live-masked table as the rows
            live = jnp.arange(m)[None, :] <= pos[:, None] // page
            lt = jnp.where(live, page_table, 0)
            k_cache = dequant_gathered(k_cache, k_scale, lt, b,
                                       m * page, q.dtype)
            v_cache = dequant_gathered(v_cache, v_scale, lt, b,
                                       m * page, q.dtype)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_cache).astype(jnp.float32)
    s = s / math.sqrt(hd)
    valid = jnp.arange(k_cache.shape[1])[None, :] <= pos[:, None]  # (B,Smax)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache)


# ------------------------------------------------------------- full layers ----

def attention_block(p, cfg, x, *, impl: str = "blockwise", causal: bool = True,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    flat_heads: bool = False, tp_shardmap: bool = False):
    """Self-attention over a full sequence (train / prefill).  Returns (y, (k, v))."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = project_qkv(p, cfg, x, x, positions, positions,
                          flat_heads=flat_heads, tp_shardmap=tp_shardmap)
    if impl == "dense":
        y = dense_attention(q, k, v, causal)
    elif impl == "blockwise":
        y = blockwise_attention(q, k, v, causal, q_chunk, kv_chunk)
    elif impl == "seqsp":
        # sequence-sharded shard_map path (archs with heads ∤ model axis)
        from repro.parallel.seqattn import seq_sharded_attention
        assert causal, "seqsp path is causal-only"
        y = seq_sharded_attention(q, k, v, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        y = kops.flash_attention(q, k, v, causal=causal)
    else:
        raise ValueError(impl)
    return output_proj(p, cfg, y, tp_shardmap=tp_shardmap), (k, v)


def cross_attention_block(p, cfg, x, enc_kv):
    """Cross-attention: queries from x, keys/values precomputed (k, v) tuples."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    dtype = x.dtype
    q = _proj(p["wq"], x, dtype)
    q = q.reshape(b, s, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads,
                  cfg.resolved_head_dim)
    k, v = enc_kv
    y = blockwise_attention(q, k, v, causal=False)
    return output_proj(p, cfg, y)


def encode_kv(p, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    dtype = enc_out.dtype
    k = _proj(p["wk"], enc_out, dtype)
    v = _proj(p["wv"], enc_out, dtype)
    return k, v


def _scatter_decode_kv(cache, new, positions):
    """Per-slot cache write: cache (B,Smax,KV,D) <- new (B,1,KV,D) at
    positions (B,).  vmap of a length-1 dynamic_update_slice lowers to a
    batched scatter — one write per slot at its own depth."""
    return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))(cache, new.astype(cache.dtype), positions)


def _scatter_paged_kv(pool, new, page_table, positions):
    """Paged cache write: pool (P,page,KV,D) <- new (B,1,KV,D), slot b's
    token landing at ``pool[page_table[b, pos//page], pos % page]``.  One flat
    scatter for the whole ragged batch.  Freed slots' table rows point at the
    scratch page (physical page 0), so their masked writes never touch pages
    owned by live requests."""
    p_pages, page = pool.shape[:2]
    flat = pool.reshape(p_pages * page, *pool.shape[2:])
    page_ids = jnp.take_along_axis(
        page_table, (positions // page)[:, None], axis=1)[:, 0]
    idx = page_ids * page + positions % page
    flat = flat.at[idx].set(new[:, 0].astype(pool.dtype))
    return flat.reshape(pool.shape)


def scatter_paged_kv_local(pool, new, page_table, positions, page_offset):
    """Sharded paged cache write (inside shard_map): each chip applies only
    the writes that land in its own (P/n, page, KV, D) pool shard.

    Slot b's write page is ``table[b, pos // page]`` — a GLOBAL id; the chip
    owning it (``offset <= id < offset + P/n``) scatters the row at the
    local flat index, every other chip routes that slot's write one past the
    end of its shard and ``mode="drop"`` discards it.  Exactly one chip
    (or zero, for freed slots whose scratch page 0 lives on chip 0) commits
    each token, so the union of shards equals the single-device pool."""
    pn, page = pool.shape[:2]
    flat = pool.reshape(pn * page, *pool.shape[2:])
    page_ids = jnp.take_along_axis(
        page_table, (positions // page)[:, None], axis=1)[:, 0]
    local = page_ids - page_offset
    idx = jnp.where((local >= 0) & (local < pn),
                    local * page + positions % page, pn * page)
    flat = flat.at[idx].set(new[:, 0].astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def _scatter_chunk_paged(pool, new, dest):
    """Chunked-prefill pool write: pool (P,page,KV,D) <- new (B,C,KV,D), the
    chunk's C tokens landing at ``dest`` (B,C) flat pool rows (page *
    page_size + row, resolved host-side by ``PagedCache.chunk_dest``).
    Padding and shared-prefix positions arrive routed to flat index 0 — the
    scratch sink — whose content is never attended un-masked."""
    p_pages, page = pool.shape[:2]
    flat = pool.reshape(p_pages * page, *pool.shape[2:])
    flat = flat.at[dest.reshape(-1)].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype))
    return flat.reshape(pool.shape)


def scatter_chunk_paged_local(pool, new, dest, row_offset):
    """Sharded chunk/prefill pool write (inside shard_map): the local-window
    twin of ``_scatter_chunk_paged``, same routing as
    ``scatter_paged_kv_local`` but over precomputed flat rows.

    ``dest`` holds GLOBAL flat pool rows (page · page_size + row); the chip
    owning rows ``[row_offset, row_offset + P/n·page)`` commits them at the
    local flat index, every other chip routes them one past its shard end
    and ``mode="drop"`` discards the update.  Scratch-routed positions
    (flat row 0) land on chip 0's scratch page, exactly as on one chip."""
    pn, page = pool.shape[:2]
    rows = pn * page
    flat = pool.reshape(rows, *pool.shape[2:])
    local = dest.reshape(-1) - row_offset
    idx = jnp.where((local >= 0) & (local < rows), local, rows)
    flat = flat.at[idx].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def paged_gather_chunk_partials(q, k_pool, v_pool, page_table, qpos,
                                last_pos, page_offset,
                                k_scale=None, v_scale=None):
    """Per-chip partial chunked-prefill attention by XLA gather — the C-row
    generalization of ``paged_gather_partials`` (decode is the C=1 case
    with a plain ``col <= pos`` mask).

    q: (B, C, KV, G, D) chunk queries; pools: one chip's LOCAL
    (P/n, page, KV, D) shard; page_table: (B, M) GLOBAL ids; qpos: (B, C)
    each row's global position; last_pos: (B,) the chunk's last valid
    position (limits the gather to claimed pages and clamps padding rows);
    page_offset: global id of the shard's first page.  Non-local pages
    redirect to local page 0 with their scores at NEG_INF — dead-page
    semantics — and the causal mask is position-exact per row
    (``col <= min(qpos, last_pos)``), matching the single-chip chunk block.

    Returns the raw fp32 triple ``(acc (B,C,KV,G,D), l (B,KV,G,C),
    m (B,KV,G,C))`` for ``merge_paged_chunk_partials``.

    ``k_scale``/``v_scale`` (int8 pools): the local (P/n, page, KV) fp32
    scale shards — gathered rows dequantize through the same redirected
    table before the score/accumulate einsums."""
    hd = q.shape[-1]
    b, m = page_table.shape
    pn, page = k_pool.shape[:2]
    live = jnp.arange(m)[None, :] <= last_pos[:, None] // page    # (B, M)
    local = page_table - page_offset
    ok = live & (local >= 0) & (local < pn)
    lt = jnp.where(ok, local, 0)
    kg = gather_pages(k_pool, lt)
    vg = gather_pages(v_pool, lt)
    if k_scale is not None:
        kg = dequant_gathered(kg, k_scale, lt, b, m * page, jnp.float32)
        vg = dequant_gathered(vg, v_scale, lt, b, m * page, jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kg).astype(jnp.float32)
    s = s / math.sqrt(hd)
    cols = jnp.arange(m * page)
    valid = (cols[None, None, :]
             <= jnp.minimum(qpos, last_pos[:, None])[:, :, None]) \
        & jnp.repeat(ok, page, axis=1)[:, None, :]                # (B, C, S)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1)                                   # (B,KV,G,C)
    pr = jnp.where(valid[:, None, None, :, :],
                   jnp.exp(s - mx[..., None]), 0.0)
    l = pr.sum(axis=-1)                                        # (B,KV,G,C)
    acc = jnp.einsum("bkgqs,bskd->bqkgd", pr, vg.astype(jnp.float32))
    return acc, l, mx


def merge_paged_chunk_partials(acc, l, m, axis_name: str):
    """Cross-chip online-softmax merge for C-row chunk partials — the chunk
    generalization of ``merge_paged_partials`` (same pmax + two psums, the
    row dim riding along).

    acc: (B, C, KV, G, D) unnormalized; l, m: (B, KV, G, C).  The
    denominator can only vanish on padding rows past ``last_pos`` — whose
    outputs the caller discards — so the 1e-30 floor never perturbs a
    consumed row."""
    gm = jax.lax.pmax(m, axis_name)
    w = jnp.exp(m - gm)                                        # (B,KV,G,C)
    num = jax.lax.psum(acc * w.transpose(0, 3, 1, 2)[..., None], axis_name)
    den = jax.lax.psum(l * w, axis_name)
    return num / jnp.maximum(den, 1e-30).transpose(0, 3, 1, 2)[..., None]


def attention_prefill_chunk_block(p, cfg, x, k_pool, v_pool, start_pos, dest,
                                  page_table, last_pos,
                                  k_scale=None, v_scale=None,
                                  mesh=None, kv_axis: str = "model",
                                  dp_axis=None):
    """Chunked-prefill attention with prior cache: a (B, C) token chunk at a
    per-request position offset writes its K/V into the paged pools and
    attends causally over everything written so far — the pages landed by
    chunks ``0..k-1`` plus the chunk itself — through the same
    ``gather_pages`` machinery the paged decode fallback uses.

    x: (B, C, d) chunk activations; start_pos: (B,) global position of each
    request's chunk start; dest: (B, C) flat pool write indices
    (``PagedCache.chunk_dest`` — padding/shared positions scratch-routed);
    page_table: the slots' REAL (B, M) table rows (``PagedCache.table_row``,
    not the shielded decode view); last_pos: (B,) last valid global position
    of the chunk — masks padding rows and limits the gather to pages the
    slot has actually claimed.  Row ``i``'s causal mask is position-exact
    (``col <= start_pos + i``), so within-chunk causality needs no separate
    path.  Returns (y, new_k_pool, new_v_pool).

    The math matches whole-prompt dense prefill op-for-op (same einsum
    contractions, fp32 masked softmax, NEG_INF mask exp-underflowing to
    exactly 0.0), which is what makes chunked and whole-prompt prefill
    bitwise-identical token streams rather than merely close ones.

    ``k_scale``/``v_scale`` (int8 pools): the chunk's K/V quantize before
    the scatter — scales land through the same ``dest`` indices — and the
    gathered views dequantize before attention, so a chunk attends its own
    rows exactly as a later decode step will read them (round-tripped
    through int8).  Returns a 5-tuple including the new scale arrays.

    ``mesh`` (kv_pages-sharded pools): the scatter + attend run through the
    unified shard_map primitive instead —
    ``repro.parallel.pagedkv.sharded_prefill_chunk_attention`` (per-chip
    ``mode="drop"`` local writes, C-row local partials, partial-softmax
    merge over ``kv_axis``; ``dp_axis`` shards the chunk batch on 2-D
    meshes)."""
    quantized = k_scale is not None
    b, c = x.shape[:2]
    qpos = start_pos[:, None] + jnp.arange(c)[None, :]            # (B, C)
    q, k, v = project_qkv(p, cfg, x, x, qpos, qpos)
    if quantized:
        from repro.kernels.quant import quantize_kv
        k, sk = quantize_kv(k)
        v, sv = quantize_kv(v)
    if mesh is not None:
        from repro.parallel.pagedkv import sharded_prefill_chunk_attention
        out = sharded_prefill_chunk_attention(
            mesh, kv_axis, q, k, v, dest, k_pool, v_pool, page_table,
            start_pos, last_pos,
            k_scale=k_scale, v_scale=v_scale,
            k_scale_new=sk if quantized else None,
            v_scale_new=sv if quantized else None, dp_axis=dp_axis)
        if quantized:
            y, k_pool, v_pool, k_scale, v_scale = out
            return (output_proj(p, cfg, y), k_pool, v_pool,
                    k_scale, v_scale)
        y, k_pool, v_pool = out
        return output_proj(p, cfg, y), k_pool, v_pool
    if quantized:
        k_scale = _scatter_chunk_paged(k_scale, sk, dest)
        v_scale = _scatter_chunk_paged(v_scale, sv, dest)
    k_pool = _scatter_chunk_paged(k_pool, k, dest)
    v_pool = _scatter_chunk_paged(v_pool, v, dest)
    kg = gather_pages(k_pool, page_table, last_pos)               # (B,S,KV,D)
    vg = gather_pages(v_pool, page_table, last_pos)
    if quantized:
        m, page = page_table.shape[1], k_pool.shape[1]
        live = jnp.arange(m)[None, :] <= last_pos[:, None] // page
        lt = jnp.where(live, page_table, 0)
        kg = dequant_gathered(kg, k_scale, lt, b, m * page, x.dtype)
        vg = dequant_gathered(vg, v_scale, lt, b, m * page, x.dtype)
    hd = q.shape[-1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kg).astype(jnp.float32)
    s = s / math.sqrt(hd)
    cols = jnp.arange(kg.shape[1])
    # padding rows (qpos > last_pos) are clamped to last_pos so they never
    # attend rows beyond claimed pages; their outputs are discarded and
    # their writes were scratch-routed by dest
    valid = cols[None, None, :] \
        <= jnp.minimum(qpos, last_pos[:, None])[:, :, None]       # (B, C, S)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    y = jnp.einsum("bkgqs,bskd->bqkgd", probs, vg)
    if quantized:
        return output_proj(p, cfg, y), k_pool, v_pool, k_scale, v_scale
    return output_proj(p, cfg, y), k_pool, v_pool


def attention_decode_block(p, cfg, x, k_cache, v_cache, cache_index,
                           rope: bool = True, page_table=None,
                           decode_impl: str = "gather", mesh=None,
                           kv_axis: str = "model", dp_axis=None,
                           k_scale=None, v_scale=None):
    """One-token decode.  x: (B,1,d).  ``cache_index`` is a scalar
    (synchronized batch) or a (B,) vector of per-slot positions (ragged
    continuous batching: per-slot RoPE, scatter-write, and causal mask).

    caches are (B,Smax,KV,D) contiguous rows, or — with ``page_table``
    (B, M) — (P,page,KV,D) physical pools indexed through the table (the
    paged backend of ``repro.serve.kvcache``), resolved per ``decode_impl``
    ("gather": XLA dense-equivalent view; "pallas": page-table-walking
    flash kernel).  With ``mesh`` (paged only), the pools are sharded P/n
    along ``kv_axis`` and the scatter-write + table resolution run under
    shard_map with a cross-chip partial-softmax merge
    (``repro.parallel.pagedkv``).  Returns (y, new_k_cache, new_v_cache).

    ``k_scale``/``v_scale`` (paged int8 pools, ``kv_dtype="int8"``): the
    (P, page, KV) fp32 scale arrays — the new token's K/V row quantizes on
    write (row + scale land through the same table-resolved index) and the
    read path dequantizes per ``decode_impl``.  Returns the 5-tuple
    (y, k_cache, v_cache, k_scale, v_scale)."""
    b = x.shape[0]
    per_slot = jnp.ndim(cache_index) > 0
    pos = decode_positions(cache_index, b)
    q, k, v = project_qkv(p, cfg, x, x, pos[:, None], pos[:, None], rope=rope)
    quantized = k_scale is not None
    assert not quantized or page_table is not None, (
        "KV scales ride on the paged int8 page format")
    if page_table is not None:
        if mesh is not None:
            from repro.parallel.pagedkv import sharded_paged_decode_attention
            out = sharded_paged_decode_attention(
                mesh, kv_axis, q, k, v, k_cache, v_cache, page_table, pos,
                decode_impl, k_scale=k_scale, v_scale=v_scale,
                dp_axis=dp_axis)
            if quantized:
                y, k_cache, v_cache, k_scale, v_scale = out
            else:
                y, k_cache, v_cache = out
        else:
            if quantized:
                from repro.kernels.quant import quantize_kv
                k, sk = quantize_kv(k)
                v, sv = quantize_kv(v)
                k_scale = _scatter_paged_kv(k_scale, sk, page_table, pos)
                v_scale = _scatter_paged_kv(v_scale, sv, page_table, pos)
            k_cache = _scatter_paged_kv(k_cache, k, page_table, pos)
            v_cache = _scatter_paged_kv(v_cache, v, page_table, pos)
            y = decode_attention(q, k_cache, v_cache, pos,
                                 page_table=page_table, impl=decode_impl,
                                 k_scale=k_scale, v_scale=v_scale)
        y = constrain(y, ("batch", None, None, None, None))
        if quantized:
            return output_proj(p, cfg, y), k_cache, v_cache, k_scale, v_scale
        return output_proj(p, cfg, y), k_cache, v_cache
    # Pin the cache sharding (batch over DP, sequence over the model axis —
    # flash-decoding style).  Without this GSPMD may back-propagate the
    # attention head sharding onto the cache and materialize a full-cache
    # reshard (observed: 2×38 GB all-gathers per step on qwen3 decode_32k).
    cache_axes = ("batch", "kv_seq", "kv_heads", None)
    if per_slot:
        k_cache = _scatter_decode_kv(k_cache, k, pos)
        v_cache = _scatter_decode_kv(v_cache, v, pos)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_index, axis=1)
    k_cache = constrain(k_cache, cache_axes)
    v_cache = constrain(v_cache, cache_axes)
    y = decode_attention(q, k_cache, v_cache, pos)
    y = constrain(y, ("batch", None, None, None, None))
    return output_proj(p, cfg, y), k_cache, v_cache
