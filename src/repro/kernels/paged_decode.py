"""Paged flash-decode Pallas TPU kernel: page-table-walking attention.

The XLA paged decode (``repro.models.attention.gather_pages``) resolves the
page table by materializing a dense-equivalent ``(B, M*page, KV, D)`` K and V
view every step — a transient that scales with the paged-enlarged concurrent
batch even though *pinned* pool bytes do not, which is exactly the
memory-movement waste the serving story is trying to kill.  This kernel walks
the indirection instead of materializing it:

* Grid ``(B, M)`` with the logical-page dimension innermost — TPU grids
  iterate the trailing dim sequentially, so each slot's program streams its
  pages one block at a time while the online-softmax running state
  (m, l, acc) lives in VMEM scratch across page steps.
* One block is one whole physical page, ``(1, page, KV, D)``: its last two
  dims are the pool's own, which is the tiling Mosaic accepts (a one-head
  ``(1, page, 1, D)`` block is refused).  In the body the page is viewed as
  ``page*KV`` key rows, every query head of the slot scores all of them in
  one matmul, and rows of other KV heads are masked out — extra MXU work
  that a memory-bound decode does not feel.
* The **page table walk happens in the BlockSpec index maps** via scalar
  prefetch (``PrefetchScalarGridSpec``): the (B, M) table, (B,) position
  vector, and (1,) local-page offset are SMEM-resident before the body runs,
  and the K/V index map for grid point (b, j) resolves physical page
  ``table[b, j] - offset`` directly — the pipeline DMAs exactly one page of
  each pool per step, so per-step transient memory is O(block) =
  O(page·KV·D), not O(B·M·page·KV·D).
* **Early exit**: pages wholly past the slot's position carry no live rows,
  and — under sharded serving — pages outside this chip's local window
  ``[offset, offset + P_local)`` belong to another chip's pool shard.  Both
  kinds redirect their index map to local page 0 (consecutive grid steps
  with an unchanged block index elide the DMA) and ``pl.when`` skips their
  compute entirely, so a slot at position p pays for the live pages *this
  chip owns*, regardless of its table width M.
* The masked-softmax math matches ``decode_attention``'s reference: scores
  are fp32, rows past the slot's position are masked to NEG_INF *before* the
  running max, and the final normalization divides once at the last page.

**Sharded serving** (``repro.parallel.pagedkv``) runs one kernel instance
per chip over its (P/n, page, KV, D) pool shard with ``page_offset =
chip * P/n`` and ``partials=True``: instead of the normalized output each
chip emits its raw online-softmax triple — unnormalized ``acc`` (B, KV, G,
D), row sum ``l`` and running max ``m`` (B, KV, G) — and the caller combines
chips with one psum-style merge::

    m*  = pmax(m);  w = exp(m - m*)
    out = psum(acc * w) / psum(l * w)

A chip that owns no live page of a slot contributes (acc=0, l=0,
m=NEG_INF) — exactly the online-softmax identity element, so its merge
weight is zero.

Layouts (model code adapts via ``repro.kernels.ops``):
  q:          (B, KV, G, D)   one query token per slot, grouped GQA
  k/v pools:  (P, page, KV, D) physical pages; page 0 is the scratch page
  page_table: (B, M) int32    logical -> physical page ids (GLOBAL ids even
                              when the pool argument is a local shard)
  positions:  (B,) int32      per-slot decode position (the row just written)
  out:        (B, KV, G, D)   — or (acc, l, m) when ``partials=True``

Shape assumptions: the page block wants page·KV key rows aligned to the
(8, 128) fp32 tiling, i.e. serve with page_size >= 8 and head_dim a
multiple of 128 (tiny test shapes still run in interpret mode).  The page
table, positions, and offset ride in SMEM: B·(M+1)+1 int32 scalars per
dispatch.  Int8 scales travel as VMEM blocks through the same page walk,
so SMEM use does not grow with the pool.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pt_ref, pos_ref, off_ref, q_ref, k_ref, v_ref, *refs,
            scale: float, page: int, kv: int, g: int, n_pages: int,
            p_local: int, partials: bool, quantized: bool):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    if partials:
        o_ref, l_ref, mx_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[b]
    local = pt_ref[b, j] - off_ref[0]

    # early exit: a page whose first row is past the slot's position has no
    # live rows, and a page outside this chip's [offset, offset+P_local)
    # window lives in another chip's pool shard (its DMA was already
    # redirected to local page 0 by the index map); skip compute entirely
    @pl.when((j * page <= pos) & (local >= 0) & (local < p_local))
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale               # (KV*G, D)
        # key row c of the page view is (page row c // KV, kv head c % KV)
        k = k_ref[0].astype(jnp.float32).reshape(page * kv, -1)
        v = v_ref[0].astype(jnp.float32).reshape(page * kv, -1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ck = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        rows = j * page + ck // kv
        live = (ck % kv == rq // g) & (rows <= pos)      # (KV*G, page*KV)
        if quantized:
            # in-register dequant: a key row's scale multiplies its score
            # and a value row's scale its probability, so the int8 tile is
            # never rescaled in full
            s = s * ks_ref[0]
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0]
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        if partials:
            # raw online-softmax triple: the caller's cross-chip merge
            # normalizes once, after combining every chip's contribution
            o_ref[0] = acc_scr[...].astype(o_ref.dtype)
            l_ref[0] = l_scr[...]
            mx_ref[0] = m_scr[...]
        else:
            denom = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_flash_decode(q, k_pool, v_pool, page_table, positions,
                       page_offset=None, k_scale=None, v_scale=None,
                       partials: bool = False, interpret: bool = False):
    """q: (B, KV, G, D); k/v pools: (P, page, KV, D); page_table: (B, M)
    int32; positions: (B,) int32.  Returns (B, KV, G, D).

    ``page_offset`` (scalar int32, default 0): global page id of the pool
    argument's first page — table entries outside ``[offset, offset + P)``
    are treated exactly like dead pages (index-map redirect + compute skip).
    ``partials=True`` returns the raw fp32 online-softmax triple
    ``(acc (B,KV,G,D), l (B,KV,G), m (B,KV,G))`` instead of the normalized
    output, for the cross-chip partial-softmax merge of sharded serving.

    ``k_scale``/``v_scale`` (int8 pools): (P, page, KV) fp32 absmax scales
    for the quantized page format.  Each page's ``page*KV`` scales ride as a
    VMEM block resolved by the same page walk as the int8 tile, and the body
    applies them to scores and probabilities, so HBM traffic stays at the
    int8 tile plus one scale row per grid step."""
    b, kv, g, d = q.shape
    p_local, page = k_pool.shape[:2]
    assert k_pool.shape == v_pool.shape and k_pool.shape[2:] == (kv, d), (
        q.shape, k_pool.shape, v_pool.shape)
    m = page_table.shape[1]
    assert page_table.shape == (b, m) and positions.shape == (b,), (
        page_table.shape, positions.shape, b)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k/v scales travel together"
    if quantized:
        assert k_scale.shape == v_scale.shape == (p_local, page, kv), (
            k_scale.shape, k_pool.shape)
    r, c = kv * g, page * kv
    if page_offset is None:
        page_offset = 0
    off = jnp.asarray(page_offset, jnp.int32).reshape(1)

    def row_map(b_, j, pt, pos, off):
        return (b_, 0, 0)

    def page_map(b_, j, pt, pos, off):
        # the page-table walk: dead pages (past the slot's position) and
        # non-local pages (outside this chip's pool shard) resolve to local
        # page 0 so repeated skipped steps elide their DMA
        local = pt[b_, j] - off[0]
        ok = (j * page <= pos[b_]) & (local >= 0) & (local < p_local)
        return (jnp.where(ok, local, 0), 0, 0, 0)

    def scale_map(b_, j, pt, pos, off):
        return page_map(b_, j, pt, pos, off)[:3]

    in_specs = [pl.BlockSpec((1, r, d), row_map),
                pl.BlockSpec((1, page, kv, d), page_map),
                pl.BlockSpec((1, page, kv, d), page_map)]
    operands = [q.reshape(b, r, d), k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, c), scale_map)] * 2
        operands += [s.astype(jnp.float32).reshape(p_local, 1, c)
                     for s in (k_scale, v_scale)]
    out_specs = [pl.BlockSpec((1, r, d), row_map)]
    out_shape = [jax.ShapeDtypeStruct(
        (b, r, d), jnp.float32 if partials else q.dtype)]
    if partials:
        out_specs += [pl.BlockSpec((1, r, 1), row_map)] * 2
        out_shape += [jax.ShapeDtypeStruct((b, r, 1), jnp.float32)] * 2
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), page=page, kv=kv, g=g,
        n_pages=m, p_local=p_local, partials=partials, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b, m),
        in_specs=in_specs,
        out_specs=out_specs if partials else out_specs[0],
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, d), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape if partials else out_shape[0],
        interpret=interpret, name="paged_decode",
    )(page_table.astype(jnp.int32), positions.astype(jnp.int32), off,
      *operands)
    if not partials:
        return out.reshape(b, kv, g, d)
    acc, l, mx = out
    return (acc.reshape(b, kv, g, d), l.reshape(b, kv, g),
            mx.reshape(b, kv, g))
