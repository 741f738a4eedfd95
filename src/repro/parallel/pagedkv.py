"""Sharded paged serving: ``kv_pages``-partitioned pools under shard_map.

The paged KV pool's leading (P) dim carries the ``kv_pages`` logical axis
(``repro.parallel.sharding.default_rules`` maps it to the ``model`` mesh
axis), so an inference mesh of n chips pins P/n pages each — pool HBM
scales *down* with the mesh instead of being replicated.  Chip c owns the
global page-id range ``[c*P/n, (c+1)*P/n)``; the (B, M) page table and the
per-step q/K/V stay replicated (B·M int32 + a token or a chunk per slot —
noise next to the pool).

**One primitive, three paths.**  Every pool access in the serving stack —
decode, whole-prompt prefill, and chunked prefill — is built from the same
three shard_map verbs:

1. **Local scatter-write** — the chip owning the write row commits it at
   its local flat index; every other chip's write is ``mode="drop"``-
   discarded (``attention.scatter_paged_kv_local`` for table-resolved
   decode writes, ``attention.scatter_chunk_paged_local`` for the flat-row
   prefill/chunk destinations).  No path leaves a pool write to GSPMD, so
   no dispatch can materialize a replicated O(P) pool transient.
2. **Local partial attention** — each chip attends only to pages inside
   its window, treating non-local pages exactly like dead pages: the
   Pallas kernel's index map redirects them to local page 0 and
   ``pl.when`` skips their compute (``kernels.ops.paged_decode_partials``),
   and the XLA gather twins mask them to NEG_INF
   (``attention.paged_gather_partials`` for one-token decode,
   ``attention.paged_gather_chunk_partials`` for C-row chunks).  Either
   way the chip emits the raw online-softmax triple (acc, l, m).
3. **Partial-softmax merge** — one pmax + two psums over the *pool* axis
   reconstruct the exact softmax over the union of chips
   (``attention.merge_paged_partials`` / ``merge_paged_chunk_partials``):
   ``out = psum(acc · exp(m - pmax(m))) / psum(l · exp(m - pmax(m)))``.

**2-D batch × pages meshes** (``dp_axis``): the pool shards P/n over the
pool axis only and is *replicated* across the DP axis; the batch dims of
q / page-table / positions shard over DP.  Writes must keep the DP
replicas of each pool shard bitwise identical, so the (tiny) per-step
write operands are made full-batch on every replica — decode
``all_gather``s them over DP inside the body, prefill/chunk declare them
replicated in their in_specs — and every replica applies the *full*
batch's writes to its shard.  Attention then runs only on the replica's
own batch shard, and the softmax merge psums over the pool axis alone:
the merge runs per DP replica, so merge traffic does not grow with the
DP width.

The merge moves O(B·KV·G·(D+2)) fp32 per layer over ICI — independent of
both the pool width and the sequence length, the flash-decoding property
that makes the page dimension the right thing to shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.parallel.mesh import mesh_axis_size
from repro.parallel.sharding import default_rules, spec_for

# logical axes of a per-layer-stacked page pool (L, P, page, KV, D); only
# kv_pages resolves to a mesh axis — the page/head/dim axes stay local so
# each chip holds whole pages (the kernel's block unit)
POOL_LOGICAL_AXES = ("layers", "kv_pages", None, None, None)

# the int8 page format's scale arrays (L, P, page, KV) drop the D axis but
# keep the page-partitioned leading dims: each chip holds exactly the scales
# of the pages it owns, so local dequant never reads a remote scale
SCALE_LOGICAL_AXES = POOL_LOGICAL_AXES[:4]


def chip_of_page(pid: int, pages_per_chip: int) -> int:
    """The chip owning global page id ``pid`` under the contiguous-range
    P/n split (chip c owns ``[c*P/n, (c+1)*P/n)``).  Shared by the
    allocator's per-chip free lists and the chip-failure drain path, so
    page->chip routing can never disagree between alloc and recovery."""
    return pid // pages_per_chip


def chip_page_range(chip: int, pages_per_chip: int) -> range:
    """The global page-id range chip ``chip`` owns (scratch page 0 included
    when chip 0 — callers that mean *usable* pages must skip id 0)."""
    return range(chip * pages_per_chip, (chip + 1) * pages_per_chip)


def kv_pool_spec(mesh, pool_shape, rules=None,
                 axis: str = None) -> PartitionSpec:
    """PartitionSpec for a (L, P, page, KV, D) pool: ``kv_pages`` -> mesh.

    ``axis`` overrides the rule's target mesh axis (PagedCache passes its
    ``kv_axis`` so a non-default axis name still shards the pool).  On a
    2-D (DP × pool) mesh the spec touches only the pool axis — the pool is
    replicated across DP by construction."""
    rules = dict(rules if rules is not None
                 else default_rules(mesh.axis_names))
    if axis is not None:
        rules["kv_pages"] = axis
    return spec_for(POOL_LOGICAL_AXES, pool_shape, rules, mesh)


def kv_pool_sharding(mesh, pool_shape, rules=None,
                     axis: str = None) -> NamedSharding:
    return NamedSharding(mesh, kv_pool_spec(mesh, pool_shape, rules, axis))


def kv_scale_spec(mesh, scale_shape, rules=None,
                  axis: str = None) -> PartitionSpec:
    """PartitionSpec for a (L, P, page, KV) scale array: same ``kv_pages``
    partitioning as its pool, minus the D axis."""
    rules = dict(rules if rules is not None
                 else default_rules(mesh.axis_names))
    if axis is not None:
        rules["kv_pages"] = axis
    return spec_for(SCALE_LOGICAL_AXES, scale_shape, rules, mesh)


def kv_scale_sharding(mesh, scale_shape, rules=None,
                      axis: str = None) -> NamedSharding:
    return NamedSharding(mesh, kv_scale_spec(mesh, scale_shape, rules, axis))


def _dp_or_none(mesh, dp_axis, batch: int):
    """Resolve the effective DP axis for a dispatch: present in the mesh,
    wider than 1, and dividing the dispatch's batch dim.  Group sizes are
    dynamic (an engine round stacks however many slots progressed), so a
    non-dividing group simply runs replicated across DP — a per-trace
    static decision, never a runtime branch."""
    if dp_axis is None:
        return None
    ndp = mesh_axis_size(mesh, dp_axis)
    return dp_axis if ndp > 1 and batch % ndp == 0 else None


def sharded_paged_decode_attention(mesh, axis: str, q, k_new, v_new,
                                   k_pool, v_pool, page_table, positions,
                                   decode_impl: str = "gather",
                                   k_scale=None, v_scale=None,
                                   dp_axis: str = None):
    """One layer's sharded paged decode: scatter the new token into the
    owning chip's pool shard, compute per-chip softmax partials, merge.

    q: (B, 1, KV, G, D); k_new/v_new: (B, 1, KV, D) this step's projected
    K/V; pools: (P, page, KV, D) GLOBAL views sharded P/n over ``axis``;
    page_table: (B, M) global ids; positions: (B,).  Returns
    (y (B,1,KV,G,D), new_k_pool, new_v_pool) with the pools still sharded.

    ``decode_impl`` picks the per-chip partial producer: ``"pallas"`` (the
    page-table-walking kernel with its local window) or ``"gather"`` (XLA
    local-masked gather) — both feed the identical merge, so the two impls
    stay in parity sharded exactly as they do on one chip.

    ``k_scale``/``v_scale`` (quantized int8 pools): (P, page, KV) fp32
    scale arrays sharded exactly like the pools.  The new token's float K/V
    is quantized *inside* the shard_map body (replicated, deterministic —
    every chip computes the identical (q, scale) pair) and the owning chip
    commits both the int8 row and its scale with the same ``mode="drop"``
    routing; the partial producers then dequantize locally.  Returns a
    5-tuple ``(y, k_pool, v_pool, k_scale, v_scale)``.

    ``dp_axis`` (2-D batch × pages mesh): q/table/positions shard their
    batch dim over DP while the pool stays sharded over ``axis`` only.
    Each replica ``all_gather``s the write operands over DP and applies the
    full batch's writes to its pool shard (keeping DP replicas bitwise
    identical), then attends its own batch shard with the merge psumming
    over ``axis`` alone — the per-DP-replica merge."""
    from repro.kernels import ops as kops
    from repro.models import attention as attn

    n = mesh_axis_size(mesh, axis)
    p_total = k_pool.shape[0]
    assert p_total % n == 0, (
        f"page pool P={p_total} must divide the {axis!r} axis ({n}); "
        "PagedCache pads the pool up to a multiple of the mesh size")
    pn = p_total // n
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k/v scales travel together"
    dp = _dp_or_none(mesh, dp_axis, q.shape[0])

    def partials(q, kp, vp, pt, pos, off, ks, vs):
        if decode_impl == "pallas":
            return kops.paged_decode_partials(q, kp, vp, pt, pos, off,
                                              k_scale=ks, v_scale=vs)
        assert decode_impl == "gather", decode_impl
        return attn.paged_gather_partials(q, kp, vp, pt, pos, off,
                                          k_scale=ks, v_scale=vs)

    def full_batch(*xs):
        # 2-D meshes: the write must apply identically on every DP replica
        # of a pool shard, so the (tiny) write operands go full-batch
        if dp is None:
            return xs
        return tuple(jax.lax.all_gather(x, dp, axis=0, tiled=True)
                     for x in xs)

    def body(q, kn, vn, pt, pos, kp, vp):
        off = (jax.lax.axis_index(axis) * pn).astype(jnp.int32)
        wkn, wvn, wpt, wpos = full_batch(kn, vn, pt, pos)
        kp = attn.scatter_paged_kv_local(kp, wkn, wpt, wpos, off)
        vp = attn.scatter_paged_kv_local(vp, wvn, wpt, wpos, off)
        acc, l, m = partials(q, kp, vp, pt, pos, off, None, None)
        y = attn.merge_paged_partials(acc, l, m, axis).astype(q.dtype)
        return y, kp, vp

    def body_quant(q, kn, vn, pt, pos, kp, vp, ks, vs):
        from repro.kernels.quant import quantize_kv
        off = (jax.lax.axis_index(axis) * pn).astype(jnp.int32)
        wkn, wvn, wpt, wpos = full_batch(kn, vn, pt, pos)
        qk, sk = quantize_kv(wkn)
        qv, sv = quantize_kv(wvn)
        kp = attn.scatter_paged_kv_local(kp, qk, wpt, wpos, off)
        vp = attn.scatter_paged_kv_local(vp, qv, wpt, wpos, off)
        ks = attn.scatter_paged_kv_local(ks, sk, wpt, wpos, off)
        vs = attn.scatter_paged_kv_local(vs, sv, wpt, wpos, off)
        acc, l, m = partials(q, kp, vp, pt, pos, off, ks, vs)
        y = attn.merge_paged_partials(acc, l, m, axis).astype(q.dtype)
        return y, kp, vp, ks, vs

    bsp = PartitionSpec(dp) if dp is not None else PartitionSpec()
    sh = PartitionSpec(axis)
    if quantized:
        fn = jax.shard_map(body_quant, mesh=mesh,
                           in_specs=(bsp, bsp, bsp, bsp, bsp, sh, sh, sh, sh),
                           out_specs=(bsp, sh, sh, sh, sh), check_vma=False)
        return fn(q, k_new, v_new, page_table, positions, k_pool, v_pool,
                  k_scale, v_scale)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bsp, bsp, bsp, bsp, bsp, sh, sh),
                       out_specs=(bsp, sh, sh), check_vma=False)
    return fn(q, k_new, v_new, page_table, positions, k_pool, v_pool)


def sharded_write_prefill(mesh, axis: str, layers, kv_block, dest):
    """Whole-prompt prefill writes as the primitive's local scatter — the
    sharded twin of ``PagedCache.staged_write_prefill``'s flat write.

    layers: the per-layer pool pytree — (L, P, page, KV, D) pools and, for
    int8, (L, P, page, KV) scale arrays — sharded P/n over ``axis``.
    kv_block: a matching pytree of (L, n, Sblk, ...) staged values (already
    quantized for int8 pools, so scales scatter through the same indices).
    dest: (n, Sblk) GLOBAL flat pool rows (page·page_size + row, masked
    positions scratch-routed to 0 by ``PagedCache.prefill_dest``).

    Each chip translates the global rows into its own window
    ``[chip·P/n·page, (chip+1)·P/n·page)`` and commits in-window rows at
    their local flat index; out-of-window rows route one past the shard end
    and ``mode="drop"`` discards them.  The per-chip transient is the
    replicated (n, Sblk) block — O(group·block) — never the O(P) replicated
    pool that GSPMD's partitioned flat scatter may stage
    (``PagedCache.gspmd_write_prefill`` keeps that path measurable).

    On a 2-D mesh the block is replicated across DP (in_specs), so every DP
    replica of a pool shard applies the identical full-group write and the
    replicas stay bitwise equal."""
    sample = jax.tree.leaves(layers)[0]
    p_total, page = sample.shape[1], sample.shape[2]
    n = mesh_axis_size(mesh, axis)
    assert p_total % n == 0, (p_total, n)
    rows = (p_total // n) * page

    def body(layers, kv_block, dest):
        start = (jax.lax.axis_index(axis) * rows).astype(jnp.int32)
        local = dest - start
        idx = jnp.where((local >= 0) & (local < rows), local, rows)

        def write(pool, small):
            flat = pool.reshape(pool.shape[0], rows, *pool.shape[3:])
            flat = flat.at[:, idx].set(small.astype(pool.dtype),
                                       mode="drop")
            return flat.reshape(pool.shape)

        return jax.tree.map(write, layers, kv_block)

    sh = jax.tree.map(lambda _: PartitionSpec(None, axis), layers)
    rep = jax.tree.map(lambda _: PartitionSpec(), kv_block)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(sh, rep, PartitionSpec()),
                       out_specs=sh, check_vma=False)
    return fn(layers, kv_block, dest)


def sharded_prefill_chunk_attention(mesh, axis: str, q, k_new, v_new, dest,
                                    k_pool, v_pool, page_table, start_pos,
                                    last_pos, k_scale=None, v_scale=None,
                                    k_scale_new=None, v_scale_new=None,
                                    dp_axis: str = None):
    """One layer's chunked-prefill scatter + attention under the primitive:
    the sharded twin of the ``_scatter_chunk_paged`` + ``gather_pages``
    body of ``attention.attention_prefill_chunk_block``.

    q: (B, C, KV, G, D) the chunk's queries; k_new/v_new: (B, C, KV, D)
    its projected K/V (already int8-quantized for quantized pools, with
    ``k_scale_new``/``v_scale_new`` the (B, C, KV) fp32 scales); dest:
    (B, C) GLOBAL flat pool rows; pools (P, page, KV, D) sharded P/n over
    ``axis``; page_table: (B, M) REAL global rows; start_pos/last_pos: (B,).

    Writes are the same local flat scatter as prefill
    (``attention.scatter_chunk_paged_local``); attention generalizes the
    decode partials to C query rows with the chunk's position-exact causal
    mask (``attention.paged_gather_chunk_partials``) and merges with the
    C-row merge.  Returns (y (B,C,KV,G,D), pools[, scales]).

    ``dp_axis`` (2-D mesh): the attend operands (q, table, positions)
    shard their batch dim over DP, the write operands (k/v/dest/scales)
    stay replicated so every DP replica applies the full group's writes —
    identical replicas, per-DP-replica merge, exactly the decode scheme."""
    from repro.models import attention as attn

    n = mesh_axis_size(mesh, axis)
    p_total, page = k_pool.shape[:2]
    assert p_total % n == 0, (p_total, n)
    pn = p_total // n
    quantized = k_scale is not None
    c = q.shape[1]
    dp = _dp_or_none(mesh, dp_axis, q.shape[0])

    def attend(q, kp, vp, pt, sp, lp, off, ks, vs):
        qpos = sp[:, None] + jnp.arange(c)[None, :]
        acc, l, m = attn.paged_gather_chunk_partials(
            q, kp, vp, pt, qpos, lp, off, k_scale=ks, v_scale=vs)
        return attn.merge_paged_chunk_partials(acc, l, m, axis).astype(
            q.dtype)

    def body(q, kn, vn, dest, pt, sp, lp, kp, vp):
        off = (jax.lax.axis_index(axis) * pn).astype(jnp.int32)
        roff = off * page  # scatter wants flat rows, partials want pages
        kp = attn.scatter_chunk_paged_local(kp, kn, dest, roff)
        vp = attn.scatter_chunk_paged_local(vp, vn, dest, roff)
        y = attend(q, kp, vp, pt, sp, lp, off, None, None)
        return y, kp, vp

    def body_quant(q, kn, vn, skn, svn, dest, pt, sp, lp, kp, vp, ks, vs):
        off = (jax.lax.axis_index(axis) * pn).astype(jnp.int32)
        roff = off * page
        kp = attn.scatter_chunk_paged_local(kp, kn, dest, roff)
        vp = attn.scatter_chunk_paged_local(vp, vn, dest, roff)
        ks = attn.scatter_chunk_paged_local(ks, skn, dest, roff)
        vs = attn.scatter_chunk_paged_local(vs, svn, dest, roff)
        y = attend(q, kp, vp, pt, sp, lp, off, ks, vs)
        return y, kp, vp, ks, vs

    bsp = PartitionSpec(dp) if dp is not None else PartitionSpec()
    rep = PartitionSpec()
    sh = PartitionSpec(axis)
    if quantized:
        fn = jax.shard_map(
            body_quant, mesh=mesh,
            in_specs=(bsp, rep, rep, rep, rep, rep, bsp, bsp, bsp,
                      sh, sh, sh, sh),
            out_specs=(bsp, sh, sh, sh, sh), check_vma=False)
        return fn(q, k_new, v_new, k_scale_new, v_scale_new, dest,
                  page_table, start_pos, last_pos, k_pool, v_pool,
                  k_scale, v_scale)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bsp, rep, rep, rep, bsp, bsp, bsp, sh, sh),
                       out_specs=(bsp, sh, sh), check_vma=False)
    return fn(q, k_new, v_new, dest, page_table, start_pos, last_pos,
              k_pool, v_pool)
