"""Pluggable KV-cache backends for the serve engine.

The paper's clusters are "constantly moved between training and inferencing",
so the serving path has to live inside whatever HBM training left behind.
The #1 waste under ragged continuous batching is the cache reservation: a
dense cache pins ``max_seq`` rows per slot no matter how short the request.
This module makes the cache a first-class API with two backends behind one
small protocol (``alloc`` / ``write_prefill`` / ``decode_view`` / ``free`` /
``memory_stats``):

* ``ContiguousCache`` — today's dense (L, B, Smax, KV, D) layout.  The
  train/dry-run layout; every slot's full capacity is reserved up front, so
  ``alloc`` never fails and admission is bounded only by the slot count.
* ``PagedCache`` — fixed-size pages.  Physical storage is a per-layer
  (P, page, KV, D) pool; each slot owns a row of a (B, M) int32 **page
  table** mapping logical page index -> physical page.  ``alloc`` reserves
  ``ceil(total_len / page)`` pages (admission control: it returns ``None``
  when the pool is exhausted, instead of the engine OOMing), and **prefix
  sharing** lets identical prompt prefixes share physical pages: full prompt
  pages are keyed by a hash of the token prefix they cover and refcounted,
  so N requests with the same system prompt pin its pages once.

Physical page 0 is the **scratch page**: never allocated, it is where freed
slots' page-table rows point, so the fused decode's masked scatter-writes
for inactive slots land in garbage space rather than in pages that may since
have been reallocated to another request.

**Chunked-prefill allocation** (``alloc_chunked`` / ``extend``): a long
prompt admitted for chunked prefill takes only the pages its *first* chunk
writes; every later chunk claims its pages just before it dispatches, and
the final chunk claims the decode pages.  Admission and every grant run a
banker-style single-resource safety check — the live slots (each with its
remaining page need and the pages it would return on completion) must still
be completable in *some* order — so a partially-prefilled slot can stall
(``extend`` returns ``False``; the engine defers the chunk and resumes when
pages free) but can never deadlock the pool.  Mid-prefill slots are
**shielded** (``set_decode_shield``): ``decode_view`` hands the fused decode
dispatch a table whose shielded rows point at scratch, so the masked decode
write for a slot that is still prefilling can never land in its own live
pages.

Device-side state stays a plain pytree (``decode_view()``) so the engine's
one-fused-dispatch-per-iteration invariant from PR 1 is untouched: the page
table rides into ``lm.decode_step`` as just another (B, M) int32 argument.
Page-table *management* (alloc / free / refcounts / hashes) is host-side
numpy — it is O(pages) bookkeeping, never a device sync.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------- byte math ----

#: fp32 bytes of the per-row-per-KV-head scale the int8 page format stores
#: next to each quantized K/V row (``repro.kernels.quant.quantize_kv``).
SCALE_BYTES = 4


def kv_position_bytes(cfg, dtype, kv_dtype: str = "native") -> int:
    """Bytes of K+V cache per token position (all layers).

    ``kv_dtype="int8"``: each of the 2·L·KV rows stores head_dim int8
    elements plus one fp32 absmax scale — ``2·L·KV·(D + 4)`` bytes per
    position instead of ``2·L·KV·D·itemsize``."""
    l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_dtype == "int8":
        return 2 * l * kvh * (hd + SCALE_BYTES)
    assert kv_dtype == "native", kv_dtype
    return 2 * l * kvh * hd * jnp.dtype(dtype).itemsize


def contiguous_kv_bytes(cfg, batch: int, max_seq: int, dtype) -> int:
    """HBM pinned by a dense cache: every slot reserves max_seq positions."""
    return batch * max_seq * kv_position_bytes(cfg, dtype)


def page_kv_bytes(cfg, page_size: int, dtype,
                  kv_dtype: str = "native") -> int:
    """HBM of one physical page (all layers, K+V, incl. int8 scales)."""
    return page_size * kv_position_bytes(cfg, dtype, kv_dtype)


def decode_transient_bytes(cfg, batch: int, max_pages: int, page_size: int,
                           dtype, decode_impl: str = "gather",
                           kv_dtype: str = "native") -> int:
    """Per-decode-step transient bytes of the paged KV *read* path, one
    layer's worth (the layer scan reuses the buffer).

    ``"gather"``: XLA materializes two dense-equivalent gathered views,
    (B, M*page, KV, D) each — the transient grows with the paged-enlarged
    concurrent batch.  ``"pallas"``: each slot's program of the
    page-table-walking kernel streams one (page, KV, D) K and V page into
    VMEM plus fp32 online-softmax state — O(page), independent of B and M.

    ``kv_dtype="int8"``: the gather twin additionally materializes the
    gathered scale views and the dequantized compute-dtype K/V (the int8
    gather shrinks but the dequant expands to ``dtype``); the kernel
    streams the int8 page + its (page, KV) scales and dequantizes
    in-register, so its transient *shrinks* with the narrow wire format."""
    itemsize = jnp.dtype(dtype).itemsize
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if decode_impl == "gather":
        rows = 2 * batch * max_pages * page_size * kvh
        if kv_dtype == "int8":
            return rows * (hd + SCALE_BYTES) + rows * hd * itemsize
        return rows * hd * itemsize
    assert decode_impl == "pallas", decode_impl
    rows, state = 2 * page_size * kvh, 4 * cfg.num_heads * (hd + 2)
    if kv_dtype == "int8":
        return rows * (hd + SCALE_BYTES) + state
    return rows * hd * itemsize + state


def prefill_transient_bytes(cfg, group: int, block_len: int, dtype,
                            kv_dtype: str = "native") -> int:
    """Per-chip transient of the *sharded* paged prefill write path: the
    replicated (group, block_len) staged K/V block each chip scatters from
    under the shard_map primitive — O(group·block), independent of the
    pool width P.  The pre-unification GSPMD scatter could instead stage a
    replicated O(P)-pool temporary (= ``P · page_kv_bytes``); benches and
    the sharded tests compare the two measured ``temp_size_in_bytes``
    against these analytic poles."""
    return group * block_len * kv_position_bytes(cfg, dtype, kv_dtype)


class CacheInvariantError(AssertionError):
    """Raised by ``PagedCache.verify`` when the allocator's host-side
    bookkeeping violates an invariant — the detection signal for silent
    state corruption (vs the fused dispatch's non-finite guard, which
    detects *content* corruption)."""


@dataclass
class MemoryStats:
    backend: str
    bytes_total: int          # HBM pinned by the backend's physical storage
    bytes_reserved: int       # portion reserved by live requests
    slots_total: int
    slots_in_use: int
    page_size: int = 0        # paged only
    pages_total: int = 0      # usable pages (excludes scratch + failed chips)
    pages_in_use: int = 0
    pages_shared: int = 0     # pages with refcount > 1 (prefix sharing)
    mesh_chips: int = 1       # chips the pool is partitioned over (device
    #                           mesh OR the mesh-free locality_chips harness)
    bytes_per_chip: int = 0   # pinned bytes each chip holds (= total / chips,
    #                           int8 scale shards included via page_kv_bytes)
    kv_dtype: str = "native"  # page element format ("native" / "int8")
    bytes_scales: int = 0     # portion of bytes_total pinned by int8 scales
    bytes_scales_per_chip: int = 0   # each chip's sharded scale-array bytes
    chips_failed: int = 0     # chips drained by fail_chip (degraded pool)
    # footprint pages charged per tenant (multi-tenant serving; empty when
    # requests carry no tenant tag)
    tenant_pages: Dict[str, int] = field(default_factory=dict)
    # host-RAM page tier (PR 10): warm prefix pages resident in the
    # PrefixStore's pinned host slabs — zero when the tier is off
    host_pages_total: int = 0     # tier capacity (pages)
    host_pages_in_use: int = 0    # prefix pages currently stored
    host_bytes: int = 0           # wire bytes those pages pin in host RAM


class KVCache(Protocol):
    """The engine-facing cache protocol.

    ``alloc(slot, length, prefix=None, tenant=None)`` reserves capacity for
    ``length`` token positions in ``slot``; returns the number of leading
    positions already covered by shared physical storage (0 without
    sharing), or ``None`` if the backend cannot admit the request now
    (admission control) — ``last_deny`` then names the cause ("pool" vs
    "quota") so the engine can defer pool pressure but *skip past* a
    quota-capped tenant.  ``write_prefill(slot, kv_block)`` lands a prompt's K/V block
    in the slot's storage.  ``decode_view()`` is the device pytree handed to
    ``lm.decode_step``; ``update()`` stores the pytree a fused dispatch
    returned.  ``free(slot)`` releases the slot's storage.

    Engine-fusion surface (beyond the five core methods): ``backend`` names
    the layout, ``state`` is the backend's persistent device pytree (with a
    ``"layers"`` per-layer K/V entry — the engine donates exactly that
    subtree into its jitted dispatches), ``can_ever_fit`` backs submit-time
    rejection of requests no amount of freeing could admit, and
    ``staged_write_prefill`` is the *pure* (jit-stageable) form of
    ``write_prefill`` the engine traces into its one-dispatch-per-bucket
    batched prefill — its ``write_spec`` is backend-defined ((n,) slot ids
    for contiguous; (n, Sblk) flat pool indices from ``prefill_dest`` for
    paged).  ``mesh`` / ``kv_axis`` describe how the backend's storage is
    device-sharded (None / 1-extent for single-chip backends) — the engine
    forwards them into ``lm.decode_step`` so the fused dispatch runs the
    matching shard_map.
    """

    backend: str
    state: dict
    mesh: object
    kv_axis: str
    last_deny: Optional[str]

    def alloc(self, slot: int, length: int,
              prefix: Optional[np.ndarray] = None,
              tenant: Optional[str] = None) -> Optional[int]: ...
    def write_prefill(self, slot: int, kv_block) -> None: ...
    def decode_view(self): ...
    def update(self, new_state) -> None: ...
    def free(self, slot: int) -> None: ...
    def memory_stats(self) -> MemoryStats: ...
    def can_ever_fit(self, length: int) -> bool: ...
    def staged_write_prefill(self, layers, kv_block, write_spec): ...


# ---------------------------------------------------------- contiguous ----

class ContiguousCache:
    """Dense (B, Smax) rows per slot — the seed layout behind the new API.

    ``alloc`` always succeeds (capacity is pre-reserved, which is exactly
    the memory waste ``PagedCache`` exists to remove) and nothing is ever
    shared, so ``memory_stats().bytes_reserved == bytes_total`` at all
    times.
    """

    backend = "contiguous"
    decode_impl = "gather"      # dense rows have no page table to resolve
    mesh = None                 # dense rows have no kv_pages dim to shard
    kv_axis = "model"
    dp_axis = None
    kv_dtype = "native"         # int8 pages are a paged-format feature
    quantized = False
    last_deny = None            # alloc never fails -> never a deny reason

    def __init__(self, lm, batch: int, max_seq: int, dtype=jnp.bfloat16):
        self.cfg = lm.cfg
        self.B, self.S = batch, max_seq
        self.dtype = dtype
        self.state = lm.init_cache(batch, max_seq, dtype=dtype)
        self._in_use = np.zeros(batch, bool)
        self._bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(self.state))

    def can_ever_fit(self, length: int) -> bool:
        return length <= self.S

    def alloc(self, slot: int, length: int,
              prefix: Optional[np.ndarray] = None,
              tenant: Optional[str] = None) -> Optional[int]:
        assert not self._in_use[slot], f"slot {slot} already allocated"
        assert 0 < length <= self.S, (length, self.S)
        self._in_use[slot] = True
        return 0

    @staticmethod
    def staged_write_prefill(layers, kv_block, slots):
        """Jit-stageable multi-slot prefill write over the per-layer K/V
        subtree (``state["layers"]``).

        kv_block: per-layer (L, n, Sblk, ...) K/V for ``n`` admitted
        requests; slots: (n,) int32 target slots.  Rows [0, Sblk) of each
        slot are overwritten — including any prompt padding, which stays
        invisible behind the decode causal mask until decode rewrites it.
        """
        def write(big, small):
            # big: (L, B, S, ...); small: (L, n, Sblk, ...)
            rows = jnp.arange(small.shape[2])
            return big.at[:, slots[:, None], rows[None, :]].set(
                small.astype(big.dtype))

        return jax.tree.map(write, layers, kv_block)

    def write_prefill(self, slot: int, kv_block) -> None:
        self.state = {**self.state, "layers": self.staged_write_prefill(
            self.state["layers"], kv_block, jnp.asarray([slot], jnp.int32))}

    def decode_view(self):
        return self.state

    def update(self, new_state) -> None:
        self.state = new_state

    def free(self, slot: int) -> None:
        self._in_use[slot] = False

    def memory_stats(self) -> MemoryStats:
        return MemoryStats(backend=self.backend, bytes_total=self._bytes,
                           bytes_reserved=self._bytes, slots_total=self.B,
                           slots_in_use=int(self._in_use.sum()),
                           bytes_per_chip=self._bytes)


# --------------------------------------------------------------- paged ----

class PagedCache:
    """Fixed-size pages + (B, M) page-table indirection + prefix sharing.

    Pool: per-layer (L, P, page, KV, D) for K and V; page 0 is scratch.
    ``alloc`` reserves the request's full footprint up front
    (prompt + max_new_tokens), so a decode can never run out of pages
    mid-flight — exhaustion surfaces only as admission control.

    Prefix sharing: full prompt pages (positions [i*page, (i+1)*page) wholly
    inside the prompt) are keyed by the token prefix they causally depend on
    — K/V at position p is a function of tokens[:p+1] only — and refcounted.
    A later request whose prompt starts with the same tokens maps its page
    table at those logical pages to the same physical pages and skips
    writing them (its prefill scatter routes those positions to scratch).
    The first page *not* fully covered by the prompt is always privately
    owned, so decode scatter-writes never touch shared storage.

    **Sharded pools** (``mesh``): the pool's leading (P) dim carries the
    ``kv_pages`` logical axis and shards P/n over ``kv_axis`` — each chip
    pins P/n pages and owns the global page-id range
    ``[chip*P/n, (chip+1)*P/n)`` (``repro.parallel.pagedkv``); the pool is
    padded up to a multiple of the mesh size.  The free list becomes
    **locality-aware**: it prefers handing one request pages from one chip
    (fewer chips touched per slot), spilling across chips only when no
    single chip can cover the request — and admission (admit vs defer)
    depends only on the *total* free count, never on placement, so locality
    is a performance hint with zero behavioural surface.
    """

    backend = "paged"

    def __init__(self, lm, batch: int, max_seq: int, dtype=jnp.bfloat16,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_sharing: bool = True, decode_impl: str = "gather",
                 mesh=None, kv_axis: str = "model", dp_axis=None,
                 locality_chips: Optional[int] = None,
                 kv_dtype: str = "native", host_pages: int = 0,
                 prefix_store=None):
        cfg = lm.cfg
        assert cfg.family in ("dense", "vlm", "moe"), (
            "paged KV is attention-cache families only "
            f"(family={cfg.family})")
        assert decode_impl in ("gather", "pallas"), decode_impl
        assert kv_dtype in ("native", "int8"), kv_dtype
        self.decode_impl = decode_impl
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        self.cfg, self.B, self.S = cfg, batch, max_seq
        self.page = page_size
        self.max_pages = -(-max_seq // page_size)              # M, per slot
        if num_pages is None:
            # default pool: full dense-equivalent capacity (+ scratch), so
            # swapping backends never changes admission behaviour
            num_pages = batch * self.max_pages + 1
        assert num_pages >= 2, "need at least scratch + one usable page"
        self.mesh, self.kv_axis = mesh, kv_axis
        self.dp_axis = dp_axis
        if mesh is not None:
            from repro.parallel.mesh import mesh_axis_size
            assert locality_chips is None, (
                "locality_chips is the mesh-free testing knob; with a mesh "
                "the chip count is the kv_axis extent")
            # 2-D batch × pages meshes: the pool shards over kv_axis only
            # (replicated across dp_axis); dp shards the dispatch batch dims
            self.chips = mesh_axis_size(mesh, kv_axis)
            if dp_axis is not None:
                assert dp_axis != kv_axis, (
                    "dp_axis and kv_axis must be distinct mesh axes")
                assert mesh_axis_size(mesh, dp_axis) >= 1
        else:
            assert dp_axis is None, (
                "dp_axis shards dispatch batch dims over a mesh; pass mesh=")
            # locality_chips simulates the per-chip free-list partitioning
            # without device sharding (host-side allocator tests)
            self.chips = locality_chips or 1
        # pad the pool so every chip holds the same P/n page count
        num_pages = -(-num_pages // self.chips) * self.chips
        self.P = num_pages
        self.pages_per_chip = num_pages // self.chips
        self.dtype = dtype
        self.prefix_sharing = prefix_sharing
        L, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        pool_shape = (L, num_pages, page_size, kvh, hd)
        scale_shape = pool_shape[:4]        # one fp32 scale per (pos, head)
        self._pool_sharding = self._scale_sharding = None
        if mesh is not None:
            from repro.parallel.pagedkv import (kv_pool_sharding,
                                                kv_scale_sharding)
            self._pool_sharding = kv_pool_sharding(mesh, pool_shape,
                                                   axis=kv_axis)
            if self.quantized:
                self._scale_sharding = kv_scale_sharding(mesh, scale_shape,
                                                         axis=kv_axis)

        def alloc_z(shape, dt, sharding):
            z = jnp.zeros(shape, dt)
            return jax.device_put(z, sharding) if sharding is not None else z

        def pool():
            return alloc_z(pool_shape, jnp.int8 if self.quantized else dtype,
                           self._pool_sharding)

        self.state = {"layers": {"k": pool(), "v": pool()}}
        if self.quantized:
            # per-page-row-per-KV-head fp32 absmax scales, stored alongside
            # the int8 pools so decode_view hands them to the dispatch as
            # part of the same donated layers subtree
            self.state["layers"]["k_scale"] = alloc_z(
                scale_shape, jnp.float32, self._scale_sharding)
            self.state["layers"]["v_scale"] = alloc_z(
                scale_shape, jnp.float32, self._scale_sharding)
        self.page_table = np.zeros((batch, self.max_pages), np.int32)
        self._page_table_dev = None      # device copy, invalidated on mutation
        # per-chip free stacks, pop() handing out the lowest id of the chip;
        # the scratch page (global id 0, chip 0) is never listed
        self._free_chip: List[List[int]] = [
            [pid for pid in range(
                min((c + 1) * self.pages_per_chip, num_pages) - 1,
                max(c * self.pages_per_chip, 1) - 1, -1)]
            for c in range(self.chips)]
        self._ref = np.zeros(num_pages, np.int32)
        self._hash_to_page: Dict[bytes, int] = {}
        self._page_to_hash: Dict[int, bytes] = {}
        self._slot_pages: List[List[int]] = [[] for _ in range(batch)]
        self._slot_shared: List[int] = [0] * batch   # leading shared pages
        # chunked-prefill bookkeeping: pages a slot has been promised but has
        # not yet claimed (drawn down by ``extend``), and slots whose table
        # rows are hidden from the fused decode dispatch while they prefill
        self._slot_need: List[int] = [0] * batch
        self._shielded: set = set()
        # multi-tenant accounting: a tenant's *full footprint* (every page
        # the slot will ever hold, shared pages included, chunked tails
        # included) is charged against its quota at admission time — so an
        # in-flight chunked slot's ``extend`` can never hit a quota wall,
        # and the banker's no-deadlock guarantee is untouched by quotas
        self._quota: Dict[str, int] = {}
        self._slot_tenant: List[Optional[str]] = [None] * batch
        self._slot_charge: List[int] = [0] * batch
        self._tenant_pages: Dict[str, int] = {}
        #: why the last ``alloc``/``alloc_chunked`` returned ``None``:
        #: "pool" (banker/exhaustion — engine defers, in-order) or "quota"
        #: (tenant cap — engine skips this request and admits others)
        self.last_deny: Optional[str] = None
        #: chips drained by ``fail_chip`` — their page-id ranges are dead:
        #: never listed free again, capacity permanently reduced
        self._failed_chips: set = set()
        # ---- host-RAM page tier (repro.serve.offload) -------------------
        # ``prefix_store`` is an externally-owned PrefixStore (persistent
        # across engines: a warmup engine's evicted prefixes prefetch into
        # a later engine's admissions); ``host_pages`` alone builds a
        # store private to this cache.  The tier rides on prefix sharing —
        # the store key IS the sharing key — so it requires it.
        self.store = None
        if prefix_store is not None or host_pages:
            from repro.serve.offload import PrefixStore
            assert prefix_sharing, (
                "the host page tier stores pages under the prefix-sharing "
                "key; construct the cache with prefix_sharing=True")
            self.store = prefix_store if prefix_store is not None \
                else PrefixStore(host_pages)
            spec = {"k": (pool_shape[2:],
                          jnp.int8 if self.quantized else dtype)}
            spec["v"] = spec["k"]
            if self.quantized:
                spec["k_scale"] = (scale_shape[2:], jnp.float32)
                spec["v_scale"] = spec["k_scale"]
            # per-page payload: pool_shape is (L, P, page, KV, D) — a page
            # slice drops the P dim, keeping the leading L
            spec = {n: ((pool_shape[0],) + shape, dt)
                    for n, (shape, dt) in spec.items()}
            self.store.bind(spec)
        #: device->host copies started by ``free`` but not yet landed in
        #: the store — drained at the next admission/stats/verify point,
        #: never on the decode hot path
        self._pending_offload: List[tuple] = []
        self._pending_keys: set = set()

    # ------------------------------------------------------------ sizing ----
    def pages_needed(self, length: int) -> int:
        return -(-length // self.page)

    def usable_pages(self) -> int:
        """Pages the allocator can ever hand out: the pool minus scratch
        minus every failed chip's range (the scratch page lives on chip 0,
        so a failed chip 0 loses one page fewer than the others)."""
        lost = sum(self.pages_per_chip - (1 if c == 0 else 0)
                   for c in self._failed_chips)
        return self.P - 1 - lost

    def can_ever_fit(self, length: int) -> bool:
        return (length <= self.S
                and self.pages_needed(length) <= self.usable_pages())

    def _chip_of(self, pid: int) -> int:
        from repro.parallel.pagedkv import chip_of_page
        return chip_of_page(pid, self.pages_per_chip)

    # ------------------------------------------------------------- alloc ----
    def _free_count(self) -> int:
        return sum(len(f) for f in self._free_chip)

    def _take_fresh(self, need: int) -> List[int]:
        """Pop ``need`` pages from the per-chip free stacks, locality-first.

        Preference order: the chip that fits the request with the fewest
        free pages to spare (best fit — keeps large same-chip runs intact
        for later requests), else spill across chips from the fullest down.
        The caller has already checked ``need <= _free_count()`` — placement
        never changes whether a request is admitted."""
        fits = [c for c in range(self.chips)
                if len(self._free_chip[c]) >= need]
        order = ([min(fits, key=lambda c: (len(self._free_chip[c]), c))]
                 if fits else
                 sorted(range(self.chips),
                        key=lambda c: (-len(self._free_chip[c]), c)))
        out: List[int] = []
        for c in order:
            while self._free_chip[c] and len(out) < need:
                out.append(self._free_chip[c].pop())
        assert len(out) == need, (len(out), need)
        return out

    def _banker_items(self, skip: Optional[int] = None):
        """(remaining_need, freeable_on_completion) per live slot — the state
        the single-resource banker's check runs over.  ``freeable`` counts
        only exclusively-owned pages (refcount 1): shared pages may outlive
        the slot, so counting them would overestimate what completion frees
        (conservative — may defer a grant that was in fact safe, never the
        reverse)."""
        items = []
        for s in range(self.B):
            if s == skip or (not self._slot_pages[s]
                             and not self._slot_need[s]):
                continue
            freeable = sum(int(self._ref[p] == 1) for p in self._slot_pages[s])
            items.append((self._slot_need[s], freeable))
        return items

    @staticmethod
    def _safe(free: int, items) -> bool:
        """Single-resource banker's safety: the live slots are completable in
        *some* order iff, walking them by ascending remaining need, each
        one's need fits in the free pool grown by its predecessors' frees."""
        for need, freeable in sorted(items):
            if need > free:
                return False
            free += freeable
        return True

    def _grant_safe(self, take: int, remaining: int, skip: Optional[int] = None,
                    extra_freeable: int = 0) -> bool:
        """Would handing out ``take`` fresh pages to a slot that will still
        need ``remaining`` more leave the pool in a banker-safe state?
        ``skip``/``extra_freeable`` describe the grantee: its current entry is
        excluded and re-added post-grant with ``take`` more freeable pages."""
        free = self._free_count()
        if take > free:
            return False
        items = self._banker_items(skip=skip)
        items.append((remaining, take + extra_freeable))
        return self._safe(free - take, items)

    # ------------------------------------------------------------ tenancy ----
    def set_quota(self, tenant: str, pages: Optional[int]) -> None:
        """Cap ``tenant``'s concurrently-charged footprint pages (``None``
        lifts the cap).  Lowering a quota below current usage only blocks
        *new* admissions — live slots run to completion."""
        if pages is None:
            self._quota.pop(tenant, None)
        else:
            assert pages >= 1, pages
            self._quota[tenant] = pages

    def tenant_pages(self, tenant: str) -> int:
        return self._tenant_pages.get(tenant, 0)

    def _quota_ok(self, tenant: Optional[str], n_total: int) -> bool:
        if tenant is None or tenant not in self._quota:
            return True
        return self.tenant_pages(tenant) + n_total <= self._quota[tenant]

    def _charge(self, slot: int, tenant: Optional[str], n_total: int) -> None:
        if tenant is None:
            return
        self._slot_tenant[slot] = tenant
        self._slot_charge[slot] = n_total
        self._tenant_pages[tenant] = self.tenant_pages(tenant) + n_total

    def slot_freeable(self, slot: int) -> int:
        """Pages ``free(slot)``/``evict(slot)`` would return to the pool
        right now (exclusively-owned only — shared prefix pages stay pinned
        by their other references)."""
        return sum(int(self._ref[p] == 1) for p in self._slot_pages[slot])

    def evict(self, slot: int) -> int:
        """Preempt ``slot``: release every page it holds (and its quota
        charge) and return the number of pages that actually re-entered the
        free pool.  The engine re-queues the request for recompute-on-resume
        prefill — if its prompt pages are still registered (another sharer
        or a not-yet-recycled page), the resume re-shares them."""
        freed = self.slot_freeable(slot)
        self.free(slot)
        return freed

    def _match_shared(self, prefix: Optional[np.ndarray], n_pages: int):
        """Leading full prompt pages this request need not recompute.

        Returns ``(shared, full, host_hits)``: ``shared`` are device pages
        already registered (content landed) that the slot maps directly;
        ``host_hits`` continue the run past the device-registered prefix
        with pages resident in the host tier — ``(logical_idx, key,
        payload)`` triples the caller prefetches into fresh device pages
        after its admission check passes.  ``full`` is the shareable
        full-page count.  Host payloads are finite-checked here: a
        poisoned host page is quarantined (counted as a poisoned miss)
        and the match run stops before it, so corrupt bytes can never
        reach ``register_landed``."""
        shared: List[int] = []
        host_hits: List[tuple] = []
        full = 0
        if self.prefix_sharing and prefix is not None:
            # only pages wholly covered by the prompt are shareable: the
            # page containing the first decode write must be private
            full = min(len(prefix) // self.page, n_pages)
            i = 0
            while i < full:
                pid = self._hash_to_page.get(self._key(prefix, i))
                if pid is None:
                    break
                shared.append(pid)
                i += 1
            if self.store is not None and i < full:
                self.drain_offloads()
                while i < full:
                    key = self._key(prefix, i)
                    payload = self.store.lookup(key)
                    if payload is None:
                        break
                    if not self._payload_finite(payload):
                        self.store.quarantine(key)
                        break
                    host_hits.append((i, key, payload))
                    i += 1
        return shared, full, host_hits

    @staticmethod
    def _payload_finite(payload: Dict[str, np.ndarray]) -> bool:
        """Prefetch-side corruption guard: every float array of the page's
        wire payload must be finite (int8 payloads are unrepresentable as
        NaN, so their fp32 scales carry the poison — same as on device)."""
        return all(np.isfinite(np.asarray(a, np.float32)).all()
                   for a in payload.values()
                   if np.issubdtype(a.dtype, np.floating))

    def _prefetch(self, host_hits: List[tuple], pids: List[int]) -> None:
        """Land ``host_hits``'s payloads in the freshly-claimed device
        pages ``pids`` (one batched ``.at[:, pids].set`` per payload
        array) and register the keys — the content IS landed, so later
        admissions in the same batch can device-share it immediately."""
        assert len(host_hits) == len(pids)
        if not host_hits:
            return
        idx = jnp.asarray(pids, jnp.int32)
        layers = dict(self.state["layers"])
        for name in layers:
            block = np.stack([payload[name]
                              for _, _, payload in host_hits], axis=1)
            arr = layers[name].at[:, idx].set(
                jnp.asarray(block, layers[name].dtype))
            if self.mesh is not None:
                sharding = (self._pool_sharding if arr.ndim == 5
                            else self._scale_sharding)
                arr = jax.device_put(arr, sharding)
            layers[name] = arr
        self.state = {**self.state, "layers": layers}
        for (_, key, _), pid in zip(host_hits, pids):
            self._hash_to_page[key] = pid
            self._page_to_hash[pid] = key
        self.store.note_prefetch(len(host_hits))

    def _offload(self, key: bytes, pid: int) -> None:
        """Start an async device->host copy of page ``pid`` under ``key``
        (called by ``free`` as the last reference drops).  The page slice
        is taken immediately — the pool buffer may be donated into the
        next fused dispatch — but materialization to host numpy waits for
        ``drain_offloads``, keeping the copy off the free/decode hot
        path.  Pages the store already holds are only LRU-refreshed."""
        if self.store.has(key) or key in self._pending_keys:
            self.store.touch(key)
            return
        slices = {}
        for name in self.state["layers"]:
            a = self.state["layers"][name][:, pid]
            a.copy_to_host_async()
            slices[name] = a
        self._pending_offload.append((key, slices))
        self._pending_keys.add(key)

    def drain_offloads(self) -> None:
        """Materialize every pending device->host copy into the store.
        Called before store lookups (so a just-freed prefix is hittable),
        from ``memory_stats``/``verify`` (accounting covers in-flight
        pages), and harmlessly when nothing is pending."""
        if not self._pending_offload:
            return
        for key, slices in self._pending_offload:
            self.store.put(key, {n: np.asarray(a)
                                 for n, a in slices.items()})
        self._pending_offload.clear()
        self._pending_keys.clear()

    def alloc(self, slot: int, length: int,
              prefix: Optional[np.ndarray] = None,
              tenant: Optional[str] = None) -> Optional[int]:
        """Reserve pages covering ``length`` positions for ``slot``.

        ``prefix``: the slot's prompt tokens starting at position 0 — the
        key for prefix sharing (pass ``None`` to disable for this request,
        e.g. VLM prompts whose leading positions are image embeddings).
        ``tenant``: charge the footprint against this tenant's page quota
        (``set_quota``); a quota deny sets ``last_deny = "quota"`` without
        touching refcounts, distinguishable from a "pool" deny.
        Returns the number of leading positions backed by shared pages, or
        ``None`` when the free pool cannot cover the unshared remainder (or
        covering it would strand an in-flight chunked prefill — the banker's
        check below degrades to the plain ``need <= free`` test whenever no
        chunked slot is live).
        """
        assert not self._slot_pages[slot], f"slot {slot} already allocated"
        assert 0 < length <= self.S, (length, self.S)
        n_pages = self.pages_needed(length)
        self.last_deny = None
        if not self._quota_ok(tenant, n_pages):
            self.last_deny = "quota"
            return None                      # tenant cap, not pool pressure
        shared, full, host_hits = self._match_shared(prefix, n_pages)
        # bump shared refs before the safety check: a page going ref 1 -> 2
        # stops being freeable by its first owner's completion, and the
        # banker must see that (rolled back on deferral)
        for pid in shared:
            self._ref[pid] += 1
        # prefetch-then-admit: host-tier hits still consume fresh DEVICE
        # pages, so the banker sees the same demand as a cold request —
        # only the recompute (prefill forward) is saved, never safety
        if not self._grant_safe(n_pages - len(shared), 0):
            for pid in shared:
                self._ref[pid] -= 1
            self.last_deny = "pool"
            return None                      # admission control, not OOM
        fresh = self._take_fresh(n_pages - len(shared))
        for pid in fresh:
            self._ref[pid] = 1
        pages = shared + fresh
        # admission granted: land the host tier's pages in the first
        # host_hits fresh pages (their logical indices continue the
        # device-shared run) and register them as landed content
        self._prefetch(host_hits, fresh[:len(host_hits)])
        covered = len(shared) + len(host_hits)
        # register this request's *new* full prompt pages so later identical
        # prefixes can share them (content lands in the same _admit step)
        if self.prefix_sharing and prefix is not None:
            for i in range(covered, full):
                key = self._key(prefix, i)
                if key not in self._hash_to_page:
                    self._hash_to_page[key] = pages[i]
                    self._page_to_hash[pages[i]] = key
        self.page_table[slot, :] = 0
        self.page_table[slot, :n_pages] = pages
        self._page_table_dev = None
        self._slot_pages[slot] = pages
        self._slot_shared[slot] = covered
        self._charge(slot, tenant, n_pages)
        return covered * self.page

    # ------------------------------------------------- chunked allocation ----
    def alloc_chunked(self, slot: int, length: int, first: int,
                      prefix: Optional[np.ndarray] = None,
                      tenant: Optional[str] = None) -> Optional[int]:
        """Admit ``slot`` for chunked prefill: claim only the pages covering
        the first ``first`` positions now; the rest of the ``length``-position
        footprint (later prompt chunks + the decode tail) is recorded as this
        slot's *remaining need* and claimed chunk-by-chunk via ``extend``.

        Admission requires the post-grant pool to be banker-safe, which is a
        strictly weaker demand than the whole-footprint ``alloc`` check: a
        long prompt can be admitted into a pool whose free pages cover only
        its first chunk, as long as the live slots' completions will free
        what its later chunks need.  Prefix sharing matches only pages whose
        content has already *landed* (``register_landed`` keys pages after
        their chunk's scatter, not at alloc time), so a sharer can never
        attend a page another request has not written yet.

        Returns the shared leading positions, or ``None`` to defer."""
        assert not self._slot_pages[slot], f"slot {slot} already allocated"
        assert 0 < first <= length <= self.S, (first, length, self.S)
        n_total = self.pages_needed(length)
        self.last_deny = None
        # quota charges the FULL footprint here, at admission — later
        # ``extend`` calls draw down an already-charged reservation, so a
        # mid-prefill slot can banker-stall but never quota-stall
        if not self._quota_ok(tenant, n_total):
            self.last_deny = "quota"
            return None
        shared, _, host_hits = self._match_shared(prefix, n_total)
        # prefetch-then-admit: host-tier hits are claimed (and landed) UP
        # FRONT alongside the first chunk's pages — the chunks they cover
        # will skip their forward entirely, so deferring the claim would
        # only re-expose the recompute the tier exists to remove.  The
        # banker check still guards the whole grant: an unsafe prefetch
        # defers the admission exactly like an unsafe cold claim.
        n_first = max(self.pages_needed(first) - len(shared), 0)
        n_first = max(n_first, len(host_hits))
        remaining = n_total - len(shared) - n_first
        for pid in shared:          # pre-check bump, as in ``alloc``
            self._ref[pid] += 1
        if not self._grant_safe(n_first, remaining):
            for pid in shared:
                self._ref[pid] -= 1
            self.last_deny = "pool"
            return None
        fresh = self._take_fresh(n_first)
        for pid in fresh:
            self._ref[pid] = 1
        pages = shared + fresh
        self._prefetch(host_hits, fresh[:len(host_hits)])
        covered = len(shared) + len(host_hits)
        self.page_table[slot, :] = 0
        self.page_table[slot, :len(pages)] = pages
        self._page_table_dev = None
        self._slot_pages[slot] = pages
        self._slot_shared[slot] = covered
        self._slot_need[slot] = remaining
        self._charge(slot, tenant, n_total)
        return covered * self.page

    def extend(self, slot: int, cover: int) -> bool:
        """Grow ``slot``'s claimed pages to cover ``cover`` positions (the
        next chunk's end — or the full footprint on the final chunk, which
        claims the decode tail).  Returns ``False`` when the grant is not
        banker-safe right now: the chunk defers and resumes once completions
        free pages — the safety invariant guarantees some live slot can
        always run to completion, so a stalled prefill never deadlocks."""
        have = len(self._slot_pages[slot])
        assert have > 0, f"slot {slot} has no chunked allocation"
        need = self.pages_needed(cover) - have
        if need <= 0:
            return True
        assert need <= self._slot_need[slot], (need, self._slot_need[slot])
        freeable = sum(int(self._ref[p] == 1) for p in self._slot_pages[slot])
        if not self._grant_safe(need, self._slot_need[slot] - need,
                                skip=slot, extra_freeable=freeable):
            return False
        fresh = self._take_fresh(need)
        for pid in fresh:
            self._ref[pid] = 1
        self.page_table[slot, have:have + need] = fresh
        self._page_table_dev = None
        self._slot_pages[slot].extend(fresh)
        self._slot_need[slot] -= need
        return True

    def register_landed(self, slot: int, prefix: np.ndarray,
                        upto: int) -> None:
        """Key ``slot``'s full prompt pages whose content has landed
        (positions ``[0, upto)`` scattered) into the prefix-sharing registry.
        Chunked prefill registers here — after the chunk's scatter — instead
        of at alloc time, so no other request can ever map a page whose
        content is still pending.  Idempotent per page."""
        if not self.prefix_sharing or prefix is None:
            return
        full = min(upto, len(prefix)) // self.page
        pages = self._slot_pages[slot]
        for i in range(self._slot_shared[slot], min(full, len(pages))):
            key = self._key(prefix, i)
            pid = pages[i]
            if key not in self._hash_to_page and pid not in self._page_to_hash:
                self._hash_to_page[key] = pid
                self._page_to_hash[pid] = key

    def _key(self, prefix: np.ndarray, page_idx: int) -> bytes:
        # K/V in page i depend on tokens[: (i+1)*page] (causality), nothing
        # else — so the prefix bytes are the complete sharing key
        return np.ascontiguousarray(
            prefix[: (page_idx + 1) * self.page], np.int32).tobytes()

    # ----------------------------------------------------------- prefill ----
    def prefill_dest(self, slot: int, block_len: int, valid_len: int,
                     shared_len: int = 0) -> np.ndarray:
        """Flat pool indices for a prefill block's positions [0, block_len).

        Positions already backed by shared pages, and padding positions
        beyond ``valid_len``, route to flat index 0 (scratch page row 0) —
        the block is computed for the padded bucket but only privately-owned
        real positions land in the pool.  (The position-0 special case of
        ``chunk_dest`` — one implementation of the resolve+mask pipeline.)
        """
        return self.chunk_dest(slot, 0, valid_len, block_len, shared_len)

    def chunk_dest(self, slot: int, start: int, end: int, chunk_len: int,
                   shared_len: int = 0) -> np.ndarray:
        """Flat pool indices for one prefill chunk: global positions
        ``[start, start + chunk_len)`` of ``slot``, of which only
        ``[max(start, shared_len), end)`` actually land (padding past the
        chunk's valid tokens and positions backed by shared pages route to
        flat index 0, the scratch sink).  The caller must have ``extend``-ed
        the slot to cover ``end`` positions first."""
        pos = start + np.arange(chunk_len)
        logical = np.minimum(pos // self.page, self.max_pages - 1)
        idx = self.page_table[slot, logical] * self.page + pos % self.page
        write = (pos >= shared_len) & (pos < end)
        return np.where(write, idx, 0).astype(np.int32)

    def table_row(self, slot: int) -> np.ndarray:
        """The slot's REAL (M,) page-table row — what a chunked-prefill
        dispatch gathers through (``decode_view`` may be shielding it)."""
        return self.page_table[slot].copy()

    def staged_write_prefill(self, layers, kv_block, dest):
        """Jit-stageable multi-request prefill scatter over the per-layer
        K/V pools (``state["layers"]``).

        kv_block: per-layer (L, n, Sblk, ...) K/V; dest: (n, Sblk) flat pool
        indices (page * page_size + row, scratch-routed where masked).  On a
        sharded pool the write routes through the unified shard_map
        primitive (``repro.parallel.pagedkv.sharded_write_prefill``): each
        chip commits only its own rows with a ``mode="drop"`` local
        scatter, so the dispatch's per-chip transient is the O(group·block)
        staged K/V — never an O(P) replicated pool (the pre-unification
        GSPMD path is kept measurable as ``gspmd_write_prefill``).

        Quantized pools (``kv_dtype="int8"``): the float K/V block is
        quantized here — inside the staged (jit-traced) write, so prefill
        stays one dispatch — and the per-row scales scatter into the scale
        arrays through the *same* flat indices (a scale array is just a
        pool with no D axis)."""
        kv_block = self._quantize_block(kv_block)
        if self.mesh is not None:
            from repro.parallel.pagedkv import sharded_write_prefill
            return sharded_write_prefill(self.mesh, self.kv_axis, layers,
                                         kv_block, dest)

        def write(pool, small):
            p, pg = pool.shape[1], pool.shape[2]
            flat = pool.reshape(pool.shape[0], p * pg, *pool.shape[3:])
            flat = flat.at[:, dest].set(small.astype(pool.dtype))
            return flat.reshape(pool.shape)

        return jax.tree.map(write, layers, kv_block)

    def gspmd_write_prefill(self, layers, kv_block, dest):
        """The pre-unification sharded prefill write: a flat global
        ``.at[:, dest].set`` left to GSPMD to partition, constrained back
        to the pool sharding.  Kept ONLY as the measured baseline for the
        replicated-pool-transient comparison (bench/tests compile both
        writes and diff ``temp_size_in_bytes``); the engine always routes
        through the shard_map primitive above."""
        assert self.mesh is not None, "the GSPMD baseline is mesh-only"
        kv_block = self._quantize_block(kv_block)

        def write(pool, small):
            p, pg = pool.shape[1], pool.shape[2]
            flat = pool.reshape(pool.shape[0], p * pg, *pool.shape[3:])
            flat = flat.at[:, dest].set(small.astype(pool.dtype))
            out = flat.reshape(pool.shape)
            sharding = (self._pool_sharding if pool.ndim == 5
                        else self._scale_sharding)
            return jax.lax.with_sharding_constraint(out, sharding)

        return jax.tree.map(write, layers, kv_block)

    def _quantize_block(self, kv_block):
        """int8 pools: quantize a staged float K/V block (inside the jit
        trace) into the {k, v, k_scale, v_scale} tree the pool expects."""
        if not self.quantized:
            return kv_block
        from repro.kernels.quant import quantize_kv
        block = {}
        for name in ("k", "v"):
            q, s = quantize_kv(kv_block[name])
            block[name], block[name + "_scale"] = q, s
        return block

    def write_prefill(self, slot: int, kv_block) -> None:
        block_len = jax.tree.leaves(kv_block)[0].shape[2]
        dest = self.prefill_dest(slot, block_len, block_len,
                                 self._slot_shared[slot] * self.page)
        self.state = {"layers": self.staged_write_prefill(
            self.state["layers"], kv_block, jnp.asarray(dest[None],
                                                        jnp.int32))}

    # ------------------------------------------------------------ decode ----
    def set_decode_shield(self, slot: int, shielded: bool) -> None:
        """Hide/expose ``slot``'s table row in ``decode_view``.

        A mid-prefill slot owns live pages but must not take decode traffic:
        the fused dispatch scatter-writes *every* slot (masked ones at
        position 0), and position 0 of a prefilling slot maps to its real
        first page — the write would corrupt prefilled content.  Shielded
        rows read as all-scratch in the decode view, so both the masked
        write and the (already inactive-masked) read land in garbage space.
        Chunk dispatches bypass the shield via ``table_row``."""
        if shielded:
            self._shielded.add(slot)
        else:
            self._shielded.discard(slot)
        self._page_table_dev = None

    def decode_view(self):
        """Device pytree for ``lm.decode_step``: pools + the page table.

        The table is a plain (B, M) int32 input to the fused dispatch — its
        shape never changes, so admits/frees never retrace the decode; and
        its device copy is cached between mutations, so steady-state decode
        (no admits, no completions) pays no host->device transfer for it.
        Rows of shielded (mid-chunked-prefill) slots are zeroed to the
        scratch page (see ``set_decode_shield``)."""
        if self._page_table_dev is None:
            tbl = self.page_table
            if self._shielded:
                tbl = tbl.copy()
                tbl[sorted(self._shielded)] = 0
            self._page_table_dev = jnp.asarray(tbl)
        return {**self.state, "page_table": self._page_table_dev}

    def update(self, new_state) -> None:
        self.state = {"layers": new_state["layers"]}

    # -------------------------------------------------------------- free ----
    def free(self, slot: int) -> None:
        for pid in self._slot_pages[slot]:
            self._ref[pid] -= 1
            if self._ref[pid] == 0:
                key = self._page_to_hash.pop(pid, None)
                if key is not None:
                    del self._hash_to_page[key]
                    # the page is about to be recycled but its content is
                    # a registered (landed, uncorrupted-as-far-as-we-know)
                    # shared prefix: spill it to the host tier so a later
                    # hash-hitting admission prefetches instead of
                    # recomputing prefill.  Poison-recovered pages never
                    # get here — recovery unregisters them first.
                    if self.store is not None:
                        self._offload(key, pid)
                chip = self._chip_of(pid)
                # a failed chip's pages are gone, not recyclable: the last
                # reference dropping is when the page leaves the pool
                if chip not in self._failed_chips:
                    self._free_chip[chip].append(pid)
        self._slot_pages[slot] = []
        self._slot_shared[slot] = 0
        self._slot_need[slot] = 0
        self._shielded.discard(slot)
        tenant = self._slot_tenant[slot]
        if tenant is not None:
            left = self._tenant_pages[tenant] - self._slot_charge[slot]
            if left:
                self._tenant_pages[tenant] = left
            else:
                del self._tenant_pages[tenant]
            self._slot_tenant[slot] = None
            self._slot_charge[slot] = 0
        self.page_table[slot, :] = 0    # point the freed slot at scratch
        self._page_table_dev = None

    # ---------------------------------------------------- fault tolerance ----
    def poison_page(self, pid: int) -> None:
        """Overwrite physical page ``pid``'s content with non-finite values
        (simulated in-HBM corruption — the ``poison_page`` fault seam).  On
        a quantized pool the int8 payload has no NaN encoding, so the fp32
        scales are poisoned instead; dequantization drags the NaN into the
        attended values either way.  The page keeps its table/refcount
        bookkeeping untouched: detecting the corruption is the *reader's*
        job (the fused dispatch's non-finite logit guard), exactly as with
        real bit rot."""
        assert 0 < pid < self.P, (pid, self.P)
        layers = dict(self.state["layers"])
        names = ("k_scale", "v_scale") if self.quantized else ("k", "v")
        for name in names:
            layers[name] = layers[name].at[:, pid].set(jnp.nan)
        self.state = {**self.state, "layers": layers}

    def unregister_pages(self, pages: List[int]) -> None:
        """Drop the prefix-sharing keys of ``pages`` (content lost or
        suspect).  Existing sharers keep their references — they are
        detected and recovered through the same guard — but no *new*
        request can map the pages again, so a poisoned prompt page cannot
        re-share into a recompute-on-resume prefill and re-poison the
        stream forever."""
        for pid in pages:
            key = self._page_to_hash.pop(pid, None)
            if key is not None:
                del self._hash_to_page[key]

    def fail_chip(self, chip: int) -> List[int]:
        """Drain chip ``chip`` from the pool (a lost accelerator): its free
        pages leave the free lists for good — capacity degrades from P to
        P·(n-1)/n — and every slot holding a page in the chip's id range is
        returned as a victim for the engine to recover (evict + recompute-
        on-resume; streams with no pages there are untouched).  The chip's
        prefix-hash keys are dropped so no later admission can share
        content that no longer exists.  Idempotent per chip.

        Note the pool can be left banker-*unsafe* for in-flight chunked
        prefills whose remaining need exceeded the surviving capacity:
        such slots stall until the engine's watchdog recovers (or
        dead-letters) them — the one case where a stall is no longer
        guaranteed to resolve by completions alone."""
        from repro.parallel.pagedkv import chip_page_range
        assert 0 <= chip < self.chips, (chip, self.chips)
        if chip in self._failed_chips:
            return []
        self._failed_chips.add(chip)
        self._free_chip[chip] = []
        span = chip_page_range(chip, self.pages_per_chip)
        self.unregister_pages([p for p in span if p in self._page_to_hash])
        return [s for s in range(self.B)
                if any(span.start <= p < span.stop
                       for p in self._slot_pages[s])]

    def verify(self) -> None:
        """Invariant sanitizer over the allocator's host-side bookkeeping
        (the debug-mode health check behind ``ServeEngine(verify_cache=)``
        and the property-test fuzzers).  O(P + B·M) numpy, no device sync.
        Raises :class:`CacheInvariantError` naming the first violated
        invariant: refcounts == live references, scratch page never handed
        out, free/owned pages partition the (surviving) pool, per-chip
        free-list membership, page-table rows mirroring ``_slot_pages``,
        prefix-registry bijection, per-tenant quota accounting, and the
        ``memory_stats`` byte math."""
        from repro.parallel.pagedkv import chip_page_range

        def check(cond, what):
            if not cond:
                raise CacheInvariantError(f"PagedCache.verify: {what}")

        owned = [pid for pages in self._slot_pages for pid in pages]
        free = [pid for chip in self._free_chip for pid in chip]
        check(0 not in owned and 0 not in free and self._ref[0] == 0,
              "scratch page 0 handed out, listed free, or refcounted")
        counts = (np.bincount(owned, minlength=self.P) if owned
                  else np.zeros(self.P, np.int64))
        check((self._ref == counts).all(),
              f"refcounts drifted from live references "
              f"(ref={self._ref.tolist()} vs owned={sorted(owned)})")
        check(len(free) == len(set(free)), "duplicate page in free lists")
        check(set(free).isdisjoint(owned),
              f"pages both free and owned: {set(free) & set(owned)}")
        lost = {p for c in self._failed_chips
                for p in chip_page_range(c, self.pages_per_chip)}
        check(not lost & set(free), "failed-chip page still listed free")
        check(not lost & set(owned), "failed-chip page still owned")
        check(set(free) | set(owned) <= set(range(1, self.P)) - lost,
              "page id outside the surviving pool")
        for c, chip in enumerate(self._free_chip):
            check(all(self._chip_of(pid) == c for pid in chip),
                  f"page filed under wrong chip's free list ({c})")
        for s in range(self.B):
            pages = self._slot_pages[s]
            row = self.page_table[s]
            check(list(row[:len(pages)]) == pages,
                  f"slot {s} page-table row != owned pages")
            check((row[len(pages):] == 0).all(),
                  f"slot {s} page-table tail not parked on scratch")
            check(0 <= self._slot_shared[s] <= len(pages),
                  f"slot {s} shared-page count out of range")
            check(self._slot_need[s] >= 0,
                  f"slot {s} negative chunked-prefill need")
            check((self._slot_tenant[s] is None) ==
                  (self._slot_charge[s] == 0),
                  f"slot {s} tenant/charge mismatch")
        check(len(self._hash_to_page) == len(self._page_to_hash),
              "prefix registry maps differ in size")
        for key, pid in self._hash_to_page.items():
            check(self._page_to_hash.get(pid) == key,
                  f"prefix registry maps disagree on page {pid}")
            check(self._ref[pid] > 0,
                  f"registered prefix page {pid} has no owner")
        charges: Dict[str, int] = {}
        for s in range(self.B):
            t = self._slot_tenant[s]
            if t is not None:
                charges[t] = charges.get(t, 0) + self._slot_charge[s]
        check(charges == self._tenant_pages,
              f"tenant accounting drifted: {charges} "
              f"vs {self._tenant_pages}")
        st = self.memory_stats()
        pb = page_kv_bytes(self.cfg, self.page, self.dtype, self.kv_dtype)
        check(st.pages_total == self.usable_pages(),
              "memory_stats pages_total != usable pool")
        check(st.pages_in_use == st.pages_total - len(free),
              "memory_stats pages_in_use != usable - free")
        check(st.bytes_reserved == st.pages_in_use * pb
              and st.bytes_total == self.P * pb,
              "memory_stats byte math inconsistent")
        if not self._failed_chips:
            # grants maintain banker safety — but a chip failure may
            # legitimately strand an in-flight chunked need (the watchdog's
            # recovery case), so the check only applies to intact pools
            check(self._safe(len(free), self._banker_items()),
                  "pool not banker-safe (a live slot can never complete)")
        if self.store is not None:
            # host-resident pages: drain in-flight offloads so the store's
            # own sanitizer sees the settled state, then cross-check the
            # stats plumbing (store bytes must be wire-format page bytes)
            self.drain_offloads()
            self.store.verify()
            check(st.host_pages_total == self.store.capacity
                  and st.host_pages_in_use == self.store.pages_in_use()
                  and st.host_bytes == self.store.bytes_in_use(),
                  "memory_stats host-tier accounting drifted from store")
            check(self.store.tier.page_bytes == pb,
                  "host tier page bytes != device wire page bytes")

    # ------------------------------------------------------------- stats ----
    def memory_stats(self) -> MemoryStats:
        # self.chips covers BOTH partition modes — a device mesh and the
        # mesh-free locality_chips harness — so the --mesh and fault-drain
        # memory lines report the real per-chip split either way (the old
        # `chips if mesh else 1` reported a locality-partitioned pool as
        # one unsharded chip).  page_kv_bytes includes the int8 scale
        # bytes, so bytes_per_chip counts each chip's sharded scale arrays
        # too; bytes_scales_per_chip breaks that portion out.
        self.drain_offloads()    # settle in-flight D2H so host stats are real
        pb = page_kv_bytes(self.cfg, self.page, self.dtype, self.kv_dtype)
        usable = self.usable_pages()
        in_use = usable - self._free_count()
        sharded = self.chips
        scale_b = (self.P * self.page * 2 * self.cfg.num_layers
                   * self.cfg.num_kv_heads * SCALE_BYTES
                   if self.quantized else 0)
        return MemoryStats(
            backend=self.backend, bytes_total=self.P * pb,
            bytes_reserved=in_use * pb, slots_total=self.B,
            slots_in_use=sum(bool(p) for p in self._slot_pages),
            page_size=self.page, pages_total=usable, pages_in_use=in_use,
            pages_shared=int((self._ref > 1).sum()),
            mesh_chips=sharded, bytes_per_chip=self.P * pb // sharded,
            kv_dtype=self.kv_dtype, bytes_scales=scale_b,
            bytes_scales_per_chip=scale_b // sharded,
            chips_failed=len(self._failed_chips),
            tenant_pages=dict(self._tenant_pages),
            host_pages_total=self.store.capacity if self.store else 0,
            host_pages_in_use=(self.store.pages_in_use()
                               if self.store else 0),
            host_bytes=self.store.bytes_in_use() if self.store else 0)


# ------------------------------------------------------------- factory ----

def make_cache(lm, batch: int, max_seq: int, dtype=jnp.bfloat16,
               backend: str = "contiguous", page_size: int = 16,
               num_pages: Optional[int] = None, prefix_sharing: bool = True,
               decode_impl: str = "gather", mesh=None,
               kv_axis: str = "model", dp_axis=None,
               kv_dtype: str = "native",
               locality_chips: Optional[int] = None,
               host_pages: int = 0, prefix_store=None):
    """Build a KV-cache backend for ``lm`` (the ``lm.init_cache(backend=...)``
    entry point).  ``decode_impl`` ("gather" / "pallas") rides on the paged
    backend and tells decode consumers how to resolve the page table; the
    contiguous backend has no table and always reports "gather".  ``mesh``
    shards the paged pool P/n over ``kv_axis`` (``kv_pages`` logical axis)
    with a locality-aware free list.  ``kv_dtype="int8"`` (paged only)
    stores pages quantized with per-row fp32 scales — quantize-on-write,
    dequantize-on-read in both decode impls.  ``locality_chips`` (paged,
    mesh-free) partitions the free list as an N-chip pool without device
    sharding — the host-side harness for per-chip locality and
    chip-failure drain tests.  ``host_pages`` (paged, needs prefix
    sharing) adds an N-page host-RAM tier: cold shared prefixes spill to
    pinned host buffers on their last free and prefetch back on a later
    hash-hit instead of recomputing prefill; ``prefix_store`` passes an
    externally-owned ``repro.serve.offload.PrefixStore`` so the warm
    prefix corpus persists across engine instances."""
    if backend == "contiguous":
        if locality_chips is not None:
            raise ValueError(
                "locality_chips partitions the paged backend's free list; "
                "the contiguous layout has no pages (use backend='paged')")
        if decode_impl != "gather":
            raise ValueError(
                "decode_impl applies to the paged backend's page-table "
                f"resolution; the contiguous layout has no table to walk "
                f"(got decode_impl={decode_impl!r})")
        if mesh is not None:
            raise ValueError(
                "kv_pages sharding partitions the paged pool's page dim; "
                "the contiguous layout has no page dim (use backend='paged' "
                "to serve over a mesh)")
        if kv_dtype != "native":
            raise ValueError(
                "the int8 page format quantizes fixed-size pages with "
                "per-row scales; the contiguous layout has no pages (use "
                f"backend='paged' for kv_dtype={kv_dtype!r})")
        if host_pages or prefix_store is not None:
            raise ValueError(
                "the host page tier offloads and prefetches fixed-size "
                "pages under the prefix-sharing key; the contiguous "
                "layout has neither (use backend='paged' for host_pages/"
                "prefix_store)")
        return ContiguousCache(lm, batch, max_seq, dtype=dtype)
    if backend == "paged":
        if lm.is_encdec:
            raise NotImplementedError(
                "paged KV covers decoder self-attention caches; encdec "
                "cross-attention K/V is per-request dense state")
        return PagedCache(lm, batch, max_seq, dtype=dtype,
                          page_size=page_size, num_pages=num_pages,
                          prefix_sharing=prefix_sharing,
                          decode_impl=decode_impl, mesh=mesh,
                          kv_axis=kv_axis, dp_axis=dp_axis,
                          kv_dtype=kv_dtype,
                          locality_chips=locality_chips,
                          host_pages=host_pages, prefix_store=prefix_store)
    raise ValueError(f"unknown KV-cache backend {backend!r}")
